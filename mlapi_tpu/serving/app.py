"""The serving application: the reference's API surface, TPU-backed.

Routes preserve the reference's observable contract:

- ``POST /predict``  (``main.py:16-27``): JSON body validated against
  the model's feature schema (422 on failure, FastAPI-shaped), reply
  ``{"prediction": "<label>", "probability": <max prob>}``.
- ``POST /files/``   (``main.py:29-38``): multipart CSV + ``token``
  form field. The reference echoed a raw DataFrame, which is not
  reliably JSON-encodable (its own author left a commented-out
  ``#return df`` at ``main.py:35``); per SURVEY §3.3 we keep the
  capability and fix the contract: a JSON echo of columns/rows/records
  plus the token.

Plus what the reference lacked (SURVEY §5): ``GET /healthz``,
``GET /metrics``, request counters and latency histograms.

(No ``from __future__ import annotations`` here: the ``/predict``
handler's schema annotation is a dynamically-built pydantic model that
must survive as a real class for routing-time body-model detection.)
"""

import asyncio
import io
import json
import time

import numpy as np
import pydantic

from mlapi_tpu.serving.asgi import (
    App,
    HTTPError,
    Request,
    Response,
    StreamingResponse,
    json_response,
)
from mlapi_tpu.serving import faults
from mlapi_tpu.serving.scoring import OverloadedError, ScorePath
from mlapi_tpu.serving.engine import InferenceEngine
from mlapi_tpu.serving.requests import DeadlineExceeded, DrainCancelled
from mlapi_tpu.utils.logging import get_logger
from mlapi_tpu.utils.metrics import MetricsRegistry

_log = get_logger("serving.app")

MAX_ECHO_RECORDS = 1000


def _validate_deadline_ms(value) -> None:
    """Shared /predict + /generate schema check: 0 would silently
    mean "no deadline" and a negative one would burn a queue slot
    just to 504 on the first batch."""
    if value is not None and value <= 0:
        raise HTTPError(
            422,
            [
                {
                    "type": "value_error",
                    "loc": ["deadline_ms"],
                    "msg": "must be > 0 (omit for no deadline)",
                    "input": value,
                }
            ],
        )


def _is_router_replica() -> bool:
    """Is this server a router replica (the only deployment where a
    trusted party stamps ``x-mlapi-router-depth``)? Spawned replicas
    carry ``MLAPI_TPU_REPLICA=1``; externally-launched fleets export
    ``MLAPI_TPU_REPLICAS`` (the same discovery convention the router
    reads). A non-replica server IGNORES the header outright — an
    arbitrary client must not be able to inject fleet pressure into
    admission control / the brownout ladder. (The router additionally
    strips client-sent copies on its forward path, so within a fleet
    only the router's own value ever arrives.)"""
    import os

    return os.environ.get("MLAPI_TPU_REPLICA") == "1" or bool(
        os.environ.get("MLAPI_TPU_REPLICAS")
    )


def _router_depth(request) -> int:
    """The fleet-backlog gauge a fronting router stamps on forwarded
    requests (``x-mlapi-router-depth``; 0 for direct traffic — a
    stale fleet spike must not keep shedding after the router is
    gone). Scans the raw ASGI header list for the one key instead of
    decoding the full header dict — ``/predict``'s hot path
    deliberately never pays the lazy full-header decode."""
    for k, v in request.scope.get("headers", []):
        if k == b"x-mlapi-router-depth":
            try:
                return max(0, int(v))
            except (TypeError, ValueError):
                return 0
    return 0


def _warm_peer(request) -> str | None:
    """The warm-peer hint a fronting router stamps on any forward
    that misses the request's HRW-preferred replica
    (``x-mlapi-warm-peer: host:port`` — who is likely warm for this
    prefix). Same raw-scope scan and same trust model as
    ``_router_depth``: read only on router replicas, and the router
    strips client-sent copies, so an arbitrary caller can never aim
    this replica's KV fetches at a host of their choosing."""
    return _scan_header(request, b"x-mlapi-warm-peer")


def _scan_header(request, key: bytes) -> str | None:
    """Raw ASGI header-list scan for one router-authored key (same
    no-full-decode discipline as ``_router_depth``)."""
    for k, v in request.scope.get("headers", []):
        if k == key:
            try:
                return v.decode("latin-1").strip() or None
            except Exception:
                return None
    return None


def _decode_peer(request) -> str | None:
    """The decode replica a fronting router named for a disaggregated
    forward (``x-mlapi-decode-peer: host:port``, r18) — stamped only
    on forwards to PREFILL-role replicas. Router-authored and
    replica-gated like ``x-mlapi-warm-peer``: the router strips
    client-sent copies, and a non-replica server never reads it, so
    an arbitrary caller can never aim a replica's KV pushes at a host
    of their choosing."""
    return _scan_header(request, b"x-mlapi-decode-peer")


def _kv_xfer(request) -> str | None:
    """The transfer id of a disaggregated request
    (``x-mlapi-kv-xfer``, r18): on a prefill replica it names the
    push stream to open; on a decode replica it names the staged
    transfer whose KV replaces this request's prefill. Same trust
    model as ``_decode_peer``."""
    return _scan_header(request, b"x-mlapi-kv-xfer")


def _overloaded_http(e: OverloadedError) -> HTTPError:
    """Overload → immediate 503 with a Retry-After hint. Shedding at
    the door keeps latency bounded for the requests that ARE admitted;
    clients with backoff recover on their own."""
    return HTTPError(
        503,
        str(e),
        headers={"retry-after": str(int(max(1, e.retry_after_s)))},
    )


def _terminal_http(e: Exception) -> HTTPError | None:
    """Map an in-band terminal error frame to its HTTP shape on the
    UNARY paths (streams carry the same information as their last
    NDJSON frame): deadline expiry → 504, drain-cancel and pool
    exhaustion → 503 (retry against a live/looser replica). Anything
    else stays a 500 via the generic handler."""
    if isinstance(e, DeadlineExceeded):
        return HTTPError(504, str(e))
    if isinstance(e, DrainCancelled):
        return HTTPError(503, str(e), headers={"retry-after": "5"})
    from mlapi_tpu.serving.paged_pool import PagePoolExhausted

    if isinstance(e, PagePoolExhausted):
        return HTTPError(503, str(e), headers={"retry-after": "1"})
    from mlapi_tpu.serving.adapter_store import (
        AdapterSlotsExhausted, AdapterUnavailable,
    )

    if isinstance(e, AdapterUnavailable):
        # The named adapter does not exist anywhere this replica can
        # reach — the resource is absent, not the server unhealthy.
        return HTTPError(404, str(e))
    if isinstance(e, AdapterSlotsExhausted):
        # Momentary: every slot pinned by live batches. Retryable.
        return HTTPError(503, str(e), headers={"retry-after": "1"})
    return None


def feature_schema(feature_names) -> type[pydantic.BaseModel]:
    """Build the request schema from the model's feature names — for
    Iris this reproduces the reference's ``IrisSpecies``
    (``main.py:10-14``): four required floats, numeric strings
    coerced. Models without named features (e.g. 784-pixel MNIST)
    take ``{"features": [..784 floats..]}`` instead. Every variant
    carries the optional ``deadline_ms`` wall-clock budget (r12)."""
    if feature_names:
        return pydantic.create_model(
            "Features",
            **{name: (float, ...) for name in feature_names},
            deadline_ms=(float | None, None),
        )
    return pydantic.create_model(
        "Features", features=(list[float], ...),
        deadline_ms=(float | None, None),
    )


def build_app(
    engine: InferenceEngine | None = None,
    *,
    max_batch: int | None = None,
    max_wait_ms: float = 0.2,
    max_queue: int | None = None,
    registry: MetricsRegistry | None = None,
    default_deadline_ms: float | None = None,
    drain_timeout_s: float = 10.0,
    admission_control: bool = True,
    models=None,
    tenants=None,
) -> App:
    """One app over one model or a whole registry.

    ``models`` (a :class:`~mlapi_tpu.serving.registry.ModelRegistry`)
    is the r22 multi-model surface: every entry serves at
    ``/models/<id>/{predict|generate}`` and the DEFAULT entry also
    owns the legacy ``/predict`` / ``/generate`` routes — a
    single-model process is just a one-entry registry, bit for bit.
    ``tenants`` (a :class:`~mlapi_tpu.serving.registry.TenantLedger`)
    attaches per-tenant quotas/weights/brownout to every generative
    entry."""
    from mlapi_tpu.serving.registry import ModelRegistry

    if models is None:
        if engine is None:
            raise ValueError("build_app needs an engine or a registry")
        models = ModelRegistry({"default": engine})
    engine = models.default
    app = App(title="mlapi-tpu")
    registry = registry or MetricsRegistry()
    app.state["engine"] = engine
    app.state["models"] = models
    app.state["tenants"] = tenants
    app.state["metrics"] = registry
    app.state["drain_timeout_s"] = float(drain_timeout_s)

    multi = len(models.ids()) > 1
    primary_gen = models.primary_generative()
    score_paths: dict[str, ScorePath] = {}
    batcher = None
    for mid, eng in models.items():
        is_default = mid == models.default_id
        if eng.kind == "generative":
            if tenants is not None:
                eng.tenants = tenants
            if is_default:
                # The generative engine owns its queue/batch limits;
                # the app-level knobs apply to it too (engine
                # defaults when None). Non-default entries keep their
                # construction-time limits.
                if max_queue is not None:
                    eng.max_queue = max_queue
                if max_batch is not None:
                    eng.max_batch = min(max_batch, eng.max_batch)
                eng.default_deadline_ms = default_deadline_ms
                eng.admission_control = bool(admission_control)
                eng.drain_timeout_s = float(drain_timeout_s)
                _install_generate(app, eng)
                if getattr(eng, "kv_peer", None) is not None and (
                    _is_router_replica()
                ):
                    # Replica-gated like the hint header itself:
                    # outside a router fleet there is no trusted
                    # hinter, and the endpoint would only be a
                    # cache-presence oracle handing raw KV bytes to
                    # arbitrary direct callers.
                    _install_kv_peer(app, eng)
                if getattr(eng, "adapter_peer", None) is not None and (
                    _is_router_replica()
                ):
                    # Same trust model as /kv/prefix: adapter weight
                    # blobs serve replica↔replica only, inside a
                    # router fleet.
                    _install_adapter_peer(app, eng)
                if (
                    getattr(eng, "kv_push", None) is not None
                    and getattr(eng, "replica_role", "mixed") == "decode"
                    and _is_router_replica()
                ):
                    # The push intake exists ONLY on decode-role
                    # replicas inside a fleet (r18): a mixed topology
                    # exposes no push endpoint at all — bit-identical
                    # to r17 — and outside a fleet there is no
                    # trusted pusher.
                    _install_kv_push(app, eng)
            if multi:
                _install_generate(
                    app, eng, path=f"/models/{mid}/generate"
                )
        else:
            # The scoring fast path: formed batches ride the primary
            # generative engine's unit queue when one is co-resident
            # (typed score units between decode chunks), the folded
            # worker-pool backend otherwise.
            sp = ScorePath(
                eng, model_id=mid, max_batch=max_batch,
                max_wait_ms=max_wait_ms,
                default_deadline_ms=default_deadline_ms,
                sched_source=(
                    (lambda g=primary_gen: g.sched)
                    if primary_gen is not None else None
                ),
                **({"max_queue": max_queue}
                   if max_queue is not None else {}),
            )
            score_paths[mid] = sp
            if is_default:
                batcher = sp
                app.state["batcher"] = sp
                _install_predict(app, eng, sp)
            if multi:
                _install_predict(
                    app, eng, sp, path=f"/models/{mid}/predict"
                )
    app.state["score_paths"] = score_paths

    @app.on_startup
    async def _start():
        # Fault-injection points arm from $MLAPI_FAULTS (chaos drills
        # against a real server); a no-op — zero per-seam overhead —
        # when unset.
        faults.arm_from_env()
        loop = asyncio.get_running_loop()
        # Warm the compiled shapes off the request path, then start
        # the collectors. No request ever sees an XLA compile.
        # Generative engines start BEFORE the scoring paths so a
        # scoring batch formed at t=0 already finds the unit queue.
        for mid, eng in models.items():
            await loop.run_in_executor(None, eng.warmup)
            if eng.kind == "generative":
                await eng.start()
            models.note_started(mid)
        for sp in score_paths.values():
            await sp.start()
        _log.info(
            "serving %s (%s)",
            ", ".join(
                f"{mid}:{type(e.model).__name__}"
                for mid, e in models.items()
            ),
            "+".join(sorted({e.kind for _, e in models.items()})),
        )

    @app.on_shutdown
    async def _stop():
        # Graceful drain first (new admissions shed 503 + retry-after
        # and /healthz flips to "draining" the moment this hook runs;
        # in-flight streams get the budget to finish, then proper
        # terminal frames), THEN the hard stop. Scoring paths drain
        # and stop BEFORE the generative engines whose unit queue
        # their in-flight batches may ride.
        budget = app.state["drain_timeout_s"]
        for sp in score_paths.values():
            await sp.drain(budget)
            await sp.stop()
        for mid, eng in models.items():
            if eng.kind == "generative" and hasattr(eng, "stop"):
                if hasattr(eng, "drain"):
                    await eng.drain(budget)
                await eng.stop()
            models.note_stopped(mid)

    _install_common(app, engine, registry, batcher)
    app.install_docs()  # /openapi.json + /docs, like FastAPI gave free
    return app


def _install_predict(app: App, engine: InferenceEngine, batcher,
                     path: str = "/predict") -> None:
    """The classification surface: ``POST /predict`` — and, in a
    multi-model process, the same handler at
    ``POST /models/<id>/predict`` (the registry's ids are static at
    build time, so per-model routes register as exact paths)."""
    if engine.kind == "text":
        schema = pydantic.create_model(
            "TextRequest", text=(str, ...),
            deadline_ms=(float | None, None),
        )
    else:
        schema = feature_schema(engine.feature_names)
    order = engine.feature_names
    expected_dim = engine.num_features
    # Pre-escaped JSON bytes per class label (labels are fixed at
    # checkpoint load; escaping them per request would be waste).
    label_json = {
        label: json.dumps(label).encode() for label in engine.vocab.labels
    }

    is_replica = _is_router_replica()

    @app.post(path)
    async def predict(features: schema, request):  # type: ignore[valid-type]
        if is_replica:
            batcher.router_queue_depth = _router_depth(request)
        if engine.kind == "text":
            row = engine.encode(features.text)
        elif order:
            row = np.asarray([getattr(features, f) for f in order], np.float32)
        else:
            row = np.asarray(features.features, np.float32)
        if row.shape != (expected_dim,):
            # Same FastAPI-shaped detail list as pydantic 422s, so
            # clients parse every validation failure one way.
            raise HTTPError(
                422,
                [
                    {
                        "type": "value_error",
                        "loc": ["features"],
                        "msg": f"expected {expected_dim} features, "
                               f"got {row.shape[0]}",
                        "input": int(row.shape[0]),
                    }
                ],
            )
        _validate_deadline_ms(features.deadline_ms)
        try:
            label, prob = await batcher.submit(
                row, deadline_ms=features.deadline_ms
            )
        except OverloadedError as e:
            raise _overloaded_http(e) from None
        except DeadlineExceeded as e:
            raise HTTPError(504, str(e)) from None
        # Hot path: hand-assembled JSON from the per-label pre-escaped
        # bytes — skips json.dumps (with its default-fn machinery) on
        # every request. %.10g is plenty for a softmax probability.
        body = b'{"prediction":%b,"probability":%.10g}' % (
            label_json.get(label) or json.dumps(label).encode(),
            prob,
        )
        return Response(body, content_type="application/json")


def _install_generate(app: App, engine, path: str = "/generate") -> None:
    """The generative surface: ``POST /generate`` — and, in a
    multi-model process, the same handler at
    ``POST /models/<id>/generate``.

    Concurrent requests coalesce into one batched decode stream
    (``TextGenerationEngine``); ``"stream": true`` returns NDJSON —
    one ``{"token_ids": [...]}`` line per decoded chunk as it lands,
    then a ``{"done": true, "text": ..., ...}`` line."""
    from mlapi_tpu.serving.adapter_store import AdapterUnavailable

    schema = pydantic.create_model(
        "GenerateRequest",
        text=(str, ...),
        max_new_tokens=(int | None, None),
        temperature=(float, 0.0),
        top_k=(int, 0),
        top_p=(float, 1.0),
        seed=(int, 0),
        stream=(bool, False),
        stop=(str | list[str] | None, None),
        # End-to-end wall-clock budget (ms, measured from submit):
        # expiry at any dispatch boundary ends the stream with a
        # deadline_exceeded terminal frame / 504; infeasible budgets
        # shed 503 at the door (server default when omitted).
        deadline_ms=(float | None, None),
        # Shared-prefix KV caching: the effective prompt is
        # prefix + text, but the prefix's forward pass is computed
        # once and its KV reused by every request that names it.
        prefix=(str | None, None),
        # Per-tenant LoRA adapter id (serving/adapter_store.py): the
        # request decodes under base + this adapter's delta, batched
        # with other tenants over the one HBM-resident base.
        adapter=(str | None, None),
        # Quota/fairness identity (serving/registry.py, r22): the
        # tenant whose page/slot quota the request reserves against
        # and whose weight scales its deadline slack. Defaults to the
        # adapter id, then the anonymous tenant.
        tenant=(str | None, None),
    )
    hard_cap = engine.model.max_positions - 1

    def _norm_stops(stop) -> list[str]:
        stops = [stop] if isinstance(stop, str) else list(stop or [])
        if len(stops) > 4 or any(not 0 < len(s) <= 64 for s in stops):
            raise HTTPError(
                422,
                [
                    {
                        "type": "value_error",
                        "loc": ["stop"],
                        "msg": "up to 4 stop strings of 1-64 chars",
                        "input": stop,
                    }
                ],
            )
        return stops

    def _first_stop(text: str, stops: list[str]):
        """(cut_index, stop) of the earliest stop occurrence, or
        ``None``. Generation halts at the FIRST match; same-index ties
        go to the LONGEST stop (deterministic, not lexicographic)."""
        hits = [(i, s) for s in stops if (i := text.find(s)) != -1]
        return min(hits, key=lambda h: (h[0], -len(h[1])), default=None)

    is_replica = _is_router_replica()

    @app.post(path)
    async def generate(req: schema, request):  # type: ignore[valid-type]
        # Router backpressure (r15): the gauge feeds the admission
        # estimate and brownout ladder — replica deployments only
        # (the header is untrusted from arbitrary direct callers).
        if is_replica:
            engine.router_queue_depth = _router_depth(request)
            # Warm-peer hint (r17): noted BEFORE submit so the encode
            # thread's prefix miss can fetch the blob from the peer
            # the router named instead of cold-prefilling.
            if engine.kv_peer is not None and req.prefix:
                wp = _warm_peer(request)
                if wp:
                    engine.kv_peer.note_hint(req.prefix, wp)
            # Same hint, adapter tier: this forward missed the
            # tenant's HRW-preferred replica, so a cold adapter
            # fetches from the peer the router named (where the
            # tenant's prefixes — and so its adapter — stay warm)
            # instead of 404ing at the local store.
            if engine.adapter_peer is not None and req.adapter:
                wp = _warm_peer(request)
                if wp:
                    engine.adapter_peer.note_hint(req.adapter, wp)
        n_new = (
            req.max_new_tokens
            if req.max_new_tokens is not None
            else engine.default_max_new_tokens
        )
        if not 0 < n_new <= hard_cap:
            raise HTTPError(
                422,
                [
                    {
                        "type": "value_error",
                        "loc": ["max_new_tokens"],
                        "msg": f"must be in [1, {hard_cap}]",
                        "input": n_new,
                    }
                ],
            )
        if not 0.0 <= req.temperature <= 10.0:
            raise HTTPError(
                422,
                [
                    {
                        "type": "value_error",
                        "loc": ["temperature"],
                        "msg": "must be in [0, 10]",
                        "input": req.temperature,
                    }
                ],
            )
        if not 0 <= req.top_k <= engine.model.vocab_size:
            # Upper bound matters: an int32-overflowing value would
            # otherwise blow up inside the coalesced batch and fail
            # innocent co-batched requests.
            raise HTTPError(
                422,
                [
                    {
                        "type": "value_error",
                        "loc": ["top_k"],
                        "msg": f"must be in [0, {engine.model.vocab_size}] "
                               "(0 disables)",
                        "input": req.top_k,
                    }
                ],
            )
        if not 0.0 < req.top_p <= 1.0:
            raise HTTPError(
                422,
                [
                    {
                        "type": "value_error",
                        "loc": ["top_p"],
                        "msg": "must be in (0, 1] (1.0 disables)",
                        "input": req.top_p,
                    }
                ],
            )
        _validate_deadline_ms(req.deadline_ms)
        stops = _norm_stops(req.stop)
        push_to = None
        kv_xfer = None
        if is_replica and getattr(engine, "kv_push", None) is not None:
            xfer = _kv_xfer(request)
            peer = _decode_peer(request)
            if (
                xfer
                and peer
                and engine.replica_role == "prefill"
                and not req.prefix
            ):
                # Disaggregated PREFILL leg (r18): run the prompt as
                # a prefill-only batch whose chunk KV streams to the
                # named decode replica; answer the router with the
                # handoff verdict — it forwards the client's request
                # to the decode replica next (with the transfer id
                # only if every chunk landed).
                host, _, port = peer.rpartition(":")
                if host and port.isdigit():
                    push_to = (host, int(port), xfer)
            elif xfer and engine.replica_role == "decode":
                # Disaggregated DECODE leg: the staged transfer's KV
                # replaces this request's prefill at formation.
                kv_xfer = xfer
        if push_to is not None:
            try:
                gen = await engine.submit(
                    req.text,
                    max_new_tokens=n_new,
                    temperature=req.temperature,
                    seed=req.seed,
                    top_k=req.top_k,
                    top_p=req.top_p,
                    deadline_ms=req.deadline_ms,
                    push_to=push_to,
                )
            except OverloadedError as e:
                raise _overloaded_http(e) from None
            first_token = None
            while True:
                item = await gen.queue.get()
                if isinstance(item, Exception):
                    http = _terminal_http(item)
                    if http is not None:
                        raise http from None
                    raise item
                if item is None:
                    break
                ids = item.get("token_ids") or []
                if ids and first_token is None:
                    first_token = int(ids[0])
            # The fin rides the FIFO sender queue behind every chunk,
            # so a True here means the decode replica has the whole
            # transfer; waited off the event loop.
            complete = await asyncio.get_running_loop().run_in_executor(
                None, engine.kv_push.wait_sent, push_to[2]
            )
            return {
                "handoff": True,
                "xfer": push_to[2],
                "complete": bool(complete and first_token is not None),
                "first_token": first_token,
                "prompt_tokens": gen.prompt_tokens,
            }
        try:
            gen = await engine.submit(
                req.text,
                max_new_tokens=n_new,
                temperature=req.temperature,
                seed=req.seed,
                top_k=req.top_k,
                top_p=req.top_p,
                prefix=req.prefix,
                # Incremental consumers (NDJSON streams, stop-sequence
                # watchers that cancel early) need tokens per chunk;
                # plain requests let the decode loop chain dispatches
                # and sync once.
                stream=bool(req.stream) or bool(stops),
                deadline_ms=req.deadline_ms,
                kv_xfer=kv_xfer,
                adapter=req.adapter,
                tenant=req.tenant,
            )
        except OverloadedError as e:
            raise _overloaded_http(e) from None
        except AdapterUnavailable as e:
            # Raised on the submit path (the encode thread resolves
            # the id before the request queues): the named adapter is
            # absent everywhere this replica can reach — 404, the
            # resource, not the server.
            raise HTTPError(404, str(e)) from None
        except ValueError as e:
            # An invalid prefix (too long for the model window, empty
            # after tokenization) is the requester's error, not a 500.
            raise HTTPError(
                422,
                [
                    {
                        "type": "value_error",
                        "loc": ["prefix"],
                        "msg": str(e),
                        "input": req.prefix,
                    }
                ],
            ) from None

        if req.stream:
            async def ndjson():
                ids: list[int] = []
                finished = False
                try:
                    while True:
                        item = await gen.queue.get()
                        if isinstance(item, Exception):
                            # The stream's TERMINAL ERROR FRAME:
                            # machine-readable ``code`` for the errors
                            # clients route on (deadline_exceeded,
                            # draining) — the status line is long gone,
                            # so the frame IS the status.
                            finished = True
                            frame = {"error": str(item)}
                            code = getattr(item, "code", None)
                            if code:
                                frame["code"] = code
                            yield json.dumps(frame).encode() + b"\n"
                            return
                        if item is None:
                            finished = True
                            yield json.dumps(
                                {
                                    "done": True,
                                    "text": engine.tokenizer.decode(ids),
                                    "token_ids": ids,
                                    "prompt_tokens": gen.prompt_tokens,
                                }
                            ).encode() + b"\n"
                            return
                        ids.extend(item["token_ids"])
                        if stops:
                            # One decode per chunk, reused for the
                            # match and the done frame (decoding the
                            # full prefix each chunk is already
                            # O(n^2)-ish; don't triple it).
                            text = engine.tokenizer.decode(ids)
                            hit = _first_stop(text, stops)
                            if hit is not None:
                                # Stop matched: end the stream with the
                                # truncated authoritative text and free
                                # the decode row (cancel → the batch
                                # compacts it away). Chunks already
                                # streamed may extend past the stop at
                                # chunk granularity; the done frame is
                                # the source of truth.
                                finished = True
                                gen.cancel()
                                cut, s = hit
                                yield json.dumps(
                                    {
                                        "done": True,
                                        "text": text[:cut],
                                        "token_ids": ids,
                                        "prompt_tokens": gen.prompt_tokens,
                                        "stopped": s,
                                    }
                                ).encode() + b"\n"
                                return
                        yield json.dumps(item).encode() + b"\n"
                finally:
                    # Generator closed early (client disconnect →
                    # server acloses the body iterator): stop the
                    # decode loop spending device time on this row.
                    if not finished:
                        gen.cancel()

            return StreamingResponse(
                ndjson(), content_type="application/x-ndjson"
            )

        ids: list[int] = []
        stopped = None
        text = None
        try:
            while True:
                item = await gen.queue.get()
                if isinstance(item, Exception):
                    http = _terminal_http(item)
                    if http is not None:
                        raise http from None
                    raise item
                if item is None:
                    break
                ids.extend(item["token_ids"])
                if stops:
                    text = engine.tokenizer.decode(ids)
                    hit = _first_stop(text, stops)
                    if hit is not None:
                        gen.cancel()  # free the decode row early
                        stopped = hit
                        break
                    # text stays valid: every path that exits the loop
                    # does so before ids grows past this decode.
        except asyncio.CancelledError:
            gen.cancel()  # non-stream handler torn down mid-decode
            raise
        if text is None:
            text = engine.tokenizer.decode(ids)
        out = {
            "text": text if stopped is None else text[: stopped[0]],
            "token_ids": ids,
            "prompt_tokens": gen.prompt_tokens,
        }
        if stopped is not None:
            out["stopped"] = stopped[1]
        return out


def _install_kv_peer(app: App, engine) -> None:
    """The internal replica↔replica KV endpoint (``--kv-peer-fetch``):
    ``GET /kv/prefix?fp=<digest>`` serves this replica's blob for a
    prefix fingerprint — stored-format bytes straight off the host
    tier (or gathered from the device-resident entry's contiguous
    KV), geometry header included (``serving/kv_peer.py`` wire
    format). Deliberately a GET with no engine-submit gate: it keeps
    answering while DRAINING, which is exactly the window a peer
    needs the drained replica's slice. The resolve + serialize run on
    an executor thread — the entry-KV gather is a device_get and must
    not freeze the event loop."""
    peer = engine.kv_peer

    @app.get("/kv/prefix")
    async def kv_prefix(request: Request):
        from urllib.parse import parse_qs

        qs = parse_qs(
            (request.scope.get("query_string") or b"").decode("latin-1")
        )
        digest = (qs.get("fp") or [""])[0]
        if not digest:
            raise HTTPError(422, "missing fp=<fingerprint digest>")
        data = await asyncio.get_running_loop().run_in_executor(
            None, peer.serve_wire, digest
        )
        if data is None:
            raise HTTPError(404, "no warm KV for that fingerprint")
        return Response(data, content_type="application/octet-stream")


def _install_adapter_peer(app: App, engine) -> None:
    """The internal replica↔replica adapter endpoint:
    ``GET /adapter/<id>`` serves this replica's HOST-STORE copy of a
    tenant's LoRA adapter in the wire format (geometry header +
    raw leaves — ``serving/adapter_store.py``). Same shape as
    ``GET /kv/prefix``: a GET with no engine-submit gate (a draining
    replica keeps answering — exactly the window a peer needs its
    tenants' adapters), resolve + serialize on an executor thread,
    404 when the store has no such id. A middleware, not a route —
    the router's exact (method, path) table has no path params, and
    the id lives in the path (``kv_peer._http_get``-framed peers
    request it that way)."""
    peer = engine.adapter_peer

    @app.middleware
    async def _adapter_blob(request: Request, nxt):
        if request.method == "GET" and request.path.startswith(
            "/adapter/"
        ):
            aid = request.path[len("/adapter/"):]
            data = await asyncio.get_running_loop().run_in_executor(
                None, peer.serve_wire, aid
            )
            if data is None:
                raise HTTPError(404, "no such adapter on this replica")
            return Response(
                data, content_type="application/octet-stream"
            )
        return await nxt(request)


def _install_kv_push(app: App, engine) -> None:
    """The internal prefill→decode push intake (r18 disaggregation,
    decode-role replicas only): ``POST /kv/push`` stages one chunk
    (or the fin) of a transfer. Parse + staging run on an executor
    thread — numpy copies of multi-KB bodies must not block the
    event loop. A corrupt body is a 400 the SENDER counts as its
    transfer failure; the decode replica then simply cold-prefills
    when the router's second hop arrives without a usable
    transfer."""
    push = engine.kv_push

    @app.post("/kv/push")
    async def kv_push(request: Request):
        body = request.body
        if not body:
            raise HTTPError(422, "empty push body")
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, push.receive, body
            )
        except ValueError as e:
            raise HTTPError(400, f"bad push body: {e}") from None
        return out


def _install_common(app: App, engine, registry: MetricsRegistry, batcher) -> None:
    """Routes/middleware every engine kind shares: CSV ingestion
    (``/files/``, the reference's second endpoint), health, metrics."""
    # Counter/histogram objects resolved once per (route, status) and
    # cached — the hot path does two dict hits, not two f-string
    # formats + registry lookups per request. Only registered routes
    # become labels — unmatched paths all collapse to one bucket, so a
    # URL scanner can't grow the registry (or this cache) without bound.
    _counters: dict = {}
    _histograms: dict = {}

    def _record(key, status: int, ms: float) -> None:
        ckey = (key, status)
        counter = _counters.get(ckey)
        if counter is None:
            route = f"{key[0]} {key[1]}" if key else "unmatched"
            counter = _counters[ckey] = registry.counter(
                f"http.requests{{route={route},status={status}}}"
            )
            _histograms.setdefault(
                key, registry.histogram(f"http.latency_ms{{route={route}}}")
            )
        counter.inc()
        _histograms[key].observe(ms)

    @app.middleware
    async def _metrics_mw(request: Request, nxt):
        t0 = time.perf_counter()
        # Errors must be counted too: a handler raising HTTPError (or
        # anything else -> 500) unwinds through this middleware before
        # App.handle converts it to a response.
        status = 500
        recorded = False
        try:
            response = await nxt(request)
            status = response.status
            if isinstance(response, StreamingResponse):
                # The handler returns before a single token decodes;
                # measuring here would log ~0 ms for every stream.
                # Record when the body iterator finishes instead.
                response.body_iter = _record_when_done(
                    response.body_iter, request, status, t0
                )
                recorded = True
            return response
        except HTTPError as e:
            status = e.status
            raise
        finally:
            if not recorded:
                key = (request.method, request.path)
                if key not in app._routes:  # plain dict hit, no frozenset
                    key = None
                _record(key, status, (time.perf_counter() - t0) * 1e3)

    async def _record_when_done(it, request: Request, status: int, t0: float):
        try:
            async for chunk in it:
                yield chunk
        finally:
            # Being closed early (client disconnect) must close the
            # WRAPPED iterator too — `async for` does not aclose its
            # source on abnormal exit (PEP 525), and the inner
            # generator's finally is what cancels the decode work.
            aclose = getattr(it, "aclose", None)
            if aclose is not None:
                try:
                    await aclose()
                except Exception:
                    pass
            key = (request.method, request.path)
            if key not in app._routes:
                key = None
            _record(key, status, (time.perf_counter() - t0) * 1e3)

    @app.post("/files/")
    async def create_file(request: Request):
        """Ingest a CSV upload (multipart) with an auth-token form
        field; echoes columns/rows/records as JSON."""
        import pandas as pd

        fields, files = request.form()
        if "token" not in fields:
            raise HTTPError(422, "missing form field 'token'")
        if "file" not in files:
            raise HTTPError(422, "missing file field 'file'")
        raw = files["file"].data
        try:
            text = raw.decode("utf-8")
        except UnicodeDecodeError as e:
            raise HTTPError(400, f"file is not utf-8 text: {e}") from None
        try:
            df = await asyncio.get_running_loop().run_in_executor(
                None, lambda: pd.read_csv(io.StringIO(text))
            )
        except Exception as e:
            raise HTTPError(400, f"could not parse CSV: {e}") from None
        records = df.head(MAX_ECHO_RECORDS).to_dict(orient="records")
        return {
            "file": {
                "columns": list(map(str, df.columns)),
                "rows": int(len(df)),
                "records": records,
                "truncated": len(df) > MAX_ECHO_RECORDS,
            },
            "token": fields["token"],
        }

    # The multipart route has no pydantic body model for the schema
    # generator to introspect; document its form contract explicitly.
    create_file.__openapi__ = {
        "requestBody": {
            "required": True,
            "content": {
                "multipart/form-data": {
                    "schema": {
                        "type": "object",
                        "required": ["file", "token"],
                        "properties": {
                            "file": {"type": "string", "format": "binary"},
                            "token": {"type": "string"},
                        },
                    }
                }
            },
        }
    }

    @app.get("/healthz")
    async def healthz():
        import os

        import jax

        draining = bool(
            getattr(engine, "draining", False)
            or (batcher is not None and batcher.draining)
        )
        depth = (
            batcher.queue_depth if batcher is not None
            else getattr(engine, "queue_depth", 0)
        )
        role = getattr(engine, "replica_role", "mixed")
        models = app.state.get("models")
        multi = models is not None and len(models.ids()) > 1
        return {
            # "draining" the moment shutdown begins: the load balancer
            # stops routing here while in-flight streams finish.
            "status": "draining" if draining else "ok",
            # Role-split fleets (r18): which disaggregation role this
            # replica plays. Absent on mixed replicas — the default
            # topology's healthz is bit-identical to r17.
            **({"role": role} if role != "mixed" else {}),
            # Multi-model registry (r22): which model ids this process
            # serves (the router's per-model candidate filter reads
            # this). Absent in single-model mode — bit-identical to
            # r21.
            **({"models": models.describe()} if multi else {}),
            # Backpressure in the SAME poll the router/balancer already
            # makes for liveness (its threshold check still scrapes the
            # authoritative /metrics gauges on the poll cadence; this
            # rides along for one-shot dashboards and humans).
            "queue_depth": depth,
            "model": type(engine.model).__name__,
            "classes": list(engine.vocab.labels),
            "checkpoint": engine.meta,
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
            # Which worker process answered — observability for
            # SO_REUSEPORT multi-worker serving (and the multiworker
            # test's distribution check).
            "pid": os.getpid(),
        }

    @app.get("/metrics")
    async def metrics():
        snap = registry.snapshot()
        if batcher is not None:
            snap["counters"]["batcher.device_calls"] = batcher.device_calls
            snap["counters"]["batcher.requests"] = batcher.requests
            snap["counters"]["batcher.timeouts"] = batcher.timeouts
            snap["counters"]["batcher.rejected"] = batcher.rejected
            snap["counters"]["batcher.shed_draining"] = (
                batcher.shed_draining
            )
            snap["counters"]["batcher.deadline_expired"] = (
                batcher.deadline_expired
            )
            # Gauges: the overload early-warning signals — queue depth
            # and in-flight batches are the first things to move when
            # offered load exceeds capacity.
            snap.setdefault("gauges", {})
            snap["gauges"]["batcher.queue_depth"] = batcher.queue_depth
            snap["gauges"]["batcher.inflight"] = batcher.inflight
            snap["gauges"]["batcher.draining"] = int(batcher.draining)
            snap["gauges"]["batcher.router_queue_depth"] = (
                batcher.router_queue_depth
            )
        elif engine.kind == "generative":
            snap["counters"]["generate.requests"] = engine.requests
            snap["counters"]["generate.batch_calls"] = engine.batch_calls
            snap["counters"]["generate.chunk_calls"] = engine.chunk_calls
            snap["counters"]["generate.rejected"] = engine.rejected
            snap["counters"]["generate.compactions"] = engine.compactions
            snap["counters"]["generate.admitted"] = engine.admitted
            snap["counters"]["generate.prefix_hits"] = engine.prefix_hits
            snap["counters"]["generate.prefix_misses"] = (
                engine.prefix_misses
            )
            snap["counters"]["generate.prefix_fallbacks"] = (
                engine.prefix_fallbacks
            )
            # Cold prefix prefills (distinct from misses, which a tier
            # restore also moves): the counter the router's affinity
            # A/B is pinned against — fleet-summed builds stay at one
            # per distinct prefix under affinity routing.
            snap["counters"]["generate.prefix_builds"] = (
                engine.prefix_builds
            )
            snap["counters"]["generate.prefill_chunks"] = (
                engine.prefill_chunks
            )
            snap["counters"]["generate.spec_rounds"] = engine.spec_rounds
            snap["counters"]["generate.spec_drafted"] = (
                engine.spec_drafted
            )
            snap["counters"]["generate.spec_accepted"] = (
                engine.spec_accepted
            )
            # One fused_calls tick per batch that dispatched at least
            # one fused-width decode chunk (r20: the whole-generation
            # programs are gone — fused traffic rides the unit queue).
            snap["counters"]["generate.fused_calls"] = engine.fused_calls
            # Page-native prefill + interleaving (r10). adopt_bytes is
            # exact dtype/shape arithmetic: 0 on the page-native path,
            # one full prefill copy per formation/admission on the
            # legacy adopt path — the gauge IS the claim.
            snap["counters"]["generate.prefill_adopt_bytes"] = (
                engine.prefill_adopt_bytes
            )
            snap["counters"]["generate.prefix_adopt_bytes"] = (
                engine.prefix_adopt_bytes
            )
            snap["counters"]["generate.kv_prefix_copy_fallback"] = (
                engine.kv_prefix_copy_fallback
            )
            snap["counters"]["generate.interleaved_prefills"] = (
                engine.interleaved_prefills
            )
            snap["counters"]["generate.spec_realign_table_ops"] = (
                engine.spec_realign_table_ops
            )
            snap["counters"]["generate.spec_realign_repacks"] = (
                engine.spec_realign_repacks
            )
            # Robustness layer (r12): what was shed at the door
            # (queue-full / infeasible deadline / draining), what
            # expired at which lifecycle stage, which brownout levers
            # engaged, and how many armed faults fired — the overload
            # POST-MORTEM block: these counters say WHY requests
            # failed, the gauges above say when it started.
            snap["counters"]["generate.shed_queue_full"] = (
                engine.shed_queue_full
            )
            snap["counters"]["generate.shed_deadline_infeasible"] = (
                engine.shed_deadline_infeasible
            )
            snap["counters"]["generate.shed_draining"] = (
                engine.shed_draining
            )
            snap["counters"]["generate.deadline_expired_queued"] = (
                engine.deadline_expired_queued
            )
            snap["counters"]["generate.deadline_expired_prefill"] = (
                engine.deadline_expired_prefill
            )
            snap["counters"]["generate.deadline_expired_decode"] = (
                engine.deadline_expired_decode
            )
            snap["counters"]["generate.brownout_spec_suppressed"] = (
                engine.brownout_spec_suppressed
            )
            snap["counters"]["generate.brownout_tokens_clamped"] = (
                engine.brownout_tokens_clamped
            )
            snap["counters"]["generate.faults_injected"] = (
                engine.faults_injected
            )
            # Continuous-batching scheduler v2 (r15; default-on and
            # the ONE execution model since r20): per-unit-type
            # dispatch counters over the typed-unit queue — the
            # counters the concurrency claims are asserted from
            # (interleaving = two lanes' units both moving in one
            # window, never wall-clock). sched_units_admit ticks as
            # lanes install staged joiners at unit boundaries (the
            # r20 in-lane admission path).
            snap["counters"]["generate.sched_units_prefill"] = (
                engine.sched_units_prefill
            )
            snap["counters"]["generate.sched_units_decode"] = (
                engine.sched_units_decode
            )
            snap["counters"]["generate.sched_units_spec"] = (
                engine.sched_units_spec
            )
            snap["counters"]["generate.sched_units_admit"] = (
                engine.sched_units_admit
            )
            snap["counters"]["generate.sched_units_compact"] = (
                engine.sched_units_compact
            )
            snap["counters"]["generate.sched_deadline_preempts"] = (
                engine.sched_deadline_preempts
            )
            snap["counters"]["generate.sched_pages_deferred"] = (
                engine.sched_pages_deferred
            )
            # Multi-model + multi-tenant (r22): scoring dispatches
            # that rode this engine's unit queue, group starts
            # deferred on a TENANT quota (pages / adapter slots —
            # distinct from the pool-wide deferral above), and
            # tenant-scoped brownout clamps (engages before the
            # fleet-wide rung 1).
            snap["counters"]["generate.sched_units_score"] = (
                engine.sched_units_score
            )
            snap["counters"]["generate.sched_tenant_pages_deferred"] = (
                engine.sched_tenant_pages_deferred
            )
            snap["counters"][
                "generate.sched_tenant_adapters_deferred"
            ] = engine.sched_tenant_adapters_deferred
            snap["counters"]["generate.brownout_tenant_clamped"] = (
                engine.brownout_tenant_clamped
            )
            # The program's own clock (utils/metrics.span), as sums of
            # microseconds (_us) and of spans (_n): a request's life
            # (submit → the scheduler's claim → first token), the
            # dispatch thread's time by unit kind, its wait for the
            # device at each token readback, and its time with nothing
            # to dispatch. Over a window, readback_wait_us + sched_idle_us
            # + the sched_unit_*_us not spent in readbacks is the
            # thread's wall time.
            sums = engine.latency.sums.snapshot()["counters"]
            for name in (
                "generate.queue_wait_us", "generate.queue_wait_n",
                "generate.prefill_wait_us", "generate.prefill_wait_n",
                "generate.readback_wait_us", "generate.readback_wait_n",
                "generate.sched_idle_us", "generate.sched_idle_n",
                "generate.sched_unit_prefill_us",
                "generate.sched_unit_prefill_n",
                "generate.sched_unit_decode_us",
                "generate.sched_unit_decode_n",
                "generate.sched_unit_spec_us",
                "generate.sched_unit_spec_n",
                "generate.sched_unit_admit_us",
                "generate.sched_unit_admit_n",
                "generate.sched_unit_compact_us",
                "generate.sched_unit_compact_n",
                "generate.sched_unit_score_us",
                "generate.sched_unit_score_n",
                # a lane's last turn: final drain and cleanup (also
                # where a unit that raised is counted)
                "generate.sched_unit_retire_us",
                "generate.sched_unit_retire_n",
            ):
                snap["counters"][name] = sums.get(
                    name.removeprefix("generate."), 0
                )
            snap.setdefault("gauges", {})
            snap["gauges"]["generate.sched_queue_depth"] = (
                engine.sched_queue_depth
            )
            snap["gauges"]["generate.sched_batches_live"] = (
                engine.sched_batches_live
            )
            snap["gauges"]["generate.sched_batches_live_max"] = (
                engine.sched_batches_live_max
            )
            # Cross-lane head-of-line bound (r20): the longest run of
            # consecutive units one lane dispatched while another was
            # live — ≤ the alternation floor means fused traffic
            # stalls concurrent lanes by at most ONE fused-chunk
            # dispatch.
            snap["gauges"]["generate.sched_lane_stall_max"] = (
                engine.sched_lane_stall_max
            )
            # Fleet pressure the fronting router last reported
            # (x-mlapi-router-depth; 0 for direct traffic).
            snap["gauges"]["generate.router_queue_depth"] = (
                engine.router_queue_depth
            )
            snap["gauges"]["generate.draining"] = int(engine.draining)
            snap["gauges"]["generate.queue_depth"] = engine.queue_depth
            # Chunked-prefill interleaving: chunks still queued for
            # the in-progress long-prompt joiner (0 when idle), and
            # the worst consecutive prefill-dispatch run live decode
            # rows ever waited behind (the design pins it at 1).
            snap["gauges"]["generate.prefill_chunk_queue_depth"] = (
                engine.prefill_chunk_queue_depth
            )
            snap["gauges"]["generate.interleave_max_stall"] = (
                engine.interleave_max_stall
            )
            # TTFT / inter-token latency summaries from the engine's
            # delivery-time reservoirs (ms; null until traffic).
            for k, v in engine.latency.summary().items():
                snap["gauges"][f"generate.{k}"] = v
            # Deterministic per-slot KV bytes at the default
            # bucket/tier (addressable_shards nbytes) — the committed
            # int8-KV number; kv_quant itself rides /healthz meta.
            # Warmup precomputes it, but a scrape that arrives FIRST
            # would build a largest-bucket cache on-device — that
            # fence goes through the executor, never the event loop
            # (mlapi-lint MLA008, caught r19).
            snap["gauges"]["generate.kv_cache_bytes_per_slot"] = (
                await asyncio.get_running_loop().run_in_executor(
                    None, engine.kv_cache_slot_bytes
                )
            )
            # Modeled HBM read per decode step for the ACTIVE (cache
            # format, decode impl) pair — the production-observable
            # form of the int8 flash-decode read saving (exact host
            # arithmetic, no device work).
            snap["gauges"]["generate.decode_bytes_per_step"] = (
                engine.decode_bytes_per_step()
            )
            # Same accounting for one multi-token extend chunk's read
            # (chunked prefill / admission / speculative verify): the
            # int8 flash saving applies to every token the server
            # processes, amortized per chunk instead of per step.
            snap["gauges"]["generate.extend_bytes_per_chunk"] = (
                engine.extend_bytes_per_chunk()
            )
            if getattr(engine, "pool", None) is not None:
                # Paged KV pool observability: capacity headroom
                # (total vs in_use), how much of the live footprint is
                # prefix sharing (shared), and the utilization ratio —
                # the "do I need more --kv-pages" dashboard block.
                snap["gauges"]["generate.kv_pages_total"] = (
                    engine.kv_pages_total
                )
                snap["gauges"]["generate.kv_pages_in_use"] = (
                    engine.kv_pages_in_use
                )
                snap["gauges"]["generate.kv_pages_shared"] = (
                    engine.kv_pages_shared
                )
                snap["gauges"]["generate.kv_page_utilization"] = (
                    engine.kv_page_utilization
                )
                snap["gauges"]["generate.kv_page_bytes"] = (
                    engine.kv_page_bytes()
                )
                # Prefix-entry page-set evictions under pool pressure
                # (alloc-pressure + brownout evict_idle): with the
                # host tier attached these are routine, recoverable
                # spills, so the per-event log dropped to debug and
                # THIS counter is the observable.
                snap["counters"]["generate.kv_entry_evictions"] = (
                    engine.pool.entry_evictions
                )
            if getattr(engine, "kv_tier", None) is not None:
                # Hierarchical KV tier (r13): spill/restore traffic
                # and the tier's occupancy. All byte counters are the
                # kv_tree_bytes closed form per blob (exact dtype/
                # shape arithmetic), never wall-clock — restore_hits
                # moving while prefix builds stay flat IS the
                # saved-prefill claim.
                snap["counters"]["generate.kv_prefix_restore_hits"] = (
                    engine.kv_prefix_restore_hits
                )
                snap["counters"]["generate.kv_prefix_restore_misses"] = (
                    engine.kv_prefix_restore_misses
                )
                snap["counters"]["generate.kv_prefix_restore_bytes"] = (
                    engine.kv_prefix_restore_bytes
                )
                snap["counters"][
                    "generate.kv_prefix_restore_failures"
                ] = engine.kv_prefix_restore_failures
                snap["counters"]["generate.kv_prefix_spill_count"] = (
                    engine.kv_prefix_spill_count
                )
                snap["counters"]["generate.kv_prefix_spill_bytes"] = (
                    engine.kv_prefix_spill_bytes
                )
                snap["counters"]["generate.kv_prefix_spill_failures"] = (
                    engine.kv_prefix_spill_failures
                )
                snap["counters"]["generate.kv_tier_evictions"] = (
                    engine.kv_tier_evictions
                )
                snap["gauges"]["generate.kv_tier_bytes_in_use"] = (
                    engine.kv_tier_bytes_in_use
                )
                snap["gauges"]["generate.kv_tier_entries"] = (
                    engine.kv_tier_entries
                )
            if getattr(engine, "kv_peer", None) is not None:
                # Peer-to-peer prefix-KV fetch (r17): wire traffic in
                # and out, exact payload-byte arithmetic per blob
                # (never wall-clock). fetch_hits moving while
                # prefix_builds stays flat IS the transferred-warmth
                # claim; the router SUMS these across replicas like
                # every other generate counter, so the fleet dashboard
                # reads total KV moved peer-to-peer directly.
                snap["counters"]["generate.kv_peer_fetch_hits"] = (
                    engine.kv_peer_fetch_hits
                )
                snap["counters"]["generate.kv_peer_fetch_misses"] = (
                    engine.kv_peer_fetch_misses
                )
                snap["counters"]["generate.kv_peer_fetch_bytes"] = (
                    engine.kv_peer_fetch_bytes
                )
                snap["counters"]["generate.kv_peer_fetch_failures"] = (
                    engine.kv_peer_fetch_failures
                )
                snap["counters"]["generate.kv_peer_serve_count"] = (
                    engine.kv_peer_serve_count
                )
                snap["counters"]["generate.kv_peer_serve_bytes"] = (
                    engine.kv_peer_serve_bytes
                )
            if getattr(engine, "kv_push", None) is not None:
                # Prefill/decode disaggregation (r18): chunk-push
                # traffic out (prefill role) and in (decode role),
                # exact payload-byte arithmetic per chunk — never
                # wall-clock. kv_push_applied moving while
                # prefix_builds AND prefill_chunks stay flat IS the
                # zero-decode-side-prefill claim; kv_push_fallbacks
                # counts the degradations (failed/incomplete/drifted
                # transfers served by the cold prefill instead).
                # Absent on mixed replicas — the default topology's
                # /metrics is bit-identical to r17.
                snap["counters"]["generate.kv_push_sent"] = (
                    engine.kv_push_sent
                )
                snap["counters"]["generate.kv_push_send_failures"] = (
                    engine.kv_push_send_failures
                )
                snap["counters"]["generate.kv_push_bytes_sent"] = (
                    engine.kv_push_bytes_sent
                )
                snap["counters"]["generate.kv_push_recv"] = (
                    engine.kv_push_recv
                )
                snap["counters"]["generate.kv_push_recv_failures"] = (
                    engine.kv_push_recv_failures
                )
                snap["counters"]["generate.kv_push_bytes_recv"] = (
                    engine.kv_push_bytes_recv
                )
                snap["counters"]["generate.kv_push_applied"] = (
                    engine.kv_push_applied
                )
                snap["counters"]["generate.kv_push_bytes_applied"] = (
                    engine.kv_push_bytes_applied
                )
                snap["counters"]["generate.kv_push_fallbacks"] = (
                    engine.kv_push_fallbacks
                )
            if getattr(engine, "adapters", None) is not None:
                # Many-adapter LoRA serving: the slot pool, the host
                # store, and the fetch/application traffic. All byte
                # gauges are exact dtype/shape arithmetic, never
                # wall-clock — adapter_resident_bytes growing by
                # EXACTLY adapter_slot_bytes per resident tenant over
                # the base footprint IS the HBM-amortization claim,
                # and adapter_fetch_hits moving while the local
                # store's entries grow (prefix_builds-style) is the
                # transferred-warmth claim, adapter tier.
                snap["counters"]["generate.adapter_fetch_hits"] = (
                    engine.adapter_fetch_hits
                )
                snap["counters"]["generate.adapter_fetch_misses"] = (
                    engine.adapter_fetch_misses
                )
                snap["counters"]["generate.adapter_fetch_bytes"] = (
                    engine.adapter_fetch_bytes
                )
                snap["counters"]["generate.adapter_fetch_failures"] = (
                    engine.adapter_fetch_failures
                )
                snap["counters"]["generate.adapter_serve_count"] = (
                    engine.adapter_serve_count
                )
                snap["counters"]["generate.adapter_serve_bytes"] = (
                    engine.adapter_serve_bytes
                )
                snap["counters"]["generate.adapter_installs"] = (
                    engine.adapter_installs
                )
                snap["counters"]["generate.adapter_evictions"] = (
                    engine.adapter_evictions
                )
                snap["counters"]["generate.adapter_grouped_batches"] = (
                    engine.adapter_grouped_batches
                )
                snap["counters"]["generate.adapter_gathered_batches"] = (
                    engine.adapter_gathered_batches
                )
                snap["counters"]["generate.adapter_store_evictions"] = (
                    engine.adapter_store_evictions
                )
                snap["counters"]["generate.sched_adapters_deferred"] = (
                    engine.sched_adapters_deferred
                )
                snap["gauges"]["generate.adapter_slots_total"] = (
                    engine.adapter_slots_total
                )
                snap["gauges"]["generate.adapter_slots_in_use"] = (
                    engine.adapter_slots_in_use
                )
                snap["gauges"]["generate.adapter_slot_bytes"] = (
                    engine.adapter_slot_bytes
                )
                snap["gauges"]["generate.adapter_resident_bytes"] = (
                    engine.adapter_resident_bytes
                )
                snap["gauges"]["generate.adapter_store_bytes_in_use"] = (
                    engine.adapter_store_bytes_in_use
                )
                snap["gauges"]["generate.adapter_store_entries"] = (
                    engine.adapter_store_entries
                )
        # Per-model counter family (r22): ONLY in multi-model mode —
        # a one-entry registry's /metrics stays bit-identical to r21.
        # Each entry exports the small per-model dashboard row; the
        # default model's full counter block above is unchanged.
        models = app.state.get("models")
        if models is not None and len(models.ids()) > 1:
            snap.setdefault("gauges", {})
            score_paths = app.state.get("score_paths") or {}
            for mid, eng in models.items():
                pfx = f"model.{mid}"
                if eng.kind == "generative":
                    snap["counters"][f"{pfx}.requests"] = eng.requests
                    snap["counters"][f"{pfx}.rejected"] = eng.rejected
                    snap["counters"][f"{pfx}.sched_units_decode"] = (
                        eng.sched_units_decode
                    )
                    snap["counters"][f"{pfx}.sched_units_score"] = (
                        eng.sched_units_score
                    )
                    snap["gauges"][f"{pfx}.queue_depth"] = (
                        eng.queue_depth
                    )
                    for k, v in eng.latency.summary().items():
                        snap["gauges"][f"{pfx}.{k}"] = v
                else:
                    sp = score_paths.get(mid)
                    if sp is None:
                        continue
                    snap["counters"][f"{pfx}.requests"] = sp.requests
                    snap["counters"][f"{pfx}.device_calls"] = (
                        sp.device_calls
                    )
                    # Dispatches that rode a co-resident generative
                    # engine's unit queue as score units (vs the pool
                    # backend): sched_dispatches ≈ device_calls IS
                    # the one-scheduler claim.
                    snap["counters"][f"{pfx}.sched_dispatches"] = (
                        sp.sched_dispatches
                    )
                    snap["counters"][f"{pfx}.rejected"] = sp.rejected
                    snap["counters"][f"{pfx}.deadline_expired"] = (
                        sp.deadline_expired
                    )
                    snap["gauges"][f"{pfx}.queue_depth"] = (
                        sp.queue_depth
                    )
                    for k, v in sp.latency.summary().items():
                        snap["gauges"][f"{pfx}.{k}"] = v
        # Per-tenant pressure block (r22): live depth plus the quota
        # deferral / brownout history — only tenants with any history
        # appear, so an untenanted deployment's scrape is unchanged.
        tenants = app.state.get("tenants")
        if tenants is not None:
            snap.setdefault("gauges", {})
            for t, row in sorted(tenants.snapshot().items()):
                pfx = f"tenant.{t or 'anonymous'}"
                snap["gauges"][f"{pfx}.depth"] = row["depth"]
                snap["counters"][f"{pfx}.deferrals"] = row["deferrals"]
                snap["counters"][f"{pfx}.brownouts"] = row["brownouts"]
        return snap

    return app
