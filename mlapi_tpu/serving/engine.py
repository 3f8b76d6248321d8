"""Inference engine: params resident on device, one warmed jit forward.

Inverts the reference's hot-path design, which re-loads the pickled
model from disk **on every request** (``main.py:19``) and then runs
the matmul twice (``predict`` then ``predict_proba``,
``main.py:21-22``). Here:

- The checkpoint is loaded **once** at startup (onto the mesh if one
  is given).
- The forward pass is jit-compiled once per batch-bucket size at
  warmup, so no request ever pays XLA compilation.
- Prediction *and* probability come out of a single device call:
  ``argmax`` + ``max(softmax)`` over one set of logits, with only two
  scalars per row transferred back to the host.
- Requests are padded to a small set of bucket sizes so arbitrary
  batch sizes never trigger recompilation (static shapes — XLA
  requirement, SURVEY §7 step 4).

The GENERATIVE engine's subsystems live in sibling modules with the
engine as their hub (r04 split): request/prefix-entry types in
``requests.py``, the shared-prefix KV cache in ``prefix.py``, the
host speculation phase in ``spec_phase.py``, the batch-1 fused fast
path in ``fused_single.py``, and the chained-dispatch drain machinery
in ``dispatch.py``. ``_run_batch`` here remains the batch LIFECYCLE —
formation, continuous admission, growth/compaction, handoffs — the
one place the pieces compose.
"""

from __future__ import annotations

import asyncio
import bisect
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from mlapi_tpu.serving import faults
from mlapi_tpu.serving.fused_single import FusedSinglePath
from mlapi_tpu.serving.prefix import PrefixCache

# Request-side data types live in serving/requests.py; re-exported
# because the engine API and the test suite name them from this module.
from mlapi_tpu.serving.requests import (
    DeadlineExceeded,
    DrainCancelled,
    GenRequest,
    _PrefixEntry,
    _SyncSink,
)
from mlapi_tpu.serving.spec_phase import SpecPhase

from mlapi_tpu.utils.logging import get_logger
from mlapi_tpu.utils.vocab import LabelVocab

_log = get_logger("serving.engine")

DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)


class NotServable(ValueError):
    """The checkpoint's model family cannot be served (its own
    ``serving_refusal`` says why, in one sentence)."""


class InferenceEngine:
    """Batched classification inference over a jitted forward pass.

    Rows are float32 feature vectors; see
    :class:`TextClassificationEngine` for the token-id variant.
    """

    kind = "tabular"
    input_dtype = np.float32

    def __init__(
        self,
        model,
        params,
        vocab: LabelVocab,
        feature_names: Sequence[str],
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        mesh: jax.sharding.Mesh | None = None,
        meta: dict | None = None,
    ):
        self.model = model
        self.vocab = vocab
        self.feature_names = tuple(feature_names)
        self.num_features = int(
            getattr(model, "num_features", 0) or len(self.feature_names) or 1
        )
        self.buckets = tuple(sorted(buckets))
        self.mesh = mesh
        self.meta = dict(meta or {})
        if mesh is not None:
            from mlapi_tpu.parallel import (
                batch_shard_size,
                model_on_mesh,
                params_for_model,
            )

            self.model = model = model_on_mesh(model, mesh)
            # Batches shard over data AND (when present) fsdp — the
            # divisibility unit is their product.
            axis = batch_shard_size(mesh)
            bad = [b for b in self.buckets if b % axis]
            if bad:
                raise ValueError(
                    f"buckets {bad} not divisible by batch-sharding "
                    f"axes of total size {axis}"
                )
            # Serve in the model's declared layout (e.g. Wide&Deep's
            # vocab-sharded tables) — the reason to serve on a mesh at
            # all is that the params don't fit (or shouldn't be
            # copied) per chip. A 3-axis mesh additionally
            # ZeRO-shards every large leaf over ``fsdp``
            # (params_for_model): weights all-gather per use, so a
            # model too big per chip serves from sharded storage.
            params = params_for_model(model, params, mesh)
        else:
            params = jax.device_put(params)
        self.params = params

        def forward(p, x):
            logits = self.model.apply(p, x)
            probs = jax.nn.softmax(logits, axis=-1)
            # ONE fused [B, 2] output (id, max-prob) — a single
            # device→host transfer. Two separate outputs would cost two
            # round trips.
            return jnp.stack(
                [jnp.argmax(logits, axis=-1).astype(jnp.float32),
                 jnp.max(probs, axis=-1)],
                axis=-1,
            )

        self._forward = jax.jit(forward)

    @classmethod
    def from_checkpoint(
        cls,
        path,
        model=None,
        *,
        mesh: jax.sharding.Mesh | None = None,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        quantize: str | None = None,
        kv_quant: str | None = None,
        decode_attn_impl: str | None = None,
        kv_page_size: int | None = None,
        kv_pages: int | None = None,
        prefill_page_native: bool = True,
        prefill_interleave: bool = True,
        kv_tier_bytes: int = 0,
        kv_tier_disk_dir: str | None = None,
        kv_peer_fetch: bool = False,
        replica_role: str = "mixed",
        draft_checkpoint=None,
        spec_sample: bool = False,
        sched_max_batches: int = 2,
        adapter_slots: int = 0,
        adapter_store_bytes: int = 0,
        adapter_disk_dir: str | None = None,
    ) -> "InferenceEngine":
        """Build an engine from a committed checkpoint dir.

        The model is reconstructed from the checkpoint's own config
        (``model`` registry name + kwargs) unless one is passed in,
        and the engine class follows the model's ``input_kind``
        (tabular feature rows vs text token ids).

        ``quantize="int8"`` converts the loaded float weights to
        weight-only per-channel int8 at load time and serves through
        the transparent :class:`~mlapi_tpu.models.quantized.QuantizedModel`
        wrapper — half the parameter HBM, dequantization fused into
        each matmul inside the jitted programs. Composes with
        ``mesh``: the ``q`` leaves take the inner model's TP layout,
        per-channel scales ride the channel axis
        (``parallel.mesh.place_params``).

        ``kv_quant="int8"`` stores every decode KV cache as int8
        payload + per-token-per-head f32 scales (``ops/quant.py``):
        ~2x less decode HBM per cached token and ~2x the
        cache/prefix/slot budget at equal hardware. The format is a
        MODEL field, so every jitted program (prefill, decode chunks,
        fused generation, admission scatter, prefix widen, spec
        mirrors) keys on it and stays format-consistent — including
        the draft, which decodes against its own int8 cache.
        Orthogonal to ``quantize`` (weights) and ``mesh``; generative
        checkpoints only.

        ``decode_attn_impl="flash"`` routes every single-token decode
        step through the Pallas split-K flash-decode kernel
        (``ops/pallas/decode_attention``) instead of the reference
        einsum — with an int8 cache the kernel reads int8 tiles from
        HBM and dequantizes in registers, so the format's 2x byte
        saving reaches the decode READ, not just storage. A model
        field like ``kv_quant`` (program factories key on it; the
        draft mirrors it); generative checkpoints only.

        ``kv_page_size=N`` switches serving KV allocation from
        contiguous per-slot tier buffers to the block-granular paged
        pool (``kv_pages`` sizes it; defaults to the
        contiguous-equivalent budget): sequences hold only the pages
        covering their actual length, shared prefixes become
        ref-counted shared pages with copy-on-write divergence, and
        batch growth/compaction become page-table bookkeeping instead
        of cache gathers. Token streams are pinned identical to the
        contiguous layout across both ``kv_quant`` formats and both
        decode impls (DESIGN §15). Generative checkpoints only.

        ``kv_tier_bytes=N`` enables the hierarchical host-RAM KV tier
        (``serving/kv_tier.py``): evicted prefix KV page sets spill to
        host memory (optionally ``kv_tier_disk_dir``-backed files) in
        their stored format instead of being discarded, and re-arrivals
        restore by ``device_put`` with zero prefill FLOPs — greedy
        streams are pinned token-identical across {evict → restore} vs
        {never evicted} (DESIGN §19). 0 (default) keeps the r12
        discard behavior bit for bit. Generative checkpoints only.

        ``kv_peer_fetch=True`` lets router replicas exchange prefix-KV
        blobs peer to peer (``serving/kv_peer.py``): a replica that
        misses a prefix locally fetches the stored-format blob from
        the router-hinted warm peer and restores it instead of
        cold-prefilling, and serves its own warm blobs on ``GET
        /kv/prefix`` (DESIGN §23). Off (default): bit-identical to
        the flag never existing. Generative checkpoints only.
        """
        import dataclasses

        from mlapi_tpu.checkpoint import load_checkpoint
        from mlapi_tpu.models import get_model

        if model is None:
            # Peek the manifest for the model config, then restore with
            # signature validation against the freshly-built model.
            meta = _load_meta_only(path)
            model = get_model(
                meta.config["model"], **meta.config.get("model_kwargs", {})
            )
            feature_names = meta.config.get("feature_names", ())
        else:
            feature_names = ()
        if getattr(model, "serving_refusal", None):
            # a family that trains but has no cache protocol yet
            raise NotServable(model.serving_refusal)

        # eval_shape: abstract tree only — a full random init of a
        # large model would allocate (and page) every parameter just
        # to read shapes.
        abstract = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        params, meta = load_checkpoint(path, abstract)

        if kv_quant is not None:
            if kv_quant != "int8":
                raise ValueError(f"unsupported kv_quant={kv_quant!r}")
            if not hasattr(model, "generate"):
                raise ValueError(
                    "kv_quant applies to generative checkpoints (they "
                    f"hold KV caches); {type(model).__name__} has none"
                )
            try:
                # The format is a model FIELD (not engine state) so
                # every lru_cache'd program factory keys on it.
                model = dataclasses.replace(model, kv_quant="int8")
            except TypeError:
                raise ValueError(
                    f"{type(model).__name__} declares no kv_quant "
                    "cache-format field"
                ) from None

        if decode_attn_impl is not None:
            if decode_attn_impl not in ("einsum", "flash"):
                raise ValueError(
                    f"unsupported decode_attn_impl={decode_attn_impl!r}"
                )
            if not hasattr(model, "generate"):
                raise ValueError(
                    "decode_attn_impl applies to generative checkpoints "
                    f"(they decode); {type(model).__name__} does not"
                )
            try:
                # Same discipline as kv_quant: a MODEL field, so every
                # cached program factory keys on the decode impl.
                model = dataclasses.replace(
                    model, decode_attn_impl=decode_attn_impl
                )
            except TypeError:
                raise ValueError(
                    f"{type(model).__name__} declares no "
                    "decode_attn_impl field"
                ) from None

        # Engine dispatch keys off the INNER model: the quantized
        # wrapper defines the full decoder protocol, so probing the
        # wrapper would route every quantized checkpoint — tabular
        # classifiers included — to the generative engine.
        inner = model
        if quantize is not None:
            if quantize != "int8":
                raise ValueError(f"unsupported quantize={quantize!r}")
            from mlapi_tpu.models.quantized import QuantizedModel
            from mlapi_tpu.ops.quant import quantize_tree, quantized_bytes

            params = quantize_tree(params)
            stored, full = quantized_bytes(params)
            _log.info(
                "weight-only int8: params %.1f MB (f32 would be %.1f MB)",
                stored / 1e6, full / 1e6,
            )
            model = QuantizedModel(model)

        if hasattr(inner, "generate"):
            # Generative LM: no label vocab — the output space is the
            # tokenizer's.
            from mlapi_tpu.text import load_tokenizer
            from mlapi_tpu.text.tokenizer import tokenizer_from_fingerprint

            tokenizer = (
                tokenizer_from_fingerprint(meta.config["tokenizer"])
                if "tokenizer" in meta.config
                else load_tokenizer(model.vocab_size)
            )
            draft = None
            if draft_checkpoint is not None:
                dmeta = _load_meta_only(draft_checkpoint)
                if dmeta.config.get("tokenizer") != meta.config.get(
                    "tokenizer"
                ):
                    raise ValueError(
                        "draft checkpoint was trained with a different "
                        "tokenizer than the target"
                    )
                dmodel = get_model(
                    dmeta.config["model"],
                    **dmeta.config.get("model_kwargs", {}),
                )
                if kv_quant is not None:
                    # The draft's spec-phase cache mirrors ride the
                    # same format as the target's — format-consistent
                    # by construction.
                    dmodel = dataclasses.replace(
                        dmodel, kv_quant="int8"
                    )
                if decode_attn_impl is not None:
                    # The draft decodes too — same impl as the target.
                    dmodel = dataclasses.replace(
                        dmodel, decode_attn_impl=decode_attn_impl
                    )
                dabstract = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    jax.eval_shape(
                        lambda: dmodel.init(jax.random.key(0))
                    ),
                )
                dparams, _ = load_checkpoint(draft_checkpoint, dabstract)
                draft = (dmodel, dparams)
            return TextGenerationEngine(
                model,
                params,
                tokenizer=tokenizer,
                mesh=mesh,
                draft=draft,
                spec_sample=spec_sample,
                kv_page_size=kv_page_size,
                kv_pages=kv_pages,
                prefill_page_native=prefill_page_native,
                prefill_interleave=prefill_interleave,
                kv_tier_bytes=kv_tier_bytes,
                kv_tier_disk_dir=kv_tier_disk_dir,
                kv_peer_fetch=kv_peer_fetch,
                replica_role=replica_role,
                sched_max_batches=sched_max_batches,
                adapter_slots=adapter_slots,
                adapter_store_bytes=adapter_store_bytes,
                adapter_disk_dir=adapter_disk_dir,
                meta={"step": meta.step, "config_hash": meta.config_hash,
                      **({"quantized": quantize} if quantize else {}),
                      **({"kv_quant": kv_quant} if kv_quant else {}),
                      **({"decode_attn_impl": decode_attn_impl}
                         if decode_attn_impl else {}),
                      **({"kv_page_size": kv_page_size}
                         if kv_page_size else {}),
                      **({"kv_tier_bytes": kv_tier_bytes}
                         if kv_tier_bytes else {}),
                      **({"kv_peer_fetch": True}
                         if kv_peer_fetch else {}),
                      **({"replica_role": replica_role}
                         if replica_role != "mixed" else {}),
                      **({"adapter_slots": adapter_slots}
                         if adapter_slots else {}),
                      **({"sched_max_batches": sched_max_batches}
                         if sched_max_batches == 1 else {}),
                      **({"draft": str(draft_checkpoint)}
                         if draft_checkpoint else {})},
            )

        if kv_page_size is not None or kv_pages is not None:
            raise ValueError(
                "kv_page_size/kv_pages apply to generative checkpoints "
                f"(they hold KV caches); {type(inner).__name__} has none"
            )
        if kv_tier_bytes or kv_tier_disk_dir:
            raise ValueError(
                "kv_tier_bytes/kv_tier_disk_dir apply to generative "
                f"checkpoints (they cache prefix KV); "
                f"{type(inner).__name__} has none"
            )
        if kv_peer_fetch:
            raise ValueError(
                "kv_peer_fetch applies to generative checkpoints "
                f"(they cache prefix KV); {type(inner).__name__} has "
                f"none"
            )
        if replica_role != "mixed":
            raise ValueError(
                "replica_role applies to generative checkpoints "
                f"(they split prefill from decode); "
                f"{type(inner).__name__} has neither"
            )
        if adapter_slots or adapter_store_bytes or adapter_disk_dir:
            raise ValueError(
                "adapter_slots/adapter_store_bytes/adapter_disk_dir "
                "apply to generative checkpoints (they serve per-"
                f"tenant LoRA adapters); {type(inner).__name__} does "
                f"not"
            )
        # ``sched_max_batches`` is a generative-only knob (it shapes
        # the decode unit queue; ``--no-scheduler`` was retired in
        # r22 — ``sched_max_batches=1`` IS serial mode) —
        # classification checkpoints simply ignore it rather than
        # forcing every caller to special-case the default.
        if meta.vocab is None:
            raise ValueError(f"checkpoint {path} has no label vocab; cannot serve")
        feature_names = meta.config.get("feature_names", feature_names)

        if getattr(inner, "input_kind", "tabular") == "text":
            from mlapi_tpu.text import load_tokenizer
            from mlapi_tpu.text.tokenizer import tokenizer_from_fingerprint

            if "tokenizer" in meta.config:
                # Rebuild exactly the training tokenizer or refuse —
                # serving must never silently substitute a different
                # tokenization scheme.
                tokenizer = tokenizer_from_fingerprint(meta.config["tokenizer"])
            else:
                tokenizer = load_tokenizer(model.vocab_size)
            default_len = min(128, getattr(model, "max_positions", 128))
            return TextClassificationEngine(
                model,
                params,
                meta.vocab,
                tokenizer=tokenizer,
                max_len=meta.config.get("max_len", default_len),
                mesh=mesh,
                buckets=buckets,
                meta={"step": meta.step, "config_hash": meta.config_hash,
                      **({"quantized": quantize} if quantize else {})},
            )
        return InferenceEngine(
            model,
            params,
            meta.vocab,
            feature_names,
            mesh=mesh,
            buckets=buckets,
            meta={"step": meta.step, "config_hash": meta.config_hash,
                      **({"quantized": quantize} if quantize else {})},
        )

    # -- shape management -------------------------------------------------
    def bucket_for(self, n: int) -> int:
        i = bisect.bisect_left(self.buckets, n)
        return self.buckets[min(i, len(self.buckets) - 1)]

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def warmup(self) -> None:
        """Compile every bucket shape before serving traffic."""
        d = self.num_features
        for b in self.buckets:
            x = np.zeros((b, d), self.input_dtype)
            jax.block_until_ready(self._predict_padded(x))
        _log.info("warmed %d bucket shapes up to batch=%d", len(self.buckets),
                  self.max_batch)

    def _predict_padded(self, x: np.ndarray):
        if self.mesh is not None:
            from mlapi_tpu.parallel import shard_batch_for_mesh

            x = shard_batch_for_mesh(x, self.mesh)
        return self._forward(self.params, x)

    # -- public API -------------------------------------------------------
    def predict(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Classify ``[n, d]`` rows → (label ids ``[n]``, max-probs
        ``[n]``); pads to bucket, chunks past the largest bucket."""
        x = np.asarray(x, self.input_dtype)
        if x.ndim != 2:
            raise ValueError(f"expected [n, d] features, got shape {x.shape}")
        n = len(x)
        ids_out = np.empty((n,), np.int32)
        probs_out = np.empty((n,), np.float32)
        start = 0
        while start < n:
            chunk = x[start : start + self.max_batch]
            b = self.bucket_for(len(chunk))
            padded = np.zeros((b, x.shape[1]), self.input_dtype)
            padded[: len(chunk)] = chunk
            fused = np.asarray(self._predict_padded(padded))  # one transfer
            ids_out[start : start + len(chunk)] = fused[: len(chunk), 0].astype(
                np.int32
            )
            probs_out[start : start + len(chunk)] = fused[: len(chunk), 1]
            start += len(chunk)
        return ids_out, probs_out

    def predict_labels(self, x: np.ndarray) -> tuple[list[str], np.ndarray]:
        ids, probs = self.predict(x)
        return self.vocab.decode(ids), probs


class TextClassificationEngine(InferenceEngine):
    """Batched text classification: tokenizer + BERT-style model.

    Rows are fixed-length int32 token-id vectors (``max_len``); the
    attention mask is recomputed inside the model (``ids != pad``),
    so the batcher/bucketing machinery is identical to the tabular
    engine — only the row dtype and the request encoding differ.
    """

    kind = "text"
    input_dtype = np.int32

    def __init__(
        self,
        model,
        params,
        vocab: LabelVocab,
        *,
        tokenizer,
        max_len: int = 128,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        mesh: jax.sharding.Mesh | None = None,
        meta: dict | None = None,
    ):
        super().__init__(
            model, params, vocab, feature_names=(), buckets=buckets,
            mesh=mesh, meta=meta,
        )
        model_vocab = getattr(model, "vocab_size", None)
        if model_vocab is not None and tokenizer.vocab_size > model_vocab:
            # JAX gather clamps out-of-range ids silently — refuse the
            # pairing instead of mispredicting.
            raise ValueError(
                f"tokenizer emits ids up to {tokenizer.vocab_size - 1} but "
                f"the model's embedding table has {model_vocab} rows"
            )
        self.tokenizer = tokenizer
        self.max_len = int(max_len)
        self.num_features = self.max_len  # row width for warmup/stacking

    def encode(self, text: str) -> np.ndarray:
        """One request's text → a fixed-length id row."""
        ids, _ = self.tokenizer.encode(text, self.max_len)
        return ids



@functools.cache
def _dispatch_rtt_ms(samples: int = 3) -> float:
    """Best-of-N device dispatch+readback round trip, in ms. The first
    call compiles a trivial program (excluded by taking the min of the
    post-warm samples)."""
    import time

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(())
    float(f(x))  # compile + warm
    best = float("inf")
    for _ in range(samples):
        t0 = time.perf_counter()
        float(f(x))
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


@functools.cache
def _compact_fn():
    """Jitted leading-dim gather over the KV cache: select a new set
    of device rows (still-active rows for compaction, old rows plus
    dummy repeats for batch growth). The gather changes the leading
    dim, so it cannot alias the old buffers — peak HBM during a
    resize is briefly old + new cache (then the old one frees).
    Compiled once per (from, to, cache-tier) shape; the batch resizes
    along the power-of-two chain only, which the warmup grid covers.
    Per-row request vectors (temps/keys/pads/steps) live on the host
    and are re-uploaded with each chunk dispatch — only the cache is
    device-resident state."""

    def _run(cache, sel):
        return jax.tree.map(lambda a: a[sel], cache)

    return jax.jit(_run)


class TextGenerationEngine:
    """Serving engine for generative LMs (``gpt_lm``).

    Decoding is *incremental and batched*: prompts are left-padded to
    a bucket (pads masked, positions shifted — output is
    bucket-invariant, see ``GptLM.decode_step``) and decoded in
    ``chunk``-token jitted scans against a donated KV cache. Two
    consequences the one-shot design lacked:

    - **Batching**: up to ``max_batch`` concurrent ``/generate``
      requests share one prefill + one decode stream — N requests cost
      ~1 request's device time (the classification batcher's win,
      brought to generation). Per-row temperature/PRNG-stream means
      mixed greedy/sampled requests batch together.
    - **Streaming**: each decoded chunk is pushed to the requester as
      it lands, so time-to-first-token is one prefill + one chunk, not
      the whole generation.

    Compile count is bounded by shape buckets only: programs are keyed
    on (batch, prompt bucket, cache length), never on
    ``max_new_tokens``/temperature/seed (request parameters are traced
    or sliced on the host).
    """

    kind = "generative"

    def __init__(
        self,
        model,
        params,
        *,
        tokenizer,
        mesh: jax.sharding.Mesh | None = None,
        meta: dict | None = None,
        default_max_new_tokens: int = 32,
        prompt_buckets: Sequence[int] = (16, 64, 128),
        max_batch: int = 8,
        chunk: int | None = None,
        max_wait_ms: float = 2.0,
        max_queue: int = 256,
        draft: tuple | None = None,
        spec_k: int = 4,
        spec_sample: bool = False,
        fused_single: bool = True,
        fused_max_new: int | None = None,
        kv_page_size: int | None = None,
        kv_pages: int | None = None,
        prefill_page_native: bool = True,
        prefill_interleave: bool = True,
        kv_tier_bytes: int = 0,
        kv_tier_disk_dir: str | None = None,
        kv_peer_fetch: bool = False,
        kv_peer_timeout_s: float = 5.0,
        replica_role: str = "mixed",
        sched_max_batches: int = 2,
        adapter_slots: int = 0,
        adapter_store_bytes: int = 0,
        adapter_disk_dir: str | None = None,
    ):
        if tokenizer.vocab_size > model.vocab_size:
            raise ValueError(
                f"tokenizer emits ids up to {tokenizer.vocab_size - 1} but "
                f"the model's embedding table has {model.vocab_size} rows"
            )
        # Speculative decoding: (draft_model, draft_params). Used only
        # while the live batch is a single greedy row — the
        # single-stream latency lever; batched throughput stays
        # continuous batching's job.
        if draft is not None:
            d_model, d_params = draft
            if d_model.vocab_size != model.vocab_size:
                raise ValueError(
                    "draft and target models must share a vocabulary"
                )
            if d_model.max_positions < model.max_positions:
                raise ValueError(
                    f"draft window ({d_model.max_positions}) must cover "
                    f"the target's ({model.max_positions})"
                )
            self.draft_model = d_model
            if mesh is not None:
                # The draft rides the same mesh as the target (its own
                # declared TP layout): fused/host spec programs take
                # BOTH param trees, and mixing a sharded target with a
                # single-device draft would force GSPMD to reshard the
                # draft on every dispatch.
                from mlapi_tpu.parallel import params_for_model

                self.draft_params = params_for_model(
                    d_model, d_params, mesh
                )
            else:
                self.draft_params = jax.device_put(d_params)
        else:
            self.draft_model = None
            self.draft_params = None
        self.spec_k = max(1, int(spec_k))
        # Opt-in: run SAMPLED (temperature > 0) single-row requests
        # through acceptance-rejection speculation (Leviathan/Chen —
        # ops/speculative.speculative_sample's scheme). The emitted
        # stream keeps the exact target sampling distribution and a
        # solo run is deterministic per seed, but a stream interleaved
        # with admission churn is NOT byte-reproducible across runs
        # (re-engagement shifts the draft's stream offsets) — hence a
        # deployment flag (--spec-sample), not a default.
        self.spec_sample = bool(spec_sample)
        # Fused-chunk widths (r20, serving/fused_single.py): a batch
        # of non-streaming rows decodes in TIER-WIDE chunks through
        # the same decode-chunk program family — the r03 dispatch
        # saving (through a high-RTT attach every dispatch costs ~one
        # round trip, so fewer, wider chunks are the single-stream
        # RTT lever), but at unit granularity: each fused chunk is
        # one schedulable unit, so deadlines, speculation, brownout,
        # faults, and drain apply between fused chunks and a
        # concurrent lane stalls at most one fused-chunk dispatch
        # (sched_lane_stall_max). The r03-r05 whole-generation fused
        # programs (one uninterruptible dispatch per generation, with
        # per-path deadline/disagg decline gates) are retired.
        # ``fused_max_new``
        # caps the WIDTH ladder, bounding the largest single
        # dispatch; fused_single=False pins the plain ``chunk``.
        self.fused_single = bool(fused_single)
        self.fused_max_new = int(
            fused_max_new
            if fused_max_new is not None
            else max(64, default_max_new_tokens)
        )
        # TP + a Pallas attention kernel: pin the mesh ON the model so
        # ``cached_attend`` (and the flash prefill) wrap the opaque
        # ``pallas_call`` in an explicit ``shard_map`` — GSPMD cannot
        # partition it. The draft mirrors the move.
        from mlapi_tpu.parallel import model_on_mesh

        model = model_on_mesh(model, mesh)
        if self.draft_model is not None:
            self.draft_model = model_on_mesh(self.draft_model, mesh)
        self.model = model
        self.tokenizer = tokenizer
        self.mesh = mesh
        self.meta = dict(meta or {})
        self.default_max_new_tokens = default_max_new_tokens
        self.prompt_buckets = tuple(
            b for b in sorted(prompt_buckets) if b < model.max_positions
        ) or (model.max_positions // 2,)
        self.max_batch = int(max_batch)
        self.max_wait_s = max_wait_ms / 1e3
        self.max_queue = int(max_queue)
        if mesh is not None:
            from mlapi_tpu.parallel import params_for_model

            params = params_for_model(model, params, mesh)
        else:
            params = jax.device_put(params)
        self.params = params
        if chunk is None:
            # Streaming latency is chunk-count x dispatch round trip,
            # so the right chunk depends on what a round trip costs:
            # a cheap one favours small chunks (fine-grained
            # streaming + compaction); an expensive one (tens of ms)
            # favours fewer, larger chunks — a 32-token request drops
            # from 5 device round trips to 3. Measure, don't assume
            # (whether the threshold survives is ROADMAP D3).
            rtt_ms = _dispatch_rtt_ms()
            chunk = 16 if rtt_ms > 15.0 else 8
            _log.info(
                "auto decode chunk=%d (device dispatch rtt %.1f ms)",
                chunk, rtt_ms,
            )
        self.chunk = max(1, int(chunk))
        # Paged KV cache: a device-resident pool of fixed-size pages +
        # per-row page tables replaces per-slot contiguous tier
        # buffers (serving/paged_pool.py; DESIGN §15). Opt-in via
        # kv_page_size; kv_pages defaults to the contiguous-equivalent
        # budget (every slot at the default tier) so flipping paging
        # on never costs MORE HBM — the win is that short/ragged
        # sequences stop paying their padded tier and shared prefixes
        # stop being copied per row.
        if kv_pages is not None and kv_page_size is None:
            raise ValueError("kv_pages requires kv_page_size")
        self.pool = None
        if kv_page_size is not None:
            from mlapi_tpu.serving.paged_pool import PagePool

            max_total = self._cache_len(
                self.prompt_buckets[-1], self.default_max_new_tokens
            )
            if kv_pages is None:
                kv_pages = (
                    self.max_batch * -(-max_total // int(kv_page_size))
                    + 1  # the reserved null page
                )
            self.pool = PagePool(
                model, page_size=int(kv_page_size),
                num_pages=int(kv_pages),
            )
        # Hierarchical KV tier (r13, serving/kv_tier.py): a host-RAM
        # (optionally disk-backed) LRU store of evicted prefix page
        # sets, multiplying the effective prefix budget by the
        # host-RAM/HBM ratio. 0 = off (the default): evictions discard
        # exactly as before — streams and counters bit-identical to
        # r12. Attached to the pool (spill seam) and consulted by the
        # PrefixCache (restore seams).
        self.kv_tier = None
        if kv_tier_disk_dir and not kv_tier_bytes:
            raise ValueError(
                "kv_tier_disk_dir requires kv_tier_bytes > 0 (the "
                "bytes budget enables the tier; a silently-ignored "
                "disk dir would store nothing)"
            )
        if kv_tier_bytes:
            from mlapi_tpu.serving.kv_tier import KVTier

            self.kv_tier = KVTier(
                int(kv_tier_bytes), disk_dir=kv_tier_disk_dir
            )
            if self.pool is not None:
                self.pool.tier = self.kv_tier
        # Peer-to-peer prefix-KV fetch (r17, serving/kv_peer.py): on
        # a device-cache AND local-tier miss, fetch the prefix blob
        # from the router-hinted warm peer (x-mlapi-warm-peer)
        # instead of cold-prefilling, and serve this replica's own
        # warm blobs on GET /kv/prefix. Off (the default): no
        # endpoint, no hint map, no fetch — streams and counters
        # bit-identical to r16.
        self.kv_peer = None
        if kv_peer_fetch:
            from mlapi_tpu.serving.kv_peer import KVPeer

            self.kv_peer = KVPeer(self, timeout_s=kv_peer_timeout_s)
        # Prefill/decode disaggregation (r18, serving/kv_peer.py):
        # role-split replicas. A "prefill" replica serves
        # disaggregated requests as prefill-only runs, pushing each
        # finished chunk's KV to the decode replica the router named;
        # a "decode" replica exposes POST /kv/push, stages the chunks,
        # and its formation installs the assembled blob into a
        # private table row — zero decode-side prefill FLOPs. "mixed"
        # (the default): no push state, no endpoint, no role headers
        # read — bit-identical to r17. The role is a ROUTING
        # specialization, not a capability fence: either role still
        # serves a plain /generate end to end (the router's
        # role-starved fallback ladder depends on that).
        if replica_role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"replica_role must be prefill|decode|mixed, got "
                f"{replica_role!r}"
            )
        self.replica_role = replica_role
        self.kv_push = None
        if replica_role != "mixed":
            from mlapi_tpu.serving.kv_peer import KVPush

            self.kv_push = KVPush(self)
        # Many-adapter LoRA serving (serving/adapter_store.py): ONE
        # HBM-resident base amortized across per-tenant adapters.
        # adapter_slots > 0 allocates a device slot pool (per-target
        # stacked (A, B) pools, slot 0 pinned all-zero for base rows)
        # plus a host-RAM (optionally disk-backed) LRU store the slots
        # install from, plus the fleet fetch tier (GET /adapter/<id>
        # from the router-hinted warm peer — the kv_peer wire idiom).
        # 0 = off (the default): no pools, no store, no endpoint —
        # requests naming an adapter are rejected loudly and every
        # base-model program traces byte-identical to before.
        self.adapters = None
        self.adapter_store = None
        self.adapter_peer = None
        if (adapter_store_bytes or adapter_disk_dir) and not adapter_slots:
            raise ValueError(
                "adapter_store_bytes/adapter_disk_dir require "
                "adapter_slots > 0 (the slot pool enables adapter "
                "serving; a silently-ignored store budget would "
                "serve nothing)"
            )
        if adapter_slots:
            from mlapi_tpu.serving.adapter_store import (
                AdapterPeer, AdapterSlots, AdapterStore,
            )

            self.adapters = AdapterSlots(self, int(adapter_slots))
            # Host tier defaults to 256 MiB — hundreds of rank-8/16
            # adapters for the model sizes this repo serves; the flag
            # overrides for bigger fleets.
            self.adapter_store = AdapterStore(
                int(adapter_store_bytes) or (1 << 28),
                disk_dir=adapter_disk_dir,
            )
            self.adapter_peer = AdapterPeer(self)
        # Page-native prefill (r10): bucket prefill and admission write
        # K/V straight into pool pages through the page table — the
        # contiguous-then-adopt copy (one full extra write of
        # everything prefill just produced) drops to exactly zero
        # bytes. False keeps the r09 adopt path (legacy), which is
        # what makes the `generate.prefill_adopt_bytes` gauge a live
        # comparison, not a dead assertion. Contiguous engines ignore
        # both flags.
        self.prefill_page_native = bool(prefill_page_native)
        # Chunked-prefill interleaving (r10): a long-prompt joiner's
        # fixed-width prefill chunks become schedulable units
        # interleaved one-for-one with the running batch's decode
        # chunks, so in-flight streams stall by at most ONE
        # prefill-chunk dispatch instead of the whole prompt
        # (paged engines only — activation is a page-table install).
        self.prefill_interleave = bool(prefill_interleave)
        # KV-cache storage format and decode-attention impl, owned by
        # the MODEL (program factories key on them); mirrored here for
        # /metrics.
        self.kv_quant = getattr(model, "kv_quant", "none")
        self.decode_attn_impl = getattr(model, "decode_attn_impl", "einsum")
        self._kv_slot_bytes: int | None = None
        self._decode_step_bytes: int | None = None
        # Batcher state (started by the app's startup hook).
        self._queue: asyncio.Queue | None = None
        self._task: asyncio.Task | None = None
        # Cross-thread collector wake: set from the scheduler's
        # dispatch thread (lane retired, request deferred) via
        # ``_wake_collector`` so staged work re-enters dispatch
        # without waiting out the poll interval.
        self._kick: asyncio.Event | None = None
        self._aloop: asyncio.AbstractEventLoop | None = None
        # Continuous-batching handoff: requests the collector has
        # popped while a batch is RUNNING, waiting to be admitted at a
        # chunk boundary (decode thread) or swept into the next batch
        # (collector, after the running one ends).
        import threading

        self._admit: list = []
        # Staged requests the RUNNING batch can never take (token
        # budget exceeds its remaining cache): handed back here for
        # the collector's next batch, so they don't camp in _admit
        # blocking compaction and queue draining.
        self._deferred: list = []
        self._alock = threading.Lock()
        # Admission is gated to warmed shapes once a full warmup ran,
        # so a joiner can never stall the running batch on an XLA
        # compile; before/without full warmup (tests, CPU), admission
        # is unrestricted. The expensive compile (joiner prefill) is
        # keyed on the prompt bucket alone; scatter/growth gathers are
        # trivial and may compile on demand when dispatch RTT is low.
        self._strict_admit = False
        self._warmed_joiner: set = set()
        self._warmed_scatter: set = set()
        self._warmed_growth: set = set()
        self._admit_eager_override: bool | None = None
        # Shared-prefix KV caching: ALL prefix state (entry LRU, build
        # events, widened-KV cache, hit/miss counters) lives in the
        # PrefixCache module; the engine only routes calls to it.
        self.prefix = PrefixCache(self)
        # Stats (read by /metrics and the coalescing test).
        self.requests = 0
        self.batch_calls = 0
        self.chunk_calls = 0
        self.rejected = 0
        self.cancelled_batches = 0
        self.compactions = 0
        self.admitted = 0
        self.growths = 0
        self.prefill_chunks = 0
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.fused_calls = 0
        # Page-native prefill + interleaving observability (r10). All
        # byte counters are exact dtype/shape arithmetic
        # (ops/quant.kv_tree_bytes), never wall-clock:
        # - prefill_adopt_bytes: bytes the legacy contiguous-then-
        #   adopt formation/admission path re-copied into pool pages
        #   (MUST read 0 on the page-native path).
        # - prefix_adopt_bytes: once-per-entry-lifetime prefix KV
        #   adoption (cache residency, not a per-batch copy).
        # - kv_prefix_copy_fallback: stacked (cross-prefix) groups
        #   that could NOT share pages because a region shift was not
        #   page-aligned (fell back to r09 copy semantics).
        # - interleaved_prefills / interleave_max_stall /
        #   prefill_chunk_queue_depth: chunked-prefill interleaving —
        #   max_stall is the largest run of consecutive prefill-chunk
        #   dispatches while live decode rows waited (the bound the
        #   design pins at 1).
        # - spec_realign_table_ops / spec_realign_repacks: paged
        #   batched-speculation handoffs realigned as a host table
        #   shift vs the loud device row-gather fallback.
        self.prefill_adopt_bytes = 0
        self.prefix_adopt_bytes = 0
        self.kv_prefix_copy_fallback = 0
        self.interleaved_prefills = 0
        self.interleave_max_stall = 0
        self.prefill_chunk_queue_depth = 0
        self.spec_realign_table_ops = 0
        self.spec_realign_repacks = 0
        # Robustness layer (r12). Deadlines: every request may carry a
        # wall-clock budget (``deadline_ms``; this engine default
        # applies when the request names none); each dispatch boundary
        # checks expiry via ``_expire_if_due`` and cancels the row the
        # way client disconnects already do, after a terminal
        # DeadlineExceeded frame. ``None`` default = no deadline — the
        # pre-r12 stream bytes, untouched.
        self.default_deadline_ms: float | None = None
        # SLO-aware admission control: before enqueueing, a deadlined
        # request's feasibility is estimated from the LatencyStats p95
        # reservoirs and the current queue depth
        # (``admission_estimate_ms``); infeasible requests shed 503 +
        # computed retry-after at the door instead of occupying a slot
        # and timing out later. Sustained queue pressure engages the
        # counted brownout ladder (clamp n_new to the default tier,
        # suppress speculation, evict idle prefix page sets) before
        # shedding.
        self.admission_control = True
        # Graceful drain: ``drain()`` flips this, sheds new admissions
        # (503 + retry-after), lets in-flight streams finish inside
        # the budget, then cancels leftovers with DrainCancelled
        # terminal frames.
        self.draining = False
        # The reqs list of the batch the decode thread is currently
        # running (None between batches) — what drain() must wait out
        # or cancel. Written by the decode thread, read from the loop.
        self._running: list | None = None
        # The reqs list the collector has claimed but not yet finished
        # running (None otherwise) — the straggler-collection window
        # plus the executor handoff, during which those requests are
        # in neither the queue, the staging lists, nor _running.
        # drain() must treat this window as in-flight work (idle
        # check + budget-exhausted cancellation) or it can declare
        # the engine idle with a batch still forming.
        self._forming: list | None = None
        # The collector's window-incompatible leftovers, kept between
        # its iterations: claimed off the queue but in neither the
        # staging lists nor a formed batch. An engine attribute — not
        # a collector local — so drain()'s budget-exhausted sweep can
        # deliver their DrainCancelled frames too.
        self._carry: list = []
        # Robustness counters (exported on /metrics).
        self.shed_queue_full = 0
        self.shed_deadline_infeasible = 0
        self.shed_draining = 0
        self.deadline_expired_queued = 0
        self.deadline_expired_prefill = 0
        self.deadline_expired_decode = 0
        self.brownout_spec_suppressed = 0
        self.brownout_tokens_clamped = 0
        # Mixed-tenant batching observability: batch runs dispatched
        # with the single-tenant grouped fast path (one x @ A @ B per
        # target) vs the gathered BGMV path (per-row slot gather).
        # Counted once per batch run, like fused_calls — never per
        # chunk. Both 0 with adapter_slots off.
        self.adapter_grouped_batches = 0
        self.adapter_gathered_batches = 0
        # Continuous-batching scheduler v2 (r15, serving/scheduler.py;
        # DEFAULT-ON since r20 — the one execution model): one
        # typed-unit queue (prefill chunk / decode chunk / spec round
        # / admission / compaction / score) across up to
        # ``sched_max_batches`` CONCURRENT BatchRuns, SLO-prioritized
        # by WEIGHTED deadline slack (per-tenant weights from the
        # ledger below) with TTFT/ITL targets fed from the
        # LatencyStats reservoirs. ``sched_max_batches=1`` pins ONE
        # lane — the legacy serial semantics (one live batch +
        # in-lane admission) on the same machinery (the
        # ``--no-scheduler`` flag was retired in r22). The scheduler
        # object itself is created by start() and torn down by stop().
        self.sched_max_batches = max(1, int(sched_max_batches))
        self.sched = None
        # Per-tenant quotas/weights/pressure (serving/registry.py
        # TenantLedger, r22), attached by the app/__main__ when any
        # tenant flag is configured. None = single-tenant semantics,
        # bit for bit.
        self.tenants = None
        # Per-unit-type dispatch counters + queue observability
        # (exported on /metrics as sched_*).
        self.sched_units_prefill = 0
        self.sched_units_decode = 0
        self.sched_units_spec = 0
        self.sched_units_admit = 0
        self.sched_units_compact = 0
        # Scoring batches from co-resident ScorePaths dispatched as
        # typed units between this engine's decode chunks (r22).
        self.sched_units_score = 0
        self.sched_deadline_preempts = 0
        self.sched_pages_deferred = 0
        # Group held back because its adapters could not all claim a
        # device slot right now (free + hold-free-evictable < needed)
        # — the adapter-slot term of the same reservation gate.
        self.sched_adapters_deferred = 0
        # Per-tenant terms of the same gate (r22): the POOL had room
        # but the group's TENANT was at its page/slot quota. The
        # ledger counts the same deferral per tenant.
        self.sched_tenant_pages_deferred = 0
        self.sched_tenant_adapters_deferred = 0
        # Tenant-scoped brownout rung (engages before the fleet-wide
        # ladder): submits clamped because ONE tenant's live depth
        # crossed its share of the queue.
        self.brownout_tenant_clamped = 0
        self.sched_batches_live_max = 0
        # Largest run of consecutive units ONE lane dispatched while
        # another lane was live — the cross-lane head-of-line bound
        # (r10's interleave_max_stall generalized across batches).
        # With fused-chunk widths folded into units, the design pins
        # a concurrent lane's stall behind a fused batch at ONE
        # fused-chunk dispatch; always counters, never wall-clock.
        self.sched_lane_stall_max = 0
        # Router backpressure (r15 satellite): the fleet backlog the
        # router observed when it forwarded the last request here
        # (x-mlapi-router-depth, EXCLUDING this replica's own share).
        # Feeds admission_estimate_ms and the brownout ladder so a
        # replica sheds/degrades on FLEET pressure, not just its own
        # queue; stays 0 without a router in front.
        self.router_queue_depth = 0
        # TTFT / inter-token reservoirs, recorded at the push seam.
        from mlapi_tpu.serving.requests import LatencyStats

        self.latency = LatencyStats()
        # (chunk width, table width) pairs whose paged chunked-extend
        # program is compiled — strict mode gates interleaved
        # admission on this set.
        self._warmed_extend: set = set()
        # Host-loop speculation phase: rounds + warmed-shape state
        # live in serving/spec_phase.py.
        self.spec = SpecPhase(self)
        # Batch-1 fused fast path: eligibility, dispatch, and warmed
        # state live in serving/fused_single.py.
        self.fused = FusedSinglePath(self)
        # Batch-resize (compaction) shapes proven compiled — in
        # strict non-eager mode a resize outside this set is skipped
        # (decode stays at full width) rather than compiled mid-batch.
        self._warmed_shrink: set = set()

    @property
    def queue_depth(self) -> int:
        base = self._queue.qsize() if self._queue is not None else 0
        # Scheduler mode: groups the collector has formed but the
        # scheduler has not yet laned are still WAITING work — without
        # this term they would vanish from backpressure, admission
        # estimates, and the router's scrape the moment the collector
        # popped them (/healthz queue_depth must reflect the typed-unit
        # queue, not just the submit queue).
        sched = self.sched.backlog if self.sched is not None else 0
        with self._alock:
            return base + len(self._admit) + len(self._deferred) + sched

    @property
    def sched_queue_depth(self) -> int:
        """Typed-unit queue depth: one runnable unit per live lane
        plus one formation unit per pending group (0, scheduler
        off)."""
        return self.sched.queue_depth if self.sched is not None else 0

    @property
    def sched_batches_live(self) -> int:
        return self.sched.batches_live if self.sched is not None else 0

    # -- robustness: deadlines, admission control, brownout ---------------

    def _expire_if_due(self, r, stage: str) -> bool:
        """THE deadline check, called at every dispatch boundary the
        scheduler owns (collector pop, formation, admission staging,
        prefill chunks, decode chunks, spec rounds). An expired
        request gets its terminal :class:`DeadlineExceeded` frame and
        is cancelled exactly the way a client disconnect is — the
        existing cancellation path frees the decode row and releases
        its pages through the refcount machinery. Returns True when
        this call expired the request. Deadline-less requests cost one
        attribute read."""
        d = getattr(r, "deadline", None)
        if d is None or r.cancelled or time.perf_counter() < d:
            return False
        counter = f"deadline_expired_{stage}"
        setattr(self, counter, getattr(self, counter) + 1)
        try:
            r.push(DeadlineExceeded(stage))
        except Exception:  # a dead consumer loop must not mask others
            pass
        r.cancel()
        return True

    def admission_estimate_ms(self) -> float:
        """Estimated queue-wait + TTFT for a request submitted NOW,
        from the r10 LatencyStats p95 reservoirs and the live queue
        depths: each ``max_batch``-worth of backlog ahead costs about
        one batch turnaround (p95 TTFT + the default token budget at
        the p95 inter-token rate), and the request then pays its own
        p95 TTFT. Returns 0 until traffic has populated the
        reservoirs — a cold server never sheds on a guess. Running as
        a router replica, the router-scraped fleet backlog
        (``router_queue_depth`` — everyone ELSE's queued work) rides
        into the backlog term: affinity means a re-arriving prefix
        cannot go elsewhere, so fleet pressure is this replica's
        future queue wait too (ROADMAP item-3 remainder: router
        backpressure feeding the item-1 scheduler)."""
        s = self.latency.summary()
        ttft = s["ttft_p95_ms"] or 0.0
        itl = s["intertoken_p50_ms"] or 0.0
        batch_ms = ttft + self.default_max_new_tokens * itl
        backlog = (
            self.queue_depth + self.prefill_chunk_queue_depth
            + self.router_queue_depth
        ) / max(1, self.max_batch)
        return backlog * batch_ms + ttft

    def _brownout_level(self) -> int:
        """Queue pressure → brownout rung: 0 normal, 1 at >= 50% of
        ``max_queue`` (clamp token budgets, suppress speculation), 2
        at >= 75% (additionally evict idle prefix page sets). The
        levers degrade work per request BEFORE the queue-full shed
        fires — Snap ML's degrade-per-tier, not fall-over-globally.
        The router-scraped fleet backlog counts as pressure too (at
        most one local queue's worth, so a huge fleet spike engages
        the ladder without instantly pinning every replica at rung
        2)."""
        if not self.admission_control:
            return 0
        q = self.queue_depth + min(self.router_queue_depth, self.max_queue)
        if q * 4 >= self.max_queue * 3:
            return 2
        if q * 2 >= self.max_queue:
            return 1
        return 0

    async def drain(self, timeout_s: float | None = None) -> None:
        """Graceful drain: stop admitting (submit sheds 503 +
        retry-after, ``/healthz`` reports draining), let in-flight
        streams run to completion inside ``timeout_s``, then cancel
        whatever remains with proper :class:`DrainCancelled` terminal
        frames so no consumer ever hangs on a half-dead stream.
        Idempotent; ``stop()`` afterwards is still the caller's job
        (the app's shutdown hook does both)."""
        self.draining = True
        if timeout_s is None:
            timeout_s = getattr(self, "drain_timeout_s", 10.0)
        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(0.0, float(timeout_s))
        while loop.time() < deadline:
            with self._alock:
                backlog = len(self._admit) + len(self._deferred)
            queued = (
                self._queue is not None and not self._queue.empty()
            )
            if (
                not queued
                and not backlog
                and not self._carry
                and self._running is None
                and self._forming is None
                and (self.sched is None or self.sched.idle)
            ):
                return
            await asyncio.sleep(0.05)
        # Budget exhausted: terminal frames for everything still in
        # flight or queued, then cancel the rows (pages come back via
        # the cancellation path; stop() handles the collector task).
        leftovers: list = []
        if self._queue is not None:
            while not self._queue.empty():
                leftovers.append(self._queue.get_nowait())
        with self._alock:
            leftovers += self._admit + self._deferred
            self._admit.clear()
            self._deferred.clear()
        # The collector's carry list: claimed off the queue but in
        # neither the queue, the staging lists, nor a formed batch.
        # Cancel-only (no clear) — the collector owns the list and
        # drops cancelled rows at its next formation.
        leftovers += list(self._carry)
        if self.sched is not None:
            # The typed-unit queue: pending groups are popped (they
            # will never be laned), live lanes' requests are
            # cancel-only — each lane notices at its next unit
            # boundary exactly like a disconnect and releases its
            # pages on the way out.
            leftovers += self.sched.sweep_requests()
        running = self._running
        if running is not None:
            leftovers += list(running)
        forming = self._forming
        if forming is not None:
            # May overlap ``running`` (the collector keeps its claim
            # until the batch finishes); the ``cancelled`` guard below
            # makes the second visit a no-op.
            leftovers += list(forming)
        for r in leftovers:
            if getattr(r, "cancelled", False):
                continue
            try:
                r.push(DrainCancelled())
            except Exception:
                pass
            r.cancel()
        # Give the decode thread a moment to notice the cancels and
        # finish the batch — bounded, never a hang.
        grace = loop.time() + 2.0
        while (
            self._running is not None
            or (self.sched is not None and not self.sched.idle)
        ) and loop.time() < grace:
            await asyncio.sleep(0.05)

    @property
    def _admit_eager(self) -> bool:
        """May the admission path compile a TRIVIAL program (KV
        scatter, growth gather) on demand? Yes when the measured
        dispatch round trip is low (sub-second compile, nobody
        notices); no when it is high, where even a trivial compile
        stalls the running batch — there, only pre-warmed shapes are
        admitted."""
        if self._admit_eager_override is not None:
            return self._admit_eager_override
        self._admit_eager_override = _dispatch_rtt_ms() < 15.0
        return self._admit_eager_override

    # Shared surface with the classification engines (healthz, app).
    @property
    def vocab(self):
        from mlapi_tpu.utils.vocab import LabelVocab

        return LabelVocab(())  # no label space; output is text

    # -- shapes ------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        i = bisect.bisect_left(self.prompt_buckets, n)
        return self.prompt_buckets[min(i, len(self.prompt_buckets) - 1)]

    @property
    def default_tier(self) -> int:
        """The power-of-two (of ``chunk``) tier covering the default
        token budget — the floor every warm grid and the fused ladder
        share (ONE definition; four copies of this loop had to agree
        before it existed)."""
        tier = self.chunk
        while tier < self.default_max_new_tokens:
            tier *= 2
        return tier

    def _cache_len(self, bucket: int, n_new: int) -> int:
        """Static KV-cache length for a batch, quantized so the
        program count stays logarithmic: new-token room is at least
        the default (every ``n_new <= default`` request shares ONE
        warmed shape) and beyond that rounds up to power-of-two
        multiples of ``chunk``; clamped to the model's window. A
        slightly roomier cache costs a few KB of HBM and zero decode
        steps (the loop stops at the requested token count) — compile
        ambushes on the request path cost p99."""
        want = max(n_new, self.default_max_new_tokens)
        tier = self.chunk
        while tier < want:
            tier *= 2
        return min(self.model.max_positions, bucket + tier)

    def kv_cache_slot_bytes(self) -> int:
        """DETERMINISTIC per-slot KV-cache STORAGE bytes at the
        default bucket/tier config (largest prompt bucket, default
        token tier): ``addressable_shards[...].data.nbytes`` summed
        over a batch-1 cache — the committed-number discipline the
        FSDP PR set (byte counts are exact where this box's
        wall-clock swings ±25-30%). One continuous-batching slot, one
        prefix-cache entry of this tier, and one spec mirror row each
        cost this much device HBM; ``kv_quant="int8"`` roughly
        halving it is the storage half of the int8-KV claim, reported
        on ``/metrics``. The READ half — whether decode traffic
        actually shrinks — depends on the decode impl too: see
        :meth:`decode_bytes_per_step`."""
        if self._kv_slot_bytes is None:
            from mlapi_tpu.parallel.layout import bytes_per_device

            total = self._cache_len(
                self.prompt_buckets[-1], self.default_max_new_tokens
            )
            cache = self.model.init_cache(1, total)
            jax.block_until_ready(cache)
            self._kv_slot_bytes = int(bytes_per_device(cache))
        return self._kv_slot_bytes

    def decode_bytes_per_step(self) -> int:
        """Modeled HBM bytes ONE decode step's attention read moves
        per slot at the default bucket/tier config — the number that
        makes the int8 READ saving observable in production
        (``/metrics`` gauge ``generate.decode_bytes_per_step``). Pure
        host arithmetic over abstract cache shapes (``jax.eval_shape``
        — no device allocation), so it is exact and deterministic. The model, per (cache format,
        ``decode_attn_impl``):

        - **flash**: the kernel streams the STORED tiles — int8
          payload + f32 scales, or the compute-dtype arrays, at the
          cache's native KV-head width (queries group in-register) —
          so the read is exactly the storage bytes.
        - **einsum**: the einsum operand is the full-precision cache
          at QUERY-head width — ``kv_cache_kv`` dequantizes at the
          read seam and GQA models broadcast KV heads to query heads
          (``_repeat_kv``), both materialized between the seam and
          the einsum. ONE consistent accounting: whenever the operand
          differs from storage (by format or head width), the
          materializing producer reads the stored cache first and the
          einsum then reads the operand — storage PLUS operand bytes;
          when they coincide (MHA, ``kv_quant="none"``) there is one
          read of the stored cache. These lines are WHY the flash
          kernel exists: it is the only path where the storage format
          (and the GQA grouping) reaches the read.

        Computed once per engine (it is constant for the engine's
        lifetime) — /metrics scrapes read the cached value.
        """
        if self._decode_step_bytes is not None:
            return self._decode_step_bytes
        total = self._cache_len(
            self.prompt_buckets[-1], self.default_max_new_tokens
        )
        abstract = jax.eval_shape(lambda: self.model.init_cache(1, total))
        stored = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(abstract)
        )
        if self.decode_attn_impl == "flash":
            self._decode_step_bytes = stored
            return stored
        # The einsum operand: full-precision payload at query-head
        # width (cache payloads store KV heads; GQA's broadcast
        # multiplies by the group factor).
        cdt = jnp.dtype(getattr(self.model, "compute_dtype", "float32"))
        heads = int(getattr(self.model, "num_heads", 0))
        full = 0
        for layer in abstract.values():
            for name in ("k", "v", "k_q", "v_q"):
                if name not in layer:
                    continue
                leaf = layer[name]
                group = max(1, heads // leaf.shape[2]) if heads else 1
                full += int(np.prod(leaf.shape)) * group * cdt.itemsize
        # Operand == storage (MHA, no format): one read. Otherwise
        # the producer reads storage and the einsum reads the
        # materialized operand.
        self._decode_step_bytes = (
            full if full == stored else stored + full
        )
        return self._decode_step_bytes

    def extend_bytes_per_chunk(self) -> int:
        """Modeled HBM bytes ONE multi-token extend chunk's attention
        read moves per slot at the default bucket/tier config —
        ``decode_bytes_per_step``'s accounting applied to the OTHER
        half of the token pipeline (``/metrics`` gauge
        ``generate.extend_bytes_per_chunk``). The read model is
        EXACTLY the decode one, by construction: an extend dispatch
        streams the same stored cache (flash — the U-row Q tile rides
        into each program, so a tile is still read once) or
        materializes the same full-precision query-head-width operand
        (einsum — ``kv_cache_kv``'s dequant and the GQA broadcast
        don't depend on the query width), so the int8 flash saving
        2D/(D+4) (1.94x at bf16 D=128) carries over verbatim. What
        differs is AMORTIZATION: a chunk pays this read once for its
        whole U-token span, where the decode loop pays
        ``decode_bytes_per_step`` per token — which is why chunked
        prefill, admission mini-prefills and speculative verify were
        worth making kernel-native at all (every server token now
        reads the cache at its stored byte format). Same
        ``jax.eval_shape`` host arithmetic: exact, deterministic, no
        device work."""
        return self.decode_bytes_per_step()

    # -- paged-pool accounting (state lives in serving/paged_pool.py) -----
    @property
    def kv_pages_total(self) -> int:
        return self.pool.pages_total if self.pool is not None else 0

    @property
    def kv_pages_in_use(self) -> int:
        return self.pool.pages_in_use if self.pool is not None else 0

    @property
    def kv_pages_shared(self) -> int:
        return self.pool.pages_shared if self.pool is not None else 0

    @property
    def kv_page_utilization(self) -> float:
        return self.pool.utilization if self.pool is not None else 0.0

    def kv_page_bytes(self) -> int:
        """Exact device bytes of ONE page across every layer (pure
        dtype/shape arithmetic) — the unit of the paged capacity
        model: a sequence of ``t`` cached tokens holds
        ``ceil(t / page)`` pages, so its padding waste is bounded by
        one page instead of (tier - t) slots."""
        return self.pool.page_bytes if self.pool is not None else 0

    @property
    def faults_injected(self) -> int:
        """Armed-fault fires since the harness was last armed (0 when
        disarmed) — state lives in ``serving/faults.py``."""
        return faults.injected_count()

    # -- host-tier accounting (state lives in serving/kv_tier.py) ---------
    # All byte counters are exact dtype/shape arithmetic (the
    # ``ops/quant.kv_tree_bytes`` closed form applied per blob), never
    # wall-clock; every gauge reads 0 with the tier disabled.
    @property
    def kv_prefix_restore_hits(self) -> int:
        """Blob applications: entry rebuilds + pool-page restores —
        each one a prefill (or adopt) the tier made unnecessary."""
        return self.kv_tier.restore_hits if self.kv_tier else 0

    @property
    def kv_prefix_restore_misses(self) -> int:
        return self.kv_tier.restore_misses if self.kv_tier else 0

    @property
    def kv_prefix_restore_bytes(self) -> int:
        return self.kv_tier.restore_bytes if self.kv_tier else 0

    @property
    def kv_prefix_restore_failures(self) -> int:
        return self.kv_tier.restore_failures if self.kv_tier else 0

    @property
    def kv_prefix_spill_count(self) -> int:
        return self.kv_tier.spill_count if self.kv_tier else 0

    @property
    def kv_prefix_spill_bytes(self) -> int:
        return self.kv_tier.spill_bytes if self.kv_tier else 0

    @property
    def kv_prefix_spill_failures(self) -> int:
        return self.kv_tier.spill_failures if self.kv_tier else 0

    @property
    def kv_tier_bytes_in_use(self) -> int:
        return self.kv_tier.bytes_in_use if self.kv_tier else 0

    @property
    def kv_tier_entries(self) -> int:
        return self.kv_tier.entries if self.kv_tier else 0

    @property
    def kv_tier_evictions(self) -> int:
        return self.kv_tier.evictions if self.kv_tier else 0

    # -- peer-fetch accounting (state lives in serving/kv_peer.py) --------
    # Byte counters are exact wire-payload arithmetic (every blob's
    # ``num_pages x kv_page_bytes`` closed form), never wall-clock;
    # all zero with --kv-peer-fetch off.
    @property
    def kv_peer_fetch_hits(self) -> int:
        """Peer blobs APPLIED (entry rebuilt from the wire) — each
        one a cold prefill the fleet's warmth made unnecessary."""
        return self.kv_peer.fetch_hits if self.kv_peer else 0

    @property
    def kv_peer_fetch_misses(self) -> int:
        return self.kv_peer.fetch_misses if self.kv_peer else 0

    @property
    def kv_peer_fetch_bytes(self) -> int:
        return self.kv_peer.fetch_bytes if self.kv_peer else 0

    @property
    def kv_peer_fetch_failures(self) -> int:
        return self.kv_peer.fetch_failures if self.kv_peer else 0

    @property
    def kv_peer_serve_count(self) -> int:
        return self.kv_peer.serve_count if self.kv_peer else 0

    @property
    def kv_peer_serve_bytes(self) -> int:
        return self.kv_peer.serve_bytes if self.kv_peer else 0

    # -- adapter accounting (state lives in serving/adapter_store.py).
    # Byte counters are exact wire/dtype-shape arithmetic (header
    # nbytes, ``slot_bytes`` closed forms), never wall-clock; all zero
    # with adapter_slots off.
    @property
    def adapter_slots_total(self) -> int:
        return self.adapters.slots_total if self.adapters else 0

    @property
    def adapter_slots_in_use(self) -> int:
        return self.adapters.slots_in_use if self.adapters else 0

    @property
    def adapter_evictions(self) -> int:
        return self.adapters.evictions if self.adapters else 0

    @property
    def adapter_installs(self) -> int:
        return self.adapters.installs if self.adapters else 0

    @property
    def adapter_slot_bytes(self) -> int:
        """Device bytes ONE resident adapter costs (per-target
        ``a [d_in, r] + b [r, d_out]`` rows at the base kernel dtype):
        the HBM-amortization claim is asserted as ``base params bytes
        + N x adapter_slot_bytes`` for N resident tenants. 0 until the
        first install fixes the engine-wide rank."""
        return self.adapters.slot_bytes() if self.adapters else 0

    @property
    def adapter_resident_bytes(self) -> int:
        """The closed-form HBM total the amortization claim pins:
        base parameter bytes + slots_in_use x adapter_slot_bytes."""
        if self.adapters is None:
            return 0
        base = sum(
            v.size * v.dtype.itemsize
            for v in jax.tree.leaves(self.params)
            if hasattr(v, "dtype")
        )
        return base + self.adapters.slots_in_use * (
            self.adapters.slot_bytes()
        )

    @property
    def adapter_fetch_hits(self) -> int:
        """Peer adapter blobs fetched AND stored — each one a tenant
        onboarded without its weights riding the client request."""
        return self.adapter_peer.fetch_hits if self.adapter_peer else 0

    @property
    def adapter_fetch_misses(self) -> int:
        return self.adapter_peer.fetch_misses if self.adapter_peer else 0

    @property
    def adapter_fetch_bytes(self) -> int:
        return self.adapter_peer.fetch_bytes if self.adapter_peer else 0

    @property
    def adapter_fetch_failures(self) -> int:
        return self.adapter_peer.fetch_failures if self.adapter_peer else 0

    @property
    def adapter_serve_count(self) -> int:
        return self.adapter_peer.serve_count if self.adapter_peer else 0

    @property
    def adapter_serve_bytes(self) -> int:
        return self.adapter_peer.serve_bytes if self.adapter_peer else 0

    @property
    def adapter_store_bytes_in_use(self) -> int:
        return self.adapter_store.bytes_in_use if self.adapter_store else 0

    @property
    def adapter_store_entries(self) -> int:
        return self.adapter_store.entries if self.adapter_store else 0

    @property
    def adapter_store_evictions(self) -> int:
        return self.adapter_store.evictions if self.adapter_store else 0

    def register_adapter(self, aid: str, payload: dict) -> int:
        """Install a pre-scaled adapter payload (``{layer: {target:
        {a, b}}}``, ``b`` already carrying alpha/rank — see
        ``models/lora.export_adapter``) into the HOST store under
        ``aid``; device slots install lazily at first request. The
        CLI's ``--adapter id=path`` and tests load through here.
        Returns the stored wire-image byte count."""
        from mlapi_tpu.serving import adapter_store as _as

        if self.adapter_store is None:
            raise ValueError(
                "engine built without adapter slots "
                "(--adapter-slots 0): cannot register adapters"
            )
        if not _as.ADAPTER_ID_RE.match(aid or ""):
            raise ValueError(f"bad adapter id {aid!r}")
        _as.adapter_rank(payload)  # loud on ragged/empty payloads
        nbytes = self.adapter_store.put(aid, payload)
        return nbytes

    # -- disaggregation accounting (state lives in serving/kv_peer.py's
    # KVPush) — byte counters are exact payload arithmetic (each
    # chunk's ``span × per-slot kv bytes`` closed form), never
    # wall-clock; everything 0 on a mixed replica.
    @property
    def kv_push_sent(self) -> int:
        return self.kv_push.push_sent if self.kv_push else 0

    @property
    def kv_push_send_failures(self) -> int:
        return self.kv_push.push_send_failures if self.kv_push else 0

    @property
    def kv_push_bytes_sent(self) -> int:
        return self.kv_push.push_bytes_sent if self.kv_push else 0

    @property
    def kv_push_recv(self) -> int:
        return self.kv_push.push_recv if self.kv_push else 0

    @property
    def kv_push_recv_failures(self) -> int:
        return self.kv_push.push_recv_failures if self.kv_push else 0

    @property
    def kv_push_bytes_recv(self) -> int:
        return self.kv_push.push_bytes_recv if self.kv_push else 0

    @property
    def kv_push_applied(self) -> int:
        """Pushed transfers installed as live decode rows — moving
        while ``prefix_builds`` AND ``prefill_chunks`` stay flat IS
        the zero-decode-side-prefill claim."""
        return self.kv_push.push_applied if self.kv_push else 0

    @property
    def kv_push_bytes_applied(self) -> int:
        return self.kv_push.push_bytes_applied if self.kv_push else 0

    @property
    def kv_push_fallbacks(self) -> int:
        return self.kv_push.push_fallbacks if self.kv_push else 0

    # -- prefix-cache counters (state lives in serving/prefix.py) ---------
    @property
    def prefix_hits(self) -> int:
        return self.prefix.hits

    @property
    def prefix_misses(self) -> int:
        return self.prefix.misses

    @property
    def prefix_fallbacks(self) -> int:
        return self.prefix.fallbacks

    @property
    def prefix_builds(self) -> int:
        """Actual cold prefills (``_build`` ran): the counter the
        router's prefix-affinity claim is asserted against — affinity
        keeps repeated prefixes on one replica, so the fleet-wide sum
        of ``builds`` stays at one per distinct prefix instead of one
        per (prefix, replica) pair. Tier restores move ``misses`` but
        never this."""
        return self.prefix.builds

    def _resolve_adapter(self, aid: str) -> None:
        """Resolve an adapter id into the HOST store (encode executor
        thread — never the dispatch thread): already registered, or
        already resident on device, or fetched from the router-hinted
        warm peer and staged. Raises ``AdapterUnavailable`` (mapped to
        404) when this replica cannot serve the tenant — feature off,
        malformed id, or no blob anywhere — BEFORE the request ever
        queues, so a mistyped tenant id costs a hash lookup, not a
        batch slot."""
        from mlapi_tpu.serving.adapter_store import (
            ADAPTER_ID_RE, AdapterUnavailable,
        )

        if self.adapters is None:
            raise AdapterUnavailable(
                "this replica serves no adapters (--adapter-slots 0)"
            )
        if not isinstance(aid, str) or not ADAPTER_ID_RE.match(aid):
            raise AdapterUnavailable(f"malformed adapter id {aid!r:.80}")
        if self.adapters.resident(aid) or self.adapter_store.has(aid):
            return
        got = self.adapter_peer.fetch(aid) if self.adapter_peer else None
        if got is not None:
            self.adapter_store.put(aid, got[0])
            return
        raise AdapterUnavailable(
            f"adapter {aid!r} is not registered on this replica"
        )

    def _encode(self, text: str, n_new: int, temperature: float, seed: int,
                loop, top_k: int = 0, top_p: float = 1.0,
                prefix: str | None = None,
                stream: bool = False,
                deadline_ms: float | None = None,
                push_to=None, kv_xfer: str | None = None,
                adapter: str | None = None) -> GenRequest:
        entry = None
        raw = None
        if adapter is not None:
            self._resolve_adapter(adapter)
            if prefix:
                # The prefix cache holds BASE-model KV; reusing it
                # under a tenant's adapted weights would condition the
                # suffix on the wrong model. Fold the prefix into the
                # prompt instead — identical semantics, zero cache
                # pollution — and count it where the cache's other
                # declined reuses land.
                self.prefix.count_fallback()
                text = prefix + text
                prefix = None
        if prefix:
            raw = self.tokenizer.token_ids(text)
            if not raw:
                # An empty suffix would condition on a fabricated pad
                # placeholder behind the prefix — serve the prefix
                # alone through the plain path instead (identical
                # output by the pinned equivalence).
                self.prefix.count_fallback()
                text = prefix + text
                raw = None  # re-tokenize the concatenation below
            else:
                # The suffix runs as ONE fused block forward against
                # the cached prefix KV (extend_core), so the KV path
                # wins for every nonempty prefix — no length
                # heuristic needed.
                entry = self.prefix.entry(prefix)
        p_len = entry.bucket if entry else 0
        limit = self.model.max_positions - n_new - p_len
        if limit <= 0:
            raise ValueError(
                f"max_new_tokens={n_new}"
                + (f" plus a {p_len}-slot prefix" if p_len else "")
                + f" leaves no room for a prompt "
                  f"(max_positions={self.model.max_positions})"
            )
        if raw is None:
            raw = self.tokenizer.token_ids(text)
        if entry is not None and len(raw) > limit:
            # The plain path documents left-truncation of oversized
            # prompts; on the KV path that would truncate the SUFFIX
            # while keeping the whole prefix — silently different
            # conditioning than the concatenated prompt. Refuse loud.
            raise ValueError(
                f"prefix + text + max_new_tokens exceed the model "
                f"window (suffix is {len(raw)} tokens, {limit} fit "
                f"behind the {p_len}-slot prefix)"
            )
        raw = raw[-limit:] if raw else [self.tokenizer.pad_id]
        # Left-pad to a bucket so common prompt lengths never
        # recompile; pads are masked out by the model (n_pad), so the
        # answer is identical whichever bucket the prompt lands in. A
        # prompt longer than the largest bucket rounds up to a
        # multiple of it and prefills in fixed-width chunks (ONE
        # compiled program per cache tier, any length — see
        # ``extend_chunk_fn``); only when even that multiple exceeds
        # the window does it take its exact length (one-off compile)
        # rather than silent truncation.
        if len(raw) > self.prompt_buckets[-1]:
            cp = self.prompt_buckets[-1]
            bucket = -(-len(raw) // cp) * cp
            if bucket > limit:
                bucket = len(raw)
        else:
            bucket = self._bucket(len(raw))
        bucket = min(bucket, limit)
        row = np.full((bucket,), self.tokenizer.pad_id, np.int32)
        used = min(len(raw), bucket)
        row[-used:] = raw[-used:]
        pushed = None
        if kv_xfer is not None and self.kv_push is not None:
            # Decode-role arrival naming a pushed transfer: take the
            # assembled blob (encode executor thread — the host
            # concat runs here, never on the dispatch thread) and
            # validate its geometry against what THIS replica's
            # encode just produced. Anything short of an exact match
            # — incomplete/failed transfer, bucket/used drift across
            # configs — is a counted fallback to the cold prefill;
            # the stream still serves, just without the saved FLOPs.
            pushed = self.kv_push.take(kv_xfer)
            if pushed is not None and (
                pushed.bucket != bucket or pushed.used != used
                or entry is not None
            ):
                _log.debug(
                    "pushed transfer %s geometry drifted "
                    "(%d/%d vs local %d/%d); cold prefill",
                    kv_xfer, pushed.bucket, pushed.used, bucket, used,
                )
                pushed = None
            if pushed is None:
                self.kv_push.count_fallback()
        return GenRequest(
            row, used, n_new, temperature, seed, loop, top_k, top_p,
            prefix=entry, stream=stream, stats=self.latency,
            deadline_ms=deadline_ms, push_to=push_to, pushed=pushed,
            adapter=adapter,
        )

    # -- the batched decode (runs on a worker thread) ----------------------
    @staticmethod
    def _key_data(seed: int) -> np.ndarray:
        return np.asarray(jax.random.key_data(jax.random.key(seed)))

    def _pack_rows(self, reqs, bucket: int, b_pad: int):
        """Pack the per-row host mirrors for a batch: left-padded
        prompt rows plus the pad/sampling vectors, dummy rows (pad to
        ``b_pad``) fully masked. ONE definition shared by the chunked
        batch formation and the fused-batched fast path — the two
        paths' byte-identity contract rests on packing rows the same
        way. Returns ``(prompt, n_pad, temps, topk, topp, keys)``."""
        b = len(reqs)
        prompt = np.full((b_pad, bucket), self.tokenizer.pad_id, np.int32)
        n_pad = np.full((b_pad,), max(bucket - 1, 0), np.int32)
        temps = np.zeros((b_pad,), np.float32)
        topk = np.zeros((b_pad,), np.int32)
        topp = np.ones((b_pad,), np.float32)
        for i, r in enumerate(reqs):
            prompt[i, bucket - len(r.row):] = r.row
            n_pad[i] = bucket - r.used
            temps[i] = r.temperature
            topk[i] = r.top_k
            topp[i] = r.top_p
        keys = np.stack(
            [self._key_data(r.seed) for r in reqs]
            + [self._key_data(0)] * (b_pad - b)
        )
        return prompt, n_pad, temps, topk, topp, keys

    def _form_batch(self, reqs: list, admit: bool,
                    fused_ok: bool = True):
        """The formation preamble shared by ``_run_batch`` and the
        unit scheduler's lane start — ONE definition, because the
        serial/concurrent identity contract rests on both gating
        formation identically. Sweeps queue-expired requests
        (terminal frame, never a device dispatch) and returns the
        formed :class:`BatchRun` — or ``None`` when everyone expired.
        Requests whose deadline passed during the queue wait never
        reach the device; the sweep edits ``reqs`` in place
        (admission appends to this list object and error delivery
        iterates it). ``fused_ok=False`` pins the plain chunk width
        (warmup's chunked grid compiles those shapes deliberately);
        otherwise the fused-chunk width is decided per dispatch
        boundary inside the run (``serving/fused_single.py``)."""
        from mlapi_tpu.serving.batch_run import BatchRun

        alive = [
            r for r in reqs if not self._expire_if_due(r, "queued")
        ]
        if not alive:
            return None
        reqs[:] = alive
        self.batch_calls += 1
        return BatchRun(self, reqs, admit, fused_ok)

    def _run_batch(self, reqs: list, admit: bool = False,
                   fused_ok: bool = True) -> None:
        """Serve one coalesced batch through the continuous-batch
        lifecycle, which lives in ``serving/batch_run.py`` as
        :class:`BatchRun` (formation + prefill, speculative handoff,
        mid-batch admission, compaction, chained chunk decode at
        plain or fused-chunk widths — see that module's seam table).

        Error delivery stays HERE: admission appends joiners to
        ``reqs`` in place, so a mid-batch failure is delivered to
        every waiter, including requests admitted after formation.
        Each gets the exception object; a ``None`` sentinel marks
        normal completion (pushed by the lifecycle stages).
        """
        try:
            self._running = reqs
            run = self._form_batch(reqs, admit, fused_ok)
            if run is not None:
                run.run()
        except Exception as e:  # noqa: BLE001 — delivered to every waiter
            _log.error("generation batch of %d failed: %s", len(reqs), e)
            for r in reqs:
                try:
                    r.push(e)
                except Exception:  # a dead loop must not mask others
                    pass
        finally:
            self._running = None

    # -- asyncio batcher ---------------------------------------------------
    async def start(self) -> None:
        if self._task is None:
            self._queue = asyncio.Queue(maxsize=self.max_queue)
            self._kick = asyncio.Event()
            self._aloop = asyncio.get_running_loop()
            if self.sched is None:
                from mlapi_tpu.serving.scheduler import UnitScheduler

                self.sched = UnitScheduler(
                    self, max_batches=self.sched_max_batches
                )
            self._task = asyncio.create_task(
                self._collect_loop(), name="genbatcher"
            )

    def _wake_collector(self) -> None:
        """Nudge the collector out of its blocking waits (queue pop /
        dispatch backoff) from ANY thread — lanes retire and requests
        defer on the scheduler's dispatch thread, and the staged work
        those events unblock must not sit until the 50 ms poll. Safe
        before start() and after the loop dies (wakes are then moot:
        stop()'s sweeps deliver everything)."""
        loop, ev = self._aloop, self._kick
        if loop is None or ev is None:
            return
        try:
            loop.call_soon_threadsafe(ev.set)
        except RuntimeError:
            pass  # loop already closed — nothing left to wake

    def _defer(self, cand) -> None:
        """Park an admission candidate for the collector to reclaim
        (lane incompatible / no room / pages exhausted) and wake it —
        the ONE deferral seam for the 8 batch-run decline sites, so a
        deferred request re-enters dispatch immediately instead of
        riding the poll interval."""
        with self._alock:
            self._deferred.append(cand)
        self._wake_collector()

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            except Exception as e:  # noqa: BLE001
                # A collector that died on its own (e.g. an injected
                # fault) already delivered its waiters' error frames
                # in its finally; stop() must still complete so
                # start() can bring up a fresh collector.
                _log.warning("collector had died: %r", e)
            self._task = None
        if self.sched is not None:
            # Off the loop: stop() joins the dispatch thread, which
            # may be mid-unit (device work takes as long as it takes).
            sched, self.sched = self.sched, None
            await asyncio.get_running_loop().run_in_executor(
                None, sched.stop
            )
        if self._queue is not None:
            while not self._queue.empty():
                req = self._queue.get_nowait()
                req.push(RuntimeError("generation engine stopped"))


    def _spec_should_yield(self) -> bool:
        """Admission candidates end a speculative phase at the next
        round boundary — the handoff seam (tests patch this to force
        a deterministic mid-phase handoff; in production a joiner can
        land during the phase's first compiles, in which case
        yielding before round one is the correct behavior). Under the
        unit scheduler, OTHER runnable lanes/pending groups end the
        phase the same way: a spec round is one unit, and a solo
        phase must not monopolize the dispatch thread while another
        batch has work."""
        with self._alock:
            if self._admit:
                return True
        s = self.sched
        return s is not None and s.queue_depth > 1

    def _compatible(self, group: list, r) -> bool:
        """Can ``r`` join ``group`` without clamping anyone? The batch
        decodes to ``max(n_new)`` from a ``max(bucket)``-wide prompt;
        both maxima together (plus the prefix region, if any) must
        still fit the model's window (each request alone always does —
        ``_encode`` guarantees it).

        Prefix-cached requests batch with each other across DIFFERENT
        prefixes (cross-batch prefix regions): each row's prefix KV is
        right-aligned to the group's common region end
        ``max(prefix_len)`` and masked by its own per-row ``lo``.
        Prefix and plain requests never mix (a plain row would pay the
        whole region in dead cache slots). In strict (high-RTT) mode a
        cross-prefix group needs its stacked program shapes pre-warmed
        (``prefix.mix_warmed``, populated at entry registration);
        unwarmed combinations fall back to same-prefix grouping."""
        if (r.prefix_fp is None) != (group[0].prefix_fp is None):
            return False
        # Disaggregated requests run SOLO (r18): a prefill-only run
        # pushes ITS row's chunk KV at each boundary and a pushed-KV
        # row installs a whole-prompt blob at formation — neither
        # composes with co-batched rows' shapes yet (batched prefill
        # handoff is a future optimization, noted in DESIGN §24).
        for x in (r, group[0]):
            if x.push_to is not None or x.pushed is not None:
                return False
        p_len = 0
        if r.prefix_fp is not None:
            p_len = max(r.prefix_len, *(g.prefix_len for g in group))
            mixed = any(g.prefix_fp != r.prefix_fp for g in group)
            if (
                mixed
                and self._strict_admit
                and p_len not in self.prefix.mix_warmed
            ):
                return False
        bucket = max(len(r.row), *(len(g.row) for g in group))
        n_new = max(r.n_new, *(g.n_new for g in group))
        return p_len + bucket + n_new <= self.model.max_positions

    async def _collect_loop(self) -> None:
        """The ONE collector (r20): forms window-compatible groups
        (deadline-slack carry seed, r12) and routes every formed
        group through ``_dispatch_group`` — in-lane admission when a
        live lane can take it at a unit boundary (continuous
        batching), a new scheduler lane otherwise, a bounded wait
        when neither has room. Serial mode (``sched_max_batches=1``;
        the ``--no-scheduler`` flag is retired) is the SAME loop: one
        live batch plus in-lane admission — the legacy collector's
        semantics on the scheduler's machinery, which is why the
        legacy scheduler-off loop could be deleted.

        Backpressure: dispatch blocks (rule 3) while lanes and the
        staging lists are full, which stops the pop below — stalled
        arrivals then fill the bounded queue and shed as 503s, the
        same ``max_queue`` contract as always."""
        loop = asyncio.get_running_loop()
        # self._carry (window-incompatible leftovers, served next) is
        # initialized in __init__ and cleared in the finally below —
        # no reset here, so items seeded between start() and the first
        # iteration (or left by a crashed predecessor, already pushed
        # terminal frames) can never be silently dropped.
        reqs: list = []
        get = None   # in-flight queue pop (outer so the finally sees it)
        kick = None  # in-flight kick wait (outer for the same reason)
        try:
            while True:
                # Clear-then-check: every wake source (deferral, lane
                # retirement) mutates state BEFORE setting _kick, so a
                # mutation landing after this clear re-sets the event
                # and the waits below wake, while one landing before
                # it is visible to this iteration's sweep.
                self._kick.clear()
                # Requests a lane could not take come first. They
                # were staged independently, so re-apply the window
                # compatibility check and the max_batch cap when
                # forming from them. ``_admit`` holds staged
                # candidates a LIVE lane may still take at its next
                # unit boundary — reclaim those only once no batch is
                # live (lane admission defers what it can never
                # admit, so nothing camps there).
                with self._alock:
                    self._carry = self._deferred + self._carry
                    self._deferred.clear()
                    if (
                        self.sched is not None
                        and self.sched.batches_live == 0
                    ):
                        self._carry = self._admit + self._carry
                        self._admit.clear()
                if self._carry:
                    # Deadline-slack pick (absolute deadlines compare
                    # directly); deadline-less carries keep FIFO order
                    # behind every deadlined one — the r12 ``_carry[0]``
                    # head-of-line fix: a tight-deadline
                    # window-incompatible request no longer waits
                    # behind every earlier carried one.
                    seed_i = min(
                        range(len(self._carry)),
                        key=lambda i: (
                            self._carry[i].deadline is None,
                            self._carry[i].deadline or 0.0,
                            i,
                        ),
                    )
                    reqs = [self._carry.pop(seed_i)]
                    self._forming = reqs
                    rest: list = []
                    for r in self._carry:
                        if (
                            len(reqs) < self.max_batch
                            and self._compatible(reqs, r)
                        ):
                            reqs.append(r)
                        else:
                            rest.append(r)
                    self._carry = rest
                else:
                    # Blocking pop, multiplexed with the cross-thread
                    # kick: a deferral or lane retirement while the
                    # queue is idle must re-enter the sweep above, not
                    # wait for the next arrival.
                    get = asyncio.ensure_future(self._queue.get())
                    kick = asyncio.ensure_future(self._kick.wait())
                    await asyncio.wait(
                        {get, kick}, return_when=asyncio.FIRST_COMPLETED
                    )
                    if get.done() and not get.cancelled():
                        reqs = [get.result()]
                        # No await between the pop resuming and this
                        # assignment, so drain() can never observe the
                        # claimed request in neither the queue nor
                        # here.
                        self._forming = reqs
                        get = None
                        # A fault here kills the COLLECTOR between
                        # claiming a request and serving it — the
                        # finally below must still deliver terminal
                        # frames to everything claimed, queued, or
                        # staged.
                        faults.fire("collector_pop")
                        kick.cancel()
                        await asyncio.wait({kick})
                        kick = None
                    else:
                        # The kick won (or an external cancel lands on
                        # the wait above and propagates): retract the
                        # pop without dropping an item it claims in
                        # the same instant — the same race-free dance
                        # as the fill window below.
                        kick.cancel()
                        await asyncio.wait({kick})
                        kick = None
                        get.cancel()
                        await asyncio.wait({get})
                        if get.cancelled():
                            get = None
                            continue  # re-sweep staged work
                        reqs = [get.result()]
                        self._forming = reqs
                        get = None
                        faults.fire("collector_pop")
                if self.max_wait_s > 0:
                    deadline = loop.time() + self.max_wait_s
                    while len(reqs) < self.max_batch:
                        timeout = deadline - loop.time()
                        if timeout <= 0:
                            break
                        # NOT asyncio.wait_for: on py<3.12 wait_for
                        # can SWALLOW an external cancel that lands
                        # just as the inner pop completes (the classic
                        # lost-cancellation race) — a killed collector
                        # then keeps collecting and stop() deadlocks.
                        # Plain asyncio.wait never consumes the
                        # waiter's cancellation, and the outer ``get``
                        # keeps a claimed request visible to the
                        # finally below.
                        get = asyncio.ensure_future(self._queue.get())
                        done, _ = await asyncio.wait({get}, timeout=timeout)
                        if not done:
                            # Window expired with the pop pending:
                            # retract it without dropping an item the
                            # pop claims in the same instant.
                            get.cancel()
                            await asyncio.wait({get})
                            if get.cancelled():
                                get = None
                                break
                        nxt = get.result()
                        get = None
                        if self._compatible(reqs, nxt):
                            reqs.append(nxt)
                        else:
                            self._carry.append(nxt)
                            break  # keep the window short; serve it next
                else:
                    while (
                        len(reqs) < self.max_batch
                        and not self._queue.empty()
                    ):
                        nxt = self._queue.get_nowait()
                        if self._compatible(reqs, nxt):
                            reqs.append(nxt)
                        else:
                            self._carry.append(nxt)
                            break
                await self._dispatch_group(reqs)
                reqs = []
                self._forming = None
        finally:
            self._forming = None
            # Cancellation (stop()) or a collector crash must not
            # strand waiters — neither those already popped off the
            # queue NOR those still queued or awaiting admission (a
            # handler awaiting ``gen.queue.get()`` on a queued request
            # would otherwise hang forever after an unexpected
            # collector death). What was handed to the scheduler is
            # the scheduler's to deliver: its stop() sweeps lanes and
            # pending groups.
            if kick is not None:
                kick.cancel()
            err = RuntimeError("generation engine stopped")
            queued = []
            if get is not None:
                if get.done() and not get.cancelled():
                    queued.append(get.result())
                else:
                    get.cancel()
            if self._queue is not None:
                while not self._queue.empty():
                    queued.append(self._queue.get_nowait())
            with self._alock:
                queued += self._admit + self._deferred
                self._admit.clear()
                self._deferred.clear()
            for r in (*reqs, *self._carry, *queued):
                try:
                    r.push(err)
                except Exception:
                    pass
            self._carry = []

    async def _dispatch_group(self, reqs: list) -> None:
        """Route one formed group, preferring the cheapest seat:

        1. IN-LANE ADMISSION — a live lane whose window fits every
           request takes the group at its next unit boundary (the
           continuous-batching growth path: no new lane, no extra
           prefill program beyond the r10 interleave). Staging is
           once-only (``GenRequest.staged``): a candidate the lane
           then defers re-enters HERE and takes a lane of its own
           instead of ping-ponging between the lists.
        2. PENDING GROUP — hand off to the scheduler, which lanes it
           when a slot and the page budget allow, in deadline-slack
           order; its units then interleave with the other lanes' at
           the typed-unit queue. Bounded at one ``max_batch`` of
           pending requests, so ``max_queue`` keeps meaning something
           during long runs.
        3. WAIT — staging and backlog both full: block on the kick
           (lane retirement / deferral) with a 50 ms poll backstop,
           then re-check. The group stays in ``self._forming`` the
           whole time, so drain() and the terminal-frame sweep always
           see it.
        """
        while True:
            sched = self.sched
            if sched is None:
                raise RuntimeError("scheduler stopped")
            self._kick.clear()
            with self._alock:
                room = (
                    self.max_batch - len(self._admit) - len(self._deferred)
                    >= len(reqs)
                )
            if room and all(not r.staged for r in reqs):
                for lane_reqs in sched.lane_groups():
                    if lane_reqs and all(
                        self._compatible(lane_reqs, r) for r in reqs
                    ):
                        for r in reqs:
                            r.staged = True
                        with self._alock:
                            self._admit.extend(reqs)
                        return
            if sched.backlog < self.max_batch:
                sched.submit(reqs)
                return
            waiter = asyncio.ensure_future(self._kick.wait())
            try:
                await asyncio.wait({waiter}, timeout=0.05)
            finally:
                waiter.cancel()

    async def submit(
        self,
        text: str,
        *,
        max_new_tokens: int | None = None,
        temperature: float = 0.0,
        seed: int = 0,
        top_k: int = 0,
        top_p: float = 1.0,
        prefix: str | None = None,
        stream: bool = False,
        deadline_ms: float | None = None,
        push_to=None,
        kv_xfer: str | None = None,
        adapter: str | None = None,
        tenant: str | None = None,
    ) -> GenRequest:
        """Queue one prompt for batched decode; consume ``req.queue``
        for ``{"token_ids": [...]}`` chunks until the ``None``
        sentinel (exceptions are delivered in-band).

        Disaggregation (r18): ``push_to=(host, port, xfer)`` runs the
        prompt as a PREFILL-ONLY batch (``n_new`` forced to 1 — the
        run ends at the sampled first token) whose chunk KV streams
        to the named decode replica; ``kv_xfer=<id>`` resolves a
        staged pushed transfer so formation installs the prompt KV
        instead of prefilling. Both default None — the pre-r18 path,
        bit for bit.

        ``deadline_ms`` is the request's end-to-end wall-clock budget
        (engine default when ``None``; see ``default_deadline_ms``).
        A deadlined request the admission estimate says cannot finish
        in time sheds HERE — 503 + computed retry-after — instead of
        occupying a queue slot and timing out mid-decode.

        ``tenant`` names the quota/fairness identity (r22, see
        ``serving/registry.py``); it defaults to the adapter id, then
        to the anonymous tenant."""
        from mlapi_tpu.serving.scoring import OverloadedError

        if self._task is None:
            raise RuntimeError("generation engine not started")
        if self._task.done():
            # A dead collector must fail requests fast, not let them
            # queue forever; surface what killed it.
            exc = (
                None if self._task.cancelled() else self._task.exception()
            )
            raise RuntimeError(
                f"generation collector died: {exc!r}"
            ) from exc
        if self.draining:
            # Drain window: new admissions go elsewhere; retry-after
            # hints how long the restart (drain budget) takes.
            self.shed_draining += 1
            self.rejected += 1
            raise OverloadedError(
                "generate",
                retry_after_s=getattr(self, "drain_timeout_s", 10.0),
                detail="server draining: retry against another replica",
            )
        n_new = int(max_new_tokens or self.default_max_new_tokens)
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        tenant = tenant or adapter or ""
        led = self.tenants
        if led is not None and tenant:
            # Tenant-scoped brownout rung (r22): engages BEFORE the
            # fleet-wide ladder — one tenant's live depth crossing a
            # QUARTER of the queue clamps that tenant's token budget
            # at half the pressure the fleet's rung 1 needs (50%), so
            # the hot tenant degrades itself before it degrades
            # everyone. Same lever, same counter discipline.
            if (
                led.depth(tenant) * 4 >= self.max_queue
                and n_new > self.default_max_new_tokens
            ):
                n_new = self.default_max_new_tokens
                self.brownout_tenant_clamped += 1
                led.note_brownout(tenant)
        level = self._brownout_level()
        if level >= 1 and n_new > self.default_max_new_tokens:
            # Brownout lever 1: clamp oversized budgets to the default
            # tier — bounded work per admitted request under pressure.
            n_new = self.default_max_new_tokens
            self.brownout_tokens_clamped += 1
        if level >= 2 and self.pool is not None:
            # Brownout lever 3: proactively evict an idle (LRU,
            # unreferenced) prefix page set so live sequences keep
            # allocating instead of hitting PagePoolExhausted. With
            # the host tier attached the eviction SPILLS instead of
            # discarding (PagePool._spill_and_release), so the brownout
            # trades HBM for host RAM, not for a future re-prefill.
            # Through the executor: the spill is a device gather plus
            # (disk tier) an npz write — run inline it would freeze
            # every stream on the loop for exactly as long as the
            # server is under the pressure that triggered it
            # (mlapi-lint MLA008, caught r19 — the r13 review moved
            # this work outside the pool LOCK; off the LOOP is the
            # other half).
            await asyncio.get_running_loop().run_in_executor(
                None, self.pool.evict_idle, 1
            )
        if (
            self.admission_control
            and deadline_ms is not None
            and deadline_ms > 0
        ):
            est = self.admission_estimate_ms()
            if est > deadline_ms:
                # Infeasible: it would expire in the queue anyway —
                # shed now, and tell the client when the backlog
                # should have cleared.
                self.shed_deadline_infeasible += 1
                self.rejected += 1
                raise OverloadedError(
                    "generate",
                    retry_after_s=max(1.0, (est - deadline_ms) / 1e3),
                    detail=(
                        f"deadline infeasible: estimated queue wait + "
                        f"TTFT {est:.0f} ms exceeds the {deadline_ms:.0f} "
                        f"ms budget"
                    ),
                )
        # Encode OFF the event loop: a first-use prefix runs a device
        # prefill (and possibly an XLA compile) inside _encode — on
        # the loop thread that would freeze every stream and timer in
        # the server for its duration.
        loop = asyncio.get_running_loop()
        req = await loop.run_in_executor(
            None,
            lambda: self._encode(
                text, n_new, float(temperature), int(seed), loop,
                int(top_k), float(top_p), prefix=prefix,
                stream=bool(stream), deadline_ms=deadline_ms,
                push_to=push_to, kv_xfer=kv_xfer, adapter=adapter,
            ),
        )
        if push_to is not None:
            # Prefill-only AFTER encoding: geometry (bucket/limit) was
            # computed with the CLIENT's token budget — identical to
            # what the decode replica computes for the same body — but
            # this run ends at the sampled first token.
            req.n_new = 1
        if self.draining or self._task is None or self._task.done():
            # Drain (or a full stop) may have COMPLETED during the
            # encode executor await: this request passed the front-door
            # check but was invisible to drain's idle sweep (not yet
            # queued, staged, or running), so enqueueing now would land
            # it in a queue no collector will ever pop — a stream with
            # no terminal frame. Shed exactly like the front door.
            self.shed_draining += 1
            self.rejected += 1
            raise OverloadedError(
                "generate",
                retry_after_s=getattr(self, "drain_timeout_s", 10.0),
                detail="server draining: retry against another replica",
            )
        req.tenant = tenant
        try:
            self._queue.put_nowait(req)
        except asyncio.QueueFull:
            self.rejected += 1
            self.shed_queue_full += 1
            raise OverloadedError("generate", retry_after_s=2.0) from None
        if led is not None and tenant:
            # Live-depth accounting: entered once here, exited once
            # at the terminal frame (GenRequest.finish — fires on
            # every delivery path, including cancels). No await
            # between the put and this, so the collector cannot
            # retire the request before its exit hook exists.
            led.enter(tenant)
            req.on_done = lambda t=tenant: led.exit(t)
        self.requests += 1
        req.rid = self.requests
        return req

    # -- synchronous single-shot (tests, CLI) ------------------------------
    def generate_text(
        self,
        text: str,
        *,
        max_new_tokens: int | None = None,
        temperature: float = 0.0,
        seed: int = 0,
        top_k: int = 0,
        top_p: float = 1.0,
        prefix: str | None = None,
        deadline_ms: float | None = None,
        push_to=None,
        kv_xfer: str | None = None,
        adapter: str | None = None,
    ) -> dict:
        """One prompt → generated continuation (text + ids), through
        the same ``_run_batch`` the batcher uses — including its
        batch-1 fused fast path (one XLA program per generation) when
        eligible; pass ``fused_single=False`` at construction to pin
        the chunked programs (e.g. when reproducing a chunked-path
        decode bug). ``push_to``/``kv_xfer`` mirror :meth:`submit`'s
        disaggregation hooks (engine-level tests and drills)."""
        n_new = int(max_new_tokens or self.default_max_new_tokens)
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        req = self._encode(
            text, n_new, float(temperature), int(seed), None,
            int(top_k), float(top_p), prefix=prefix,
            deadline_ms=deadline_ms, push_to=push_to, kv_xfer=kv_xfer,
            adapter=adapter,
        )
        if push_to is not None:
            # Same contract as submit(): encode with the client's
            # budget (geometry parity with the decode replica), then
            # run prefill-only.
            req.n_new = 1
        out_ids: list[int] = []
        sink = _SyncSink(req, out_ids)
        self._run_batch([sink])
        if sink.error is not None:
            raise sink.error
        return {
            "text": self.tokenizer.decode(out_ids),
            "token_ids": out_ids,
            "prompt_tokens": req.prompt_tokens,  # incl. prefix tokens
        }

    def warmup(self, *, full: bool | None = None) -> None:
        """Compile every (prompt bucket × power-of-two batch) prefill
        and decode program at the default-``max_new_tokens`` cache
        tier, off the request path. Combined with batch padding
        (``_run_batch``) and cache-tier quantization (``_cache_len``),
        this means NO request with ``n_new <= default_max_new_tokens``
        ever pays an XLA compile — the classification engine's
        contract, honoured by generation too. Larger ``n_new`` tiers
        (power-of-two chunk multiples, log-many) compile on first use.
        Because ``decode_attn_impl`` (like ``kv_quant``) is a model
        field every program factory keys on, this same grid
        precompiles the flash-decode kernel per (bucket, cache tier)
        when the model selects it — no kernel-specific warm pass.

        ``full=False`` (or env ``MLAPI_TPU_WARMUP=minimal``, used by
        the CPU test suite) warms only the smallest bucket at batch=1.
        """
        import os

        if full is None:
            full = os.environ.get("MLAPI_TPU_WARMUP", "full") != "minimal"
        buckets = self.prompt_buckets if full else self.prompt_buckets[:1]
        # Cover every shape _run_batch can produce: it pads the batch
        # dim to the NEXT power of two, so for max_batch=6 the grid
        # must include 8 (batches of 5-6 pad up past max_batch).
        batches = [1]
        while full and batches[-1] < self.max_batch:
            batches.append(batches[-1] * 2)
        shapes = 0
        for bucket in buckets:
            n_new = min(
                self.default_max_new_tokens,
                self.model.max_positions - bucket,
            )
            if n_new < 1:
                continue
            # Largest n_new that still lands in the default cache tier
            # (so warm programs are byte-identical to default traffic).
            tier = self.default_tier
            for bsz in batches:
                # Row 0 runs two chunks, the rest finish after chunk
                # one: chunk 1 executes the FULL-width decode program,
                # then the batch compacts bsz → bsz/2 for chunk 2 —
                # one _run_batch call compiles the prefill, the
                # decode-chunk program, and that halving's compaction
                # gather. Across the grid this covers the whole
                # halving chain (8→4, 4→2, 2→1). All n_new values stay
                # within the default cache tier, so these are the
                # exact programs default traffic reuses.
                long_n = min(n_new, 2 * self.chunk + 1, tier)
                sinks = []
                for j in range(bsz):
                    row = np.full((bucket,), self.tokenizer.pad_id, np.int32)
                    req = GenRequest(
                        row, 1,
                        long_n if j == 0 else min(2, long_n),
                        0.0, 0, None,
                    )
                    sinks.append(_SyncSink(req, []))
                # fused_ok=False: the warm grid exists to compile the
                # PLAIN-chunk programs (prefill/decode/compaction);
                # the fused-chunk width ladder has its own grid below.
                self._run_batch(sinks, fused_ok=False)
                if sinks[0].error is not None:
                    raise sinks[0].error
                shapes += 1
        # Pre-compute the /metrics per-slot KV byte gauge here, off
        # the request path — lazily it would build a largest-bucket
        # cache on-device inside the first monitoring scrape.
        self.kv_cache_slot_bytes()
        if self.fused_single:
            shapes += self.fused.warm(full)
        if full:
            shapes += self._warm_admission(batches)
            if self.draft_model is not None:
                shapes += self.spec.warm()
            # From here on, a joiner is only admitted into a RUNNING
            # batch when its admission program is already compiled —
            # an unwarmed shape waits for the next batch instead of
            # stalling the running one on an XLA compile.
            self._strict_admit = True
        _log.info(
            "warmed generate: %d (bucket x batch x admission) shapes, "
            "chunk=%d",
            shapes, self.chunk,
        )

    def _warm_admission(self, batches: list) -> int:
        """Compile the continuous-batching admission programs off the
        request path. The expensive program — the joiner's [1, bucket]
        prefill — is keyed on the prompt bucket ALONE (one compile per
        bucket, reusing ``prefill_fn(model, bucket)``); the trivial
        KV-scatter and growth-gather programs are warmed across the
        default-tier (cache × batch) grid. Populates the warmed-shape
        sets that gate strict admission; other cache tiers' scatters
        compile on demand when ``_admit_eager`` allows (low-RTT
        attach) and defer otherwise."""
        from mlapi_tpu.models.gpt import admit_scatter_fn, prefill_fn

        tier = self.default_tier
        shapes = 0
        minis = {}
        for bj in self.prompt_buckets:
            prompt = np.full((1, bj), self.tokenizer.pad_id, np.int32)
            _, minis[bj] = prefill_fn(self.model, bj)(
                self.params, jnp.asarray(prompt),
                jnp.asarray(self._key_data(0)[None]),
                jnp.asarray(np.zeros((1,), np.float32)),
                jnp.asarray(np.asarray([max(bj - 1, 0)], np.int32)),
                jnp.asarray(np.zeros((1,), np.int32)),
                jnp.asarray(np.ones((1,), np.float32)),
            )
            self._warmed_joiner.add(bj)
            shapes += 1
        if self.pool is not None:
            # Paged admission: growth and compaction are host-side
            # page-table ops (no device gather to warm), and the
            # admission program is batch-size-independent — one [1, W]
            # row lands in one table row whatever the running batch
            # is. Page-native mode warms the joiner's direct-to-pages
            # prefill (the ONE admission program — prefill and landing
            # fused); legacy mode warms the adopt scatter it pairs
            # with the contiguous joiner prefill above. Both key on
            # (bucket, table width), the shape pair they compile on.
            # All warm writes go through a null table, i.e. into the
            # never-read null page — the pool is untouched.
            from mlapi_tpu.models.gpt import (
                paged_extend_fn, paged_prefill_fn, paged_scatter_fn,
                sample_fn,
            )
            from mlapi_tpu.ops.quant import (
                paged_cache_tree, paged_pools_of,
            )

            tiers = {
                min(self.model.max_positions, rb + tier)
                for rb in self.prompt_buckets
            }
            one_key = jnp.asarray(self._key_data(0)[None])
            zt1 = jnp.asarray(np.zeros((1,), np.float32))
            zk1 = jnp.asarray(np.zeros((1,), np.int32))
            op1 = jnp.asarray(np.ones((1,), np.float32))
            for bj in self.prompt_buckets:
                for total in tiers:
                    if bj >= total:
                        continue
                    npv = -(-total // self.pool.page)
                    tab1 = np.zeros((1, npv), np.int32)
                    cache = paged_cache_tree(self.pool.layers, tab1)
                    if self.prefill_page_native:
                        row = np.full(
                            (1, bj), self.tokenizer.pad_id, np.int32
                        )
                        _, cache = paged_prefill_fn(self.model, bj)(
                            self.params, cache, jnp.asarray(row),
                            jnp.int32(0), one_key, zt1,
                            jnp.asarray(
                                np.asarray([max(bj - 1, 0)], np.int32)
                            ),
                            zk1, op1,
                        )
                    else:
                        cache = paged_scatter_fn()(
                            cache, self.model.init_cache(1, bj),
                            jnp.asarray(tab1), jnp.int32(0),
                        )
                    self.pool.layers = paged_pools_of(cache)
                    self._warmed_scatter.add((bj, npv))
                    shapes += 1
            if self.prefill_interleave:
                # Interleaved long-prompt admission: the cp-wide paged
                # extend chunk at [1, npv] plus the standalone sampler
                # — the two programs an interleaved prefill dispatches.
                cp = self.prompt_buckets[-1]
                for total in tiers:
                    npv = -(-total // self.pool.page)
                    tab1 = np.zeros((1, npv), np.int32)
                    cache = paged_cache_tree(self.pool.layers, tab1)
                    cache, logits = paged_extend_fn(self.model, cp)(
                        self.params, cache,
                        jnp.asarray(np.full(
                            (1, cp), self.tokenizer.pad_id, np.int32
                        )),
                        jnp.int32(0),
                        jnp.asarray(np.asarray([cp - 1], np.int32)),
                        jnp.int32(0), jnp.int32(0),
                    )
                    self.pool.layers = paged_pools_of(cache)
                    sample_fn(self.model)(
                        logits, one_key, zt1, zk1, op1
                    )
                    self._warmed_extend.add((cp, npv))
                    shapes += 1
            return shapes
        for run_bucket in self.prompt_buckets:
            total = min(self.model.max_positions, run_bucket + tier)
            if total - run_bucket < 1:
                continue
            for bsz in batches:
                if bsz * 2 <= batches[-1]:
                    sel = np.concatenate(
                        [np.arange(bsz), np.zeros(bsz)]
                    ).astype(np.int32)
                    _compact_fn()(
                        self.model.init_cache(bsz, total), jnp.asarray(sel)
                    )
                    self._warmed_growth.add((bsz, bsz * 2, total))
                for bj in self.prompt_buckets:
                    # A joiner's bucket must fit below some reachable
                    # decode position: pos ranges over
                    # [run_bucket, total).
                    if bj >= total:
                        continue
                    admit_scatter_fn()(
                        self.model.init_cache(bsz, total), minis[bj],
                        jnp.int32(0), jnp.int32(0),
                    )
                    self._warmed_scatter.add((bj, total, bsz))
                    shapes += 1
        return shapes


def _load_meta_only(path):
    """Read just the manifest (no params I/O)."""
    import json
    from pathlib import Path

    from mlapi_tpu.checkpoint.io import CheckpointMeta, _MANIFEST

    manifest = Path(path) / _MANIFEST
    if not manifest.exists():
        raise FileNotFoundError(f"{path} is not a committed checkpoint")
    return CheckpointMeta.from_json(json.loads(manifest.read_text()))
