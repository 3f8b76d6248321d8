"""Peer-to-peer prefix-KV fetch between engine replicas.

The r14 router gives the fleet ONE cold prefill per distinct prefix —
but only while the affinity-preferred replica stays up and under its
depth limit. Any failover, drain, or depth overflow lands the prefix
on a replica whose caches have never seen it, and that replica pays
the full O(P²) prefill again even though a peer still holds the exact
stored-format bytes (device-resident prefix entry, or an r13 host-tier
blob). This module promotes the tier blob into the fleet's
TRANSFERABLE KV unit: a wire hop between replica tiers, so affinity
becomes a soft hint and a replica death no longer costs its whole
prefix working set (ROADMAP item 2, step one; the hierarchical-memory
move Snap ML makes across DRAM/NVMe levels, taken across hosts).

Topology — who knows what:

- **The router knows warmth.** Its HRW affinity map already names the
  replica most likely to hold a prefix; any forward to a
  NON-preferred replica (p2c fallback, failover, depth overflow,
  post-drain remap) carries ``x-mlapi-warm-peer: host:port`` naming
  the HRW head (``Router.forward``). Replica-gated like
  ``x-mlapi-router-depth`` — direct callers cannot aim a replica's
  fetches at an arbitrary host.
- **The serving replica knows bytes.** ``GET
  /kv/prefix?fp=<digest>`` (``serving/app.py``, installed only with
  ``--kv-peer-fetch``) serves the prefix's blob in its STORED format
  — int8-halved payloads cross the wire at half the bytes for free —
  from the host tier when spilled, else gathered from the
  device-resident prefix entry's contiguous KV (safe from any
  thread: entry KV is never donated). A GET works while DRAINING —
  exactly the window where a peer needs the drained replica's slice.
- **The fetching replica stays off the dispatch thread.** The fetch
  runs inside ``PrefixCache._restore`` on the encode executor thread
  (where the cold prefill it replaces would have run); the fetched
  blob rebuilds the ``_PrefixEntry`` and is STAGED into the local
  tier (``KVTier.stage``), so the dispatch-thread paged formation
  restores pool pages through the existing alloc-first
  ``PagePool.restore_entry`` path — a mid-fetch or mid-restore
  failure conserves pages exactly and degrades to the r13 cold path.
  No wire I/O ever touches the dispatch thread.

Wire format (one blob): a single JSON header line —
``{"v": 1, "page", "num_pages", "nbytes", "bucket", "lo", "used",
"leaves": [[layer, name, shape, dtype], ...]}`` — followed by each
leaf's raw C-order bytes in header order. The payload bytes are
EXACTLY the ``num_pages × kv_page_bytes`` closed form (the same
``ops/quant.kv_tree_bytes`` arithmetic the tier's counters use);
``deserialize_blob`` validates every leaf's size and the total
against the header, so a truncated or corrupt body is a counted MISS,
never a wrong cache. Geometry against the LOCAL replica (bucket/page
drift across builds or configs) is validated by the same ``_plan`` /
``restore_entry`` checks every tier blob passes — a peer can never
install bytes the local pool would not have produced itself.

Failure grammar (``serving/faults.py``): ``peer_fetch`` fires before
the wire request, ``peer_serve`` before the serve-side blob resolve —
a raise at either point falls back to the cold prefill with pages
conserved and the stream completing.

Since r18 this module also carries the DISAGGREGATION wire
(:class:`KVPush`): the same blob framing, extended with
``{xfer, chunk, num_chunks, span}``, pushed PROACTIVELY at chunk
granularity from prefill-role replicas to decode-role replicas
(``POST /kv/push``) — where the peer fetch moves warmth reactively
on a miss, the push moves a request's entire prompt KV while the
prefill is still running, so the decode replica activates the
stream with zero local prefill FLOPs. ``kv_push_send`` /
``kv_push_recv`` extend the failure grammar with the same contract:
a raise fails the transfer and the decode replica cold-prefills,
pages conserved on both ends.
"""

from __future__ import annotations

import collections
import hashlib
import json
import threading

import numpy as np

from mlapi_tpu.serving import faults
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("serving.kv_peer")

WIRE_VERSION = 1
# Header line length cap: a dozen layers of leaf manifests fit in a
# few KB; anything larger is a corrupt/hostile response, refused
# before allocation.
_MAX_HEADER_BYTES = 1 << 20


def fp_digest(fp: str) -> str:
    """URL-safe fingerprint of a prefix string: blake2b-128 hex of
    its UTF-8 bytes (prefix text is arbitrary — it cannot ride a URL
    path raw, and the serving replica must not need the full text to
    index its blobs)."""
    return hashlib.blake2b(
        fp.encode("utf-8", "surrogatepass"), digest_size=16
    ).hexdigest()


def serialize_blob(blob) -> bytes:
    """A :class:`~mlapi_tpu.serving.kv_tier.KVTierBlob` → wire bytes:
    JSON header line + concatenated raw leaf payloads in header
    order. Payload bytes total exactly ``blob.nbytes`` (the
    ``num_pages × kv_page_bytes`` closed form)."""
    leaves = []
    chunks = []
    for ln in sorted(blob.payload):
        for name in sorted(blob.payload[ln]):
            a = np.ascontiguousarray(blob.payload[ln][name])
            leaves.append([ln, name, list(a.shape), a.dtype.str])
            chunks.append(a.tobytes())
    header = json.dumps(
        {
            "v": WIRE_VERSION,
            "page": blob.page,
            "num_pages": blob.num_pages,
            "nbytes": blob.nbytes,
            "bucket": blob.bucket,
            "lo": blob.lo,
            "used": blob.used,
            "leaves": leaves,
        }
    ).encode()
    return header + b"\n" + b"".join(chunks)


def deserialize_blob(fp, data: bytes):
    """Wire bytes → a validated ``KVTierBlob`` for ``fp``. Raises
    ``ValueError`` on ANY inconsistency — unparseable header, leaf
    shapes that are not ``[num_pages, page, ...]``, a payload whose
    size does not match the manifest, trailing bytes, or a byte total
    that disagrees with the header's ``nbytes`` — so a corrupt wire
    response is dropped as a counted miss, never installed."""
    from mlapi_tpu.serving.kv_tier import KVTierBlob

    nl = data.find(b"\n", 0, _MAX_HEADER_BYTES)
    if nl < 0:
        raise ValueError("no header line in peer blob")
    try:
        head = json.loads(data[:nl])
    except Exception as e:
        raise ValueError(f"unparseable peer blob header: {e}") from None
    if not isinstance(head, dict) or head.get("v") != WIRE_VERSION:
        raise ValueError(f"unknown peer blob version {head!r:.80}")
    try:
        page = int(head["page"])
        num_pages = int(head["num_pages"])
        nbytes = int(head["nbytes"])
        # A meta-less blob cannot rebuild an entry and the serve side
        # never emits one, so a None here is corruption — and int()
        # turns it (or any non-numeric junk) into the TypeError this
        # clause converts to the one documented exception type.
        bucket = int(head["bucket"])
        lo = int(head["lo"])
        used = int(head["used"])
        leaves = head["leaves"]
        if not isinstance(leaves, list) or not leaves:
            raise ValueError("leaf manifest is not a non-empty list")
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(f"incomplete peer blob header: {e}") from None
    payload: dict = {}
    off = nl + 1
    total = 0
    for leaf in leaves:
        try:
            ln, name, shape, dtype = leaf
            shape = tuple(int(s) for s in shape)
            dt = np.dtype(dtype)
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad leaf manifest {leaf!r:.80}: {e}") from None
        if (
            len(shape) < 2
            or shape[0] != num_pages
            or shape[1] != page
            or any(s <= 0 for s in shape)
        ):
            # Non-positive dims included: a negative dim would make
            # ``size`` negative — defeating the truncation check
            # below and letting ``off`` walk backward into already-
            # consumed bytes (np.frombuffer treats a negative count
            # as "the rest of the buffer", silently).
            raise ValueError(
                f"leaf {ln}/{name} shape {shape} is not "
                f"[{num_pages}, {page}, ...] with positive dims"
            )
        size = int(np.prod(shape)) * dt.itemsize
        if off + size > len(data):
            raise ValueError("truncated peer blob payload")
        payload.setdefault(ln, {})[name] = np.frombuffer(
            data, dtype=dt, count=int(np.prod(shape)), offset=off
        ).reshape(shape)
        off += size
        total += size
    if off != len(data):
        raise ValueError("trailing bytes after peer blob payload")
    if total != nbytes:
        raise ValueError(
            f"peer blob payload is {total} bytes, header says {nbytes}"
        )
    return KVTierBlob(fp, payload, page, nbytes, bucket, lo, used)


def serialize_push_chunk(xfer: str, chunk: int, num_chunks: int,
                         span: tuple[int, int], kv: dict) -> bytes:
    """One prefill chunk's KV slice → wire bytes (r18 disaggregation:
    the r17 blob format extended with ``{xfer, chunk, num_chunks,
    span}``). ``kv`` is ``{layer: {leaf: [1, span, ...]}}`` in the
    STORED format — int8 KV crosses the wire at half the bf/f32
    bytes, exactly like the peer-fetch blob. Payload bytes are the
    closed form ``(hi - lo) × kv_page_bytes(model, 1)``."""
    lo, hi = int(span[0]), int(span[1])
    leaves = []
    chunks = []
    total = 0
    for ln in sorted(kv):
        for name in sorted(kv[ln]):
            a = np.ascontiguousarray(kv[ln][name])
            leaves.append([ln, name, list(a.shape), a.dtype.str])
            chunks.append(a.tobytes())
            total += a.nbytes
    header = json.dumps(
        {
            "v": WIRE_VERSION,
            "kind": "chunk",
            "xfer": xfer,
            "chunk": int(chunk),
            "num_chunks": int(num_chunks),
            "span": [lo, hi],
            "nbytes": total,
            "leaves": leaves,
        }
    ).encode()
    return header + b"\n" + b"".join(chunks)


def serialize_push_fin(xfer: str, num_chunks: int, first_token: int,
                       bucket: int, used: int) -> bytes:
    """The transfer's FINALIZE message: no KV payload — it carries
    the prefill replica's sampled first token plus the geometry the
    decode replica validates against its own ``_encode`` (bucket/used
    drift ⇒ the transfer can never apply ⇒ cold prefill)."""
    return json.dumps(
        {
            "v": WIRE_VERSION,
            "kind": "fin",
            "xfer": xfer,
            "num_chunks": int(num_chunks),
            "first_token": int(first_token),
            "bucket": int(bucket),
            "used": int(used),
        }
    ).encode() + b"\n"


def deserialize_push(data: bytes) -> dict:
    """Wire bytes → a validated push message dict (``kind`` is
    ``"chunk"`` — with ``payload`` — or ``"fin"``). Raises
    ``ValueError`` on ANY inconsistency, same contract as
    :func:`deserialize_blob`: a corrupt push is a counted receive
    failure, never a staged wrong chunk."""
    nl = data.find(b"\n", 0, _MAX_HEADER_BYTES)
    if nl < 0:
        raise ValueError("no header line in pushed chunk")
    try:
        head = json.loads(data[:nl])
    except Exception as e:
        raise ValueError(f"unparseable push header: {e}") from None
    if not isinstance(head, dict) or head.get("v") != WIRE_VERSION:
        raise ValueError(f"unknown push version {head!r:.80}")
    kind = head.get("kind")
    try:
        xfer = head["xfer"]
        if not isinstance(xfer, str) or not xfer:
            raise ValueError("xfer id is not a non-empty string")
        num_chunks = int(head["num_chunks"])
        if num_chunks < 1:
            raise ValueError("num_chunks must be >= 1")
        if kind == "fin":
            if data[nl + 1:]:
                raise ValueError("trailing bytes after fin header")
            return {
                "kind": "fin",
                "xfer": xfer,
                "num_chunks": num_chunks,
                "first_token": int(head["first_token"]),
                "bucket": int(head["bucket"]),
                "used": int(head["used"]),
            }
        if kind != "chunk":
            raise ValueError(f"unknown push kind {kind!r}")
        chunk = int(head["chunk"])
        if not 0 <= chunk < num_chunks:
            raise ValueError(f"chunk {chunk} outside [0, {num_chunks})")
        lo, hi = (int(s) for s in head["span"])
        if not 0 <= lo < hi:
            raise ValueError(f"bad span [{lo}, {hi})")
        nbytes = int(head["nbytes"])
        leaves = head["leaves"]
        if not isinstance(leaves, list) or not leaves:
            raise ValueError("leaf manifest is not a non-empty list")
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(f"incomplete push header: {e}") from None
    payload: dict = {}
    off = nl + 1
    total = 0
    span = hi - lo
    for leaf in leaves:
        try:
            ln, name, shape, dtype = leaf
            shape = tuple(int(s) for s in shape)
            dt = np.dtype(dtype)
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad leaf manifest {leaf!r:.80}: {e}") from None
        if (
            len(shape) < 2
            or shape[0] != 1
            or shape[1] != span
            or any(s <= 0 for s in shape)
        ):
            # Same non-positive-dim refusal as deserialize_blob: a
            # negative dim defeats the truncation check below.
            raise ValueError(
                f"leaf {ln}/{name} shape {shape} is not "
                f"[1, {span}, ...] with positive dims"
            )
        size = int(np.prod(shape)) * dt.itemsize
        if off + size > len(data):
            raise ValueError("truncated push payload")
        payload.setdefault(ln, {})[name] = np.frombuffer(
            data, dtype=dt, count=int(np.prod(shape)), offset=off
        ).reshape(shape)
        off += size
        total += size
    if off != len(data):
        raise ValueError("trailing bytes after push payload")
    if total != nbytes:
        raise ValueError(
            f"push payload is {total} bytes, header says {nbytes}"
        )
    return {
        "kind": "chunk",
        "xfer": xfer,
        "chunk": chunk,
        "num_chunks": num_chunks,
        "span": (lo, hi),
        "nbytes": nbytes,
        "payload": payload,
    }


class PushedKV:
    """One COMPLETE assembled transfer on the decode replica: the
    prompt's contiguous ``[1, bucket]`` stored-format KV (chunks
    concatenated in span order), the prefill replica's sampled first
    token, and the geometry the local ``_encode`` must reproduce for
    the bytes to apply."""

    __slots__ = ("kv", "first_token", "bucket", "used", "nbytes")

    def __init__(self, kv, first_token, bucket, used, nbytes):
        self.kv = kv
        self.first_token = int(first_token)
        self.bucket = int(bucket)
        self.used = int(used)
        self.nbytes = int(nbytes)


class _Xfer:
    """Sender-side transfer record (one per in-flight handoff)."""

    __slots__ = ("host", "port", "failed", "done")

    def __init__(self, host: str, port: int):
        self.host = host
        self.port = port
        self.failed = False
        self.done = threading.Event()


class _Staged:
    """Receiver-side staging record: chunks land out of band (the
    /kv/push handler) and are assembled once the fin arrives with
    every chunk present."""

    __slots__ = ("chunks", "spans", "num_chunks", "fin", "nbytes")

    def __init__(self):
        self.chunks: dict = {}
        self.spans: dict = {}
        self.num_chunks: int | None = None
        self.fin: dict | None = None
        self.nbytes = 0

    @property
    def complete(self) -> bool:
        return (
            self.fin is not None
            and self.num_chunks is not None
            and len(self.chunks) == self.num_chunks
        )


class KVPush:
    """Prefill/decode disaggregation state (r18): the PREFILL side's
    chunk-push client (a background sender thread so the dispatch
    thread never blocks on the wire) and the DECODE side's staging
    store feeding ``BatchRun``'s pushed-KV formation. One instance
    per role-carrying engine; a ``mixed`` replica has none — the
    default topology is bit-identical to r17. Thread-safe: chunks
    enqueue from the dispatch thread, the sender thread posts,
    receives land on the app executor, assembly runs on the encode
    executor, and /metrics scrapes from the event loop."""

    # Receiver caps: a staged transfer is host RAM a remote peer
    # controls — bound both the count and the bytes.
    _STAGE_CAP = 32
    _STAGE_BYTES_CAP = 1 << 30

    def __init__(self, engine, *, timeout_s: float = 10.0):
        self.eng = engine
        self.timeout_s = float(timeout_s)
        self._lock = threading.Lock()
        # Sender side.
        self._xfers: dict[str, _Xfer] = {}
        self._sendq: "queue.Queue" = None  # created with the worker
        self._worker: threading.Thread | None = None
        # Receiver side (xfer -> _Staged, insertion-ordered for LRU
        # eviction of stale incompletes).
        self._staged: collections.OrderedDict = collections.OrderedDict()
        self._staged_bytes = 0
        # Counters (exported as generate.kv_push_*; byte counters are
        # exact payload arithmetic — every chunk's bytes are the
        # ``span × kv_page_bytes(model, 1)`` closed form — never
        # wall-clock).
        self.push_sent = 0
        self.push_send_failures = 0
        self.push_bytes_sent = 0
        self.push_recv = 0
        self.push_recv_failures = 0
        self.push_bytes_recv = 0
        self.push_applied = 0
        self.push_bytes_applied = 0
        self.push_fallbacks = 0

    # -- sender (prefill replica) ---------------------------------------
    # Patch point for in-process tests: (host, port, path, body,
    # timeout_s) -> (status, body).
    _transport = None  # set below (staticmethod of _http_post)

    def begin(self, xfer: str, host: str, port: int) -> None:
        """Open a transfer toward the decode replica at host:port.
        Chunks enqueued before ``begin`` would have nowhere to go —
        the BatchRun push hook calls this at formation."""
        with self._lock:
            self._xfers[xfer] = _Xfer(host, int(port))

    def send_chunk(self, xfer: str, chunk: int, num_chunks: int,
                   span: tuple[int, int], kv: dict) -> None:
        """Enqueue one finished chunk's KV slice for the sender
        thread. Called from the dispatch thread at the chunk
        boundary — the device→host gather already happened there (the
        chunk's bytes are needed on host either way); serialization
        and the wire POST stay on the sender thread, so the running
        prefill is never stalled by a slow decode replica."""
        self._enqueue(("chunk", xfer, chunk, num_chunks, span, kv))

    def finish(self, xfer: str, num_chunks: int, first_token: int,
               bucket: int, used: int) -> None:
        """Enqueue the transfer's finalize (first token + geometry).
        Processed strictly after every chunk of the transfer — the
        send queue is FIFO — so a decode replica that has the fin has
        everything."""
        self._enqueue(
            ("fin", xfer, num_chunks, first_token, bucket, used)
        )

    def abort(self, xfer: str) -> None:
        """Fail a transfer NOW (formation died before the fin): the
        waiter unblocks immediately and the router's fallback submits
        the request cold instead of blocking out its full timeout."""
        with self._lock:
            x = self._xfers.get(xfer)
        if x is not None:
            x.failed = True
            x.done.set()

    def wait_sent(self, xfer: str, timeout_s: float | None = None) -> bool:
        """Block until the transfer's fin was sent (or it failed);
        returns True only for a fully-delivered transfer. Pops the
        sender record — a transfer is waited on exactly once (the
        prefill replica's handler, off the event loop)."""
        with self._lock:
            x = self._xfers.get(xfer)
        if x is None:
            return False
        ok = x.done.wait(
            self.timeout_s if timeout_s is None else timeout_s
        )
        with self._lock:
            self._xfers.pop(xfer, None)
        return ok and not x.failed

    def _enqueue(self, item) -> None:
        import queue

        with self._lock:
            if self._worker is None:
                self._sendq = queue.Queue()
                self._worker = threading.Thread(
                    target=self._send_loop, name="kv-push-send",
                    daemon=True,
                )
                self._worker.start()
            q = self._sendq
        q.put(item)

    def _send_loop(self) -> None:
        while True:
            item = self._sendq.get()
            kind, xfer = item[0], item[1]
            with self._lock:
                x = self._xfers.get(xfer)
            if x is None:
                continue  # transfer already reaped (timed out waiter)
            if x.failed:
                if kind == "fin":
                    x.done.set()
                continue  # drop the rest of a failed transfer
            try:
                # The kv_push_send seam: BEFORE serialization or any
                # wire byte — an injected raise exercises the exact
                # degradation contract (transfer failed, remaining
                # chunks dropped, decode replica cold-prefills).
                faults.fire("kv_push_send")
                if kind == "chunk":
                    _, _, chunk, num_chunks, span, kv = item
                    body = serialize_push_chunk(
                        xfer, chunk, num_chunks, span, kv
                    )
                    # Exact payload arithmetic (the closed form the
                    # tests assert) — header bytes excluded.
                    nbytes = sum(
                        np.asarray(a).nbytes
                        for layer in kv.values()
                        for a in layer.values()
                    )
                else:
                    _, _, num_chunks, first_token, bucket, used = item
                    body = serialize_push_fin(
                        xfer, num_chunks, first_token, bucket, used
                    )
                    nbytes = 0
                status, _ = self._transport(
                    x.host, x.port, "/kv/push", body, self.timeout_s
                )
                if status != 200:
                    raise RuntimeError(f"/kv/push answered {status}")
            except Exception as e:
                with self._lock:
                    self.push_send_failures += 1
                x.failed = True
                x.done.set()
                _log.debug(
                    "kv push to %s:%d failed (%s); decode replica "
                    "will cold-prefill", x.host, x.port, e,
                )
                continue
            with self._lock:
                if kind == "chunk":
                    self.push_sent += 1
                    self.push_bytes_sent += nbytes
            if kind == "fin":
                x.done.set()

    # -- receiver (decode replica) --------------------------------------
    def receive(self, data: bytes) -> dict:
        """Stage one pushed message (the /kv/push handler, app
        executor thread). Raises ``ValueError`` on corrupt bodies
        (counted receive failures — the sender sees the non-200 and
        fails the transfer). The ``kv_push_recv`` seam fires before
        any parse or counter mutation."""
        try:
            faults.fire("kv_push_recv")
            msg = deserialize_push(data)
        except Exception:
            with self._lock:
                self.push_recv_failures += 1
            raise
        with self._lock:
            st = self._staged.get(msg["xfer"])
            if st is None:
                st = self._staged[msg["xfer"]] = _Staged()
            self._staged.move_to_end(msg["xfer"])
            if msg["kind"] == "chunk":
                prev = st.chunks.pop(msg["chunk"], None)
                if prev is not None:
                    prev_bytes = sum(
                        a.nbytes for layer in prev.values()
                        for a in layer.values()
                    )
                    self._staged_bytes -= prev_bytes
                    st.nbytes -= prev_bytes
                st.chunks[msg["chunk"]] = msg["payload"]
                st.spans[msg["chunk"]] = msg["span"]
                st.num_chunks = msg["num_chunks"]
                st.nbytes += msg["nbytes"]
                self._staged_bytes += msg["nbytes"]
                self.push_recv += 1
                self.push_bytes_recv += msg["nbytes"]
            else:
                st.fin = msg
                st.num_chunks = msg["num_chunks"]
            # Bound what remote peers can pin in host RAM: evict the
            # LRU staged transfer (complete or not) past either cap.
            while len(self._staged) > self._STAGE_CAP or (
                self._staged_bytes > self._STAGE_BYTES_CAP
                and len(self._staged) > 1
            ):
                _, victim = self._staged.popitem(last=False)
                self._staged_bytes -= victim.nbytes
            return {"ok": True, "complete": st.complete}

    def take(self, xfer: str) -> PushedKV | None:
        """Pop a COMPLETE staged transfer and assemble the contiguous
        ``[1, bucket]`` KV (encode executor thread — host concat off
        the dispatch thread). ``None`` for unknown/incomplete
        transfers or spans that do not tile ``[0, bucket)`` exactly —
        the caller cold-prefills, counted via
        :meth:`count_fallback`."""
        with self._lock:
            st = self._staged.get(xfer)
            if st is None or not st.complete:
                return None
            self._staged.pop(xfer)
            self._staged_bytes -= st.nbytes
        bucket = st.fin["bucket"]
        order = sorted(st.spans, key=lambda i: st.spans[i][0])
        pos = 0
        for i in order:
            lo, hi = st.spans[i]
            if lo != pos:
                _log.debug(
                    "push transfer %s spans do not tile the bucket "
                    "(gap at %d); cold prefill", xfer, pos,
                )
                return None
            pos = hi
        if pos != bucket:
            _log.debug(
                "push transfer %s covers %d of %d slots; cold "
                "prefill", xfer, pos, bucket,
            )
            return None
        first = st.chunks[order[0]]
        kv = {
            ln: {
                name: np.concatenate(
                    [st.chunks[i][ln][name] for i in order], axis=1
                )
                for name in first[ln]
            }
            for ln in first
        }
        return PushedKV(
            kv, st.fin["first_token"], bucket, st.fin["used"], st.nbytes
        )

    def count_applied(self, nbytes: int) -> None:
        """A pushed transfer became a live decode row (BatchRun's
        formation installed it): THE disaggregation counter — it
        moving while ``prefix_builds``/``prefill_chunks`` stay flat
        is the zero-decode-side-prefill claim."""
        with self._lock:
            self.push_applied += 1
            self.push_bytes_applied += int(nbytes)

    def count_fallback(self) -> None:
        """A request that named a transfer cold-prefilled instead
        (incomplete/failed/drifted transfer): the degradation leg,
        counted so the fault matrix asserts it from state."""
        with self._lock:
            self.push_fallbacks += 1

    @property
    def staged_count(self) -> int:
        with self._lock:
            return len(self._staged)


def _http_post(host: str, port: int, path: str, body: bytes,
               timeout_s: float) -> tuple[int, bytes]:
    """One bounded POST against a peer replica (the push transport).
    Blocking by design — it only ever runs on the KVPush sender
    thread, never the event loop or the dispatch thread."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        conn.request(
            "POST", path, body=body,
            headers={"content-type": "application/octet-stream"},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


KVPush._transport = staticmethod(_http_post)


def _http_get(host: str, port: int, path: str,
              timeout_s: float) -> tuple[int, bytes]:
    """One bounded GET against a peer replica. Blocking by design —
    every caller runs on an encode executor thread (the same place
    the cold prefill it replaces would block), never the event loop
    or the dispatch thread."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class KVPeer:
    """Per-engine peer-fetch state: the warm-peer hint map the router
    feeds, the fetch client, the serve-side blob resolver, and the
    counters ``/metrics`` exports. Thread-safe: hints arrive from the
    event loop (header scan), fetches run on encode executor threads,
    serves on the app's executor."""

    def __init__(self, engine, *, timeout_s: float = 5.0):
        self.eng = engine
        self.timeout_s = float(timeout_s)
        self._lock = threading.Lock()
        # fp_digest(fp) -> (host, port) of the replica the router
        # last named warm for that prefix; bounded LRU. Keyed by the
        # 32-char DIGEST, not the prefix text — hints are noted from
        # the request header BEFORE any validation rejects the
        # request, so text keys would let a caller pin up to
        # hint_cap arbitrarily long strings in host RAM. The fetch
        # path only ever needs the digest (it is what rides the
        # wire), so nothing is lost.
        self._hints: collections.OrderedDict = collections.OrderedDict()
        self._hint_cap = 1024
        # Counters (exported as generate.kv_peer_*). Hits/bytes count
        # blobs APPLIED (an entry rebuilt from the fetch); misses
        # count completed fetches that yielded nothing usable (404,
        # corrupt wire body, local geometry drift); failures count
        # transport errors, non-200/404 statuses, and injected
        # ``peer_fetch`` faults — the legs that degrade to the cold
        # prefill without ever having had usable bytes.
        self.fetch_hits = 0
        self.fetch_misses = 0
        self.fetch_bytes = 0
        self.fetch_failures = 0
        self.serve_count = 0
        self.serve_bytes = 0
        # digest -> serialized wire image, small LRU. A prefix's blob
        # bytes are DETERMINISTIC per engine config (same params +
        # tokenizer -> the same stored-format KV, whether prefilled,
        # tier-restored, or re-adopted — the r13 byte-identity pins),
        # so the serialized image can be reused across peers: N-1
        # replicas fetching one hot prefix cost ONE device gather +
        # serialize, not N-1. Bounded tight (a few blobs) — this is a
        # latency cache for the hot serve path, not a store.
        self._serve_cache: collections.OrderedDict = (
            collections.OrderedDict()
        )
        self._serve_cache_cap = 4

    # -- warm-peer hints ------------------------------------------------
    def note_hint(self, fp: str, peer: str) -> None:
        """Record the router's warmth hint for ``fp``. Validated here
        (host:port shape) so a malformed header can never become a
        connect attempt later."""
        host, _, port = peer.rpartition(":")
        if not host or not port.isdigit():
            return
        key = fp_digest(fp)
        with self._lock:
            self._hints[key] = (host, int(port))
            self._hints.move_to_end(key)
            while len(self._hints) > self._hint_cap:
                self._hints.popitem(last=False)

    def hint_for(self, fp: str):
        with self._lock:
            return self._hints.get(fp_digest(fp))

    def drop_hint(self, fp: str) -> None:
        with self._lock:
            self._hints.pop(fp_digest(fp), None)

    # -- fetch (encode executor thread) ---------------------------------
    # Patch point for in-process tests and drills: (host, port, path,
    # timeout_s) -> (status, body).
    _transport = staticmethod(_http_get)

    def fetch(self, fp: str):
        """Fetch ``fp``'s blob from its hinted warm peer, or ``None``
        (no hint / miss / failure — every ``None`` means the caller
        goes cold). The ``peer_fetch`` fault point fires before any
        wire byte moves. Returns an UNVALIDATED-against-local-geometry
        blob — the caller applies the same ``_plan`` check every tier
        blob passes and reports the outcome via
        :meth:`count_applied` / :meth:`count_miss`."""
        digest = fp_digest(fp)
        with self._lock:
            hint = self._hints.get(digest)
        if hint is None:
            return None
        host, port = hint
        try:
            faults.fire("peer_fetch")
            status, body = self._transport(
                host, port, f"/kv/prefix?fp={digest}",
                self.timeout_s,
            )
        except Exception as e:
            with self._lock:
                self.fetch_failures += 1
            _log.debug(
                "peer fetch from %s:%d failed (%s); cold path",
                host, port, e,
            )
            return None
        if status == 404:
            # The peer is not warm after all (evicted, restarted):
            # drop the hint so the next miss does not re-pay the hop.
            with self._lock:
                self.fetch_misses += 1
                self._hints.pop(digest, None)
            return None
        if status != 200:
            with self._lock:
                self.fetch_failures += 1
            _log.debug(
                "peer %s:%d answered %d for a KV fetch; cold path",
                host, port, status,
            )
            return None
        try:
            return deserialize_blob(fp, body)
        except Exception as e:
            # ValueError is the documented corruption signal, but the
            # contract here is the CALLER's: any body that does not
            # parse is a counted miss and a cold prefill — never an
            # exception escaping into the user's request.
            with self._lock:
                self.fetch_misses += 1
            _log.debug("corrupt peer blob dropped as a miss: %s", e)
            return None

    def count_applied(self, nbytes: int) -> None:
        """The fetched blob rebuilt an entry: the fetch is a hit and
        its exact payload bytes count."""
        with self._lock:
            self.fetch_hits += 1
            self.fetch_bytes += int(nbytes)

    def count_miss(self) -> None:
        """The fetched blob can never apply here (geometry drift vs
        what a local build would produce today): a miss, like a
        corrupt body — the bytes were real, just not ours."""
        with self._lock:
            self.fetch_misses += 1

    # -- serve (app executor thread) ------------------------------------
    def serve_wire(self, digest: str) -> bytes | None:
        """Resolve a fingerprint digest against this replica's warm
        state and return the blob's wire bytes, or ``None`` (404).
        Sources, warmest-cheapest first: the host tier's blob (already
        page-shaped host numpy — no device work), else the prefix
        dict's device-resident entry gathered via its contiguous KV
        (never donated, safe from any thread). The ``peer_serve``
        fault point fires before anything is resolved; counters move
        only after serialization succeeds."""
        from mlapi_tpu.serving.kv_tier import (
            payload_bytes,
            payload_from_contiguous,
        )

        faults.fire("peer_serve")
        with self._lock:
            cached = self._serve_cache.get(digest)
            if cached is not None:
                self._serve_cache.move_to_end(digest)
                self.serve_count += 1
                self.serve_bytes += cached[1]
                return cached[0]
        eng = self.eng
        tier = getattr(eng, "kv_tier", None)
        fp = None
        if tier is not None:
            fp = next(
                (
                    f for f in tier.fingerprints()
                    if isinstance(f, str) and fp_digest(f) == digest
                ),
                None,
            )
        blob = None
        if fp is not None:
            blob = tier.lookup(fp, count=False)
            if blob is not None and blob.bucket is None:
                # Spilled before any entry registration recorded its
                # metadata: a peer cannot rebuild an entry from it —
                # fall through to the entry scan below.
                blob = None
        if blob is None:
            # Snapshot under the lock, hash OUTSIDE it: every
            # /generate request's entry() fast path takes this same
            # lock, and hashing N full prefix texts under it would
            # serialize encode threads behind every peer probe.
            with eng.prefix._lock:
                candidates = list(eng.prefix._entries.items())
            entry = next(
                (e for f, e in candidates if fp_digest(f) == digest),
                None,
            )
            if entry is None:
                return None
            from mlapi_tpu.serving.kv_tier import KVTierBlob

            page = eng.pool.page if eng.pool is not None else entry.bucket
            payload = payload_from_contiguous(entry.kv, page)
            blob = KVTierBlob(
                entry.fp, payload, page, payload_bytes(payload),
                entry.bucket, entry.lo, entry.used,
            )
        data = serialize_blob(blob)
        with self._lock:
            self._serve_cache[digest] = (data, blob.nbytes)
            self._serve_cache.move_to_end(digest)
            while len(self._serve_cache) > self._serve_cache_cap:
                self._serve_cache.popitem(last=False)
            self.serve_count += 1
            self.serve_bytes += blob.nbytes
        return data
