"""Many-adapter LoRA serving: one HBM-resident base, paged adapters.

Training already composes LoRA (``models/lora.py``) and exports a
MERGED tree — which serves fine, but costs a full model replica per
fine-tuned variant. This module is the serving half of ROADMAP item
3's many-tenant story: the base model's params stay resident ONCE,
and each tenant contributes only its tiny ``(A, B)`` pair, so N
resident tenants cost exactly ``base_bytes + N × slot_bytes`` (closed
dtype/shape arithmetic, asserted in the tests — never wall-clock).

Three tiers, coldest to hottest, each generalizing an existing
mechanism rather than inventing one:

- :class:`AdapterPeer` — the fleet tier (``serving/kv_peer.py``
  mechanics): a cold adapter is fetched from the HRW-preferred peer
  over ``GET /adapter/<id>`` in the same geometry-header +
  raw-leaves framing as ``GET /kv/prefix``; corruption classes are
  counted misses, never installed.
- :class:`AdapterStore` — the host tier (``serving/kv_tier.py``
  mechanics): registered/fetched adapter payloads under an LRU bytes
  budget, optionally spilled to disk as their exact wire image.
- :class:`AdapterSlots` — the device tier (``serving/paged_pool.py``
  mechanics): a fixed pool of ``S + 1`` adapter slots per target
  kernel — slot 0 is the permanently-zero NULL slot, so base-only
  rows in a mixed batch gather an exactly-zero delta — installed via
  one donated scatter with the r12 poisoned-pool discipline and
  evicted LRU under pressure.

Batched application (``serving/batch_run.py``) augments the params
pytree per dispatch: every ``layer_{n}`` dict gains a ``"lora"``
sub-dict holding the full per-target slot pools plus either a scalar
``"slot"`` (grouped batch — one ``x @ A @ B`` per block) or a
per-row ``"rows"`` vector (mixed tenants — the gathered BGMV path,
``ops/bgmv.py``). The pytree-structure difference keys separate
compiled traces; plain params pass through untouched, so a build
with no adapter traffic runs byte-identical programs.

Threading discipline (the donation rule, same as the page pool):
only the dispatch thread installs into or evicts from the slot pool
— the donated install scatter consumes the pool arrays, and a
concurrent reader would die on deleted buffers. Encode executor
threads resolve ids against the HOST store (fetching from a peer on
a miss); the dispatch thread turns store blobs into resident slots
at batch formation/admission. ``/metrics`` reads only lock-guarded
host counters.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import re
import threading

import numpy as np

from mlapi_tpu.serving import faults
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("serving.adapter_store")

WIRE_VERSION = 1
# Header line length cap, same rationale as the KV peer wire: a few
# dozen layers of leaf manifests fit in a few KB; anything larger is
# a corrupt/hostile response, refused before allocation.
_MAX_HEADER_BYTES = 1 << 20

# Adapter ids ride URL paths, HTTP headers, and disk filenames raw —
# the grammar is locked down so none of those channels needs escaping
# (and a hostile id can never traverse paths or split headers).
ADAPTER_ID_RE = re.compile(r"^[A-Za-z0-9_.-]{1,64}$")


class AdapterUnavailable(RuntimeError):
    """A request named an adapter this replica cannot resolve — not
    registered, not in the host store, and not fetchable from a warm
    peer. Surfaced as the request's terminal error (HTTP 404): the
    caller named a tenant that does not exist here, which is their
    bug or a fleet-provisioning gap, never something to paper over
    with silent base-model output."""


class AdapterSlotsExhausted(RuntimeError):
    """No free adapter slot and every resident adapter is held by a
    live batch: the slot pool is sized too small for the offered
    tenant concurrency — a capacity-planning signal, surfaced loudly
    (the same contract as ``PagePoolExhausted``) with nothing
    half-installed."""


class AdapterPoolPoisoned(RuntimeError):
    """A donated slot-install scatter failed DURING execution: the
    pool arrays were consumed and never rebound, so no fallback path
    may read them (the r12 formation-poisoning bug class, applied to
    adapter pools)."""


def adapter_bytes(payload: dict) -> int:
    """Exact adapter bytes from dtype/shape arithmetic — the closed
    form every counter and the tests assert; never wall-clock."""
    return sum(
        int(np.prod(ab[k].shape)) * ab[k].dtype.itemsize
        for layer in payload.values()
        for ab in layer.values()
        for k in ("a", "b")
    )


def adapter_rank(payload: dict) -> int:
    """The payload's LoRA rank (``a`` is ``[d_in, r]``)."""
    for layer in payload.values():
        for ab in layer.values():
            return int(ab["a"].shape[1])
    raise ValueError("empty adapter payload")


def serialize_adapter(aid: str, payload: dict) -> bytes:
    """An adapter payload → wire bytes: one JSON header line —
    ``{"v": 1, "adapter", "rank", "nbytes", "leaves": [[layer,
    target, ab, shape, dtype], ...]}`` — followed by each leaf's raw
    C-order bytes in header order (the ``GET /kv/prefix`` framing,
    applied to adapter weights). The payload is the CANONICAL
    effective pair — ``b`` pre-scaled by alpha/rank at registration —
    so the delta is exactly ``x @ a @ b`` with no scale riding the
    wire."""
    leaves = []
    chunks = []
    for ln in sorted(payload):
        for target in sorted(payload[ln]):
            for ab in ("a", "b"):
                arr = np.ascontiguousarray(payload[ln][target][ab])
                leaves.append([ln, target, ab, list(arr.shape), arr.dtype.str])
                chunks.append(arr.tobytes())
    header = json.dumps(
        {
            "v": WIRE_VERSION,
            "adapter": aid,
            "rank": adapter_rank(payload),
            "nbytes": adapter_bytes(payload),
            "leaves": leaves,
        }
    ).encode()
    return header + b"\n" + b"".join(chunks)


def deserialize_adapter(aid: str, data: bytes):
    """Wire bytes → ``(payload, rank, nbytes)`` for ``aid``. Raises
    ``ValueError`` on ANY inconsistency — unparseable header, an
    adapter id that does not match the one requested, ``a``/``b``
    shapes that are not ``[d, r]`` / ``[r, d]`` at one consistent
    rank, a leaf whose size disagrees with its manifest, trailing
    bytes, or a total that disagrees with the header's ``nbytes`` —
    so a corrupt wire response (or stale disk file) is dropped as a
    counted miss, never installed."""
    nl = data.find(b"\n", 0, _MAX_HEADER_BYTES)
    if nl < 0:
        raise ValueError("no header line in adapter blob")
    try:
        head = json.loads(data[:nl])
    except Exception as e:
        raise ValueError(f"unparseable adapter header: {e}") from None
    if not isinstance(head, dict) or head.get("v") != WIRE_VERSION:
        raise ValueError(f"unknown adapter blob version {head!r:.80}")
    try:
        wire_aid = head["adapter"]
        if aid is not None and wire_aid != aid:
            raise ValueError(
                f"blob names adapter {wire_aid!r:.80}, wanted {aid!r}"
            )
        rank = int(head["rank"])
        if rank < 1:
            raise ValueError("rank must be >= 1")
        nbytes = int(head["nbytes"])
        leaves = head["leaves"]
        if not isinstance(leaves, list) or not leaves:
            raise ValueError("leaf manifest is not a non-empty list")
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(f"incomplete adapter header: {e}") from None
    payload: dict = {}
    off = nl + 1
    total = 0
    for leaf in leaves:
        try:
            ln, target, ab, shape, dtype = leaf
            shape = tuple(int(s) for s in shape)
            dt = np.dtype(dtype)
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad leaf manifest {leaf!r:.80}: {e}") from None
        if ab not in ("a", "b"):
            raise ValueError(f"leaf {ln}/{target} kind {ab!r:.20} not a|b")
        # Non-positive dims refused for the same reason as the KV
        # wire: a negative dim defeats the truncation check below.
        if (
            len(shape) != 2
            or any(s <= 0 for s in shape)
            or (ab == "a" and shape[1] != rank)
            or (ab == "b" and shape[0] != rank)
        ):
            raise ValueError(
                f"leaf {ln}/{target}/{ab} shape {shape} is not a rank-"
                f"{rank} {'[d, r]' if ab == 'a' else '[r, d]'} matrix"
            )
        size = int(np.prod(shape)) * dt.itemsize
        if off + size > len(data):
            raise ValueError("truncated adapter payload")
        tgt = payload.setdefault(ln, {}).setdefault(target, {})
        if ab in tgt:
            raise ValueError(f"duplicate leaf {ln}/{target}/{ab}")
        tgt[ab] = np.frombuffer(
            data, dtype=dt, count=int(np.prod(shape)), offset=off
        ).reshape(shape)
        off += size
        total += size
    for ln, layer in payload.items():
        for target, ab in layer.items():
            if "a" not in ab or "b" not in ab:
                raise ValueError(f"leaf {ln}/{target} missing a or b")
    if off != len(data):
        raise ValueError("trailing bytes after adapter payload")
    if total != nbytes:
        raise ValueError(
            f"adapter payload is {total} bytes, header says {nbytes}"
        )
    return payload, rank, nbytes


def save_adapter(path: str, aid: str, payload: dict) -> int:
    """Write an adapter artifact: the file IS the wire image, so the
    CLI's ``--adapter id=path``, the disk-backed store, and the peer
    wire all share one format and one validator. Returns the payload
    bytes (header excluded — the closed form)."""
    data = serialize_adapter(aid, payload)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)
    return adapter_bytes(payload)


def load_adapter(path: str):
    """Read + validate an adapter artifact → ``(aid, payload, rank,
    nbytes)``. Raises ``ValueError`` on any corruption (same
    validator as the wire)."""
    with open(path, "rb") as f:
        data = f.read()
    nl = data.find(b"\n", 0, _MAX_HEADER_BYTES)
    if nl < 0:
        raise ValueError(f"no header line in adapter file {path!r}")
    try:
        aid = json.loads(data[:nl]).get("adapter")
    except Exception as e:
        raise ValueError(f"unparseable adapter file {path!r}: {e}") from None
    if not isinstance(aid, str) or not ADAPTER_ID_RE.match(aid):
        raise ValueError(f"bad adapter id in file {path!r}: {aid!r:.80}")
    payload, rank, nbytes = deserialize_adapter(aid, data)
    return aid, payload, rank, nbytes


class _StoredAdapter:
    """Index record: payload in RAM or a wire-image path on disk."""

    __slots__ = ("payload", "path", "rank", "nbytes")

    def __init__(self, payload, path, rank, nbytes):
        self.payload = payload      # None when disk-backed
        self.path = path            # None when RAM-resident
        self.rank = rank
        self.nbytes = nbytes


class AdapterStore:
    """LRU bytes-budgeted host store of adapter payloads, keyed by
    adapter id — the ``KVTier`` mechanics applied to weights instead
    of KV. Thread-safe: encode executor threads stage peer fetches
    and resolve ids concurrently with CLI/HTTP registration and the
    dispatch thread's install reads."""

    def __init__(self, max_bytes: int, disk_dir: str | None = None):
        if max_bytes <= 0:
            raise ValueError(
                f"adapter_store_bytes must be > 0, got {max_bytes}"
            )
        self.max_bytes = int(max_bytes)
        self.disk_dir = disk_dir
        if disk_dir:
            os.makedirs(disk_dir, exist_ok=True)
            self._sweep_stale(disk_dir)
        self._lock = threading.Lock()
        # aid -> _StoredAdapter, LRU-ordered (front = coldest).
        self._blobs: collections.OrderedDict = collections.OrderedDict()
        self._bytes = 0
        self._seq = 0
        self.evictions = 0

    @staticmethod
    def _sweep_stale(disk_dir: str) -> None:
        """Unlink adapter files left by DEAD former owners (filenames
        are pid-scoped and the index is per-process — same restart-
        loop hygiene as ``KVTier._sweep_stale``). Live siblings and
        unparseable names are left alone."""
        for name in os.listdir(disk_dir):
            if not (name.startswith("adstore-") and name.endswith(".bin")):
                continue
            try:
                pid = int(name.split("-")[1])
            except (IndexError, ValueError):
                continue
            if pid == os.getpid():
                continue
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                try:
                    os.unlink(os.path.join(disk_dir, name))
                    _log.debug("swept stale adapter blob %s", name)
                except OSError:
                    pass
            except OSError:
                pass  # EPERM etc.: a live process we can't signal

    # -- accounting ----------------------------------------------------
    @property
    def bytes_in_use(self) -> int:
        with self._lock:
            return self._bytes

    @property
    def entries(self) -> int:
        with self._lock:
            return len(self._blobs)

    def has(self, aid: str) -> bool:
        with self._lock:
            return aid in self._blobs

    def ids(self) -> list:
        with self._lock:
            return list(self._blobs)

    # -- registration --------------------------------------------------
    def put(self, aid: str, payload: dict) -> int:
        """Register ``aid``'s payload (replacing any prior blob),
        evicting LRU blobs past the bytes budget. Disk mode registers
        RAM-resident first and moves the wire image to its file AFTER
        releasing the lock — the write must not block concurrent
        lookups; a blob replaced or evicted mid-write just unlinks
        the fresh file (same swap discipline as ``KVTier``)."""
        nbytes = adapter_bytes(payload)
        rank = adapter_rank(payload)
        with self._lock:
            old = self._blobs.pop(aid, None)
            if old is not None:
                self._discard_locked(old)
            if nbytes > self.max_bytes:
                # Can't ever fit: count it as an eviction of itself
                # rather than thrashing the whole store out.
                self.evictions += 1
                _log.debug(
                    "adapter %r (%d bytes) exceeds the %d-byte budget; "
                    "not stored", aid, nbytes, self.max_bytes,
                )
                return nbytes
            path = None
            if self.disk_dir:
                path = os.path.join(
                    self.disk_dir, f"adstore-{os.getpid()}-{self._seq}.bin"
                )
                self._seq += 1
            stored = _StoredAdapter(payload, None, rank, nbytes)
            self._blobs[aid] = stored
            self._bytes += nbytes
            while self._bytes > self.max_bytes and len(self._blobs) > 1:
                _, victim = self._blobs.popitem(last=False)  # LRU
                self._discard_locked(victim)
                self.evictions += 1
        if path is not None:
            try:
                data = serialize_adapter(aid, payload)
                with open(path, "wb") as f:
                    f.write(data)
            except Exception as e:
                _log.debug("adapter disk write failed (%s); RAM blob", e)
                return nbytes
            with self._lock:
                live = self._blobs.get(aid)
                if live is stored and live.payload is payload:
                    live.path = path
                    live.payload = None
                else:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
        return nbytes

    def drop(self, aid: str) -> None:
        """Forget ``aid``'s blob (no-op if absent): an install proved
        it can never apply to the live model (shape/rank drift), so
        keeping it would repeat the failed validation on every
        request. Not counted as an eviction (`evictions` measures
        budget pressure, not invalidation)."""
        with self._lock:
            stored = self._blobs.pop(aid, None)
            if stored is not None:
                self._discard_locked(stored)
                _log.debug("dropped inapplicable adapter blob %r", aid)

    def _discard_locked(self, stored: _StoredAdapter) -> None:
        self._bytes -= stored.nbytes
        if stored.path is not None:
            try:
                os.unlink(stored.path)
            except OSError:
                pass

    # -- lookup --------------------------------------------------------
    def get(self, aid: str):
        """``(payload, rank, nbytes)`` for ``aid`` (LRU-touched),
        loaded back from disk if spilled, or ``None``. A vanished or
        corrupt disk file is a miss, not a crash — dropped from the
        index unless a concurrent re-put already replaced it."""
        with self._lock:
            stored = self._blobs.get(aid)
            if stored is None:
                return None
            self._blobs.move_to_end(aid)
            payload = stored.payload
            path = stored.path
            rank = stored.rank
            nbytes = stored.nbytes
        if payload is None:
            try:
                with open(path, "rb") as f:
                    data = f.read()
                payload, rank, nbytes = deserialize_adapter(aid, data)
            except Exception as e:
                _log.debug("adapter disk blob unreadable (%s); dropping", e)
                with self._lock:
                    if self._blobs.get(aid) is stored:
                        self._blobs.pop(aid)
                        self._discard_locked(stored)
                return None
        return payload, rank, nbytes


@functools.cache
def _install_fn():
    """Jitted slot-install scatter: write one adapter's ``(a, b)``
    pair into slot row ``slot`` across every layer/target pool. The
    pools are DONATED — the updated arrays replace them in place, so
    an install never doubles the pool's HBM footprint (the page
    pool's adopt-scatter discipline, applied to weights)."""
    import jax

    def _run(pools, payload, slot):
        return {
            ln: {
                target: {
                    ab: leaf.at[slot].set(
                        payload[ln][target][ab].astype(leaf.dtype)
                    )
                    for ab, leaf in pair.items()
                }
                for target, pair in layer.items()
            }
            for ln, layer in pools.items()
        }

    return jax.jit(_run, donate_argnums=(0,))


class AdapterSlots:
    """The device-resident adapter slot pool: per layer and adapted
    target, one ``a [S+1, d_in, r]`` and one ``b [S+1, r, d_out]``
    array, where slot 0 is the permanently-zero NULL slot (base-only
    rows in a mixed batch index it and gather an exactly-zero delta)
    and slots ``1..S`` hold resident tenants, evicted LRU when no
    live batch holds them.

    Pools materialize lazily at the FIRST install — the engine-wide
    rank is whatever that first adapter carries (slot arrays force
    one rank; a later mismatch is rejected loudly). Targets are the
    intersection of ``models/lora.py`` ``DEFAULT_TARGETS`` with what
    the model's ``layer_0`` actually holds, dtype follows the base
    kernel. Only the dispatch thread installs or evicts (the donated
    scatter consumes the pool arrays — the page-pool donation rule);
    ``lock`` guards the host-side maps for /metrics' and the
    scheduler's cross-thread reads."""

    def __init__(self, engine, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"adapter_slots must be >= 1, got {n_slots}")
        self.eng = engine
        self.n_slots = int(n_slots)
        self.lock = threading.Lock()
        self.rank: int | None = None
        # {layer: {target: {"a": [S+1, d_in, r], "b": [S+1, r, d_out]}}}
        # — None until the first install fixes the rank.
        self.pools = None
        self._slot_of: collections.OrderedDict = collections.OrderedDict()
        self._holds: dict[str, int] = {}
        self._free: list[int] = list(range(self.n_slots, 0, -1))
        self.installs = 0
        self.evictions = 0

    # -- accounting ----------------------------------------------------
    @property
    def slots_total(self) -> int:
        return self.n_slots

    @property
    def slots_in_use(self) -> int:
        with self.lock:
            return len(self._slot_of)

    def resident(self, aid: str) -> bool:
        with self.lock:
            return aid in self._slot_of

    def slot_bytes(self) -> int:
        """One slot's exact bytes — the per-tenant increment in the
        ``base_bytes + N × slot_bytes`` amortization gauge — from
        dtype/shape arithmetic over one slot row of every pool leaf.
        0 until the first install materializes the pools."""
        if self.pools is None:
            return 0
        return sum(
            int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
            for layer in self.pools.values()
            for pair in layer.values()
            for leaf in pair.values()
        )

    # -- scheduler gate ------------------------------------------------
    def can_claim(self, aids) -> bool:
        """Worst-case admission check for the scheduler's reservation
        gate: could every adapter in ``aids`` be made resident RIGHT
        NOW — resident already, or a free slot, or an LRU-evictable
        (hold-free, not itself requested) slot for each one that is
        not? A group that fails this gate defers instead of starting
        a lane that would die on ``AdapterSlotsExhausted``
        mid-formation."""
        need = set(aids)
        with self.lock:
            missing = sum(1 for a in need if a not in self._slot_of)
            if missing == 0:
                return True
            free = len(self._free)
            evictable = sum(
                1 for a in self._slot_of
                if a not in need and self._holds.get(a, 0) == 0
            )
            return missing <= free + evictable

    # -- resolution (dispatch thread) -----------------------------------
    def acquire(self, aid: str, store: AdapterStore | None) -> int:
        """Resolve ``aid`` to a resident slot — installing from the
        host store on a miss — and bump its hold count (a held
        adapter is pinned against eviction until :meth:`release`).
        Dispatch thread only. Raises :class:`AdapterUnavailable`
        when the store has no blob (or the blob cannot apply to this
        model) and :class:`AdapterSlotsExhausted` when no slot can
        be freed — in both cases with nothing half-installed and
        every hold unchanged."""
        with self.lock:
            slot = self._slot_of.get(aid)
            if slot is not None:
                self._holds[aid] = self._holds.get(aid, 0) + 1
                self._slot_of.move_to_end(aid)
                return slot
        got = store.get(aid) if store is not None else None
        if got is None:
            raise AdapterUnavailable(
                f"adapter {aid!r} is not registered on this replica"
            )
        payload, rank, _ = got
        try:
            slot = self.install(aid, payload, rank)
        except ValueError as e:
            # Shape/rank drift against the live model: the blob can
            # NEVER apply — drop it so the next request 404s fast
            # instead of re-validating, and surface the why.
            if store is not None:
                store.drop(aid)
            raise AdapterUnavailable(
                f"adapter {aid!r} does not fit this model: {e}"
            ) from None
        with self.lock:
            self._holds[aid] = self._holds.get(aid, 0) + 1
        return slot

    def release(self, aid: str) -> None:
        """Drop one hold on ``aid`` (batch teardown). Loud on a
        double-release — same contract as the page pool's refcount
        assert: a silent negative hold would let a live batch's
        adapter be evicted under it."""
        with self.lock:
            held = self._holds.get(aid, 0)
            assert held > 0, f"adapter hold double-release for {aid!r}"
            self._holds[aid] = held - 1

    def install(self, aid: str, payload: dict, rank: int) -> int:
        """Install ``payload`` into a slot (dispatch thread only):
        materialize the pools on first use, validate every leaf
        against the model's kernels, fire the ``adapter_install``
        fault seam, allocate a slot — free list first, else evict the
        LRU hold-free resident, else raise
        :class:`AdapterSlotsExhausted` — then run ONE donated scatter.
        The aid→slot mapping is published only after the scatter
        returns, so a failure at any point leaves nothing
        half-installed; a failure DURING the donated program poisons
        the pool loudly (:class:`AdapterPoolPoisoned`)."""
        if self.pools is None:
            self._materialize(rank)
        if rank != self.rank:
            raise ValueError(
                f"rank {rank} adapter in a rank-{self.rank} slot pool "
                f"(the engine's rank is fixed by the first install)"
            )
        self._validate(aid, payload)
        # Fired BEFORE the slot allocation (MLA003): an injected
        # failure here must land on untouched state — no slot popped,
        # no victim evicted — so the drill exercises the clean reject,
        # not a rollback.
        faults.fire("adapter_install")
        with self.lock:
            if aid in self._slot_of:
                return self._slot_of[aid]
            if self._free:
                slot = self._free.pop()
            else:
                victim = next(
                    (
                        a for a in self._slot_of
                        if self._holds.get(a, 0) == 0
                    ),
                    None,
                )
                if victim is None:
                    raise AdapterSlotsExhausted(
                        f"all {self.n_slots} adapter slots are held by "
                        f"live batches; cannot install {aid!r}"
                    )
                slot = self._slot_of.pop(victim)
                self._holds.pop(victim, None)
                self.evictions += 1
                _log.debug(
                    "evicted adapter %r from slot %d for %r",
                    victim, slot, aid,
                )
        try:
            dev = {
                ln: {
                    target: {
                        ab: np.ascontiguousarray(pair[ab])
                        for ab in ("a", "b")
                    }
                    for target, pair in payload[ln].items()
                }
                for ln in self.pools
            }
            self.pools = _install_fn()(
                self.pools, dev, np.int32(slot)
            )
        except BaseException as e:
            first = next(
                leaf
                for layer in self.pools.values()
                for pair in layer.values()
                for leaf in pair.values()
            )
            if getattr(first, "is_deleted", lambda: False)():
                raise AdapterPoolPoisoned(
                    f"adapter slot pool consumed by a failed install "
                    f"({e}); no fallback may read it"
                ) from e
            with self.lock:
                self._free.append(slot)
            raise
        with self.lock:
            self._slot_of[aid] = slot
            self._slot_of.move_to_end(aid)
            self.installs += 1
        return slot

    def _materialize(self, rank: int) -> None:
        """Build the zero-filled pools: ``S + 1`` slots per adapted
        target, dtype following the base kernel, replicated across
        the mesh when the base is sharded (adapters are tiny — the
        ``models/lora.py`` sharding stance). Slot 0 stays all-zero
        forever: it is never allocated, and base rows in a gathered
        batch read their exactly-zero delta from it."""
        import jax
        import jax.numpy as jnp

        from mlapi_tpu.models.lora import DEFAULT_TARGETS, _kernel_of

        params = self.eng.params
        layers = sorted(
            (k for k in params if k.startswith("layer_")),
            key=lambda k: int(k.split("_")[1]),
        )
        if not layers:
            raise ValueError("model params hold no layer_{n} blocks")
        targets = [
            t for t in DEFAULT_TARGETS
            if t in params[layers[0]]
            and _kernel_of(params[layers[0]][t]) is not None
        ]
        if not targets:
            raise ValueError(
                f"no LoRA targets among {DEFAULT_TARGETS} in the model"
            )
        kernel0 = _kernel_of(params[layers[0]][targets[0]])
        sh = getattr(kernel0, "sharding", None)
        if isinstance(sh, jax.sharding.NamedSharding):
            rep = jax.sharding.NamedSharding(
                sh.mesh, jax.sharding.PartitionSpec()
            )

            def _place(x):
                return jax.device_put(x, rep)
        else:
            def _place(x):
                return x

        pools: dict = {}
        for ln in layers:
            pools[ln] = {}
            for t in targets:
                kernel = _kernel_of(params[ln][t])
                d_in, d_out = kernel.shape
                dt = kernel.dtype
                pools[ln][t] = {
                    "a": _place(
                        jnp.zeros((self.n_slots + 1, d_in, rank), dt)
                    ),
                    "b": _place(
                        jnp.zeros((self.n_slots + 1, rank, d_out), dt)
                    ),
                }
        self.pools = pools
        self.rank = int(rank)

    def _validate(self, aid: str, payload: dict) -> None:
        """Every pool leaf must have its counterpart in the payload
        at the kernel's exact shape — and nothing extra. A mismatch
        means the adapter was trained against a different
        architecture; installing a subset silently would serve a
        tenant HALF their fine-tune."""
        for ln, layer in self.pools.items():
            got = payload.get(ln)
            if got is None:
                raise ValueError(f"adapter {aid!r} missing layer {ln}")
            for target, pair in layer.items():
                p = got.get(target)
                if p is None:
                    raise ValueError(
                        f"adapter {aid!r} missing {ln}/{target}"
                    )
                for ab in ("a", "b"):
                    want = pair[ab].shape[1:]
                    have = tuple(p[ab].shape)
                    if want != have:
                        raise ValueError(
                            f"adapter {aid!r} {ln}/{target}/{ab} shape "
                            f"{have} != model's {tuple(want)}"
                        )
        extra = {
            (ln, t)
            for ln, layer in payload.items()
            for t in layer
            if ln not in self.pools or t not in self.pools[ln]
        }
        if extra:
            raise ValueError(
                f"adapter {aid!r} carries leaves the model does not "
                f"adapt: {sorted(extra)[:4]}"
            )

    # -- params augmentation (dispatch thread) --------------------------
    def batch_params(self, params: dict, *, slot=None, rows=None):
        """The per-dispatch params pytree for an adapter-carrying
        batch: each ``layer_{n}`` dict gains a ``"lora"`` sub-dict of
        the full per-target pools plus the batch's marker — a scalar
        ``"slot"`` (grouped: every row one tenant) or an int32
        ``"rows"`` vector (gathered BGMV: per-row slot indices, 0 for
        base rows). Shallow dicts only — no device work here; the
        marker's pytree structure keys the grouped/gathered traces
        apart, and plain params (no adapters) never pass through this
        method at all, so the no-adapter programs stay
        byte-identical."""
        import jax.numpy as jnp

        mark = (
            {"slot": jnp.asarray(slot, jnp.int32)}
            if rows is None
            else {"rows": jnp.asarray(rows, jnp.int32)}
        )
        out = dict(params)
        for ln, layer_pools in self.pools.items():
            layer = dict(params[ln])
            layer["lora"] = {**layer_pools, **mark}
            out[ln] = layer
        return out


class AdapterPeer:
    """Fleet-tier adapter fetch (the ``KVPeer`` mechanics): the
    router's warm-peer hint names where a tenant's adapter (and its
    prefixes) live; a cold replica pulls the adapter's wire blob
    from there instead of 404ing the tenant. Thread-safe: hints
    arrive from the event loop, fetches run on encode executor
    threads, serves on the app executor."""

    def __init__(self, engine, *, timeout_s: float = 5.0):
        self.eng = engine
        self.timeout_s = float(timeout_s)
        self._lock = threading.Lock()
        # aid -> (host, port); bounded LRU. Keyed by the id itself —
        # the grammar (ADAPTER_ID_RE) already bounds it to 64 safe
        # chars, so no digesting is needed.
        self._hints: collections.OrderedDict = collections.OrderedDict()
        self._hint_cap = 1024
        # Counters (exported as generate.adapter_fetch_*). Hits/bytes
        # count blobs STAGED into the local store; misses count
        # completed fetches that yielded nothing usable (404, corrupt
        # body); failures count transport errors and injected
        # ``adapter_fetch`` faults.
        self.fetch_hits = 0
        self.fetch_misses = 0
        self.fetch_bytes = 0
        self.fetch_failures = 0
        self.serve_count = 0
        self.serve_bytes = 0

    # -- warm-peer hints ------------------------------------------------
    def note_hint(self, aid: str, peer: str) -> None:
        """Record the router's warmth hint for ``aid``. Validated
        here (id grammar + host:port shape) so a malformed header can
        never become a connect attempt later."""
        if not ADAPTER_ID_RE.match(aid or ""):
            return
        host, _, port = peer.rpartition(":")
        if not host or not port.isdigit():
            return
        with self._lock:
            self._hints[aid] = (host, int(port))
            self._hints.move_to_end(aid)
            while len(self._hints) > self._hint_cap:
                self._hints.popitem(last=False)

    def hint_for(self, aid: str):
        with self._lock:
            return self._hints.get(aid)

    # -- fetch (encode executor thread) ---------------------------------
    # Patch point for in-process tests and drills: (host, port, path,
    # timeout_s) -> (status, body). Shares the KV peer's transport.
    _transport = None  # set below

    def fetch(self, aid: str):
        """Fetch ``aid``'s blob from its hinted warm peer, or
        ``None`` (no hint / miss / failure — every ``None`` means the
        caller falls through to :class:`AdapterUnavailable`). The
        ``adapter_fetch`` fault point fires before any wire byte
        moves or counter mutates. Returns ``(payload, rank, nbytes)``
        validated against the WIRE manifest only — the model-shape
        check happens at install, where a drift is counted as the
        same class of miss."""
        with self._lock:
            hint = self._hints.get(aid)
        if hint is None:
            return None
        host, port = hint
        try:
            faults.fire("adapter_fetch")
            status, body = self._transport(
                host, port, f"/adapter/{aid}", self.timeout_s
            )
        except Exception as e:
            with self._lock:
                self.fetch_failures += 1
            _log.debug(
                "adapter fetch from %s:%d failed (%s); unavailable",
                host, port, e,
            )
            return None
        if status == 404:
            # The peer is not warm after all (evicted, restarted):
            # drop the hint so the next miss does not re-pay the hop.
            with self._lock:
                self.fetch_misses += 1
                self._hints.pop(aid, None)
            return None
        if status != 200:
            with self._lock:
                self.fetch_failures += 1
            _log.debug(
                "peer %s:%d answered %d for an adapter fetch",
                host, port, status,
            )
            return None
        try:
            payload, rank, nbytes = deserialize_adapter(aid, body)
        except Exception as e:
            with self._lock:
                self.fetch_misses += 1
            _log.debug("corrupt adapter blob dropped as a miss: %s", e)
            return None
        with self._lock:
            self.fetch_hits += 1
            self.fetch_bytes += nbytes
        return payload, rank, nbytes

    # -- serve (app executor thread) ------------------------------------
    def serve_wire(self, aid: str) -> bytes | None:
        """Resolve ``aid`` against this replica's HOST store and
        return the wire image, or ``None`` (404). The device slot
        pool is deliberately NOT a source: its arrays are donated by
        dispatch-thread installs, and every resident adapter entered
        through the store anyway. The ``peer_serve``-analogous
        ``adapter_fetch`` grammar lives on the FETCH side; serves
        fire no fault of their own beyond the handler's."""
        store = getattr(self.eng, "adapter_store", None)
        if store is None:
            return None
        got = store.get(aid)
        if got is None:
            return None
        payload, _, nbytes = got
        data = serialize_adapter(aid, payload)
        with self._lock:
            self.serve_count += 1
            self.serve_bytes += nbytes
        return data


def _default_transport(host, port, path, timeout_s):
    from mlapi_tpu.serving.kv_peer import _http_get

    return _http_get(host, port, path, timeout_s)


AdapterPeer._transport = staticmethod(_default_transport)
