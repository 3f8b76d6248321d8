"""Block-granular KV page pool for generative serving.

The contiguous engine allocates one ``[B, total]`` cache per batch,
sized to the batch's whole TIER: every sequence pays for its padded
tier length, batch growth/compaction GATHER the full cache bytes, and
a shared prefix is broadcast-copied into every row. This module is the
host half of the paged replacement (the vLLM/PagedAttention move,
landed on this repo's flash-decode layout): device HBM holds one
fixed-size POOL of KV pages per layer plus per-row page TABLES, and
everything that used to move cache payloads — admission rows, batch
growth, compaction, prefix reuse — becomes page-table bookkeeping
here, in plain numpy, under one lock.

Division of labor:

- **Device** (``ops/quant`` seams + ``models/gpt`` paged factories +
  ``ops/pallas`` kernels): pool arrays, scatter/gather/COW-copy
  programs, the page-table flash-decode kernel. The pool's device
  arrays live on this object (``layers``) between batches and are
  DONATED through each batch's programs; only the decode thread may
  touch them.
- **Host** (this class): the free list, per-page reference counts,
  prefix-entry page sets with LRU eviction under pressure, and the
  observability counters ``/metrics`` exports. All guarded by
  ``self.lock`` — prefix registration threads mutate metadata
  concurrently with the decode thread.

Invariants:

- Page id 0 is the NULL page: never allocated, permanently ref-pinned.
  Unallocated table entries point at it; dummy and finished rows write
  their dead tokens into it; it is never read unmasked (a row only
  reads slots it wrote — see DESIGN §15).
- A page with ``ref == 1`` is privately owned and writable. ``ref >
  1`` means shared (prefix pages): writers must COW first
  (``models/gpt.paged_cow_fn`` + a table rewrite).
- Exhaustion first evicts prefix-entry page sets nobody currently
  references (LRU), then raises :class:`PagePoolExhausted` — a LOUD
  reject. With a :class:`~mlapi_tpu.serving.kv_tier.KVTier` attached
  (``self.tier``), eviction SPILLS the victim's pages to host before
  freeing them (gather registered before release, so a fault can
  never lose both copies) and a later miss restores them by
  ``device_put`` into fresh pages — see ``serving/kv_tier.py`` and
  DESIGN §19.
"""

from __future__ import annotations

import collections
import functools
import threading

import numpy as np

from mlapi_tpu.serving import faults
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("serving.paged_pool")

NULL_PAGE = 0


class PagePoolExhausted(RuntimeError):
    """No free KV pages (after prefix eviction): the pool is sized too
    small for the offered concurrency — a capacity-planning signal,
    surfaced loudly to every waiter of the batch that hit it."""


class PagePoolPoisoned(RuntimeError):
    """A donated pool program failed DURING execution: the pool
    arrays were consumed and never rebound, so no fallback path may
    read them. Surfaced loudly (callers must not swallow this into a
    cold-path retry — the retry would die on deleted buffers, the
    r12 formation-poisoning bug class)."""


@functools.cache
def _tier_restore_fn():
    """Jitted tier-restore scatter: write a host blob's
    ``[n, page, ...]`` payload rows into pool pages ``pages`` across
    every layer. The pools are DONATED — the restored arrays replace
    them in place, exactly like the adopt scatter's donation — so a
    restore never doubles the pool's HBM footprint. Shape-keyed by
    jit's own cache (one compile per distinct page count), and safe
    under mesh-sharded pools: the payload uploads replicated and
    GSPMD partitions the scatter like any other pool write."""
    import jax

    def _run(pools, payload, pages):
        return {
            ln: {
                name: leaf.at[pages].set(
                    payload[ln][name].astype(leaf.dtype)
                )
                for name, leaf in layer.items()
            }
            for ln, layer in pools.items()
        }

    return jax.jit(_run, donate_argnums=(0,))


class PagePool:
    def __init__(self, model, *, page_size: int, num_pages: int):
        from mlapi_tpu.ops.quant import kv_page_bytes, make_paged_pools

        if page_size < 1:
            raise ValueError(f"kv_page_size must be >= 1, got {page_size}")
        if num_pages < 2:
            raise ValueError(
                f"kv_pages must be >= 2 (one null + one usable), got "
                f"{num_pages}"
            )
        self.page = int(page_size)
        self.num_pages = int(num_pages)
        # Device pools, one [num_pages, page, H, D(|1)] array per cache
        # leaf per layer. Rebound by the decode thread after every
        # donated program (BatchRun writes the updated arrays back).
        self.layers = make_paged_pools(model, num_pages, page_size)
        self.page_bytes = kv_page_bytes(model, page_size)
        self.lock = threading.Lock()
        # Eviction runs its spill (device gather + optional disk
        # write) OUTSIDE the lock; this condition (sharing the lock)
        # lets a concurrent alloc that finds no free pages AND no
        # victim wait for an in-flight eviction's release instead of
        # raising a spurious PagePoolExhausted for capacity that is
        # moments from free.
        self._evict_cond = threading.Condition(self.lock)
        self._evicting = 0
        self.ref = np.zeros((num_pages,), np.int64)
        self.ref[NULL_PAGE] = 1  # pinned forever
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        # Prefix-entry page sets: fingerprint -> int32[NPe] page ids,
        # LRU-ordered. Each set holds ONE ref per page for the entry
        # itself; rows sharing the prefix retain on top of that.
        self._entries: collections.OrderedDict[object, np.ndarray] = (
            collections.OrderedDict()
        )
        # Donation epoch (r15, unit scheduler): bumped by the
        # scheduler after every unit that may have donated the pool
        # arrays through a dispatch, so CONCURRENT lanes know their
        # cache pytree is stale and re-bind from ``layers`` before
        # their next unit. Only the scheduler's single dispatch
        # thread reads or writes it — no lock.
        self.epoch = 0
        # Counters (exported via the engine's /metrics block).
        self.cow_copies = 0
        self.entry_evictions = 0
        self.exhaustions = 0
        # Host-RAM spill tier (serving/kv_tier.py), attached by the
        # engine when --kv-tier-bytes > 0. None = the pre-tier
        # behavior: eviction discards, restore never happens.
        self.tier = None

    # -- accounting (read by /metrics) ---------------------------------
    @property
    def pages_total(self) -> int:
        """Allocatable pages (the null page is bookkeeping, not
        capacity)."""
        return self.num_pages - 1

    @property
    def pages_in_use(self) -> int:
        with self.lock:
            return self.pages_total - len(self._free)

    @property
    def pages_shared(self) -> int:
        """Pages referenced more than once (shared prefix blocks).
        The null page is excluded by index — it is pinned at ref 1,
        never above."""
        with self.lock:
            return int(np.sum(self.ref[NULL_PAGE + 1:] > 1))

    @property
    def utilization(self) -> float:
        return self.pages_in_use / max(1, self.pages_total)

    # -- allocation ----------------------------------------------------
    def alloc(self, n: int) -> np.ndarray:
        """Pop ``n`` free pages (ref = 1 each). Under pressure, evict
        prefix-entry page sets with no live-row references, LRU-first;
        still short → :class:`PagePoolExhausted`."""
        if n == 0:
            return np.zeros((0,), np.int32)
        # Injection point: armed tests force exhaustion (or a slow
        # allocator) at exactly this seam, BEFORE any free-list state
        # changes — the pool stays consistent and callers exercise
        # their real PagePoolExhausted handling. The armed guard keeps
        # the exception construction off the disarmed hot path.
        if faults.armed:
            faults.fire(
                "pool_alloc",
                exc=PagePoolExhausted(
                    f"KV page pool exhausted (injected fault): "
                    f"need {n} pages"
                ),
            )
        while True:
            with self.lock:
                if len(self._free) >= n:
                    out = np.asarray(
                        [self._free.pop() for _ in range(n)], np.int32
                    )
                    self.ref[out] = 1
                    return out
                victim = self._pop_victim_locked()
                if victim is None:
                    if self._evicting:
                        # Another thread's eviction is mid-spill: its
                        # pages free the moment it finishes — wait for
                        # the release instead of shedding capacity
                        # that exists.
                        self._evict_cond.wait(timeout=5.0)
                        continue
                    self.exhaustions += 1
                    raise PagePoolExhausted(
                        f"KV page pool exhausted: need {n} pages, "
                        f"{len(self._free)} free of {self.pages_total} "
                        f"(page={self.page} tokens); raise --kv-pages "
                        f"or lower concurrency"
                    )
                self._evicting += 1
            # Outside the lock: the victim's pages still carry their
            # entry references (the set is popped, so no other thread
            # can find or free them), and the spill's device gather +
            # optional disk write must not convoy every concurrent
            # pool operation behind one eviction.
            self._spill_and_release(*victim)

    def _pop_victim_locked(self):
        """Claim (pop) the LRU prefix-entry page set whose pages
        nobody else references (ref == 1 everywhere: only the entry's
        own hold) — or ``None``. The pop IS the claim: the pages keep
        their entry refs until :meth:`_spill_and_release` frees them,
        invisible to every other thread in between."""
        victim = next(
            (
                fp for fp, pages in self._entries.items()
                if np.all(self.ref[pages] == 1)
            ),
            None,
        )
        if victim is None:
            return None
        return victim, self._entries.pop(victim)

    def _spill_and_release(self, fp, pages) -> None:
        """Spill a claimed victim to the host tier (when attached),
        then free its pages. Runs OUTSIDE the pool lock (caller
        bumped ``_evicting`` under it); the spill happens BEFORE the
        release so the bytes exist somewhere at every instant. A
        spill failure at any point (including an injected
        ``tier_spill`` raise, or a gather racing a donated program
        when brownout's ``evict_idle`` fires from the event loop)
        leaves the tier untouched and falls back to the pre-tier
        discard, counted — it can never strand pages or lose the
        only copy. The PrefixCache entry itself survives either way
        — its contiguous KV re-adopts into fresh pages on next use.
        Logged at debug: with the tier this is a routine,
        recoverable path (the ``entry_evictions`` counter is the
        observable, exported as ``generate.kv_entry_evictions``)."""
        try:
            if self.tier is not None:
                try:
                    idx = np.asarray(pages)
                    payload = {
                        ln: {
                            name: np.asarray(leaf[idx])
                            for name, leaf in layer.items()
                        }
                        for ln, layer in self.layers.items()
                    }
                    self.tier.spill(fp, payload, self.page)
                except Exception as e:
                    self.tier.count_spill_failure()
                    _log.debug(
                        "tier spill failed (%s); evicting cold", e
                    )
        finally:
            with self.lock:
                # Decrement BEFORE the release: if the release ever
                # raised (a double-release lifecycle bug), waiters
                # must not spin forever on a phantom in-flight
                # eviction.
                self._evicting -= 1
                self._release_locked(np.asarray(pages))
                # Counted under the lock: evictions run concurrently
                # from the decode thread (alloc pressure) and the
                # event loop (brownout evict_idle) — a bare += here
                # lost updates under exactly the load /metrics is
                # read to diagnose (mlapi-lint MLA002, fixed r16).
                self.entry_evictions += 1
        _log.debug(
            "evicted prefix page set (%d pages) under pool pressure%s",
            len(pages),
            " (spilled to host tier)" if self.tier is not None else "",
        )

    def _blob_geometry_ok(self, blob) -> bool:
        """Does a host blob match this pool's page size and every
        layer's leaf shapes/dtypes? ONE definition shared by the tier
        restore and the r18 push install — the two blob-install paths
        must never diverge on what 'applies here' means."""
        if blob.page != self.page:
            return False
        for ln, layer in self.layers.items():
            pl = blob.payload.get(ln)
            if pl is None:
                return False
            for name, leaf in layer.items():
                a = pl.get(name)
                if (
                    a is None
                    or a.shape[1:] != leaf.shape[1:]
                    or a.dtype != leaf.dtype
                ):
                    return False
        return True

    def _scatter_blob(self, pages, blob, *, fire: str | None,
                      what: str) -> None:
        """The shared alloc-first install core: one donated scatter
        rebinds ``self.layers`` atomically. On ANY failure the pages
        go back (``kv_pages_in_use`` conserved exactly) — UNLESS the
        donated scatter failed DURING execution: then the pool
        buffers are consumed with no result to rebind, and any
        fallback that reads them dies on deleted buffers (the r12
        formation-poisoning bug class) — surfaced loudly as
        :class:`PagePoolPoisoned` instead. The optional fault point
        fires BEFORE the call on purpose, so injected raises always
        take the safe branch. Shared by :meth:`restore_entry` and
        :meth:`install_blob` so a fix to the poisoning detection can
        never reach one install path and not the other."""
        import jax.numpy as jnp

        try:
            if fire is not None:
                faults.fire(fire)
            self.layers = _tier_restore_fn()(
                self.layers, blob.payload, jnp.asarray(pages)
            )
        except BaseException as e:
            self.release(pages)
            leaf = next(
                iter(next(iter(self.layers.values())).values())
            )
            if getattr(leaf, "is_deleted", lambda: False)():
                raise PagePoolPoisoned(
                    f"KV pool consumed by a {what} that failed "
                    "mid-execution; no fallback may read the pool"
                ) from e
            raise

    def restore_entry(self, fp, blob, holds: int = 0):
        """Repopulate fresh pool pages from a spilled tier blob and
        register them as ``fp``'s entry page set (with ``holds`` row
        references, like :meth:`put_entry_pages`). Ordering is the
        whole point: pages are ALLOCATED first (a
        :class:`PagePoolExhausted` here propagates with nothing
        installed and nothing device-written — no half-restored entry
        can exist), the ``tier_restore`` fault point fires before any
        device write, the donated scatter rebinds ``self.layers``
        atomically, and registration is last. Returns the installed
        page ids, or ``None`` when the blob does not match this
        pool's geometry (dropped from the tier — it can never apply).
        Decode-thread only, like every other pool-array touch."""
        if not self._blob_geometry_ok(blob):
            self.tier.drop(blob.fp)
            return None
        pages = self.alloc(blob.num_pages)
        self._scatter_blob(
            pages, blob, fire="tier_restore", what="tier restore"
        )
        self.put_entry_pages(fp, pages, holds=holds)
        self.tier.count_restore(blob)
        return pages

    def install_blob(self, blob) -> np.ndarray | None:
        """Repopulate fresh pool pages from a host blob WITHOUT
        registering an entry set — the r18 disaggregation install: a
        pushed prompt's KV becomes a PRIVATE table row (each page at
        ref 1, writable in place), not a shared prefix entry. Same
        ordering contract as :meth:`restore_entry` (the shared
        :meth:`_scatter_blob` core): pages ALLOCATED first, one
        donated scatter, :class:`PagePoolPoisoned` on mid-execution
        failure. Returns the page ids (caller assigns them into its
        row table and owns the release), or ``None`` when the blob
        does not match this pool's geometry (caller cold-prefills,
        pages conserved). Decode-thread only, like every other
        pool-array touch."""
        if not self._blob_geometry_ok(blob):
            return None
        pages = self.alloc(blob.num_pages)
        self._scatter_blob(pages, blob, fire=None, what="push install")
        return pages

    def evict_idle(self, n: int = 1) -> int:
        """Brownout lever: proactively drop up to ``n`` idle
        (unreferenced, LRU-first) prefix-entry page sets so live
        sequences keep allocating under pressure instead of slamming
        into :class:`PagePoolExhausted`. Same eviction ``alloc`` runs
        reactively (claim under the lock, spill+free outside it);
        returns how many sets were dropped."""
        dropped = 0
        while dropped < n:
            with self.lock:
                victim = self._pop_victim_locked()
                if victim is not None:
                    self._evicting += 1
            if victim is None:
                break
            self._spill_and_release(*victim)
            dropped += 1
        return dropped

    def retain(self, pages) -> None:
        """One more holder of each page (a row sharing prefix
        pages)."""
        pages = np.asarray(pages)
        pages = pages[pages != NULL_PAGE]
        if len(pages):
            with self.lock:
                np.add.at(self.ref, pages, 1)

    def release(self, pages) -> None:
        """Drop one hold per page; pages at ref 0 return to the free
        list. Null entries are ignored, so callers can release whole
        table rows."""
        pages = np.asarray(pages).ravel()
        pages = pages[pages != NULL_PAGE]
        if len(pages):
            with self.lock:
                self._release_locked(pages)

    def _release_locked(self, pages) -> None:
        np.subtract.at(self.ref, pages, 1)
        if np.any(self.ref[pages] < 0):
            # A double release is a lifecycle bug: loud, not silent —
            # the page may already belong to someone else.
            bad = pages[self.ref[pages] < 0]
            self.ref[bad] = 0
            raise AssertionError(
                f"KV page(s) {sorted(set(int(p) for p in bad))} "
                "released below zero references"
            )
        freed = np.unique(pages[self.ref[pages] == 0])
        if len(freed):
            self._free.extend(int(p) for p in freed)
            # Wake any alloc waiting out an in-flight eviction (the
            # condition shares self.lock, already held here).
            self._evict_cond.notify_all()

    def is_shared(self, page: int) -> bool:
        with self.lock:
            return bool(self.ref[page] > 1)

    # -- prefix-entry page sets ----------------------------------------
    def entry_pages(self, fp, holds: int = 0) -> np.ndarray | None:
        """The pool-resident page set of a prefix entry, if paged in
        (marks it most-recently-used). ``holds`` extra references are
        taken ATOMICALLY with the lookup — a concurrent entry
        eviction (``drop_entry`` from a registration thread) between
        a bare lookup and a later ``retain`` could otherwise free the
        pages out from under the forming batch."""
        with self.lock:
            pages = self._entries.get(fp)
            if pages is not None:
                self._entries.move_to_end(fp)
                if holds:
                    np.add.at(self.ref, pages, holds)
            return pages

    def put_entry_pages(self, fp, pages: np.ndarray,
                        holds: int = 0) -> None:
        """Register a freshly-adopted entry page set (pages arrive
        from ``alloc`` holding the entry's own reference); ``holds``
        row references are added under the same lock so the set is
        never observable in its evictable state while a batch is
        about to use it."""
        with self.lock:
            pages = np.asarray(pages, np.int32)
            if holds:
                np.add.at(self.ref, pages, holds)
            self._entries[fp] = pages

    def drop_entry(self, fp) -> None:
        """Release an evicted PrefixCache entry's page set (no-op if
        never paged in or already evicted under pressure)."""
        with self.lock:
            pages = self._entries.pop(fp, None)
            if pages is not None:
                self._release_locked(pages)
