"""Shared-prefix KV caching for generative serving.

One :class:`PrefixCache` per :class:`TextGenerationEngine`: it owns
the LRU of prefilled prefix KVs, the per-key build events (concurrent
first requests for the SAME prefix share one build; hits on other
prefixes never wait), the cross-batch widened-KV cache, and the
hit/miss/fallback counters ``/metrics`` exports. Device work (prefill,
widen, warm grids) runs through the engine's model/params — the cache
holds a back-reference for those, but every piece of PREFIX STATE
lives here. Split out of ``engine.py`` (r04 VERDICT "Next" #7).

Host-tier integration (r13, ``serving/kv_tier.py``): when the engine
carries a :class:`~mlapi_tpu.serving.kv_tier.KVTier`
(``--kv-tier-bytes``), this cache is BOTH tier seams' client — an
entry falling off this dict's own LRU spills its contiguous KV to the
tier before being discarded, and a device-cache miss consults the
tier before paying the cold prefill: :meth:`entry` rebuilds the
``_PrefixEntry`` from the spilled blob (``device_put``, zero prefill
FLOPs — ``builds`` does not move), and :meth:`paged_entry` restores
evicted pool page sets straight from the blob
(``PagePool.restore_entry``) instead of re-adopting. Every restore is
byte-identical to the state it replaces, so greedy streams cannot
tell {evict → restore} from {never evicted}. Tier absent (the
default): every path below is bit-for-bit the r12 behavior.
"""

from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np

from mlapi_tpu.serving.requests import _PrefixEntry
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("serving.prefix")


class PrefixCache:
    def __init__(self, engine, max_entries: int = 8):
        self.eng = engine
        self.max_entries = max_entries
        # text -> _PrefixEntry, LRU-bounded (each entry holds a
        # [1, prefix_bucket] KV pytree on device).
        self._entries: collections.OrderedDict = collections.OrderedDict()
        # Guards the LRU against concurrent _encode calls (submit runs
        # encoding in executor threads): without it, N first requests
        # naming the same prefix would each pay the cold prefill.
        # ``_building`` holds per-key in-flight build events so cold
        # builds never block hits on OTHER prefixes.
        self._lock = threading.Lock()
        self._building: dict = {}
        # Cross-batch prefix sharing: right-aligned [1, P] widenings
        # of registered prefix KVs (keyed (fp, P), LRU-bounded) and
        # the region widths P whose stacked program grid is warmed
        # (strict mode groups cross-prefix only within this set).
        self._wide: collections.OrderedDict = collections.OrderedDict()
        self.mix_warmed: set = set()
        # Stats (read by /metrics via the engine's properties).
        # ``builds`` counts actual cold prefills (``_build`` runs) —
        # the counter the zero-prefill-FLOPs restore claim is pinned
        # against: a tier restore increments ``misses`` (it missed the
        # device cache) but never ``builds``.
        self.hits = 0
        self.misses = 0
        self.fallbacks = 0
        self.builds = 0

    def __len__(self) -> int:
        return len(self._entries)

    def count_fallback(self) -> None:
        """A prefix request served through the plain path instead of
        the KV path (empty suffix, unstackable entry): counted under
        the lock — callers run on concurrent encode executor threads
        (mlapi-lint MLA002, caught r19)."""
        with self._lock:
            self.fallbacks += 1

    def entry(self, text: str) -> _PrefixEntry:
        """Return (computing on first use, LRU-cached after) the KV
        cache of a shared prompt prefix. The forward pass over the
        prefix runs ONCE; every request naming the same prefix reuses
        its keys/values straight from device memory — the
        time-to-first-token win prefix caching exists for. The first
        request with a new prefix pays the prefill (and possibly XLA
        compiles for its shapes) on its own latency. Concurrent first
        requests for the SAME prefix share one build (per-key event);
        hits on other prefixes never wait behind a build — the lock
        guards only the dict, not the device work."""
        while True:
            with self._lock:
                entry = self._entries.get(text)
                if entry is not None:
                    self._entries.move_to_end(text)
                    self.hits += 1
                    return entry
                ev = self._building.get(text)
                if ev is None:
                    ev = threading.Event()
                    self._building[text] = ev
                    break
            # Someone else is building this prefix: wait, then re-check
            # (their failure leaves the entry absent — we retry as the
            # builder and surface the same error to this caller).
            ev.wait(timeout=600.0)
        try:
            # Device-cache miss: the host tier first (a spilled blob
            # rebuilds the entry with ZERO prefill FLOPs), then the
            # cold prefill. Either way it is a miss — the tier's own
            # restore_hits counter carries the savings story.
            entry = self._restore(text)
            if entry is None:
                entry = self._build(text)
            tier = getattr(self.eng, "kv_tier", None)
            if tier is not None:
                # The rebuild metadata a later spill must attach (the
                # pool spill seam knows page ids, not buckets).
                tier.note_meta(
                    text, bucket=entry.bucket, lo=entry.lo,
                    used=entry.used,
                )
            evicted = []
            with self._lock:
                self._entries[text] = entry
                self.misses += 1
                while len(self._entries) > self.max_entries:
                    old, old_e = self._entries.popitem(last=False)  # LRU
                    evicted.append(old_e)
                    if self.eng.pool is not None:
                        # The evicted entry's pool pages lose their
                        # entry hold (rows still sharing them keep
                        # theirs; the pages free when the last row
                        # departs). No pool-side spill here — the
                        # entry's contiguous KV below is the same
                        # bytes, readable from THIS thread.
                        self.eng.pool.drop_entry(old)
            for old_e in evicted:
                # Outside the lock: the spill device_gets a [1, P]
                # cache — other prefixes' lookups must not wait on it.
                # A concurrent re-arrival of the evicted prefix in
                # this window just pays a cold build (correct, merely
                # unlucky).
                self._spill_entry(old_e)
            return entry
        finally:
            with self._lock:
                self._building.pop(text, None)
            ev.set()

    def _plan(self, text: str):
        """Tokenize and bucket one prefix EXACTLY as a cold build
        would — ``(ids, bucket, lo)``. Shared between :meth:`_build`
        and tier-restore validation, so a spilled blob only ever
        applies when its geometry matches what a build would produce
        today (tokenizer/bucket/page-size drift turns the blob into a
        miss, never a wrong cache)."""
        eng = self.eng
        ids = eng.tokenizer.token_ids(text)
        if not ids:
            raise ValueError("prefix tokenizes to nothing")
        # The prefix must leave room for at least the smallest suffix
        # bucket plus one generated token.
        cap = eng.model.max_positions - eng.prompt_buckets[0] - 1
        if len(ids) > cap:
            raise ValueError(
                f"prefix is {len(ids)} tokens; at most {cap} fit "
                f"the model window (max_positions="
                f"{eng.model.max_positions})"
            )
        bucket = min(max(eng._bucket(len(ids)), len(ids)), cap)
        if eng.pool is not None:
            # Page-align the prefix bucket AT STORE TIME: region ends
            # and right-alignment shifts between entries then land on
            # page boundaries, so stacked (cross-prefix) groups share
            # ref-counted pages instead of copying widened stacks
            # (BatchRun._prefill_paged_prefix), and a same-fp batch's
            # suffix starts on a fresh tile (no COW). A few pad slots
            # per entry buy pointer sharing per batch. When the model
            # window can't fit the aligned bucket the entry stays
            # unaligned — groups containing it fall back to copy
            # semantics, counted in ``eng.kv_prefix_copy_fallback``.
            aligned = -(-bucket // eng.pool.page) * eng.pool.page
            if aligned <= cap:
                bucket = aligned
        return ids, bucket, bucket - len(ids)

    def _build(self, text: str) -> _PrefixEntry:
        """Tokenize, validate, prefill, and (strict mode) warm one
        prefix — device work, run OUTSIDE the registry lock."""
        from mlapi_tpu.models.gpt import prefill_fn

        eng = self.eng
        ids, bucket, _ = self._plan(text)
        with self._lock:
            # Concurrent builds of DIFFERENT prefixes run on separate
            # encode executor threads; a bare += here lost updates on
            # the counter the zero-prefill-FLOPs claims are pinned
            # against (mlapi-lint MLA002, caught r19).
            self.builds += 1
        row = np.full((1, bucket), eng.tokenizer.pad_id, np.int32)
        row[0, -len(ids):] = ids
        lo = bucket - len(ids)
        _, kv = prefill_fn(eng.model, bucket)(
            eng.params, jnp.asarray(row),
            jnp.asarray(eng._key_data(0)[None]),
            jnp.asarray(np.zeros((1,), np.float32)),
            jnp.asarray(np.asarray([lo], np.int32)),
            jnp.asarray(np.zeros((1,), np.int32)),
            jnp.asarray(np.ones((1,), np.float32)),
        )
        entry = _PrefixEntry(text, kv, bucket, lo, len(ids))
        if eng._strict_admit:
            self.warm_shapes(entry)
        return entry

    # -- host-tier + peer seams (kv_tier.py / kv_peer.py; no-ops when
    # absent) -----------------------------------------------------------
    def _restore(self, text: str) -> _PrefixEntry | None:
        """Warm-source consult on a device-cache miss, cheapest
        first: the LOCAL tier blob, then (``--kv-peer-fetch``) a
        router-hinted WARM PEER's blob over the wire — either way the
        entry rebuilds by ``device_put`` of stored-format bytes, ZERO
        prefill FLOPs (``builds`` does not move) — or ``None`` to
        fall back to the cold build. Runs on the encode executor
        thread, so the peer hop never touches the dispatch thread
        (the cold prefill it replaces blocks this same thread for
        longer). Failure discipline: geometry or metadata drift DROPS
        a tier blob / counts a peer MISS (the bytes can never apply
        here) and goes cold; a transient failure (including injected
        ``tier_restore``/``peer_fetch`` raises) counts its seam's
        failure counter and goes cold — either way the caller's path
        is the normal prefill, never a half-built entry. A peer blob
        that DOES apply is additionally staged into the local tier
        (``KVTier.stage``) so the paged formation restores its pool
        pages through the existing alloc-first
        ``PagePool.restore_entry`` path on the dispatch thread."""
        from mlapi_tpu.serving import faults

        tier = getattr(self.eng, "kv_tier", None)
        peer = getattr(self.eng, "kv_peer", None)
        if tier is not None:
            # absent -> counted restore miss (the local-tier story)
            blob = tier.lookup(text)
            if blob is not None:
                entry = None
                try:
                    faults.fire("tier_restore")
                    entry = self._entry_from_blob(text, blob)
                except Exception as e:
                    tier.count_restore_failure()
                    _log.debug(
                        "tier entry restore failed (%s); cold prefill", e
                    )
                if entry is not None:
                    if self.eng._strict_admit:
                        self.warm_shapes(entry)
                    tier.count_restore(blob)
                    return entry
                # Drifted (blob dropped) or transiently failed: the
                # peer below may still beat the cold prefill.
        if peer is None:
            return None
        blob = peer.fetch(text)  # miss/failure counted inside
        if blob is None:
            return None
        try:
            entry = self._entry_from_blob(text, blob, drop=False)
        except Exception as e:
            peer.count_miss()
            _log.debug(
                "peer blob failed to apply (%s); cold prefill", e
            )
            return None
        if entry is None:
            # Geometry drift vs what a local build would produce
            # today (different bucket/page config than the peer):
            # dropped as a miss, exactly like a corrupt wire body —
            # and the hint goes too: config drift is persistent, so
            # every future miss would re-transfer a full blob that
            # provably can never apply (the same pure-loss argument
            # as the 404 hint drop).
            peer.count_miss()
            peer.drop_hint(text)
            return None
        peer.count_applied(blob.nbytes)
        if tier is not None:
            try:
                # Stage locally: the dispatch-thread paged_entry path
                # then finds the blob in the LOCAL tier and restores
                # pool pages alloc-first via restore_entry — no wire
                # I/O on the dispatch thread, pages conserved on any
                # failure. Best-effort: a staging failure only costs
                # the adopt-path copy at formation.
                tier.stage(
                    text, blob.payload, blob.page,
                    bucket=blob.bucket, lo=blob.lo, used=blob.used,
                )
            except Exception as e:
                _log.debug("peer blob staging failed (%s)", e)
        if self.eng._strict_admit:
            self.warm_shapes(entry)
        return entry

    def _entry_from_blob(self, text: str, blob,
                         drop: bool = True) -> _PrefixEntry | None:
        """Blob payload ``{layer: {leaf: [n, page, ...]}}`` → the
        ``[1, bucket]`` contiguous entry KV, byte-identical to the one
        the original build produced (the spill gathered exactly those
        bytes; slots past ``bucket`` in the final page are spill-time
        pool residue, sliced off here and never read). Returns
        ``None`` when the blob's recorded geometry does not match
        what a cold build would produce today — after dropping the
        blob from the tier when ``drop`` (peer-fetched blobs pass
        ``drop=False``: there is nothing local to drop, and the
        caller counts the miss on the peer's own counters)."""
        if blob.bucket is None:
            # Spilled before any entry registration recorded its
            # metadata: pool-page restore still works (paged_entry),
            # but an entry cannot be rebuilt. Keep the blob.
            return None
        ids, bucket, lo = self._plan(text)
        if (
            blob.bucket != bucket
            or blob.lo != lo
            or blob.used != len(ids)
            or blob.num_pages * blob.page < bucket
        ):
            if drop:
                self.eng.kv_tier.drop(text)
            _log.debug(
                "%s blob geometry drifted for %r; cold prefill",
                "tier" if drop else "peer", text,
            )
            return None
        kv = {
            ln: {
                name: jnp.asarray(
                    np.ascontiguousarray(
                        a.reshape(
                            (1, a.shape[0] * a.shape[1]) + a.shape[2:]
                        )[:, :bucket]
                    )
                )
                for name, a in layer.items()
            }
            for ln, layer in blob.payload.items()
        }
        return _PrefixEntry(text, kv, bucket, lo, len(ids))

    def _spill_entry(self, entry: _PrefixEntry) -> None:
        """Spill a dict-LRU-evicted entry's contiguous KV to the host
        tier before it is garbage-collected — the second spill seam
        (the first is ``PagePool._spill_and_release``). Reads the
        entry's own ``[1, P]`` KV, never pool arrays, so it is safe
        from registration threads; page-shaped to the pool's page size
        (paged engines) so the blob is interchangeable with pool
        spills, or one bucket-wide page (contiguous engines). A
        failure here (including an injected ``tier_spill`` raise)
        falls back to the pre-tier discard, counted."""
        tier = getattr(self.eng, "kv_tier", None)
        if tier is None:
            return
        from mlapi_tpu.serving.kv_tier import payload_from_contiguous

        page = (
            self.eng.pool.page if self.eng.pool is not None
            else entry.bucket
        )
        try:
            tier.note_meta(
                entry.fp, bucket=entry.bucket, lo=entry.lo,
                used=entry.used,
            )
            payload = payload_from_contiguous(entry.kv, page)
            tier.spill(entry.fp, payload, page)
        except Exception as e:
            tier.count_spill_failure()
            _log.debug("tier entry spill failed (%s); evicting cold", e)

    def warm_shapes(self, entry: _PrefixEntry) -> None:
        """Registration-time warm of the prefix-batch programs: in
        strict mode (high measured RTT) the first BATCH using a new prefix
        must not stall the device stream on an XLA compile, so the
        (suffix bucket × small batch) grid at the default cache tier
        compiles as part of building the entry — the registration
        request already owns that latency."""
        from mlapi_tpu.models.gpt import decode_chunk_fn, prefix_prefill_fn

        eng = self.eng
        if eng.pool is not None:
            # Paged engines run the suffix through paged_extend_fn
            # against pool-shaped caches; warming those needs live
            # pool state this registration thread must not touch (the
            # decode thread owns the pool arrays). Strict-mode paged
            # prefix batches therefore compile their suffix program on
            # first formation, and cross-prefix mixing stays
            # same-prefix (mix_warmed never populates) — noted in
            # DESIGN §15.
            return
        batches = [1]
        while batches[-1] < eng.max_batch:
            batches.append(batches[-1] * 2)

        p = entry.bucket
        for sb in eng.prompt_buckets:
            if p + sb + 1 > eng.model.max_positions:
                continue  # no room for such suffixes behind this prefix
            total = eng._cache_len(p + sb, eng.default_max_new_tokens)
            for bsz in batches:
                suffix = np.full(
                    (bsz, sb), eng.tokenizer.pad_id, np.int32
                )
                hole = jnp.asarray(np.full((bsz,), sb - 1, np.int32))
                keys = jnp.asarray(
                    np.stack([eng._key_data(0)] * bsz)
                )
                zt = jnp.asarray(np.zeros((bsz,), np.float32))
                zk = jnp.asarray(np.zeros((bsz,), np.int32))
                op = jnp.asarray(np.ones((bsz,), np.float32))
                _, cache = prefix_prefill_fn(eng.model, sb, total)(
                    eng.params, entry.kv, jnp.asarray(suffix),
                    hole, jnp.int32(entry.lo), keys, zt, zk, op,
                )
                # Cross-prefix (stacked) variants: per-row KV stack +
                # lo vector, and the vector-lo decode-chunk program —
                # these are keyed on SHAPES only, so warming them once
                # per region width covers every combination of
                # registered prefixes whose group max is this bucket.
                # bsz == 1 is a mixed batch compacted to one row: the
                # scalar-path cache with the vector-lo decode.
                lo_vec = jnp.asarray(np.full((bsz,), entry.lo, np.int32))
                if bsz > 1:
                    kv_stack = jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a, (bsz,) + a.shape[1:]
                        ),
                        entry.kv,
                    )
                    _, cache = prefix_prefill_fn(eng.model, sb, total)(
                        eng.params, kv_stack, jnp.asarray(suffix),
                        hole, lo_vec, keys, zt, zk, op,
                    )
                decode_chunk_fn(eng.model, eng.chunk)(
                    eng.params, cache,
                    jnp.asarray(np.zeros((bsz,), np.int32)),
                    jnp.int32(p + sb), hole, zt, keys,
                    jnp.asarray(np.ones((bsz,), np.int32)), zk, op,
                    jnp.int32(p), lo_vec,
                )
        with self._lock:
            # Registration threads warm concurrently; the formation
            # path reads membership from the dispatch thread
            # (mlapi-lint MLA002, caught r19).
            self.mix_warmed.add(p)

    def paged_entry(self, fp, kv, holds: int):
        """Pool-page residency for a prefix entry (paged engines):
        return ``(pages, need_adopt)`` — the shared page ids with
        ``holds`` row references ALREADY taken (atomically with the
        lookup/registration, so a concurrent entry eviction can never
        free the set between lookup and use), plus whether the
        entry's contiguous ``[1, P]`` KV still has to be scattered
        into them (first use; once per entry LIFETIME). HOST-ONLY on
        purpose: the caller performs the adopt scatter after ALL of
        the batch's page allocation has succeeded, so a
        :class:`PagePoolExhausted` can never fire after a donating
        device call has already consumed the pool arrays. After
        adoption, every batch row naming this prefix just points its
        page table here (ref-counted; the contiguous path
        re-broadcast the prefix KV into every row of every batch).
        Under pool pressure the page set may have been evicted
        (``PagePool._spill_and_release``); with a host tier attached
        the eviction SPILLED those pages, so the miss first tries
        ``PagePool.restore_entry`` — a ``device_put`` of the blob back
        into fresh pages, byte-identical to the re-adopt it replaces —
        and only then falls back to the adopt scatter. A
        :class:`~mlapi_tpu.serving.paged_pool.PagePoolExhausted`
        during restore propagates loudly (restore allocates FIRST, so
        nothing is half-installed; the adopt path would need the same
        pages and fail the same way); any other restore failure
        (including an injected ``tier_restore`` raise) is counted and
        falls back to the adopt, pages conserved."""
        import jax

        pool = self.eng.pool
        pages = pool.entry_pages(fp, holds=holds)
        if pages is not None:
            return pages, False
        tier = getattr(self.eng, "kv_tier", None)
        if tier is not None:
            from mlapi_tpu.serving.paged_pool import (
                PagePoolExhausted, PagePoolPoisoned,
            )

            blob = tier.lookup(fp)  # absent -> counted restore miss
            if blob is not None:
                try:
                    pages = pool.restore_entry(fp, blob, holds=holds)
                except (PagePoolExhausted, PagePoolPoisoned):
                    # Exhaustion: the adopt fallback needs the same
                    # pages and would fail the same way. Poisoning:
                    # the fallback would read consumed buffers. Both
                    # propagate loudly, nothing half-installed.
                    raise
                except Exception as e:
                    tier.count_restore_failure()
                    _log.debug(
                        "tier page restore failed (%s); re-adopting", e
                    )
                    pages = None
                if pages is not None:
                    return pages, False
        p = jax.tree.leaves(kv)[0].shape[1]
        pages = pool.alloc(-(-p // pool.page))
        pool.put_entry_pages(fp, pages, holds=holds)
        return pages, True

    @staticmethod
    def widen(kv, own_len: int, p_len: int):
        """``[1, own_len]`` prefix-KV pytree → ``[1, p_len]``,
        right-aligned (real content ends at the common region end)."""
        if own_len == p_len:
            return kv
        off = p_len - own_len
        return jax.tree.map(
            lambda a: jax.lax.dynamic_update_slice(
                jnp.zeros((1, p_len) + a.shape[2:], a.dtype), a,
                (0, off) + (0,) * (a.ndim - 2),
            ),
            kv,
        )

    def stacked(self, reqs, p_len: int, b_pad: int):
        """Per-row ``[b_pad, p_len]`` prefix-KV stack for a
        cross-prefix batch: each live row's own prefix right-aligned
        to the common region end (cached per (fp, p_len) — the widen
        runs once per prefix per width, not once per batch); dummy
        rows are zeros, fully masked by ``lo == p_len``."""
        rows = []
        for r in reqs:
            key = (r.prefix_fp, p_len)
            wide = self._wide.get(key)
            if wide is None:
                wide = self.widen(r.prefix_kv, r.prefix_len, p_len)
                self._wide[key] = wide
                while len(self._wide) > 2 * self.max_entries:
                    self._wide.popitem(last=False)
            else:
                self._wide.move_to_end(key)
            rows.append(wide)
        if b_pad > len(reqs):
            zero = jax.tree.map(jnp.zeros_like, rows[0])
            rows.extend([zero] * (b_pad - len(reqs)))
        return jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *rows
        )
