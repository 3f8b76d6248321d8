"""One continuous batch's whole lifecycle, as an object with seams.

``TextGenerationEngine._run_batch`` used to hold this as a single
~650-line method; the state it threaded through nested closures is now
explicit attributes on :class:`BatchRun`, and each lifecycle stage is
its own method:

======================  ================================================
``__init__``            formation: shape/bucket/prefix resolution, host
                        mirror packing (``_pack_rows``), batch padding
``_prefill``            the three prefill variants (shared-prefix,
                        chunked long-prompt, plain) → ``(first, cache)``
``_first_token``        sync-vs-chained first-token policy (speculation
                        reads the host mirror; everyone else defers the
                        readback onto the dispatch chain)
``_spec_handoff``       solo / batched speculative phases, handing off
                        to the chunk loop at any ``(cache, pos, tok)``
``_admit_waiting``      mid-batch continuous admission (+ batch growth)
``_pf_step`` et al.     interleaved chunked prefill: a long-prompt
                        joiner's prefill chunks scheduled one per
                        decode boundary (paged engines; r10)
``_maybe_shrink``       compaction along the warmed halving chain
``_decode_chunk``       one chained chunk dispatch + drain policy
``units``               the loop AS A GENERATOR of typed schedulable
                        units (prefill/decode/spec/admit/compact):
                        pf-activation → admission → liveness → spec
                        re-engage → resize → pf-chunk → chunk, then
                        terminators — yielding after each unit of
                        device work so the engine-level scheduler
                        (``serving/scheduler.py``, r15) can interleave
                        several batches' units on one device stream
``run``                 scheduler-off entry: drain ``units()`` to
                        exhaustion (identical code either way — the
                        scheduler-on/off token-identity contract is
                        structural)
======================  ================================================

Invariants the stages share (and why the state is one object):

* Host mirrors (``n_pad``/``temps``/``topk``/``topp``/``keys``/``tok``/
  ``step``/``lo``) are the source of truth; the device holds ONLY the
  KV cache. Every resize rebinds all mirrors together
  (:meth:`_mirrors_take`) so a stage can never see a half-resized
  batch.
* ``rows[i]`` maps request *i* to its current device row across
  resizes; ``produced``/``sched`` split delivered-vs-dispatched token
  counts so the chained-dispatch frontier can run ahead of readbacks.
* Anything that mutates batch state (admission, compaction, spec)
  first ``chain.invalidate()``s — the host mirrors must be current
  before they are rewritten.

The engine's ``_run_batch`` is now a thin wrapper: the fused
whole-generation fast paths (``fused_single.py``), then
``BatchRun(engine, reqs, admit).run()``, with error delivery to every
waiter kept at the wrapper level.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from mlapi_tpu.serving import faults
from mlapi_tpu.serving.dispatch import DispatchChain
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("serving.batch_run")


class BatchRun:
    """Decode one coalesced batch, streaming chunks to each request's
    queue; a ``None`` sentinel marks completion (error delivery lives
    in the engine wrapper, which owns the ``reqs`` list reference).

    With ``admit=True`` (the collector's batches) this is a CONTINUOUS
    batch: at every chunk boundary, waiting requests whose prompt
    bucket and token budget fit the running cache are prefilled into a
    free device row (bucket-keyed ``prefill_fn`` + ``admit_scatter_fn``)
    and decode alongside the original members — a long generation no
    longer head-of-line-blocks short arrivals. Admission never stalls
    the batch on an EXPENSIVE compile: in strict mode the joiner's
    prefill bucket must be pre-warmed, and the trivial scatter/growth
    programs either compile on demand (low measured RTT) or must be
    warmed too (high measured RTT). The batch grows along the warmed power-of-two
    chain only, and per-row sampling-stream indices keep every row's
    output byte-identical to a solo run.

    Device-resident state is the KV cache and nothing else: all
    per-row vectors (pads, temps, keys, stream steps, last token) are
    host mirrors re-uploaded with each chunk dispatch, which is what
    makes admission/compaction/growth bookkeeping plain numpy instead
    of extra device programs.
    """

    def __init__(self, eng, reqs: list, admit: bool,
                 fused_ok: bool = True) -> None:
        self.eng = eng
        self.reqs = reqs  # the engine's list object: admission appends
        self.admit = admit
        # Brownout spec suppression is counted ONCE per batch run: the
        # lever is consulted at formation AND at every chunk boundary,
        # and per-call counting would inflate "suppressed engagements"
        # by the chunk count (a 20-chunk suppressed stream is one
        # blocked engagement, not twenty).
        self._spec_supp_counted = False
        # Joiners this lane claimed since the scheduler last looked
        # (their ``rid``): the scheduler names them on its span of
        # the unit that admitted them, and empties the list.
        self.claimed: list = []

        self.bucket = max(len(r.row) for r in reqs)
        n_new_max = max(r.n_new for r in reqs)
        # The prefix region spans [0, p_len) of every row's cache.
        # Same-fp batches share ONE scattered KV (scalar lo);
        # cross-prefix batches stack each row's own KV right-aligned
        # to the common region end p_len, masked by a per-row lo
        # vector (lo == p_len ⇒ empty region, the dummy-row case).
        self.p_len = max((r.prefix_len for r in reqs), default=0)
        self.p_lo = reqs[0].prefix_lo
        self.mixed_prefix = bool(self.p_len) and any(
            r.prefix_fp != reqs[0].prefix_fp
            or r.prefix_len != self.p_len
            for r in reqs
        )
        self.total = eng._cache_len(self.p_len + self.bucket, n_new_max)
        self.n_new_max = min(
            n_new_max, self.total - self.p_len - self.bucket
        )
        b = len(reqs)
        # Pad the BATCH dimension to a power of two: programs are
        # keyed on batch size, so without padding every distinct
        # concurrency level compiles its own prefill+decode. Dummy
        # rows are a 1-token pad prompt (masked out like any pad).
        b_pad = 1
        while b_pad < b:
            b_pad *= 2
        b_max = 1
        while b_max < eng.max_batch:
            b_max *= 2
        self.b, self.b_pad, self.b_max = b, b_pad, b_max
        # Fused-chunk width (r20): the top dispatch width for a batch
        # of non-streaming rows — tier-wide decode chunks through the
        # SAME decode-chunk program family, one schedulable unit per
        # fused chunk (0 pins the plain ``eng.chunk``; warmup's
        # chunked grid passes fused_ok=False to compile the plain
        # widths deliberately).
        self.fused_w = eng.fused.chunk_width(self) if fused_ok else 0
        self._fused_counted = False
        # Per-row adapter slot mirror (serving/adapter_store.py):
        # arow[row] is the device row's resident adapter slot, 0 (the
        # all-zero NULL slot) for base-model rows. A host mirror like
        # n_pad — it resizes through _mirrors_take and is reassigned
        # whenever a row changes owner (admission, pf activation).
        # _adapter_holds records every acquire for the run-end
        # release; grouped/gathered is counted once per run, like
        # fused_calls.
        self.arow = np.zeros((b_pad,), np.int32)
        self._adapter_holds: list = []
        self._adapter_counted = False

        (self.prompt, self.n_pad, self.temps, self.topk, self.topp,
         self.keys) = eng._pack_rows(reqs, self.bucket, b_pad)
        self.lo = np.full((b_pad,), self.p_len, np.int32)
        for i, r in enumerate(reqs):
            self.lo[i] = self.p_len - r.prefix_len + r.prefix_lo

        # Paged mode: the device batch state is (pool arrays, HOST
        # page table). ``tab[row, i]`` maps virtual tile i of device
        # row ``row`` to a pool page (0 = the unallocated null page);
        # it is re-uploaded into the cache pytree whenever it changes
        # (``_tab_dirty``). Page lifecycle (alloc/COW/release) is host
        # bookkeeping against ``eng.pool``.
        self.pool = eng.pool
        self.page = self.pool.page if self.pool is not None else 0
        self.npv = (
            -(-self.total // self.page) if self.pool is not None else 0
        )
        self.tab = (
            np.zeros((b_pad, self.npv), np.int32)
            if self.pool is not None else None
        )
        self._tab_dirty = False
        # Active interleaved chunked prefill (paged long-prompt
        # joiner) + its consecutive-dispatch stall counter.
        self._pf: dict | None = None
        self._pf_consec = 0
        # Disaggregation push state (r18): a prefill-role run whose
        # chunk KV streams to a decode replica as each chunk
        # finishes. Solo by the collector's compatibility rule, so
        # the pushed row is always device row 0.
        self._push: dict | None = None
        r0 = reqs[0]
        if (
            getattr(r0, "push_to", None) is not None
            and self.b == 1 and not self.p_len
            and eng.kv_push is not None
        ):
            host, port, xfer = r0.push_to
            cp = eng.prompt_buckets[-1]
            n_run = (
                self.bucket // cp
                if self.bucket > cp and self.bucket % cp == 0
                else 1
            )
            self._push = {"xfer": xfer, "n": n_run, "sent": 0}
            eng.kv_push.begin(xfer, host, int(port))
        # rows[i]: request i's current row in the (possibly
        # resized) device batch. Rows are independent (per-row
        # mask/positions/PRNG streams), so gathering live rows
        # into a different-size warmed program changes nothing
        # but cost.
        self.rows: list = list(range(b))
        self.b_cur = b_pad
        try:
            # Pin every member's adapter into a device slot BEFORE the
            # prefill dispatches read _params() — a miss here (store
            # empty, slots exhausted) fails the formation loudly with
            # every hold rolled back and nothing half-installed.
            for i, r in enumerate(reqs):
                self.arow[i] = self._acquire_adapter(r)
            s0 = int(self.arow[0])
            if s0 and bool(np.all(self.arow[:b] == s0)):
                # Single-tenant batch: paint the dummy pad rows with
                # the same slot so the GROUPED (scalar-slot) program
                # applies — dummy rows are fully masked, so the delta
                # they compute is never read.
                self.arow[:] = s0
            first = self._prefill()
            self.pos = self.p_len + self.bucket
            self._first_token(first)
            if self._push is not None:
                # Finalize the transfer: the sampled first token (one
                # synchronous readback — this run IS the prefill, it
                # ends here) plus the geometry the decode replica
                # validates. FIFO behind every chunk on the sender
                # thread, so a fin implies a complete transfer.
                eng.kv_push.finish(
                    self._push["xfer"], self._push["n"],
                    int(np.asarray(self._first)[0]),
                    self.bucket, reqs[0].used,
                )
            self.chain = DispatchChain(self._deliver, eng.latency.sums)
        except BaseException:
            if self._push is not None:
                # A failed formation must not leave the handler
                # blocking out its full wait: fail the transfer NOW
                # (the decode replica will cold-prefill).
                eng.kv_push.abort(self._push["xfer"])
            # Formation failed (incl. a loud PagePoolExhausted before
            # any dispatch): give every held page back — the wrapper
            # delivers the error to the waiters. write_back matters
            # (r12): a failure AFTER the prefill dispatch succeeded
            # (e.g. a fault in the first-token push) leaves the pool's
            # device arrays consumed by donation and the LIVE ones on
            # ``self.cache`` — skipping the re-bind here poisoned
            # every subsequent batch with deleted-buffer errors. The
            # cleanup's own guard skips write-back when no cache
            # exists yet.
            self._paged_cleanup()
            self._release_adapters()
            raise

    def _spec_brownout(self) -> bool:
        """Once-per-run counting wrapper around the brownout spec
        lever: suppression is re-decided at every consultation (the
        queue may drain mid-batch, lifting it), but
        ``brownout_spec_suppressed`` ticks at most once per batch run
        — one suppressed engagement, however many chunk boundaries
        re-confirm it."""
        if self.eng._brownout_level() < 1:
            return False
        if not self._spec_supp_counted:
            self._spec_supp_counted = True
            self.eng.brownout_spec_suppressed += 1
        return True

    # -- per-tenant adapters (serving/adapter_store.py) ----------------

    def _acquire_adapter(self, req) -> int:
        """Resolve one request's adapter id to a resident device slot
        (installing from the host store on a miss) and pin it — the
        hold is released at the run's end, so a live batch's adapter
        can never be evicted under it. 0 (the NULL slot) for base
        requests: one attribute read, no locks."""
        aid = getattr(req, "adapter", None)
        if aid is None:
            return 0
        slot = self.eng.adapters.acquire(aid, self.eng.adapter_store)
        self._adapter_holds.append(aid)
        return slot

    def _release_adapters(self) -> None:
        """Drop every hold this run took (idempotent — the list
        empties). Slots stay RESIDENT (warm for the tenant's next
        request); they merely become evictable again."""
        while self._adapter_holds:
            self.eng.adapters.release(self._adapter_holds.pop())

    def _params(self):
        """The params pytree for this batch's next dispatch: plain
        (no adapter rows — the byte-identical base programs),
        GROUPED (every row one tenant: scalar slot marker, one
        ``x @ A @ B`` per target), or GATHERED (mixed tenants:
        per-row slot vector through ``ops/bgmv.py``; base and dummy
        rows index the all-zero NULL slot). Host-side decision per
        dispatch — the marker's pytree structure keys the traces
        apart, and the mode is counted once per run at its first
        adapter dispatch."""
        eng = self.eng
        if eng.adapters is None:
            return eng.params
        rows = self.arow[:self.b_cur]
        if not rows.any():
            return eng.params
        if bool(np.all(rows == rows[0])):
            if not self._adapter_counted:
                self._adapter_counted = True
                eng.adapter_grouped_batches += 1
            return eng.adapters.batch_params(
                eng.params, slot=int(rows[0])
            )
        if not self._adapter_counted:
            self._adapter_counted = True
            eng.adapter_gathered_batches += 1
        return eng.adapters.batch_params(eng.params, rows=rows)

    def _params1(self, slot: int):
        """Solo-row dispatch params (joiner prefills run the single
        candidate's row alone): the joiner's tenant via the grouped
        marker, or the plain tree for a base joiner."""
        eng = self.eng
        if not slot:
            return eng.params
        return eng.adapters.batch_params(eng.params, slot=slot)

    # -- disaggregation: chunk-boundary KV push (prefill replica) -----

    def _push_boundary(self, lo: int, hi: int) -> None:
        """The r18 chunk-boundary push hook: gather row 0's freshly
        written KV slots ``[lo, hi)`` to host (the device→host copy —
        forced here because the bytes must cross hosts either way)
        and hand them to the KVPush sender thread. The wire POST
        never runs on this thread, so a slow decode replica slows the
        TRANSFER, not the prefill. No-op for every non-push batch —
        one attribute read."""
        if self._push is None:
            return
        kv: dict = {}
        if self.pool is not None:
            page = self.page
            t0, t1 = lo // page, -(-hi // page)
            pages = np.asarray(self.tab[0, t0:t1])
            base = t0 * page
            from mlapi_tpu.ops.quant import paged_pools_of

            for ln, layer in paged_pools_of(self.cache).items():
                kv[ln] = {}
                for name, leaf in layer.items():
                    # [n, page, ...] gather → [1, n*page, ...] → the
                    # exact slot slice. Null-page tiles (pad slots the
                    # page-native row never mapped) contribute
                    # never-read bytes — masked on the decode side
                    # exactly as they are here.
                    a = np.asarray(leaf[pages])
                    a = a.reshape((1, a.shape[0] * page) + a.shape[2:])
                    kv[ln][name] = a[:, lo - base:hi - base]
        else:
            for ln, layer in self.cache.items():
                kv[ln] = {
                    name: np.asarray(leaf[0:1, lo:hi])
                    for name, leaf in layer.items()
                }
        self.eng.kv_push.send_chunk(
            self._push["xfer"], self._push["sent"], self._push["n"],
            (lo, hi), kv,
        )
        self._push["sent"] += 1

    # -- disaggregation: pushed-KV formation (decode replica) ---------

    def _prefill_pushed(self):
        """Install a pushed transfer's assembled prompt KV as this
        (solo) batch's row 0 — ZERO prefill FLOPs on this replica.
        Paged: the blob goes through the pool's alloc-first donated
        install (``PagePool.install_blob`` — ``PagePoolExhausted``
        propagates with nothing installed, the restore_entry
        ordering) and the pages become a PRIVATE table row; decode
        pages beyond the prompt allocate at chunk boundaries as
        usual. Contiguous: one admission-style scatter of the
        device_put blob into a fresh cache. Returns the ``[B]`` first
        token vector (the prefill replica sampled it from the final
        chunk's logits — same program, same key), or ``None`` to fall
        back to the cold prefill (geometry mismatch; counted)."""
        eng, r = self.eng, self.reqs[0]
        pushed = r.pushed
        if self.pool is not None:
            from mlapi_tpu.ops.quant import paged_cache_tree
            from mlapi_tpu.serving.kv_tier import (
                KVTierBlob,
                payload_bytes,
                payload_from_contiguous,
            )

            payload = payload_from_contiguous(pushed.kv, self.page)
            blob = KVTierBlob(
                None, payload, self.page, payload_bytes(payload),
                pushed.bucket, 0, pushed.used,
            )
            pages = self.pool.install_blob(blob)
            if pages is None:
                eng.kv_push.count_fallback()
                _log.debug(
                    "pushed blob does not match the local pool "
                    "geometry; cold prefill"
                )
                return None
            self.tab[0, :len(pages)] = pages
            self.cache = paged_cache_tree(eng.pool.layers, self.tab)
            self._tab_dirty = False
        else:
            import jax

            from mlapi_tpu.models.gpt import admit_scatter_fn

            # Validate the pushed tree against the model's OWN cache
            # leaves before any device work — the contiguous twin of
            # install_blob's geometry check. A cross-config peer
            # (different head dim, kv format) whose bucket/used
            # happened to match must still degrade to the counted
            # cold prefill, never a formation error (and never a
            # silent astype of wrong-format bytes into a live cache).
            proto = jax.eval_shape(
                lambda: eng.model.init_cache(1, pushed.bucket)
            )
            ok = True
            for ln, layer in proto.items():
                pl = pushed.kv.get(ln) if isinstance(pushed.kv, dict) \
                    else None
                if pl is None or set(pl) != set(layer):
                    ok = False
                    break
                for name, leaf in layer.items():
                    a = pl[name]
                    if a.shape != leaf.shape or a.dtype != leaf.dtype:
                        ok = False
                        break
                if not ok:
                    break
            if not ok or set(pushed.kv) != set(proto):
                eng.kv_push.count_fallback()
                _log.debug(
                    "pushed blob does not match the local cache "
                    "format; cold prefill"
                )
                return None
            mini = jax.tree.map(jnp.asarray, pushed.kv)
            self.cache = admit_scatter_fn()(
                eng.model.init_cache(self.b_pad, self.total), mini,
                jnp.int32(0), jnp.int32(0),
            )
        eng.kv_push.count_applied(pushed.nbytes)
        return jnp.asarray(
            np.full((self.b_pad,), pushed.first_token, np.int32)
        )

    # -- formation ----------------------------------------------------

    def _prefill(self):
        """Run the batch's prefill and set ``self.cache``; returns the
        ``[B]`` device vector of first sampled tokens."""
        eng, reqs = self.eng, self.reqs
        bucket, total = self.bucket, self.total
        from mlapi_tpu.models.gpt import prefill_fn, prefix_prefill_fn

        if (
            getattr(reqs[0], "pushed", None) is not None
            and self.b == 1 and not self.p_len
            and eng.kv_push is not None
        ):
            first = self._prefill_pushed()
            if first is not None:
                return first
            # Fallback: the cold prefill below — counted above.
        if self.pool is not None:
            return self._prefill_paged()
        if self.p_len:
            # Shared-prefix batch: the prefix KV is scattered into
            # every row and only the suffix block is computed — the
            # prefix's forward work is paid once per prefix, not once
            # per request. Cross-prefix batches pass the per-row
            # right-aligned KV stack + lo vector; same-fp batches keep
            # the broadcast [1, P] + scalar-lo program they always
            # compiled.
            lo_arg = (
                jnp.asarray(self.lo) if self.mixed_prefix
                else jnp.int32(self.p_lo)
            )
            kv_arg = (
                eng.prefix.stacked(reqs, self.p_len, self.b_pad)
                if self.mixed_prefix else reqs[0].prefix_kv
            )
            first, self.cache = prefix_prefill_fn(
                eng.model, bucket, total
            )(
                self._params(), kv_arg, jnp.asarray(self.prompt),
                jnp.asarray(self.n_pad), lo_arg,
                jnp.asarray(self.keys), jnp.asarray(self.temps),
                jnp.asarray(self.topk), jnp.asarray(self.topp),
            )
        elif (
            bucket > eng.prompt_buckets[-1]
            and bucket % eng.prompt_buckets[-1] == 0
        ):
            # Chunked prefill: the long prompt runs as fixed-width
            # extend_core blocks at a TRACED offset — one compiled
            # program per cache tier serves every prompt length,
            # instead of a bespoke compile per exact length.
            from mlapi_tpu.models.gpt import extend_chunk_fn, sample_fn

            cp = eng.prompt_buckets[-1]
            self.cache = eng.model.init_cache(self.b_pad, total)
            n_pad_j = jnp.asarray(self.n_pad)
            logits = None
            for c0 in range(0, bucket, cp):
                faults.fire("prefill_chunk")
                for r in reqs:
                    eng._expire_if_due(r, "prefill")
                eng.prefill_chunks += 1
                self.cache, logits = extend_chunk_fn(
                    eng.model, cp, total
                )(
                    self._params(), self.cache,
                    jnp.asarray(self.prompt[:, c0:c0 + cp]),
                    jnp.int32(c0), n_pad_j,
                )
                # r18: the finished chunk's KV streams to the decode
                # replica while the NEXT chunk computes (no-op for
                # non-push batches).
                self._push_boundary(c0, c0 + cp)
            first = sample_fn(eng.model)(
                logits, jnp.asarray(self.keys), jnp.asarray(self.temps),
                jnp.asarray(self.topk), jnp.asarray(self.topp),
            )
        else:
            first, self.cache = prefill_fn(eng.model, total)(
                self._params(), jnp.asarray(self.prompt),
                jnp.asarray(self.keys), jnp.asarray(self.temps),
                jnp.asarray(self.n_pad), jnp.asarray(self.topk),
                jnp.asarray(self.topp),
            )
            # r18: a bucket-sized prompt is one "chunk" — the whole
            # span pushes at its (single) boundary.
            self._push_boundary(0, bucket)
        return first

    # -- paged formation + page lifecycle ------------------------------

    def _alloc_rows(self, rows, lo_slot: int, hi_slot: int) -> None:
        """Allocate pool pages covering virtual slots
        ``[lo_slot, hi_slot)`` for the given device rows, skipping
        tiles already mapped. THE paged capacity lever: a row only
        ever holds pages covering slots it has actually reached, so
        padding waste is bounded by one page per row instead of the
        tier remainder. Raises :class:`PagePoolExhausted` BEFORE any
        device work, so a loud reject leaves the pool consistent."""
        if hi_slot <= lo_slot:
            return
        want: list[tuple[int, int]] = []
        for row in rows:
            for i in range(lo_slot // self.page,
                           -(-hi_slot // self.page)):
                if self.tab[row, i] == 0:
                    want.append((row, i))
        if not want:
            return
        pages = self.pool.alloc(len(want))
        for (row, i), pid in zip(want, pages):
            self.tab[row, i] = pid
        self._tab_dirty = True

    def _release_row(self, row: int) -> None:
        """Zero a device row's table and drop its page holds (shared
        prefix pages just lose one reference). In-flight chunks may
        still WRITE the released pages through the old device table —
        that is safe by the layout invariant that a row only READS
        (unmasked) slots it wrote itself: stale bytes land in slots a
        future owner has either not yet written (still masked for it)
        or will overwrite before its ``pos`` reaches them."""
        if self.tab[row].any():
            self.pool.release(self.tab[row])
            self.tab[row] = 0
            self._tab_dirty = True

    def _paged_cleanup(self, write_back: bool = True) -> None:
        """End-of-batch page release + pool write-back (idempotent;
        also the error path's safety net). ``write_back`` re-binds the
        engine pool's device arrays from the batch's last cache pytree
        — skipped when formation failed before a cache existed."""
        if self.pool is None or self.tab is None:
            return
        if self._pf is not None:
            # An in-progress interleaved prefill holds private pages.
            self.pool.release(self._pf["ptab"])
            self._pf = None
            self.eng.prefill_chunk_queue_depth = 0
        for row in range(len(self.tab)):
            self._release_row(row)
        if write_back and getattr(self, "cache", None) is not None:
            from mlapi_tpu.ops.quant import paged_pools_of

            self.pool.layers = paged_pools_of(self.cache)

    def _with_tables(self) -> None:
        """Re-upload the host page table into every layer of the cache
        pytree (each layer gets its own device copy — donation forbids
        one buffer appearing twice)."""
        from mlapi_tpu.ops.quant import paged_cache_tree

        self.cache = paged_cache_tree(self.cache, self.tab[:self.b_cur])
        self._tab_dirty = False

    def _ensure_pages(self, size: int, live: list) -> None:
        """Chunk-boundary page allocation: the next ``size`` decode
        steps write slots ``[pos, pos+size)`` — map them for every
        live row (dummy and finished rows write into the null page).
        Also flushes any pending host-table change to the device
        mirrors before the dispatch reads them."""
        self._alloc_rows(
            sorted({self.rows[i] for i in live}),
            self.pos, min(self.pos + size, self.total),
        )
        if self._tab_dirty:
            self._with_tables()

    def _spec_ensure(self, cache, lo: int, hi: int):
        """Page-allocation hook the speculative phase calls before
        each verify block: map virtual slots ``[lo, hi)`` for every
        live row (the phase writes ahead of the chunk loop's
        ``_ensure_pages``) and push any table change into the cache it
        is holding. Exhaustion raises loudly mid-phase — same contract
        as the chunk loop's boundary allocation."""
        from mlapi_tpu.ops.quant import paged_cache_tree

        self._alloc_rows(
            sorted({
                self.rows[i] for i, r in enumerate(self.reqs)
                if self.rows[i] is not None and not self.done[i]
                and not r.cancelled
            }),
            lo, min(hi, self.total),
        )
        if self._tab_dirty:
            self._tab_dirty = False
            return paged_cache_tree(cache, self.tab[:self.b_cur])
        return cache

    def _paged_realign(self, cache, delta: np.ndarray, top: int):
        """The batched-speculation handoff realign, paged: rows shift
        right by ``delta[row]`` so the scalar-``pos`` chunk loop can
        resume. When every delta is a page multiple this is a pure
        HOST table edit — each row's table rolls right by
        ``delta/page`` tiles (shifted-in leading tiles go null, masked
        by the caller's ``n_pad`` bump; shifted-off tail pages are
        released) — zero cache bytes move. Sub-page deltas fall back
        to the device row-gather rewrite (``paged_realign_fn``),
        O(live row bytes), counted loudly: the one case page identity
        cannot express."""
        import jax.numpy as jnp

        from mlapi_tpu.ops.quant import paged_cache_tree

        eng, page = self.eng, self.page
        if np.all(delta % page == 0):
            for row in range(self.b_cur):
                s = int(delta[row]) // page
                if s == 0:
                    continue
                dropped = self.tab[row, self.npv - s:]
                if dropped.any():
                    self.pool.release(dropped)
                self.tab[row] = np.roll(self.tab[row], s)
                self.tab[row, :s] = 0
            eng.spec_realign_table_ops += 1
            self._tab_dirty = False
            return paged_cache_tree(cache, self.tab[:self.b_cur])
        # Destination slots (every row's content ends at ``top`` after
        # the shift) must be mapped before the device gather writes —
        # LIVE rows only: a finished row's shifted bytes are never
        # read again, so its unmapped writes may die in the null page.
        from mlapi_tpu.models.gpt import paged_realign_fn

        for i, r in enumerate(self.reqs):
            row = self.rows[i]
            if row is None or self.done[i] or r.cancelled:
                continue
            self._alloc_rows(
                [row], int(self.n_pad[row] + delta[row]), top,
            )
        if self._tab_dirty:
            self._tab_dirty = False
            cache = paged_cache_tree(cache, self.tab[:self.b_cur])
        eng.spec_realign_repacks += 1
        return paged_realign_fn()(cache, jnp.asarray(delta))

    def _prefill_paged(self):
        """Paged formation: page-table setup (host) + prefill via the
        paged program set. PAGE-NATIVE (default): the bucket prefill
        writes K/V straight into pool pages through the table
        (``paged_prefill_fn`` — same forward, different append
        destination), so formation writes the prefill bytes exactly
        once and each row holds only the pages covering its REAL
        tokens (pad-slot writes land in the null page — prefill
        padding waste drops to sub-page, like decode's). The legacy
        r09 path (``prefill_page_native=False``) keeps the contiguous
        bucket prefill and ADOPTS its cache into pages — one full
        extra copy of the bytes prefill just wrote, counted exactly
        into ``eng.prefill_adopt_bytes`` (dtype/shape arithmetic).
        Chunked long prompts extend straight into the paged cache;
        prefix batches point their table rows at the entry's shared
        pages (ref-counted) and only compute the suffix."""
        eng = self.eng
        bucket = self.bucket
        import jax.numpy as jnp

        from mlapi_tpu.models.gpt import (
            paged_extend_fn, paged_prefill_fn, paged_scatter_fn,
            prefill_fn, sample_fn,
        )
        from mlapi_tpu.ops.quant import kv_tree_bytes, paged_cache_tree

        if self.p_len:
            return self._prefill_paged_prefix()
        cp = eng.prompt_buckets[-1]
        if bucket > cp and bucket % cp == 0:
            # Chunked long-prompt prefill, page-native: extend_core
            # writes every block straight into pool pages. Rows map
            # only the tiles covering their real tokens; the pad
            # blocks' dead writes land in the null page.
            for i in range(self.b):
                self._alloc_rows([i], int(self.n_pad[i]), bucket)
            self.cache = paged_cache_tree(
                eng.pool.layers, self.tab
            )
            self._tab_dirty = False
            n_pad_j = jnp.asarray(self.n_pad)
            logits = None
            for c0 in range(0, bucket, cp):
                faults.fire("prefill_chunk")
                for r in self.reqs:
                    eng._expire_if_due(r, "prefill")
                eng.prefill_chunks += 1
                self.cache, logits = paged_extend_fn(eng.model, cp)(
                    self._params(), self.cache,
                    jnp.asarray(self.prompt[:, c0:c0 + cp]),
                    jnp.int32(c0), n_pad_j, jnp.int32(0), jnp.int32(0),
                )
                # r18 chunk-boundary push (no-op off the disagg path).
                self._push_boundary(c0, c0 + cp)
            return sample_fn(eng.model)(
                logits, jnp.asarray(self.keys), jnp.asarray(self.temps),
                jnp.asarray(self.topk), jnp.asarray(self.topp),
            )
        if eng.prefill_page_native:
            # Page-native plain formation: allocate each row's real
            # span, then ONE fused prefill+sample writing through the
            # tables at virtual offset 0. Zero adopt bytes — there is
            # no contiguous intermediate to copy.
            for i in range(self.b):
                self._alloc_rows([i], int(self.n_pad[i]), bucket)
            self.cache = paged_cache_tree(eng.pool.layers, self.tab)
            self._tab_dirty = False
            first, self.cache = paged_prefill_fn(eng.model, bucket)(
                self._params(), self.cache, jnp.asarray(self.prompt),
                jnp.int32(0), jnp.asarray(self.keys),
                jnp.asarray(self.temps), jnp.asarray(self.n_pad),
                jnp.asarray(self.topk), jnp.asarray(self.topp),
            )
            self._push_boundary(0, bucket)  # r18: one-chunk push
            return first
        # Legacy: the bucket-length contiguous prefill (the same
        # program admission warms), adopted into pages — the extra
        # copy the page-native path exists to kill, kept measurable.
        first, mini = prefill_fn(eng.model, bucket)(
            self._params(), jnp.asarray(self.prompt),
            jnp.asarray(self.keys), jnp.asarray(self.temps),
            jnp.asarray(self.n_pad), jnp.asarray(self.topk),
            jnp.asarray(self.topp),
        )
        eng.prefill_adopt_bytes += kv_tree_bytes(mini)
        self._alloc_rows(range(self.b), 0, bucket)
        self.cache = paged_cache_tree(eng.pool.layers, self.tab)
        self._tab_dirty = False
        self.cache = paged_scatter_fn()(
            self.cache, mini, jnp.asarray(self.tab), jnp.int32(0)
        )
        self._push_boundary(0, bucket)  # r18: one-chunk push
        return first

    def _prefill_paged_prefix(self):
        """Paged shared-prefix formation. Same-fp batches SHARE the
        entry's pool pages: every live row's table points at them
        (one reference each), a partial last page is copied-on-write
        per row (the suffix's first tokens land mid-page), and only
        the suffix block is computed — the per-row prefix broadcast
        copy of the contiguous path is gone. Cross-prefix (stacked)
        batches now share the same way whenever every row's
        right-alignment shift ``P - prefix_len`` is a PAGE MULTIPLE:
        the row's table points at ITS entry's pages starting at tile
        ``shift/page`` (leading tiles stay null — masked below the
        row's ``lo``), ref-counted exactly like same-fp rows, with the
        group-end tile COW-diverged per row when ``P % page != 0``.
        Prefix entries page-align their buckets at store time
        (``PrefixCache._build``), so the aligned case is the norm; a
        group whose shifts are NOT page multiples (a cap-clamped
        entry) falls back to r09's widened-stack copy, counted loudly
        in ``eng.kv_prefix_copy_fallback``."""
        eng, reqs = self.eng, self.reqs
        import jax.numpy as jnp

        from mlapi_tpu.models.gpt import (
            paged_cow_fn, paged_extend_fn, paged_scatter_fn, sample_fn,
        )
        from mlapi_tpu.ops.quant import kv_tree_bytes, paged_cache_tree

        P, page = self.p_len, self.page
        npp = -(-P // page)
        # HOST PHASE first — every allocation that can raise
        # PagePoolExhausted happens before any donating device call,
        # so a loud reject can never leave the engine pool bound to
        # consumed buffers.
        adopts: list = []
        srcs, dsts = [], []

        def share_row(i: int, kv, entry_pages, need_adopt,
                      shift_tiles: int) -> None:
            """Point row ``i``'s table at an entry's pages (reference
            already held), COW-diverging the group-end tile when the
            suffix would write into it."""
            self.tab[i, shift_tiles:shift_tiles + len(entry_pages)] = (
                entry_pages
            )
            if need_adopt:
                adopts.append((kv, entry_pages))
            if P % page:
                # The group-end page is partially prefix: this row's
                # suffix will write into it, so diverge it by COW —
                # one page copied per row, not one cache.
                own = self.eng.pool.alloc(1)[0]
                srcs.append(int(entry_pages[-1]))
                dsts.append(int(own))
                self.eng.pool.release([entry_pages[-1]])
                self.tab[i, npp - 1] = own

        mixed_copy = False
        if not self.mixed_prefix:
            # holds=b: every live row's reference is taken atomically
            # with the entry lookup — a concurrent LRU eviction of
            # this entry can then only drop the ENTRY's own hold.
            # This call is ALSO where fleet warmth lands on the
            # dispatch thread (r17): a peer-fetched blob was staged
            # into the local tier at encode time (PrefixCache._restore,
            # executor thread), so paged_entry's tier consult finds it
            # HERE and restores pool pages through the alloc-first
            # restore_entry path — the formation never does wire I/O,
            # and a mid-restore failure conserves pages exactly like
            # the r13 local-tier case.
            entry_pages, need_adopt = eng.prefix.paged_entry(
                reqs[0].prefix_fp, reqs[0].prefix_kv, holds=self.b
            )
            for i in range(self.b):
                share_row(
                    i, reqs[0].prefix_kv, entry_pages,
                    need_adopt and i == 0, 0,
                )
        elif all((P - r.prefix_len) % page == 0 for r in reqs):
            # Aligned stacked group: each row shares ITS OWN entry's
            # ref-counted pages at a tile shift — no widened copy.
            for i, r in enumerate(reqs):
                entry_pages, need_adopt = eng.prefix.paged_entry(
                    r.prefix_fp, r.prefix_kv, holds=1
                )
                share_row(
                    i, r.prefix_kv, entry_pages, need_adopt,
                    (P - r.prefix_len) // page,
                )
        else:
            # Copy fallback: widened per-row stacks into private
            # pages — sub-page shifts page identity cannot express.
            eng.kv_prefix_copy_fallback += 1
            mixed_copy = True
            self._alloc_rows(range(self.b), 0, npp * page)
        # Suffix pages behind the prefix region.
        self._alloc_rows(range(self.b), npp * page, P + self.bucket)

        # DEVICE PHASE: adopt/copy/COW scatters, then ONE fused block
        # forward of the suffix against the shared pages.
        self.cache = paged_cache_tree(eng.pool.layers, self.tab)
        self._tab_dirty = False
        if mixed_copy:
            stack = eng.prefix.stacked(reqs, P, self.b_pad)
            eng.prefill_adopt_bytes += kv_tree_bytes(stack)
            self.cache = paged_scatter_fn()(
                self.cache, stack, jnp.asarray(self.tab[:, :npp]),
                jnp.int32(0),
            )
        for kv, entry_pages in adopts:
            # Once per entry LIFETIME: the entry's contiguous KV
            # becomes pool-resident (cache residency, not a per-batch
            # copy — counted apart from the formation adopt gauge).
            eng.prefix_adopt_bytes += kv_tree_bytes(kv)
            tab1 = np.zeros((1, len(entry_pages)), np.int32)
            tab1[0] = entry_pages
            self.cache = paged_scatter_fn()(
                self.cache, kv, jnp.asarray(tab1), jnp.int32(0)
            )
        if srcs:
            # Under the pool lock: cow_copies is scraped by /metrics
            # from the event loop while this decode-thread increment
            # runs (mlapi-lint MLA002, fixed r16).
            with self.eng.pool.lock:
                self.eng.pool.cow_copies += len(srcs)
            self.cache = paged_cow_fn()(
                self.cache,
                jnp.asarray(np.asarray(srcs, np.int32)),
                jnp.asarray(np.asarray(dsts, np.int32)),
            )
        lo_arg = (
            jnp.asarray(self.lo) if self.mixed_prefix
            else jnp.int32(self.p_lo)
        )
        self.cache, logits = paged_extend_fn(eng.model, self.bucket)(
            self._params(), self.cache, jnp.asarray(self.prompt),
            jnp.int32(P), jnp.asarray(self.n_pad), jnp.int32(P),
            lo_arg,
        )
        return sample_fn(eng.model)(
            logits, jnp.asarray(self.keys), jnp.asarray(self.temps),
            jnp.asarray(self.topk), jnp.asarray(self.topp),
        )

    def _first_token(self, first) -> None:
        """Decide the first token's delivery: the speculative phase
        reads/writes the host token mirror, so spec-eligible batches
        sync the first token here as before; everyone else CHAINS it —
        the prefill's sampled token stays on device as the first
        chunk's feedback and is delivered by the first drain, saving
        one readback round trip per request."""
        eng, reqs, b = self.eng, self.reqs, self.b
        temps, topk, topp = self.temps, self.topk, self.topp
        # Paged × speculative, fully lifted (r11). r10 lifted the
        # common case (solo spec needs no realign; the batched handoff
        # realigns as a host table shift or the counted row-gather)
        # but kept two declines. Both are gone:
        # - strict (high-RTT) mode: the spec warm grid now compiles the
        #   POOL-SHAPED verify/realign programs for paged engines
        #   (SpecPhase.warm branches on eng.pool), so the phase's own
        #   warmed-key gate admits paged batches without a mid-batch
        #   compile;
        # - mesh-sharded pools: flash-extend gave `_head_sharded_call`
        #   an extend leg, so pool-shaped verify blocks run per shard
        #   under an explicit shard_map (einsum verifies partition as
        #   plain GSPMD gather+einsum) — pinned end-to-end by
        #   tests/test_prefill_paged_native.py's former decline pins,
        #   rewritten as passing stream-identity tests.
        self.spec_eligible = (
            eng.draft_model is not None
            and b == 1 and self.p_len == 0
            and not reqs[0].cancelled
            # Disaggregated rows never speculate: a prefill-only run
            # ends at its first token, and a pushed row's stream must
            # stay structurally identical to the mixed replica's
            # chunked decode (greedy spec emits the same tokens, but
            # the draft replay from a wire-restored cache is a
            # surface r18 does not need).
            and reqs[0].push_to is None and reqs[0].pushed is None
            # Adapter rows never speculate: the spec phase drafts and
            # verifies against ``eng.params`` internally, which would
            # emit the BASE model's stream for a tenant row. getattr —
            # warmup requests are plain objects without the slot.
            and getattr(reqs[0], "adapter", None) is None
            and (
                (temps[0] <= 0.0 and topk[0] == 0 and topp[0] >= 1.0)
                or (eng.spec_sample and temps[0] > 0.0)
            )
            and not self._spec_brownout()  # brownout lever (counted)
        )
        # BATCHED speculation: a freshly-formed all-greedy batch
        # speculates as a whole — per-row acceptance lengths
        # desynchronize row positions (rank-polymorphic pos + vmapped
        # cache writes), and the phase REALIGNS the cache (per-row
        # roll, n_pad bump) before handing off to the scalar-pos chunk
        # loop, so admission keeps working. Needs k+1 slots of cache
        # headroom past every row's budget for the final round's
        # verify block.
        self.spec_batched = (
            eng.draft_model is not None
            and b > 1 and self.p_len == 0
            and bool(
                np.all(temps[:b] <= 0.0)
                and np.all(topk[:b] == 0)
                and np.all(topp[:b] >= 1.0)
            )
            and self.total >= (
                self.bucket + self.n_new_max + eng.spec_k + 1
            )
            # Same adapter decline as the solo gate, batch-wide.
            and all(getattr(r, "adapter", None) is None for r in reqs)
            # In strict (high-RTT) mode an unwarmed batched-spec shape
            # would decline inside the phase anyway — decide at
            # formation so such batches keep the chained (deferred)
            # first token instead of paying a synchronous readback for
            # nothing.
            and (
                not eng._strict_admit
                or (self.bucket, self.total, self.b_pad, "batched")
                in eng.spec.warmed
            )
            and not self._spec_brownout()  # brownout lever (counted)
        )
        # step[row]: the row's NEXT sampling-stream index — its own
        # produced-token count, NOT a batch-global counter, so a row
        # admitted later still reproduces its solo stream.
        self.step = np.ones((self.b_pad,), np.int32)
        self.done = [False] * b
        if self.spec_eligible or self.spec_batched:
            # np.array (copy): the spec phase mutates tok[0] in place;
            # np.asarray of a device array is read-only.
            self.tok = np.array(first)
            self.produced = [1] * b
            for i, r in enumerate(self.reqs):
                r.push({"token_ids": [int(self.tok[i])]})
                if r.n_new <= 1:
                    r.push(None)
                    self.done[i] = True
            self.first_chunk = None
        else:
            # set by first drain
            self.tok = np.zeros((self.b_pad,), np.int32)
            self.produced = [0] * b
            self.first_chunk = first[:, None]  # [B, 1] device, deferred
        # produced as of the DISPATCH frontier (tokens already
        # scheduled on device but possibly not yet drained); the
        # chained-dispatch loop schedules against this, while
        # ``produced`` tracks what was delivered.
        self.sched = list(self.produced)
        self.spec_hist: list | None = None
        if self.spec_eligible:
            self.spec_hist = [int(self.tok[0])]
        self._first = first  # device handle for the chain's feedback

    # -- shared bookkeeping -------------------------------------------

    def _mirrors_take(self, sel: np.ndarray) -> None:
        """Rebind every host mirror through a row gather — ALL of them
        together, so no stage can observe a half-resized batch."""
        self.n_pad, self.temps, self.topk, self.topp = (
            self.n_pad[sel], self.temps[sel], self.topk[sel],
            self.topp[sel],
        )
        self.tok, self.step, self.lo = (
            self.tok[sel], self.step[sel], self.lo[sel],
        )
        self.keys = self.keys[sel]
        self.arow = self.arow[sel]

    def _grow(self) -> list:
        """Double the batch along the warmed power-of-two chain; the
        new rows are dummies (fully masked) until admitted into.
        Paged growth moves ZERO cache bytes — new rows get null page
        tables (duplicating row 0's TABLE would alias its live pages)
        and only the host mirrors double; contiguous growth gathers
        the cache through the warmed ``_compact_fn`` shape. Shared by
        one-shot admission and the interleaved-prefill row claim.
        Returns the freshly-created free rows."""
        from mlapi_tpu.serving.engine import _compact_fn

        self.chain.invalidate()  # mirrors are about to be rebound
        sel = np.concatenate(
            [np.arange(self.b_cur), np.zeros(self.b_cur)]
        ).astype(np.int32)
        if self.pool is not None:
            self.tab = np.vstack([self.tab, np.zeros_like(self.tab)])
            self._tab_dirty = True
        else:
            self.cache = _compact_fn()(self.cache, jnp.asarray(sel))
            self.eng._warmed_growth.add(
                (self.b_cur, self.b_cur * 2, self.total)
            )
        self._mirrors_take(sel)
        self.n_pad[self.b_cur:] = self.pos  # mask dummies fully
        self.temps[self.b_cur:] = 0.0
        self.b_cur *= 2
        self.eng.growths += 1
        return list(range(self.b_cur // 2, self.b_cur))

    def _never_admissible(self, r) -> bool:
        """Token budget exceeds the running cache's remaining room —
        and ``pos`` only grows, so this can never change for THIS
        batch. Such requests must leave the admission list
        (→ ``_deferred``) rather than camp in it suppressing
        compaction and queue draining."""
        return self.pos + (r.n_new - 1) > self.total

    def _admissible(self, r) -> bool:
        """Can ``r`` join the RUNNING batch right now? Its prompt
        bucket must fit below the current decode position (``pos``
        grows, so a False here can flip True later) and its remaining
        tokens inside the remaining cache (the final chunk may be
        remainder-sized)."""
        return len(r.row) <= self.pos and not self._never_admissible(r)

    def _unstage(self, cand) -> None:
        eng = self.eng
        with eng._alock:
            try:
                eng._admit.remove(cand)
            except ValueError:
                pass

    def _claim(self, cand) -> None:
        """``cand`` is committed to this lane: the stamp that splits
        its TTFT into queue wait and prefill wait (a joiner handed
        back later is stamped again by whoever claims it next)."""
        cand.t_claim = time.perf_counter()
        self.claimed.append(cand.rid)

    def _deliver(self, toks_host, got, plive):
        self.tok = toks_host[:, -1].copy()
        for i in plive:
            r = self.reqs[i]
            if r.cancelled:
                continue
            want = r.n_new - self.produced[i]
            if want > 0:
                chunk_ids = toks_host[self.rows[i], : min(want, got)]
                r.push({"token_ids": chunk_ids.tolist()})
                if self.spec_hist is not None and i == 0:
                    self.spec_hist.extend(chunk_ids.tolist())
                self.produced[i] += got
                if want <= got:
                    r.push(None)
                    self.done[i] = True

    def _sdone(self, i: int) -> bool:
        """done[] as of the DISPATCH frontier: a row whose in-flight
        chunks already cover its budget must not be scheduled more
        device work."""
        return self.done[i] or self.sched[i] >= self.reqs[i].n_new

    # -- speculative phases -------------------------------------------

    def _try_spec(self) -> None:
        """Speculative decoding applies while this batch is one greedy
        row: the draft proposes spec_k tokens per round and the target
        verifies them in ONE block forward — fewer target weight
        passes per emitted token. The spec phase hands off to the
        normal chunk loop (which resumes from any (cache, pos, tok)
        state) the moment an admission candidate arrives, and
        RE-engages for the tail once transient joiners depart
        (spec_hist tracks the row's emitted tokens for the draft-cache
        replay)."""
        if (
            self.spec_hist is None or self.done[0]
            or self.reqs[0].cancelled
        ):
            return
        self.cache, self.pos = self.eng.spec.run_solo(
            self.reqs[0], self.cache, self.pos, self.total, self.bucket,
            self.tok, self.step, self.produced, self.n_pad, self.keys,
            self.spec_hist, self.temps, self.topk, self.topp,
            ensure=self._spec_ensure if self.pool is not None else None,
        )
        self.sched[0] = self.produced[0]
        if self.produced[0] >= self.reqs[0].n_new:
            self.reqs[0].push(None)
            self.done[0] = True

    def _spec_handoff(self) -> None:
        """Run the formation-time speculative phase (solo or batched),
        leaving ``(cache, pos, tok, produced)`` ready for the chunk
        loop."""
        self._try_spec()
        if self.spec_batched and not all(self.done):
            paged = self.pool is not None
            self.cache, self.pos = self.eng.spec.run_batched(
                self.reqs, self.cache, self.pos, self.total,
                self.bucket, self.prompt, self.tok, self.step,
                self.produced, self.done, self.n_pad, self.keys,
                self.b_pad,
                ensure=self._spec_ensure if paged else None,
                paged_realign=self._paged_realign if paged else None,
            )
            self.sched[:] = self.produced

    # -- continuous admission -----------------------------------------

    def _admit_waiting(self) -> int:
        """Admit staged joiners into free (or grown) device rows at a
        chunk boundary; returns the number of candidates still staged
        (the loop's compaction policy reads it)."""
        eng, reqs = self.eng, self.reqs
        from mlapi_tpu.models.gpt import admit_scatter_fn, prefill_fn

        with eng._alock:
            candidates = list(eng._admit)
        n_live = sum(
            1 for i, r in enumerate(reqs)
            if not self.done[i] and not r.cancelled
        )
        if self._pf is not None:
            n_live += 1  # the interleaved joiner owns its row already
        for cand in candidates:
            if eng._expire_if_due(cand, "queued"):
                # Its deadline passed while staged: terminal frame
                # pushed; never spend a prefill on it.
                self._unstage(cand)
                continue
            if cand.cancelled:
                self._unstage(cand)  # drop silently
                continue
            if cand.push_to is not None or cand.pushed is not None:
                # Disaggregated requests form their own solo batches
                # (same reason they never group at formation): defer
                # to the collector's next batch.
                self._unstage(cand)
                eng._defer(cand)
                continue
            if self.p_len or cand.prefix_fp is not None:
                # Prefix rows batch only at FORMATION time (incl.
                # cross-prefix groups): mid-batch admission would need
                # the running batch's region re-stacked and the
                # joiner's lo spliced into the live mirrors — the
                # admission scatter/regroup paths don't handle the
                # prefix mirrors (yet). Defer to the collector's next
                # batch.
                self._unstage(cand)
                eng._defer(cand)
                continue
            if (
                getattr(cand, "adapter", None) is not None
                and not eng.adapters.can_claim([cand.adapter])
            ):
                # Every adapter slot is pinned by this run's holds:
                # the joiner's acquire would fail mid-admission. Hand
                # it back — the next formation (fresh holds) pins its
                # adapter before any device work.
                self._unstage(cand)
                eng._defer(cand)
                continue
            bkt = len(cand.row)
            cp = eng.prompt_buckets[-1]
            if (
                self.pool is not None and eng.prefill_interleave
                and bkt > cp and bkt % cp == 0
            ):
                # LONG-PROMPT joiner: its prefill runs as chunked
                # extend dispatches INTERLEAVED with the running
                # batch's decode chunks (one prefill chunk per chunk
                # boundary), so in-flight streams stall by at most one
                # prefill-chunk dispatch instead of the whole prompt.
                taken = self._try_start_pf(cand, n_live)
                if taken:
                    n_live += 1
                continue
            if self._never_admissible(cand):
                # Hand back to the collector for the NEXT batch;
                # leaving it staged would block compaction and
                # backpressure for the whole run.
                self._unstage(cand)
                eng._defer(cand)
                continue
            if n_live + 1 > eng.max_batch:
                break
            if not self._admissible(cand):
                continue
            used_rows = {
                self.rows[i] for i, r in enumerate(reqs)
                if not self.done[i] and not r.cancelled
            }
            if self._pf is not None:
                used_rows.add(self._pf["row"])
            free = [
                j for j in range(self.b_cur) if j not in used_rows
            ]
            grow = not free and self.b_cur < self.b_max
            bkt = len(cand.row)
            if eng._strict_admit:
                # The EXPENSIVE compile (the joiner's prefill) is
                # keyed on the prompt bucket alone and must be
                # pre-warmed; the scatter/growth gathers are trivial
                # compiles, allowed on demand when the dispatch RTT is
                # low and required-warm when it is measured high,
                # where even a trivial compile stalls the running
                # batch. A shape miss cannot resolve
                # during this batch (warmed sets only grow via
                # admissions this mode forbids), so the joiner is
                # handed back for the next batch rather than left
                # camping in the staging list where it would block
                # compaction and draining.
                b_t = self.b_cur * 2 if grow else self.b_cur
                if self.pool is not None and eng.prefill_page_native:
                    # Page-native paged admission is ONE program —
                    # the joiner's direct-to-pages prefill, keyed on
                    # (bucket, table width) — so that is the whole
                    # gate (growth stays a host table op).
                    blocked = (bkt, self.npv) not in eng._warmed_scatter
                elif self.pool is not None:
                    # Legacy paged: growth is a host table op (nothing
                    # to warm) and the admission scatter is keyed on
                    # (bucket, table width) — batch-size-free.
                    blocked = bkt not in eng._warmed_joiner or (
                        not eng._admit_eager
                        and (bkt, self.npv) not in eng._warmed_scatter
                    )
                else:
                    blocked = bkt not in eng._warmed_joiner or (
                        not eng._admit_eager
                        and (
                            (bkt, self.total, b_t)
                            not in eng._warmed_scatter
                            or (
                                grow
                                and (
                                    self.b_cur, self.b_cur * 2,
                                    self.total,
                                )
                                not in eng._warmed_growth
                            )
                        )
                    )
                if blocked:
                    self._unstage(cand)
                    eng._defer(cand)
                    continue
            if not free and not grow:
                break
            # Committed: the joiner will mutate the host mirrors and
            # possibly the cache layout, so the dispatch chain ends
            # here (draining also brings `done` current for the
            # bookkeeping below). Candidates that merely unstage or
            # defer above never pay this — a camping incompatible
            # candidate must not degrade the batch to synced per-chunk
            # readbacks.
            self.chain.invalidate()
            # Leave the staging list BEFORE the device work, so a
            # mid-admission failure (the wrapper's except delivers the
            # error to every member of ``reqs``) cannot also re-serve
            # an already-admitted joiner from ``_admit``.
            self._unstage(cand)
            self._claim(cand)
            if grow:
                free = self._grow()
            row = free[0]
            if self.pool is not None:
                from mlapi_tpu.serving.paged_pool import (
                    PagePoolExhausted,
                )

                # The row may still hold a finished request's pages;
                # its slots restart at the joiner's region. Page-
                # native rows map only the REAL-token span — the
                # bucket's pad-slot writes land in the null page.
                self._release_row(row)
                lo = self.pos - (
                    cand.used if eng.prefill_page_native else bkt
                )
                try:
                    self._alloc_rows([row], lo, self.pos)
                except PagePoolExhausted:
                    # Not an error: the pool is momentarily full of
                    # live sequences — hand the joiner to the next
                    # batch instead of killing this one.
                    self._unstage(cand)
                    eng._defer(cand)
                    continue
            # True once a call that DONATES the batch cache has been
            # entered: past that point a failure may have consumed the
            # live buffers, and joiner-only recovery would hand every
            # later chunk deleted buffers — the poisoning class the
            # formation cleanup fix addresses. Such failures go
            # batch-fatal instead (run()'s cleanup returns the pages
            # and the wrapper delivers the error to every waiter).
            donating = False
            try:
                # Injection point: the admission INSTALL — after the
                # joiner's pages are allocated, before its prefill/
                # scatter dispatch. The except below is the r12
                # leak-window fix this point exists to pin.
                faults.fire("table_install")
                # Pin the joiner's adapter BEFORE its prefill
                # dispatches: a miss here (slots exhausted despite the
                # can_claim gate — racing acquire, or a store entry
                # evicted since encode) is joiner-only, handled by the
                # except below with nothing half-installed.
                jslot = self._acquire_adapter(cand)
                if self.pool is not None and eng.prefill_page_native:
                    # Page-native admission: ONE dispatch prefills the
                    # joiner's bucket straight into its freshly-mapped
                    # pages at virtual offset pos - bkt — the
                    # contiguous mini cache and its adopt scatter are
                    # gone (zero adopt bytes, same as formation).
                    from mlapi_tpu.models.gpt import paged_prefill_fn
                    from mlapi_tpu.ops.quant import paged_cache_tree

                    if self._tab_dirty:
                        self._with_tables()
                    cache1 = paged_cache_tree(
                        self.cache, self.tab[row:row + 1]
                    )
                    donating = True  # paged_prefill_fn donates cache1
                    first1, cache1 = paged_prefill_fn(eng.model, bkt)(
                        self._params1(jslot), cache1,
                        jnp.asarray(cand.row[None]),
                        jnp.int32(self.pos - bkt),
                        jnp.asarray(eng._key_data(cand.seed)[None]),
                        jnp.asarray(
                            np.asarray([cand.temperature], np.float32)
                        ),
                        jnp.asarray(
                            np.asarray([bkt - cand.used], np.int32)
                        ),
                        jnp.asarray(np.asarray([cand.top_k], np.int32)),
                        jnp.asarray(
                            np.asarray([cand.top_p], np.float32)
                        ),
                    )
                    self.cache = paged_cache_tree(
                        cache1, self.tab[:self.b_cur]
                    )
                    eng._warmed_scatter.add((bkt, self.npv))
                else:
                    first1, mini = prefill_fn(eng.model, bkt)(
                        self._params1(jslot),
                        jnp.asarray(cand.row[None]),
                        jnp.asarray(eng._key_data(cand.seed)[None]),
                        jnp.asarray(
                            np.asarray([cand.temperature], np.float32)
                        ),
                        jnp.asarray(
                            np.asarray([bkt - cand.used], np.int32)
                        ),
                        jnp.asarray(np.asarray([cand.top_k], np.int32)),
                        jnp.asarray(
                            np.asarray([cand.top_p], np.float32)
                        ),
                    )
                    if self.pool is not None:
                        from mlapi_tpu.models.gpt import paged_scatter_fn
                        from mlapi_tpu.ops.quant import kv_tree_bytes

                        eng.prefill_adopt_bytes += kv_tree_bytes(mini)
                        if self._tab_dirty:
                            self._with_tables()
                        donating = True  # scatter donates self.cache
                        self.cache = paged_scatter_fn()(
                            self.cache, mini,
                            jnp.asarray(self.tab[row:row + 1]),
                            jnp.int32(self.pos - bkt),
                        )
                        eng._warmed_scatter.add((bkt, self.npv))
                    else:
                        donating = True  # scatter donates self.cache
                        self.cache = admit_scatter_fn()(
                            self.cache, mini, jnp.int32(row),
                            jnp.int32(self.pos - bkt),
                        )
                        eng._warmed_scatter.add(
                            (bkt, self.total, self.b_cur)
                        )
                ftok = int(np.asarray(first1)[0])
            except Exception as e:  # noqa: BLE001 — joiner-only failure
                if donating:
                    # The donating dispatch itself failed: the batch
                    # cache may be bound to donation-consumed buffers,
                    # so continuing the batch would poison every later
                    # chunk. Batch-fatal — run()'s cleanup path.
                    raise
                # THE r12 mid-admission leak-window fix. A failure
                # between the joiner's page allocation and its install
                # (alloc-then-raise) used to propagate and kill the
                # WHOLE running batch; the joiner's freshly-mapped
                # pages were only returned by the batch teardown it
                # caused. Scope the blast radius to the joiner: give
                # its pages back (``kv_pages_in_use`` returns to its
                # pre-admission value — the row was released before
                # the alloc, so its table holds ONLY this admission's
                # pages), deliver the error as the joiner's terminal
                # frame (503-mapped for PagePoolExhausted), and let
                # the running batch stream on, token-identical — its
                # mirrors and cache were not yet touched for the
                # joiner.
                _log.warning(
                    "admission of joiner failed (%s); running batch "
                    "continues", e,
                )
                if self.pool is not None:
                    self._release_row(row)
                try:
                    cand.push(e)
                except Exception:
                    pass
                cand.cancel()
                continue
            self.n_pad[row] = self.pos - cand.used
            self.temps[row] = cand.temperature
            self.topk[row] = cand.top_k
            self.topp[row] = cand.top_p
            self.keys[row] = eng._key_data(cand.seed)
            # Row changes owner: ALWAYS reassign its adapter slot —
            # a reused row keeping a finished tenant's stale slot
            # would apply that adapter to this (possibly base) joiner.
            self.arow[row] = jslot
            self.tok[row] = ftok
            self.step[row] = 1
            reqs.append(cand)
            self.rows.append(row)
            self.produced.append(1)
            self.sched.append(1)
            cand.push({"token_ids": [ftok]})
            fin = cand.n_new <= 1
            if fin:
                cand.push(None)
            self.done.append(fin)
            if not fin:
                n_live += 1
            eng.admitted += 1
        with eng._alock:
            return len(eng._admit)

    # -- interleaved chunked prefill (paged long-prompt joiners) ------
    #
    # A long prompt's prefill is ceil(bucket/cp) fixed-width extend
    # dispatches. Run back-to-back (the r09 formation path) they stall
    # every in-flight decode stream for the whole prompt. Here they
    # become SCHEDULABLE UNITS: `_admit_waiting` stages the joiner as
    # `self._pf`, the chunk loop dispatches ONE prefill chunk per
    # decode-chunk boundary (`_pf_step`), and when the chunks are done
    # and `pos` reaches the planned activation point A, `_pf_activate`
    # installs the joiner with a pure page-table row assignment — the
    # prompt's K/V were written ONCE, into the joiner's private pages,
    # while decode kept running. Head-of-line cost to running streams:
    # exactly one prefill-chunk dispatch per boundary
    # (`eng.interleave_max_stall` pins it).
    #
    # Placement: the prompt lands at virtual slots [A - bucket, A)
    # where A = pos0 + m*chunk is fixed at admission (m covers the
    # chunk count, plus decode-only iterations when the prompt would
    # otherwise start below slot 0). During the window the loop must
    # advance pos by exactly `chunk` per iteration, so the spec
    # re-engage and compaction are suppressed while a prefill is
    # active (one-shot admissions and growth stay allowed — they never
    # move `pos`). The joiner's row stays a DUMMY (null table) until
    # activation, so interleaved decode writes for it die in the null
    # page instead of clobbering prompt pages. All-pad leading chunks
    # are skipped outright — nothing ever attends them.

    def _try_start_pf(self, cand, n_live: int) -> bool:
        """Begin an interleaved chunked prefill for ``cand`` (a
        long-prompt joiner). Returns True ONLY when the window
        STARTED (the joiner owns a device row and counts against
        ``max_batch``); every other outcome returns False — either
        the candidate was handed back to the collector (strict shape
        miss, a window that can never fit this batch's cache, pool
        exhaustion) or it stays staged for a later boundary (another
        prefill active, batch full)."""
        eng = self.eng
        from mlapi_tpu.serving.paged_pool import PagePoolExhausted

        if self._pf is not None:
            return False  # one interleaved prefill at a time
        if n_live + 1 > eng.max_batch:
            return False
        cp = eng.prompt_buckets[-1]
        bkt, used = len(cand.row), cand.used
        if eng._strict_admit and (cp, self.npv) not in eng._warmed_extend:
            self._unstage(cand)
            eng._defer(cand)
            return False
        # All-pad leading chunks are skipped (nothing attends them):
        # the dispatched window covers ceil(used/cp) chunks.
        bkt_eff = -(-used // cp) * cp
        n_run = bkt_eff // cp
        # Activation point A: decode advances `chunk` per boundary and
        # the prompt must END at the activation position (the row
        # joins the scalar-pos loop there), with its first real chunk
        # at a non-negative virtual slot — so A covers n_run
        # boundaries or the catch-up to the prompt's own length,
        # whichever is later. Chunks dispatch EAGERLY from the first
        # boundary (their write coordinates depend on A, not on the
        # current pos); any remaining boundaries are decode-only.
        m = max(n_run, -(-max(bkt_eff - self.pos, 0) // eng.chunk))
        A = self.pos + m * eng.chunk
        if A + (cand.n_new - 1) > self.total:
            # Can never finish inside this batch's cache window —
            # the collector forms it into its own batch instead.
            self._unstage(cand)
            eng._defer(cand)
            return False
        used_rows = {
            self.rows[i] for i, r in enumerate(self.reqs)
            if not self.done[i] and not r.cancelled
        }
        free = [j for j in range(self.b_cur) if j not in used_rows]
        if not free:
            if self.b_cur >= self.b_max:
                return False
            free = self._grow()
        row = free[0]
        self._release_row(row)  # a finished request's leftover pages
        try:
            # Pin the joiner's adapter before any pool pages move.
            pf_slot = self._acquire_adapter(cand)
        except Exception as e:  # noqa: BLE001 — joiner-only failure
            from mlapi_tpu.serving.adapter_store import (
                AdapterSlotsExhausted,
            )

            self._unstage(cand)
            if isinstance(e, AdapterSlotsExhausted):
                # Slots momentarily pinned by live runs: next batch.
                eng._defer(cand)
                return False
            # Unresolvable (store entry evicted since encode): the
            # error is this joiner's terminal frame; the batch and the
            # pool were never touched.
            try:
                cand.push(e)
            except Exception:
                pass
            cand.cancel()
            return False
        # Private table: the prompt's pages belong to `ptab` until
        # activation — the batch row stays a null-table dummy, so
        # interleaved decode writes for it stay in the null page.
        ptab = np.zeros((1, self.npv), np.int32)
        lo_tile = (A - used) // self.page
        hi_tile = -(-A // self.page)
        try:
            pages = self.pool.alloc(hi_tile - lo_tile)
        except PagePoolExhausted:
            # The pool is momentarily full of live sequences: hand
            # the joiner to the next batch, pool left consistent.
            self._unstage(cand)
            eng._defer(cand)
            return False
        ptab[0, lo_tile:hi_tile] = pages
        self._unstage(cand)
        self._claim(cand)
        self._pf = {
            "cand": cand, "row": row, "ptab": ptab, "A": A,
            "off": A - bkt, "cp": cp, "skip": (bkt - bkt_eff) // cp,
            "next": 0, "n_run": n_run, "logits": None,
            "slot": pf_slot,
        }
        eng.interleaved_prefills += 1
        eng.prefill_chunk_queue_depth = n_run
        return True

    def _pf_dispatch_chunk(self) -> None:
        """Dispatch the next prefill chunk through the joiner's
        private table (its virtual offset is already batch-virtual,
        so activation needs no remap)."""
        from mlapi_tpu.models.gpt import paged_extend_fn
        from mlapi_tpu.ops.quant import paged_cache_tree

        eng, pf = self.eng, self._pf
        cand, cp = pf["cand"], pf["cp"]
        c0 = (pf["skip"] + pf["next"]) * cp
        faults.fire("prefill_chunk")
        eng.prefill_chunks += 1
        cache1 = paged_cache_tree(self.cache, pf["ptab"])
        cache1, pf["logits"] = paged_extend_fn(eng.model, cp)(
            self._params1(pf["slot"]), cache1,
            jnp.asarray(cand.row[None, c0:c0 + cp]),
            jnp.int32(pf["off"] + c0),
            jnp.asarray(np.asarray([pf["A"] - cand.used], np.int32)),
            jnp.int32(0), jnp.int32(0),
        )
        self.cache = paged_cache_tree(cache1, self.tab[:self.b_cur])
        self._tab_dirty = False
        pf["next"] += 1
        eng.prefill_chunk_queue_depth = pf["n_run"] - pf["next"]
        eng._warmed_extend.add((cp, self.npv))

    def _pf_abort(self) -> None:
        """Drop a cancelled interleaved prefill: its private pages go
        back; nothing was installed, so no batch state unwinds."""
        self.pool.release(self._pf["ptab"])
        self._pf = None
        self.eng.prefill_chunk_queue_depth = 0

    def _pf_step(self, live: list) -> None:
        """One scheduling decision at a chunk boundary: dispatch at
        most ONE prefill chunk before the decode chunk — the bound
        `eng.interleave_max_stall` records."""
        eng, pf = self.eng, self._pf
        # A joiner whose deadline passed mid-prefill aborts its window
        # (terminal frame pushed; private pages go back) before the
        # next chunk spends device time on it.
        eng._expire_if_due(pf["cand"], "prefill")
        if pf["cand"].cancelled:
            self._pf_abort()
            return
        if pf["next"] >= pf["n_run"]:
            return  # chunks done; waiting for pos to reach A
        self._pf_dispatch_chunk()
        if live:
            self._pf_consec += 1
            eng.interleave_max_stall = max(
                eng.interleave_max_stall, self._pf_consec
            )

    def _pf_activate(self) -> None:
        """``pos`` reached the planned activation point with every
        chunk dispatched: sample the first token from the final
        chunk's logits (stream index 0 — the draw the formation paths
        make) and install the joiner as a live row. The install is a
        page-table ROW ASSIGNMENT — zero cache bytes move."""
        eng, pf = self.eng, self._pf
        cand, row = pf["cand"], pf["row"]
        from mlapi_tpu.models.gpt import sample_fn

        self.chain.invalidate()  # mirrors are about to change
        if cand.cancelled:
            self._pf_abort()
            return
        # Injection point: the activation-time table-row install (a
        # raise here is batch-fatal by design — run()'s except path
        # appends the staged joiner so every waiter gets its frame,
        # and the finally releases the private pages).
        faults.fire("table_install")
        first = sample_fn(eng.model)(
            pf["logits"], jnp.asarray(eng._key_data(cand.seed)[None]),
            jnp.asarray(np.asarray([cand.temperature], np.float32)),
            jnp.asarray(np.asarray([cand.top_k], np.int32)),
            jnp.asarray(np.asarray([cand.top_p], np.float32)),
        )
        ftok = int(np.asarray(first)[0])
        self._release_row(row)  # idempotent: eager release may have run
        self.tab[row] = pf["ptab"][0]
        self._tab_dirty = True
        self.n_pad[row] = pf["A"] - cand.used
        self.temps[row] = cand.temperature
        self.topk[row] = cand.top_k
        self.topp[row] = cand.top_p
        self.keys[row] = eng._key_data(cand.seed)
        # Row changes owner — same stale-slot rule as one-shot
        # admission: always reassign, even to 0.
        self.arow[row] = pf["slot"]
        self.tok[row] = ftok
        self.step[row] = 1
        self.reqs.append(cand)
        self.rows.append(row)
        self.produced.append(1)
        self.sched.append(1)
        cand.push({"token_ids": [ftok]})
        fin = cand.n_new <= 1
        if fin:
            cand.push(None)
        self.done.append(fin)
        eng.admitted += 1
        self._pf = None
        eng.prefill_chunk_queue_depth = 0

    def _pf_flush(self) -> None:
        """No live decode rows remain, so nothing can stall: run the
        remaining prefill chunks back-to-back, jump ``pos`` to the
        activation point (slots in between belong to no one — the
        joiner's mask starts at its own prompt), and activate."""
        pf = self._pf
        self.chain.drain()
        if pf["cand"].cancelled:
            self._pf_abort()
            return
        while pf["next"] < pf["n_run"]:
            self._pf_dispatch_chunk()
        self.pos = pf["A"]
        self._pf_activate()

    # -- resize -------------------------------------------------------

    def _maybe_shrink(self, live: list, pending_n: int) -> None:
        """Compact the device batch along the warmed halving chain
        when enough rows finished; at most one halving per chunk keeps
        the compaction shape set to the chain (8→4→2→1), which the
        warmup grid compiles — an arbitrary (from, to) jump would
        compile on the request path. Skip shrinking while joiners
        wait: they would force a regrow."""
        eng = self.eng
        from mlapi_tpu.serving.engine import _compact_fn

        want_b = 1
        while want_b < len(live):
            want_b *= 2
        want_b = max(want_b, self.b_cur // 2)
        # In strict non-eager mode (high measured RTT) a resize whose
        # gather shape was never compiled would stall the batch on a
        # compile — skip it and keep decoding at full width
        # instead (correct, just less compact). Shapes prove
        # themselves as warmup and low-RTT runs execute them.
        resize_ok = (
            self.pool is not None  # paged: no gather program to warm
            or not eng._strict_admit
            or eng._admit_eager
            or (self.b_cur, want_b, self.total) in eng._warmed_shrink
        )
        if want_b < self.b_cur and not pending_n and resize_ok:
            self.chain.invalidate()
            sel = [self.rows[i] for i in live]
            sel += [sel[0]] * (want_b - len(sel))
            sel = np.asarray(sel, np.int32)
            if self.pool is not None:
                # Paged compaction is O(table), not O(bytes): dropped
                # rows release their page holds (host refcounts), the
                # table gathers the survivors, and NO cache payload
                # moves. Pad rows get null tables (a duplicated table
                # row would alias live pages) and are masked fully so
                # their dead writes stay in the null page.
                keep = {self.rows[i] for i in live}
                for row in range(self.b_cur):
                    if row not in keep:
                        self._release_row(row)
                self.tab = self.tab[sel]
                self.tab[len(live):] = 0
                self._tab_dirty = True
                self._mirrors_take(sel)
                self.n_pad[len(live):] = self.pos
                self.temps[len(live):] = 0.0
            else:
                self.cache = _compact_fn()(self.cache, jnp.asarray(sel))
                eng._warmed_shrink.add((self.b_cur, want_b, self.total))
                self._mirrors_take(sel)
            self.rows = [None] * len(self.reqs)
            for row, i in enumerate(live):
                self.rows[i] = row
            self.b_cur = want_b
            eng.compactions += 1

    # -- chained chunk dispatch ---------------------------------------

    def _decode_chunk(self, size: int, live: list) -> None:
        """One decode chunk on the dispatch chain. decode_chunk_fn
        RETURNS the feedback token as a device array (last_tok), so
        consecutive chunks need no host round trip between them: the
        loop dispatches ahead and drains token readbacks lazily.
        A synced readback costs a host round trip while argument
        uploads pipeline for free, so this turns a request's serial
        cost from one round trip PER CHUNK into one readback at the
        end. Policy: non-incremental batches chain
        every chunk; a batch with any `stream` consumer keeps at most
        one chunk in flight (tokens land promptly); speculative solo
        batches stay synchronous (spec rounds read tokens by design).
        Anything that mutates batch state — admission, compaction, the
        spec phase — drains fully first and drops the device chain
        (the host mirrors are the source of truth again)."""
        eng = self.eng
        from mlapi_tpu.models.gpt import decode_chunk_fn

        faults.fire("decode")
        eng.chunk_calls += 1
        toks, self.cache, last_tok = decode_chunk_fn(eng.model, size)(
            self._params(), self.cache,
            self.chain.tok_dev if self.chain.tok_dev is not None
            else jnp.asarray(self.tok),
            jnp.int32(self.pos),
            jnp.asarray(self.n_pad), jnp.asarray(self.temps),
            jnp.asarray(self.keys), jnp.asarray(self.step),
            jnp.asarray(self.topk), jnp.asarray(self.topp),
            jnp.int32(self.p_len),
            jnp.asarray(self.lo) if self.mixed_prefix
            else jnp.int32(self.p_lo),
        )
        self.chain.push(toks, size, live)
        if size > eng.chunk:
            # A fused-width program compiled (or reused) for this
            # exact shape: record it at the dispatch site, so strict
            # mode's fused-width gate can never disagree with what
            # actually compiled.
            eng.fused.warmed.add((self.b_cur, self.total, size))
        for i in live:
            self.sched[i] += size
        self.step = self.step + np.int32(size)
        self.pos += size
        self.chain.tok_dev = last_tok
        if any(
            self.reqs[i].stream for i in self.chain.pending_live()
        ):
            # A chunk covering an incremental consumer may wait behind
            # at most ONE newer chunk — including a stream row's FINAL
            # chunk after it left `live` (its terminator must not ride
            # the chain until the co-batched requests finish).
            if len(self.chain) > 1:
                self.chain.drain(len(self.chain) - 1)
        elif len(self.chain) >= 4:
            # Bounded run-ahead: one overlapped readback window per 4
            # chunks keeps ~the full RTT win while cancellation and
            # mid-batch admission get a real sync point every few
            # chunks instead of after the whole generation.
            self.chain.drain()

    # -- the loop -----------------------------------------------------

    def run(self) -> None:
        # Scheduler-off entry: drain the unit generator to
        # exhaustion. Scheduler-on (serving/scheduler.py) advances the
        # SAME generator one unit at a time, interleaved with other
        # batches' units — the two modes execute identical code, which
        # is what makes the scheduler-on/off token-identity contract
        # structural rather than a matter of careful duplication.
        for _ in self.units():
            pass

    def units(self):
        """The batch lifecycle as a stream of TYPED SCHEDULABLE UNITS:
        yields one of ``"prefill"``, ``"decode"``, ``"spec"``,
        ``"admit"``, ``"compact"`` after each unit of device work, so
        an engine-level scheduler can interleave several batches'
        units on one device stream. Cleanup/error semantics live here
        (generator ``finally`` runs on exhaustion, raise, AND
        ``close()``), so a scheduler that kills a lane mid-flight
        still releases its pages."""
        try:
            yield from self._units()
        except BaseException:
            if self._pf is not None:
                # The interleaved joiner was unstaged but never
                # installed: append it so the engine wrapper's error
                # delivery reaches it too (it must not hang).
                self.reqs.append(self._pf["cand"])
            raise
        finally:
            # Paged: give every page back (shared prefix pages lose
            # one hold per row) and re-bind the engine pool's device
            # arrays from the batch's final cache — the pool outlives
            # the batch; that persistence is what makes prefix pages
            # shareable ACROSS batches.
            self._paged_cleanup()
            # Drop every adapter hold this run took: the slots stay
            # RESIDENT (warm for the tenants' next requests) but
            # become evictable again.
            self._release_adapters()

    def _units(self):
        eng, reqs, chain = self.eng, self.reqs, self.chain
        self._spec_handoff()
        if self.spec_eligible or self.spec_batched:
            # The formation-time speculative phase ran (it yields
            # internally at round boundaries when candidates or other
            # scheduler lanes wait — engine._spec_should_yield).
            yield "spec"

        if self.first_chunk is not None:
            # The deferred first token rides the chain as a width-1
            # chunk: delivered by the first drain, chained into
            # chunk 1 on device.
            all_rows = list(range(self.b))
            chain.push(self.first_chunk, 1, all_rows)
            for i in all_rows:
                self.sched[i] += 1
            chain.tok_dev = self._first

        while True:
            if (
                self._pf is not None
                and self._pf["next"] >= self._pf["n_run"]
                and self.pos >= self._pf["A"]
            ):
                # Interleaved prefill complete and the decode frontier
                # reached its activation point: install the joiner (a
                # table-row assignment) before this boundary's
                # admission/scheduling.
                self._pf_activate()
                yield "admit"
            # Deadline sweep at the chunk boundary: an expired row
            # gets its terminal DeadlineExceeded frame and cancels
            # exactly like a disconnect — it leaves ``live`` below,
            # and the paged eager sweep releases its pages.
            for i, r in enumerate(reqs):
                if not self.done[i]:
                    eng._expire_if_due(r, "decode")
            pending_n = 0
            if self.admit and eng._admit:
                pending_n = self._admit_waiting()
                yield "admit"
            live = [
                i for i, r in enumerate(reqs)
                if not self._sdone(i) and not r.cancelled
            ]
            if self.pool is not None:
                # Free finished/cancelled rows' pages EAGERLY (their
                # tables go null, so any still-chained writes for them
                # land in the null page) — under pool pressure a long
                # batch must not sit on dead sequences' pages.
                for i, r in enumerate(reqs):
                    row = self.rows[i]
                    if row is not None and (self.done[i] or r.cancelled):
                        self._release_row(row)
                        # Drop the mapping: the row may be reused by a
                        # joiner, and this request must never release
                        # the NEW owner's pages on a later sweep. (No
                        # pending chunk still lists a done row — its
                        # dispatch frontier was exhausted first.)
                        self.rows[i] = None
            if not live:
                if self._pf is not None:
                    # Nothing to stall: finish the interleaved prefill
                    # back-to-back and activate its row — it becomes
                    # the batch's only live member. One unit: with no
                    # live rows in THIS batch there is nothing its
                    # chunks can stall (other lanes wait one flush).
                    self._pf_flush()
                    yield "prefill"
                    continue
                # Every remaining consumer disconnected, finished, or
                # is fully covered by in-flight chunks: deliver what's
                # pending and stop scheduling device time.
                chain.drain()
                if not all(self.done):
                    eng.cancelled_batches += 1
                break
            # Re-engage speculation once the batch is a single greedy
            # row again (transient joiners departed): the spec phase
            # replays the row's history into a fresh draft cache and
            # resumes rounds for the tail. Its cheap disqualifiers
            # make this retry free when speculation cannot currently
            # help.
            if (
                self.spec_hist is not None and self.b_cur == 1
                and live == [0] and not pending_n
                # Never during an interleaved prefill: spec rounds
                # move `pos` off the activation-point plan.
                and self._pf is None
                # Cheap frontier-side disqualifiers first: breaking
                # the dispatch chain (a full drain) is only worth it
                # when the spec phase could actually run rounds.
                and reqs[0].n_new - self.sched[0] > 1
                and self.pos + 1 + eng.spec_k + 1 <= self.total
                # Brownout: under queue pressure speculation's extra
                # device work is the wrong trade — last in the chain
                # so the counter only ticks when it actually blocked
                # an engagement.
                and not self._spec_brownout()
            ):
                chain.invalidate()
                self._try_spec()
                yield "spec"
                if self.done[0]:
                    continue
            # Fused-chunk width (r20): an all-non-streaming batch
            # dispatches tier-wide decode chunks — the r03 dispatch
            # saving, one schedulable unit per fused chunk instead of
            # one uninterruptible whole-generation program. The width
            # shrinks to the live rows' remaining budgets and drops
            # to the plain chunk while a streaming joiner is hosted
            # (serving/fused_single.py owns the policy).
            w = self.fused_w and eng.fused.width_at(self, live)
            if w and not self._fused_counted:
                # Once per batch, at the first fused-width dispatch —
                # a strict-mode fallback that never engages must not
                # count as a fused run.
                self._fused_counted = True
                eng.fused_calls += 1
            # The final chunk may be remainder-sized: when
            # max_positions clamps the cache tier, (total - bucket)
            # need not be a chunk multiple, and a window-edge request
            # is owed the partial chunk (the old whole-chunk stop
            # silently ran past the cache end and corrupted the tail
            # positions).
            size = min(w or eng.chunk, self.total - self.pos)
            if size <= 0:
                chain.drain()
                break  # cache exhausted — safety net below
            # An active interleaved prefill suppresses compaction
            # (its row plan pins device row indices) — fold it into
            # the pending count the shrink policy already respects.
            b_before = self.b_cur
            self._maybe_shrink(
                live, pending_n + (1 if self._pf is not None else 0)
            )
            if self.b_cur != b_before:
                yield "compact"
            if self._pf is not None:
                # At most ONE prefill-chunk dispatch ahead of this
                # boundary's decode chunk — the interleaving bound.
                pfc = eng.prefill_chunks
                self._pf_step(live)
                if eng.prefill_chunks != pfc:
                    yield "prefill"
            if self.pool is not None:
                # Map the chunk's write range to pool pages (and push
                # any table change to the device mirrors) BEFORE the
                # dispatch — a pool-exhausted batch fails loudly here,
                # with the pool metadata still consistent.
                self._ensure_pages(size, live)
            self._decode_chunk(size, live)
            self._pf_consec = 0
            yield "decode"
        chain.drain()
        # Safety net: every waiter MUST get a terminator. The
        # collector/admission only group window-compatible requests,
        # so this fires only if that invariant is ever broken — a loud
        # error beats a silently-truncated hang.
        for i, r in enumerate(reqs):
            if self.done[i] or r.cancelled:
                continue
            _log.error(
                "request truncated at %d/%d tokens (batch window "
                "exhausted) — collector grouping bug?",
                self.produced[i], r.n_new,
            )
            r.push(RuntimeError(
                f"generation truncated at {self.produced[i]}/"
                f"{r.n_new} tokens (incompatible batch)"
            ))
