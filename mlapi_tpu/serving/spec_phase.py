"""The host-loop speculative-decoding phase of generative serving.

One :class:`SpecPhase` per :class:`TextGenerationEngine`: it owns the
warmed-shape set and runs the draft-propose / target-verify rounds —
solo (:meth:`run_solo`) and batched (:meth:`run_batched`) — plus the
startup warm grid (:meth:`warm`). The engine's ``_run_batch`` hands it
the live cache and host mirrors at a round boundary and resumes
chunked decoding from whatever ``(cache, pos)`` comes back; yield
discipline routes through ``engine._spec_should_yield`` (tests
monkeypatch it there). Split out of ``engine.py`` (r04 VERDICT
"Next" #7). This is one of two implementations of speculation: the
host-loop library in ``ops/speculative.py`` is the other, builds the
round programs both run, and is what the tests hold to plain decoding.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from mlapi_tpu.serving import faults

_log = logging.getLogger(__name__)


class SpecPhase:
    def __init__(self, engine):
        self.eng = engine
        # (bucket, total[, batch, "batched"]) spec-program shapes
        # proven compiled — strict mode runs the phase only for these.
        self.warmed: set = set()

    def run_solo(self, r, cache, pos, total, bucket, tok, step,
                    produced, n_pad, keys, history, temps, topk, topp,
                    ensure=None):
        """Run speculative rounds for a single request against the
        engine's live target cache; returns ``(cache, pos)`` for
        the normal decode loop to resume from. Mutates the host
        mirrors (``tok``, ``step``, ``produced``) in place — the
        handoff contract with ``_run_batch``. Library twins:
        ``ops/speculative.speculative_generate`` (greedy rows —
        byte-exact stream) and ``.speculative_sample`` (sampled rows
        under ``spec_sample=True`` — exact target distribution); this
        variant adds the engine's per-row pad mask, streaming pushes,
        admission handoff, and RE-ENGAGEMENT: ``history`` (the row's
        emitted tokens so far) replays into a fresh draft cache
        through already-compiled chunk programs, so a stream whose
        transient joiners departed speculates again for its tail.

        Each round is TWO device dispatches (scan-propose + verify)
        regardless of k — where the dispatch round trip dominates
        this, not the acceptance rate, sets the wall-clock win.

        ``ensure`` (paged targets): ``cache = ensure(cache, lo, hi)``
        maps virtual slots ``[lo, hi)`` to pool pages before each
        verify block writes them — the phase's stand-in for the chunk
        loop's boundary allocation. The DRAFT cache stays contiguous
        (the draft has no pool), so the draft-side programs are
        untouched by paging."""
        eng = self.eng
        from mlapi_tpu.models.gpt import (
            decode_chunk_fn, extend_chunk_fn, prefill_fn,
        )
        from mlapi_tpu.ops.speculative import (
            propose_fn, sample_verify_fn, verify_fn,
        )

        k = eng.spec_k
        # The draft prefill/replay are EXPENSIVE compiles: strict mode
        # requires them pre-warmed regardless of attach RTT (same rule
        # as the admission joiner prefill).
        if eng._strict_admit and (bucket, total) not in self.warmed:
            return cache, pos
        # Cheap disqualifiers BEFORE any device work: nothing to
        # speculate, no block room, or joiners already waiting.
        if r.n_new - produced[0] <= 1 or pos + 1 + k + 1 > total:
            return cache, pos
        if eng._spec_should_yield():
            return cache, pos

        npj = jnp.asarray(n_pad)
        zt = jnp.zeros((1,), jnp.float32)
        z0 = jnp.zeros((1,), jnp.int32)
        o1 = jnp.ones((1,), jnp.float32)
        keys_j = jnp.asarray(keys)

        # Draft prefill over the SAME padded prompt row (its KV layout
        # mirrors the target's, pads masked identically) ...
        row = np.full((1, bucket), eng.tokenizer.pad_id, np.int32)
        row[0, bucket - len(r.row):] = r.row
        _, d_cache = prefill_fn(eng.draft_model, total)(
            eng.draft_params, jnp.asarray(row), keys_j, zt, npj, z0, o1,
        )
        # ... then replay the already-emitted tokens (all but the
        # unconsumed last, which seeds the first round) in
        # fixed-width chunks plus single-step remainder — every
        # program already compiled for this (bucket, total).
        replay = history[:-1]
        d_replay_upto = bucket
        ri = 0
        while len(replay) - ri >= eng.chunk:
            blk = np.asarray([replay[ri:ri + eng.chunk]], np.int32)
            d_cache, _ = extend_chunk_fn(
                eng.draft_model, eng.chunk, total
            )(
                eng.draft_params, d_cache, jnp.asarray(blk),
                jnp.int32(d_replay_upto), npj,
            )
            d_replay_upto += eng.chunk
            ri += eng.chunk
        self.warmed.add((bucket, total))

        def dstep(dcache, token, at):
            toks, dcache, _ = decode_chunk_fn(eng.draft_model, 1)(
                eng.draft_params, dcache,
                jnp.asarray(np.asarray([token], np.int32)),
                jnp.int32(at), npj, zt, keys_j, jnp.int32(0), z0, o1,
                jnp.int32(0), jnp.int32(0),
            )
            return int(np.asarray(toks)[0, 0]), dcache

        while ri < len(replay):  # sub-chunk replay remainder
            _, d_cache = dstep(d_cache, replay[ri], d_replay_upto)
            d_replay_upto += 1
            ri += 1

        sampled = bool(temps[0] > 0.0)
        temps_j = jnp.asarray(temps)
        topk_j = jnp.asarray(topk)
        topp_j = jnp.asarray(topp)
        d_upto = t_upto = pos
        d_pend = [int(tok[0])]
        while not r.cancelled and produced[0] < r.n_new:
            if eng._expire_if_due(r, "decode"):
                break  # round boundary = a deadline dispatch boundary
            if eng._spec_should_yield():
                break  # joiners waiting: normal loop admits them
            budget = r.n_new - produced[0]
            if budget <= 1 or t_upto + 1 + k + 1 > total:
                break
            # Draft phase: ONE scanned dispatch consumes the pending
            # accepted tokens and chains all k proposals. Greedy rows
            # (temp 0) argmax inside the same program; sampled rows
            # draw from the draft's warped distribution at the
            # DRAFT-tagged per-token streams.
            if ensure is not None:
                # The verify block writes [t_upto, t_upto + k + 1).
                cache = ensure(cache, t_upto, t_upto + k + 1)
            step0 = int(produced[0])
            d_cache, props, q_probs = propose_fn(
                eng.draft_model, len(d_pend), k, sampled
            )(
                eng.draft_params, d_cache,
                jnp.asarray(np.asarray(d_pend, np.int32)),
                jnp.int32(d_upto), npj, keys_j, temps_j, topk_j,
                topp_j, jnp.int32(step0),
            )
            d_upto += len(d_pend) + k - 1
            usable = min(k, budget - 1)
            faults.fire("spec_verify")
            if sampled:
                cache, packed = sample_verify_fn(eng.model, k + 1)(
                    eng.params, cache, jnp.int32(int(tok[0])), props,
                    jnp.int32(t_upto), npj, q_probs, keys_j, temps_j,
                    topk_j, topp_j, jnp.int32(step0),
                    jnp.int32(usable),
                )
                packed = np.asarray(packed)
                m = int(packed[k + 1])
                emitted = packed[: m + 1].tolist()
                kth = int(packed[k - 1])  # props[k-1] when m == k
            else:
                proposals = np.asarray(props).tolist()
                cache, expect = verify_fn(eng.model, k + 1)(
                    eng.params, cache,
                    jnp.asarray(
                        np.asarray([[int(tok[0]), *proposals]], np.int32)
                    ),
                    jnp.int32(t_upto), npj,
                )
                expect = np.asarray(expect)[0]
                m = 0
                while m < usable and proposals[m] == int(expect[m]):
                    m += 1
                emitted = [*proposals[:m], int(expect[m])]
                kth = proposals[-1]
            r.push({"token_ids": emitted})
            history.extend(emitted)  # keeps replay state current
            produced[0] += m + 1
            step[0] = produced[0]
            t_upto += m + 1
            tok[0] = emitted[-1]
            eng.spec_rounds += 1
            eng.spec_drafted += usable
            eng.spec_accepted += m
            if m == k:
                d_pend = [kth, emitted[-1]]
            else:
                d_upto = t_upto
                d_pend = [emitted[-1]]
        return cache, t_upto

    def run_batched(self, reqs, cache, pos, total, bucket,
                            prompt, tok, step, produced, done, n_pad,
                            keys, b_cur, ensure=None,
                            paged_realign=None):
        """Speculative rounds for a WHOLE freshly-formed greedy batch:
        every row drafts k proposals and verifies them in one block
        per round, advancing by its OWN acceptance length (the
        rank-polymorphic per-row position layout). Rows that finish
        (or cancel) freeze and ride as dummies — their writes land
        beyond their valid bound, masked until the batch ends.

        Handoff: the phase exits at a round boundary when admission
        candidates arrive (or every row is done) and REALIGNS the
        cache — each row rolls right by ``max(t_upto) - t_upto_b``
        with ``n_pad`` bumped by the same amount, which keeps every
        effective position identical (wpe indices and stored rotary
        phases key on effective position) — so the scalar-``pos``
        chunk loop resumes exactly as if the batch had always been
        synchronized. Engages only at batch FORMATION; after a
        handoff the batch stays on the chunk loop (library twin with
        the full algebra: ``ops.speculative.speculative_generate_batched``).

        Paged targets pass ``ensure`` (per-round page mapping — see
        :meth:`run_solo`) and ``paged_realign(cache, delta, top)``,
        which replaces ``realign_fn``'s byte roll: a host page-table
        shift when every delta is a page multiple, the counted
        device row-gather rewrite otherwise (DESIGN §16).
        """
        eng = self.eng
        from mlapi_tpu.models.gpt import prefill_fn, realign_fn
        from mlapi_tpu.ops.speculative import (
            propose_batched_fn, verify_fn,
        )

        k = eng.spec_k
        key = (bucket, total, b_cur, "batched")
        if eng._strict_admit and key not in self.warmed:
            return cache, pos

        if eng._spec_should_yield():
            return cache, pos  # joiners already staged: skip the
            # whole-batch draft prefill, not just round one
        zb = jnp.zeros((b_cur,), jnp.int32)
        zt = jnp.zeros((b_cur,), jnp.float32)
        ob = jnp.ones((b_cur,), jnp.float32)
        npj = jnp.asarray(n_pad)
        keys_j = jnp.asarray(keys)
        _, d_cache = prefill_fn(eng.draft_model, total)(
            eng.draft_params, jnp.asarray(prompt), keys_j, zt, npj,
            zb, ob,
        )
        self.warmed.add(key)

        b = len(reqs)
        t_upto = np.full((b_cur,), pos, np.int64)
        d_upto = np.full((b_cur,), pos, np.int64)
        d_pend = [[int(tok[i])] for i in range(b_cur)]

        while True:
            if eng._spec_should_yield():
                break  # joiners waiting: realign and hand off
            for i in range(b):
                if not done[i]:
                    # Round boundary = dispatch boundary: expired rows
                    # cancel (terminal frame pushed) and freeze below.
                    eng._expire_if_due(reqs[i], "decode")
            active = [
                i for i in range(b)
                if not done[i] and not reqs[i].cancelled
                and reqs[i].n_new - produced[i] >= 1
            ]
            if not active:
                break
            # Desync-headroom invariant: after ANY round, the realign
            # frontier (max position, growing by <= k+1) plus the
            # laggiest row's remaining budget (shrinking by >= 1)
            # must still fit the cache — otherwise a lopsided round
            # could strand a slow row past the window and the chunk
            # loop would truncate it. Stop speculating one round
            # early instead; the synchronized chunk loop finishes
            # within the formation guarantee.
            rem = max(reqs[i].n_new - produced[i] for i in active)
            if int(t_upto.max()) + k + 1 + rem - 1 > total:
                break
            pend_buf = np.zeros((b_cur, 2), np.int32)
            n_in = np.ones((b_cur,), np.int32)
            for i in range(b_cur):
                pend = d_pend[i]
                n_in[i] = len(pend)
                pend_buf[i, : len(pend)] = pend
            d_cache, props, _ = propose_batched_fn(eng.draft_model, k)(
                eng.draft_params, d_cache, jnp.asarray(pend_buf),
                jnp.asarray(n_in),
                jnp.asarray(d_upto.astype(np.int32)), npj, keys_j,
                zt, zb, ob, zb,
            )
            props = np.asarray(props)
            d_upto += n_in + k - 1

            if ensure is not None:
                # Every row's verify block writes
                # [t_upto_b, t_upto_b + k + 1).
                cache = ensure(
                    cache, int(t_upto.min()), int(t_upto.max()) + k + 1
                )
            block = np.concatenate(
                [np.asarray(tok[:b_cur], np.int32)[:, None], props],
                axis=1,
            )
            faults.fire("spec_verify")
            cache, expect = verify_fn(eng.model, k + 1)(
                eng.params, cache, jnp.asarray(block),
                jnp.asarray(t_upto.astype(np.int32)), npj,
            )
            expect = np.asarray(expect)
            eng.spec_rounds += 1
            for i in active:
                r = reqs[i]
                budget = r.n_new - produced[i]
                usable = min(k, budget - 1)
                m = 0
                while m < usable and props[i, m] == int(expect[i, m]):
                    m += 1
                bonus = int(expect[i, m])
                emitted = [int(t) for t in props[i, :m]] + [bonus]
                r.push({"token_ids": emitted})
                produced[i] += m + 1
                step[i] = produced[i]
                t_upto[i] += m + 1
                tok[i] = bonus
                eng.spec_drafted += usable
                eng.spec_accepted += m
                if m == k:
                    d_pend[i] = [int(props[i, -1]), bonus]
                else:
                    d_upto[i] = t_upto[i]
                    d_pend[i] = [bonus]
                if produced[i] >= r.n_new:
                    r.push(None)
                    done[i] = True
            for i in range(b_cur):
                if i >= b or done[i] or (
                    i < b and reqs[i].cancelled
                ):
                    # Frozen/dummy rows: keep their state pinned so
                    # the realign delta stays correct.
                    d_upto[i] = t_upto[i]
                    d_pend[i] = d_pend[i][-1:]

        top = int(t_upto.max())
        if int(t_upto.min()) < top:
            delta = (top - t_upto).astype(np.int32)
            if paged_realign is not None:
                cache = paged_realign(cache, delta, top)
            else:
                cache = realign_fn()(cache, jnp.asarray(delta))
            n_pad += delta  # in place: the chunk loop's mirror
        return cache, top

    def _target_cache(self, bsz: int, total: int):
        """A target-side cache pytree of the shape the phase's verify
        programs will ACTUALLY take for a ``bsz``-row batch at tier
        ``total``: contiguous for contiguous engines; for paged
        engines the pool leaves + a null ``[bsz, npv]`` table — the
        exact operand shapes ``BatchRun`` dispatches, which is what
        makes the warmed keys honest for paged batches (the r10
        strict-mode decline existed because this used to warm
        contiguous shapes a paged batch never dispatches). Null-table
        warm writes land in the never-read null page, so the pool is
        untouched; callers must hand the donated result back through
        :meth:`_rebind_pool`."""
        eng = self.eng
        if eng.pool is None:
            return eng.model.init_cache(bsz, total)
        from mlapi_tpu.ops.quant import paged_cache_tree

        npv = -(-total // eng.pool.page)
        return paged_cache_tree(
            eng.pool.layers, np.zeros((bsz, npv), np.int32)
        )

    def _rebind_pool(self, cache) -> None:
        """Donating warm programs consumed the pool's device arrays;
        re-bind them from the returned cache (no-op contiguous)."""
        if self.eng.pool is not None:
            from mlapi_tpu.ops.quant import paged_pools_of

            self.eng.pool.layers = paged_pools_of(cache)

    def warm(self) -> int:
        """Compile the speculative-phase programs (draft prefill, the
        scanned propose for both pending widths, the verify block —
        greedy argmax and, under ``spec_sample``, the sampled
        acceptance-rejection variant — and the replay-remainder step)
        for every prompt bucket at the default cache tier, off the
        request path. PAGED engines warm the POOL-SHAPED target
        programs (verify blocks over pool leaves + tables, and the
        sub-page realign repack) — the missing piece that kept
        strict-admit mode declining paged speculation (r10 → r11)."""
        eng = self.eng
        from mlapi_tpu.models.gpt import (
            decode_chunk_fn, extend_chunk_fn, prefill_fn,
        )
        from mlapi_tpu.ops.speculative import (
            propose_fn, sample_verify_fn, verify_fn,
        )

        shapes = 0
        zt = jnp.zeros((1,), jnp.float32)
        z0 = jnp.zeros((1,), jnp.int32)
        o1 = jnp.ones((1,), jnp.float32)
        key1 = jnp.asarray(eng._key_data(0)[None])
        k = eng.spec_k
        for bucket in eng.prompt_buckets:
            total = eng._cache_len(bucket, eng.default_max_new_tokens)
            if bucket + 1 + k + 1 > total:
                continue
            row = np.full((1, bucket), eng.tokenizer.pad_id, np.int32)
            npj = jnp.asarray(np.asarray([bucket - 1], np.int32))
            _, d_cache = prefill_fn(eng.draft_model, total)(
                eng.draft_params, jnp.asarray(row), key1, zt, npj,
                z0, o1,
            )
            # Rounds start from 1 pending token (partial acceptance)
            # or 2 (a fully-accepted round's unfed k-th proposal);
            # sampled speculation compiles its own propose variant.
            variants = (False, True) if eng.spec_sample else (False,)
            for n_in in (1, 2):
                for sampled in variants:
                    d_cache, _, _ = propose_fn(
                        eng.draft_model, n_in, k, sampled
                    )(
                        eng.draft_params, d_cache,
                        jnp.asarray(np.zeros((n_in,), np.int32)),
                        jnp.int32(bucket), npj, key1,
                        o1 if sampled else zt, z0, o1,
                        jnp.int32(0),
                    )
            _, d_cache, _ = decode_chunk_fn(eng.draft_model, 1)(
                eng.draft_params, d_cache, jnp.asarray(
                    np.zeros((1,), np.int32)
                ),
                jnp.int32(bucket), npj, zt, key1, jnp.int32(0), z0, o1,
                jnp.int32(0), jnp.int32(0),
            )
            block = np.zeros((1, k + 1), np.int32)
            wcache, _ = verify_fn(eng.model, k + 1)(
                eng.params, self._target_cache(1, total),
                jnp.asarray(block), jnp.int32(bucket), npj,
            )
            self._rebind_pool(wcache)
            if eng.spec_sample:
                wcache, _ = sample_verify_fn(eng.model, k + 1)(
                    eng.params, self._target_cache(1, total),
                    jnp.int32(0),
                    jnp.asarray(np.zeros((k,), np.int32)),
                    jnp.int32(bucket), npj,
                    jnp.full((k, eng.model.vocab_size),
                             1.0 / eng.model.vocab_size, np.float32),
                    key1, o1, z0, o1, jnp.int32(0), jnp.int32(k),
                )
                self._rebind_pool(wcache)
            if bucket + eng.chunk <= total:
                # Re-engagement replays history in chunk-wide blocks.
                extend_chunk_fn(eng.draft_model, eng.chunk, total)(
                    eng.draft_params, d_cache,
                    jnp.asarray(
                        np.zeros((1, eng.chunk), np.int32)
                    ),
                    jnp.int32(bucket), npj,
                )
            self.warmed.add((bucket, total))
            shapes += 1
            # Batched-speculation grid: the whole-batch draft
            # prefill, the per-row propose scan, the vector-position
            # verify retrace, and the realign roll, per batch size.
            from mlapi_tpu.models.gpt import realign_fn
            from mlapi_tpu.ops.speculative import propose_batched_fn

            # No batch of size 2 can ever form when max_batch < 2 —
            # skip the whole batched grid rather than paying its
            # draft-prefill/propose/verify/realign compiles at startup.
            bsz = 2
            while eng.max_batch > 1 and bsz <= max(
                2, 1 << (eng.max_batch - 1).bit_length()
            ):
                bt = total  # the enclosing loop's tier
                rows_b = np.full(
                    (bsz, bucket), eng.tokenizer.pad_id, np.int32
                )
                np_b = jnp.asarray(
                    np.full((bsz,), bucket - 1, np.int32)
                )
                keys_b = jnp.asarray(
                    np.stack([eng._key_data(0)] * bsz)
                )
                ztb = jnp.zeros((bsz,), jnp.float32)
                zbb = jnp.zeros((bsz,), jnp.int32)
                obb = jnp.ones((bsz,), jnp.float32)
                _, dcb = prefill_fn(eng.draft_model, bt)(
                    eng.draft_params, jnp.asarray(rows_b), keys_b,
                    ztb, np_b, zbb, obb,
                )
                propose_batched_fn(eng.draft_model, k)(
                    eng.draft_params, dcb,
                    jnp.asarray(np.zeros((bsz, 2), np.int32)),
                    jnp.asarray(np.ones((bsz,), np.int32)),
                    jnp.asarray(np.full((bsz,), bucket, np.int32)),
                    np_b, keys_b, ztb, zbb, obb, zbb,
                )
                wcache, _ = verify_fn(eng.model, k + 1)(
                    eng.params, self._target_cache(bsz, bt),
                    jnp.asarray(np.zeros((bsz, k + 1), np.int32)),
                    jnp.asarray(np.full((bsz,), bucket, np.int32)),
                    np_b,
                )
                self._rebind_pool(wcache)
                if eng.pool is None:
                    realign_fn()(
                        eng.model.init_cache(bsz, bt), zbb,
                    )
                else:
                    # The paged handoff's page-aligned case is a host
                    # table op (nothing to compile); warm the counted
                    # sub-page device repack so a strict-mode batch
                    # never pays its compile mid-phase.
                    from mlapi_tpu.models.gpt import paged_realign_fn

                    wcache = paged_realign_fn()(
                        self._target_cache(bsz, bt), zbb,
                    )
                    self._rebind_pool(wcache)
                self.warmed.add((bucket, bt, bsz, "batched"))
                shapes += 1
                bsz *= 2
        return shapes

