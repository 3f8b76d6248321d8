"""Speculative decoding: a small DRAFT model proposes k tokens, the
TARGET model verifies them all in ONE block forward.

Decode on TPU is one full target-weight read per token; verification
reads the target weights once per ROUND of up to k+1 tokens, so with
an in-domain draft the target's HBM bill drops by the mean accepted
length. Greedy-exact: the emitted stream is byte-identical to plain
target-only greedy decoding (accepted drafts ARE the target's argmax;
the round's last token is the target's own argmax after them) — the
guarantee the tests pin, including with draft == target where every
round must accept the full k+1.

TPU-first mechanics worth noting:

- **Rollback is free.** Rejected draft positions leave stale K/V in
  the target cache, but attention masks ``idx <= pos`` and the next
  round overwrites them — no copies, no cache surgery, static shapes
  throughout.
- The verify block is ``extend_core(all_logits=True)`` — one fused
  program per (k+1) width, position-offset traced, so a generation
  compiles exactly three programs (target prefill, verify block,
  draft step) regardless of length.
- The draft runs single-token steps through the same
  ``decode_chunk_fn`` program the serving engine uses.

This module is the LIBRARY implementation: host loops over jitted
round programs, one row (:func:`speculative_generate`,
:func:`speculative_sample`) or a whole batch with a cache position
per row (:func:`speculative_generate_batched`,
:func:`speculative_sample_batched`), and the oracle the tests hold to
plain decoding. The other implementation is the serving engine's own
(``serving/spec_phase.py``), which runs the same round programs
(:func:`propose_fn`, :func:`verify_fn` and their sampled and batched
twins) against the engine's live caches between scheduler units.

Two schemes share the round/cache algebra:

- :func:`speculative_generate` — greedy (temperature 0), emitted
  stream byte-identical to plain target greedy decoding.
- :func:`speculative_sample` — temperature > 0 via the
  acceptance-rejection rule of Leviathan et al. / Chen et al.
  (accept draft token x with prob ``min(1, p(x)/q(x))``; on the
  first rejection sample from the residual ``norm(max(p - q, 0))``):
  the emitted stream is distributed EXACTLY as plain target sampling
  with the same temperature/top-k/top-p warps, though not
  byte-identical to the non-speculative stream for a given seed (the
  two consume randomness differently — an inherent property of the
  scheme, not an implementation gap).

Both run the draft phase as ONE jitted program per round
(:func:`propose_fn`, a ``lax.scan`` over single decode steps that
consumes the round's pending tokens and chains all k proposals) —
wherever a dispatch has a fixed host cost that is the difference
between ``k + 1`` device round trips per round and 2.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

# Distinct fold_in namespaces so the draft's sampling stream, the
# acceptance uniforms, and the residual/bonus draws are mutually
# independent while all deriving from the ONE request key. Within a
# tag, index = the emitted-token position it decides — each
# output-affecting draw has a unique (tag, index) and is never reused
# for a different role. The tags sit far above any reachable token
# index (engine max_new_tokens tiers are << 2**30) so a tagged
# namespace root can never collide with an untagged per-token
# fold_in(key, token_index) drawn by the plain chunked decode path —
# threefry fold_in and random-bits share one counter space, so a
# collision would correlate draft/acceptance key material with an
# emitted token's draw.
_DRAFT_TAG = 1 << 30
_ACC_TAG = (1 << 30) + 1
_RES_TAG = (1 << 30) + 2


@dataclass
class SpecStats:
    rounds: int = 0
    drafted: int = 0
    accepted: int = 0
    emitted: int = 0
    fallback_steps: int = 0  # first-draft mismatch → plain decode step
    per_round: list = field(default_factory=list)

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def tokens_per_round(self) -> float:
        return self.emitted / self.rounds if self.rounds else 0.0


@functools.cache
def _zero_key():
    """Greedy decoding never consumes randomness; one shared dummy
    key avoids rebuilding it in the per-token hot loop."""
    return jnp.asarray(
        np.asarray(jax.random.key_data(jax.random.key(0)))[None]
    )


def _prefill(model, params, prompt_ids, total):
    from mlapi_tpu.models.gpt import prefill_fn

    b, _ = prompt_ids.shape
    first, cache = prefill_fn(model, total)(
        params, prompt_ids, _zero_key(),
        jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.float32),
    )
    return int(np.asarray(first)[0]), cache


def _step(model, params, cache, tok, pos):
    """One greedy decode step; returns (next_tok, cache)."""
    from mlapi_tpu.models.gpt import decode_chunk_fn

    toks, cache, _ = decode_chunk_fn(model, 1)(
        params, cache, jnp.asarray(np.asarray([tok], np.int32)),
        jnp.int32(pos), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.float32), _zero_key(), jnp.int32(0),
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.float32),
        jnp.int32(0), jnp.int32(0),
    )
    return int(np.asarray(toks)[0, 0]), cache


@functools.lru_cache(maxsize=32)
def verify_fn(model, width: int):
    """Jitted verify block: greedy argmax at every position of a
    ``[B, width]`` token block extended onto the target cache at a
    traced offset, honoring per-row left-pad masks (``n_pad``) so the
    serving engine's bucketed rows verify identically to unpadded
    library rows."""

    def _run(params, cache, block, pos0, n_pad):
        cache, logits = model.extend_core(
            params, cache, block, pos0, n_pad,
            jnp.int32(0), jnp.int32(0), all_logits=True,
        )
        return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return jax.jit(_run, donate_argnums=(1,))


def _warped_probs(logits, temps, top_k, top_p):
    """The exact distribution ``models.gpt._pick_token`` samples from
    for ``temps > 0`` rows: softmax of top-k/top-p-filtered
    temperature-scaled logits ``[B, V]``. Sharing the model zoo's own
    filter keeps the acceptance ratio ``p/q`` exactly 1 when draft ==
    target (the 100%-acceptance pin). Greedy rows (``temps <= 0``)
    have no sampling distribution — callers route them to the argmax
    verify instead."""
    from mlapi_tpu.models.gpt import _filter_top_k_top_p

    v = logits.shape[-1]
    safe_t = jnp.where(temps > 0.0, temps, 1.0)
    scaled = logits / safe_t[:, None]
    need = jnp.any((top_k > 0) & (top_k < v)) | jnp.any(
        (top_p > 0.0) & (top_p < 1.0)
    )
    scaled = jax.lax.cond(
        need,
        lambda s: _filter_top_k_top_p(s, top_k, top_p),
        lambda s: s,
        scaled,
    )
    return jax.nn.softmax(scaled, axis=-1)


@functools.lru_cache(maxsize=64)
def propose_fn(model, n_in: int, k: int, sampled: bool = False):
    """Jitted DRAFT PHASE: one ``lax.scan`` program that consumes the
    round's ``n_in`` pending accepted tokens (cache writes at
    ``pos0..``) and chains ``k`` proposals — the last consume's output
    distribution yields proposal 1. One device dispatch replaces the
    ``n_in + k - 1`` chained single-step calls (each a full host
    round trip) the first implementation made.

    ``sampled`` is STATIC (part of the compile key): greedy rounds
    argmax with none of the warp/softmax/PRNG machinery in the
    program (temp is traced, so a runtime select could not be
    dead-code-eliminated); sampled rounds draw each proposal from the
    draft's warped distribution at stream
    ``fold(fold(key, DRAFT), step0+i)`` (``i`` = proposal index).
    Returns ``(cache, proposals [k], q_probs [k, V])`` — ``q_probs``
    stays on device for the sampled verify; zeros (unused) in the
    greedy variant.
    """

    def _run(params, cache, toks_in, pos0, n_pad, key_data, temp,
             topk, topp, step0):
        def body(carry, i):
            cache, tok = carry
            logits, cache = model.decode_step(
                params, cache, tok[:, None], pos0 + i, n_pad
            )
            if sampled:
                probs = _warped_probs(logits, temp, topk, topp)
                prop_i = jnp.maximum(i - (n_in - 1), 0) + step0
                keys = jax.vmap(
                    lambda kd: jax.random.fold_in(
                        jax.random.fold_in(
                            jax.random.wrap_key_data(kd), _DRAFT_TAG
                        ),
                        prop_i,
                    )
                )(key_data)
                nxt = jax.vmap(
                    lambda kk, pr: jax.random.categorical(
                        kk, jnp.log(pr)
                    )
                )(keys, probs).astype(jnp.int32)
            else:
                # Greedy: no distribution to carry — a zero-width
                # placeholder keeps the scan ys structure without
                # stacking a [steps, V] buffer nobody reads.
                probs = jnp.zeros((1, 0), jnp.float32)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if n_in > 1:
                feed = jnp.where(
                    i + 1 < n_in,
                    toks_in[jnp.minimum(i + 1, n_in - 1)],
                    nxt[0],
                )
                nxt = jnp.reshape(feed, (1,))
            return (cache, nxt), (nxt[0], probs[0])

        (cache, _), (toks, probs) = jax.lax.scan(
            body, (cache, toks_in[:1]), jnp.arange(n_in + k - 1)
        )
        return cache, toks[n_in - 1:], probs[n_in - 1:]

    return jax.jit(_run, donate_argnums=(1,))


def _accept_and_draw(key, pr, q_probs, props, usable, step0):
    """The distribution-critical acceptance-rejection core shared by
    the solo and batched jitted verifies (:func:`sample_verify_fn`,
    :func:`sample_verify_batched_fn`): test each proposal with ``u*q < p``
    (ACC-tagged per-token uniforms), find the first rejection ``m``
    (capped by ``usable``), and draw the round's final token — from
    the normalized residual ``max(p_m - q_m, 0)`` at a NATURAL
    rejection, else from the full target distribution ``p_m``
    (all-accepted bonus / budget-capped round) — on the RES-tagged
    stream at the token's own index. Returns ``(m, final_token)``.

    ``pr``: warped target probs ``[k+1, V]``; ``q_probs``: draft
    probs ``[k, V]``; ``props``: ``[k]`` proposal ids.
    """
    k, v = q_probs.shape[0], pr.shape[-1]
    idx = jnp.arange(k)
    ukeys = jax.vmap(
        lambda i: jax.random.fold_in(
            jax.random.fold_in(key, _ACC_TAG), step0 + i
        )
    )(idx)
    us = jax.vmap(jax.random.uniform)(ukeys)
    p_at = pr[idx, props]
    q_at = q_probs[idx, props]
    # u < p/q as u*q < p: no divide, exact at q == 0 (unreachable
    # for a draft-sampled token, but cheap insurance).
    acc = (us * q_at < p_at) & (idx < usable)
    m = jnp.argmin(
        jnp.concatenate([acc, jnp.zeros((1,), bool)]).astype(jnp.int32)
    )
    natural = m < usable  # a tested proposal actually failed
    q_ext = jnp.concatenate([q_probs, jnp.zeros((1, v), q_probs.dtype)])
    r = jnp.where(natural, jnp.maximum(pr[m] - q_ext[m], 0.0), pr[m])
    rsum = jnp.sum(r)
    # Degenerate residual (p <= q everywhere, float ties): fall back
    # to the target distribution — still a valid sample and
    # unreachable in exact arithmetic.
    r = jnp.where(rsum > 0.0, r / rsum, pr[m] / jnp.sum(pr[m]))
    skey = jax.random.fold_in(
        jax.random.fold_in(key, _RES_TAG), step0 + m
    )
    final = jax.random.categorical(skey, jnp.log(r)).astype(jnp.int32)
    return m, final


def _verify_pack_row(key, pr, q_probs, props, usable, step0):
    """One row's accept/draw plus the packed output layout shared by
    the solo and batched sampled verifies: ``[width + 1]`` = emitted
    tokens (``[:m]`` accepted proposals, ``[m]`` the final draw, rest
    garbage) then ``m``."""
    width = props.shape[0] + 1
    m, fin = _accept_and_draw(key, pr, q_probs, props, usable, step0)
    out = jnp.where(
        jnp.arange(width) < m,
        jnp.concatenate([props, jnp.zeros((1,), jnp.int32)]),
        fin,
    )
    return jnp.concatenate([out, m[None].astype(jnp.int32)])


@functools.lru_cache(maxsize=32)
def sample_verify_fn(model, width: int):
    """Jitted SAMPLED verify: the whole acceptance-rejection round on
    device — extend the target cache with ``[t0, x1..xk]``
    (``width = k + 1``), warp the per-position logits with the same
    temperature/top-k/top-p pipeline the draft used, test each
    proposal with ``u_i < p_i(x_i) / q_i(x_i)`` (uniforms from the
    ACC-tagged stream at the token's own index), and draw the round's
    final token: from the normalized residual ``max(p_m - q_m, 0)``
    at a NATURAL rejection ``m < usable``, or from the full target
    distribution ``p_m`` when every usable proposal was accepted
    (``m = usable`` — covers both the all-accepted bonus and the
    budget-capped round, where position ``usable``'s proposal is
    never tested so no residual applies). ``usable`` is traced: the
    budget-capped last round reuses the same program.

    Returns ``(cache, packed [width + 1])`` where ``packed[:width]``
    holds the emitted tokens (``[:m]`` accepted proposals, ``[m]``
    the final draw, rest garbage) and ``packed[width]`` is ``m`` —
    one host readback per round.
    """
    k = width - 1

    def _run(params, cache, tok0, props, pos0, n_pad, q_probs,
             key_data, temp, topk, topp, step0, usable):
        block = jnp.concatenate([tok0[None], props])[None]  # [1, k+1]
        cache, logits = model.extend_core(
            params, cache, block, pos0, n_pad,
            jnp.int32(0), jnp.int32(0), all_logits=True,
        )
        lg = logits[0]  # [width, V]
        wide = lambda x: jnp.broadcast_to(x, (width,))
        p = _warped_probs(lg, wide(temp[0]), wide(topk[0]), wide(topp[0]))
        key = jax.random.wrap_key_data(key_data[0])
        return cache, _verify_pack_row(
            key, p, q_probs, props, usable, step0
        )

    return jax.jit(_run, donate_argnums=(1,))


def speculative_generate(
    target,
    t_params,
    draft,
    d_params,
    prompt_ids,
    *,
    max_new_tokens: int,
    k: int = 4,
) -> tuple[list[int], SpecStats]:
    """Greedy speculative generation for ONE prompt row.

    ``prompt_ids``: ``[1, P]`` int32 (no padding — callers bucket
    upstream if they care about compile reuse). Returns
    ``(token_ids, stats)``; ``token_ids`` equals plain target greedy
    decoding exactly.
    """
    b, p = prompt_ids.shape
    if b != 1:
        raise ValueError("speculative decoding is single-row (batch=1)")
    if target.vocab_size != draft.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    n = int(max_new_tokens)
    if p + n > target.max_positions or p + n > draft.max_positions:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({n}) exceeds a model window"
        )
    k = max(1, min(int(k), n))
    # Room for a full round's block (t0 + k drafts) past the last
    # needed position keeps every verify the same width.
    total_t = min(target.max_positions, p + n + k + 1)
    total_d = min(draft.max_positions, p + n + k + 1)

    stats = SpecStats()
    prompt_ids = jnp.asarray(prompt_ids)
    t0, t_cache = _prefill(target, t_params, prompt_ids, total_t)
    _, d_cache = _prefill(draft, d_params, prompt_ids, total_d)

    out: list[int] = [t0]
    # Per-model bookkeeping: `upto` = cache slots holding VALID
    # accepted content; `pend` = accepted tokens not yet written to
    # that model's cache (their slots start at `upto`). The target's
    # pend is always one token (the round's bonus); the draft's can be
    # two after a fully-accepted round (its k-th proposal was never
    # fed back to it).
    t_upto, t_pend = p, [t0]
    d_upto, d_pend = p, [t0]

    while len(out) < n:
        budget = n - len(out)
        room = (
            t_upto + 1 + k + 1 <= total_t
            and d_upto + len(d_pend) + k <= total_d
        )
        if budget == 1 or not room:
            # One plain target step. The draft is NOT consulted again
            # once fallback starts (budget exhaustion and the room
            # inequalities are both monotone under growing caches and
            # pending lists), so syncing its cache here would be pure
            # waste — accumulate its pending tokens instead, which
            # keeps the consume loop correct in the impossible-return
            # case and costs nothing.
            nxt, t_cache = _step(target, t_params, t_cache,
                                 t_pend[0], t_upto)
            t_upto += 1
            d_pend.append(nxt)
            t_pend = [nxt]
            out.append(nxt)
            stats.fallback_steps += 1
            continue

        # Draft phase — ONE dispatch: consume the pending accepted
        # tokens and chain all k proposals in a single scanned
        # program (the last consume's greedy output is proposal 1).
        d_cache, props, _ = propose_fn(draft, len(d_pend), k)(
            d_params, d_cache,
            jnp.asarray(np.asarray(d_pend, np.int32)),
            jnp.int32(d_upto), jnp.zeros((1,), jnp.int32), _zero_key(),
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32), jnp.int32(0),
        )
        proposals = np.asarray(props).tolist()
        d_upto += len(d_pend) + k - 1
        # d_upto now covers t0 + proposals[:-1]; proposals[-1] was
        # proposed but never fed back (its slot is unwritten).

        # Verify [t0, d1..dk] in ONE target block: argmax at position
        # i is the target's next token AFTER t0, d1..di.
        block = np.asarray([[t_pend[0], *proposals]], np.int32)
        t_cache, expect = verify_fn(target, k + 1)(
            t_params, t_cache, jnp.asarray(block), jnp.int32(t_upto),
            jnp.zeros((1,), jnp.int32),
        )
        expect = np.asarray(expect)[0]  # [k+1]
        # Only `usable` proposals can be emitted this round (the
        # bonus token takes the last budget slot); drafts beyond it
        # are neither accepted nor rejected — they don't count.
        usable = min(k, budget - 1)
        m = 0
        while m < usable and proposals[m] == int(expect[m]):
            m += 1
        bonus = int(expect[m])
        out.extend(proposals[:m])
        out.append(bonus)
        stats.rounds += 1
        stats.drafted += usable
        stats.accepted += m
        stats.emitted += m + 1
        stats.per_round.append(m + 1)

        t_upto += m + 1  # t0 + m accepted drafts are valid content
        t_pend = [bonus]
        if m == k:
            # Draft never cached its own k-th proposal: it is pending
            # alongside the bonus (consecutive slots from d_upto).
            d_pend = [proposals[-1], bonus]
        else:
            # Rewind over the draft's stale rejected tail; future
            # writes overwrite it and `pos <= upto` masks it until
            # then.
            d_upto = t_upto
            d_pend = [bonus]
    return out[:n], stats


@functools.lru_cache(maxsize=32)
def propose_batched_fn(model, k: int, sampled: bool = False):
    """Jitted BATCHED draft phase with per-row cache positions: every
    row consumes its own pending tokens (``pend_buf [B, 2]``, row
    count ``n_in[b]`` ∈ {1, 2}) and chains ``k`` proposals, writing
    K/V at its OWN slots ``d_pos[b] + i`` (the vmapped
    ``dynamic_update_slice`` path in ``cached_attend``). Rows whose
    pending list is shorter run one trailing extra step; its output
    is never gathered and its stale cache write sits beyond the row's
    valid bound, masked by ``idx <= pos`` until overwritten — the
    same free-rollback property single-row rounds rely on.

    Returns ``(cache, proposals [B, k], q_probs [B, k, V])``, each
    row's proposals gathered from its own scan offsets.
    """

    def _run(params, cache, pend_buf, n_in, d_pos, n_pad, key_data,
             temps, topk, topp, step0):
        def body(carry, i):
            cache, tok = carry
            logits, cache = model.decode_step(
                params, cache, tok[:, None], d_pos + i, n_pad
            )
            if sampled:
                probs = _warped_probs(logits, temps, topk, topp)
                prop_i = jnp.maximum(i - (n_in - 1), 0) + step0  # [B]
                keys = jax.vmap(
                    lambda kd, s: jax.random.fold_in(
                        jax.random.fold_in(
                            jax.random.wrap_key_data(kd), _DRAFT_TAG
                        ),
                        s,
                    )
                )(key_data, prop_i)
                nxt = jax.vmap(
                    lambda kk, pr: jax.random.categorical(
                        kk, jnp.log(pr)
                    )
                )(keys, probs).astype(jnp.int32)
            else:
                probs = jnp.zeros((logits.shape[0], 0), jnp.float32)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            feed = jnp.where(
                i + 1 < n_in, pend_buf[:, jnp.minimum(i + 1, 1)], nxt
            )
            return (cache, feed), (nxt, probs)

        (cache, _), (toks, probs) = jax.lax.scan(
            body, (cache, pend_buf[:, 0]), jnp.arange(k + 1)
        )
        toks = toks.T                      # [B, k+1]
        probs = probs.transpose(1, 0, 2)   # [B, k+1, V]
        j = (n_in - 1)[:, None] + jnp.arange(k)[None, :]  # [B, k]
        props = jnp.take_along_axis(toks, j, axis=1)
        q = jnp.take_along_axis(probs, j[:, :, None], axis=1)
        return cache, props, q

    return jax.jit(_run, donate_argnums=(1,))


@functools.lru_cache(maxsize=32)
def sample_verify_batched_fn(model, width: int):
    """Batched SAMPLED verify: one target block forward over every
    row at its OWN cache position (``pos0 [B]``), then the shared
    acceptance-rejection core (:func:`_accept_and_draw`) vmapped per
    row with per-row keys, warps, budgets, and stream offsets.
    Returns ``(cache, packed [B, width + 1])`` — per row the emitted
    tokens then ``m`` (same layout as :func:`sample_verify_fn`)."""
    k = width - 1

    def _run(params, cache, tok0, props, pos0, n_pad, q_probs,
             key_data, temps, topk, topp, step0, usable):
        block = jnp.concatenate([tok0[:, None], props], axis=1)
        cache, logits = model.extend_core(
            params, cache, block, pos0, n_pad,
            jnp.int32(0), jnp.int32(0), all_logits=True,
        )

        # Warp OUTSIDE the per-row vmap: under vmap the
        # no-filter lax.cond would become a select and the two
        # per-row sorts in the top-k/top-p filter would run even
        # when disabled (the batch-wide `need` branch must survive).
        bsz, w, v = logits.shape
        pr_all = _warped_probs(
            logits.reshape(bsz * w, v),
            jnp.repeat(temps, w), jnp.repeat(topk, w),
            jnp.repeat(topp, w),
        ).reshape(bsz, w, v)
        packed = jax.vmap(
            lambda pr, kd, q, pr_, u, s0: _verify_pack_row(
                jax.random.wrap_key_data(kd), pr, q, pr_, u, s0
            )
        )(pr_all, key_data, q_probs, props, usable, step0)
        return cache, packed

    return jax.jit(_run, donate_argnums=(1,))


def speculative_sample_batched(
    target,
    t_params,
    draft,
    d_params,
    prompt_ids,
    *,
    max_new_tokens: int,
    k: int = 4,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    seeds=None,
) -> tuple[list[list[int]], SpecStats]:
    """SAMPLED speculative generation for a WHOLE BATCH of rows, each
    with its own PRNG stream (``seeds``: one per row, default
    ``0..B-1``) and its own acceptance-driven cache position. Every
    row's emitted stream is byte-identical to a batch of that row
    alone with the same seed (a row never reads another row's keys or
    cache; a budget-capped round is ``usable = 0``) and exactly
    target-distributed for any draft. Same window-headroom
    requirement as the greedy batched variant. ``temperature <= 0``
    delegates to :func:`speculative_generate_batched`."""
    if temperature <= 0.0:
        return speculative_generate_batched(
            target, t_params, draft, d_params, prompt_ids,
            max_new_tokens=max_new_tokens, k=k,
        )
    b, p = prompt_ids.shape
    if target.vocab_size != draft.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    n = int(max_new_tokens)
    k = max(1, min(int(k), n))
    total = p + n + k + 1
    if total > target.max_positions or total > draft.max_positions:
        raise ValueError(
            f"batched speculation needs prompt + max_new_tokens + k + 1 "
            f"(= {total}) cache slots within both model windows; use "
            "speculative_sample per row near the window edge"
        )
    if seeds is None:
        seeds = list(range(b))
    if len(seeds) != b:
        raise ValueError(f"need {b} seeds, got {len(seeds)}")

    stats = SpecStats()
    prompt_ids = jnp.asarray(prompt_ids)
    zb = jnp.zeros((b,), jnp.int32)
    keys = jnp.asarray(
        np.stack([
            np.asarray(jax.random.key_data(jax.random.key(int(s))))
            for s in seeds
        ])
    )
    temps = jnp.full((b,), temperature, jnp.float32)
    topk_v = jnp.full((b,), top_k, jnp.int32)
    topp_v = jnp.full((b,), top_p, jnp.float32)

    from mlapi_tpu.models.gpt import prefill_fn

    first, t_cache = prefill_fn(target, total)(
        t_params, prompt_ids, keys, temps, zb, topk_v, topp_v,
    )
    _, d_cache = prefill_fn(draft, total)(
        d_params, prompt_ids, keys, jnp.zeros((b,), jnp.float32), zb,
        zb, jnp.ones((b,), jnp.float32),
    )
    first = np.asarray(first)

    out = [[int(first[i])] for i in range(b)]
    t_upto = np.full((b,), p, np.int64)
    d_upto = np.full((b,), p, np.int64)
    d_pend = [[int(first[i])] for i in range(b)]

    while any(len(o) < n for o in out):
        pend_buf = np.zeros((b, 2), np.int32)
        n_in = np.ones((b,), np.int32)
        step0 = np.zeros((b,), np.int32)
        usable = np.zeros((b,), np.int32)
        for i in range(b):
            n_in[i] = len(d_pend[i])
            pend_buf[i, : n_in[i]] = d_pend[i]
            step0[i] = len(out[i])
            usable[i] = max(0, min(k, n - len(out[i]) - 1))
        d_cache, props, q_probs = propose_batched_fn(draft, k, True)(
            d_params, d_cache, jnp.asarray(pend_buf),
            jnp.asarray(n_in), jnp.asarray(d_upto.astype(np.int32)),
            zb, keys, temps, topk_v, topp_v, jnp.asarray(step0),
        )
        d_upto += n_in + k - 1

        tok0 = np.asarray([o[-1] for o in out], np.int32)
        t_cache, packed = sample_verify_batched_fn(target, k + 1)(
            t_params, t_cache, jnp.asarray(tok0), props,
            jnp.asarray(t_upto.astype(np.int32)), zb, q_probs, keys,
            temps, topk_v, topp_v, jnp.asarray(step0),
            jnp.asarray(usable),
        )
        packed = np.asarray(packed)
        stats.rounds += 1
        for i in range(b):
            budget = n - len(out[i])
            if budget <= 0:
                d_upto[i] = t_upto[i]
                continue
            m = int(packed[i, k + 1])
            emitted = [int(t) for t in packed[i, : m + 1]]
            out[i].extend(emitted)
            stats.drafted += int(usable[i])
            stats.accepted += m
            stats.emitted += m + 1
            t_upto[i] += m + 1
            if m == k:
                d_pend[i] = [int(packed[i, k - 1]), emitted[-1]]
            else:
                d_upto[i] = t_upto[i]
                d_pend[i] = [emitted[-1]]
    return [o[:n] for o in out], stats


def speculative_generate_batched(
    target,
    t_params,
    draft,
    d_params,
    prompt_ids,
    *,
    max_new_tokens: int,
    k: int = 4,
) -> tuple[list[list[int]], SpecStats]:
    """Greedy speculative generation for a WHOLE BATCH of prompt rows
    — every row's stream byte-identical to its solo plain greedy
    stream.

    The thing that makes this possible is per-row cache positions:
    each round, row ``b`` accepts ``m_b`` proposals and advances by
    ``m_b + 1``, so rows desynchronize immediately. Draft writes land
    at per-row slots via :func:`propose_batched_fn`; the verify block
    (:func:`verify_fn` — the same program, retraced with a ``[B]``
    position vector) extends each row's cache at its own offset. A
    row that exhausts its budget freezes: it keeps riding the batch
    as a dummy (its writes land beyond its valid bound and are
    masked) until every row finishes. Rounds never need plain-step
    fallback — a budget-1 row emits exactly its bonus token
    (``usable = 0``) — but the cache must hold a full final round:
    ``prompt + max_new_tokens + k + 1 <= max_positions`` for both
    models, or ``ValueError`` (tight windows: loop the single-row
    :func:`speculative_generate`, which degrades to plain steps).
    """
    b, p = prompt_ids.shape
    if target.vocab_size != draft.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    n = int(max_new_tokens)
    k = max(1, min(int(k), n))
    total = p + n + k + 1
    if total > target.max_positions or total > draft.max_positions:
        raise ValueError(
            f"batched speculation needs prompt + max_new_tokens + k + 1 "
            f"(= {total}) cache slots within both model windows; use "
            "speculative_generate per row near the window edge"
        )

    stats = SpecStats()
    prompt_ids = jnp.asarray(prompt_ids)
    zb = jnp.zeros((b,), jnp.int32)
    zbf = jnp.zeros((b,), jnp.float32)
    ob = jnp.ones((b,), jnp.float32)
    keys = jnp.asarray(
        np.tile(
            np.asarray(jax.random.key_data(jax.random.key(0)))[None], (b, 1)
        )
    )

    from mlapi_tpu.models.gpt import prefill_fn

    first, t_cache = prefill_fn(target, total)(
        t_params, prompt_ids, keys, zbf, zb, zb, ob,
    )
    _, d_cache = prefill_fn(draft, total)(
        d_params, prompt_ids, keys, zbf, zb, zb, ob,
    )
    first = np.asarray(first)

    out = [[int(first[i])] for i in range(b)]
    t_upto = np.full((b,), p, np.int64)
    d_upto = np.full((b,), p, np.int64)
    d_pend = [[int(first[i])] for i in range(b)]

    while any(len(o) < n for o in out):
        pend_buf = np.zeros((b, 2), np.int32)
        n_in = np.ones((b,), np.int32)
        for i in range(b):
            n_in[i] = len(d_pend[i])
            pend_buf[i, : n_in[i]] = d_pend[i]
        d_cache, props, _ = propose_batched_fn(draft, k)(
            d_params, d_cache, jnp.asarray(pend_buf),
            jnp.asarray(n_in), jnp.asarray(d_upto.astype(np.int32)),
            zb, keys, zbf, zb, ob, zb,
        )
        props = np.asarray(props)
        d_upto += n_in + k - 1

        tok0 = np.asarray([o[-1] for o in out], np.int32)
        block = np.concatenate([tok0[:, None], props], axis=1)
        t_cache, expect = verify_fn(target, k + 1)(
            t_params, t_cache, jnp.asarray(block),
            jnp.asarray(t_upto.astype(np.int32)), zb,
        )
        expect = np.asarray(expect)
        stats.rounds += 1
        for i in range(b):
            budget = n - len(out[i])
            if budget <= 0:
                # Finished row riding as a dummy: freeze its state
                # (the round's writes sit beyond its valid bound).
                d_upto[i] = t_upto[i]
                continue
            usable = min(k, budget - 1)
            m = 0
            while m < usable and props[i, m] == int(expect[i, m]):
                m += 1
            bonus = int(expect[i, m])
            out[i].extend([int(t) for t in props[i, :m]] + [bonus])
            stats.drafted += usable
            stats.accepted += m
            stats.emitted += m + 1
            t_upto[i] += m + 1
            if m == k:
                d_pend[i] = [int(props[i, -1]), bonus]
            else:
                d_upto[i] = t_upto[i]
                d_pend[i] = [bonus]
    return [o[:n] for o in out], stats


def speculative_sample(
    target,
    t_params,
    draft,
    d_params,
    prompt_ids,
    *,
    max_new_tokens: int,
    k: int = 4,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    seed: int = 0,
) -> tuple[list[int], SpecStats]:
    """SAMPLED speculative generation for ONE prompt row (the
    Leviathan/Chen acceptance-rejection scheme — module docstring).

    The emitted stream is distributed exactly as plain target
    sampling under the same ``temperature``/``top_k``/``top_p`` warp
    (``tests/test_speculative_sampling.py`` pins this two ways: a
    synthetic-p/q kernel-level distribution check and an end-to-end
    total-variation bound), deterministic given ``seed``, and
    independent of draft quality — the draft only moves the SPEED
    (acceptance rate), never the distribution. ``temperature <= 0``
    delegates to the byte-exact greedy :func:`speculative_generate`.
    """
    if temperature <= 0.0:
        return speculative_generate(
            target, t_params, draft, d_params, prompt_ids,
            max_new_tokens=max_new_tokens, k=k,
        )
    b, p = prompt_ids.shape
    if b != 1:
        raise ValueError("speculative decoding is single-row (batch=1)")
    if target.vocab_size != draft.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    n = int(max_new_tokens)
    if p + n > target.max_positions or p + n > draft.max_positions:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({n}) exceeds a model window"
        )
    k = max(1, min(int(k), n))
    total_t = min(target.max_positions, p + n + k + 1)
    total_d = min(draft.max_positions, p + n + k + 1)

    from mlapi_tpu.models.gpt import decode_chunk_fn, prefill_fn

    key_data = jnp.asarray(
        np.asarray(jax.random.key_data(jax.random.key(seed)))[None]
    )
    temps = jnp.asarray(np.asarray([temperature], np.float32))
    topk_v = jnp.asarray(np.asarray([top_k], np.int32))
    topp_v = jnp.asarray(np.asarray([top_p], np.float32))
    z_pad = jnp.zeros((1,), jnp.int32)

    stats = SpecStats()
    prompt_ids = jnp.asarray(prompt_ids)
    # Target prefill SAMPLES the first token at stream index 0 —
    # identical to the plain sampled path's first draw.
    first, t_cache = prefill_fn(target, total_t)(
        t_params, prompt_ids, key_data, temps, z_pad, topk_v, topp_v,
    )
    t0 = int(np.asarray(first)[0])
    _, d_cache = _prefill(draft, d_params, prompt_ids, total_d)

    out: list[int] = [t0]
    t_upto, t_pend = p, [t0]
    d_upto, d_pend = p, [t0]

    while len(out) < n:
        budget = n - len(out)
        room = (
            t_upto + 1 + k + 1 <= total_t
            and d_upto + len(d_pend) + k <= total_d
        )
        if budget == 1 or not room:
            # One plain SAMPLED target step at the token's own
            # (untagged) stream index — the same per-token stream
            # discipline as the engine's chunk decoder.
            toks, t_cache, _ = decode_chunk_fn(target, 1)(
                t_params, t_cache,
                jnp.asarray(np.asarray([t_pend[0]], np.int32)),
                jnp.int32(t_upto), z_pad, temps, key_data,
                jnp.int32(len(out)), topk_v, topp_v,
                jnp.int32(0), jnp.int32(0),
            )
            nxt = int(np.asarray(toks)[0, 0])
            t_upto += 1
            d_pend.append(nxt)
            t_pend = [nxt]
            out.append(nxt)
            stats.fallback_steps += 1
            continue

        step0 = len(out)  # stream index of this round's first proposal
        d_cache, props, q_probs = propose_fn(
            draft, len(d_pend), k, True
        )(
            d_params, d_cache,
            jnp.asarray(np.asarray(d_pend, np.int32)),
            jnp.int32(d_upto), z_pad, key_data, temps, topk_v, topp_v,
            jnp.int32(step0),
        )
        d_upto += len(d_pend) + k - 1

        usable = min(k, budget - 1)
        t_cache, packed = sample_verify_fn(target, k + 1)(
            t_params, t_cache, jnp.int32(t_pend[0]), props,
            jnp.int32(t_upto), z_pad, q_probs, key_data, temps,
            topk_v, topp_v, jnp.int32(step0), jnp.int32(usable),
        )
        packed = np.asarray(packed)
        m = int(packed[k + 1])
        emitted = packed[: m + 1].tolist()
        out.extend(emitted)
        stats.rounds += 1
        stats.drafted += usable
        stats.accepted += m
        stats.emitted += m + 1
        stats.per_round.append(m + 1)

        t_upto += m + 1
        t_pend = [emitted[-1]]
        if m == k:
            # The draft never cached its own k-th proposal; it is
            # pending alongside the round's final token.
            d_pend = [int(packed[k - 1]), emitted[-1]]
        else:
            d_upto = t_upto
            d_pend = [emitted[-1]]
    return out[:n], stats
