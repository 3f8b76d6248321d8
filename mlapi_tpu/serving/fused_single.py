"""Fused-chunk width policy for generative serving.

One :class:`FusedSinglePath` per :class:`TextGenerationEngine`. A
fused-eligible batch (no streaming consumer) decodes through the SAME
``decode_chunk_fn`` the chunked path uses, at TIER-WIDE chunk sizes:
each fused chunk is one ``"decode"`` unit yielded at
``BatchRun.units()`` boundaries, so fewer dispatches carry a
generation while deadlines, speculation, brownout, faults, roles and
drain apply to it through that one seam. A concurrent lane's
head-of-line stall is bounded by one fused-chunk dispatch
(``engine.sched_lane_stall_max`` pins it from counters). Streams are
byte-identical to plain-chunk decoding
(``tests/test_serving_fused.py``).

The policy:

- :meth:`tiers` — the fused width ladder.
- :meth:`chunk_width` — formation-time decision: the batch's top
  fused width, 0 to pin the plain ``eng.chunk``.
- :meth:`width_at` — per-boundary width: shrinks to the smallest
  power-of-two-of-chunk covering the live rows' remaining budgets
  (bounded program count), drops to the plain chunk while a
  streaming row is live (incremental delivery) and, in strict mode,
  for any (batch, cache, width) shape the warm grid did not compile.
- :meth:`warm` — drives real solo runs at ladder budgets so the
  fused-width decode-chunk programs compile off the request path;
  the warmed set itself is populated at the dispatch site
  (``BatchRun._decode_chunk``), so it can never disagree with what
  actually compiled.
"""

from __future__ import annotations


class FusedSinglePath:
    def __init__(self, engine):
        self.eng = engine
        # (b_cur, total, width) fused-width decode-chunk programs
        # proven compiled (recorded at the dispatch site) — strict
        # mode takes a fused width only for these; an unwarmed shape
        # falls back to the plain chunk rather than stalling a
        # concurrent lane on a remote compile.
        self.warmed: set = set()

    def tiers(self) -> list:
        """The fused width ladder, ascending: powers of two (of
        ``chunk``) from the DEFAULT budget's tier up to the
        ``fused_max_new`` cap's. The floor is the default tier
        because smaller budgets shrink per boundary via
        :meth:`width_at` — extra rungs below the default would only
        multiply compiles. ONE definition shared by the request path
        (:meth:`chunk_width`) and the warm grid (:meth:`warm`)."""
        eng = self.eng
        t = eng.default_tier
        tiers = [t]
        while t < eng.fused_max_new:
            t *= 2
            tiers.append(t)
        return tiers

    def chunk_width(self, run) -> int:
        """Formation-time fused width for ``run``: the smallest
        ladder tier covering the batch's token budget (the largest
        rung when the budget exceeds ``fused_max_new`` — the cap now
        bounds the DISPATCH width, not eligibility, so oversized
        budgets ride fused chunks instead of declining). 0 pins the
        plain ``eng.chunk``: the path is off, the batch hosts a
        streaming consumer at formation (incremental delivery — a
        joiner arriving later drops the width per boundary instead),
        or the ladder would not beat the plain chunk anyway."""
        eng = self.eng
        if not eng.fused_single:
            return 0
        if any(r.stream for r in run.reqs):
            return 0
        w = eng.chunk
        for t in self.tiers():
            w = t
            if t >= run.n_new_max:
                break
        return w if w > eng.chunk else 0

    def width_at(self, run, live: list) -> int:
        """Per-boundary dispatch width for a fused batch: the
        smallest power of two of ``chunk`` covering the live rows'
        remaining budgets, capped at the formation width — the tail
        of a generation never dispatches (and never page-allocates)
        wider than it can use, and the program count stays
        logarithmic. Falls back to the plain chunk (returns 0) while
        a streaming row is live, and in strict (high-RTT) mode for any
        (batch width, cache length, width) shape not proven compiled
        — those widths compile on demand only where a compile is
        cheap."""
        eng = self.eng
        reqs = run.reqs
        if any(reqs[i].stream for i in live):
            return 0
        need = max(reqs[i].n_new - run.sched[i] for i in live)
        w = eng.chunk
        while w < need:
            w *= 2
        w = min(w, run.fused_w)
        if w <= eng.chunk:
            return 0
        if (
            eng._strict_admit
            and (run.b_cur, run.total, w) not in self.warmed
        ):
            return 0
        return w

    def warm(self, full: bool) -> int:
        """Compile the fused-width decode-chunk ladder off the
        request path by running REAL solo batches (``_run_batch``
        with ``fused_ok=True``) at each ladder budget — the exact
        programs fused traffic dispatches, recorded into ``warmed``
        at the dispatch site. Minimal warmup covers the first bucket;
        full covers every bucket at the default tier's ladder plus
        the larger tiers on the first bucket (wider multi-row shapes
        fall back to the plain chunk in strict mode — already warm).
        Returns the shape count for the warmup log."""
        import numpy as np

        from mlapi_tpu.serving.requests import GenRequest, _SyncSink

        eng = self.eng
        buckets = eng.prompt_buckets if full else eng.prompt_buckets[:1]
        # Ladder budgets: every power-of-two width width_at can pick
        # below the default tier, plus each full tier rung.
        widths = []
        w = 2 * eng.chunk
        while w <= eng.default_tier:
            widths.append(w)
            w *= 2
        shapes = 0
        for bi, bucket in enumerate(buckets):
            grid = list(widths)
            if full and bi == 0:
                grid += [t for t in self.tiers() if t > eng.default_tier]
            for n_new in grid:
                if bucket + n_new > eng.model.max_positions:
                    continue
                row = np.full((bucket,), eng.tokenizer.pad_id, np.int32)
                req = GenRequest(row, 1, n_new, 0.0, 0, None)
                sink = _SyncSink(req, [])
                eng._run_batch([sink])
                if sink.error is not None:
                    raise sink.error
                shapes += 1
        return shapes
