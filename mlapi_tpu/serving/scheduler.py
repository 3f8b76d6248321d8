"""Continuous-batching scheduler v2: one typed-unit queue across
concurrent batches (r15; ROADMAP item 1).

Before this module the engine ran exactly ONE live :class:`BatchRun`
at a time: the collector formed a batch, handed it to an executor
thread, and every request that missed the window waited in ``_carry``
for the whole run to finish — dispatch boundaries idled while queued
work existed. r10 already made prefill chunks *schedulable units*
inside one batch and noted "the same schedulable-unit machinery
applies across batches"; this module is that generalization, the
vLLM-style continuous-batching shape.

Design:

- **Lanes.** Each formed request group becomes a *lane*: a
  :class:`~mlapi_tpu.serving.batch_run.BatchRun` plus its ``units()``
  generator. The generator yields one of the five typed units —
  ``prefill`` chunk, ``decode`` chunk, ``spec`` round/phase, ``admit``
  (joiner install), ``compact`` (batch resize) — after each unit of
  device work. Since r20 this is the ONE execution model (default-on;
  the ``--no-scheduler`` escape hatch was retired in r22): serial
  mode (``sched_max_batches=1``) is the same machinery pinned to one
  lane, so the two modes execute identical code and greedy streams are
  token-identical by construction (pinned across the config matrix
  in ``tests/test_scheduler.py``). A sixth unit kind, ``score``
  (r22), carries a co-resident scoring model's formed batch through
  the same queue — see ``serving/scoring.py``. Fused-eligible batches dispatch
  tier-wide decode chunks through the same generator (one schedulable
  unit per fused chunk — ``serving/fused_single.py``), so a concurrent
  lane's head-of-line stall behind fused traffic is bounded at one
  fused-chunk dispatch (``engine.sched_lane_stall_max``).
- **One dispatch thread.** All lanes advance on THIS thread, one unit
  at a time — the device stream stays serial (the same property the
  single decode-executor gave), only the *order* across batches is now
  a policy decision. No dispatch boundary idles while any lane or
  pending group has work.
- **SLO-aware policy.** Every candidate (a runnable lane, or starting
  a pending group — its formation prefill) gets an URGENCY in seconds:
  the minimum deadline slack of its live requests when any carries a
  deadline (the r12 machinery), else a relaxed constant that tightens
  from the r10 LatencyStats reservoirs — a deadline-less pending group
  that has waited past ~2x the observed TTFT p95 competes like a
  near-due deadline (TTFT target), and a deadline-less running lane
  competes at the inter-token p50 scale once it has work outstanding
  (ITL target). Minimum urgency wins; exact ties fall back to
  least-recently-dispatched, which makes equal-priority lanes
  alternate strictly — the interleaving the tests pin from counters.
  Choosing a deadlined candidate OVER the fairness choice counts as a
  deadline preemption (``sched_deadline_preempts``). Across candidate
  TYPES, a live lane whose slack is inside ~one formation's worth of
  work blocks new group starts (formation is a whole batch prefill —
  the one unit big enough to blow a near-due deadline); otherwise
  pending groups start eagerly (their formation IS their TTFT).
- **Page-budget arbitration.** Concurrent paged lanes share one
  :class:`~mlapi_tpu.serving.paged_pool.PagePool`. Two rules keep them
  from starving each other: (1) every lane RESERVES its worst-case
  footprint from the BATCH geometry (rows re-pack to the group's max
  bucket and live rows map the same decode spans:
  ``ceil((prefix + group_bucket + group_n_new + chunk)/page)`` per
  row, fixed at start), and a pending group only STARTS while other
  lanes are live if its
  own worst case plus the live reservations fit the pool — lanes
  allocate per chunk, so free pages at start wildly undercount what a
  live lane will still take. Otherwise it waits, counted in
  ``sched_pages_deferred``, and starts when a lane releases; with no
  lanes live it starts unconditionally (the single-batch semantics,
  loud ``PagePoolExhausted`` if truly too big). (2) The pool's device
  arrays are DONATED through every paged
  dispatch, so after each unit the scheduler writes the advancing
  lane's arrays back (``pool.layers``) and bumps ``pool.epoch``; a
  lane whose epoch is stale re-binds its cache pytree from the pool +
  its own table before its next unit. All on the one dispatch thread —
  no locking, just the rebind.
- **Deadlines and faults.** The r12 ``_expire_if_due`` sweeps run
  inside ``units()`` at every boundary exactly as before (the
  ``deadline_expired_*`` counters keep ticking), and every existing
  ``serving/faults.py`` point fires from the same seams. One NEW
  point, ``sched_unit``, fires before each unit dispatch (including a
  lane's formation): a raise kills THAT lane only — its generator is
  closed (pages released by the generator's ``finally``), its waiters
  get the error as their terminal frame, and the other lanes stream
  on.

The collector (``engine._collect_loop``) forms groups exactly as
before and routes each through ``engine._dispatch_group``: a group a
live lane's window fits is STAGED for that lane's in-lane admission
(the continuous-batching growth path — ``sched_units_admit`` ticks as
the lane installs joiners at unit boundaries); otherwise it hands off
here as a new lane and collection continues, so bucket-incompatible
traffic runs as concurrent interleaved lanes instead of serial
``_carry`` turns. Pending groups are started in urgency order — the
r12 ``_carry[0]``-FIFO head-of-line pick is gone. Lane retirement
wakes the collector (``engine._wake_collector``) so staged and
deferred work re-enters dispatch immediately.
"""

from __future__ import annotations

import collections
import threading
import time

from mlapi_tpu.serving import faults
from mlapi_tpu.utils.logging import get_logger
from mlapi_tpu.utils.metrics import span

_log = get_logger("serving.scheduler")

UNIT_KINDS = ("prefill", "decode", "spec", "admit", "compact", "score")

# Urgency (seconds) of work nobody is waiting on with a deadline and
# the reservoirs don't yet flag as SLO-risky: large enough that ANY
# real deadline outranks it, finite so the ordering stays total.
_RELAXED_S = 3600.0


class _Group:
    """A formed request group waiting for a lane slot."""

    __slots__ = ("reqs", "t_submit", "deferred_counted")

    def __init__(self, reqs: list):
        self.reqs = reqs
        self.t_submit = time.perf_counter()
        # One ``sched_pages_deferred`` tick per deferral EPISODE (a
        # group blocked on the page budget), not per re-evaluation —
        # the gate is re-checked every dispatch-loop iteration.
        self.deferred_counted = False


class _Lane:
    """One live BatchRun and its unit generator."""

    __slots__ = (
        "lane_id", "run", "gen", "last_pick", "pool_epoch", "reserved",
        "tenant_pages", "tenant_adapters",
    )

    def __init__(self, lane_id: int, run, gen, pick_seq: int,
                 reserved: int = 0, tenant_pages: dict | None = None,
                 tenant_adapters: dict | None = None):
        self.lane_id = lane_id
        self.run = run
        self.gen = gen
        self.last_pick = pick_seq
        self.pool_epoch = -1  # forces a first-unit rebind check
        # Worst-case page footprint (ceil((bucket + n_new)/page) per
        # row), fixed at lane start — the arbitration unit.
        self.reserved = reserved
        # The same reservation SPLIT BY TENANT (tenant → pages,
        # tenant → adapter-id set), fixed at lane start: the per-
        # tenant quota gate sums these instead of re-deriving from
        # live rows, so a tenant's held footprint can only shrink
        # (rows finish) — never grow past what the gate admitted.
        self.tenant_pages = tenant_pages or {}
        self.tenant_adapters = tenant_adapters or {}

    @property
    def reqs(self) -> list:
        return self.run.reqs


class _ScoreUnit:
    """One formed scoring batch (serving/scoring.py), queued as a
    first-class typed unit: ``fn`` runs the device call on the
    dispatch thread and resolves the batch's futures thread-safely;
    ``fail`` delivers the stop-path error without running the call.
    Microsecond-scale by construction — the padded-shape jit program
    is cached — so interleaving one between decode chunks costs a
    decode lane at most one unit of head-of-line wait (the same bound
    fused chunks carry, pinned by ``sched_lane_stall_max``)."""

    __slots__ = ("fn", "fail", "n_rows", "deadline", "stats", "weight",
                 "t_submit", "target")

    def __init__(self, fn, fail, n_rows: int, deadline: float | None,
                 stats, weight: float):
        self.fn = fn
        self.fail = fail
        self.n_rows = n_rows
        self.deadline = deadline   # perf_counter domain, or None
        self.stats = stats         # the SCORING model's LatencyStats
        self.weight = max(float(weight), 1e-6)
        self.t_submit = time.perf_counter()
        # Deadline-less aging target: THIS model's observed first-
        # result p95 (floor 5 ms cold) — computed once per unit (one
        # bounded-reservoir sort, trivial next to the device call it
        # schedules), frozen so the dispatch thread never sorts.
        self.target = 0.005
        if stats is not None:
            t95 = stats.summary()["ttft_p95_ms"]
            if t95:
                self.target = max(t95 / 1e3, 0.005)


def _min_slack(reqs, now: float) -> float | None:
    """Smallest deadline slack (s) among live deadlined requests, or
    ``None`` when nobody carries a deadline."""
    best = None
    for r in reqs:
        d = getattr(r, "deadline", None)
        if d is None or getattr(r, "cancelled", False):
            continue
        s = d - now
        if best is None or s < best:
            best = s
    return best


class UnitScheduler:
    """The engine-level typed-unit queue over concurrent BatchRuns.

    Owned by :class:`~mlapi_tpu.serving.engine.TextGenerationEngine`
    — ALWAYS (r20): ``engine.start()`` creates one unconditionally
    (``sched_max_batches=1`` pins the serial shape; the
    ``--no-scheduler`` flag was retired in r22), ``engine.stop()``
    tears it down. In a multi-model process the registry's scoring
    paths feed this queue too (``submit_score``).
    """

    def __init__(self, eng, max_batches: int = 2):
        self.eng = eng
        self.max_batches = max(1, int(max_batches))
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._pending: list[_Group] = []
        self._lanes: list[_Lane] = []
        # The group CLAIMED off _pending but not yet a lane (its
        # formation prefill is running on the dispatch thread): in
        # neither list, yet very much in-flight — idle/backlog/
        # queue_depth and drain's sweep must see it, or drain can
        # declare the engine idle with a batch mid-formation.
        self._forming_group: _Group | None = None
        # Typed score units from co-resident ScorePaths (r22): FIFO —
        # scoring batches are homogeneous microsecond work, so arrival
        # order IS deadline order within the queue; the policy decides
        # score-vs-lane, not score-vs-score.
        self._score: collections.deque = collections.deque()
        # Strict alternation state for the deadline-less case: when
        # neither the score head nor any lane carries real slack, the
        # dispatcher alternates score/lane so neither direction can
        # starve the other by construction.
        self._last_was_score = False
        self._stopped = False
        self._pick_seq = 0
        self._lane_seq = 0
        # Cross-lane head-of-line accounting: the lane the last unit
        # dispatched for and its consecutive-dispatch streak while
        # other lanes were live — feeds engine.sched_lane_stall_max.
        self._last_lane = -1
        self._streak = 0
        # LatencyStats.summary() sorts both reservoirs; the policy
        # only needs it at reservoir-drift granularity — cache it for
        # a window of picks instead of sorting per dispatched unit.
        self._summary_cache = None
        self._summary_seq = -1000
        # Bounded unit trace (lane_id, kind, start_ns, end_ns) — the
        # in-memory log of the ``sched.unit`` spans: the interleaving
        # evidence the tests (and post-mortems) read from its first
        # two fields, and each unit's ``perf_counter_ns`` interval.
        self.trace: collections.deque = collections.deque(maxlen=2048)
        # Where the dispatch thread's spans are summed (``/metrics``
        # exports them as ``generate.sched_unit_<kind>_us`` etc.).
        self._sums = eng.latency.sums
        self._thread = threading.Thread(
            target=self._loop, name="unitsched", daemon=True
        )
        self._thread.start()

    # -- intake / shutdown (event-loop side) ---------------------------

    def submit(self, reqs: list) -> None:
        """Hand a formed group to the unit queue (collector thread)."""
        with self._work:
            if self._stopped:
                raise RuntimeError("scheduler stopped")
            self._pending.append(_Group(reqs))
            self._work.notify_all()

    def submit_score(self, fn, fail, *, n_rows: int = 0,
                     deadline: float | None = None, stats=None,
                     weight: float = 1.0) -> None:
        """Hand one formed scoring batch to the unit queue (event-loop
        side, via ScorePath). ``fn`` runs the device call on the
        dispatch thread; ``fail`` is the stop-path terminal. Raises
        once stopped so the caller falls back to its pool backend."""
        with self._work:
            if self._stopped:
                raise RuntimeError("scheduler stopped")
            self._score.append(
                _ScoreUnit(fn, fail, n_rows, deadline, stats, weight)
            )
            self._work.notify_all()

    def stop(self, timeout_s: float = 10.0) -> None:
        """Stop the dispatch thread; anything still pending or live
        gets the engine-stopped error as its terminal frame (parity
        with the collector's ``finally``)."""
        with self._work:
            self._stopped = True
            self._work.notify_all()
        self._thread.join(timeout=timeout_s)

    # -- observability -------------------------------------------------

    @property
    def backlog(self) -> int:
        """Requests formed but not yet running — the piece of the
        submit queue that moved here (pending groups + the one mid-
        formation); counted into ``engine.queue_depth`` so
        backpressure, admission estimates and the router's scrape
        keep seeing it."""
        with self._lock:
            n = sum(len(g.reqs) for g in self._pending)
            if self._forming_group is not None:
                n += len(self._forming_group.reqs)
            return n

    @property
    def queue_depth(self) -> int:
        """Typed-unit queue depth: one runnable unit per live lane
        plus one formation unit per pending/forming group plus every
        queued score unit."""
        with self._lock:
            return (
                len(self._pending) + len(self._lanes)
                + (1 if self._forming_group is not None else 0)
                + len(self._score)
            )

    @property
    def batches_live(self) -> int:
        with self._lock:
            return len(self._lanes)

    def lane_groups(self) -> list:
        """Snapshot of each live lane's request group (copies — lanes
        mutate their lists on the dispatch thread as joiners install
        and rows finish). The collector's in-lane-admission check
        reads this; staleness is safe: a lane that retires between
        the snapshot and the staging leaves the candidates in
        ``_admit``, where the collector's no-batch-live sweep
        reclaims them."""
        with self._lock:
            return [list(ln.run.reqs) for ln in self._lanes]

    @property
    def idle(self) -> bool:
        with self._lock:
            return (
                not self._pending
                and not self._lanes
                and self._forming_group is None
                and not self._score
            )

    def sweep_requests(self) -> list:
        """Drain's budget-exhausted sweep: pop every pending group's
        requests (they will never be laned) and list — cancel-only,
        the generators own them — every live lane's plus the group
        mid-formation (its lane notices the cancels at its first
        boundary). The caller pushes terminal frames and cancels;
        cancelled lane rows finish at their next boundary exactly
        like disconnects."""
        with self._lock:
            out: list = []
            for g in self._pending:
                out += g.reqs
            self._pending.clear()
            for lane in self._lanes:
                out += list(lane.run.reqs)
            if self._forming_group is not None:
                out += list(self._forming_group.reqs)
            return out

    # -- the dispatch loop ---------------------------------------------

    def _loop(self) -> None:
        eng = self.eng
        while True:
            with self._work:
                while (
                    not self._stopped
                    and not self._lanes
                    and not self._pending
                    and not self._score
                ):
                    with span("sched.idle", "sched_idle",
                              registry=self._sums):
                        self._work.wait(timeout=0.1)
                if self._stopped:
                    break
            try:
                started = self._maybe_start()
                su = self._claim_score()
                if su is not None:
                    self._dispatch_score(su)
                else:
                    lane = self._pick()
                    if lane is not None:
                        self._advance(lane)
                        self._last_was_score = False
                    elif not started:
                        # Pending work blocked on the page budget with
                        # every lane idle-free: wait for a release tick.
                        with span("sched.idle", "sched_idle",
                                  registry=self._sums):
                            time.sleep(0.002)
            except BaseException:  # noqa: BLE001 — scheduler must survive
                _log.exception("unit scheduler internal error")
                time.sleep(0.01)
        # Stopped: deliver the collector's error contract to whatever
        # is still here (normal shutdown drains first, so this is the
        # crash/stop() path).
        err = RuntimeError("generation engine stopped")
        with self._lock:
            pending, self._pending = self._pending, []
            lanes, self._lanes = self._lanes, []
            score = list(self._score)
            self._score.clear()
        for su in score:
            try:
                su.fail(err)  # the batch's futures get the stop error
            except BaseException:
                _log.exception("score-unit fail delivery failed")
        for lane in lanes:
            try:
                # close() throws GeneratorExit into a STARTED
                # generator, whose finally write-backs its cache —
                # re-bind first so a stale lane never writes
                # donation-consumed buffers over the live pool (the
                # same rebind-before-teardown ordering _advance
                # uses).
                self._rebind_pool(lane)
            except BaseException:
                _log.exception("stop-path rebind failed")
            try:
                lane.gen.close()
            except BaseException:
                pass
            try:
                # A never-advanced generator's close() runs no finally
                # — release the lane's pages directly (idempotent).
                # Only the lane holding the pool's current binding
                # (epoch match — true after the rebind above) may
                # write its arrays back.
                pool = lane.run.pool
                lane.run._paged_cleanup(
                    write_back=pool is None
                    or lane.pool_epoch == pool.epoch
                )
            except BaseException:
                _log.exception("lane cleanup failed")
            self._deliver_error(lane.run.reqs, err)
        for g in pending:
            self._deliver_error(g.reqs, err)

    @staticmethod
    def _deliver_error(reqs, err) -> None:
        for r in reqs:
            if getattr(r, "cancelled", False):
                # No consumer to deliver to, but the terminal hook
                # still fires (idempotent) so tenant-ledger depth
                # balances on the cancel path too.
                fin = getattr(r, "finish", None)
                if fin is not None:
                    fin()
                continue
            try:
                r.push(err)
            except Exception:  # a dead consumer must not mask others
                pass

    # -- policy --------------------------------------------------------

    def _weight_of(self, reqs) -> float:
        """Max tenant weight among a candidate's live requests (1.0
        with no ledger or only anonymous tenants). Weighted deadline
        slack divides by this: a weight-2 tenant's 100 ms of slack
        competes like 50 ms — it wins ties against weight-1 traffic
        but cannot starve it (every urgency stays finite, and the
        deadline-less alternation below ignores weights)."""
        led = getattr(self.eng, "tenants", None)
        if led is None:
            return 1.0
        w = 1.0
        for r in reqs:
            t = getattr(r, "tenant", "") or ""
            if t:
                w = max(w, led.weight(t))
        return w

    def _urgency_group(self, g: _Group, now: float, summary) -> float:
        w = self._weight_of(g.reqs)
        slack = _min_slack(g.reqs, now)
        if slack is not None:
            return slack / w
        # TTFT feed (r10 reservoirs): a deadline-less group that has
        # queued past ~2x the observed TTFT p95 starts competing like
        # a near-due deadline; cold reservoirs keep it relaxed.
        ttft = (summary["ttft_p95_ms"] or 0.0) / 1e3
        if ttft > 0.0 and (now - g.t_submit) > 2.0 * ttft:
            return ttft / w
        return _RELAXED_S / w

    def _urgency_lane(self, lane: _Lane, now: float, summary) -> float:
        w = self._weight_of(lane.run.reqs)
        slack = _min_slack(lane.run.reqs, now)
        if slack is not None:
            return slack / w
        # ITL feed: a deadline-less RUNNING lane competes at the
        # inter-token p50 scale (its consumers are waiting a token
        # gap, not a TTFT) — equal for all such lanes, so the
        # least-recently-picked tie-break alternates them strictly.
        itl = (summary["intertoken_p50_ms"] or 0.0) / 1e3
        return (itl if itl > 0.0 else _RELAXED_S) / w

    # -- score units (the scoring fast path's backend) -----------------

    @staticmethod
    def _urgency_score(su: _ScoreUnit, now: float) -> float:
        """Weighted urgency of one queued scoring batch. Deadlined:
        weighted slack, same currency as lanes. Deadline-less: linear
        aging from the SCORING model's observed TTFT p95 target
        (floor 5 ms cold) down to zero — a waiting score unit always
        reaches urgency 0 within its own latency target, so decode
        traffic can delay it at most one target's worth, never
        starve it."""
        if su.deadline is not None:
            return (su.deadline - now) / su.weight
        return max(su.target - (now - su.t_submit), 0.0) / su.weight

    def _claim_score(self) -> _ScoreUnit | None:
        """Decide score-vs-lane for this dispatch slot and pop the
        head score unit when scoring wins. Real deadline slack on
        either side decides by weighted minimum (a deadline override
        of the alternation counts as a preemption, the same
        ``sched_deadline_preempts`` currency lanes use); with no
        deadlines anywhere the dispatcher strictly ALTERNATES
        score/lane, so neither generation nor scoring can starve the
        other by construction — the no-starvation half of the
        acceptance bar, pinned from counters."""
        with self._lock:
            if not self._score:
                return None
            su = self._score[0]
            lanes = list(self._lanes)
        if not lanes:
            with self._lock:
                return self._score.popleft() if self._score else None
        now = time.perf_counter()
        u_score = self._urgency_score(su, now)
        summary = self._cached_summary()
        u_lane = min(
            self._urgency_lane(ln, now, summary) for ln in lanes
        )
        score_deadlined = su.deadline is not None
        lane_deadlined = any(
            _min_slack(ln.run.reqs, now) is not None for ln in lanes
        )
        alternation = not self._last_was_score
        if score_deadlined or lane_deadlined:
            take = u_score <= u_lane
            if take != alternation and (
                score_deadlined if take else lane_deadlined
            ):
                self.eng.sched_deadline_preempts += 1
        else:
            take = alternation
        if not take:
            return None
        with self._lock:
            return self._score.popleft() if self._score else None

    def _dispatch_score(self, su: _ScoreUnit) -> None:
        """One score unit on the dispatch thread: the device call runs
        inline (``fn`` resolves the batch's futures thread-safely) and
        the unit enters the SAME accounting lanes get — kind counter,
        trace, head-of-line streak under pseudo-lane id 0, so the
        stall bound covers scoring-behind-decode and decode-behind-
        scoring symmetrically."""
        eng = self.eng
        with self._lock:
            n_live = len(self._lanes)
        try:
            with span("sched.unit", "sched_unit_score",
                      registry=self._sums, kind="score", lane=0,
                      rows=su.n_rows) as sp:
                faults.fire("sched_unit")
                su.fn()
        except BaseException as e:  # noqa: BLE001 — unit-scoped failure
            _log.error("score unit of %d rows failed: %s", su.n_rows, e)
            try:
                su.fail(e)
            except BaseException:
                _log.exception("score-unit fail delivery failed")
        eng.sched_units_score += 1
        self._log_unit(0, "score", sp)
        # Score units count as one extra live party: consecutive
        # score dispatches while lanes wait (and vice versa) feed the
        # same streak gauge.
        self._note_dispatch(0, n_live + 1)
        self._last_was_score = True
        self._pick_seq += 1

    def _pick(self) -> _Lane | None:
        """Minimum-urgency lane; exact ties go least-recently-picked
        (fair alternation). A pick that overrides fairness because of
        a real deadline counts as a preemption."""
        now = time.perf_counter()
        with self._lock:
            lanes = list(self._lanes)
        if not lanes:
            return None
        if len(lanes) == 1:
            chosen = lanes[0]
        else:
            summary = self._cached_summary()
            scored = [
                (self._urgency_lane(ln, now, summary), ln.last_pick, ln)
                for ln in lanes
            ]
            scored.sort(key=lambda t: (t[0], t[1]))
            chosen = scored[0][2]
            fair = min(scored, key=lambda t: t[1])[2]
            if chosen is not fair and _min_slack(
                chosen.run.reqs, now
            ) is not None:
                self.eng.sched_deadline_preempts += 1
        self._pick_seq += 1
        chosen.last_pick = self._pick_seq
        return chosen

    # -- lane lifecycle ------------------------------------------------

    def _page_need(self, reqs) -> int:
        """Worst-case pool footprint of a group, from the BATCH
        geometry BatchRun will actually build: rows re-pack to the
        GROUP's max bucket and every live row maps the same decode
        spans, so the per-row span is the group's full static cache
        length (``engine._cache_len`` — the tier-quantized total a
        fused-width dispatch may map in ONE chunk, so fused-chunk
        lanes reserve what they can actually touch), plus the
        batched-spec headroom when a draft is attached. Prefix
        sharing and early finishes only make the real usage smaller
        (over-reservation costs a deferred start, never a mid-decode
        exhaustion)."""
        return len(reqs) * self._row_pages(reqs)

    def _row_pages(self, reqs) -> int:
        """Per-row worst-case page count of a group — one number for
        every row, because rows re-pack to the GROUP's geometry. The
        per-tenant split multiplies this by each tenant's row count."""
        eng = self.eng
        page = eng.pool.page
        span = eng._cache_len(
            max(r.prefix_len for r in reqs)
            + max(len(r.row) for r in reqs),
            max(r.n_new for r in reqs),
        ) + (eng.spec_k + 1 if eng.draft_model is not None else 0)
        return -(-span // page)

    @staticmethod
    def _tenant_split(reqs, row_pages: int) -> tuple[dict, dict]:
        """(tenant → worst-case pages, tenant → adapter-id set) of a
        group, anonymous tenants excluded — they are unquotaed."""
        pages: dict = {}
        adapters: dict = {}
        for r in reqs:
            t = getattr(r, "tenant", "") or ""
            if not t:
                continue
            pages[t] = pages.get(t, 0) + row_pages
            a = getattr(r, "adapter", None)
            if a is not None:
                adapters.setdefault(t, set()).add(a)
        return pages, adapters

    def _tenant_block(self, g: _Group, row_pages: int):
        """Per-tenant term of the reservation gate (caller holds the
        lock, lanes are live). A tenant already HOLDING reservations
        may not grow past its quota — need + held must fit; a tenant
        holding nothing starts unconditionally (quota smaller than
        one group must reject loudly downstream, not starve silently
        — the same escape the fleet-wide gate gives an empty pool).
        Returns the blocking (kind, tenant) or None. Never touches
        other tenants' reservations: a deferral leaves every live
        lane's pages exactly where they were."""
        led = getattr(self.eng, "tenants", None)
        if led is None or not self._lanes:
            return None
        need_pages, need_adapters = self._tenant_split(g.reqs, row_pages)
        for t, need in need_pages.items():
            quota = led.quota_pages_of(t)
            if quota is None:
                continue
            held = sum(
                ln.tenant_pages.get(t, 0) for ln in self._lanes
            )
            if held and need + held > quota:
                return ("pages", t)
        for t, ads in need_adapters.items():
            quota = led.quota_slots_of(t)
            if quota is None:
                continue
            held = set()
            for ln in self._lanes:
                held |= ln.tenant_adapters.get(t, set())
            if held and len(held | ads) > quota:
                return ("slots", t)
        return None

    def _claim_next_group(self) -> _Group | None:
        """Pop the most-urgent pending group that passes the
        page-budget gate — selection and pop under ONE lock hold, so
        a concurrent drain sweep or collector submit can never shift
        indices between the vetting and the pop.

        The gate: the group's worst-case footprint plus every live
        lane's RESERVATION must fit the pool, so concurrent lanes
        cannot grow each other into a mid-decode
        ``PagePoolExhausted`` (lanes allocate per chunk, so free
        pages at start wildly undercount what a live lane will still
        take). Prefix-entry pages don't count against the budget —
        they are evictable on demand. With no lanes live a group
        starts unconditionally (single-batch semantics — a loud
        reject beats silent starvation when the pool is simply too
        small)."""
        now = time.perf_counter()
        pool = self.eng.pool
        with self._lock:
            n_pending = len(self._pending)
            if not n_pending or len(self._lanes) >= self.max_batches:
                return None
        # The reservoir work lives OUTSIDE the lock — submit's
        # admission estimate, /healthz, and /metrics contend on it
        # via backlog/queue_depth. A single pending group skips the
        # scoring entirely (it wins unopposed).
        summary = self._cached_summary() if n_pending > 1 else None
        with self._lock:
            if not self._pending or len(self._lanes) >= self.max_batches:
                return None
            if summary is not None and len(self._pending) > 1:
                order = sorted(
                    enumerate(self._pending),
                    key=lambda t: (
                        self._urgency_group(t[1], now, summary), t[0]
                    ),
                )
            else:
                order = list(enumerate(self._pending))
            held = sum(ln.reserved for ln in self._lanes)
            for _, g in order:
                pages_ok = (
                    pool is None
                    or not self._lanes
                    or self._page_need(g.reqs) + held
                    <= pool.pages_total
                )
                # Adapter-slot term of the same reservation gate: the
                # group's adapters must be installable NOW (free slots
                # plus hold-free evictable ones), or its formation
                # acquire would fail loudly mid-batch. With no lanes
                # live the group starts unconditionally — the loud
                # AdapterSlotsExhausted beats silent starvation when
                # the slot pool is simply too small for one batch.
                slots_ok = (
                    self.eng.adapters is None
                    or not self._lanes
                    or self.eng.adapters.can_claim({
                        r.adapter for r in g.reqs
                        if getattr(r, "adapter", None) is not None
                    })
                )
                t_block = None
                if pages_ok and slots_ok:
                    # Per-tenant term, checked only once the fleet-
                    # wide terms pass — a tenant deferral means the
                    # POOL had room and this tenant's quota alone
                    # said no (the quota-pin test's distinction).
                    t_block = self._tenant_block(
                        g,
                        self._row_pages(g.reqs)
                        if pool is not None else 0,
                    )
                    if t_block is None:
                        self._pending.remove(g)
                        # Claimed: visible to idle/backlog/sweep via
                        # the forming slot until the lane exists.
                        self._forming_group = g
                        return g
                if not g.deferred_counted:
                    # Once per deferral episode, not per re-check.
                    g.deferred_counted = True
                    if t_block is not None:
                        kind, tenant = t_block
                        led = getattr(self.eng, "tenants", None)
                        if led is not None:
                            led.note_deferral(tenant)
                        if kind == "pages":
                            self.eng.sched_tenant_pages_deferred += 1
                        else:
                            self.eng.sched_tenant_adapters_deferred += 1
                    elif pages_ok:
                        self.eng.sched_adapters_deferred += 1
                    else:
                        self.eng.sched_pages_deferred += 1
            return None

    def _cached_summary(self):
        """The LatencyStats snapshot at pick granularity: recomputed
        every 32 picks (or on first use) instead of per unit —
        ``summary()`` sorts both reservoirs, and the policy only
        needs it at reservoir-drift resolution. Equal-urgency
        tie-breaks are unaffected (all deadline-less candidates read
        the SAME cached value)."""
        if (
            self._summary_cache is None
            or self._pick_seq - self._summary_seq >= 32
        ):
            self._summary_cache = self.eng.latency.summary()
            self._summary_seq = self._pick_seq
        return self._summary_cache

    def _urgent_lane_blocks_start(self) -> bool:
        """Cross-candidate-type priority: a live lane whose deadline
        slack is inside ~one formation's worth of work (2x the
        observed TTFT p95, floor 250 ms cold) outranks STARTING a new
        group — formation is a whole batch prefill, the one unit big
        enough to blow a near-due deadline. Starts resume once the
        tight lane finishes or expires (bounded: it is within its own
        slack of doing either)."""
        with self._lock:
            if not self._pending or not self._lanes:
                return False
            lanes = list(self._lanes)
        now = time.perf_counter()
        slack = None
        for ln in lanes:
            s = _min_slack(ln.run.reqs, now)
            if s is not None and (slack is None or s < slack):
                slack = s
        if slack is None:
            return False
        ttft = (self._cached_summary()["ttft_p95_ms"] or 0.0) / 1e3
        return slack < 2.0 * max(ttft, 0.125)

    def _maybe_start(self) -> bool:
        """Start pending groups (urgency order) while lane slots and
        the page budget allow — unless a live lane's deadline slack
        outranks a formation (see :meth:`_urgent_lane_blocks_start`).
        Formation — the group's prefill — runs here, on the dispatch
        thread, as the lane's first unit."""
        started = False
        while True:
            if self._urgent_lane_blocks_start():
                return started
            g = self._claim_next_group()
            if g is None:
                return started
            try:
                self._start_lane(g)
            finally:
                with self._lock:
                    self._forming_group = None
            started = True

    def _start_lane(self, g: _Group) -> None:
        """Formation as a unit: the engine's shared formation
        preamble (``_form_batch`` — the SAME expiry sweep
        ``_run_batch`` applies, one definition so serial and
        concurrent modes can never diverge), then the lane. A
        fused-eligible group decodes tier-wide chunks through the
        same units() generator — no uninterruptible whole-generation
        unit remains. Failures deliver to every waiter, scoped to
        this group — other lanes stream on."""
        eng, reqs = self.eng, g.reqs
        now = time.perf_counter()
        for r in reqs:
            r.t_claim = now  # queue wait ends, prefill wait begins
        try:
            # ``_lane_seq`` moves on this thread only, so the id the
            # lane will get is known before it exists.
            with span("sched.unit", registry=self._sums, kind="prefill",
                      lane=self._lane_seq + 1, rows=len(reqs),
                      rid=",".join(str(r.rid) for r in reqs)) as sp:
                faults.fire("sched_unit")
                run = eng._form_batch(reqs, admit=True)
                if run is not None:
                    sp.counter = "sched_unit_prefill"
            if run is None:
                return  # everyone expired before formation
        except BaseException as e:  # noqa: BLE001 — delivered to waiters
            if eng.pool is not None:
                # A failed paged formation may have DONATED the pool
                # arrays before dying; BatchRun.__init__'s cleanup
                # rewrote pool.layers from its fresh cache but knows
                # nothing of epochs — bump here or every live lane
                # skips its rebind and dispatches deleted buffers
                # (harmless over-bump when the failure preceded any
                # donation: lanes re-bind to the same arrays).
                eng.pool.epoch += 1
            _log.error(
                "scheduler formation of %d failed: %s", len(reqs), e
            )
            self._deliver_error(reqs, e)
            return
        eng.sched_units_prefill += 1  # formation IS the prefill unit
        self._writeback_pool(run)
        row_pages = (
            self._row_pages(reqs) if eng.pool is not None else 0
        )
        t_pages, t_adapters = self._tenant_split(reqs, row_pages)
        with self._lock:
            self._lane_seq += 1
            lane = _Lane(
                self._lane_seq, run, run.units(), self._pick_seq,
                reserved=len(reqs) * row_pages,
                tenant_pages=t_pages,
                tenant_adapters=t_adapters,
            )
            lane.pool_epoch = (
                eng.pool.epoch if eng.pool is not None else -1
            )
            self._lanes.append(lane)
            live = len(self._lanes)
        self._log_unit(lane.lane_id, "prefill", sp)
        self._note_dispatch(lane.lane_id, live)
        if live > eng.sched_batches_live_max:
            eng.sched_batches_live_max = live

    def _log_unit(self, lane_id: int, kind: str, sp) -> None:
        self.trace.append(
            (lane_id, kind, sp.start_ns, sp.start_ns + sp.elapsed_ns)
        )

    def _note_dispatch(self, lane_id: int, n_live: int) -> None:
        """Head-of-line accounting, counters not wall-clock: the
        longest run of consecutive units ONE lane received while
        another lane was live is the bound on how long concurrent
        traffic stalls behind it — with fused chunks folded into
        units, one fused-chunk dispatch (tests pin the gauge ≤ the
        alternation floor; deadline preemption can legitimately
        exceed it)."""
        if n_live > 1 and lane_id == self._last_lane:
            self._streak += 1
        else:
            self._streak = 1
        self._last_lane = lane_id
        if n_live > 1 and self._streak > self.eng.sched_lane_stall_max:
            self.eng.sched_lane_stall_max = self._streak

    def _rebind_pool(self, lane: _Lane) -> None:
        """Another lane's donated dispatch consumed the pool arrays
        this lane's cache pytree was bound to: re-bind from the pool's
        current arrays + this lane's own page table. One dispatch
        thread ⇒ no lock; the table upload is the only device work."""
        run = lane.run
        pool = run.pool
        if pool is None or lane.pool_epoch == pool.epoch:
            return
        from mlapi_tpu.ops.quant import paged_cache_tree

        run.cache = paged_cache_tree(pool.layers, run.tab[:run.b_cur])
        run._tab_dirty = False
        lane.pool_epoch = pool.epoch

    def _writeback_pool(self, run) -> None:
        """After a paged lane's unit: publish its (donation-fresh)
        pool arrays so the next lane to dispatch re-binds against
        them."""
        pool = run.pool
        if pool is None or getattr(run, "cache", None) is None:
            return
        from mlapi_tpu.ops.quant import paged_pools_of

        pool.layers = paged_pools_of(run.cache)
        pool.epoch += 1

    def _advance(self, lane: _Lane) -> None:
        """One unit of one lane: the heart of the queue."""
        eng = self.eng
        run = lane.run
        err: BaseException | None = None
        done = False
        kind = None
        try:
            # Rebind BEFORE the fault point: if the injected raise
            # closes this lane's generator, its cleanup writes the
            # lane's cache back to the pool — which must be the
            # CURRENT arrays, not the stale pytree another lane's
            # donation consumed (write-back of deleted buffers would
            # poison every surviving lane).
            self._rebind_pool(lane)
            faults.fire("sched_unit")
            # The kind is the generator's to say, after the work: the
            # span learns its counter and its ``kind`` on the way out.
            # The last ``next`` (StopIteration: the final drain and
            # the cleanup) is no unit of UNIT_KINDS; its time counts
            # as ``retire`` so the thread's account has no hole.
            with span("sched.unit", "sched_unit_retire",
                      registry=self._sums, lane=lane.lane_id,
                      rows=len(run.reqs)) as sp:
                try:
                    kind = next(lane.gen)
                except StopIteration:
                    sp.set(kind="retire")
                    raise
                sp.counter = f"sched_unit_{kind}"
                sp.set(kind=kind)
                if run.claimed:
                    sp.set(rid=",".join(map(str, run.claimed)))
                    run.claimed.clear()
        except StopIteration:
            done = True
        except BaseException as e:  # noqa: BLE001 — lane-scoped failure
            err = e
            done = True
            try:
                lane.gen.close()
            except BaseException:
                pass
            # close() on a generator that never ran its FIRST next()
            # (the fault fired before this lane's first unit) is a
            # no-op — units()'s cleanup ``finally`` never executed, so
            # release the formation's pages directly. Idempotent when
            # the generator DID run its finally (tables already null,
            # write-back repeats the same arrays). Write back only
            # when this lane's cache is the pool's CURRENT binding
            # (epoch match) — a stale pytree must never rebind
            # donation-consumed buffers over the live pool.
            try:
                run._paged_cleanup(
                    write_back=run.pool is None
                    or lane.pool_epoch == run.pool.epoch
                )
            except BaseException:
                _log.exception("lane cleanup failed")
        if run.pool is not None:
            if not done:
                self._writeback_pool(run)  # bumps the epoch
            else:
                # The generator's cleanup already wrote the final
                # arrays back on exhaustion/close; bump the epoch
                # here so surviving lanes re-bind. One write-back =
                # one bump, always.
                run.pool.epoch += 1
            lane.pool_epoch = run.pool.epoch
        if kind is not None:
            counter = f"sched_units_{kind}"
            setattr(eng, counter, getattr(eng, counter) + 1)
            self._log_unit(lane.lane_id, kind, sp)
            with self._lock:
                n_live = len(self._lanes)
            self._note_dispatch(lane.lane_id, n_live)
        if err is not None:
            _log.error(
                "scheduler lane of %d failed: %s", len(run.reqs), err
            )
            self._deliver_error(run.reqs, err)
        if done:
            with self._work:
                try:
                    self._lanes.remove(lane)
                except ValueError:
                    pass
                self._work.notify_all()
            # A retired lane frees a slot (and may strand staged
            # _admit candidates): wake the collector so staged and
            # deferred work re-enters dispatch immediately instead of
            # riding the 50 ms poll.
            eng._wake_collector()
