"""The repo-contract registry the rules check against.

Everything repo-specific lives HERE, not in the rule logic: which
attributes are lock-guarded and by which lock, which callables donate
their arguments, which module must stay async-pure, where the fault
points and metric exports live. A new shared structure (or a new
serving module) extends this file; the rules themselves stay generic
over the registry.

The registries are also what the tier-1 fixture tests parameterize:
``tests/test_static_analysis.py`` builds a Config pointed at
``tests/lint_fixtures/`` and asserts each rule flags its minimal
historical-bug repro at the exact ``file:line``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent.parent


@dataclass(frozen=True)
class LockSpec:
    """One class's lock discipline: mutations of ``attrs`` (on
    ``self``) must happen lexically inside ``with self.<lock>`` for a
    lock named in ``locks``, or inside a method whose name ends in
    ``_locked`` (the documented caller-holds-the-lock convention)."""

    locks: frozenset[str]
    attrs: frozenset[str]


# -- MLA002: lock-guarded shared state -------------------------------------
#
# The shared-mutable registry. Deliberately NOT listed:
# - PagePool.layers / PagePool.epoch — single-dispatch-thread by
#   contract (the donation rule's domain, not the lock rule's).
# - UnitScheduler._pick_seq/_lane_seq/_summary_cache/_summary_seq and
#   the engine's sched_* counters — dispatch-thread-only by design
#   (DESIGN §21); registering them would force locks the one-writer
#   model does not need.
LOCK_REGISTRY: dict[str, LockSpec] = {
    "PagePool": LockSpec(
        locks=frozenset({"lock", "_evict_cond"}),
        attrs=frozenset({
            "ref", "_free", "_entries", "_evicting",
            # Counters: incremented from the decode thread AND the
            # event loop (brownout evict_idle, admission shed paths),
            # scraped by /metrics — a bare += is a lost update.
            "cow_copies", "entry_evictions", "exhaustions",
        }),
    ),
    "KVTier": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({
            "_blobs", "_bytes", "_seq", "_meta",
            "spill_count", "spill_bytes", "spill_failures",
            "restore_hits", "restore_misses", "restore_bytes",
            "restore_failures", "evictions",
        }),
    ),
    # Deliberately NOT listed: ``_last_was_score`` — the alternation
    # bit is read/written only on the dispatch thread (the same
    # one-writer contract as _pick_seq).
    "UnitScheduler": LockSpec(
        locks=frozenset({"_lock", "_work"}),  # _work wraps _lock
        attrs=frozenset({
            "_pending", "_lanes", "_forming_group", "_stopped",
            # r22 scoring fast path: formed batches enqueue from the
            # event loop (submit_score) while the dispatch thread
            # claims/drains — same cross-thread shape as _pending.
            "_score",
        }),
    ),
    # r22 multi-model/multi-tenant state. ModelRegistry's engine map
    # is frozen at build_app time; only the started-set mutates
    # (startup/shutdown hooks vs /healthz reads). TenantLedger is
    # crossed by the event loop (enter/brownout), the dispatch thread
    # (quota deferrals, terminal exits), and /metrics reads.
    "ModelRegistry": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({"_started"}),
    ),
    "TenantLedger": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({"_depth", "_deferrals", "_brownouts"}),
    ),
    # r17 peer-fetch state: hints arrive from the event loop, fetch
    # counters from encode executor threads, serve counters from the
    # app executor — all /metrics-scraped, all lost-update-prone.
    "KVPeer": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({
            "_hints", "_serve_cache",
            "fetch_hits", "fetch_misses", "fetch_bytes",
            "fetch_failures", "serve_count", "serve_bytes",
        }),
    ),
    # r18 disaggregation push state: chunk sends enqueue from the
    # dispatch thread, the sender thread posts and counts, receives
    # land on the app executor, applied/fallback counts come from the
    # dispatch thread AND encode executors — all /metrics-scraped,
    # all lost-update-prone.
    "KVPush": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({
            "_xfers", "_staged", "_staged_bytes", "_sendq", "_worker",
            "push_sent", "push_send_failures", "push_bytes_sent",
            "push_recv", "push_recv_failures", "push_bytes_recv",
            "push_applied", "push_bytes_applied", "push_fallbacks",
        }),
    ),
    # Prefix registry (r04, registered r19 for MLA007/MLA008's
    # whole-program view): entry lookups/registrations race across
    # encode executor threads; the counters are /metrics-scraped.
    # Deliberately NOT listed: ``_wide`` — the widened-stack cache is
    # mutated only at batch formation (the one dispatch thread at a
    # time), the same single-writer contract as ``PagePool.layers``.
    "PrefixCache": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({
            "_entries", "_building", "mix_warmed",
            "hits", "misses", "fallbacks", "builds",
        }),
    ),
    # r21 per-tenant adapter state. Deliberately NOT listed:
    # AdapterSlots.pools / AdapterSlots.rank — mutated only by
    # install()/_materialize() on the one dispatch thread (the same
    # single-writer contract as PagePool.layers); the donated scatter
    # could not tolerate a concurrent reader anyway.
    "AdapterStore": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({
            # Host LRU index + byte accounting: registrations arrive
            # from the event loop (register_adapter), fetch staging
            # from encode executor threads, spill/evict from either;
            # evictions is /metrics-scraped.
            "_blobs", "_bytes", "_seq", "evictions",
        }),
    ),
    "AdapterSlots": LockSpec(
        locks=frozenset({"lock"}),
        attrs=frozenset({
            # Slot map + holds: acquire/release cross from the
            # dispatch thread (batch formation/teardown) while
            # can_claim reads from the scheduler's advance; installs/
            # evictions are /metrics-scraped.
            "_slot_of", "_holds", "_free", "installs", "evictions",
        }),
    ),
    "AdapterPeer": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({
            # Warm-peer hints land from the event loop, fetch
            # counters from encode executor threads, serve counters
            # from the app executor — the KVPeer shape exactly.
            "_hints",
            "fetch_hits", "fetch_misses", "fetch_bytes",
            "fetch_failures", "serve_count", "serve_bytes",
        }),
    ),
    "LatencyStats": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({"_ttft_ms", "_itl_ms"}),
    ),
    "MetricsRegistry": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({"_counters", "_histograms"}),
    ),
    "Counter": LockSpec(
        locks=frozenset({"_lock"}), attrs=frozenset({"value"})
    ),
    "Histogram": LockSpec(
        locks=frozenset({"_lock"}),
        attrs=frozenset({"count", "total", "_reservoir"}),
    ),
}

# Attribute names distinctive enough to check OUTSIDE their class's
# own methods (e.g. ``self.eng.pool.cow_copies += n`` from
# batch_run): a mutation of ``<base>.<attr>`` for these must sit
# inside ``with <base>.lock``-family for the SAME base expression.
# Generic names (value, count, ref, total) stay self-scoped — the
# cross-module check would drown in unrelated matches.
DISTINCTIVE_ATTRS: dict[str, frozenset[str]] = {
    "cow_copies": frozenset({"lock"}),
    "entry_evictions": frozenset({"lock"}),
    "exhaustions": frozenset({"lock"}),
    "_free": frozenset({"lock"}),
    "_entries": frozenset({"lock"}),
    "_blobs": frozenset({"_lock"}),
    "spill_failures": frozenset({"_lock"}),
    "restore_failures": frozenset({"_lock"}),
    # r17/r18 additions, registered here r19 (they postdated the
    # registry and were cross-module-unchecked): the KVPush staging
    # store + its byte accounting and sender records, the KVPeer
    # warm-hint map and serve-side wire-image cache, and the
    # PrefixCache counters engine.py bumps from encode threads.
    "_staged": frozenset({"_lock"}),
    "_staged_bytes": frozenset({"_lock"}),
    "_xfers": frozenset({"_lock"}),
    "_hints": frozenset({"_lock"}),
    "_serve_cache": frozenset({"_lock"}),
    "builds": frozenset({"_lock"}),
    "fallbacks": frozenset({"_lock"}),
    "mix_warmed": frozenset({"_lock"}),
    # r21 adapter containers (batch_run holds/releases through the
    # AdapterSlots API today, but a future direct mutation of the
    # slot map or hold table from outside the class must still sit
    # under the instance's lock).
    "_slot_of": frozenset({"lock"}),
    "_holds": frozenset({"lock"}),
}

# Methods on guarded attributes that mutate the container. Reads
# (len, iteration, .get) stay free — the rule is MUTATION discipline.
MUTATING_METHODS = frozenset({
    "append", "appendleft", "extend", "insert", "pop", "popitem",
    "popleft", "remove", "clear", "update", "add", "discard",
    "setdefault", "move_to_end", "sort",
})

# -- MLA007: lock-order graph ----------------------------------------------
# Attribute-name -> registered-class bindings the cross-module call
# resolver uses when the assignment shape (``self.pool =
# PagePool(...)``) is not visible in the AST (constructor args, plain
# name rebinds like ``pool.tier = self.kv_tier``). Inferred bindings
# (scanned from ``self.<attr> = <Class>(...)``) are merged first;
# entries here win on conflict.
INSTANCE_BINDINGS: dict[str, str] = {
    "pool": "PagePool",
    "tier": "KVTier",
    "kv_tier": "KVTier",
    "kv_peer": "KVPeer",
    "kv_push": "KVPush",
    "prefix": "PrefixCache",
    "sched": "UnitScheduler",
    "latency": "LatencyStats",
    "eng": "TextGenerationEngine",
    "engine": "TextGenerationEngine",
    "batcher": "ScorePath",
    "adapter_store": "AdapterStore",
    "adapters": "AdapterSlots",
    "adapter_peer": "AdapterPeer",
    "models": "ModelRegistry",
    "tenants": "TenantLedger",
    "led": "TenantLedger",
}
# Where the machine-readable partial order is committed (the rule
# recomputes it every run; the tier-1 test pins the committed file to
# the recomputed graph so the artifact can never drift silently, and
# the runtime witness loads it as the allowed order).
LOCKORDER_ARTIFACT = "tools/lint/lockorder.json"

# -- MLA008: thread-context inference --------------------------------------
# Functions seeded DISPATCH-thread (the one device-stream owner):
# BatchRun's unit generator and the scheduler's advance/loop. Thread
# targets and run_in_executor callees seed WORKER; every async def in
# a serving module seeds EVENT_LOOP.
DISPATCH_SEEDS: tuple[tuple[str, str], ...] = (
    ("BatchRun", "units"),
    ("UnitScheduler", "_advance"),
    ("UnitScheduler", "_loop"),
)
# Calls that BLOCK the calling thread — flagged when reachable in
# event-loop context outside an executor hop. Dotted prefixes match
# the trailing segments of the call chain (``np.savez`` matches
# ``np.savez_compressed`` via the startswith check in the rule).
EVENT_LOOP_BLOCKING_PREFIXES = (
    "time.sleep",
    "np.savez", "np.save", "np.load",
    "numpy.savez", "numpy.save", "numpy.load",
    "socket.socket", "socket.create_connection",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output", "subprocess.Popen",
    "os.system", "os.popen",
    "urllib.request.urlopen", "request.urlopen",
    "requests.get", "requests.post",
    "http.client.HTTPConnection",
)
# Bare attribute names that block or dispatch device work regardless
# of receiver: jax fences and host<->device transfers have no
# business on the event loop (they belong to the dispatch thread or
# an executor worker — the r13 spill-under-lock shape).
EVENT_LOOP_BLOCKING_ATTRS = frozenset({
    "block_until_ready", "device_put", "device_get",
})

# -- MLA009: terminal-frame wait discipline --------------------------------
# Counters that only SETTLE after a stream's terminal frame (their
# mutation runs on the dispatch thread during batch cleanup, strictly
# after the last frame reaches the awaiting test): asserting them
# lexically after a terminal read without a condition wait is the
# r17/r18 flake class.
SETTLE_AFTER_TERMINAL = ("kv_pages_in_use",)
# An await of a call whose name contains one of these consumed a
# stream to its terminal frame...
TERMINAL_READ_HINTS = ("collect", "gather")
# ...and one of these between the terminal read and the assert means
# the test waited for the state to settle (condition waits, engine
# stop/drain joins, and this suite's own `_quiesce`/`_settle`
# helpers). A ``while`` loop polling the counter inline counts as a
# wait too (the rule special-cases it).
SETTLE_WAIT_HINTS = (
    "wait", "stop", "drain", "join", "shutdown", "quiesce", "settle",
)

# -- MLA004: async purity --------------------------------------------------
# Modules that run ON the event loop and must not import jax or call
# blocking primitives outside run_in_executor.
ASYNC_PURE_MODULES = ("mlapi_tpu/serving/router.py",)

# (module, attr) call pairs that block the calling thread.
BLOCKING_CALLS = frozenset({
    ("time", "sleep"),
    ("subprocess", "run"), ("subprocess", "call"),
    ("subprocess", "check_call"), ("subprocess", "check_output"),
    ("subprocess", "Popen"),
    ("socket", "socket"), ("socket", "create_connection"),
    ("os", "system"), ("os", "popen"),
    ("urllib.request", "urlopen"), ("request", "urlopen"),
    ("requests", "get"), ("requests", "post"),
})
# Bare builtins that block (sync file IO on the event loop).
BLOCKING_BUILTINS = frozenset({"open"})

# -- MLA005: metrics -------------------------------------------------------
# Dotted metric tokens. Brace shorthand in docs
# (``generate.shed_{queue_full,...}``) stops the match at the brace,
# leaving a prefix the satisfiability check handles; file-path
# lookalikes (``batcher.py::...``) are filtered in the rule.
METRIC_NAME_RE = r"(?:generate|batcher|router|replica|http|model|tenant)\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*"
# Families whose exported names are constructed dynamically (router
# relabels replica gauges, sums arbitrary replica counters; http
# route labels are f-strings; the r22 per-model and per-tenant
# families key on registry ids / tenant names). A scraped/doc name
# under these prefixes is satisfiable by construction.
DYNAMIC_METRIC_PREFIXES = (
    "replica.", "router.", "http.", "model.", "tenant.",
)

# -- default scan set ------------------------------------------------------
DEFAULT_PY_GLOBS = (
    "mlapi_tpu/**/*.py",
    "tests/**/*.py",
    "tools/**/*.py",
)
# The fixtures are DELIBERATE violations (the negative tests); the
# clean-tree run must not see them. datasets/docs_corpus holds
# corpus text, not code.
DEFAULT_EXCLUDES = (
    "tests/lint_fixtures/",
    "mlapi_tpu/datasets/docs_corpus/",
)


@dataclass
class Config:
    root: Path = REPO_ROOT
    py_globs: tuple[str, ...] = DEFAULT_PY_GLOBS
    exclude_prefixes: tuple[str, ...] = DEFAULT_EXCLUDES
    # Role anchors (repo-relative); rules no-op when absent so a
    # fixture Config can exercise one rule in isolation.
    faults_module: str = "mlapi_tpu/serving/faults.py"
    latency_stats_module: str = "mlapi_tpu/serving/requests.py"
    # Where fire() seams live / where donation+locks apply.
    production_prefix: str = "mlapi_tpu/"
    serving_prefix: str = "mlapi_tpu/serving/"
    # Where fault-matrix coverage and metric scrapes are read from.
    test_prefix: str = "tests/"
    doc_files: tuple[str, ...] = ("README.md", "docs/DESIGN.md")
    async_pure_modules: tuple[str, ...] = ASYNC_PURE_MODULES
    lock_registry: dict = field(
        default_factory=lambda: dict(LOCK_REGISTRY)
    )
    distinctive_attrs: dict = field(
        default_factory=lambda: dict(DISTINCTIVE_ATTRS)
    )
    baseline_file: str = "tools/lint/baseline.txt"
    # MLA007 / MLA008 / MLA009 knobs (fixture Configs override).
    instance_bindings: dict = field(
        default_factory=lambda: dict(INSTANCE_BINDINGS)
    )
    lockorder_artifact: str = LOCKORDER_ARTIFACT
    dispatch_seeds: tuple = DISPATCH_SEEDS
    blocking_prefixes: tuple = EVENT_LOOP_BLOCKING_PREFIXES
    blocking_attrs: frozenset = EVENT_LOOP_BLOCKING_ATTRS
    settle_counters: tuple = SETTLE_AFTER_TERMINAL
    terminal_read_hints: tuple = TERMINAL_READ_HINTS
    settle_wait_hints: tuple = SETTLE_WAIT_HINTS
