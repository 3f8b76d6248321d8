"""Test harness config.

Tests run on CPU with 8 virtual XLA devices
(``--xla_force_host_platform_device_count=8``) so mesh/sharding/
collective behaviour is exercised without TPU hardware (SURVEY §4,
"distributed without a cluster"). Real-TPU runs use the
``requires_tpu`` marker and are skipped here.

Env vars must be set before the first ``import jax`` anywhere in the
test process, hence this header runs at conftest import time.
"""

import os

# Unit tests run on the CPU backend whatever the machine holds.
# Set MLAPI_TPU_TESTS=1 to run on the attached TPU instead — this is
# how the ``requires_tpu``-marked tests execute for real. ONE process
# only (a chip belongs to one process; under xdist every worker would
# reach for it during collection):
#   MLAPI_TPU_TESTS=1 pytest tests/ -m requires_tpu -p no:xdist
_ON_TPU = os.environ.get("MLAPI_TPU_TESTS") == "1"
# Generation warmup compiles (bucket x batch) shape grids — right for
# serving, wasteful for unit tests. Tests that specifically exercise
# the full warmup opt back in with warmup(full=True).
os.environ.setdefault("MLAPI_TPU_WARMUP", "minimal")
if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # XLA:CPU sizes its worker pool from the core count (or from
    # $NPROC, which it reads as the test's CPU reservation). A
    # collective over the 8 virtual devices blocks one pool thread per
    # participant until all 8 have arrived; with no more threads than
    # cores — and 6 xdist workers sharing them — the last participants
    # never get a thread, the all-reduce never completes and XLA
    # aborts the process after 40 s ("Expected 8 threads to join the
    # rendezvous"). A pool larger than any mesh here cannot starve.
    # Env, not config, so the CLIs the tests spawn inherit it.
    os.environ.setdefault("NPROC", "64")

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "requires_tpu: needs real TPU hardware; skipped on CPU"
    )
    config.addinivalue_line(
        "markers",
        "heavy: in-suite model training or soak-style test (tens of "
        "seconds each on this box). The fast dev profile deselects "
        "them: pytest -m 'not heavy' (~8 min serial vs ~10.5 full — "
        "measured times in README). CI and tier-1 run the full suite.",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 window by its time budget "
        "(-m 'not slow'); run explicitly with pytest -m slow. Two "
        "populations: multi-minute spawned-process drills (e.g. the "
        "--router SIGTERM/respawn topology test), and — since the "
        "r16 buyback — the five in-suite churn/long-tail soaks whose "
        "per-test measured call times (5.5 + 4.8 + 7.2 + 7.1 + "
        "12.6 s, noted at each demotion site) were pushing the suite "
        "against the 870 s window (r14/r15 both timed out there with "
        "zero failures). The soaks duplicate tier-1 functional "
        "coverage at larger iteration counts, so demoting them "
        "regains ~37 s (~31 s net of the new test_static_analysis "
        "module) without dropping any invariant from the window.",
    )


@pytest.hookimpl(optionalhook=True)  # absent under -p no:xdist
def pytest_xdist_make_scheduler(config, log):
    """Deal tests out by FILE whatever ``--dist`` says. The suite is
    laid out for it: trained models and engines are module-scoped
    fixtures and the cache-clearing fixture below fires on module
    switches, so ``--dist load`` — which interleaves tests of many
    modules on every worker — rebuilds fixtures and recompiles
    programs per visit (a ~7 min suite that did not finish in 24)."""
    from xdist.scheduler import LoadFileScheduling

    class _ByFile(LoadFileScheduling):
        def _reschedule(self, node):
            # A replacement worker is added before it has reported
            # its collection; upstream's reschedule-all then raises
            # KeyError and takes the whole run down with it.
            if node in self.registered_collections:
                super()._reschedule(node)

    return _ByFile(config, log)


def pytest_collection_modifyitems(config, items):
    # Without MLAPI_TPU_TESTS=1 the backend is the CPU by construction
    # (JAX_PLATFORMS above): no device query during collection.
    if not _ON_TPU or jax.default_backend() != "tpu":
        skip = pytest.mark.skip(reason="no TPU attached")
        for item in items:
            if "requires_tpu" in item.keywords:
                item.add_marker(skip)
    # Cluster each cache family at its first member's position so the
    # shared-window fixture shares even when a CLI file list or -k
    # selection breaks the default alphabetical adjacency the family
    # relies on. A no-op for default runs (the spec family is already
    # contiguous); stable within and across groups.
    first_seen: dict = {}
    for i, item in enumerate(items):
        g = _cache_group(item.module.__name__)
        first_seen.setdefault(g, i)
    items.sort(key=lambda it: first_seen[_cache_group(it.module.__name__)])


# Module families that share a model config compile IDENTICAL
# expensive programs (whole-generation fused loops, prefill/decode
# grids) — clearing the XLA cache between them just recompiles the
# same executables (r04 suite creep, VERDICT #8). Each family forms
# one cache window; every other module stays its own window, and
# collection is reordered so family members run consecutively.
_CACHE_FAMILIES = {
    # h48/3L target + h24/1L draft speculation pair (sampling's
    # synthetic-kernel tests add small v32 models on top of the same
    # pair). Only IDENTICAL-config families share a window: a
    # serving-family grouping (same arch, differing max_positions)
    # was measured at ~38s saved and rejected — partially-overlapping
    # program sets accumulate across the window and weaken the
    # segfault guard the clears exist for.
    "spec-family": frozenset({
        "test_speculative",
        "test_speculative_batched",
        "test_speculative_sampling",
        "test_spec_batched_serving",
    }),
    # Identical tiny-model CFG (vocab 260 / h32 / 2L / 4H / 160 pos,
    # f32) and the same {gpt, llama} x {none, int8} engine shapes at
    # page 8 / chunk 2: the tier module re-drives the SAME compiled
    # prefill/decode programs test_paged_kv built, plus only its own
    # restore scatter — sharing the window saves the whole 4-config
    # compile ladder a second time (~15 s).
    # + the scheduler module (r15): same CFG and engine shapes again —
    # scheduler-on drives the SAME compiled prefill/decode programs
    # (the unit generator changes dispatch ORDER, never shapes), so
    # sharing the window costs it only its own handful of tier
    # variants instead of the whole ladder.
    # + the kv_peer module (r17): identical CFG and the same
    # {gpt, llama} x {none, int8} engine shapes at page 8 / chunk 2 —
    # peer restores re-drive the programs the tier module compiled;
    # only the wire hop is new, and it compiles nothing.
    # + the kv_push module (r18): the same CFG again — disaggregated
    # prefill/decode drive the family's compiled programs at a
    # (16, 64) bucket ladder (a handful of extra shapes, paid once in
    # the shared window); the push wire hop compiles nothing.
    # + the lock-witness module (r19): identical CFG once more — the
    # armed smoke re-drives the family's compiled prefix/scheduler
    # programs with wrapped locks; wrapping compiles nothing.
    # + the fused-serving module (r20 fold): same CFG at page 8 /
    # chunk 2 — fused-width decode chunks are the family's
    # decode_chunk_fn at tier-wide sizes, so only the handful of
    # fused-width shapes are new; prefill and plain-chunk programs
    # come from the shared window.
    # + the lora-serving module (r21): same CFG and engine shapes at
    # page 8 / chunk 2 — adapter traffic reaches the family's
    # prefill/decode programs through the one decode_chunk_fn seam;
    # only the lora-augmented trace variants (grouped scalar-slot and
    # gathered rows) are new, and they compile once in the shared
    # window instead of re-paying the whole ladder.
    # + the multi-model module (r22): same CFG and engine shapes at
    # page 8 / chunk 2 — a registry's generative entries drive the
    # family's prefill/decode programs unchanged (score units change
    # dispatch ORDER, never shapes), and the scoring fast path's
    # padded-shape jit programs are tiny tabular predicts.
    # + the span-serving module (PR 27): same CFG and shapes once
    # more — timing the units compiles nothing.
    "paged-family": frozenset({
        "test_serving_fused",
        "test_kv_peer",
        "test_kv_push",
        "test_lock_witness",
        "test_lora_serving",
        "test_multi_model",
        "test_paged_kv",
        "test_paged_kv_tier",
        "test_scheduler",
        "test_span_serving",
    }),
}
_last_cache_group = [None]


def _cache_group(module_name: str) -> str:
    name = module_name.rsplit(".", 1)[-1]
    for family, members in _CACHE_FAMILIES.items():
        if name in members:
            return family
    return name


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_module_groups(request):
    """Drop compiled executables when crossing a module-GROUP
    boundary. A full-suite run accumulates hundreds of XLA CPU
    programs in one process and eventually SEGFAULTS inside a later
    compile (reproduced twice at the same test with ~128 GB RAM
    free — compiler-internal state, not host memory). Clearing
    between groups keeps the process within whatever envelope the
    compiler needs, while the spec-family modules — which compile the
    SAME programs — share one window instead of paying the compiles
    per module. Serial runs visit the family consecutively
    (alphabetical collection); under xdist each worker tracks its own
    last-group, so the bound holds per process either way."""
    group = _cache_group(request.module.__name__)
    if _last_cache_group[0] is not None and group != _last_cache_group[0]:
        jax.clear_caches()
    _last_cache_group[0] = group
    yield


# The longest tier-1 test (the smoke rehearsal) runs two minutes; one
# that spins on a condition that never comes true (a poll loop without
# a bound) would otherwise hold its worker until the run's own time
# limit cuts everything.
_TEST_DEADLINE_S = 600


@pytest.fixture(autouse=True)
def _test_deadline(request):
    """Fail a test that outlives ``_TEST_DEADLINE_S`` instead of
    hanging the run: SIGALRM raises in the main thread, which is where
    pytest (and every xdist worker) runs tests and event loops."""
    import signal
    import threading

    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def _expired(signum, frame):
        raise TimeoutError(
            f"{request.node.nodeid} exceeded {_TEST_DEADLINE_S} s"
        )

    previous = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, _TEST_DEADLINE_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(scope="session")
def mesh8():
    """An 8-device (data=8, model=1) mesh on virtual CPU devices."""
    from mlapi_tpu.parallel import create_mesh

    return create_mesh((8, 1))


@pytest.fixture(scope="session")
def mesh_2x4():
    """A (data=2, model=4) mesh for sharded-param configs."""
    from mlapi_tpu.parallel import create_mesh

    return create_mesh((2, 4))


@pytest.fixture(scope="session")
def mesh_1x4():
    """A (data=1, model=4) mesh — pure TP, the generative-serving
    decode layout (batch stays whole; params split over `model`)."""
    import jax as _jax

    from mlapi_tpu.parallel import create_mesh

    return create_mesh((1, 4), devices=_jax.devices()[:4])


@pytest.fixture(scope="session")
def count_primitives():
    """``count_primitives(jaxpr) -> Counter``: how often each primitive
    runs in a jaxpr, every sub-program counted where it is called; a
    Pallas call under its kernel's name (``name=`` or the kernel
    function's)."""
    import collections

    def count(jaxpr, counts=None):
        counts = collections.Counter() if counts is None else counts
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[eqn.params["name"]
                       or eqn.params["jaxpr"].debug_info.func_name] += 1
                continue
            counts[eqn.primitive.name] += 1
            for sub in jax.tree.leaves(
                    eqn.params, is_leaf=lambda v: hasattr(v, "eqns")):
                if hasattr(sub, "eqns"):
                    count(getattr(sub, "jaxpr", sub), counts)
        return counts

    return count


def _armed_witness():
    """One arming protocol for both witness fixtures: install the
    runtime lock-order witness (tools/lint/witness.py, the dynamic
    half of MLA007), yield it, uninstall, and FAIL on any recorded
    order inversion against the committed lockorder.json (or
    hold-budget breach when MLAPI_LOCK_WITNESS_BUDGET_S is set)."""
    import sys

    root = str(os.path.dirname(os.path.dirname(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from tools.lint.witness import LockWitness, install

    w = LockWitness.from_artifact()
    uninstall = install(w)
    try:
        yield w
    finally:
        uninstall()
    assert not w.violations, "\n".join(w.violations)


@pytest.fixture
def lock_witness():
    """Opt-in per-test witness: every registered serving lock
    constructed inside the fixture's scope records per-thread
    acquisition stacks; teardown fails the test on violations. Arm
    it suite-wide instead with MLAPI_LOCK_WITNESS=1."""
    yield from _armed_witness()


@pytest.fixture(scope="session", autouse=True)
def _lock_witness_env():
    """MLAPI_LOCK_WITNESS=1 arms the witness for the WHOLE session:
    every engine any test builds runs wrapped, and the session fails
    at teardown on any recorded violation. Off (the default), this
    fixture is a no-op — zero cost, nothing imported."""
    if os.environ.get("MLAPI_LOCK_WITNESS") != "1":
        yield
        return
    yield from _armed_witness()
