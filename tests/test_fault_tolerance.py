"""Fault-tolerant search runtime: every failure path, deterministically.

The contract under test (core/search_pool.py "Failure semantics"): task
results are pure functions of (token, sub-space), so retry after worker
death, transient-error re-dispatch, straggler duplicates, device-replay
fallback, journal resume and preemption drain must all merge to a
``SearchResult`` byte-identical to the clean serial run -- same cuts,
same metrics, same ``evaluated`` -- with every recovery surfaced on
``result.events``, and the genuine error paths (exhausted retries,
corrupt journal, deterministic worker exceptions) must raise, never hang
or silently degrade.  All injected faults come from the seeded chaos
harness (runtime/chaos.py), so each scenario reproduces exactly.
"""
import contextlib
import hashlib
import multiprocessing as mp
import signal

import pytest

from repro.cnn import build_cnn
from repro.core import search_pool
from repro.core.compiler import compile_graph
from repro.core.cutpoint import monotone_runs, search, split_blocks
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500
from repro.core.options import CompileOptions
from repro.core.search_pool import (TASKS_PER_WORKER, ParallelSearchDriver,
                                    SearchPreempted, partition_space)
from repro.runtime import chaos
from repro.runtime.fault_tolerance import PreemptionGuard, StragglerMonitor

from test_search_pool import TEST_LIMIT, assert_results_identical

HAS_FORK = "fork" in mp.get_all_start_methods()
needs_fork = pytest.mark.skipif(
    not HAS_FORK, reason="fork start method required for workers to "
    "inherit the parent-installed chaos injector")

# Zoo slice for the fuzz sweep: resnet50/152 take the partitioned
# exhaustive path at TEST_LIMIT, the rest the per-start descent path, so
# both task shapes get fuzzed.
FUZZ_CNNS = ["vgg16-conv", "yolov3", "resnet50", "resnet152",
             "efficientnet-b1", "retinanet", "mobilenet-v3"]

TEST_OPTS = CompileOptions(exhaustive_limit=TEST_LIMIT)


@contextlib.contextmanager
def injected(injector):
    chaos.install(injector)
    try:
        yield injector
    finally:
        chaos.uninstall()


@pytest.fixture(scope="module")
def resnet():
    gg = group_nodes(build_cnn("resnet50"))
    return gg, search(gg, KCU1500, TEST_OPTS)


def resnet_prefixes(gg, workers=2):
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    return partition_space(runs, workers * TASKS_PER_WORKER)[0]


# ------------------------------------------------------- satellite fixes
def test_step_end_without_step_start_is_a_noop():
    """Used to crash with TypeError on ``None - float`` arithmetic."""
    m = StragglerMonitor()
    assert m.step_end(0) is False
    assert len(m.times) == 0
    m.step_start()
    assert m.step_end(1) is False          # normal pairing still works
    assert len(m.times) == 1


def test_straggler_monitor_honors_window():
    """The deque maxlen used to be hardcoded to 256, ignoring window."""
    m = StragglerMonitor(window=7)
    for i in range(50):
        m.observe(1.0 + i)
    assert m.times.maxlen == 7
    assert len(m.times) == 7
    assert list(m.times) == [1.0 + i for i in range(43, 50)]


def test_straggler_ewma_deadline_warmup_and_value():
    m = StragglerMonitor(threshold=3.0, alpha=0.5, min_samples=3)
    assert m.straggler_after() is None
    m.observe(1.0)
    m.observe(1.0)
    assert m.straggler_after() is None     # still warming up
    m.observe(2.0)
    # ewma: 1.0 -> 1.0 -> 0.5*2 + 0.5*1 = 1.5; deadline = 3 * 1.5
    assert m.straggler_after() == pytest.approx(4.5)


def test_preemption_guard_uninstall_restores_handlers():
    """install() used to overwrite the handlers permanently."""
    before = signal.getsignal(signal.SIGTERM)
    g = PreemptionGuard()
    g.install()
    assert signal.getsignal(signal.SIGTERM) == g._handler
    g.uninstall()
    assert signal.getsignal(signal.SIGTERM) == before
    with PreemptionGuard() as g2:          # context manager pairs them
        assert signal.getsignal(signal.SIGTERM) == g2._handler
        assert not g2.preempted
        g2.request()
        assert g2.preempted
    assert signal.getsignal(signal.SIGTERM) == before


# ------------------------------------------------------- chaos injector
def test_chaos_schedule_is_deterministic_and_scheduling_independent():
    a = chaos.ChaosInjector(seed=11, p_kill=0.2, p_raise=0.2, p_delay=0.2)
    b = chaos.ChaosInjector(seed=11, p_kill=0.2, p_raise=0.2, p_delay=0.2)
    keys = [(i, j) for i in range(10) for j in range(10)]
    plan_a = [a.event_for("task", k) for k in keys]
    # same seed, any consultation order -> same plan per (site, key)
    plan_b = [b.event_for("task", k) for k in reversed(keys)][::-1]
    assert plan_a == plan_b
    assert any(e is not None for e in plan_a)
    assert any(e is None for e in plan_a)
    # a different seed reshuffles the schedule
    c = chaos.ChaosInjector(seed=12, p_kill=0.2, p_raise=0.2, p_delay=0.2)
    assert [c.event_for("task", k) for k in keys] != plan_a
    # sites draw independently
    assert ([a.event_for("device", k) for k in keys] != plan_a)


def test_chaos_explicit_events_override_seeded_draw():
    inj = chaos.ChaosInjector(
        seed=0, p_kill=1.0,
        events={("task", "pinned"): chaos.ChaosEvent("delay", delay_s=0.0)})
    assert inj.event_for("task", "pinned").action == "delay"
    assert inj.event_for("task", "other").action == "kill"
    with pytest.raises(ValueError):
        chaos.ChaosEvent("segfault")


def test_chaos_fires_only_below_max_attempt():
    inj = chaos.ChaosInjector(seed=0, p_raise=1.0, max_attempt=2)
    with pytest.raises(chaos.ChaosError):
        inj.fire("task", "k", attempt=0)
    with pytest.raises(chaos.ChaosError):
        inj.fire("task", "k", attempt=1)
    inj.fire("task", "k", attempt=2)       # retry budget reached: no-op
    assert chaos.ChaosError.transient is True
    assert [f[3] for f in inj.fired] == ["raise", "raise"]


def test_chaos_maybe_fire_is_noop_without_injector():
    chaos.uninstall()
    chaos.maybe_fire("task", "anything")   # must not raise


# --------------------------------------------- retry & healing identity
@needs_fork
def test_worker_kill_heals_pool_and_result_is_bit_identical(resnet):
    gg, serial = resnet
    with injected(chaos.ChaosInjector(seed=7, p_kill=0.08)):
        with ParallelSearchDriver(workers=2, mp_context="fork") as d:
            r = d.search(gg, KCU1500, TEST_OPTS)
    assert_results_identical(serial, r, ctx="kill-retry")
    retries = [e for e in r.events if e.kind == "retry"]
    assert retries and all("died" in e.detail for e in retries)


@needs_fork
def test_transient_raise_is_retried_and_bit_identical(resnet):
    gg, serial = resnet
    with injected(chaos.ChaosInjector(seed=3, p_raise=0.15)):
        with ParallelSearchDriver(workers=2, mp_context="fork") as d:
            r = d.search(gg, KCU1500, TEST_OPTS)
    assert_results_identical(serial, r, ctx="transient-raise")
    retries = [e for e in r.events if e.kind == "retry"]
    assert retries and all("chaos" in e.detail for e in retries)


@needs_fork
def test_exhausted_retries_raise_instead_of_hanging(resnet):
    gg, _ = resnet
    # max_attempt high: the fault outlives every re-dispatch
    with injected(chaos.ChaosInjector(seed=7, p_kill=0.08, max_attempt=99)):
        with ParallelSearchDriver(workers=2, mp_context="fork",
                                  max_retries=1) as d:
            with pytest.raises(RuntimeError,
                               match="worker process died"):
                d.search(gg, KCU1500, TEST_OPTS)
    with injected(chaos.ChaosInjector(seed=3, p_raise=0.15,
                                      max_attempt=99)):
        with ParallelSearchDriver(workers=2, mp_context="fork",
                                  max_retries=1) as d:
            with pytest.raises(RuntimeError, match="failed after"):
                d.search(gg, KCU1500, TEST_OPTS)


@needs_fork
def test_deterministic_worker_exception_is_never_retried(resnet):
    """A worker exception without ``transient=True`` propagates unchanged
    on the first attempt -- no retry events, no healing (invalid knob
    values no longer reach workers at all: CompileOptions rejects them in
    the caller)."""
    gg, _ = resnet
    search_pool._TEST_FAIL_HOOK = "raise"
    try:
        with ParallelSearchDriver(workers=2, mp_context="fork",
                                  max_retries=5) as d:
            with pytest.raises(RuntimeError,
                               match="simulated worker failure"):
                d.search(gg, KCU1500, TEST_OPTS)
    finally:
        search_pool._TEST_FAIL_HOOK = None


# --------------------------------------------- deadlines & degradation
@needs_fork
def test_straggler_duplicate_rescues_delayed_task(resnet):
    """The victim's first attempt blocks on a chaos *hold* gate (not a
    wall-clock sleep, which races the deadline timer under load): it
    deterministically overruns the deadline, the speculative duplicate
    (attempt 1, past max_attempt) completes, and the gate is released
    before pool shutdown so ``close()`` never joins a blocked worker."""
    gg, serial = resnet
    victim = resnet_prefixes(gg)[1]
    inj = chaos.ChaosInjector()
    release = inj.hold("task", victim)
    with injected(inj):
        with ParallelSearchDriver(workers=2, mp_context="fork",
                                  task_deadline_s=0.5) as d:
            try:
                r = d.search(gg, KCU1500, TEST_OPTS)
            finally:
                release()
    assert_results_identical(serial, r, ctx="straggler")
    stragglers = [e for e in r.events if e.kind == "straggler"]
    # Membership, not equality: a slow CI box may legitimately flag a
    # second straggler; the held victim must always be one of them.
    assert victim in [e.task for e in stragglers]


@needs_fork
def test_device_replay_falls_back_to_journal_loudly(resnet):
    gg, serial = resnet
    victim = resnet_prefixes(gg)[2]
    ev = {("device", victim): chaos.ChaosEvent("raise")}
    with injected(chaos.ChaosInjector(events=ev)):
        with ParallelSearchDriver(workers=2, mp_context="fork") as d:
            r = d.search(gg, KCU1500, TEST_OPTS.replace(engine="device"))
    assert_results_identical(serial, r, ctx="device-fallback")
    falls = [e for e in r.events if e.kind == "device_fallback"]
    assert [e.task for e in falls] == [victim]
    assert "journal engine substituted" in falls[0].detail


@needs_fork
def test_device_replay_deterministic_error_propagates(resnet, monkeypatch):
    """A deterministic device failure (a kernel that does not compile, a
    shape error) must surface, not hide behind the journal engine: the
    search raises it unchanged, with no fallback and no retry."""
    gg, _ = resnet

    def broken(self, frame, skip=None):
        raise ValueError("Incompatible shapes for broadcasting")

    # fork-started workers inherit the patched engine method
    monkeypatch.setattr(search_pool._cp.CutpointEngine, "_device_replay",
                        broken)
    with ParallelSearchDriver(workers=2, mp_context="fork",
                              max_retries=5) as d:
        with pytest.raises(ValueError, match="Incompatible shapes"):
            d.search(gg, KCU1500, TEST_OPTS.replace(engine="device"))


def test_chaos_hold_gate_mechanics():
    """hold events need a gate, release unblocks fire(), and attempts at
    or past max_attempt (the straggler duplicate) never block."""
    with pytest.raises(ValueError, match="need a gate"):
        chaos.ChaosEvent("hold")
    inj = chaos.ChaosInjector()
    release = inj.hold("task", ("k",))
    inj.fire("task", ("k",), attempt=1)     # duplicate: no block
    release()
    inj.fire("task", ("k",), attempt=0)     # released gate: returns
    assert [f[:2] for f in inj.fired] == [("task", ("k",))]


# ------------------------------------------------- journal & preemption
def test_resume_skips_journaled_tasks_bit_identically(resnet, tmp_path):
    gg, serial = resnet
    with ParallelSearchDriver(workers=2) as d:
        first = d.search(gg, KCU1500,
                         TEST_OPTS.replace(resume_dir=tmp_path))
    assert_results_identical(serial, first, ctx="journal-first")
    assert not first.events               # clean run: nothing to report
    recs = list(tmp_path.glob("search_*/task_*.rec"))
    assert recs                           # every task committed a record
    with ParallelSearchDriver(workers=2) as d:
        second = d.search(gg, KCU1500,
                          TEST_OPTS.replace(resume_dir=tmp_path))
    assert_results_identical(serial, second, ctx="journal-second")
    resumed = [e for e in second.events if e.kind == "resume"]
    assert len(resumed) == len(recs)      # fully replayed from disk


@needs_fork
def test_killed_compile_resumes_from_task_journal(resnet, tmp_path):
    """The acceptance scenario at test scale: a parallel search killed
    mid-flight (injected worker death, retries exhausted) leaves its
    completed tasks journaled; the re-run resumes and merges to the
    byte-identical result, surfacing the resume events."""
    gg, serial = resnet
    # the doomed task is dispatched last (sliding window), so earlier
    # tasks deterministically complete and journal before it exhausts
    doomed = resnet_prefixes(gg)[-1]
    ev = {("task", doomed): chaos.ChaosEvent("kill", max_attempt=99)}
    with injected(chaos.ChaosInjector(events=ev)):
        with ParallelSearchDriver(workers=2, mp_context="fork",
                                  max_retries=1) as d:
            with pytest.raises(RuntimeError, match="worker process died"):
                d.search(gg, KCU1500,
                         TEST_OPTS.replace(resume_dir=tmp_path))
    survivors = len(list(tmp_path.glob("search_*/task_*.rec")))
    assert survivors > 0
    with ParallelSearchDriver(workers=2, mp_context="fork") as d:
        r = d.search(gg, KCU1500, TEST_OPTS.replace(resume_dir=tmp_path))
    assert_results_identical(serial, r, ctx="resume-after-kill")
    assert len([e for e in r.events if e.kind == "resume"]) == survivors


def test_preemption_drains_and_resumes(resnet, tmp_path):
    gg, serial = resnet
    guard = PreemptionGuard()
    guard.request()                       # SIGTERM already latched
    with ParallelSearchDriver(workers=2, guard=guard) as d:
        with pytest.raises(SearchPreempted, match="resume to finish"):
            d.search(gg, KCU1500, TEST_OPTS.replace(resume_dir=tmp_path))
    with ParallelSearchDriver(workers=2) as d:
        r = d.search(gg, KCU1500, TEST_OPTS.replace(resume_dir=tmp_path))
    assert_results_identical(serial, r, ctx="resume-after-preempt")


def test_corrupt_journal_record_raises_not_resumes(resnet, tmp_path):
    from repro.checkpoint.checkpoint import JournalError
    gg, _ = resnet
    with ParallelSearchDriver(workers=2) as d:
        d.search(gg, KCU1500, TEST_OPTS.replace(resume_dir=tmp_path))
    rec = sorted(tmp_path.glob("search_*/task_*.rec"))[0]
    rec.write_bytes(b"\x00garbage" + rec.read_bytes()[4:])
    with ParallelSearchDriver(workers=2) as d:
        with pytest.raises(JournalError, match="corrupt task-journal"):
            d.search(gg, KCU1500, TEST_OPTS.replace(resume_dir=tmp_path))


def test_journal_keyed_by_search_content(resnet, tmp_path):
    """A journal written for one (objective, partition) must not be
    consulted for another -- the content hash separates them."""
    gg, _ = resnet
    with ParallelSearchDriver(workers=2) as d:
        d.search(gg, KCU1500, TEST_OPTS.replace(resume_dir=tmp_path))
        serial_sram = search(gg, KCU1500,
                             TEST_OPTS.replace(objective="sram"))
        r = d.search(gg, KCU1500,
                     TEST_OPTS.replace(objective="sram",
                                       resume_dir=tmp_path))
    assert not [e for e in r.events if e.kind == "resume"]
    assert_results_identical(serial_sram, r, ctx="objective-keyed")
    assert len(list(tmp_path.glob("search_*"))) == 2


# ------------------------------------------------------------ zoo fuzz
@needs_fork
@pytest.mark.parametrize("name", FUZZ_CNNS)
def test_fuzzed_chaos_preserves_bit_identity_across_zoo(name):
    """Seeded kill/raise/delay schedule over each zoo net (exhaustive
    and descent task shapes): whatever fires, the merged result must be
    byte-identical to the clean serial run."""
    gg = group_nodes(build_cnn(name))
    serial = search(gg, KCU1500, TEST_OPTS)
    # stable per-net seed (Python's str hash is salted per process)
    seed = int(hashlib.sha256(name.encode()).hexdigest()[:4], 16)
    inj = chaos.ChaosInjector(seed=seed, p_kill=0.03, p_raise=0.05,
                              p_delay=0.05, delay_s=0.2)
    # No task_deadline_s here: injected wall-clock delays must never race
    # a deadline timer (that interaction is covered deterministically by
    # the hold-gate straggler test above).
    with injected(inj):
        with ParallelSearchDriver(workers=2, mp_context="fork") as d:
            r = d.search(gg, KCU1500, TEST_OPTS)
    assert_results_identical(serial, r, ctx=f"fuzz-{name}")


@needs_fork
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fuzzed_chaos_multi_seed_resume_round_trip(seed, tmp_path, resnet):
    """Different schedules, same invariant: chaos run journals into
    resume_dir, a clean resume completes it, both bit-identical."""
    gg, serial = resnet
    inj = chaos.ChaosInjector(seed=seed, p_kill=0.05, p_raise=0.05)
    with injected(inj):
        with ParallelSearchDriver(workers=2, mp_context="fork") as d:
            try:
                r = d.search(gg, KCU1500,
                             TEST_OPTS.replace(resume_dir=tmp_path))
            except RuntimeError:
                r = None                  # retries exhausted: resume below
    if r is not None:
        assert_results_identical(serial, r, ctx=f"fuzz-seed{seed}")
    with ParallelSearchDriver(workers=2, mp_context="fork") as d:
        r2 = d.search(gg, KCU1500, TEST_OPTS.replace(resume_dir=tmp_path))
    assert_results_identical(serial, r2, ctx=f"fuzz-seed{seed}-resume")


# ------------------------------------------------------ compiler surface
@needs_fork
def test_compile_graph_resume_dir_end_to_end(tmp_path):
    graph = build_cnn("resnet50")
    clean = compile_graph(graph, KCU1500, TEST_OPTS.replace(workers=2))
    doomed = resnet_prefixes(group_nodes(graph))[-1]
    ev = {("task", doomed): chaos.ChaosEvent("kill", max_attempt=99)}
    with injected(chaos.ChaosInjector(events=ev)):
        with pytest.raises(RuntimeError, match="worker process died"):
            compile_graph(graph, KCU1500,
                          TEST_OPTS.replace(workers=2, max_retries=1,
                                            resume_dir=tmp_path))
    plan = compile_graph(graph, KCU1500,
                         TEST_OPTS.replace(workers=2,
                                           resume_dir=tmp_path))
    assert plan.candidate.cuts == clean.candidate.cuts
    assert plan.latency.cycles == clean.latency.cycles
    assert plan.search.evaluated == clean.search.evaluated
    assert plan.instructions == clean.instructions
    assert any(e.kind == "resume" for e in plan.search.events)
