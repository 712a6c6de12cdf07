"""Compiler-throughput benchmark: batched scorer vs engine vs oracle.

For every CNN-zoo network, measures
  * candidate evaluations/sec of the direct oracle (``cutpoint.evaluate``:
    full allocate + whole-graph reports per tuple, the seed inner loop),
  * candidate evaluations/sec of :class:`CutpointEngine` per tuple over
    the same product-order enumeration the exhaustive search walks,
  * candidate evaluations/sec of ``CutpointEngine.score_batch`` (the
    mask-matrix batched scorer the search uses by default),
  * end-to-end ``compile_graph`` wall time (at ``--workers``, since the
    default 8M ``exhaustive_limit`` makes yolov2's 7.96M-tuple space fully
    enumerable),
plus a **batched slice** (the headline): a fixed slice of yolov2's
partitioned cut space scored per-tuple and batched, interleaved
best-of-N per mode so this container's CPU-burst variance mostly cancels,
with the PR 3 per-tuple engine rate as the committed reference point; an
**allocator-replay comparison** (``alloc_replay``): the same slice scored
under the journal Python replay vs the tensorized device replay of
kernels/alloc_scan.py (numpy reference / jax scan / Pallas interpret);
a **fused-pipeline comparison** (``pipeline_slice``): the same slice
searched end-to-end under ``engine="pipeline:lax"`` /
``"pipeline:reference"`` (kernels/search_pipeline.py -- in-kernel
enumeration, alloc-scan replay, cost reduction and hierarchical argmin,
no host candidate stream) vs the journal engine, argmin and evaluation
counts asserted identical; a **workers sweep**: the same kind of slice
pushed through the search
pool at 1/2/4/8 workers; and a **pruning benchmark** (``prune``): the
FULL yolov2 space searched unpruned vs branch-and-bound pruned vs
kill-healed at 2 workers, byte-identity asserted, recording the pruned
fraction, the normalized speedup and the healed search rate.  Everything
lands in ``BENCH_compile.json``.
The numbers are only meaningful because the engine and the batched scorer
are oracle-exact -- equivalence is enforced by
tests/test_cutpoint_engine.py and tests/test_score_batch.py, and
serial/parallel search bit-identity by tests/test_search_pool.py; all are
spot-checked here in smoke mode.

Usage:
    PYTHONPATH=src python benchmarks/compile_throughput.py [--smoke] [-o F]

``--smoke`` (the CI regression gate) runs two small networks with short
budgets, asserts engine/oracle/batched agreement plus serial-vs-parallel
bit-identity, and compares the batched scorer's evals/sec against the
committed floor in BENCH_compile.json -- normalized by the busy-loop
calibration so a slow CI machine doesn't trip it -- failing on >30%
regression.  It writes its measurements to BENCH_smoke.json (uploaded as
a CI artifact) instead of touching the committed JSON.
"""
from __future__ import annotations

import argparse
import itertools
import json
import multiprocessing as _mp
import os
import pickle
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.cnn import build_cnn                                  # noqa: E402
from repro.core.compiler import compile_graph                    # noqa: E402
from repro.core.cutpoint import (DEFAULT_BATCH_SIZE,             # noqa: E402
                                 CutpointEngine, _key, evaluate,
                                 monotone_runs, search, split_blocks)
from repro.core.grouping import group_nodes                      # noqa: E402
from repro.core.hw import KCU1500                                # noqa: E402
from repro.core.options import CompileOptions              # noqa: E402
from repro.core.search_pool import (TASKS_PER_WORKER,            # noqa: E402
                                    ParallelSearchDriver, SearchPreempted,
                                    _run_subspace, partition_space)
from repro.runtime import chaos                                  # noqa: E402
from repro.runtime.fault_tolerance import PreemptionGuard        # noqa: E402
from repro.utils.compile_cache import enable_compile_cache       # noqa: E402

try:                                                             # noqa: E402
    from busyloop import measure_busyloop_rate, measure_parallel_capacity
except ImportError:                                  # pragma: no cover
    from benchmarks.busyloop import (measure_busyloop_rate,
                                     measure_parallel_capacity)

# PR 3's committed per-tuple engine rate on the yolov2 slice (this
# machine, BENCH_compile.json workers_sweep["1"] before the batched
# scorer landed) -- the reference the batched slice's speedup is gated
# against.
PR3_SLICE_EVALS_PER_SEC = 11387.9

ZOO = [("vgg16-conv", 224), ("yolov2", 416), ("yolov3", 416),
       ("resnet50", 224), ("resnet152", 224), ("efficientnet-b1", 256),
       ("retinanet", 512), ("mobilenet-v3", 224)]
SMOKE_ZOO = [("vgg16-conv", 224), ("resnet50", 224)]

METRICS = ["latency_cycles", "dram_total", "dram_fm", "sram_total",
           "bram18k", "feasible"]


def _product_tuples(runs):
    return itertools.product(*[range(len(r) + 1) for r in runs])


def bench_workers_sweep(name: str, size: int, worker_counts: list[int],
                        n_tasks: int = 16) -> dict:
    """Fixed-work scaling measurement on a detector-scale cut space.

    Partitions the network's cut product exactly as ``search(workers=N)``
    does, takes the first ``n_tasks`` equal-sized sub-spaces (a deep slice
    of yolov2's 7.96M tuples -- large enough to amortize pool startup,
    small enough to sweep four worker counts in minutes), and pushes the
    *same* slice through the pool at each worker count.  Also asserts that
    every configuration merges to the same argmin (determinism is not a
    matter of luck -- tests/test_search_pool.py proves it, this keeps the
    benchmark honest about it).
    """
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    prefixes, suffix_dims = partition_space(
        runs, target_tasks=max(64, 8 * max(worker_counts)))
    prefixes = prefixes[:n_tasks]
    task_size = 1
    for d in suffix_dims:
        task_size *= d + 1
    tuples = len(prefixes) * task_size
    payload = pickle.dumps((gg, KCU1500), protocol=pickle.HIGHEST_PROTOCOL)

    sweep: dict[str, dict] = {}
    argmins = set()
    base_eps = None
    for w in worker_counts:
        token = ("sweep", name, size, w)
        tasks = [(token, payload, p, suffix_dims, "latency",
                  DEFAULT_BATCH_SIZE, "journal", "numpy") for p in prefixes]
        t0 = time.perf_counter()
        if w == 1:
            results = [_run_subspace(t) for t in tasks]
        else:
            with ParallelSearchDriver(workers=w) as driver:
                results = driver.map(_run_subspace, tasks)
        wall = time.perf_counter() - t0
        evals = sum(n for _, n, _p, _e in results)
        assert evals == tuples
        best = min((m for m, _n, _p, _e in results),
                   key=lambda m: (_key(m, "latency"), m.cuts))
        argmins.add(best.cuts)
        eps = evals / wall
        if base_eps is None:
            base_eps = eps
        sweep[str(w)] = {"wall_s": round(wall, 2),
                         "evals_per_sec": round(eps, 1),
                         "speedup_vs_1w": round(eps / base_eps, 2)}
        print(f"workers sweep {name}: w={w} {wall:.1f}s "
              f"{eps:.0f} evals/s ({sweep[str(w)]['speedup_vs_1w']}x)")
    assert len(argmins) == 1, "sub-space merge must be worker-independent"
    capacity = measure_parallel_capacity(max(worker_counts))
    print(f"machine parallel capacity at {max(worker_counts)} busy loops: "
          f"{capacity:.2f}x")
    return {
        "network": f"{name}@{size}",
        "tuples": tuples,
        "tasks": len(prefixes),
        "cpu_count": os.cpu_count(),
        "parallel_capacity": round(capacity, 2),
        "note": "fixed slice of the partitioned cut space; speedup "
                "saturates at the machine's measured parallel_capacity "
                "(busy-loop ceiling), not at the advertised cpu_count",
        "workers": sweep,
    }


def bench_batched_slice(name: str = "yolov2", size: int = 416,
                        n_tasks: int = 8, reps: int = 2) -> dict:
    """Headline measurement: batched vs per-tuple scoring on a fixed
    exhaustive sub-space slice of the detector's cut product.

    Runs the *same* ``_run_subspace`` worker body both ways
    (``batch_size=1`` vs the production default), interleaved
    ``reps`` times with best-of per mode so the container's bursty CPU
    mostly cancels, and asserts both modes merge to the same argmin.
    The recorded speedups are (a) batched vs the per-tuple rate measured
    in this same run and (b) batched vs the PR 3 per-tuple engine rate
    committed in BENCH_compile.json before the batched scorer existed."""
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    prefixes, suffix_dims = partition_space(runs, target_tasks=64)
    prefixes = prefixes[:n_tasks]
    task_size = 1
    for d in suffix_dims:
        task_size *= d + 1
    tuples = len(prefixes) * task_size
    payload = pickle.dumps((gg, KCU1500), protocol=pickle.HIGHEST_PROTOCOL)

    modes = [("per_tuple", 1), ("batched", DEFAULT_BATCH_SIZE)]
    best_eps = {m: 0.0 for m, _ in modes}
    argmins = set()
    for rep in range(reps):
        for mode, bs in modes:
            token = ("slice", name, size, mode, rep)
            tasks = [(token, payload, p, suffix_dims, "latency", bs,
                      "journal", "numpy") for p in prefixes]
            t0 = time.perf_counter()
            results = [_run_subspace(t) for t in tasks]
            wall = time.perf_counter() - t0
            evals = sum(n for _, n, _p, _e in results)
            assert evals == tuples
            best = min((m for m, _n, _p, _e in results),
                       key=lambda m: (_key(m, "latency"), m.cuts))
            argmins.add(best.cuts)
            eps = evals / wall
            best_eps[mode] = max(best_eps[mode], eps)
            print(f"batched slice {name} rep{rep} {mode}: "
                  f"{wall:.1f}s {eps:.0f} evals/s")
    assert len(argmins) == 1, "batched/per-tuple argmin must agree"
    speedup = best_eps["batched"] / best_eps["per_tuple"]
    vs_pr3 = best_eps["batched"] / PR3_SLICE_EVALS_PER_SEC
    print(f"batched slice: {speedup:.2f}x vs same-run per-tuple, "
          f"{vs_pr3:.2f}x vs PR3 engine ({PR3_SLICE_EVALS_PER_SEC}/s)")
    return {
        "network": f"{name}@{size}",
        "tuples": tuples,
        "tasks": len(prefixes),
        "batch_size": DEFAULT_BATCH_SIZE,
        "reps": reps,
        "per_tuple_evals_per_sec": round(best_eps["per_tuple"], 1),
        "batched_evals_per_sec": round(best_eps["batched"], 1),
        "speedup_vs_per_tuple": round(speedup, 2),
        "pr3_per_tuple_evals_per_sec": PR3_SLICE_EVALS_PER_SEC,
        "speedup_vs_pr3_engine": round(vs_pr3, 2),
        "note": "interleaved best-of per mode on one fixed exhaustive "
                "slice; identical argmin asserted across modes",
    }


def bench_alloc_replay(name: str = "yolov2", size: int = 416,
                       n_tasks: int = 8, reps: int = 2,
                       pallas_batches: int = 4) -> dict:
    """Allocator-replay comparison on the fixed yolov2 slice: the
    journal-based Python replay vs the tensorized device replay
    (kernels/alloc_scan.py) under its numpy-reference, jax.lax.scan and
    Pallas-interpret backends.

    Each mode scores the *same* product-order slice through
    ``CutpointEngine.score_batch`` in production-size batches and must
    produce the same argmin (they are bit-identical by contract --
    tests/test_alloc_scan.py; the assertion keeps the benchmark honest).
    Interleaved best-of per mode, like the batched slice.  The Pallas
    interpret mode runs the kernel body un-compiled, so it is measured on
    a few batches and reported for completeness -- on a real TPU the same
    kernel compiles; off-TPU its rate is a correctness artifact, not a
    speed claim."""
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    prefixes, suffix_dims = partition_space(runs, target_tasks=64)
    prefixes = prefixes[:n_tasks]
    tuples = [p + s for p in prefixes
              for s in itertools.product(*[range(d + 1)
                                           for d in suffix_dims])]
    chunks = [tuples[i:i + DEFAULT_BATCH_SIZE]
              for i in range(0, len(tuples), DEFAULT_BATCH_SIZE)]

    modes = [("python_journal", "journal", None),
             ("scan_reference", "device", "reference"),
             ("jax_scan", "device", "scan"),
             ("pallas_interpret", "device", "pallas")]
    best_eps = {m: 0.0 for m, _, _ in modes}
    argmins = {}
    for rep in range(reps):
        for mode, replay, alloc_backend in modes:
            engine = CutpointEngine(gg, KCU1500, blocks, runs,
                                    replay=replay,
                                    alloc_backend=alloc_backend)
            use = chunks if mode != "pallas_interpret" \
                else chunks[:pallas_batches]
            best = None
            t0 = time.perf_counter()
            for chunk in use:
                for c in engine.score_batch(chunk, memoize=False):
                    if best is None or (_key(c, "latency")
                                        < _key(best, "latency")):
                        best = c
            wall = time.perf_counter() - t0
            assert engine.evaluations == sum(len(c) for c in use)
            eps = engine.evaluations / wall
            best_eps[mode] = max(best_eps[mode], eps)
            if mode != "pallas_interpret":          # partial slice differs
                argmins.setdefault(mode, best.cuts)
            print(f"alloc replay {name} rep{rep} {mode}: "
                  f"{wall:.1f}s {eps:.0f} evals/s")
    assert len(set(argmins.values())) == 1, \
        "journal/device argmin must agree"
    return {
        "network": f"{name}@{size}",
        "tuples": len(tuples),
        "batch_size": DEFAULT_BATCH_SIZE,
        "reps": reps,
        "evals_per_sec": {m: round(r, 1) for m, r in best_eps.items()},
        "device_vs_journal": round(
            best_eps["scan_reference"] / best_eps["python_journal"], 2),
        "note": "same fixed yolov2 slice as batched_slice, scored via "
                "score_batch under each allocator-replay mode; argmin "
                "asserted identical (bit-identity is the tested "
                "contract); pallas_interpret is un-compiled kernel "
                "emulation measured on a few batches",
    }


def bench_pipeline_slice(name: str = "yolov2", size: int = 416,
                         n_tasks: int = 8, reps: int = 2) -> dict:
    """Fused-pipeline throughput on the fixed yolov2 slice: the same
    sub-spaces as ``batched_slice`` searched under
    ``engine="pipeline:lax"`` (the production fused on-device loop) and
    ``engine="pipeline:reference"`` (its numpy oracle), against the
    journal engine measured in the same run.

    Every mode runs the identical ``_run_subspace`` worker body the
    parallel search dispatches, interleaved best-of per mode; the argmin
    AND the per-task evaluation counts are asserted identical across
    engines (the pipeline scores everything in-kernel, so its count
    equals journal scored+pruned under the unpruned walk used here)."""
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    prefixes, suffix_dims = partition_space(runs, target_tasks=64)
    prefixes = prefixes[:n_tasks]
    task_size = 1
    for d in suffix_dims:
        task_size *= d + 1
    tuples = len(prefixes) * task_size
    payload = pickle.dumps((gg, KCU1500), protocol=pickle.HIGHEST_PROTOCOL)

    modes = ["journal", "pipeline:reference", "pipeline:lax"]
    best_eps = {m: 0.0 for m in modes}
    argmins = set()
    counts = set()
    for rep in range(reps):
        for mode in modes:
            token = ("pipe", name, size, mode, rep)
            tasks = [(token, payload, p, suffix_dims, "latency",
                      DEFAULT_BATCH_SIZE, mode, "numpy") for p in prefixes]
            t0 = time.perf_counter()
            results = [_run_subspace(t) for t in tasks]
            wall = time.perf_counter() - t0
            evals = sum(n for _, n, _p, _e in results)
            assert evals == tuples, (mode, evals, tuples)
            counts.add(tuple(n for _, n, _p, _e in results))
            best = min((m for m, _n, _p, _e in results),
                       key=lambda m: (_key(m, "latency"), m.cuts))
            argmins.add(best.cuts)
            eps = evals / wall
            best_eps[mode] = max(best_eps[mode], eps)
            print(f"pipeline slice {name} rep{rep} {mode}: "
                  f"{wall:.1f}s {eps:.0f} evals/s")
    assert len(argmins) == 1, "pipeline/journal argmin must agree"
    assert len(counts) == 1, "pipeline/journal eval counts must agree"
    speedup = best_eps["pipeline:lax"] / best_eps["journal"]
    print(f"pipeline slice: lax {speedup:.2f}x vs same-run journal")
    return {
        "network": f"{name}@{size}",
        "tuples": tuples,
        "tasks": len(prefixes),
        "batch_size": DEFAULT_BATCH_SIZE,
        "reps": reps,
        "evals_per_sec": {m: round(r, 1) for m, r in best_eps.items()},
        "lax_speedup_vs_journal": round(speedup, 2),
        "note": "same fixed yolov2 slice as batched_slice, searched "
                "through _run_subspace under each engine; argmin and "
                "per-task evaluation counts asserted identical "
                "(tests/test_search_pipeline.py proves the contract)",
    }


def smoke_pipeline_gate(committed_path: Path | None) -> dict:
    """CI gate for the fused pipeline: on a small fixed yolov2 slice the
    ``pipeline:lax`` engine must (a) merge to the byte-identical argmin
    and evaluation counts as the journal engine, and (b) keep its
    evals/sec within ``max_regression`` of the committed
    ``pipeline_floor``, normalized by the busy-loop calibration (same
    discipline as the batched-scorer gate)."""
    gg = group_nodes(build_cnn("yolov2", 416))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    prefixes, suffix_dims = partition_space(runs, target_tasks=256)
    prefixes = prefixes[:2]
    payload = pickle.dumps((gg, KCU1500), protocol=pickle.HIGHEST_PROTOCOL)
    rate = measure_busyloop_rate()

    outcomes = {}
    for mode in ("journal", "pipeline:lax"):
        tasks = [(("pipe-smoke", mode), payload, p, suffix_dims,
                  "latency", DEFAULT_BATCH_SIZE, mode, "numpy")
                 for p in prefixes]
        # warm-up pass: triggers the engine build and (for the pipeline)
        # the one jit compile per sub-space shape, so the timed pass
        # measures steady-state throughput -- the thing the floor gates
        # -- not fixed compile latency that busy-loop normalization
        # cannot scale
        [_run_subspace(t) for t in tasks]
        t0 = time.perf_counter()
        results = [_run_subspace(t) for t in tasks]
        wall = time.perf_counter() - t0
        evals = sum(n for _, n, _p, _e in results)
        best = min((m for m, _n, _p, _e in results),
                   key=lambda m: (_key(m, "latency"), m.cuts))
        outcomes[mode] = (best.cuts, evals, evals / wall)
    assert outcomes["journal"][:2] == outcomes["pipeline:lax"][:2], \
        "pipeline argmin/evaluated diverged from journal"
    measured = outcomes["pipeline:lax"][2]
    record: dict = {
        "network": "yolov2@416",
        "tuples": outcomes["journal"][1],
        "busyloop_ops_per_sec": round(rate, 1),
        "journal_evals_per_sec": round(outcomes["journal"][2], 1),
        "pipeline_evals_per_sec": round(measured, 1),
        "bit_identical": True,               # asserted above
    }
    floor = None
    if committed_path is not None and committed_path.exists():
        floor = json.loads(committed_path.read_text()).get("pipeline_floor")
    if not floor:
        print("pipeline gate: no committed pipeline_floor -- "
              "measuring only")
        return record
    speed = rate / floor["busyloop_ops_per_sec"]
    need = floor["pipeline_evals_per_sec"] * speed \
        * (1 - floor["max_regression"])
    record.update({
        "floor_evals_per_sec": floor["pipeline_evals_per_sec"],
        "machine_speed_vs_floor": round(speed, 3),
        "required_evals_per_sec": round(need, 1),
        "passed": measured >= need,
    })
    if record["passed"]:
        print(f"pipeline gate OK: {measured:.0f} evals/s >= {need:.0f} "
              f"required (machine speed {speed:.2f}x vs floor)")
    else:
        record["fail_msg"] = (
            f"pipeline regression gate: measured {measured:.0f} evals/s "
            f"< required {need:.0f} (committed floor "
            f"{floor['pipeline_evals_per_sec']:.0f} x machine speed "
            f"{speed:.2f} x {1 - floor['max_regression']:.2f})")
    return record


def bench_chaos(name: str = "yolov2", size: int = 416,
                n_tasks: int = 24, workers: int = 2,
                max_overhead: float = 0.15) -> dict:
    """Fault-tolerance benchmark + gate on a yolov2 slice (the PR 6
    acceptance scenario at benchmark scale).

    Pushes the *same* fixed slice of yolov2's partitioned cut space
    through the pool four ways -- clean; with an injected worker death
    (seeded chaos harness: the pool heals, the lost task is re-dispatched,
    the run completes); preempted by a latched SIGTERM (clean drain,
    completed tasks journaled, ``SearchPreempted``); and resumed from
    that journal -- asserting every completed run's ``SearchResult`` is
    byte-identical to the clean one (cuts, metrics, ``evaluated``) with
    the recovery events surfaced, and gating the kill run's overhead at
    ``max_overhead`` vs the clean floor (both walls normalized by the
    busy-loop calibration taken next to each run, so a CPU burst between
    runs doesn't fake a regression).  Failures land in the returned
    record (``passed``/``fail_msg``) so the caller can write the
    BENCH_chaos.json artifact *before* raising."""
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    prefixes, suffix_dims = partition_space(runs, target_tasks=256)
    prefixes = prefixes[:n_tasks]
    task_size = 1
    for d in suffix_dims:
        task_size *= d + 1

    def run_slice(tag, injector=None, guard=None, resume_dir=None,
                  expect_preempt=False):
        if injector is not None:
            chaos.install(injector)
        try:
            rate = measure_busyloop_rate()
            t0 = time.perf_counter()
            with ParallelSearchDriver(workers=workers, mp_context="fork",
                                      guard=guard) as d:
                try:
                    res = d.run_subspaces(
                        gg, KCU1500, prefixes, suffix_dims,
                        CompileOptions(resume_dir=resume_dir),
                        blocks=blocks, runs=runs)
                except SearchPreempted:
                    assert expect_preempt, "unexpected preemption"
                    res = None
            wall = time.perf_counter() - t0
        finally:
            if injector is not None:
                chaos.uninstall()
        ev = [] if res is None else [e.kind for e in res.events]
        print(f"chaos {tag}: {wall:.1f}s busyloop={rate:.0f}/s "
              f"events={ev or ('preempted' if res is None else 'none')}")
        return res, wall, rate

    def assert_identical(res, ctx):
        assert res.best.cuts == clean.best.cuts, ctx
        for f in METRICS:
            assert getattr(res.best, f) == getattr(clean.best, f), (ctx, f)
        assert res.evaluated == clean.evaluated, ctx

    clean, clean_wall, clean_rate = run_slice("clean")
    assert not clean.events

    # injected worker death mid-sweep: the task at the slice midpoint is
    # hard-killed on its first attempt; the pool heals and re-dispatches
    doomed = prefixes[n_tasks // 2]
    inj = chaos.ChaosInjector(
        events={("task", doomed): chaos.ChaosEvent("kill")})
    with tempfile.TemporaryDirectory() as td:
        killed, kill_wall, kill_rate = run_slice("worker-kill", injector=inj,
                                                 resume_dir=td)
        assert_identical(killed, "worker-kill")
        kinds = [e.kind for e in killed.events]
        assert "retry" in kinds, kinds

    # SIGTERM drain: the latched guard stops dispatch, in-flight tasks
    # finish and journal, the re-run resumes from the journal
    with tempfile.TemporaryDirectory() as td:
        guard = PreemptionGuard()
        guard.request()                   # as the SIGTERM handler would
        _, preempt_wall, _ = run_slice("sigterm-drain", guard=guard,
                                       resume_dir=td, expect_preempt=True)
        journaled = len(list(Path(td).glob("search_*/task_*.rec")))
        resumed, resume_wall, _ = run_slice("resume", resume_dir=td)
        assert_identical(resumed, "resume")
        n_resume = sum(1 for e in resumed.events if e.kind == "resume")
        assert n_resume == journaled

    # overhead gate: busy-loop-normalized work (wall x concurrent
    # busy-loop rate) of the kill run vs the clean floor
    overhead = (kill_wall * kill_rate) / (clean_wall * clean_rate) - 1
    record = {
        "network": f"{name}@{size}",
        "tasks": n_tasks,
        "tuples": n_tasks * task_size,
        "workers": workers,
        "clean_wall_s": round(clean_wall, 2),
        "kill_wall_s": round(kill_wall, 2),
        "preempt_drain_wall_s": round(preempt_wall, 2),
        "resume_wall_s": round(resume_wall, 2),
        "journaled_at_preempt": journaled,
        "resumed_tasks": n_resume,
        "busyloop_clean": round(clean_rate, 1),
        "busyloop_kill": round(kill_rate, 1),
        "kill_overhead_normalized": round(overhead, 4),
        "max_overhead": max_overhead,
        "bit_identical": True,            # asserted above for every run
        "passed": overhead < max_overhead,
        "note": "same fixed yolov2 slice through the pool clean / with an "
                "injected worker death / SIGTERM-drained+resumed; all "
                "completed runs asserted byte-identical (cuts, metrics, "
                "evaluated); overhead is busy-loop-normalized",
    }
    if record["passed"]:
        print(f"chaos gate OK: kill overhead "
              f"{100 * overhead:.1f}% < {100 * max_overhead:.0f}%")
    else:
        record["fail_msg"] = (
            f"chaos overhead gate: worker-kill run cost "
            f"{100 * overhead:.1f}% over the clean floor "
            f"(limit {100 * max_overhead:.0f}%; clean {clean_wall:.1f}s @ "
            f"{clean_rate:.0f} ops/s vs kill {kill_wall:.1f}s @ "
            f"{kill_rate:.0f} ops/s)")
    return record


def bench_prune(name: str = "yolov2", size: int = 416,
                workers: int = 2) -> dict:
    """Branch-and-bound pruning benchmark on the FULL detector cut space
    (the ISSUE 8 acceptance scenario): yolov2's 7.96M tuples searched
    unpruned and pruned at ``--workers`` worker processes, asserting the
    results byte-identical (cuts, metrics and -- under the default
    ``count_pruned`` -- ``evaluated``), then a third *healed* pruned run
    with an injected worker death mid-space, also byte-identical.  The
    record lands in BENCH_compile.json: ``pruned`` / ``pruned_fraction``
    (how much of the space the admissible bound eliminated before any
    replay), the busy-loop-normalized wall-clock speedup, and the healed
    run's search rate (``healed_evals_per_sec``)."""
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    space = 1
    for r in runs:
        space *= len(r) + 1

    def run(tag, prune, injector=None):
        if injector is not None:
            chaos.install(injector)
        try:
            rate = measure_busyloop_rate()
            t0 = time.perf_counter()
            with ParallelSearchDriver(workers=workers,
                                      mp_context="fork") as d:
                res = d.search(gg, KCU1500, CompileOptions(prune=prune))
            wall = time.perf_counter() - t0
        finally:
            if injector is not None:
                chaos.uninstall()
        print(f"prune bench {tag}: {wall:.1f}s busyloop={rate:.0f}/s "
              f"pruned={res.pruned}")
        return res, wall, rate

    unp, unp_wall, unp_rate = run("unpruned", False)
    prn, prn_wall, prn_rate = run("pruned", True)
    for f in METRICS:
        assert getattr(prn.best, f) == getattr(unp.best, f), f
    assert prn.best.cuts == unp.best.cuts
    assert prn.evaluated == unp.evaluated      # count_pruned default
    assert unp.pruned == 0 and prn.pruned > 0

    # healed pruned run: hard-kill the worker on the mid-space task's
    # first attempt; the pool heals, re-dispatches, and must still merge
    # to the identical result with pruning active
    prefixes, _sd = partition_space(runs, workers * TASKS_PER_WORKER)
    doomed = prefixes[len(prefixes) // 2]
    inj = chaos.ChaosInjector(
        events={("task", doomed): chaos.ChaosEvent("kill")})
    healed, healed_wall, healed_rate = run("healed", True, injector=inj)
    assert healed.best.cuts == prn.best.cuts
    assert healed.evaluated == prn.evaluated
    assert any(e.kind == "retry" for e in healed.events)

    speedup = (unp_wall * unp_rate) / (prn_wall * prn_rate)
    record = {
        "network": f"{name}@{size}",
        "workers": workers,
        "search_space": space,
        "pruned": prn.pruned,
        "pruned_fraction": round(prn.pruned / space, 4),
        "unpruned_wall_s": round(unp_wall, 2),
        "pruned_wall_s": round(prn_wall, 2),
        "speedup_normalized": round(speedup, 2),
        "busyloop_unpruned": round(unp_rate, 1),
        "busyloop_pruned": round(prn_rate, 1),
        "healed_wall_s": round(healed_wall, 2),
        "healed_evals_per_sec": round(healed.evaluated / healed_wall, 1),
        "healed_pruned": healed.pruned,
        "healed_bit_identical": True,          # asserted above
        "note": "full cut space searched unpruned vs branch-and-bound "
                "pruned (argmin, metrics, evaluated asserted identical); "
                "speedup is busy-loop-normalized; healed run repeats the "
                "pruned search through an injected worker death",
    }
    print(f"prune bench: {100 * record['pruned_fraction']:.1f}% of "
          f"{space} tuples pruned, {speedup:.2f}x normalized speedup, "
          f"healed rate {record['healed_evals_per_sec']:.0f} evals/s")
    return record


def smoke_prune_gate() -> dict:
    """CI gate for branch-and-bound pruning: on resnet50 the pruned
    serial search must (a) return the byte-identical SearchResult of the
    unpruned search, (b) eliminate at least half the cut space (measured
    share on this net is ~0.8), and (c) win on busy-loop-normalized wall
    clock by >=1.3x (measured ~4-5x; the floor leaves room for CI
    weather without letting the bound rot into a no-op)."""
    gg = group_nodes(build_cnn("resnet50", 224))
    rate_u = measure_busyloop_rate()
    t0 = time.perf_counter()
    unp = search(gg, KCU1500, CompileOptions(prune=False))
    unp_wall = time.perf_counter() - t0
    rate_p = measure_busyloop_rate()
    t0 = time.perf_counter()
    prn = search(gg, KCU1500)
    prn_wall = time.perf_counter() - t0
    assert prn.best.cuts == unp.best.cuts
    for f in METRICS:
        assert getattr(prn.best, f) == getattr(unp.best, f), f
    assert prn.evaluated == unp.evaluated
    fraction = prn.pruned / unp.evaluated
    speedup = (unp_wall * rate_u) / (prn_wall * rate_p)
    record = {
        "network": "resnet50@224",
        "pruned_fraction": round(fraction, 4),
        "unpruned_wall_s": round(unp_wall, 3),
        "pruned_wall_s": round(prn_wall, 3),
        "speedup_normalized": round(speedup, 2),
        "min_fraction": 0.5,
        "min_speedup": 1.3,
        "bit_identical": True,                 # asserted above
        "passed": fraction >= 0.5 and speedup >= 1.3,
    }
    if record["passed"]:
        print(f"prune gate OK: {100 * fraction:.1f}% pruned, "
              f"{speedup:.2f}x normalized speedup")
    else:
        record["fail_msg"] = (
            f"prune gate: {100 * fraction:.1f}% pruned (need >=50%) at "
            f"{speedup:.2f}x normalized speedup (need >=1.3x) -- the "
            f"bound stopped eliminating work")
    return record


def bench_network(name: str, size: int, budget_s: float,
                  check_equiv: bool = False,
                  compile_workers: int = 1) -> dict:
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    space = 1
    for r in runs:
        space *= len(r) + 1

    # direct oracle throughput
    n_direct = 0
    t0 = time.perf_counter()
    for cuts in _product_tuples(runs):
        evaluate(gg, blocks, runs, cuts, KCU1500)
        n_direct += 1
        if time.perf_counter() - t0 > budget_s:
            break
    direct_eps = n_direct / (time.perf_counter() - t0)

    # incremental engine throughput over the same enumeration order
    engine = CutpointEngine(gg, KCU1500, blocks, runs)
    n_engine = 0
    t0 = time.perf_counter()
    for cuts in _product_tuples(runs):
        engine.evaluate(cuts, memoize=False)    # as the exhaustive search does
        n_engine += 1
        if n_engine % 256 == 0 and time.perf_counter() - t0 > budget_s:
            break
    engine_eps = n_engine / (time.perf_counter() - t0)

    # batched scorer throughput over the same enumeration order (the
    # production search inner loop since the mask-matrix scorer landed)
    engine_b = CutpointEngine(gg, KCU1500, blocks, runs)
    n_batched = 0
    it = _product_tuples(runs)
    t0 = time.perf_counter()
    while True:
        chunk = list(itertools.islice(it, DEFAULT_BATCH_SIZE))
        if not chunk:
            break
        engine_b.score_batch(chunk, memoize=False)
        n_batched += len(chunk)
        if time.perf_counter() - t0 > budget_s:
            break
    batched_eps = n_batched / (time.perf_counter() - t0)

    if check_equiv:
        fresh = CutpointEngine(gg, KCU1500, blocks, runs)
        fresh_b = CutpointEngine(gg, KCU1500, blocks, runs)
        fresh_d = CutpointEngine(gg, KCU1500, blocks, runs,
                                 engine="device")
        sample = list(itertools.islice(_product_tuples(runs), 10))
        for cuts, m_b, m_d in zip(sample,
                                  fresh_b.score_batch(sample,
                                                      memoize=False),
                                  fresh_d.score_batch(sample,
                                                      memoize=False)):
            o = evaluate(gg, blocks, runs, cuts, KCU1500)
            m = fresh.evaluate(cuts)
            for f in METRICS:
                assert getattr(o, f) == getattr(m, f), (name, cuts, f)
                assert getattr(o, f) == getattr(m_b, f), (name, cuts, f)
                assert getattr(o, f) == getattr(m_d, f), (name, cuts, f)

    # end-to-end compile (grouping + search + instruction generation)
    graph = build_cnn(name, size)
    t0 = time.perf_counter()
    plan = compile_graph(graph, KCU1500,
                         CompileOptions(workers=compile_workers))
    compile_s = time.perf_counter() - t0

    row = {
        "groups": len(gg.groups), "blocks": len(blocks), "runs": len(runs),
        "search_space": space,
        "direct_evals_per_sec": round(direct_eps, 1),
        "engine_evals_per_sec": round(engine_eps, 1),
        "batched_evals_per_sec": round(batched_eps, 1),
        "speedup": round(engine_eps / direct_eps, 2),
        "batched_speedup_vs_engine": round(batched_eps / engine_eps, 2),
        "compile_wall_s": round(compile_s, 3),
        "search_evaluations": plan.search.evaluated if plan.search else 0,
    }
    print(f"{name}: space={space} direct={direct_eps:.0f}/s "
          f"engine={engine_eps:.0f}/s batched={batched_eps:.0f}/s "
          f"speedup={row['speedup']}x compile={compile_s:.2f}s")
    return row


def smoke_batched_gate(results: dict, committed_path: Path) -> dict:
    """Benchmark-regression gate: the batched scorer's measured evals/sec
    must stay within ``max_regression`` of the committed floor, after
    normalizing by the busy-loop calibration ratio (so the gate compares
    scorer efficiency, not machine speed).  Returns the gate record that
    lands in BENCH_smoke.json; a failure is reported via
    ``record["passed"]``/``record["fail_msg"]`` and raised by the caller
    only *after* the artifact is written (the diagnostic JSON must
    survive the exact failure it exists to explain)."""
    rate = measure_busyloop_rate()
    floor = None
    if committed_path.exists():
        floor = json.loads(committed_path.read_text()).get("smoke_floor")
    record: dict = {
        "busyloop_ops_per_sec": round(rate, 1),
        "measured": {n: r["batched_evals_per_sec"]
                     for n, r in results.items()},
    }
    if not floor:
        print("smoke gate: no committed smoke_floor -- measuring only")
        return record
    net = floor["network"]
    if net not in results:
        print(f"smoke gate: committed floor network {net!r} not among the "
              f"smoke networks -- measuring only (keep SMOKE_ZOO and the "
              f"committed floor in sync)")
        record["floor_network_missing"] = net
        return record
    measured = results[net]["batched_evals_per_sec"]
    speed = rate / floor["busyloop_ops_per_sec"]
    need = floor["batched_evals_per_sec"] * speed * (1 - floor["max_regression"])
    record.update({
        "floor_network": net,
        "floor_evals_per_sec": floor["batched_evals_per_sec"],
        "machine_speed_vs_floor": round(speed, 3),
        "required_evals_per_sec": round(need, 1),
        "passed": measured >= need,
    })
    if measured >= need:
        print(f"batched gate OK: {net} {measured:.0f} evals/s >= "
              f"{need:.0f} required (machine speed {speed:.2f}x vs floor)")
    else:
        record["fail_msg"] = (
            f"batched-scorer regression gate: {net} measured "
            f"{measured:.0f} evals/s < required {need:.0f} (committed "
            f"floor {floor['batched_evals_per_sec']:.0f} x machine speed "
            f"{speed:.2f} x {1 - floor['max_regression']:.2f})")
    return record


def smoke_parallel_gate() -> None:
    """CI gate for the search pool: parallel search must reproduce the
    serial SearchResult exactly (metrics, winning tuple, evaluation
    count) on a real network whose space is actually partitioned."""
    gg = group_nodes(build_cnn("resnet50", 224))
    serial = search(gg, KCU1500)
    parallel = search(gg, KCU1500, CompileOptions(workers=2))
    assert serial.best.cuts == parallel.best.cuts
    for f in METRICS:
        assert getattr(serial.best, f) == getattr(parallel.best, f), f
    assert serial.evaluated == parallel.evaluated
    print(f"parallel smoke OK: {parallel.evaluated} evals, "
          f"cuts={parallel.best.cuts}")


def smoke_verify_gate() -> dict:
    """CI gate for the static verifier's compile-time cost: the one
    ``verify_execution_plan`` pass that ``verify="warn"`` appends to
    ``compile_graph`` must cost <5% of the compile wall itself.  The
    verify pass is timed directly on the compiled plan (best of 5)
    against the best-of-3 compile wall -- differencing two full compile
    runs was tried first and is too noisy: compile-to-compile wall
    variance on a shared CI core exceeds the ~0.5% true cost, so the
    gate flaked on machine weather rather than on regressions.  The
    busy-loop rate is recorded in the artifact for cross-run
    comparability."""
    from repro.analysis import verify_execution_plan

    g = build_cnn("resnet50", 224)
    rate = measure_busyloop_rate()

    plan = None
    compile_walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        plan = compile_graph(g, options=CompileOptions(exhaustive_limit=50_000))
        compile_walls.append(time.perf_counter() - t0)
    verify_walls = []
    for _ in range(5):
        t0 = time.perf_counter()
        diags = verify_execution_plan(plan)
        verify_walls.append(time.perf_counter() - t0)
        assert not [d for d in diags if d.severity.value == "error"]
    wall_compile, wall_verify = min(compile_walls), min(verify_walls)
    overhead = wall_verify / wall_compile
    record = {
        "network": "resnet50@224",
        "busyloop_ops_per_sec": round(rate, 1),
        "wall_compile_s": round(wall_compile, 3),
        "wall_verify_s": round(wall_verify, 4),
        "normalized_overhead": round(overhead, 4),
        "max_overhead": 0.05,
        "passed": overhead < 0.05,
    }
    if record["passed"]:
        print(f"verify gate OK: warn-mode verify pass costs "
              f"{100 * overhead:.2f}% of the compile wall (< 5%)")
    else:
        record["fail_msg"] = (
            f"verify overhead gate: the verify pass costs "
            f"{100 * overhead:.2f}% of the compile wall (limit 5%); "
            f"compile {wall_compile:.3f}s, verify {wall_verify:.4f}s")
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short CI run: 2 networks, equivalence + parallel "
                         "bit-identity asserted, no JSON written")
    ap.add_argument("--workers", type=int, default=os.cpu_count() or 1,
                    help="worker processes for the end-to-end compiles "
                         "(default: all cores)")
    ap.add_argument("--sweep-only", action="store_true",
                    help="re-measure only the workers sweep and splice it "
                         "into the existing output JSON (the per-network "
                         "table takes ~20 min; the sweep ~5)")
    ap.add_argument("--alloc-only", action="store_true",
                    help="re-measure only the allocator-replay comparison "
                         "and splice it into the existing output JSON")
    ap.add_argument("--pipeline-only", action="store_true",
                    help="re-measure only the fused-pipeline slice and "
                         "its smoke floor and splice them into the "
                         "existing output JSON")
    ap.add_argument("--prune-only", action="store_true",
                    help="re-measure only the branch-and-bound pruning "
                         "benchmark (full yolov2 space, pruned vs unpruned "
                         "vs kill-healed at 2 workers) and splice it into "
                         "the existing output JSON")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-tolerance benchmark+gate on the yolov2 "
                         "slice (clean / worker-kill / SIGTERM-drain+"
                         "resume, bit-identity asserted, <15%% normalized "
                         "overhead); writes BENCH_chaos.json and runs "
                         "INSTEAD of the throughput benches (combine with "
                         "--smoke for the CI-sized slice)")
    ap.add_argument("-o", "--output", default="BENCH_compile.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.chaos:
        if "fork" not in _mp.get_all_start_methods():
            print("chaos bench requires the fork start method (workers "
                  "must inherit the parent-installed injector); skipping")
            return
        record = bench_chaos(n_tasks=12 if args.smoke else 24)
        out = Path("BENCH_chaos.json")
        out.write_text(json.dumps(record, indent=2) + "\n")
        print(f"wrote {out}")
        # raised only now, after the diagnostic artifact is on disk
        assert record["passed"], record["fail_msg"]
        return

    if args.sweep_only:
        payload = json.loads(Path(args.output).read_text())
        payload["workers_sweep"] = bench_workers_sweep(
            "yolov2", 416, worker_counts=[1, 2, 4, 8])
        Path(args.output).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"updated workers_sweep in {args.output}")
        return

    if args.alloc_only:
        payload = json.loads(Path(args.output).read_text())
        payload["alloc_replay"] = bench_alloc_replay("yolov2", 416)
        Path(args.output).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"updated alloc_replay in {args.output}")
        return

    if args.pipeline_only:
        payload = json.loads(Path(args.output).read_text())
        payload["pipeline_slice"] = bench_pipeline_slice("yolov2", 416)
        gate = smoke_pipeline_gate(None)              # measure, no gate
        payload["pipeline_floor"] = {
            "network": gate["network"],
            "pipeline_evals_per_sec": gate["pipeline_evals_per_sec"],
            "busyloop_ops_per_sec": gate["busyloop_ops_per_sec"],
            "max_regression": 0.30,
        }
        Path(args.output).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"updated pipeline_slice + pipeline_floor in {args.output}")
        return

    if args.prune_only:
        if "fork" not in _mp.get_all_start_methods():
            print("prune bench requires the fork start method (the healed "
                  "run's injector must reach workers); skipping")
            return
        payload = json.loads(Path(args.output).read_text())
        payload["prune"] = bench_prune("yolov2", 416)
        Path(args.output).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"updated prune in {args.output}")
        return

    zoo = SMOKE_ZOO if args.smoke else ZOO
    budget = 0.4 if args.smoke else 3.0
    results = {}
    for name, size in zoo:
        results[f"{name}@{size}"] = bench_network(
            name, size, budget, check_equiv=args.smoke,
            compile_workers=1 if args.smoke else args.workers)

    if args.smoke:
        worst = min(r["speedup"] for r in results.values())
        # regression gate: the engine must stay clearly ahead of the direct
        # oracle even on small graphs / loaded CI machines (real margin on
        # an idle machine is 3-20x)
        assert worst > 1.5, f"engine speedup regressed to {worst}x"
        print(f"smoke OK: min speedup {worst}x")
        committed = Path(__file__).resolve().parent.parent / args.output
        gate = smoke_batched_gate(results, committed)
        smoke_parallel_gate()
        verify_gate = smoke_verify_gate()
        prune_gate = smoke_prune_gate()
        pipeline_gate = smoke_pipeline_gate(committed)
        smoke_out = Path("BENCH_smoke.json")
        smoke_out.write_text(json.dumps(
            {"networks": results, "batched_gate": gate,
             "verify_gate": verify_gate, "prune_gate": prune_gate,
             "pipeline_gate": pipeline_gate},
            indent=2) + "\n")
        print(f"wrote {smoke_out} (CI artifact; committed JSON untouched)")
        # raised only now, after the diagnostic artifacts are on disk
        assert gate.get("passed", True), gate["fail_msg"]
        assert verify_gate["passed"], verify_gate["fail_msg"]
        assert prune_gate["passed"], prune_gate["fail_msg"]
        assert pipeline_gate.get("passed", True), pipeline_gate["fail_msg"]
        return

    sweep = bench_workers_sweep("yolov2", 416, worker_counts=[1, 2, 4, 8])
    batched_slice = bench_batched_slice("yolov2", 416)
    alloc_replay = bench_alloc_replay("yolov2", 416)
    pipeline_slice = bench_pipeline_slice("yolov2", 416)
    pipe_gate = smoke_pipeline_gate(None)              # measure the floor
    pipeline_floor = {
        "network": pipe_gate["network"],
        "pipeline_evals_per_sec": pipe_gate["pipeline_evals_per_sec"],
        "busyloop_ops_per_sec": pipe_gate["busyloop_ops_per_sec"],
        "max_regression": 0.30,
    }
    prune = bench_prune("yolov2", 416) \
        if "fork" in _mp.get_all_start_methods() else None

    # the floor the CI smoke gate regresses against: the batched scorer's
    # rate on SMOKE_ZOO[1] (resnet50 -- the larger smoke network, whose
    # measurement window is the least noisy), next to this machine's
    # busy-loop calibration
    floor_net = f"{SMOKE_ZOO[1][0]}@{SMOKE_ZOO[1][1]}"
    smoke_floor = {
        "network": floor_net,
        "batched_evals_per_sec": results[floor_net]["batched_evals_per_sec"],
        "busyloop_ops_per_sec": round(measure_busyloop_rate(), 1),
        "max_regression": 0.30,
    }

    payload = {
        "hw": KCU1500.name,
        "note": "evals/sec over product-order cut enumeration; engine and "
                "batched scorer are oracle-exact "
                "(tests/test_cutpoint_engine.py, tests/test_score_batch.py) "
                "and parallel search is bit-identical to serial "
                "(tests/test_search_pool.py); per-network rows are "
                "single-shot and noisy on bursty container CPU -- "
                "batched_slice (interleaved best-of) is the robust "
                "batched-vs-per-tuple comparison",
        "compile_workers": args.workers,
        "networks": results,
        "batched_slice": batched_slice,
        "alloc_replay": alloc_replay,
        "pipeline_slice": pipeline_slice,
        "prune": prune,
        "smoke_floor": smoke_floor,
        "pipeline_floor": pipeline_floor,
        "workers_sweep": sweep,
    }
    Path(args.output).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {args.output}")


if __name__ == "__main__":
    main()
