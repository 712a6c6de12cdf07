"""Fused device search pipeline (kernels/search_pipeline.py): the
``engine="pipeline"`` contract.

Three layers of bit-identity, mirroring how the pipeline is built:

* **argmin_lanes** -- the hierarchical masked-minima reduction must pick
  the identical ``(key, index)`` winner as the host's stable lexsort on
  fuzzed batches stuffed with duplicated key components, under all three
  backends (numpy reference / traced lax / Pallas-interpret kernel);
* **pipeline_subspace** -- on real partitioned sub-spaces (prefix x
  suffix product) every variant must return the same
  ``(CandidateMetrics, pruned)`` as the host branch-and-bound walk, for
  every objective;
* **search(engine="pipeline")** -- end to end, serial and workers=2 and
  under a forced 2-device jax host, the SearchResult must be
  bit-identical to the journal engine's, ``evaluated`` included (the
  pipeline scores everything in-kernel and reports ``pruned=0``, which
  under the default ``count_pruned=True`` reproduces the journal count).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.cnn import build_cnn
from repro.core.cutpoint import (CutpointEngine, branch_bound_subspace,
                                 monotone_runs, search, split_blocks)
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500
from repro.core.options import CompileOptions
from repro.core.search_pool import partition_space
from repro.kernels.search_pipeline import (OBJECTIVES, VARIANTS,
                                           argmin_lanes, pipeline_subspace)
from repro.kernels.score_batch import HAVE_JAX

from test_search_pool import (METRICS, TEST_LIMIT, assert_results_identical)

TEST_OPTS = CompileOptions(exhaustive_limit=TEST_LIMIT)

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not importable")


def _jax_variants():
    return [v for v in VARIANTS if v == "reference" or HAVE_JAX]


# ------------------------------------------------------------ argmin fuzz
def _host_winner(infeas, primary, secondary, idx):
    """The oracle: stable lexicographic first-minimum."""
    order = np.lexsort((idx, secondary, primary, infeas))
    j = int(order[0])
    return (float(infeas[j]), float(primary[j]), float(secondary[j]),
            int(idx[j]))


def _fuzz_lanes(rng, n):
    """Key batches designed to tie: every component is drawn from a tiny
    value set, so duplicated full keys are the common case and only the
    index tie-break separates winners."""
    infeas = rng.choice([0.0, 1.0], size=n)
    primary = rng.choice([3.0, 7.0, 7.0, 11.0, 1e9], size=n)
    secondary = rng.choice([2.0, 5.0, 5.0, 123456.0], size=n)
    idx = rng.permutation(10 * n)[:n].astype(np.float64)
    return infeas, primary, secondary, idx


@pytest.mark.parametrize("backend", ["reference", "lax", "pallas"])
def test_argmin_lanes_fuzzed_duplicate_keys(backend):
    if backend != "reference" and not HAVE_JAX:
        pytest.skip("jax not importable")
    rng = np.random.default_rng(20260808)
    trials = 60 if backend != "pallas" else 12
    for t in range(trials):
        n = int(rng.integers(1, 300))
        lanes = _fuzz_lanes(rng, n)
        assert argmin_lanes(*lanes, backend=backend) \
            == _host_winner(*lanes), (backend, t, n)


@pytest.mark.parametrize("backend", ["reference", "lax", "pallas"])
def test_argmin_lanes_all_infeasible_and_singleton(backend):
    if backend != "reference" and not HAVE_JAX:
        pytest.skip("jax not importable")
    # all-infeasible batch: the winner is still the best infeasible lane
    lanes = (np.ones(7), np.arange(7.0, 0.0, -1.0),
             np.zeros(7), np.arange(7.0))
    assert argmin_lanes(*lanes, backend=backend) == (1.0, 1.0, 0.0, 6)
    # singleton batch
    lanes = (np.array([0.0]), np.array([42.0]),
             np.array([9.0]), np.array([3.0]))
    assert argmin_lanes(*lanes, backend=backend) == (0.0, 42.0, 9.0, 3)


def test_argmin_lanes_duplicated_key_takes_smallest_index():
    # four lanes with the identical winning key: index decides, exactly
    # as the host merge tie-breaks equal-key candidates by cut tuple
    infeas = np.array([1.0, 0.0, 0.0, 0.0, 0.0])
    primary = np.array([0.0, 5.0, 5.0, 5.0, 6.0])
    secondary = np.array([0.0, 2.0, 2.0, 2.0, 1.0])
    idx = np.array([0.0, 17.0, 4.0, 9.0, 1.0])
    for backend in ["reference"] + (["lax", "pallas"] if HAVE_JAX else []):
        assert argmin_lanes(infeas, primary, secondary, idx,
                            backend=backend) == (0.0, 5.0, 2.0, 4), backend


def test_pallas_variant_refuses_f64_stages_on_tpu(monkeypatch):
    """Mosaic has no float64: on a TPU the pallas variant's f64 cost and
    argmin stages raise instead of running the Pallas interpreter on the
    chip."""
    import repro.kernels.search_pipeline as sp
    monkeypatch.setattr(sp, "_on_tpu", lambda: True)
    engine, runs = _engine("vgg16-conv")
    with pytest.raises(NotImplementedError, match="cost stage"):
        pipeline_subspace(engine, (), [len(r) for r in runs], "latency",
                          variant="pallas")
    with pytest.raises(NotImplementedError, match="argmin stage"):
        argmin_lanes(np.zeros(3), np.zeros(3), np.zeros(3), np.arange(3.0),
                     backend="pallas")


def test_argmin_lanes_rejects_bad_input():
    with pytest.raises(ValueError, match="equal-length"):
        argmin_lanes(np.zeros(3), np.zeros(2), np.zeros(3), np.zeros(3))
    with pytest.raises(ValueError, match="backend"):
        argmin_lanes(np.zeros(3), np.zeros(3), np.zeros(3), np.zeros(3),
                     backend="cuda")


# ------------------------------------------------- sub-space bit-identity
def _engine(name="resnet50", size=224):
    gg = group_nodes(build_cnn(name, size))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    return CutpointEngine(gg, KCU1500, blocks, runs), runs


@pytest.mark.parametrize("variant", VARIANTS)
def test_pipeline_subspace_matches_branch_bound(variant):
    if variant != "reference" and not HAVE_JAX:
        pytest.skip("jax not importable")
    engine, runs = _engine()
    prefixes, suffix_dims = partition_space(runs, target_tasks=8)
    host = CutpointEngine(engine.gg, engine.hw, engine.blocks, engine.runs)
    for prefix in prefixes[:3]:
        want, _pruned = branch_bound_subspace(host, prefix, suffix_dims,
                                              "latency", prune=False)
        got, pruned = pipeline_subspace(engine, prefix, suffix_dims,
                                        "latency", batch_size=256,
                                        variant=variant)
        assert pruned == 0
        assert got.cuts == want.cuts, (variant, prefix)
        for f in METRICS:
            assert getattr(got, f) == getattr(want, f), (variant, prefix, f)


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_pipeline_subspace_objectives(objective, monkeypatch):
    """Every variant returns the journal's plan, from the same winner
    row as the numpy reference (the lax variant's from its device-held
    tables, two sub-spaces on one engine)."""
    import repro.kernels.search_pipeline as sp
    engine, runs = _engine()
    prefixes, suffix_dims = partition_space(runs, target_tasks=8)
    host = CutpointEngine(engine.gg, engine.hw, engine.blocks, engine.runs)
    variants = _jax_variants()
    rows = {}
    for variant in variants:
        run = getattr(sp, f"_run_{variant}")

        def recorded(*args, run=run, variant=variant):
            best = run(*args)
            rows.setdefault(variant, []).append(best)
            return best
        monkeypatch.setattr(sp, f"_run_{variant}", recorded)
    for prefix in prefixes[:2]:
        want, _ = branch_bound_subspace(host, prefix, suffix_dims,
                                        objective, prune=False)
        for variant in variants:
            got, _ = pipeline_subspace(engine, prefix, suffix_dims,
                                       objective, batch_size=128,
                                       variant=variant)
            assert got.cuts == want.cuts, (objective, variant)
            for f in METRICS:
                assert getattr(got, f) == getattr(want, f), (objective,
                                                             variant)
    for variant in variants:
        assert rows[variant] == rows["reference"], (objective, variant)


def _device_operands(engine):
    """The lax step for ``engine``'s whole space on one device, with its
    host operands and the device copies a launch passes instead."""
    import jax
    import repro.kernels.search_pipeline as sp
    tbl = sp._engine_tables(engine)
    dims = tuple(len(r) + 1 for r in engine.runs)
    fused = sp._make_fused(tbl, 128, 0, dims, sp._space_strides(dims),
                           int(np.prod(dims)), "latency")
    host = sp._lax_args(tbl, ())
    _mesh, placement, tables = sp._device_tables(engine, host[1:], 1)
    assert placement is None
    return fused, host, (jax.device_put(host[0]),) + tables


@needs_jax
def test_device_operands_lower_like_host_operands():
    """One device: the operands stay uncommitted, so the jitted step
    lowers to the same program as with host arrays."""
    import jax
    engine, _ = _engine()
    with jax.enable_x64(True):
        fused, host, dev = _device_operands(engine)
        for a in jax.tree.leaves(dev):
            assert isinstance(a, jax.Array) and not a.committed
        lo = np.int32(0)
        assert (jax.jit(fused).lower(lo, *dev).as_text()
                == jax.jit(fused).lower(lo, *host).as_text())


@needs_jax
def test_device_operands_keep_host_dtypes_and_values():
    """Put on the device inside the x64 scope: f64 cost tables stay
    f64, and every copy holds its host table's values."""
    import jax
    engine, _ = _engine()
    with jax.enable_x64(True):
        _, host, dev = _device_operands(engine)
    pairs = list(zip(jax.tree.leaves(host), jax.tree.leaves(dev)))
    assert len(pairs) == 26
    assert any(h.dtype == np.float64 for h, _ in pairs)
    for h, d in pairs:
        assert d.dtype == h.dtype and d.shape == h.shape
        assert np.array_equal(np.asarray(d), h)


@needs_jax
def test_tables_upload_once_per_engine(tmp_path):
    """Under the profiler, an engine's first sub-space opens the one
    ``pipeline.upload`` span; later sub-spaces reuse the device copies,
    and a new engine uploads its own."""
    import jax
    from repro.utils import trace
    engine, runs = _engine()
    prefixes, suffix_dims = partition_space(runs, target_tasks=8)
    trace.clear()
    try:
        with jax.profiler.trace(str(tmp_path)):
            for prefix in prefixes[:5]:
                pipeline_subspace(engine, prefix, suffix_dims, "latency",
                                  batch_size=512, variant="lax")
            names = [r.name for r in trace.records()]
            assert names.count("pipeline.upload") == 1
            assert names.count("pipeline.subspace") == 5
            fresh, _ = _engine()
            pipeline_subspace(fresh, prefixes[0], suffix_dims, "latency",
                              batch_size=512, variant="lax")
            names = [r.name for r in trace.records()]
    finally:
        trace.clear()
    assert names.count("pipeline.upload") == 2
    assert list(engine._pipeline_operands) == [1]


def test_pipeline_subspace_counts_full_enumeration():
    """``evaluations`` is credited with the whole sub-space S, matching
    the journal path's scored+pruned accounting."""
    engine, runs = _engine()
    prefixes, suffix_dims = partition_space(runs, target_tasks=8)
    S = 1
    for d in suffix_dims:
        S *= d + 1
    before = engine.evaluations
    pipeline_subspace(engine, prefixes[0], suffix_dims, "latency",
                      variant="reference")
    assert engine.evaluations == before + S


def test_pipeline_subspace_singleton_space():
    """A fully-pinned sub-space (every dim 0) short-circuits to the one
    candidate, still crediting one evaluation."""
    engine, runs = _engine()
    cuts = tuple(0 for _ in runs)
    before = engine.evaluations
    m, pruned = pipeline_subspace(engine, cuts, [], "latency")
    assert pruned == 0 and m.cuts == cuts
    assert engine.evaluations == before + 1


def test_pipeline_subspace_validates_arguments():
    engine, runs = _engine()
    with pytest.raises(ValueError, match="objective"):
        pipeline_subspace(engine, (), [len(r) for r in runs], "bogus")
    with pytest.raises(ValueError, match="variant"):
        pipeline_subspace(engine, (), [len(r) for r in runs], "latency",
                          variant="cuda")
    with pytest.raises(ValueError, match="cover all"):
        pipeline_subspace(engine, (0,), [len(r) for r in runs], "latency",
                          variant="reference")


# -------------------------------------------------- end-to-end bit-identity
@pytest.mark.parametrize("variant", VARIANTS)
def test_search_pipeline_matches_journal_exhaustive(variant):
    """resnet50's 8748-tuple space, enumerated exhaustively: every
    pipeline variant returns the journal SearchResult byte-for-byte,
    ``evaluated`` and ``path`` included."""
    if variant != "reference" and not HAVE_JAX:
        pytest.skip("jax not importable")
    gg = group_nodes(build_cnn("resnet50"))
    journal = search(gg, KCU1500, TEST_OPTS)
    piped = search(gg, KCU1500,
                   TEST_OPTS.replace(engine=f"pipeline:{variant}"))
    assert_results_identical(journal, piped, ctx=f"pipeline:{variant}")
    assert piped.path == journal.path == "exhaustive"
    assert piped.pruned == 0


def test_search_pipeline_parallel_matches_serial_journal():
    """workers=2: disjoint sub-spaces each fused on device, merged with
    the deterministic (key, cuts) order -- still journal-identical."""
    gg = group_nodes(build_cnn("resnet50"))
    journal = search(gg, KCU1500, TEST_OPTS)
    piped = search(gg, KCU1500,
                   TEST_OPTS.replace(engine="pipeline", workers=2))
    assert_results_identical(journal, piped, ctx="pipeline-workers2")


def test_search_pipeline_descent_path_matches_journal():
    """On the CPU, beyond the exhaustive limit, the pipeline engine's
    search falls back to the host-driven coordinate descent (score_batch
    under the journal replay) -- results and path must match the journal
    engine exactly."""
    gg = group_nodes(build_cnn("mobilenet-v3"))
    journal = search(gg, KCU1500, TEST_OPTS)
    piped = search(gg, KCU1500, TEST_OPTS.replace(engine="pipeline"))
    assert journal.path == piped.path == "descent"
    assert_results_identical(journal, piped, ctx="pipeline-descent")


def test_search_pipeline_batch_suffix():
    """An @batch engine suffix only changes chunking, never the result."""
    gg = group_nodes(build_cnn("vgg16-conv"))
    journal = search(gg, KCU1500, TEST_OPTS)
    for spelling in ("pipeline:reference@64", "pipeline:reference@4096"):
        piped = search(gg, KCU1500, TEST_OPTS.replace(engine=spelling))
        assert_results_identical(journal, piped, ctx=spelling)


@needs_jax
def test_search_pipeline_sharded_two_devices():
    """The shard_map path: a subprocess forced to expose two host
    devices must produce the identical SearchResult as the journal
    engine (contiguous index ranges per device, deterministic merge),
    with the tables replicated on both devices.  Subprocess because
    device count is fixed at first jax import."""
    code = """
import jax
assert jax.device_count() == 2, jax.devices()
from repro.cnn import build_cnn
from repro.core.cutpoint import search
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500
from repro.core.options import CompileOptions
gg = group_nodes(build_cnn("resnet50"))
opts = CompileOptions(exhaustive_limit=200_000)
journal = search(gg, KCU1500, opts)
piped = search(gg, KCU1500, opts.replace(engine="pipeline:lax"))
assert piped.best.cuts == journal.best.cuts
for f in ("latency_cycles", "dram_total", "dram_fm", "sram_total",
          "bram18k", "feasible"):
    assert getattr(piped.best, f) == getattr(journal.best, f), f
assert piped.evaluated == journal.evaluated
# the tables go up once, replicated over both devices, and a sub-space
# gives the one-device reference's plan
from repro.core.cutpoint import CutpointEngine
from repro.kernels.search_pipeline import pipeline_subspace
engine = CutpointEngine(gg, KCU1500, engine="pipeline:lax")
dims = [len(r) for r in engine.runs]
got, _ = pipeline_subspace(engine, (), dims, "dram", variant="lax")
want, _ = pipeline_subspace(engine, (), dims, "dram", variant="reference")
assert got.cuts == want.cuts and got.dram_total == want.dram_total
[(ndev, (mesh, placement, tables))] = engine._pipeline_operands.items()
assert ndev == 2 and mesh.size == 2
for a in jax.tree.leaves(tables):
    assert a.sharding == placement and a.sharding.is_fully_replicated
    assert len(a.sharding.device_set) == 2
print("SHARDED-OK", piped.evaluated)
"""
    env = dict(os.environ)
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=2"])
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(p) for p in sys.path if p] + [env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "SHARDED-OK" in out.stdout, out.stdout


@needs_jax
def test_parallel_pipeline_runs_in_process():
    """A chip belongs to one process, so a jax engine never enters the
    worker pool: a workers=2 search on pipeline:lax -- whole-space and
    over an explicit partition -- runs in the calling process and is
    journal-identical."""
    from repro.core.options import resolve_engine
    from repro.core.search_pool import (ParallelSearchDriver,
                                        _engine_needs_jax)

    for spelling in ("pipeline:lax", "pipeline:pallas", "device:scan",
                     "device:pallas"):
        assert _engine_needs_jax(resolve_engine(spelling)), spelling
    for spelling in ("journal", "pipeline:reference", "device"):
        assert not _engine_needs_jax(resolve_engine(spelling)), spelling

    gg = group_nodes(build_cnn("resnet50"))
    journal = search(gg, KCU1500, TEST_OPTS)
    opts = TEST_OPTS.replace(engine="pipeline:lax", workers=2)
    runs = monotone_runs(split_blocks(gg))
    prefixes, suffix_dims = partition_space(runs, target_tasks=16)
    with ParallelSearchDriver(workers=2) as d:
        whole = d.search(gg, KCU1500, opts)
        parts = d.run_subspaces(gg, KCU1500, prefixes, suffix_dims, opts)
        assert d._pool is None             # no worker process was started
    assert_results_identical(journal, whole, ctx="pipeline-in-process")
    assert_results_identical(journal, parts, ctx="pipeline-partitioned")
    assert not whole.events and not parts.events


# ------------------------------------------------------ pipelined launches
PIPELINED_CHUNK = 8       # vgg16-conv@224's 1,080 tuples: 135 launches


def _whole_space(engine):
    """``_run_lax``'s arguments after ``engine`` for its whole space."""
    import repro.kernels.search_pipeline as sp
    dims = tuple(len(r) + 1 for r in engine.runs)
    return (sp._engine_tables(engine), (), dims, sp._space_strides(dims),
            int(np.prod(dims)), PIPELINED_CHUNK)


@needs_jax
@pytest.mark.parametrize("objective", OBJECTIVES)
def test_lax_many_launches_match_reference(objective):
    """A sub-space of 135 pipelined launches on one device gives the
    numpy reference's winner row."""
    import jax
    import repro.kernels.search_pipeline as sp
    engine, _ = _engine("vgg16-conv")
    space = _whole_space(engine)
    assert space[4] // PIPELINED_CHUNK >= 100
    want = sp._run_reference(engine, *space, objective)
    with jax.enable_x64(True):
        got = sp._run_lax(engine, *space, objective)
    assert got == want, objective


@needs_jax
def test_lax_enqueues_every_launch_before_the_first_fold(monkeypatch):
    """The engine's jitted step is called for every launch of the
    sub-space, in ascending start order, before ``_fold`` sees a row;
    ``_fold`` then gets one row a launch in launch order, the order the
    benchmark's planted fold faults count on."""
    import jax
    import repro.kernels.search_pipeline as sp
    engine, _ = _engine("vgg16-conv")
    space = _whole_space(engine)
    S = space[4]
    with jax.enable_x64(True):
        want = sp._run_lax(engine, *space, "latency")
    [(key, (jfused, sharded))] = engine._pipeline_calls.items()
    assert sharded is None
    events = []

    def launch(lo, *args):
        out = jfused(lo, *args)
        events.append(("launch", int(lo), out))
        return out

    def fold(best, row, fold=sp._fold):
        events.append(("fold", tuple(float(x) for x in row)))
        return fold(best, row)
    engine._pipeline_calls[key] = (launch, sharded)
    monkeypatch.setattr(sp, "_fold", fold)
    with jax.enable_x64(True):
        got = sp._run_lax(engine, *space, "latency")
    assert got == want
    n = len(range(0, S, PIPELINED_CHUNK))
    assert [e[0] for e in events] == ["launch"] * n + ["fold"] * n
    launches, folds = events[:n], events[n:]
    assert [lo for _, lo, _ in launches] == list(range(0, S,
                                                       PIPELINED_CHUNK))
    assert [row for _, row in folds] == [
        tuple(float(x) for x in np.asarray(out)) for _, _, out in launches]


@needs_jax
def test_lax_pipelined_sharded_two_devices():
    """The same two checks with the step sharded over two host devices:
    every objective's winner row is the reference's, and every step is
    enqueued before the first fold, which then takes two rows a step
    (device order) in step order."""
    code = """
import jax
import numpy as np
assert jax.device_count() == 2, jax.devices()
from repro.cnn import build_cnn
from repro.core.cutpoint import CutpointEngine
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500
import repro.kernels.search_pipeline as sp
engine = CutpointEngine(group_nodes(build_cnn("vgg16-conv", 224)), KCU1500,
                        engine="pipeline:lax")
tbl = sp._engine_tables(engine)
dims = tuple(len(r) + 1 for r in engine.runs)
strides = sp._space_strides(dims)
S, chunk = int(np.prod(dims)), 8
space = (tbl, (), dims, strides, S, chunk)
for objective in sp.OBJECTIVES:
    want = sp._run_reference(engine, *space, objective)
    with jax.enable_x64(True):
        got = sp._run_lax(engine, *space, objective)
    assert got == want, (objective, got, want)
key = ("lax", chunk, 0, dims, "latency")
jfused, sharded = engine._pipeline_calls[key]
events = []
def launch(los, *args):
    out = sharded(los, *args)
    events.append(("launch", [int(x) for x in los], out))
    return out
def fold(best, row, fold=sp._fold):
    events.append(("fold", tuple(float(x) for x in row)))
    return fold(best, row)
engine._pipeline_calls[key] = (jfused, launch)
sp._fold = fold
with jax.enable_x64(True):
    got = sp._run_lax(engine, *space, "latency")
steps = len(range(0, S, 2 * chunk))
assert steps >= 50
assert [e[0] for e in events] == ["launch"] * steps + ["fold"] * (2 * steps)
assert [e[1] for e in events[:steps]] == [[b, b + chunk]
                                          for b in range(0, S, 2 * chunk)]
rows = [tuple(float(x) for x in r) for e in events[:steps]
        for r in np.asarray(e[2])]
assert [e[1] for e in events[steps:]] == rows
print("PIPELINED-SHARDED-OK", steps)
"""
    env = dict(os.environ)
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=2"])
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(p) for p in sys.path if p] + [env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "PIPELINED-SHARDED-OK" in out.stdout, out.stdout
