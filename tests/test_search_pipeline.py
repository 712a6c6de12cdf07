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
def test_pipeline_subspace_objectives(objective):
    engine, runs = _engine()
    prefixes, suffix_dims = partition_space(runs, target_tasks=8)
    host = CutpointEngine(engine.gg, engine.hw, engine.blocks, engine.runs)
    variants = _jax_variants()
    want, _ = branch_bound_subspace(host, prefixes[0], suffix_dims,
                                    objective, prune=False)
    for variant in variants:
        got, _ = pipeline_subspace(engine, prefixes[0], suffix_dims,
                                   objective, batch_size=128,
                                   variant=variant)
        assert got.cuts == want.cuts, (objective, variant)
        for f in METRICS:
            assert getattr(got, f) == getattr(want, f), (objective, variant)


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
    """Beyond exhaustive_limit the pipeline engine's search falls back to
    the host-driven coordinate descent (score_batch under the journal
    replay) -- results and path must match the journal engine exactly."""
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
    engine (contiguous index ranges per device, deterministic merge).
    Subprocess because device count is fixed at first jax import."""
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
