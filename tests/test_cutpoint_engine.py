"""Incremental cut-point engine: oracle contract + seed regression.

The engine (prefix-cached allocation + vectorized cost models) must return
bit-identical metrics to the direct ``evaluate`` oracle for every cut tuple,
and ``search`` must return exactly the candidates the seed implementation
found (same cuts, same metrics, bit-for-bit latencies)."""
import itertools
import random

import pytest

from repro.cnn import build_cnn
from repro.core.cutpoint import (CutpointEngine, evaluate, monotone_runs,
                                 search, split_blocks)
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500

ALL_CNNS = ["vgg16-conv", "yolov2", "yolov3", "resnet50", "resnet152",
            "efficientnet-b1", "retinanet", "mobilenet-v3"]

METRICS = ["latency_cycles", "dram_total", "dram_fm", "sram_total",
           "bram18k", "feasible"]


def _sample_tuples(runs, n_prefix=25, n_random=15, seed=0):
    """Deterministic mix of product-order (max prefix reuse) and random
    (worst-case restart) cut tuples."""
    dims = [range(len(r) + 1) for r in runs]
    tuples = list(itertools.islice(itertools.product(*dims), n_prefix))
    rng = random.Random(seed)
    tuples += [tuple(rng.randint(0, len(r)) for r in runs)
               for _ in range(n_random)]
    # extremes: all-row / all-frame encodings land on the space corners
    tuples.append(tuple(0 for _ in runs))
    tuples.append(tuple(len(r) for r in runs))
    return tuples


@pytest.mark.parametrize("name", ALL_CNNS)
def test_engine_matches_oracle(name):
    gg = group_nodes(build_cnn(name))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    engine = CutpointEngine(gg, KCU1500, blocks, runs)
    for cuts in _sample_tuples(runs):
        oracle = evaluate(gg, blocks, runs, cuts, KCU1500)
        fast = engine.evaluate(cuts)
        for f in METRICS:
            assert getattr(oracle, f) == getattr(fast, f), (
                f"{name} cuts={cuts}: {f} oracle={getattr(oracle, f)!r} "
                f"engine={getattr(fast, f)!r}")


def test_engine_cache_returns_identical_metrics():
    gg = group_nodes(build_cnn("resnet50", 224))
    engine = CutpointEngine(gg, KCU1500)
    cuts = tuple(0 for _ in engine.runs)
    first = engine.evaluate(cuts)
    n = engine.evaluations
    assert engine.evaluate(cuts) is first          # memoized
    assert engine.evaluations == n


def test_engine_repeated_unmemoized_tuple():
    """Re-evaluating the same tuple with memoize=False must replay, not
    crash on a missing checkpoint, and stay bit-identical."""
    gg = group_nodes(build_cnn("resnet50", 224))
    engine = CutpointEngine(gg, KCU1500)
    cuts = tuple(1 for _ in engine.runs)
    a = engine.evaluate(cuts, memoize=False)
    b = engine.evaluate(cuts, memoize=False)
    for f in METRICS:
        assert getattr(a, f) == getattr(b, f)


# Seed search() outputs, recorded from the direct (pre-engine)
# implementation at PR 1.  The engine must reproduce them exactly, on the
# CPU with default options: resnet50/resnet152 exercise the exhaustive path
# on a ResNet-style graph, efficientnet-b1/mobilenet-v3 the
# coordinate-descent fallback on SE-style graphs (on an accelerator the
# fused pipeline searches mobilenet-v3 exhaustively).
SEED_RESULTS = {
    ("resnet50", 224): dict(
        cuts=(5, 0, 2, 0, 2, 0, 1, 0), latency_cycles=2163251.1999999993,
        dram_total=25653440, dram_fm=150528, sram_total=5706728,
        bram18k=4352, feasible=True),
    ("resnet152", 224): dict(
        cuts=(5, 0, 2, 0, 2, 0, 1, 0), latency_cycles=4073779.2000000086,
        dram_total=60190912, dram_fm=150528, sram_total=5706728,
        bram18k=4352, feasible=True),
    ("efficientnet-b1", 256): dict(
        cuts=(0, 2, 1, 1, 0, 2, 1, 1, 0, 2, 1, 1, 0, 2, 1, 2, 1, 2, 1, 1,
              0, 2, 1, 2, 1, 2, 0),
        latency_cycles=818109.9999999995, dram_total=7913584,
        dram_fm=196608, sram_total=7040896, bram18k=4928, feasible=True),
    ("mobilenet-v3", 224): dict(
        cuts=(2, 0, 1, 0, 2, 1, 1, 0, 2, 1, 2, 1, 1, 0, 2, 0, 1, 1),
        latency_cycles=304965.0, dram_total=5599976, dram_fm=150528,
        sram_total=4523392, bram18k=3136, feasible=True),
}


@pytest.mark.parametrize("net,size", sorted(SEED_RESULTS))
def test_search_results_unchanged_from_seed(net, size):
    gg = group_nodes(build_cnn(net, size))
    best = search(gg, KCU1500).best
    expect = SEED_RESULTS[(net, size)]
    assert best.cuts == expect["cuts"]
    for f in METRICS:
        assert getattr(best, f) == expect[f], (
            f"{net}: {f} {getattr(best, f)!r} != seed {expect[f]!r}")


def test_search_best_is_true_argmin_on_exhaustive_space():
    """The exhaustive path must return the strict product-order argmin."""
    gg = group_nodes(build_cnn("vgg16-conv", 224))
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    result = search(gg, KCU1500)
    dims = [range(len(r) + 1) for r in runs]
    best = None
    for cuts in itertools.product(*dims):
        c = evaluate(gg, blocks, runs, cuts, KCU1500)
        key = (not c.feasible, c.latency_cycles, c.sram_total)
        if best is None or key < best[0]:
            best = (key, c)
    assert result.best.cuts == best[1].cuts
    assert result.best.latency_cycles == best[1].latency_cycles


def test_search_materializes_full_candidate():
    """search() must still hand back a complete Candidate (policy + alloc),
    identical to what the oracle produces for the winning tuple."""
    gg = group_nodes(build_cnn("resnet50", 224))
    result = search(gg, KCU1500)
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    oracle = evaluate(gg, blocks, runs, result.best.cuts, KCU1500)
    assert result.best.policy == oracle.policy
    assert result.best.alloc.buff == oracle.alloc.buff
    assert result.best.alloc.spilled == oracle.alloc.spilled
    assert result.best.alloc.boundary_writes == oracle.alloc.boundary_writes
    assert result.best.alloc.boundary_reads == oracle.alloc.boundary_reads
