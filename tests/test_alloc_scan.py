"""Tensorized allocator replay (kernels/alloc_scan.py + replay="device").

The device replay must reproduce the journal-based Python replay bit for
bit: same frame-mask matrix, same boundary-I/O matrix, same buffer
maxima / write-buffer max / DRAM boundary total / spill feasibility --
for every cut tuple of every zoo net, every batch shape, and every
alloc_scan backend (numpy reference, jax.lax.scan, Pallas interpret).
On top sit the engine-level contracts: ``score_batch(replay="device")``
is bit-identical to the ``evaluate`` oracle with unchanged memo /
``evaluations`` bookkeeping, and ``search(replay="device")`` returns
byte-identical SearchResults serial and parallel.  The AllocState
export/import round-trip that seeds the scan is covered last."""
import itertools
import random

import numpy as np
import pytest

from repro.cnn import build_cnn
from repro.core.allocator import (alloc_step, arrays_to_state, graph_steps,
                                  init_alloc_state, state_to_arrays)
from repro.core.cutpoint import (CutpointEngine, evaluate, monotone_runs,
                                 search, split_blocks)
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500
from repro.core.options import CompileOptions
from repro.kernels.alloc_scan import alloc_scan_ref, pack_alloc_tables

ALL_CNNS = ["vgg16-conv", "yolov2", "yolov3", "resnet50", "resnet152",
            "efficientnet-b1", "retinanet", "mobilenet-v3"]

METRICS = ["latency_cycles", "dram_total", "dram_fm", "sram_total",
           "bram18k", "feasible"]

_GG_CACHE: dict = {}


def _grouped(name):
    got = _GG_CACHE.get(name)
    if got is None:
        gg = group_nodes(build_cnn(name))
        blocks = split_blocks(gg)
        runs = monotone_runs(blocks)
        got = _GG_CACHE[name] = (gg, blocks, runs)
    return got


def _mixed_tuples(runs, n_prefix=25, n_random=25, seed=17):
    dims = [range(len(r) + 1) for r in runs]
    tuples = list(itertools.islice(itertools.product(*dims), n_prefix))
    rng = random.Random(seed)
    tuples += [tuple(rng.randint(0, len(r)) for r in runs)
               for _ in range(n_random)]
    tuples.append(tuple(0 for _ in runs))
    tuples.append(tuple(len(r) for r in runs))
    return tuples


def _journal_outputs(engine, tuples):
    """Frame masks + the engine's journal-fed extraction for each tuple."""
    n = len(engine.gg.groups)
    b = len(tuples)
    out = {
        "frame": np.zeros((b, n), dtype=bool),
        "io": np.zeros((b, n), dtype=np.int64),
        "buff": np.zeros((b, 3), dtype=np.int64),
        "side_buff": np.zeros(b, dtype=np.int64),
        "wrf": np.zeros(b, dtype=np.int64),
        "bfm": np.zeros(b, dtype=np.int64),
        "feasible": np.zeros(b, dtype=bool),
    }
    for j, cuts in enumerate(tuples):
        alloc = engine._replay(cuts)
        out["frame"][j] = engine._frame
        out["io"][j] = engine._x_io
        out["buff"][j] = alloc.buff
        out["side_buff"][j] = alloc.side_buff
        out["wrf"][j] = engine._x_wrf
        out["bfm"][j] = engine._x_bfm
        out["feasible"][j] = engine._x_feas
    return out


def _assert_scan_equal(res, journal, ctx):
    for field in ["io", "buff", "side_buff", "wrf", "bfm", "feasible"]:
        got = getattr(res, field)
        want = journal[field]
        assert np.array_equal(got, want), (
            f"{ctx}: {field} mismatch at "
            f"{np.argwhere(np.asarray(got) != np.asarray(want))[:4]}")


# ------------------------------------------------- replay-level bit-identity
@pytest.mark.parametrize("name", ALL_CNNS)
def test_device_replay_matches_journal(name):
    """Fuzzed oracle bit-identity: frame masks, boundary-IO matrix and all
    per-candidate extraction scalars, whole zoo."""
    gg, blocks, runs = _grouped(name)
    engine = CutpointEngine(gg, KCU1500, blocks, runs)
    tuples = _mixed_tuples(runs)
    journal = _journal_outputs(engine, tuples)
    frame = engine._frame_matrix(tuples)
    assert np.array_equal(frame, journal["frame"]), name
    res = alloc_scan_ref(pack_alloc_tables(gg, KCU1500), frame)
    _assert_scan_equal(res, journal, name)


def test_zoo_quantities_fit_int32():
    """The jax/pallas backends run in int32; every replayed quantity of
    every zoo net must stay far inside that range (the numpy reference is
    int64, so this guard is what licenses the narrower backends).  Mixed
    random tuples are the maximizers here -- the all-row/all-frame
    corners barely cross any boundary (all-row never fills a buffer,
    all-frame rarely writes one out), so sampling only them would bound
    ~half the real worst case."""
    lim = 2 ** 31 - 1
    for name in ALL_CNNS:
        gg, blocks, runs = _grouped(name)
        engine = CutpointEngine(gg, KCU1500, blocks, runs)
        tuples = _mixed_tuples(runs, n_prefix=10, n_random=40, seed=13)
        res = alloc_scan_ref(pack_alloc_tables(gg, KCU1500),
                             engine._frame_matrix(tuples))
        worst = max(int(res.io.max(initial=0)), int(res.buff.max()),
                    int(res.bfm.max()), int(res.wrf.max()),
                    int(res.side_buff.max()))
        assert worst < lim // 4, (name, worst)


# -------------------------------------------------------- backend equality
@pytest.mark.parametrize("name", ["resnet50", "retinanet"])
def test_scan_backend_matches_reference(name):
    """jax.lax.scan replay == numpy reference, including a spilling net."""
    pytest.importorskip("jax")
    from repro.kernels.alloc_scan import alloc_scan_jax
    gg, blocks, runs = _grouped(name)
    engine = CutpointEngine(gg, KCU1500, blocks, runs)
    tuples = _mixed_tuples(runs, n_prefix=12, n_random=12, seed=5)
    frame = engine._frame_matrix(tuples)
    tables = pack_alloc_tables(gg, KCU1500)
    journal = _journal_outputs(engine, tuples)
    _assert_scan_equal(alloc_scan_jax(tables, frame), journal, name)


@pytest.mark.parametrize("name", ["vgg16-conv", "resnet50", "resnet152"])
def test_pallas_backend_matches_reference(name):
    """Pallas interpret-mode replay == numpy reference (integer-exact,
    unlike the float32 scoring kernel); resnet152's 160 groups span two
    128-lane tiles."""
    pytest.importorskip("jax")
    from repro.kernels.alloc_scan import alloc_scan_pallas
    gg, blocks, runs = _grouped(name)
    engine = CutpointEngine(gg, KCU1500, blocks, runs)
    tuples = _mixed_tuples(runs, n_prefix=8, n_random=8, seed=2)
    frame = engine._frame_matrix(tuples)
    tables = pack_alloc_tables(gg, KCU1500)
    journal = _journal_outputs(engine, tuples)
    res = alloc_scan_pallas(tables, frame, interpret=True, block_b=8)
    _assert_scan_equal(res, journal, name)


# -------------------------------------------------- engine-level contracts
@pytest.mark.parametrize("name", ALL_CNNS)
def test_score_batch_device_matches_oracle(name):
    gg, blocks, runs = _grouped(name)
    engine = CutpointEngine(gg, KCU1500, blocks, runs)
    tuples = _mixed_tuples(runs, n_prefix=15, n_random=15, seed=23)
    scored = engine.score_batch(tuples, memoize=False, replay="device")
    assert engine.evaluations == len(tuples)
    for cuts, fast in zip(tuples, scored):
        oracle = evaluate(gg, blocks, runs, cuts, KCU1500)
        for f in METRICS:
            assert getattr(oracle, f) == getattr(fast, f), (
                f"{name} cuts={cuts}: {f} {getattr(oracle, f)!r} != "
                f"{getattr(fast, f)!r}")


def test_device_b1_and_ragged_batches():
    gg, blocks, runs = _grouped("yolov2")
    tuples = _mixed_tuples(runs, n_prefix=9, n_random=10, seed=31)  # 21
    one = CutpointEngine(gg, KCU1500, blocks, runs, replay="device")
    singles = [one.score_batch([c], memoize=False)[0] for c in tuples]
    ragged = []
    re = CutpointEngine(gg, KCU1500, blocks, runs, replay="device")
    for i in range(0, len(tuples), 8):                  # 21 = 8 + 8 + 5
        ragged.extend(re.score_batch(tuples[i:i + 8], memoize=False))
    for cuts, a, b in zip(tuples, singles, ragged):
        oracle = evaluate(gg, blocks, runs, cuts, KCU1500)
        for f in METRICS:
            assert getattr(oracle, f) == getattr(a, f), (cuts, f)
            assert getattr(oracle, f) == getattr(b, f), (cuts, f)


def test_device_memo_bookkeeping_matches_journal():
    """Cache hits served, in-batch duplicates scored once, memo shared
    with evaluate -- and the stored metrics are the journal-exact ones."""
    gg, blocks, runs = _grouped("resnet50")
    engine = CutpointEngine(gg, KCU1500, blocks, runs, replay="device")
    t0 = tuple(0 for _ in runs)
    t1 = tuple(min(1, len(r)) for r in runs)
    t2 = tuple(len(r) for r in runs)
    warm = engine.evaluate(t0)                 # journal replay into memo
    n0 = engine.evaluations
    got = engine.score_batch([t0, t1, t1, t2])
    assert got[0] is warm
    assert got[1] is got[2]
    assert engine.evaluations == n0 + 2
    assert engine.evaluate(t1) is got[1]
    assert engine.evaluations == n0 + 2
    # journal engine scoring the same batch stores equal metrics
    ref = CutpointEngine(gg, KCU1500, blocks, runs)
    ref_got = ref.score_batch([t0, t1, t1, t2])
    for a, b in zip(got, ref_got):
        for f in METRICS:
            assert getattr(a, f) == getattr(b, f), f


def test_device_and_journal_interleave_on_one_engine():
    """Device batches must not disturb the journal checkpoints: alternate
    paths on one engine and check every result against the oracle."""
    gg, blocks, runs = _grouped("retinanet")
    engine = CutpointEngine(gg, KCU1500, blocks, runs)
    tuples = _mixed_tuples(runs, n_prefix=6, n_random=6, seed=41)
    for i, cuts in enumerate(tuples):
        if i % 2:
            got = engine.score_batch([cuts], memoize=False,
                                     replay="device")[0]
        else:
            got = engine.evaluate(cuts, memoize=False)
        oracle = evaluate(gg, blocks, runs, cuts, KCU1500)
        for f in METRICS:
            assert getattr(oracle, f) == getattr(got, f), (cuts, f)


# -------------------------------------------------- search-level contracts
def test_search_device_bit_identity_exhaustive():
    gg, _, _ = _grouped("resnet50")
    a = search(gg, KCU1500)
    b = search(gg, KCU1500, CompileOptions(engine="device"))
    assert a.best.cuts == b.best.cuts
    assert a.evaluated == b.evaluated
    for f in METRICS:
        assert getattr(a.best, f) == getattr(b.best, f), f
    assert a.best.policy == b.best.policy
    assert a.best.alloc.buff == b.best.alloc.buff


def test_search_device_bit_identity_descent():
    gg, _, _ = _grouped("mobilenet-v3")
    a = search(gg, KCU1500)
    b = search(gg, KCU1500, CompileOptions(engine="device"))
    assert a.best.cuts == b.best.cuts
    assert a.evaluated == b.evaluated
    for f in METRICS:
        assert getattr(a.best, f) == getattr(b.best, f), f


def test_search_parallel_device_bit_identity():
    gg, _, _ = _grouped("resnet50")
    serial = search(gg, KCU1500)
    parallel = search(gg, KCU1500,
                      CompileOptions(workers=2, engine="device"))
    assert serial.best.cuts == parallel.best.cuts
    assert serial.evaluated == parallel.evaluated
    for f in METRICS:
        assert getattr(serial.best, f) == getattr(parallel.best, f), f


# --------------------------------------------------- state export round-trip
def _states_equal(a, b):
    return (a.remaining == b.remaining
            and a.location == b.location
            and a.live_in_buffer == b.live_in_buffer
            and a.alloc.buff == b.alloc.buff
            and a.alloc.side_buff == b.alloc.side_buff
            and a.alloc.spilled == b.alloc.spilled
            and a.alloc.boundary_writes == b.alloc.boundary_writes
            and a.alloc.boundary_reads == b.alloc.boundary_reads)


@pytest.mark.parametrize("name", ["resnet50", "retinanet",
                                  "efficientnet-b1"])
def test_state_roundtrip_mid_replay(name):
    """Export/import at every quartile of an allocator walk must (a)
    reproduce the state exactly and (b) keep replaying to the same final
    allocation as the original."""
    gg, blocks, runs = _grouped(name)
    from repro.core.cutpoint import policy_from_cuts
    rng = random.Random(9)
    cuts = tuple(rng.randint(0, len(r)) for r in runs)
    policy = policy_from_cuts(gg, blocks, runs, cuts)
    steps = graph_steps(gg)
    for frac in (0, 1, 2, 3):
        stop = len(steps) * frac // 4
        state = init_alloc_state(gg, lean=True)
        for s in steps[:stop]:
            alloc_step(state, s, policy[s.gid])
        state.j_writes.clear()
        state.j_reads.clear()
        state.j_spills.clear()
        back = arrays_to_state(state_to_arrays(state))
        assert _states_equal(state, back), (name, stop)
        for s in steps[stop:]:
            alloc_step(state, s, policy[s.gid])
            alloc_step(back, s, policy[s.gid])
        assert _states_equal(state, back), (name, stop, "after continue")
