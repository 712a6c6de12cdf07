"""The trace reduction and the per-layer readers, on a synthetic trace
whose numbers are worked out by hand here."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from chipbench import registry
from chipbench import trace as tr

MS = 1_000_000  # ns


def test_union_clips_and_merges_overlaps():
    ivs = [(0, 10), (5, 20), (30, 40), (38, 45), (60, 70), (-5, 2)]
    assert tr.merge(ivs, 0, 65) == [(0, 20), (30, 45), (60, 65)]
    assert tr.union_length(ivs, 0, 65) == 20 + 15 + 5
    assert tr.gaps(ivs, 0, 65) == [(20, 30), (45, 60)]
    assert tr.gaps([], 0, 5) == [(0, 5)]
    assert tr.union_length([(0, 3), (1, 2)], 0, 10) == 3  # nested op


def synthetic() -> tr.TraceSummary:
    """One device, a 100 ms window from t=10 ms, three launches of the
    fused step (10, 20 and 10 ms long) with nested ops, idle gaps of 20
    and 30 ms between them, and 10 ms idle at each end."""
    step = "jit_fused(123)"
    modules = [(step, 20 * MS, 30 * MS), (step, 50 * MS, 70 * MS),
               (step, 100 * MS, 110 * MS), ("jit_other", 0, 5 * MS)]
    ops = [("%while.1", 20 * MS, 30 * MS), ("%fusion.2", 21 * MS, 22 * MS),
           ("%while.1", 50 * MS, 70 * MS), ("%while.1", 100 * MS, 110 * MS),
           ("%fusion.2", 105 * MS, 106 * MS), ("%early", 0, 5 * MS)]
    spans = [("bench:window", 10 * MS, 110 * MS),
             ("bench:request", 15 * MS, 75 * MS),
             ("bench:request", 80 * MS, 110 * MS),
             ("other:span", 0, 200 * MS)]
    return tr.summarize(spans, {0: tr.Device(ops=ops, modules=modules)})


def test_busy_idle_and_launches():
    s = synthetic()
    assert s.window == (10 * MS, 110 * MS)
    assert s.busy_ns(s.devices[0]) == 40 * MS
    assert s.idle_percent() == pytest.approx(60.0)
    launches = s.launches(r"^jit_fused")[0]
    assert [e[1] for e in launches] == [20 * MS, 50 * MS, 100 * MS]


def test_breakdown_names_ops_and_gaps():
    b = synthetic().breakdown(top=3)
    assert b["device_ops"][0] == ["%while.1", pytest.approx(0.040)]
    assert b["device_ops"][1] == ["%fusion.2", pytest.approx(0.002)]
    # gaps: 10-20 (request), 30-50 (request), 70-100 (midpoint 85 ms,
    # inside the second request)
    assert b["idle_gaps"] == [["request", pytest.approx(0.030)],
                              ["request", pytest.approx(0.020)],
                              ["request", pytest.approx(0.010)]]


def test_two_devices_average_busy():
    s = synthetic()
    s.devices[1] = tr.Device(ops=[("%x", 10 * MS, 110 * MS)])
    assert s.mean_busy_ns() == pytest.approx(70 * MS)
    assert s.idle_percent() == pytest.approx(30.0)


def _ctx(summary, spans=None):
    return SimpleNamespace(trace=summary, spans=spans or {}, cell="t",
                           chips=1)


def test_readers_on_the_synthetic_trace():
    s = synthetic()
    read = registry.load_reader
    assert read("device_idle.search")(_ctx(s)) == pytest.approx(60.0)
    assert read("device_idle.compile")(_ctx(s)) == pytest.approx(60.0)
    # launches 10, 20, 10 ms; gaps 20 and 30 ms between them
    assert read("fused_step_ms.search")(_ctx(s)) == pytest.approx(40 / 3)
    assert read("launch_gap_ms.search")(_ctx(s)) == pytest.approx(25.0)
    assert read("search_ms.compile")(
        _ctx(s, {"compiler.search": [0.2, 0.4]})) == pytest.approx(300.0)


@pytest.mark.parametrize("name", ["device_idle.search",
                                  "fused_step_ms.search",
                                  "launch_gap_ms.search",
                                  "search_ms.compile"])
def test_readers_return_nothing_without_data(name):
    empty = tr.summarize([], {})
    assert registry.load_reader(name)(_ctx(None)) is None
    assert registry.load_reader(name)(_ctx(empty)) is None


def test_sharded_step_module_name_matches():
    from chipbench.kernels import FUSED_STEP
    import re
    for name in ("jit_fused(2631856881355208559)", "jit_per_device",
                 "jit_per_device(77)"):
        assert re.search(FUSED_STEP, name)
    for name in ("jit_fused_other", "jit_convert_element_type"):
        assert not re.search(FUSED_STEP, name)
