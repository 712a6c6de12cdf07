"""The program's host spans (``repro.utils.trace``): nothing while no
profiler session runs; while one does, records nested as the compile path
and the search loop open them, and the same intervals as the ``repro:``
events of the profiler's own trace."""
from __future__ import annotations

import math
import threading
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.cnn import build_cnn  # noqa: E402
from repro.core.compiler import compile_graph  # noqa: E402
from repro.core.cutpoint import CutpointEngine  # noqa: E402
from repro.core.grouping import group_nodes  # noqa: E402
from repro.core.hw import KCU1500  # noqa: E402
from repro.core.options import CompileOptions  # noqa: E402
from repro.kernels import search_pipeline as sp  # noqa: E402
from repro.utils import trace  # noqa: E402

NET = ("vgg16-conv", 224)          # 1,080 tuples
CHUNK = 64
LAUNCHES = math.ceil(1080 / CHUNK)

# span -> the span it opens inside, in a pipeline:lax compile
PARENT = {"compile": None, "compile.group": "compile",
          "compile.search": "compile", "compile.materialise": "compile",
          "compile.codegen": "compile", "compile.verify": "compile",
          "search.engine": "compile.search",
          "search.exhaustive": "compile.search",
          "pipeline.subspace": "search.exhaustive",
          "pipeline.tables": "pipeline.subspace",
          "pipeline.upload": "pipeline.subspace",
          "pipeline.load": "pipeline.subspace",
          "pipeline.dispatch": "pipeline.subspace",
          "pipeline.wait": "pipeline.subspace",
          "pipeline.rescore": "pipeline.subspace"}


@pytest.fixture(autouse=True)
def _empty():
    trace.clear()
    yield
    trace.clear()


def _engine():
    return CutpointEngine(group_nodes(build_cnn(*NET)), KCU1500,
                          engine="pipeline:lax")


def _subspace(engine):
    return engine.run_subspace((), [len(r) for r in engine.runs],
                               "latency", batch_size=CHUNK)


def _compile():
    return compile_graph(build_cnn(*NET), KCU1500,
                         CompileOptions(engine="pipeline:lax",
                                        batch_size=CHUNK))


def test_off_span_is_the_shared_noop_and_records_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert trace.span("pipeline.wait") is trace.OFF
    assert not hasattr(trace.OFF, "__dict__")
    with trace.span("pipeline.subspace") as sub:
        assert sub is trace.OFF
    _subspace(_engine())
    assert trace.records() == []


def test_off_span_site_costs_one_check(monkeypatch):
    """Off, every span the search opens costs one ``is_enabled`` call:
    the sub-space, the tables, the tables' upload to the device, two a
    launch and the winner's re-price."""
    calls = [0]

    def off():
        calls[0] += 1
        return False
    monkeypatch.setattr(trace, "_is_enabled", off)
    _subspace(_engine())
    assert calls[0] == 1 + 1 + 1 + 2 * LAUNCHES + 1


def test_off_compile_costs_one_check_a_span_site(monkeypatch):
    """Off, a whole compile, its search path's span included, costs one
    ``is_enabled`` call a span site and records nothing."""
    calls = [0]

    def off():
        calls[0] += 1
        return False
    monkeypatch.setattr(trace, "_is_enabled", off)
    plan = _compile()
    assert plan.search.path == "exhaustive"
    per_launch = {"pipeline.load", "pipeline.dispatch", "pipeline.wait"}
    assert calls[0] == len(set(PARENT) - per_launch) + 2 * LAUNCHES
    assert trace.records() == []


def test_profiler_records_the_spans_nested(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        plan = _compile()
    recs = trace.records()
    assert plan.search.evaluated == 1080
    assert {r.name for r in recs} == set(PARENT)
    assert {r.request for r in recs} == {recs[-1].request}
    for r in recs:
        assert r.parent == PARENT[r.name], r
        assert r.end_ns >= r.start_ns
    by = {r.name: r for r in recs}
    assert recs[-1].name == "compile"
    launches = [r for r in recs
                if r.name in ("pipeline.load", "pipeline.dispatch")]
    assert len(launches) == LAUNCHES
    assert [r.name for r in launches].count("pipeline.load") == 1
    assert launches[0].name == "pipeline.load"
    assert [r.name for r in recs].count("pipeline.wait") == LAUNCHES
    # children lie inside their parent
    for r in recs:
        if r.parent is not None:
            p = by[r.parent]
            assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns
    _matches_profile(recs, tmp_path)


def test_a_descent_compile_records_its_path(tmp_path):
    """mobilenet-v3@224's 15.1M tuples exceed the exhaustive limit the
    pipeline resolves to on jax's CPU backend: the traced compile opens
    ``search.descent`` under ``compile.search``, and no exhaustive
    path."""
    with jax.profiler.trace(str(tmp_path)):
        plan = compile_graph(build_cnn("mobilenet-v3", 224), KCU1500,
                             CompileOptions(engine="pipeline:lax"))
    assert plan.search.path == "descent"
    names = [(r.name, r.parent) for r in trace.records()]
    assert ("search.descent", "compile.search") in names
    assert not any(n.startswith(("search.exhaustive", "pipeline."))
                   for n, _ in names)


def _matches_profile(recs, logdir: Path):
    """Every record is a ``repro:`` event of the ``.xplane.pb`` and lies
    inside it on one offset between the two clocks (the annotation starts
    before the record's first clock read and stops after its last), and
    the durations match within 50 us at the median (a thread preempted
    between the two reads widens a single pair, not the median)."""
    import statistics
    from jax.profiler import ProfileData
    [path] = sorted(logdir.glob("**/*.xplane.pb"))
    events = [(e.name[len(trace.PREFIX):], e.start_ns, e.start_ns
               + e.duration_ns)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(trace.PREFIX)]
    assert sorted(n for n, _, _ in events) == sorted(r.name for r in recs)
    starts, ends, widths = [], [], []
    for name in {r.name for r in recs}:
        mine = sorted((r for r in recs if r.name == name),
                      key=lambda r: r.start_ns)
        theirs = sorted((e for e in events if e[0] == name),
                        key=lambda e: e[1])
        for r, (_, s, e) in zip(mine, theirs):
            starts.append(s - r.start_ns)
            ends.append(e - r.end_ns)
            widths.append((e - s) - (r.end_ns - r.start_ns))
    assert max(starts) <= min(ends) + 20_000
    assert statistics.median(widths) <= 50_000


def test_a_span_closing_after_the_session_is_not_recorded(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        outer = trace.span("compile")
        outer.__enter__()
        with trace.span("compile.group"):
            pass
    finally:
        jax.profiler.stop_trace()
    outer.__exit__(None, None, None)
    assert [(r.name, r.parent) for r in trace.records()] == [
        ("compile.group", "compile")]


def test_the_store_holds_the_newest_session(tmp_path):
    """Records outlive their session until a request opens under the
    next one; the store never holds more than ``MAX_RECORDS``."""
    def request(name):
        with trace.span(name):
            with trace.span("compile.group"):
                pass

    with jax.profiler.trace(str(tmp_path / "a")):
        request("compile")
    request("compile")                 # off: neither records nor clears
    assert [r.parent for r in trace.records()] == ["compile", None]
    with jax.profiler.trace(str(tmp_path / "b")):
        request("pipeline.subspace")
        request("pipeline.subspace")
    recs = trace.records()
    assert [r.name for r in recs] == ["compile.group",
                                      "pipeline.subspace"] * 2
    assert len({r.request for r in recs}) == 2
    assert trace._records.maxlen == trace.MAX_RECORDS <= 1 << 16


def test_each_thread_opens_its_own_requests(tmp_path):
    def work():
        with trace.span("compile"):
            with trace.span("compile.group"):
                pass

    with jax.profiler.trace(str(tmp_path)):
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    recs = trace.records()
    assert len({r.request for r in recs}) == 4
    for req in {r.request for r in recs}:
        assert sorted((r.name, r.parent) for r in recs
                      if r.request == req) == [("compile", None),
                                               ("compile.group", "compile")]


def test_fused_step_lowers_the_same_with_the_profiler_on(tmp_path):
    engine = _engine()
    engine.run_subspace((), [len(r) for r in engine.runs], "latency",
                        batch_size=CHUNK)
    tbl = sp._engine_tables(engine)
    dims = tuple(len(r) + 1 for r in engine.runs)
    S = int(np.prod(dims))

    def lowered() -> str:
        with jax.enable_x64(True):
            fused = sp._make_fused(tbl, CHUNK, 0, dims,
                                   sp._space_strides(dims), S, "latency")
            return jax.jit(fused).lower(np.int32(0),
                                        *sp._lax_args(tbl, ())).as_text()

    off = lowered()
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("pipeline.load"):
            on = lowered()
    assert on == off
