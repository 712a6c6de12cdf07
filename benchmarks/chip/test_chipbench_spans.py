"""The readers of the program's host spans, on synthetic records whose
numbers are worked out by hand here."""
from __future__ import annotations

import sys
from types import SimpleNamespace

import pytest

from chipbench import registry
from repro.utils import trace
from repro.utils.trace import Record

MS = 1_000_000  # ns

READERS = ["launch_dispatch_ms.search", "launch_wait_ms.search",
           "fused_load_ms.compile", "engine_build_ms.compile",
           "compile_host_ms.compile"]


def _subspace(req: int, t0: int, dispatch: list, wait: list,
              closed: bool = True) -> list:
    """One sub-space request from ``t0`` ms: launches of ``dispatch[i]``
    then ``wait[i]`` ms, back to back with 1 ms of fold after each, then
    a 2 ms re-price; the root span last, if it closed."""
    out, t = [], t0 * MS
    for d, w in zip(dispatch, wait):
        out.append(Record("pipeline.dispatch", t, t + d * MS,
                          "pipeline.subspace", req))
        t += d * MS
        out.append(Record("pipeline.wait", t, t + w * MS,
                          "pipeline.subspace", req))
        t += (w + 1) * MS
    out.append(Record("pipeline.rescore", t, t + 2 * MS,
                      "pipeline.subspace", req))
    if closed:
        out.append(Record("pipeline.subspace", t0 * MS, t + 2 * MS, None,
                          req))
    return out


def search_records() -> list:
    """Two whole requests and one cut by the end of the traced part."""
    return (_subspace(0, 0, [2, 4], [1, 1])
            + _subspace(1, 100, [6], [3])
            + _subspace(2, 200, [50, 50], [50, 50], closed=False))


def _compile(req: int, t0: int, total: int, search: int, engine: int,
             tables: int, load: int, dispatch: list,
             closed: bool = True) -> list:
    """One compile request from ``t0`` ms lasting ``total`` ms, whose
    search lasts ``search`` ms and holds the engine build, the tables, the
    first (load) launch and later launches."""
    s = (t0 + 1) * MS
    out = [Record("compile.group", t0 * MS, s, "compile", req),
           Record("search.engine", s, s + engine * MS, "compile.search",
                  req)]
    t = s + engine * MS
    sub = [Record("pipeline.tables", t, t + tables * MS,
                  "pipeline.subspace", req)]
    t += tables * MS
    sub.append(Record("pipeline.load", t, t + load * MS,
                      "pipeline.subspace", req))
    t += load * MS
    for d in dispatch:
        sub.append(Record("pipeline.dispatch", t, t + d * MS,
                          "pipeline.subspace", req))
        t += d * MS
    out += sub
    out.append(Record("pipeline.subspace", s + engine * MS, t,
                      "compile.search", req))
    out.append(Record("compile.search", s, s + search * MS, "compile",
                      req))
    if closed:
        out.append(Record("compile", t0 * MS, (t0 + total) * MS, None,
                          req))
    return out


def compile_records() -> list:
    return (_compile(0, 0, total=300, search=200, engine=20, tables=10,
                     load=100, dispatch=[1, 1])
            + _compile(1, 400, total=340, search=220, engine=30, tables=20,
                       load=140, dispatch=[1])
            + _compile(2, 800, total=999, search=900, engine=500,
                       tables=100, load=300, dispatch=[], closed=False))


@pytest.fixture
def recorded(monkeypatch):
    def use(recs):
        monkeypatch.setattr(trace, "records", lambda: list(recs))
    return use


def _read(name):
    return registry.load_reader(name)(
        SimpleNamespace(trace=None, spans={}, cell="t", chips=1))


def test_search_readers_count_whole_requests_only(recorded):
    recorded(search_records())
    # dispatch 2, 4 and 6 ms; wait 1, 1 and 3 ms; request 2 was cut
    assert _read("launch_dispatch_ms.search") == pytest.approx(4.0)
    assert _read("launch_wait_ms.search") == pytest.approx(5 / 3)
    assert _read("fused_load_ms.compile") is None


def test_compile_readers_count_whole_requests_only(recorded):
    recorded(compile_records())
    # requests 0 and 1; request 2 was cut
    assert _read("fused_load_ms.compile") == pytest.approx((100 + 140) / 2)
    assert _read("engine_build_ms.compile") == pytest.approx(
        (20 + 10 + 30 + 20) / 2)
    assert _read("compile_host_ms.compile") == pytest.approx(
        (300 - 200 + 340 - 220) / 2)
    assert _read("launch_dispatch_ms.search") is None


def test_a_compile_inside_a_search_root_is_not_a_search_request(recorded):
    """The compile cell's sub-spaces open inside ``compile``: they are
    not the search cells' requests."""
    recorded(compile_records())
    assert _read("launch_wait_ms.search") is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_records(name, recorded):
    recorded([])
    assert _read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_from_a_program_without_spans(name,
                                                            monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert _read(name) is None
