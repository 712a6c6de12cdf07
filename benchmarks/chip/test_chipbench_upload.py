"""The reader of the tables' upload to the device, ``upload_ms.compile``,
on synthetic records whose numbers are worked out by hand here."""
from __future__ import annotations

import sys
from types import SimpleNamespace

import pytest

from chipbench import registry
from repro.utils import trace
from repro.utils.trace import Record

MS = 1_000_000  # ns


def _compile(req: int, t0: int, uploads: list, closed: bool = True) -> list:
    """One compile request from ``t0`` ms: its sub-spaces, the first
    ``len(uploads)`` of them each opening with an upload of ``uploads[i]``
    ms, then a 3 ms load and a 1 ms dispatch; the root span last, if it
    closed."""
    out, t = [], t0 * MS
    for u in uploads + [None]:
        s = t
        if u is not None:
            out.append(Record("pipeline.upload", t, t + u * MS,
                              "pipeline.subspace", req))
            t += u * MS
        out.append(Record("pipeline.load", t, t + 3 * MS,
                          "pipeline.subspace", req))
        out.append(Record("pipeline.dispatch", t + 3 * MS, t + 4 * MS,
                          "pipeline.subspace", req))
        t += 4 * MS
        out.append(Record("pipeline.subspace", s, t, "compile.search", req))
    out.append(Record("compile.search", t0 * MS, t, "compile", req))
    if closed:
        out.append(Record("compile", t0 * MS, t + MS, None, req))
    return out


@pytest.fixture
def recorded(monkeypatch):
    def use(recs):
        monkeypatch.setattr(trace, "records", lambda: list(recs))
    return use


def _read():
    return registry.load_reader("upload_ms.compile")(
        SimpleNamespace(trace=None, spans={}, cell="t", chips=1))


def test_mean_over_requests_with_one_and_with_zero_uploads(recorded):
    # request 0 uploads once (2 ms), request 1 reuses its engine's copies
    recorded(_compile(0, 0, [2]) + _compile(1, 100, []))
    assert _read() == pytest.approx((2 + 0) / 2)


def test_uploads_of_one_request_add_up(recorded):
    recorded(_compile(0, 0, [2, 5]) + _compile(1, 100, [4]))
    assert _read() == pytest.approx((2 + 5 + 4) / 2)


def test_a_request_cut_by_the_end_of_the_trace_is_left_out(recorded):
    recorded(_compile(0, 0, [2]) + _compile(1, 100, [50], closed=False))
    assert _read() == pytest.approx(2.0)


@pytest.mark.parametrize("recs", [[], _compile(0, 0, [])],
                         ids=["no records", "no upload span"])
def test_nothing_to_read_without_upload_spans(recs, recorded):
    recorded(recs)
    assert _read() is None


def test_nothing_to_read_from_a_program_without_spans(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert _read() is None
