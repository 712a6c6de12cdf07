"""The program's own host spans, per request.

``repro.utils.trace`` keeps a record of each span the program closes while
a profiler session runs, so in a traced run its records cover exactly the
traced part of the window.  A record is ``(name, start_ns, end_ns,
parent, request)``; the program's outermost span opens a request.
A request counts only where its outermost span closed inside the traced
part: otherwise that span has no record.

Where the program has no such module, or recorded nothing, there is
nothing to read: :func:`requests` returns an empty list.
"""
from __future__ import annotations


def program_records() -> list:
    try:
        from repro.utils.trace import records
    except ImportError:             # a program without spans
        return []
    return records()


def requests(root: str) -> list[tuple]:
    """``(root record, records of the request)`` for every request whose
    outermost span, named ``root``, closed inside the traced part."""
    by_request: dict = {}
    for r in program_records():
        by_request.setdefault(r.request, []).append(r)
    out = []
    for recs in by_request.values():
        roots = [r for r in recs if r.parent is None and r.name == root]
        if roots:
            out.append((roots[0], recs))
    return out


def ms(r) -> float:
    return (r.end_ns - r.start_ns) / 1e6


def mean_span_ms(root: str, name: str) -> float | None:
    """Mean duration of the spans ``name`` inside ``root`` requests."""
    durs = [ms(r) for _, recs in requests(root) for r in recs
            if r.name == name]
    return sum(durs) / len(durs) if durs else None


def mean_per_request_ms(root: str, per_request) -> float | None:
    """Mean over ``root`` requests of ``per_request(root record,
    records)``, in ms."""
    vals = [per_request(top, recs) for top, recs in requests(root)]
    return sum(vals) / len(vals) if vals else None
