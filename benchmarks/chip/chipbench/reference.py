"""The plain reference's work, spread over a pool of host processes.

The reference is ``plainref.oracle``: it builds its own graph from the
configuration file and prices one cut tuple at a time from scratch.  It
imports nothing of the program.  Two kinds of job run on it:

* ``price`` -- the metrics of given cut tuples (every answer and every
  winner row the device produced is priced this way);
* ``scan`` -- one contiguous range of a (sub-)space's linear indices,
  every candidate priced, and for each question ``(objective, budget)``
  the first candidate of least key kept.  A space's ranges merge in
  order, so the merged winner is the space's first minimum in product
  order, the order the program's tie-break follows.

Linear index ``j`` of a space with ``dims`` choices per suffix run decodes
by mixed radix, last run fastest; the prefix cuts come first.
"""
from __future__ import annotations

import os

_ORACLES: dict = {}


def oracle(cfg: dict):
    key = (cfg["network"], int(cfg["input_size"]),
           tuple(sorted(cfg["hw"].items())))
    if key not in _ORACLES:
        from plainref.oracle import Oracle
        _ORACLES[key] = Oracle(cfg["network"], cfg["input_size"], cfg["hw"])
    return _ORACLES[key]


def run_lengths(cfg: dict) -> list[int]:
    return oracle(cfg).run_lengths()


def check_config(cfg: dict) -> list[int]:
    """The configuration's monotone-run lengths, once its stated shape and
    precision are the reference's: the comparison is float64 only."""
    o = oracle(cfg)
    lengths = o.run_lengths()
    shape = {"groups": len(o.gg.groups), "monotone_runs": len(lengths),
             "cut_tuples": space_size([n + 1 for n in lengths])}
    if cfg.get("shape", shape) != shape:
        raise ValueError(f"configuration {cfg.get('name')!r} states shape "
                         f"{cfg['shape']}, the reference builds {shape}")
    if cfg.get("cost_precision", "float64") != "float64":
        raise ValueError(f"cost_precision {cfg['cost_precision']!r}: the "
                         f"comparison is float64 only")
    return lengths


def space_size(dims) -> int:
    size = 1
    for d in dims:
        size *= int(d)
    return size


def decode(prefix, dims, j: int) -> tuple:
    """The cut tuple at linear index ``j`` of ``prefix x product(dims)``."""
    suffix = []
    for d in reversed(dims):
        j, c = divmod(int(j), int(d))
        suffix.append(c)
    return tuple(int(c) for c in prefix) + tuple(reversed(suffix))


def price(cfg: dict, cuts_list) -> list:
    """Each tuple's ``Price``, or None for what is no cut tuple."""
    o = oracle(cfg)
    out = []
    for c in cuts_list:
        try:
            out.append(o.price(c))
        except ValueError:
            out.append(None)
    return out


def scan(cfg: dict, prefix, dims, lo: int, hi: int, questions) -> list:
    """``[(key, j)]`` per question: the first least key in ``[lo, hi)``."""
    o = oracle(cfg)
    best = [None] * len(questions)
    for j in range(lo, hi):
        p = o.price(decode(prefix, dims, j))
        for q, (objective, budget) in enumerate(questions):
            k = p.key(objective, budget)
            if best[q] is None or k < best[q][0]:
                best[q] = (k, j)
    return best


def _call(job):
    fn, args = job
    return {"price": price, "scan": scan}[fn](*args)


def run_jobs(jobs: list, workers: int | None = None) -> list:
    """Results of ``jobs`` (``(name, args)`` pairs), in order, over
    ``workers`` spawn-started processes.  The caller holds the chip
    through jax, whose threads a forked child must not inherit; the
    children import only ``plainref`` and this module."""
    if workers is None:
        workers = max(1, (os.cpu_count() or 2) - 1)
    workers = min(workers, len(jobs))
    if workers <= 1:
        return [_call(j) for j in jobs]
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    with ProcessPoolExecutor(max_workers=workers,
                             mp_context=multiprocessing.get_context(
                                 "spawn")) as pool:
        return list(pool.map(_call, jobs))


def scan_jobs(cfg: dict, prefix, dims, questions, pieces: int) -> list:
    """A space's scan cut into ``pieces`` contiguous ranges."""
    size = space_size(dims)
    pieces = max(1, min(pieces, size))
    bounds = [size * i // pieces for i in range(pieces + 1)]
    return [("scan", (cfg, tuple(prefix), tuple(dims), lo, hi,
                      list(questions)))
            for lo, hi in zip(bounds, bounds[1:]) if hi > lo]


def merge_scans(results: list) -> list:
    """One space's ranges, in order, merged to ``[(key, j)]`` per
    question: a later range wins only with a strictly smaller key."""
    best = list(results[0])
    for res in results[1:]:
        for q, kj in enumerate(res):
            if kj is not None and (best[q] is None or kj[0] < best[q][0]):
                best[q] = kj
    return best
