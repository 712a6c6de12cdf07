"""Helpers the request kinds (``kinds/<kind>.py``) share."""
from __future__ import annotations

import itertools

import numpy as np

FIELDS = ("latency_cycles", "dram_total", "dram_fm", "sram_total",
          "bram18k", "feasible")


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of draws from ``--seed`` (any whole
    number, negative or beyond 64 bits included)."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def take(stream, n: int) -> list:
    return list(itertools.islice(stream, n))


def answer_of(m, evaluated: int) -> dict:
    """The program's winner as plain values: its cut tuple, every
    ``CandidateMetrics`` field and ``evaluated``."""
    out = {"cuts": [int(c) for c in m.cuts]}
    for f in FIELDS:
        v = getattr(m, f)
        out[f] = (bool(v) if f == "feasible" else
                  float(v) if f == "latency_cycles" else int(v))
    out["evaluated"] = int(evaluated)
    return out
