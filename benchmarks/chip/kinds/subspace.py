"""Request kind ``subspace``: one exhaustive sub-space search each, through
``CutpointEngine.run_subspace``, the entry every pool worker and the serial
search use.

Mix parameters: ``target_tasks`` (the space is split along its leading
monotone runs into at least that many equal, disjoint sub-spaces, the split
the compiler's own process pool makes), ``batch_size`` (candidates per
launch), ``objectives``, and ``argmin_sample`` (how many answered requests,
drawn from the seed, the reference scans whole).

Requests visit the sub-spaces in a seeded permutation, which wraps; the
objectives cycle in a seeded order, shifted by one on every wrap so that a
wrapped request is never a repeat.  Every seed sends the same set of
requests in another order.  One engine is built in set-up and serves every
request, as one search's engine does.
"""
from __future__ import annotations

import itertools

from chipbench import reference
from chipbench.capture import DeviceRows
from chipbench.traffic import answer_of, rng


def partition_space(run_lengths: list[int], target_tasks: int):
    """``(prefixes, suffix_lengths)``: the smallest number ``k`` of leading
    runs whose cut choices number at least ``target_tasks`` fixes the
    prefix; the remaining runs span each sub-space."""
    k, tasks = 0, 1
    while k < len(run_lengths) and tasks < target_tasks:
        tasks *= run_lengths[k] + 1
        k += 1
    prefixes = list(itertools.product(*[range(n + 1)
                                        for n in run_lengths[:k]]))
    return prefixes, list(run_lengths[k:])


def requests(mix: dict, seed: int, run_lengths: list[int]):
    r = rng(seed, 0)
    objectives = list(mix["objectives"])
    prefixes, suffix = partition_space(run_lengths, int(mix["target_tasks"]))
    perm = r.permutation(len(prefixes)).tolist()
    order = [objectives[i] for i in r.permutation(len(objectives))]
    work = reference.space_size([d + 1 for d in suffix])
    for i in itertools.count():
        wrap, pos = divmod(i, len(perm))
        yield {"id": i, "prefix": tuple(prefixes[perm[pos]]),
               "suffix_dims": tuple(suffix),
               "objective": order[(i + wrap) % len(order)], "work": work}


def warmup_requests(mix: dict, run_lengths: list[int]) -> list:
    """One request per objective: the fused step is specialised on the
    objective, and every sub-space of a split shares one shape."""
    out, seen = [], set()
    for req in requests(mix, 0, run_lengths):
        if req["objective"] not in seen:
            seen.add(req["objective"])
            out.append(req)
        if len(seen) == len(mix["objectives"]):
            return out


class Target:
    def __init__(self, cfg: dict, mix: dict):
        from repro.cnn import build_cnn
        from repro.core.cutpoint import CutpointEngine
        from repro.core.grouping import group_nodes
        from repro.core.hw import FPGAConfig
        gg = group_nodes(build_cnn(cfg["network"], int(cfg["input_size"])))
        self.engine = CutpointEngine(gg, FPGAConfig(**cfg["hw"]),
                                     engine=cfg["engine"])
        self.batch_size = int(mix["batch_size"])
        self.rows = DeviceRows(cfg["engine"])

    def run_lengths(self) -> list[int]:
        return [len(r) for r in self.engine.runs]

    def serve(self, req: dict) -> dict:
        eng = self.engine
        self.rows.take()
        before = eng.evaluations
        best, pruned = eng.run_subspace(req["prefix"],
                                        list(req["suffix_dims"]),
                                        req["objective"],
                                        batch_size=self.batch_size)
        out = answer_of(best, eng.evaluations - before + pruned)
        out["device_rows"] = self.rows.take()
        return out

    def close(self) -> dict:
        self.rows.close()
        self.engine = None
        return {}


def items(cfg: dict, mix: dict, seed: int, answered: list,
          run_lengths: list[int]) -> list:
    """What the judge compares: every answered request, and the argmin of
    ``argmin_sample`` of them, drawn from the seed."""
    n = min(int(mix["argmin_sample"]), len(answered))
    scan = set(rng(seed, 1).choice(len(answered), n, replace=False).tolist())
    out = []
    for i, r in enumerate(answered):
        req = r["req"]
        dims = [d + 1 for d in req["suffix_dims"]]
        out.append({"req": req, "answer": r["answer"],
                    "objective": req["objective"],
                    "budget": int(cfg["hw"]["sram_budget"]),
                    "evaluated": reference.space_size(dims),
                    "bounds": (list(req["prefix"]), dims),
                    "scan": i in scan})
    return out
