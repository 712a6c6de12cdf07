"""Request kind ``compile``: one whole ``compile_graph`` each (grouping,
search, materialisation, codegen), with a new engine per call, as every
user's compile builds one.

Mix parameters: ``objectives`` and ``sram_budgets_mb``.  Each request
compiles the configuration's network under one (objective, SRAM budget)
pair: every pair once per cycle, in a fresh seeded permutation per cycle.
The reference scans the whole space of every answered request.
"""
from __future__ import annotations

import itertools

from chipbench import reference
from chipbench.capture import DeviceRows
from chipbench.traffic import answer_of, rng

MB = 1 << 20


def _pairs(mix: dict) -> list:
    return [(o, int(round(mb * MB)))
            for o in mix["objectives"] for mb in mix["sram_budgets_mb"]]


def requests(mix: dict, seed: int, run_lengths: list[int]):
    r = rng(seed, 0)
    pairs = _pairs(mix)
    work = reference.space_size([n + 1 for n in run_lengths])
    ids = itertools.count()
    while True:
        for j in r.permutation(len(pairs)).tolist():
            obj, budget = pairs[j]
            yield {"id": next(ids), "objective": obj, "sram_budget": budget,
                   "work": work}


def warmup_requests(mix: dict, run_lengths: list[int]) -> list:
    """One request per pair: ``_make_fused`` bakes the SRAM budget into
    the fused step as a constant, so each pair is a program of its own."""
    return [{"id": -1 - i, "objective": o, "sram_budget": b, "work": 0}
            for i, (o, b) in enumerate(_pairs(mix))]


class Target:
    def __init__(self, cfg: dict, mix: dict):
        from repro.cnn import build_cnn
        from repro.core.cutpoint import monotone_runs, split_blocks
        from repro.core.grouping import group_nodes
        self.cfg = cfg
        self.graph = build_cnn(cfg["network"], int(cfg["input_size"]))
        self._runs = monotone_runs(split_blocks(group_nodes(self.graph)))
        self.plans: dict = {}       # request key -> one plan, for the verifier
        self.rows = DeviceRows(cfg["engine"])

    def run_lengths(self) -> list[int]:
        return [len(r) for r in self._runs]

    def serve(self, req: dict) -> dict:
        from repro.core.compiler import compile_graph
        from repro.core.hw import FPGAConfig
        from repro.core.options import CompileOptions
        hw = FPGAConfig(**{**self.cfg["hw"],
                           "sram_budget": int(req["sram_budget"])})
        opts = CompileOptions(engine=self.cfg["engine"],
                              objective=req["objective"])
        self.rows.take()
        plan = compile_graph(self.graph, hw, opts)
        out = answer_of(plan.search.best, plan.search.evaluated)
        out["plan_latency_cycles"] = float(plan.latency.cycles)
        out["plan_dram_total"] = int(plan.dram.total)
        out["plan_sram_total"] = int(plan.sram.sram_total)
        out["device_rows"] = self.rows.take()
        if req["id"] >= 0:
            self.plans.setdefault((req["objective"], req["sram_budget"]),
                                  plan)
        return out

    def close(self) -> dict:
        """``verifier_errors``: error-severity findings of the strict plan
        verifier over one plan of each distinct request the window
        served."""
        from repro.analysis import errors_of, verify_execution_plan
        errors = sum(len(errors_of(verify_execution_plan(p)))
                     for p in self.plans.values())
        self.rows.close()
        self.plans = {}
        return {"verifier_errors": (errors, 0)}


def items(cfg: dict, mix: dict, seed: int, answered: list,
          run_lengths: list[int]) -> list:
    out = []
    for r in answered:
        req = r["req"]
        dims = [n + 1 for n in run_lengths]
        out.append({"req": req, "answer": r["answer"],
                    "objective": req["objective"],
                    "budget": int(req["sram_budget"]),
                    "evaluated": reference.space_size(dims),
                    "bounds": ([], dims), "scan": True})
    return out
