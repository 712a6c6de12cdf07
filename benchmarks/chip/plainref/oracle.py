"""The plain oracle: one cut tuple in, its metrics out, from scratch.

For every candidate it builds the reuse policy, runs the allocator
(Algorithm 1) over the whole graph, and reads the SRAM report (eqs. 1-7),
the DRAM model (eqs. 8-9) and the latency model group by group.  Nothing
is cached between candidates but the graph itself: no checkpoints, no
tables, no batches.

A candidate's metrics do not depend on the SRAM budget, which decides only
feasibility, so one pricing answers every budget.
"""
from __future__ import annotations

from typing import NamedTuple

from plainref.allocator import allocate, frame_feasible
from plainref.blocks import monotone_runs, policy_from_cuts, split_blocks
from plainref.dram import dram_report
from plainref.grouping import group_nodes
from plainref.hw import FPGAConfig
from plainref.sram import sram_report
from plainref.timing import latency_cycles
from plainref.zoo import build_cnn


class Price(NamedTuple):
    latency_cycles: float
    dram_total: int
    dram_fm: int
    sram_total: int
    bram18k: int
    frame_ok: bool             # constraint (10): only long-path data spilled

    def feasible(self, budget: int) -> bool:
        return self.frame_ok and self.sram_total <= budget

    def key(self, objective: str, budget: int) -> tuple:
        """The objective's order: ``(infeasible, primary, secondary)``."""
        rank = 0.0 if self.feasible(budget) else 1.0
        lat, sram = float(self.latency_cycles), float(self.sram_total)
        if objective == "latency":
            return rank, lat, sram
        if objective == "sram":
            return rank, sram, lat
        if objective == "dram":
            return rank, float(self.dram_total), lat
        raise ValueError(f"unknown objective {objective!r}")


class Oracle:
    def __init__(self, network: str, input_size: int, hw: dict):
        self.gg = group_nodes(build_cnn(network, int(input_size)))
        self.blocks = split_blocks(self.gg)
        self.runs = monotone_runs(self.blocks)
        self.hw = FPGAConfig(**hw)

    def run_lengths(self) -> list[int]:
        return [len(r) for r in self.runs]

    def price(self, cuts) -> Price:
        cuts = tuple(int(c) for c in cuts)
        if len(cuts) != len(self.runs) or any(
                not 0 <= c <= len(r) for c, r in zip(cuts, self.runs)):
            raise ValueError(f"{cuts} is no cut tuple of runs "
                             f"{self.run_lengths()}")
        policy = policy_from_cuts(self.gg, self.blocks, self.runs, cuts)
        alloc = allocate(self.gg, policy)
        sram = sram_report(self.gg, alloc, self.hw)
        dram = dram_report(self.gg, alloc)
        return Price(latency_cycles=latency_cycles(self.gg, alloc, self.hw),
                     dram_total=dram.total, dram_fm=dram.fm_bytes,
                     sram_total=sram.sram_total, bram18k=sram.bram18k,
                     frame_ok=frame_feasible(self.gg, policy, alloc))
