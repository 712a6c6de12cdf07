"""The cut space (paper §IV-B): blocks, monotone runs, and the reuse policy
of one cut tuple.

A *block* is a residual block or a standalone group (Fig. 10); all groups
in a block share one reuse mode.  Feature-map sizes are monotone within
runs of blocks, so a plan has one cut per monotone run (Fig. 11/12):
within a decreasing run the blocks from the cut on run frame-reuse, within
an increasing run the blocks before the cut do.
"""
from __future__ import annotations

from dataclasses import dataclass

from plainref.allocator import Policy
from plainref.grouping import GroupedGraph


@dataclass
class Block:
    bid: int
    gids: list[int]
    out_size: int                 # feature-map bytes at block output


def split_blocks(gg: GroupedGraph) -> list[Block]:
    """Residual blocks (groups up to and including a fused/standalone add
    whose shortcut source is inside the window) + standalone groups."""
    blocks: list[Block] = []
    current: list[int] = []
    open_shortcuts: set[int] = set()     # gids still awaited as shortcut src

    for g in gg.groups:
        current.append(g.gid)
        # does any later group take this one as a shortcut operand?
        for c in gg.group_consumers(g):
            cg = gg.groups[c]
            if cg.fused_add is not None and gg.shortcut_source_group(cg) == g.gid:
                if c - g.gid <= 8:       # short-path residual
                    open_shortcuts.add(g.gid)
        if g.fused_add is not None:
            src = gg.shortcut_source_group(g)
            open_shortcuts.discard(src)
        if not open_shortcuts:
            blocks.append(Block(bid=len(blocks), gids=current,
                                out_size=g.out_size))
            current = []
    if current:
        blocks.append(Block(bid=len(blocks), gids=current,
                            out_size=gg.groups[current[-1]].out_size))
    return blocks


def monotone_runs(blocks: list[Block]) -> list[list[int]]:
    """Split block indices into monotone runs of out_size (ties extend)."""
    if not blocks:
        return []
    runs: list[list[int]] = [[0]]
    direction = 0
    for i in range(1, len(blocks)):
        prev, cur = blocks[i - 1].out_size, blocks[i].out_size
        d = 0 if cur == prev else (1 if cur > prev else -1)
        if d == 0 or direction == 0 or d == direction:
            runs[-1].append(i)
            if d != 0:
                direction = d
        else:
            runs.append([i])
            direction = d
    return runs


def _run_direction(blocks: list[Block], run: list[int]) -> int:
    return 1 if blocks[run[-1]].out_size >= blocks[run[0]].out_size else -1


def policy_from_cuts(gg: GroupedGraph, blocks: list[Block],
                     runs: list[list[int]], cuts: tuple[int, ...]) -> Policy:
    """cut c in run r: for decreasing runs blocks[run[c:]] are frame-reuse;
    for increasing runs blocks[run[:c]] are frame-reuse."""
    mode_by_block: dict[int, str] = {}
    for run, cut in zip(runs, cuts):
        d = _run_direction(blocks, run)
        for pos, b in enumerate(run):
            if d < 0:
                mode_by_block[b] = "frame" if pos >= cut else "row"
            else:
                mode_by_block[b] = "frame" if pos < cut else "row"
    policy: Policy = {}
    for b, mode in mode_by_block.items():
        for gid in blocks[b].gids:
            policy[gid] = mode
    return policy
