"""Device-idle gap between consecutive launches of the fused search step,
in ms: the mean, over every chip, of the time from one launch's end to the
next one's start (the host's launch, sync and fold in ``_run_lax``)."""
from chipbench.kernels import FUSED_STEP


def read(ctx):
    if ctx.trace is None:
        return None
    gaps = [b[1] - a[2]
            for evs in ctx.trace.launches(FUSED_STEP).values()
            for a, b in zip(evs, evs[1:])]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
