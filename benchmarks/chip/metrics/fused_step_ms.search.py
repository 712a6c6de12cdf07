"""Device time of one launch of the fused search step, in ms: the mean
duration of its module's events in the trace, over every launch on every
chip (kernels/search_pipeline.py ``_make_fused`` / ``_shard_fused``)."""
from chipbench.kernels import FUSED_STEP


def read(ctx):
    if ctx.trace is None:
        return None
    durs = [e - s for evs in ctx.trace.launches(FUSED_STEP).values()
            for _, s, e in evs]
    return sum(durs) / len(durs) / 1e6 if durs else None
