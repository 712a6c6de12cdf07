"""Time a compile request spends on the first call of its newly built
fused step (trace, lowering, persistent-cache load, first run), in ms: per
``compile`` request, the sum of its ``pipeline.load`` spans; the mean over
requests (kernels/search_pipeline.py ``_run_lax``)."""
from chipbench.spans import mean_per_request_ms, ms


def read(ctx):
    return mean_per_request_ms("compile", lambda top, recs: sum(
        ms(r) for r in recs if r.name == "pipeline.load"))
