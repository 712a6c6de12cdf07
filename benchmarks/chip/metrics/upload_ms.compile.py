"""Time a compile request spends putting its engine's fused-step tables on
the device, in ms: per ``compile`` request, the sum of its
``pipeline.upload`` spans (kernels/search_pipeline.py ``_device_tables``,
once per new engine); the mean over requests.  A program that opens no
such span in any request has nothing to read."""
from chipbench.spans import mean_per_request_ms, ms, requests


def read(ctx):
    if not any(r.name == "pipeline.upload"
               for _, recs in requests("compile") for r in recs):
        return None
    return mean_per_request_ms("compile", lambda top, recs: sum(
        ms(r) for r in recs if r.name == "pipeline.upload"))
