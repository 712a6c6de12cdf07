"""Host time to hand one launch of the fused search step to the device, in
ms: the mean ``pipeline.dispatch`` span (argument conversion and upload,
enqueue) of the program's sub-space searches
(kernels/search_pipeline.py ``_run_lax``)."""
from chipbench.spans import mean_span_ms


def read(ctx):
    return mean_span_ms("pipeline.subspace", "pipeline.dispatch")
