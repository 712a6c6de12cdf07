"""Host wait for one launch's winner row, in ms: the mean
``pipeline.wait`` span (the device time left after dispatch, then the
device-to-host copy) of the program's sub-space searches
(kernels/search_pipeline.py ``_run_lax``)."""
from chipbench.spans import mean_span_ms


def read(ctx):
    return mean_span_ms("pipeline.subspace", "pipeline.wait")
