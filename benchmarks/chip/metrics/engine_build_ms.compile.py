"""Time a compile request spends building its search engine and packing
its device tables, in ms: per ``compile`` request, its ``search.engine``
(core/cutpoint.py ``search``) and ``pipeline.tables``
(kernels/search_pipeline.py ``_engine_tables``) spans; the mean over
requests."""
from chipbench.spans import mean_per_request_ms, ms


def read(ctx):
    return mean_per_request_ms("compile", lambda top, recs: sum(
        ms(r) for r in recs
        if r.name in ("search.engine", "pipeline.tables")))
