"""Host time of a compile request outside the cut-point search, in ms:
per ``compile`` request (core/compiler.py ``compile_graph``), its duration
minus its ``compile.search`` span -- grouping, materialisation, codegen,
verification; the mean over requests."""
from chipbench.spans import mean_per_request_ms, ms


def read(ctx):
    return mean_per_request_ms("compile", lambda top, recs: ms(top) - sum(
        ms(r) for r in recs if r.name == "compile.search"))
