"""Host time of the compiler's cut-point search per compile request, in
ms: the mean of a host-clock span the benchmark places around
``core/compiler.py``'s call to ``core/cutpoint.search`` in traced runs."""


def read(ctx):
    walls = ctx.spans.get("compiler.search", [])
    return 1e3 * sum(walls) / len(walls) if walls else None
