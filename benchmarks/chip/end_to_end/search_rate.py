"""Candidates decided per second: the candidates of every sub-space search
the window completed, each counted whole, over the whole window."""


def value(ctx):
    return sum(r["req"]["work"] for r in ctx.served
               if r["answer"] is not None) / ctx.window_s
