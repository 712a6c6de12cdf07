"""Median latency of the window's compile requests, on the host clock."""
import statistics


def value(ctx):
    return statistics.median(r["latency_s"] for r in ctx.served)
