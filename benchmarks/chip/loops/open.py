"""Open loop at a fixed offered rate, one server: requests arrive at
``rate_per_s`` whatever the system does, and wait in arrival order while it
is busy.  The gaps between arrivals are the exponential distribution's
quantiles at evenly spaced levels, one set for all seeds, in an order drawn
from the seed, so every seed offers the same load.  Requests that arrive
within ``--seconds`` are served; the window closes when the last of them
returns.  A request's latency runs from its arrival, so it counts the wait
in the queue."""
from __future__ import annotations

import math
import time

from chipbench.traffic import rng


def arrivals(rate: float, seconds: float, seed: int) -> list[float]:
    n = max(1, math.ceil(rate * seconds))
    gaps = [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]
    order = rng(seed, 2).permutation(n).tolist()
    out, t = [], 0.0
    for k in order:
        t += gaps[k]
        if t >= seconds:
            break
        out.append(t)
    return out


def run(target, stream, seconds: float, mix: dict, seed: int, win) -> tuple:
    served = []
    t0 = time.perf_counter()
    win.start()
    for at in arrivals(float(mix["rate_per_s"]), seconds, seed):
        t_arr = t0 + at
        wait = t_arr - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        req = next(stream)
        t_sub = time.perf_counter()
        answer, error = win.serve(target, req)
        t_done = time.perf_counter()
        served.append({"req": req, "answer": answer, "error": error,
                       "t_sub": t_sub, "latency_s": t_done - t_arr})
        win.tick()
    window_s = max(time.perf_counter() - t0, seconds)
    win.stop()
    return served, window_s
