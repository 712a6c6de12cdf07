"""Closed loop, one client: each request is sent when the previous one has
returned, until ``--seconds`` have passed.  The window closes when the last
request returns, so every request counts whole; a request's latency runs
from its sending to its return."""
from __future__ import annotations

import time


def run(target, stream, seconds: float, mix: dict, seed: int, win) -> tuple:
    """``(served, window_s)``; ``win.serve`` calls the target."""
    served = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    win.start()
    while time.perf_counter() < deadline:
        req = next(stream)
        t_sub = time.perf_counter()
        answer, error = win.serve(target, req)
        t_done = time.perf_counter()
        served.append({"req": req, "answer": answer, "error": error,
                       "t_sub": t_sub, "latency_s": t_done - t_sub})
        win.tick()
    window_s = time.perf_counter() - t0
    win.stop()
    return served, window_s
