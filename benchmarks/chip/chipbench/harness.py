"""One run of one cell: set-up, the measured window, the comparison, the result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

1. The cell is found by name in ``BENCHMARK.json``; its configuration,
   traffic mix, request kind, loop and metrics are files of their own
   (``registry.py``).
2. The device check: exactly the cell's number of TPU chips, or exit
   non-zero without a result.
3. Set-up: jax's persistent compilation cache in ``.jax_cache/`` at the
   checkout's root (or where ``JAX_COMPILATION_CACHE_DIR`` says), the
   system under test built from the configuration (the kind's
   ``Target``), and the kind's warm-up requests, one per program the mix
   can use, which compile or load each of them.  ``setup_s`` runs from
   the process's start to the window's.
4. The window: the mix's loop offers the kind's requests from ``--seed``
   for ``--seconds``.  ``--trace 1`` records the window's first
   ``TRACE_SECONDS`` under the profiler (``Tracer``).
5. After the window: the device's memory peak, then (the program's state
   freed) the plain reference's comparison with what the window produced
   (``judge.py``).
6. The result: end-to-end metrics with ``--trace 0``, per-layer metrics
   with ``--trace 1``, and the numbers compared beside their limits.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import os
import shutil
import tempfile
import time
from types import SimpleNamespace

from chipbench import judge, reference, registry, result
from chipbench.capture import expects_rows
from chipbench.device import (CompileCounter, describe, log,
                              memory_peak_bytes, require_tpu)


# Seconds of the window a traced run records (see Tracer).
TRACE_SECONDS = 4.0


@contextlib.contextmanager
def span(name: str, on: bool):
    if on:
        import jax
        with jax.profiler.TraceAnnotation("bench:" + name):
            yield
    else:
        yield


@contextlib.contextmanager
def search_spans(spans: dict, on: bool):
    """Traced runs: a host-clock span around ``core/compiler.py``'s call
    to the cut-point search."""
    if not on:
        yield
        return
    import repro.core.compiler as compiler
    orig = compiler.search
    walls = spans.setdefault("compiler.search", [])

    def timed(*args, **kwargs):
        t = time.perf_counter()
        try:
            with span("compiler.search", True):
                return orig(*args, **kwargs)
        finally:
            walls.append(time.perf_counter() - t)

    compiler.search = timed
    try:
        yield
    finally:
        compiler.search = orig


class Tracer:
    """The profiler over the first ``seconds`` of the window.

    A whole window at full length holds more device events than the
    profiler keeps, and reading them would outlast the run's time limit,
    so a traced run records a fixed leading part of its window; the rest
    of the window runs untraced.  ``bench:window`` spans the traced part."""

    def __init__(self, logdir: str, seconds: float):
        import jax
        self.jax, self.logdir, self.seconds = jax, logdir, seconds
        self.on = False
        self.traced_s = 0.0
        self.stop_s = 0.0

    def start(self) -> None:
        opts = self.jax.profiler.ProfileOptions()
        # the benchmark's own spans, jax's and the runtime's; no Python
        # function tracing, which would slow the host it measures
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self._span = self.jax.profiler.TraceAnnotation("bench:window")
        self._span.__enter__()
        self.t0 = time.perf_counter()
        self.on = True

    def maybe_stop(self, force: bool = False) -> None:
        if self.on and (force or
                        time.perf_counter() - self.t0 >= self.seconds):
            self._span.__exit__(None, None, None)
            self.traced_s = time.perf_counter() - self.t0
            self.jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - self.t0 - self.traced_s
            self.on = False


class Window:
    """What a loop (``loops/<loop>.py``) calls around each request: the
    profiler's start and stop, a span per request, and the call itself,
    whose exception counts the request as failed."""

    def __init__(self, tracer: Tracer | None):
        self.tracer = tracer

    def start(self) -> None:
        if self.tracer is not None:
            self.tracer.start()

    def serve(self, target, req) -> tuple:
        with span("request", self.tracer is not None and self.tracer.on):
            try:
                return target.serve(req), None
            except Exception as e:          # counted as failed
                return None, repr(e)

    def tick(self) -> None:
        if self.tracer is not None:
            self.tracer.maybe_stop()

    def stop(self) -> None:
        if self.tracer is not None:
            self.tracer.maybe_stop(force=True)


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, check_device: bool = True,
             ref_workers: int | None = None) -> bool:
    """One run; prints the earlier lines and the result, returns
    ``correct``."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(registry.ROOT / ".jax_cache"))
    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devs = require_tpu(cell.chips) if check_device else jax.devices()
    device = describe(devs)
    counter = CompileCounter()
    log(f"cell {cell.name}: config {cell.config['name']}, traffic "
        f"{cell.traffic['name']}; device {device}; compile cache "
        f"{cache_dir}")

    cfg, mix = cell.config, cell.traffic
    kind, loop = cell.kind(), cell.loop()
    lengths = reference.check_config(cfg)
    target = kind.Target(cfg, mix)
    if target.run_lengths() != lengths:
        raise RuntimeError(f"the program's monotone runs "
                           f"{target.run_lengths()} differ from the "
                           f"reference's {lengths}")
    warm = kind.warmup_requests(mix, lengths)
    t_warm = time.perf_counter()
    for req in warm:
        target.serve(req)
    before = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} (warm-up of {len(warm)} programs "
        f"{time.perf_counter() - t_warm:.3f}s); compiles in set-up "
        f"{before}")

    spans: dict = {}
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    tracer = Tracer(logdir, min(seconds, TRACE_SECONDS)) if trace else None
    stream = kind.requests(mix, seed, lengths)
    with search_spans(spans, trace):
        served, window_s = loop.run(target, stream, seconds, mix, seed,
                                    Window(tracer))
    after = counter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    device["memory_peak_bytes"] = memory_peak_bytes(devs)
    gaps = [b["t_sub"] - (a["t_sub"] + a["latency_s"])
            for a, b in zip(served, served[1:])]
    errors = [r for r in served if r["error"] is not None]
    log(f"window_s={window_s:.3f} requests={len(served)} "
        f"errors={len(errors)}; client gap between requests "
        f"max={max(gaps, default=0.0) * 1e3:.3f}ms "
        f"total={sum(gaps) * 1e3:.3f}ms; in the window: {in_window}")
    for r in errors[:3]:
        log(f"request {r['req']['id']} raised {r['error']}")

    checks = target.close()
    del target
    gc.collect()

    answered = [r for r in served if r["answer"] is not None]
    t_ref = time.perf_counter()
    checks.update(judge.judge(cfg, kind.items(cfg, mix, seed, answered,
                                              lengths),
                              rows_expected=expects_rows(cfg["engine"]),
                              workers=ref_workers, log=log))
    log(f"reference: {len(answered)} answered requests compared, "
        f"{time.perf_counter() - t_ref:.3f}s")
    checks["errors"] = (len(errors), 0)

    metrics, breakdown = {}, None
    if trace:
        from chipbench import trace as tr
        t_read = time.perf_counter()
        path = tr.find_xplane(logdir)
        summary = tr.load(path) if path is not None else None
        log(f"trace: {tracer.traced_s:.3f}s of the window traced, "
            f"stop_trace {tracer.stop_s:.3f}s, read "
            f"{time.perf_counter() - t_read:.3f}s")
        shutil.rmtree(logdir, ignore_errors=True)
        ctx = SimpleNamespace(trace=summary, spans=spans, cell=cell.name,
                              chips=cell.chips)
        for spec in cell.per_layer:
            value = registry.load_reader(spec["name"], cell.bench_dir)(ctx)
            if value is not None:
                metrics[spec["name"]] = (value, spec["unit"])
        if summary is not None:
            device["busy_s"] = summary.mean_busy_ns() / 1e9
            device["window_s"] = summary.window_ns / 1e9
            breakdown = summary.breakdown()
    else:
        ctx = SimpleNamespace(served=served, window_s=window_s,
                              setup_s=setup_s)
        for spec in cell.end_to_end:
            value = registry.load_piece("end_to_end", spec["name"],
                                        cell.bench_dir).value(ctx)
            metrics[spec["name"]] = (value, spec["unit"])
    return result.emit(attempted=len(served),
                       failed=len(errors) + checks["wrong_answers"][0],
                       metrics=metrics,
                       device=device, checks=checks, breakdown=breakdown)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = registry.resolve_cell(args.workload)
    run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    return 0
