"""Run the compiler's device search on a TPU through its normal entry points.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded search only

One chip:

1. **Device check** -- ``jax.devices()`` must report a TPU; anything else
   exits non-zero before any work, never falling back to the CPU.
2. **Requests** -- a ``CompileService`` per device engine
   (``pipeline:lax``, ``device:pallas``), each with its plan cache in a
   fresh directory under ``--out``, answers vgg16-conv@224,
   resnet50@224, resnet152@224 and yolov2@416 (the full 7,962,624-tuple
   exhaustive space) on KCU1500.
3. **Comparison** -- every result is checked against a host
   ``engine="journal"`` search of the same request: the cuts, every
   ``CandidateMetrics`` field and ``evaluated`` must match, and the
   result may carry no ``device_fallback`` event.
4. **Cache hit** -- one request is repeated; it must be a hit whose
   ``encode_plan`` bytes equal the miss's.
5. **Plan audit** -- the resnet50@224 plan's instruction stream is
   executed by the functional simulator on the device and compared with
   the op-by-op JAX reference, both at float32 matmul precision.

``--chips 4`` runs only the yolov2@416 ``pipeline:lax`` search, which
shards its index range over every visible device, and its journal
reference.

Earlier lines report counts and one-off wall times per request (not a
benchmark).  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check raises, so the script exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

REQUESTS = (("vgg16-conv", 224), ("resnet50", 224), ("resnet152", 224),
            ("yolov2", 416))
ENGINES = ("pipeline:lax", "device:pallas")
REPEAT = ("resnet50", 224, "pipeline:lax")
AUDIT = ("resnet50", 224)
SHARDED = ("yolov2", 416, "pipeline:lax")
METRICS = ("latency_cycles", "dram_total", "dram_fm", "sram_total",
           "bram18k", "feasible")
# simulator vs reference on the audited network's output
AUDIT_RTOL = AUDIT_ATOL = 1e-4


class SmokeFailure(RuntimeError):
    """A device result disagreed with its reference."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    """The device check: exactly ``chips`` TPU devices, or exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (platform "
                 f"{devs[0].platform!r}); refusing to run on the CPU")
    if len(devs) != chips:
        sys.exit(f"chip_smoke: --chips {chips} but jax sees {len(devs)} "
                 f"devices")
    return devs


def cache_counter() -> dict:
    """Counts of jax persistent-cache hits and misses in this process."""
    import jax
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_kw):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def journal_references(graphs: dict, workers: int) -> dict:
    """The host oracle: the journal engine's search of every graph, over
    ``workers`` spawn-started processes (this process already runs jax,
    whose threads a forked child must not inherit)."""
    from repro.core.grouping import group_nodes
    from repro.core.hw import KCU1500
    from repro.core.options import CompileOptions
    from repro.core.search_pool import ParallelSearchDriver
    opts = CompileOptions(engine="journal", workers=workers)
    refs = {}
    with ParallelSearchDriver(workers=workers, mp_context="spawn") as pool:
        for key, g in graphs.items():
            t0 = time.perf_counter()
            refs[key] = ref = pool.search(group_nodes(g), KCU1500, opts)
            log(f"journal {key[0]}@{key[1]}: "
                f"wall_s={time.perf_counter() - t0:.3f} "
                f"evaluated={ref.evaluated} cuts={ref.best.cuts}")
    return refs


def compare(tag: str, got, want) -> None:
    """Cuts, every metric and ``evaluated`` equal; no hidden fallback."""
    falls = [e for e in got.events if e.kind == "device_fallback"]
    if falls:
        raise SmokeFailure(f"{tag}: device_fallback events {falls}")
    diffs = []
    if got.best.cuts != want.best.cuts:
        diffs.append(f"cuts {got.best.cuts} != journal {want.best.cuts}")
    for f in METRICS:
        a, b = getattr(got.best, f), getattr(want.best, f)
        if a != b:
            diffs.append(f"{f} {a!r} != journal {b!r}")
    if got.evaluated != want.evaluated:
        diffs.append(f"evaluated {got.evaluated} != journal "
                     f"{want.evaluated}")
    if diffs:
        raise SmokeFailure(f"{tag}: " + "; ".join(diffs))


def fresh_dir(path: Path) -> Path:
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


def serve_and_compare(requests, engines, out: Path, journal_workers: int,
                      repeat=None) -> dict:
    """Phases 2-4: each engine's service answers every request, each
    answer is checked against the journal search, and ``repeat`` (a
    ``(net, size, engine)`` already served) is asked again and must hit
    byte-identically.  Returns the served plans keyed by (net, size,
    engine)."""
    from repro.cnn import build_cnn
    from repro.core.hw import KCU1500
    from repro.core.options import CompileOptions
    from repro.service import CompileService
    from repro.service.codec import encode_plan

    graphs = {(n, s): build_cnn(n, s) for n, s in requests}
    refs = journal_references(graphs, journal_workers)
    plans = {}
    for engine in engines:
        opts = CompileOptions(engine=engine, verify="strict")
        cache_dir = fresh_dir(out / "plan_cache" / engine.replace(":", "_"))
        with CompileService(cache_dir, options=opts) as svc:
            for (n, s), g in graphs.items():
                ticket = svc.submit(g, KCU1500, opts)
                plan = ticket.result()
                if ticket.hit:
                    raise SmokeFailure(f"{n}@{s} {engine}: first request "
                                       f"hit a fresh cache")
                compare(f"{n}@{s} {engine}", plan.search, refs[n, s])
                plans[n, s, engine] = plan
                log(f"request {n}@{s} engine={engine} miss "
                    f"wall_s={ticket.service_s:.3f} "
                    f"evaluated={plan.search.evaluated} "
                    f"journal_match=true device_fallback=0")
            if repeat is not None and repeat[2] == engine:
                n, s, _ = repeat
                ticket = svc.submit(graphs[n, s], KCU1500, opts)
                hit = ticket.result()
                same = encode_plan(hit) == encode_plan(plans[repeat])
                if not (ticket.hit and same):
                    raise SmokeFailure(f"repeat {n}@{s} {engine}: hit="
                                       f"{ticket.hit} bytes_equal={same}")
                log(f"request {n}@{s} engine={engine} hit "
                    f"wall_s={ticket.service_s:.3f} bytes_equal=true")
    return plans


def plan_audit(plan) -> float:
    """Phase 5: run the plan's instruction stream through the functional
    simulator and compare with the op-by-op reference; returns the
    largest absolute difference."""
    import jax
    import numpy as np
    from repro.cnn.jax_ref import init_params, run_graph
    from repro.core.simulator import simulate

    g = plan.graph
    params = init_params(g, seed=0)
    size = g.nodes[0].out_h
    x = np.random.default_rng(0).standard_normal(
        (1, size, size, 3), dtype=np.float32)
    with jax.default_matmul_precision("float32"):
        out, _ = simulate(plan.grouped, plan.alloc, plan.instructions,
                          params, x, execute=True)
        ref = run_graph(g, params, x)[len(g.nodes) - 1]
    out, ref = np.asarray(out), np.asarray(ref)
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        raise SmokeFailure(f"audit {g.name}: shape {out.shape} vs "
                           f"{ref.shape} or non-finite output")
    np.testing.assert_allclose(out, ref, rtol=AUDIT_RTOL, atol=AUDIT_ATOL)
    return float(np.max(np.abs(out - ref)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the yolov2@416 search sharded over "
                         "four chips and its journal reference")
    ap.add_argument("--out", type=Path,
                    default=ROOT / "build" / "chip_smoke",
                    help="directory for the plan caches")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = require_tpu(args.chips)
    counts = cache_counter()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device {device}; jax compile cache {cache_dir}")
    workers = max(1, min(16, (os.cpu_count() or 1) - 1))
    t0 = time.perf_counter()
    if args.chips == 1:
        plans = serve_and_compare(REQUESTS, ENGINES, args.out, workers,
                                  repeat=REPEAT)
        err = plan_audit(plans[AUDIT + ("pipeline:lax",)])
        log(f"audit {AUDIT[0]}@{AUDIT[1]}: simulator vs reference max "
            f"abs diff {err:.3e} (rtol={AUDIT_RTOL}, atol={AUDIT_ATOL}, "
            f"float32 matmul precision)")
    else:
        net, size, engine = SHARDED
        serve_and_compare([(net, size)], [engine], args.out, workers)
        log(f"sharded {net}@{size} engine={engine} over {len(devs)} "
            f"devices: journal_match=true")
    log(f"total wall_s={time.perf_counter() - t0:.3f}; jax compile cache "
        f"hits={counts['hits']} misses={counts['misses']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
