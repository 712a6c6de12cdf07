"""Which spaces are searched exhaustively: the exhaustive limit resolved from
the engine and the platform (``CompileOptions.exhaustive_limit_for``), the
plan key that carries it, the search pool and the service reading that one value,
and the exact path on an MBConv + squeeze-excite graph against brute force.
"""
import dataclasses
import itertools
import warnings

import pytest

from repro.cnn import build_cnn
from repro.core import cutpoint as cutpoint_mod
from repro.core import options as options_mod
from repro.core.cutpoint import evaluate, monotone_runs, search, split_blocks
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500
from repro.core.ir import Graph, make_input
from repro.core.options import (DEVICE_EXHAUSTIVE_LIMIT, EXHAUSTIVE_LIMIT,
                                CompileOptions)
from repro.core.search_pool import ParallelSearchDriver
from repro.service import CompileService

# mobilenet-v3@224: 15,116,544 cut tuples, between the two limits
MOBILENET_SPACE = 15_116_544


def _space(graph) -> int:
    size = 1
    for r in monotone_runs(split_blocks(group_nodes(graph))):
        size *= len(r) + 1
    return size


# ------------------------------------------------------------- resolution
def test_limits_bracket_mobilenet_v3():
    assert _space(build_cnn("mobilenet-v3", 224)) == MOBILENET_SPACE
    assert EXHAUSTIVE_LIMIT < MOBILENET_SPACE <= DEVICE_EXHAUSTIVE_LIMIT
    assert DEVICE_EXHAUSTIVE_LIMIT == 2 ** 25


@pytest.mark.parametrize("engine", ["journal", "device", "device:scan",
                                    "pipeline:reference", "pipeline:lax",
                                    "pipeline"])
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_an_explicit_limit_wins(engine, platform):
    opts = CompileOptions(engine=engine, exhaustive_limit=1234)
    assert opts.exhaustive_limit_for(platform) == 1234
    assert dict(opts.plan_key(platform))["exhaustive_limit"] == 1234


@pytest.mark.parametrize("engine", ["journal", "device", "device:scan",
                                    "device:pallas", "pipeline:reference",
                                    "pipeline:lax", "pipeline:pallas",
                                    "pipeline"])
def test_the_cpu_keeps_the_host_limit(engine):
    opts = CompileOptions(engine=engine)
    assert opts.exhaustive_limit is None
    assert opts.exhaustive_limit_for("cpu") == EXHAUSTIVE_LIMIT
    # the tests run on jax's CPU backend: the observed platform agrees
    assert opts.exhaustive_limit_for() == EXHAUSTIVE_LIMIT


@pytest.mark.parametrize("engine,limit", [
    ("pipeline:lax", DEVICE_EXHAUSTIVE_LIMIT),
    ("pipeline:lax@4096", DEVICE_EXHAUSTIVE_LIMIT),
    ("pipeline", DEVICE_EXHAUSTIVE_LIMIT),         # auto: lax under jax
    ("pipeline:pallas", EXHAUSTIVE_LIMIT),         # refuses to run on a TPU
    ("pipeline:reference", EXHAUSTIVE_LIMIT),
    ("device:scan", EXHAUSTIVE_LIMIT),             # only the replay on device
    ("device:pallas", EXHAUSTIVE_LIMIT),
    ("journal", EXHAUSTIVE_LIMIT),
])
def test_an_accelerator_raises_only_the_fused_pipeline(engine, limit):
    assert CompileOptions(engine=engine).exhaustive_limit_for("tpu") == limit


def test_plan_key_carries_the_resolved_limit():
    opts = CompileOptions(engine="pipeline:lax")
    on_cpu, on_tpu = opts.plan_key("cpu"), opts.plan_key("tpu")
    assert on_cpu != on_tpu
    assert dict(on_cpu)["exhaustive_limit"] == EXHAUSTIVE_LIMIT
    assert dict(on_tpu)["exhaustive_limit"] == DEVICE_EXHAUSTIVE_LIMIT
    # the host default keys as the explicit host limit always did
    assert on_cpu == CompileOptions(
        engine="pipeline:lax", exhaustive_limit=EXHAUSTIVE_LIMIT).plan_key()
    assert opts.plan_key() == on_cpu


# ---------------------------------------------- one value at every site
def test_the_search_pool_searches_by_the_resolved_limit(monkeypatch):
    gg = group_nodes(build_cnn("vgg16-conv", 64))
    with ParallelSearchDriver(workers=2) as pool:
        assert pool.search(gg, KCU1500).path == "exhaustive"
        monkeypatch.setattr(options_mod, "EXHAUSTIVE_LIMIT", 1)
        assert pool.search(gg, KCU1500).path == "descent"
        # a jax engine runs inline, on the pool's resolved value
        monkeypatch.setattr(options_mod, "jax_backend", lambda: "tpu")
        monkeypatch.setattr(options_mod, "DEVICE_EXHAUSTIVE_LIMIT", 1)
        res = pool.search(gg, KCU1500,
                            CompileOptions(engine="pipeline:lax", workers=2))
        assert res.path == "descent"


def test_the_service_warm_start_reads_the_resolved_limit(tmp_path,
                                                         monkeypatch):
    """A descent-path request seeds only from exhaustive donors; which
    path a request takes follows the resolved limit."""
    g = build_cnn("mobilenet-v3", 224)
    calls = []
    with CompileService(tmp_path) as svc:
        monkeypatch.setattr(svc.cache, "nearest",
                            lambda fp, sig, **kw: calls.append(kw))
        lax = CompileOptions(engine="pipeline:lax")
        svc._warm_start(g, "fp", [], lax)                  # CPU: descent
        svc._warm_start(g, "fp", [],
                        lax.replace(exhaustive_limit=MOBILENET_SPACE))
        monkeypatch.setattr(options_mod, "jax_backend", lambda: "tpu")
        svc._warm_start(g, "fp", [], lax)                  # exact
        svc._warm_start(g, "fp", [], CompileOptions())     # journal: descent
    assert calls == [{"require_path": "exhaustive"}, {}, {},
                     {"require_path": "exhaustive"}]


class _Stop(Exception):
    pass


@pytest.mark.parametrize("engine,platform,warns", [
    ("journal", "tpu", True),          # the host engine, on any platform
    ("pipeline:lax", "cpu", True),     # jax on the CPU is the host
    ("pipeline:lax", "tpu", False),
    ("device:scan", "tpu", False),
])
def test_only_a_host_search_warns_of_its_length(monkeypatch, engine,
                                                platform, warns):
    """yolov2@416's unpruned 7.96M tuples: the warning names the host
    core it would take, and the device path stays silent."""
    monkeypatch.setattr(options_mod, "jax_backend", lambda: platform)
    monkeypatch.setattr(cutpoint_mod, "jax_backend", lambda: platform)

    def stop(*args, **kwargs):
        raise _Stop
    monkeypatch.setattr(cutpoint_mod.CutpointEngine, "run_subspace", stop)
    gg = group_nodes(build_cnn("yolov2", 416))
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        with pytest.raises(_Stop):
            search(gg, KCU1500, CompileOptions(engine=engine, prune=False))
    said = [str(w.message) for w in seen
            if issubclass(w.category, RuntimeWarning)]
    assert len(said) == warns
    assert all("host core" in m for m in said)


# ----------------------------------- MBConv + SE: exact against brute force
# MobileNetV3-Large (arXiv 1905.02244, Table 1) cut after its first three
# SE rows: (kernel, expand, out, SE, activation, stride), as
# repro.cnn.zoo.mobilenet_v3 builds them.
MBCONV_ROWS = [
    (3, 16, 16, False, "relu", 1), (3, 64, 24, False, "relu", 2),
    (3, 72, 24, False, "relu", 1), (5, 72, 40, True, "relu", 2),
    (5, 120, 40, True, "relu", 1), (5, 120, 40, True, "relu", 1),
]
# A 64x64 input: the space (324 tuples) does not depend on it, the brute
# force below prices every tuple in well under a second.
INPUT = 64
SPACE = 324


def mobilenet_v3_cut(input_size: int = INPUT) -> Graph:
    g = Graph("mobilenet-v3-cut")
    make_input(g, input_size, input_size)
    g.add("conv", out_ch=16, k=3, stride=2, act="swish")           # stem
    for k, exp, out, se, act, s in MBCONV_ROWS:
        entry = g.nodes[-1]
        if exp != entry.out_ch:
            g.add("conv", out_ch=exp, k=1, act=act)                # expand
        dw = g.add("dwconv", k=k, stride=s, act=act)               # depthwise
        if se:
            g.add("globalpool", inputs=[dw.idx])
            g.add("fc", out_ch=exp // 4, in_ch=exp, in_h=1, in_w=1,
                  out_h=1, out_w=1, act="relu")
            gate = g.add("fc", out_ch=exp, in_ch=exp // 4, in_h=1, in_w=1,
                         out_h=1, out_w=1, act="sigmoid")
            g.add("scale", inputs=[dw.idx, gate.idx])
        main = g.add("conv", out_ch=out, k=1, act="linear")        # project
        if s == 1 and entry.out_ch == out:
            g.add("add", inputs=[main.idx, entry.idx])
    g.validate()
    return g


KEYS = {"latency": lambda c: (not c.feasible, c.latency_cycles, c.sram_total),
        "sram": lambda c: (not c.feasible, c.sram_total, c.latency_cycles),
        "dram": lambda c: (not c.feasible, c.dram_total, c.latency_cycles)}
# the KCU1500's budget, and one that leaves part of the space infeasible
BUDGETS = [KCU1500.sram_budget, 150_000]


@pytest.fixture(scope="module")
def brute():
    """Every tuple's candidate, in product order."""
    gg = group_nodes(mobilenet_v3_cut())
    blocks = split_blocks(gg)
    runs = monotone_runs(blocks)
    cands = {}
    for budget in BUDGETS:
        hw = dataclasses.replace(KCU1500, sram_budget=budget)
        cands[budget] = (hw, [evaluate(gg, blocks, runs, cuts, hw)
                              for cuts in itertools.product(
                                  *[range(len(r) + 1) for r in runs])])
    return gg, cands


def test_the_cut_graph_holds_se_and_residual_shortcuts(brute):
    gg, cands = brute
    kinds = [n.kind for n in gg.graph.nodes]
    assert kinds.count("scale") == 3 and kinds.count("add") == 4
    assert len(cands[KCU1500.sram_budget][1]) == SPACE
    feasible = [c.feasible for c in cands[150_000][1]]
    assert any(feasible) and not all(feasible)


@pytest.mark.parametrize("engine", ["pipeline:lax", "journal"])
@pytest.mark.parametrize("budget", BUDGETS)
@pytest.mark.parametrize("objective", sorted(KEYS))
def test_the_exhaustive_path_is_the_brute_force_argmin(brute, engine,
                                                       budget, objective):
    gg, cands = brute
    hw, every = cands[budget]
    want = min(every, key=KEYS[objective])      # first minimum: min is stable
    res = search(gg, hw, CompileOptions(engine=engine, objective=objective,
                                        exhaustive_limit=2 * SPACE))
    assert res.path == "exhaustive" and res.evaluated == SPACE
    assert res.best.cuts == want.cuts
    assert KEYS[objective](res.best) == KEYS[objective](want)
