"""The benchmark finds every piece by name, and a new piece is a new file.

CPU only: nothing here needs a chip, and the runs of the command below
are expected to refuse to start without one.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

from chipbench import reference, registry, traffic
from chipbench.registry import load_piece

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)
    return env


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = registry.resolve_cell(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(registry.load_reader(m["name"]))
    for m in c.end_to_end:
        assert callable(load_piece("end_to_end", m["name"]).value)
    kind = c.kind()
    for attr in ("requests", "warmup_requests", "Target", "items"):
        assert hasattr(kind, attr)
    assert callable(c.loop().run)
    for piece in (c.config, c.traffic):
        assert piece["why"] and piece["assumed"]
    assert c.config["source"].startswith("https://")


def test_every_named_file_exists_and_is_used():
    bench_files = {str(p.relative_to(ROOT)) for p in BENCH_DIR.rglob("*")}
    for cfg in BENCH["configs"]:
        assert cfg["file"] in bench_files
        assert registry.load_config(cfg["name"]) == json.loads(
            (ROOT / cfg["file"]).read_text()) | {"name": cfg["name"]}
        assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    for m in BENCH["per_layer"]:
        assert f"benchmarks/chip/metrics/{m['name']}.py" in bench_files
    for m in BENCH["end_to_end"]:
        assert f"benchmarks/chip/end_to_end/{m['name']}.py" in bench_files


@pytest.mark.parametrize("name", sorted(
    {json.loads(p.read_text())["loop"]
     for p in (BENCH_DIR / "traffic").glob("*.json")}
    | {json.loads(p.read_text())["request"]
       for p in (BENCH_DIR / "traffic").glob("*.json")}))
def test_every_key_a_mix_sets_is_read(name):
    """A mix names its kind and its loop; every other key is read by the
    kind or the loop it names (or documents the mix)."""
    for p in (BENCH_DIR / "traffic").glob("*.json"):
        mix = json.loads(p.read_text())
        if name not in (mix["loop"], mix["request"]):
            continue
        code = ((BENCH_DIR / "kinds" / f"{mix['request']}.py").read_text()
                + (BENCH_DIR / "loops" / f"{mix['loop']}.py").read_text())
        for key in mix:
            if key not in ("request", "loop", "assumed", "why", "name"):
                assert f'"{key}"' in code, f"{p.name}: {key!r} is read by nothing"


def test_file_only_addition_is_picked_up(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus entries in BENCHMARK.json, with no existing file edited."""
    bench_dir = tmp_path / "chip"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = json.loads((BENCH_DIR / "configs" /
                      "resnet152-224-kcu1500.json").read_text())
    cfg.update(network="resnet50", input_size=224)
    (bench_dir / "configs" / "resnet50-224-kcu1500.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH_DIR / "traffic" /
                      "subspace-sweep.json").read_text())
    mix["target_tasks"] = 16
    (bench_dir / "traffic" / "subspace-sweep-16.json").write_text(
        json.dumps(mix))
    (bench_dir / "metrics" / "requests_seen.search.py").write_text(
        "def read(ctx):\n    return float(len(ctx.spans))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "resnet50-224.search16",
                               "config": "resnet50-224-kcu1500",
                               "traffic": "subspace-sweep-16", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("resnet50-224.search16")
    bench["per_layer"].append({"name": "requests_seen.search", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "search_rate",
                               "workloads": ["resnet50-224.search16"]})
    cell = registry.resolve_cell("resnet50-224.search16", bench, bench_dir)
    assert cell.config["network"] == "resnet50"
    assert cell.traffic["target_tasks"] == 16
    assert [m["name"] for m in cell.per_layer] == ["requests_seen.search"]
    reader = registry.load_reader("requests_seen.search", bench_dir)
    assert reader(type("Ctx", (), {"spans": {"a": [1]}})) == 1.0
    lengths = reference.run_lengths(cell.config)
    kind = cell.kind()
    prefixes, _ = kind.partition_space(lengths, 16)
    reqs = traffic.take(kind.requests(cell.traffic, 3, lengths),
                        len(prefixes))
    assert {r["prefix"] for r in reqs} == set(prefixes)
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


class _Echo:
    """A system under test that answers at once."""

    def serve(self, req):
        return {"id": req["id"]}


class _Win:
    def start(self):
        pass

    def serve(self, target, req):
        return target.serve(req), None

    def tick(self):
        pass

    def stop(self):
        pass


def test_open_loop_mix_is_files_only(tmp_path):
    """An open-loop mix of compile requests with a tail metric of its own:
    a traffic file, an end-to-end metric file and entries in
    BENCHMARK.json; the loop it names already exists, and no existing
    file is edited."""
    bench_dir = tmp_path / "chip"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    mix = json.loads((BENCH_DIR / "traffic" /
                      "compile-sweep.json").read_text())
    mix.update(loop="open", rate_per_s=40.0)
    (bench_dir / "traffic" / "compile-open.json").write_text(
        json.dumps(mix))
    (bench_dir / "end_to_end" / "served_p99_s.py").write_text(
        "import numpy as np\n\n\ndef value(ctx):\n"
        "    return float(np.percentile("
        "[r['latency_s'] for r in ctx.served], 99))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "resnet152-224.open",
                               "config": "resnet152-224-kcu1500",
                               "traffic": "compile-open", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "served_p99_s", "unit": "s",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["resnet152-224.open"]})
    cell = registry.resolve_cell("resnet152-224.open", bench, bench_dir)
    assert {m["name"] for m in cell.end_to_end} == {"served_p99_s",
                                                    "setup_s"}
    lengths = reference.run_lengths(cell.config)
    stream = cell.kind().requests(cell.traffic, 5, lengths)
    served, window_s = cell.loop().run(_Echo(), stream, 0.5, cell.traffic,
                                       5, _Win())
    assert window_s >= 0.5
    # 40 a second over half a second, the same offered load from every seed
    assert 15 <= len(served) <= 20
    assert all(r["answer"] == {"id": r["req"]["id"]} for r in served)
    ctx = type("Ctx", (), {"served": served, "window_s": window_s})
    p99 = load_piece("end_to_end", "served_p99_s", bench_dir).value(ctx)
    assert 0.0 <= p99 < 0.5
    open_loop = load_piece("loops", "open", bench_dir)
    assert (sorted(open_loop.arrivals(40.0, 0.5, 1))[-1]
            < 0.5) and len(open_loop.arrivals(40.0, 0.5, 1)) == len(
                open_loop.arrivals(40.0, 0.5, 2))
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_command_exits_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_command_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cmd = BENCH["command"] + ["--workload", CELLS[0], "--seed", "1",
                              "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    proc = subprocess.run(cmd, cwd=tmp_path, env=_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_is_deterministic_in_the_seed(cell):
    c = registry.resolve_cell(cell)
    lengths = reference.run_lengths(c.config)
    seed = 2 ** 31 + 12345

    kind = c.kind()

    def first(s, n):
        return traffic.take(kind.requests(c.traffic, s, lengths), n)

    assert first(seed, 40) == first(seed, 40)
    assert first(seed, 40) != first(seed + 1, 40)
    assert first(-seed, 40) == first(-seed, 40)
    # every seed sends the same set of requests in a full cycle, in
    # another order: the same work from every seed
    if c.traffic["request"] == "subspace":
        prefixes, _ = kind.partition_space(lengths,
                                           c.traffic["target_tasks"])
        n = len(prefixes) * len(c.traffic["objectives"])
    else:
        n = (len(c.traffic["objectives"])
             * len(c.traffic["sram_budgets_mb"]))

    def bag(s):
        return Counter(tuple(sorted((k, v) for k, v in r.items()
                                    if k != "id")) for r in first(s, n))

    assert bag(seed) == bag(seed + 1)
    assert len(bag(seed)) == n


def test_yolov2_split_sizes():
    c = registry.resolve_cell("yolov2-416.search")
    lengths = reference.run_lengths(c.config)
    prefixes, lens = c.kind().partition_space(lengths, 64)
    assert len(prefixes) == 108
    assert reference.space_size([n + 1 for n in lens]) == 73_728
    assert reference.space_size([n + 1 for n in lengths]) == 7_962_624


@pytest.mark.parametrize("cell,programs", [("yolov2-416.search", 3),
                                           ("resnet152-224.compile", 12)])
def test_warmup_covers_each_program_once(cell, programs):
    c = registry.resolve_cell(cell)
    warm = c.kind().warmup_requests(c.traffic,
                                    reference.run_lengths(c.config))
    keys = {(r["objective"], r.get("sram_budget")) for r in warm}
    assert len(warm) == len(keys) == programs
