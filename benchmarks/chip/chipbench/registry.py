"""Find every piece of a cell by name.

``BENCHMARK.json`` at the checkout's root names every cell.  Each piece a
cell needs is a file of its own under ``benchmarks/chip/``:

* ``configs/<config>.json`` -- one configuration (network, input size,
  accelerator, engine, source, assumed values);
* ``traffic/<traffic>.json`` -- one traffic mix, parameters only: the
  request kind (``"request"``), the loop (``"loop"``) and the values they
  draw from;
* ``kinds/<request>.py`` -- one request kind: its generator from the seed,
  its warm-up, the system under test it drives (``Target``), and the items
  the comparison judges;
* ``loops/<loop>.py`` -- one way of offering the requests in the window;
* ``end_to_end/<metric>.py`` -- one end-to-end metric, ``value(ctx)``;
* ``metrics/<metric>.py`` -- one per-layer metric's reader, ``read(ctx)``,
  which returns a number or ``None``.

A later change adds a cell, a mix, a kind, a loop or a metric by adding
such files and entries in ``BENCHMARK.json``; no file here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)   # metric specs
    per_layer: list = field(default_factory=list)    # metric specs
    bench_dir: Path = BENCH_DIR

    def kind(self):
        return load_piece("kinds", self.traffic["request"], self.bench_dir)

    def loop(self):
        return load_piece("loops", self.traffic["loop"], self.bench_dir)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    cfg = load_json(Path(bench_dir) / "configs" / f"{name}.json")
    cfg.setdefault("name", name)
    return cfg


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    mix = load_json(Path(bench_dir) / "traffic" / f"{name}.json")
    mix.setdefault("name", name)
    return mix


def load_piece(folder: str, name: str, bench_dir: Path = BENCH_DIR):
    """The module ``<folder>/<name>.py``, loaded from its file."""
    path = Path(bench_dir) / folder / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {folder} piece {name!r} ({path})")
    mod_name = "chipbench_{}_{}".format(
        folder, name.replace(".", "_").replace("-", "_"))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<metric>.py``."""
    return load_piece("metrics", metric, bench_dir).read


def _reports(spec: dict, cell: str, e2e_names: set) -> bool:
    """Whether a metric spec is reported in ``cell``: listed there, or
    unlisted and moving an end-to-end metric the cell reports."""
    if "workloads" in spec:
        return cell in spec["workloads"]
    return spec.get("moves", spec["name"]) in e2e_names


def resolve_cell(name: str, bench: dict | None = None,
                 bench_dir: Path = BENCH_DIR) -> Cell:
    """Everything a run of cell ``name`` needs, found by name."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_config(w["config"], bench_dir),
                traffic=load_traffic(w["traffic"], bench_dir),
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
