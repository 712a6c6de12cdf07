"""The pieces of the two cells on the search loop's deeper graphs, on the CPU.

``mobilenet-v3-224.search``: a whole run of the cell (mobilenet-v3@224, its
mix split finer: sub-spaces of 2,592 candidates, 32 a launch, 81 launches a
sub-space), with the device check skipped and the plain reference judging:
sound, it is correct; with a fault planted in the timed path, or with the
argmin keys in float32, ``correct`` comes out false.  Each run is a subprocess, so that jax's settings and the
planted fault stay out of the test process.

``resnet152-224.search``: found by name, one request is the whole space.
"""
from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import reference, registry

HERE = Path(__file__).resolve().parent
MOBILENET = "mobilenet-v3-224.search"
# 5,832 prefixes over the first nine runs, 2,592 candidates each
FINER = {"target_tasks": 4096, "batch_size": 32}

RUN = """
import sys, time
T_START = time.perf_counter()
sys.path.insert(0, {src!r})
sys.path.insert(0, {here!r})
from chipbench import harness, registry
from chipbench.selftest import plant
cell = registry.resolve_cell({cell!r})
cell.traffic = dict(cell.traffic, **{finer!r})
plant({fault!r})
harness.run_cell(cell, 2 ** 31 + 11, 1.0, False, T_START,
                 check_device=False, ref_workers=2)
"""


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def drive(fault: str, cache_dir) -> dict | None:
    """The run's result line, or None where it ended without one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    code = RUN.format(src=str(HERE.parents[1] / "src"), here=str(HERE),
                      cell=MOBILENET, finer=FINER, fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines or not lines[-1].startswith("{"):
        return None
    return json.loads(lines[-1])


def test_the_configuration_states_the_reference_shape():
    cell = registry.resolve_cell(MOBILENET)
    assert cell.chips == 1 and cell.traffic["target_tasks"] == 256
    lengths = reference.check_config(cell.config)
    assert lengths == [2, 2, 1, 2, 2, 1, 1, 2, 2, 1, 2, 1, 1, 2, 2, 2, 1, 1]
    with pytest.raises(ValueError):
        reference.check_config(dict(cell.config, shape=dict(
            cell.config["shape"], cut_tuples=15116543)))
    kind = cell.kind()
    prefixes, suffix = kind.partition_space(lengths,
                                            cell.traffic["target_tasks"])
    assert len(prefixes) == 324
    assert reference.space_size([n + 1 for n in suffix]) == 46_656
    prefixes, suffix = kind.partition_space(lengths, FINER["target_tasks"])
    assert reference.space_size([n + 1 for n in suffix]) == 2_592


def test_the_resnet152_search_cell_sends_the_whole_space():
    cell = registry.resolve_cell("resnet152-224.search")
    assert cell.config["name"] == "resnet152-224-kcu1500"
    assert cell.chips == 1
    assert "search_rate" in {m["name"] for m in cell.end_to_end}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle.search", "fused_step_ms.search",
        "launch_gap_ms.search", "launch_dispatch_ms.search",
        "launch_wait_ms.search"}
    lengths = reference.check_config(cell.config)
    reqs = list(itertools.islice(
        cell.kind().requests(cell.traffic, 2 ** 31 + 5, lengths), 6))
    assert {r["prefix"] for r in reqs} == {()}
    assert {r["work"] for r in reqs} == {8_748}
    assert {r["objective"] for r in reqs[:3]} == {"latency", "dram", "sram"}
    assert [r["objective"] for r in reqs[3:]] == [
        r["objective"] for r in reqs[:3]]


def test_sound_run_is_correct(cache_dir):
    res = drive("none", cache_dir)
    assert res is not None and res["correct"], res
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert res["checks"]["regret"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_fault_is_not_correct(fault, cache_dir):
    res = drive(fault, cache_dir)
    assert res is not None and not res["correct"], res
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_float32_keys_are_not_correct(cache_dir):
    """The control: argmin keys in float32, the precision below the
    configuration's float64, fail through ``key_gap``."""
    res = drive("float32_keys", cache_dir)
    assert res is not None and not res["correct"], res
    gap = res["checks"]["key_gap"]
    assert gap["value"] > gap["limit"]
