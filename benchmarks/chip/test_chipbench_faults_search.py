"""A whole run of the search cell on the CPU (yolov2@416, its mix split
finer: sub-spaces of 1,152 candidates, 32 a launch), with the device check
skipped: sound, it is correct; with each fault the cell can have planted in
the timed path, ``correct`` comes out false.

Each run is a subprocess (``chipbench/selftest.py``), so that jax's
settings and the planted fault stay out of the test process.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SELFTEST = Path(__file__).resolve().parent / "chipbench" / "selftest.py"


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def drive(kind: str, fault: str, cache_dir, devices: int = 1) -> dict | None:
    """The run's result line, or None where it ended without one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    proc = subprocess.run([sys.executable, str(SELFTEST), kind, fault,
                           "1", "2"],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines or not lines[-1].startswith("{"):
        return None
    return json.loads(lines[-1])


def test_sound_run_is_correct(cache_dir):
    res = drive("subspace", "none", cache_dir)
    assert res is not None and res["correct"], res
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert res["checks"]["key_gap"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_not_correct(fault, cache_dir):
    res = drive("subspace", fault, cache_dir)
    assert res is None or not res["correct"], res


def test_sharded_sound_run_is_correct(cache_dir):
    res = drive("subspace", "none", cache_dir, devices=4)
    assert res is not None and res["correct"], res
    assert res["device"]["count"] == 4


def test_exchange_left_out_is_not_correct(cache_dir):
    res = drive("subspace", "exchange_left_out", cache_dir, devices=4)
    assert res is not None and not res["correct"], res
    assert res["checks"]["regret"]["value"] > res["checks"]["regret"]["limit"]
