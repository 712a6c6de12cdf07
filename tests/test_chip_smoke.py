"""chip_smoke.py on the CPU: its phases at @64 with Pallas in interpret
mode, its comparison's failure cases, and its refusal to run without a
TPU or without the rest of the repository."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_64_match_journal(smoke, tmp_path):
    requests = [("vgg16-conv", 64), ("resnet50", 64)]
    plans = smoke.serve_and_compare(requests, smoke.ENGINES, tmp_path,
                                    journal_workers=1,
                                    repeat=("resnet50", 64, "pipeline:lax"))
    assert set(plans) == {(n, s, e) for n, s in requests
                          for e in smoke.ENGINES}
    err = smoke.plan_audit(plans["resnet50", 64, "pipeline:lax"])
    assert 0.0 <= err <= smoke.AUDIT_ATOL


def _result(cuts=(1, 2), latency=10.0, evaluated=6, events=()):
    best = SimpleNamespace(cuts=cuts, latency_cycles=latency, dram_total=5,
                           dram_fm=3, sram_total=7, bram18k=2, feasible=True)
    return SimpleNamespace(best=best, evaluated=evaluated,
                           events=list(events))


@pytest.mark.parametrize("got,match", [
    (_result(cuts=(1, 3)), "cuts"),
    (_result(latency=10.000000000000002), "latency_cycles"),
    (_result(evaluated=5), "evaluated"),
    (_result(events=[SimpleNamespace(kind="device_fallback")]),
     "device_fallback"),
])
def test_compare_rejects_any_difference(smoke, got, match):
    smoke.compare("net", _result(), _result())      # identical: passes
    with pytest.raises(smoke.SmokeFailure, match=match):
        smoke.compare("net", got, _result())


@pytest.mark.parametrize("chips,visible,ok", [
    (1, 1, True), (1, 4, False), (4, 4, True), (4, 1, False)])
def test_require_tpu_wants_exactly_the_chips_asked(smoke, monkeypatch,
                                                   chips, visible, ok):
    import jax
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [tpu] * visible)
    if ok:
        assert len(smoke.require_tpu(chips)) == chips
    else:
        with pytest.raises(SystemExit, match=f"jax sees {visible} devices"):
            smoke.require_tpu(chips)


def _run(args, cwd, env):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = _run([str(SCRIPT), "--out", str(tmp_path / "out")], ROOT, env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = _run([SCRIPT.name], tmp_path, env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
