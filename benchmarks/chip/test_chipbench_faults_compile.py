"""A whole run of the compile cell on the CPU at its own size
(resnet152@224, every objective and SRAM budget warmed), with the device
check skipped: sound, it is correct; with a fault planted in the timed
path, ``correct`` comes out false."""
from __future__ import annotations

import pytest

from test_chipbench_faults_search import drive


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def test_sound_compile_run_is_correct(cache_dir):
    res = drive("compile", "none", cache_dir)
    assert res is not None and res["correct"], res
    assert res["checks"]["verifier_errors"] == {"value": 0, "limit": 0}
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_compile_fault_is_not_correct(fault, cache_dir):
    res = drive("compile", fault, cache_dir)
    assert res is not None and not res["correct"], res
