"""The control through a whole run: the fused step with its argmin keys in
float32 (the precision below the configurations' float64) comes out not
correct, through ``key_gap``, where a sound run of the same cell is
correct.  On the CPU; the chip readings are in PERF.md §2."""
from __future__ import annotations

import pytest

from test_chipbench_faults_search import drive


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


@pytest.mark.parametrize("kind", ["subspace", "compile"])
def test_float32_keys_control_is_not_correct(kind, cache_dir):
    res = drive(kind, "float32_keys", cache_dir)
    assert res is not None and not res["correct"], res
    gap = res["checks"]["key_gap"]
    assert gap["value"] > 100 * gap["limit"], gap
