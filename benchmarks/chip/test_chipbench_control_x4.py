"""The control on the sharded path: with the search step sharded over 4
devices, the fused step with its argmin keys in float32 comes out not
correct, through ``key_gap``.  On 4 virtual CPU devices; the chip readings
are in PERF.md §2."""
from __future__ import annotations

import pytest

from test_chipbench_faults_search import drive


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def test_float32_keys_control_is_not_correct_on_four_devices(cache_dir):
    res = drive("subspace", "float32_keys", cache_dir, devices=4)
    assert res is not None and not res["correct"], res
    assert res["device"]["count"] == 4
    gap = res["checks"]["key_gap"]
    assert gap["value"] > 100 * gap["limit"], gap
