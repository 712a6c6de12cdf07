"""The search's device kernels compile for a TPU v5e, at real sizes.

Each case lowers and compiles one main-path kernel for a described (not
attached) ``v5e:2x2`` topology with ``interpret=False``: the compiler
refuses here what the chip would refuse -- a bool truncation Mosaic
cannot lower, mismatched tile widths, float64 where it has none -- at no
chip time.  Nothing runs, so results are the CPU suites' business.

This is the only test file that describes the chip.  The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU compiler library, and every pytest-xdist worker imports
every test file.
"""
import os

import numpy as np
import pytest

from repro.cnn import build_cnn
from repro.core.cutpoint import CutpointEngine
from repro.core.grouping import group_nodes
from repro.core.hw import KCU1500

jax = pytest.importorskip("jax")

CHUNK = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh
    return Mesh(np.asarray(topo.devices[:4]), ("d",))


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to jax's persistent
    cache but cannot be read back without one; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _engine(name, size):
    from repro.kernels.alloc_scan import pack_alloc_tables
    engine = CutpointEngine(group_nodes(build_cnn(name, size)), KCU1500)
    engine._at = pack_alloc_tables(engine.gg, engine.hw)
    return engine


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _compiles_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("net,size,groups", [("yolov2", 416, 26),
                                             ("resnet152", 224, 160)])
def test_alloc_scan_kernel_compiles(one_chip, net, size, groups):
    """G = 26 fits one 128-lane tile; G = 160 needs two (G + 2 > 128)."""
    from repro.kernels.alloc_scan import _alloc_operands, _build_alloc_call
    t = _engine(net, size)._at
    assert t.n == groups
    args, (nb, block_b, lanes) = _alloc_operands(
        t, np.zeros((CHUNK, t.n), bool), 256)
    fn = _build_alloc_call(nb, t.n, t.k, block_b, lanes, False)
    _compiles_kernel(fn.lower(*_shapes(args, one_chip)).compile())


def test_enum_kernel_compiles(one_chip):
    from repro.core.cutpoint import monotone_runs, split_blocks
    from repro.kernels.search_pipeline import (_build_enum_call,
                                               _engine_tables,
                                               _space_strides)
    engine = _engine("yolov2", 416)
    tbl = _engine_tables(engine)
    dims = tuple(len(r) + 1
                 for r in monotone_runs(split_blocks(engine.gg)))
    nb, block_b = CHUNK // 256, 256
    call = _build_enum_call(nb, block_b, tbl["lanes"], len(dims), 0,
                            _space_strides(dims), dims, False)
    args = (np.zeros(1, np.int32), np.zeros(1, np.int32),
            tbl["runof_row"], tbl["pos_row"], tbl["dirneg_row"])
    _compiles_kernel(jax.jit(call).lower(*_shapes(args, one_chip))
                     .compile())


def _fused_yolov2():
    from repro.kernels.search_pipeline import (_engine_tables, _lax_args,
                                               _make_fused, _space_strides)
    engine = _engine("yolov2", 416)
    tbl = _engine_tables(engine)
    dims = tuple(len(r) + 1 for r in engine.runs)
    S = int(np.prod(dims))
    assert S == 7_962_624                   # the full exhaustive space
    fused = _make_fused(tbl, CHUNK, 0, dims, _space_strides(dims), S,
                        "latency")
    return fused, _lax_args(tbl, ())


def test_fused_search_step_compiles(one_chip):
    with jax.enable_x64(True):
        fused, args = _fused_yolov2()
        lo = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
        compiled = jax.jit(fused).lower(
            lo, *_shapes(args, one_chip)).compile()
    assert "f64" in compiled.as_text()


def test_sharded_fused_step_compiles_on_four_chips(mesh4):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels.search_pipeline import _shard_fused
    with jax.enable_x64(True):
        fused, args = _fused_yolov2()
        los = jax.ShapeDtypeStruct((4,), np.int32,
                                   sharding=NamedSharding(mesh4, P("d")))
        compiled = _shard_fused(fused, mesh4).lower(
            los, *_shapes(args, NamedSharding(mesh4, P()))).compile()
    assert compiled.output_shardings.num_devices == 4
