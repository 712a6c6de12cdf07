"""Drive a whole run on the CPU, optionally with a fault planted in the
timed path, and print its result line.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/chipbench/selftest.py \
        <subspace|compile> <fault> [seconds] [reference workers]

The device check is skipped; everything else is a run: set-up, window,
reference, comparison.  The configurations are the cells' own; the search
cell's mix is split finer for the CPU (yolov2@416 sub-spaces of 1,152
candidates, 32 a launch, so a sub-space takes 36 launches, or 9 steps over
four devices; winners lie near a sub-space's end, past the first device's
share of its step), the compile
cell's is unchanged (whole resnet152@224 compiles).  With ``XLA_FLAGS=
--xla_force_host_platform_device_count=4`` the search runs sharded over four
CPU devices.  Faults, each planted where the program produces it:

* ``float32_keys`` -- the control: the fused step's argmin keys are
  computed in float32, the precision below the configurations' float64;
* ``state_unchanged`` -- the host fold of launch winners keeps its state;
* ``half_batch`` -- every other launch's winner is left out of the fold;
* ``exchange_left_out`` -- only the first device's winner of a sharded
  step reaches the host;
* ``answer_altered`` -- the winning index is decoded as its neighbour.
"""
from __future__ import annotations

import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
sys.path.insert(0, HERE)

CELLS = {"subspace": ("yolov2-416.search",
                      {"target_tasks": 4096, "batch_size": 32}),
         "compile": ("resnet152-224.compile", {})}


def plant(fault: str) -> None:
    import numpy as np
    from repro.kernels import search_pipeline as sp
    if fault == "none":
        return
    if fault == "float32_keys":
        import jax.numpy as jnp
        argmin = sp._argmin_hier

        def float32_keys(infeas, primary, secondary, idxf, xp):
            if xp is jnp:
                primary = primary.astype(jnp.float32).astype(jnp.float64)
                secondary = secondary.astype(jnp.float32).astype(
                    jnp.float64)
            return argmin(infeas, primary, secondary, idxf, xp)
        sp._argmin_hier = float32_keys
    elif fault == "state_unchanged":
        sp._fold = lambda best, w: best
    elif fault == "half_batch":
        fold, calls = sp._fold, [0]

        def half(best, w):
            calls[0] += 1
            return best if calls[0] % 2 == 0 and best is not None \
                else fold(best, w)
        sp._fold = half
    elif fault == "exchange_left_out":
        shard = sp._shard_fused

        def local_only(fused, mesh):
            step = shard(fused, mesh)

            def first_device(*args):
                rows = np.array(step(*args))
                rows[1:] = (sp._PAD_RANK, np.inf, np.inf, sp._HUGE_IDX)
                return rows
            return first_device
        sp._shard_fused = local_only
    elif fault == "answer_altered":
        decode = sp._decode_index

        def neighbour(idx, strides, dims):
            size = strides[0] * dims[0]
            return decode((idx + 1) % size, strides, dims)
        sp._decode_index = neighbour
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main(kind: str, fault: str, seconds: float = 1.0,
         ref_workers: int = 1) -> bool:
    from chipbench import harness, registry
    cell_name, mix_changes = CELLS[kind]
    cell = registry.resolve_cell(cell_name)
    cell.traffic = dict(cell.traffic, **mix_changes)
    import jax
    cell.chips = len(jax.devices())
    plant(fault)
    return harness.run_cell(cell, 2 ** 31 + 7, seconds, False, T_START,
                            check_device=False, ref_workers=ref_workers)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2],
         float(sys.argv[3]) if len(sys.argv) > 3 else 1.0,
         int(sys.argv[4]) if len(sys.argv) > 4 else 1)
