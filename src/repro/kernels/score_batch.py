"""Pallas kernel for batched cut-candidate scoring (tuples x groups).

The cut-point engine's batched scorer (``CutpointEngine.score_batch``)
expands B cut tuples into a B x G frame-mask matrix plus a B x G
boundary-IO matrix and reduces them against the static per-group cost
tables (``latency_tables`` / ``dram_tables`` / ``sram_tables``).  On CPU
those reductions are numpy; this module stages the *same* masked
reduction as a Pallas TPU kernel -- the on-device path the ROADMAP names
for moving the search itself onto the accelerator.  One kernel launch
computes, per candidate:

* ``latency``  -- sum over groups of
  ``where(side, comp, where(frame, max(comp, (weight+io)/bpc) + ovh, row))``
  (the row-major masked latency reduction of ``latency_cycles_fast_batch``)
* ``row_fm``   -- the row-mode DRAM feature-map term,
  ``sum(where(~frame, row_fm, 0))``
* the four SRAM maxima of eqs. (1)/(4)/(5):
  ``weight_buff`` (row-mode weight max), ``out_frame`` / ``out_row``
  (partial-sum buffer candidates) and ``wr_row`` (write-buffer max)

Layout: candidates ride the sublane axis (one candidate per row), groups
ride the lane axis padded to 128; the per-group tables are (1, Gp) rows
broadcast across the candidate tile.  Outputs land in a (B, 128) stats
matrix whose first ``N_STATS`` lanes are the reductions above.

Exactness: the kernel runs in float32 (TPU-native), so it is NOT part of
the engine's bit-exact oracle contract -- the numpy backend stays the
default and the oracle of record.  The kernel's own contract is agreement
with :func:`score_batch_ref` (the float32 numpy reference below), which
tests/test_score_batch.py enforces in interpret mode, exactly like the
other kernels in this package validate against kernels/ref.py.  On hosts
without a TPU the wrapper automatically falls back to interpret mode.

The tiling/layout helpers here (``LANES``/``SUBLANES``/``_pad_up``/
``_on_tpu``) are shared with the allocator-replay scan kernel
(kernels/alloc_scan.py), which uses the same candidates-on-sublanes,
gids-on-lanes layout for its state rows.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

try:                                   # optional at runtime, like ops.py
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    HAVE_JAX = True
except Exception:                      # pragma: no cover - jax is baked in
    HAVE_JAX = False

LANES = 128                            # TPU lane width (last axis)
SUBLANES = 8                           # float32 sublane tile
N_STATS = 6                            # stats lanes used per candidate
TABLE_KEYS = ("comp", "row", "weight", "side", "row_fm", "compute",
              "out_frame", "out_row", "wr_row")


def _pad_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _on_tpu() -> bool:
    """Whether the default jax device is a TPU (compiled-vs-interpret
    auto-selection for this kernel and kernels/alloc_scan.py).  A backend
    that fails to initialize raises here rather than passing for a CPU."""
    return HAVE_JAX and jax.devices()[0].platform == "tpu"


def pack_tables(lt, dt, st) -> dict:
    """Pack the engine's static cost tables into (1, Gp) float32 rows.

    ``lt`` / ``dt`` / ``st`` are the ``LatencyTables`` / ``DRAMTables`` /
    ``SRAMTables`` of one graph; Gp pads the group axis to the TPU lane
    width.  Padding lanes hold zeros, which make every reduction a no-op
    there (masks are 0, ``row``/``row_fm`` are 0, maxima are against 0).
    """
    g = lt.comp.shape[0]
    gp = _pad_up(max(g, 1), LANES)

    def pad(a) -> np.ndarray:
        out = np.zeros((1, gp), np.float32)
        out[0, :g] = np.asarray(a, np.float64)[:g]
        return out

    return {
        "g": g, "gp": gp,
        "comp": pad(lt.comp), "row": pad(lt.row), "weight": pad(lt.weight),
        "side": pad(lt.side), "row_fm": pad(dt.row_fm),
        "compute": pad(st.compute), "out_frame": pad(st.out_frame),
        "out_row": pad(st.out_row), "wr_row": pad(st.wr_row),
    }


@dataclass(frozen=True)
class BatchStats:
    """Per-candidate reductions, shaped (B,), host-side."""
    latency: np.ndarray        # float64 (cast from f32)
    row_fm: np.ndarray         # int64: row-mode DRAM fm term
    maxima: tuple              # (weight_buff, out_frame, out_row, wr_row)


def score_batch_ref(tables: dict, frame: np.ndarray, io: np.ndarray,
                    bpc: float, overhead: float) -> np.ndarray:
    """Float32 numpy reference for the kernel (the agreement target).

    Returns the (B, N_STATS) stats matrix
    ``[latency, row_fm, weight_buff, out_frame, out_row, wr_row]``
    computed with the same op structure and dtype as the kernel body.
    """
    g = tables["g"]
    fr = np.asarray(frame, bool)[:, :g]
    iof = np.asarray(io, np.float32)[:, :g]
    comp = tables["comp"][:, :g]
    row = tables["row"][:, :g]
    weight = tables["weight"][:, :g]
    side = tables["side"][:, :g] > 0
    row_fm = tables["row_fm"][:, :g]
    cm = tables["compute"][:, :g] > 0
    out_frame = tables["out_frame"][:, :g]
    out_row = tables["out_row"][:, :g]
    wr_row = tables["wr_row"][:, :g]

    mem = (weight + iof) / np.float32(bpc)
    frame_lat = np.maximum(comp, mem) + np.float32(overhead)
    per = np.where(side, comp, np.where(fr, frame_lat, row))
    lat = per.sum(axis=1, dtype=np.float32)
    rfm = np.where(fr, np.float32(0), row_fm).sum(axis=1, dtype=np.float32)
    rowm = cm & ~fr
    frm = cm & fr
    z = np.float32(0)
    wbuff = np.where(rowm, weight, z).max(axis=1, initial=0)
    outf = np.where(frm, out_frame, z).max(axis=1, initial=0)
    outr = np.where(rowm, out_row, z).max(axis=1, initial=0)
    wrr = np.where(rowm, wr_row, z).max(axis=1, initial=0)
    return np.stack([lat, rfm, wbuff, outf, outr, wrr],
                    axis=1).astype(np.float32)


if HAVE_JAX:

    def _score_kernel(frame_ref, io_ref, comp_ref, row_ref, weight_ref,
                      side_ref, rowfm_ref, computem_ref, outf_ref, outr_ref,
                      wrr_ref, out_ref, *, bpc: float, overhead: float):
        frame = frame_ref[...] > 0           # (TB, Gp) mask
        io = io_ref[...]
        comp = comp_ref[...]                 # (1, Gp), broadcasts over TB
        mem = (weight_ref[...] + io) / bpc
        frame_lat = jnp.maximum(comp, mem) + overhead
        per = jnp.where(side_ref[...] > 0, comp,
                        jnp.where(frame, frame_lat, row_ref[...]))
        lat = jnp.sum(per, axis=1)
        rfm = jnp.sum(jnp.where(frame, 0.0, rowfm_ref[...]), axis=1)
        cm = computem_ref[...] > 0
        rowm = cm & ~frame
        frm = cm & frame
        wbuff = jnp.max(jnp.where(rowm, weight_ref[...], 0.0), axis=1)
        outf = jnp.max(jnp.where(frm, outf_ref[...], 0.0), axis=1)
        outr = jnp.max(jnp.where(rowm, outr_ref[...], 0.0), axis=1)
        wrr = jnp.max(jnp.where(rowm, wrr_ref[...], 0.0), axis=1)
        stats = jnp.stack([lat, rfm, wbuff, outf, outr, wrr], axis=1)
        pad = jnp.zeros((stats.shape[0], out_ref.shape[1] - N_STATS),
                        stats.dtype)
        out_ref[...] = jnp.concatenate([stats, pad], axis=1)

    _CALL_CACHE: dict = {}

    def _build_call(bp: int, gp: int, block_b: int, bpc: float,
                    overhead: float, interpret: bool):
        key = (bp, gp, block_b, bpc, overhead, interpret)
        fn = _CALL_CACHE.get(key)
        if fn is not None:
            return fn
        tab_spec = pl.BlockSpec((1, gp), lambda i: (0, 0))
        call = pl.pallas_call(
            partial(_score_kernel, bpc=bpc, overhead=overhead),
            grid=(bp // block_b,),
            in_specs=[pl.BlockSpec((block_b, gp), lambda i: (i, 0)),
                      pl.BlockSpec((block_b, gp), lambda i: (i, 0))]
            + [tab_spec] * len(TABLE_KEYS),
            out_specs=pl.BlockSpec((block_b, LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((bp, LANES), jnp.float32),
            interpret=interpret,
        )
        fn = _CALL_CACHE[key] = jax.jit(call)
        return fn

    def score_batch_pallas(tables: dict, frame: np.ndarray, io: np.ndarray,
                           bpc: float, overhead: float,
                           interpret: bool | None = None,
                           block_b: int = 256) -> np.ndarray:
        """Run the kernel; returns the (B, N_STATS) float32 stats matrix.

        ``interpret=None`` auto-selects: compiled on TPU hosts, Pallas
        interpret mode elsewhere (same kernel body, jax-evaluated)."""
        if interpret is None:
            interpret = not _on_tpu()
        b, g = frame.shape
        gp = tables["gp"]
        block_b = max(SUBLANES, min(block_b, _pad_up(max(b, 1), SUBLANES)))
        bp = _pad_up(max(b, 1), block_b)
        fp = np.zeros((bp, gp), np.float32)
        fp[:b, :g] = frame
        iop = np.zeros((bp, gp), np.float32)
        iop[:b, :g] = io
        fn = _build_call(bp, gp, block_b, float(bpc), float(overhead),
                         interpret)
        out = fn(fp, iop, *[tables[k] for k in TABLE_KEYS])
        return np.asarray(out)[:b, :N_STATS]

else:                                      # pragma: no cover - jax baked in

    def score_batch_pallas(tables, frame, io, bpc, overhead,
                           interpret=None, block_b=256):
        raise RuntimeError("jax is not available: the pallas score_batch "
                           "backend requires jax (use backend='numpy')")


def score_stats(tables: dict, frame: np.ndarray, io: np.ndarray,
                hw, interpret: bool | None = None) -> BatchStats:
    """Engine adapter: kernel stats for one batch against ``hw``.

    Converts the (B, N_STATS) float32 stats matrix into the shapes the
    batched cost models consume (``row_terms`` / ``maxima`` injection
    points of ``dram_fm_fast_batch`` / ``sram_total_fast_batch``).  The
    int quantities are rounded from float32 -- exact only while the true
    values stay under 2**24, which is why this path is staged behind
    ``backend="pallas"`` rather than replacing the numpy oracle."""
    stats = score_batch_pallas(tables, frame, io,
                               hw.dram_bytes_per_cycle,
                               hw.group_overhead_cycles,
                               interpret=interpret)
    as_int = [np.rint(stats[:, i]).astype(np.int64) for i in range(1, 6)]
    return BatchStats(latency=stats[:, 0].astype(np.float64),
                      row_fm=as_int[0],
                      maxima=(as_int[1], as_int[2], as_int[3], as_int[4]))
