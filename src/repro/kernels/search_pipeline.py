"""Fused on-device sub-space search: enumerate -> replay -> score -> argmin.

The journal/device engines drive the exhaustive cut search from the host:
``branch_bound_subspace`` materializes every candidate tuple in Python,
batches them through ``score_batch``, and keeps the running winner on the
host.  This module fuses that whole loop into one device pipeline behind
``CompileOptions(engine="pipeline")``:

1. **In-kernel enumeration** -- a sub-space is ``prefix`` (fixed cuts for
   the leading runs) x the product order over ``suffix_dims``.  Product
   order over runs *is* lexicographic order of the cut tuples, so every
   candidate has a global linear index ``j in [0, S)`` with the last run
   varying fastest (``stride[q] = prod(dims[q+1:])``).  Kernels decode
   ``j`` straight into the B x G frame-mask matrix (the same three
   gathers as ``CutpointEngine._frame_matrix``); the host never
   materializes the candidate tuple stream.
2. **Allocator replay** -- the decoded masks feed the tensorized
   allocator scan (``kernels/alloc_scan.py``), integer-exact under every
   backend.
3. **Cost reduction** -- the B x G mask-matrix reductions of
   ``timing/dram/sram.*_fast_batch``, evaluated in float64.  Every
   integer quantity is far below 2**53, so the int -> f64 embedding is
   exact and ``<=`` comparisons match the host's integer comparisons
   bit-for-bit.  The latency total is the one order-sensitive float
   reduction: the host uses ``np.cumsum`` (strictly sequential
   left-to-right), so the device path accumulates with a sequential
   ``lax.fori_loop`` over groups -- never ``jnp.sum``, whose pairwise
   re-association would break oracle exactness.
4. **Hierarchical argmin** -- the objective key is the host's
   ``_key``: ``(infeasible, primary, secondary)``, tie-broken by the cut
   tuple, i.e. by the linear index ``j``.  ``argmin_lanes`` reduces it as
   nested masked minima (min infeasibility -> min primary among those ->
   min secondary among those -> min index among those), which equals the
   lexicographic first-minimum exactly; only the winning
   ``(key, index)`` 4-tuple leaves the device per chunk.

Chunk winners are folded on the host by plain tuple comparison and the
final index is decoded back into cuts (mixed radix, last run fastest);
the winner is then re-priced through the engine's exact journal oracle,
so the returned ``CandidateMetrics`` is byte-identical to the journal
path's and the kernels only ever decide *which* candidate wins.
``evaluations`` is credited with the full enumeration count ``S``, which
equals the journal path's ``scored + pruned`` -- the two engines report
identical ``evaluated`` under the default ``count_pruned=True``.

Variants (``engine="pipeline[:variant]"``):

* ``reference`` -- numpy end-to-end (enumeration + ``alloc_scan_ref`` +
  the very ``*_fast_batch`` reductions of the journal scorer).  The
  oracle the other two are tested against.
* ``lax`` -- one jitted fused function per sub-space shape: decode,
  frame masks, ``_scan_impl`` allocator scan, f64 reductions and the
  hierarchical argmin all in a single XLA computation returning four
  scalars.  The engine's tables go to the device once, on its first
  launch, so a launch transfers only its start index.  With more than
  one visible device the chunk range is sharded with ``shard_map`` over
  contiguous index ranges -- the same disjoint partitioning
  ``search_pool.partition_space`` uses, expressed on the linear index --
  and the per-device winners are folded with the same deterministic
  tuple comparison, so the merged result is bit-identical at any device
  count.
* ``pallas`` -- the staged TPU composition: an enumeration kernel
  (int32) decodes indices to masks, ``alloc_scan_pallas`` replays them,
  and a cost/argmin kernel reduces each block to one winner row.  The
  cost kernel works in float64 for exactness; Mosaic has no float64, so
  on a TPU this variant raises (use ``lax`` there) and off the TPU the
  f64 stages run in interpret mode.  The integer enumeration and
  allocator stages compile natively on TPU.

All three variants return the bit-identical winner
(tests/test_search_pipeline.py fuzzes them against the host merge on
batches with duplicated keys).
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core.dram import dram_fm_fast_batch
from repro.core.options import DEFAULT_BATCH_SIZE
from repro.core.sram import sram_total_fast_batch
from repro.core.timing import latency_cycles_fast_batch
from repro.kernels.score_batch import (HAVE_JAX, LANES, SUBLANES, _on_tpu,
                                       _pad_up)
from repro.utils.trace import span

if HAVE_JAX:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

VARIANTS = ("reference", "lax", "pallas")
OBJECTIVES = ("latency", "sram", "dram")

# Rank sentinels for padded / out-of-range lanes: a real candidate's
# infeasibility rank is 0.0 or 1.0, so rank 2.0 never wins; the index
# sentinel exceeds any real linear index (spaces are capped at
# EXHAUSTIVE_LIMIT = 8M << 2**62).
_PAD_RANK = 2.0
_HUGE_IDX = float(2 ** 62)
# The float64 cost and argmin kernels always run in the Pallas
# interpreter: Mosaic has no float64 (see _refuse_f64_on_tpu).
_F64_INTERPRET = True
# Largest sub-space the kernels index: linear indices are int32, with
# headroom for the padded chunk starts past S.
_MAX_SPACE = 2 ** 30


# --------------------------------------------------------------- index math
def _space_strides(dims: tuple[int, ...]) -> tuple[int, ...]:
    """Mixed-radix strides of the product order (last run fastest)."""
    strides = [1] * len(dims)
    for q in range(len(dims) - 2, -1, -1):
        strides[q] = strides[q + 1] * dims[q + 1]
    return tuple(strides)


def _decode_index(idx: int, strides: tuple[int, ...],
                  dims: tuple[int, ...]) -> tuple[int, ...]:
    """Linear index -> suffix cut tuple (inverse of the in-kernel decode)."""
    return tuple((idx // s) % d for s, d in zip(strides, dims))


def _keys_np(objective: str, lat: np.ndarray, dram_total: np.ndarray,
             sram_total: np.ndarray, feasible: np.ndarray):
    """Host objective key columns, mirroring ``cutpoint._key`` exactly:
    ``(not feasible, primary, secondary)`` in float64 (exact embedding:
    every integer magnitude here is far below 2**53)."""
    infeas = (~np.asarray(feasible, dtype=bool)).astype(np.float64)
    lat = np.asarray(lat, dtype=np.float64)
    sram = np.asarray(sram_total, dtype=np.float64)
    if objective == "latency":
        return infeas, lat, sram
    if objective == "sram":
        return infeas, sram, lat
    if objective == "dram":
        return infeas, np.asarray(dram_total, dtype=np.float64), lat
    raise ValueError(f"unknown objective: {objective!r}")


# --------------------------------------------------------- hierarchical argmin
def _argmin_hier(infeas, primary, secondary, idxf, xp):
    """Nested masked minima == lexicographic first-minimum.

    Each level keeps only the lanes that achieved the previous minima,
    then minimizes the next key component over them; the final level
    minimizes the (unique) lane index, so ties on the full key resolve
    to the *first* lane -- exactly the host merge's
    ``(objective key, cut tuple)`` order, since index order is cut-tuple
    order.  Pure elementwise/min ops, so the same code body runs under
    numpy, traced lax, and inside a Pallas kernel."""
    i_min = xp.min(infeas)
    m0 = infeas == i_min
    p = xp.where(m0, primary, xp.inf)
    p_min = xp.min(p)
    m1 = m0 & (p == p_min)
    s = xp.where(m1, secondary, xp.inf)
    s_min = xp.min(s)
    m2 = m1 & (s == s_min)
    i_win = xp.min(xp.where(m2, idxf, _HUGE_IDX))
    return i_min, p_min, s_min, i_win


def argmin_lanes(infeas, primary, secondary, idx,
                 backend: str = "reference") -> tuple:
    """Winner of a batch of objective keys: ``(infeas, primary,
    secondary, idx)`` of the first lane attaining the lexicographic
    minimum key.

    ``backend="reference"`` is the host oracle (a stable ``np.lexsort``,
    so the first minimum wins); ``"lax"`` / ``"pallas"`` run the
    hierarchical masked-minima reduction the fused pipeline uses
    in-kernel.  All three return bit-identical winners -- the fuzzed
    contract of tests/test_search_pipeline.py."""
    infeas = np.asarray(infeas, dtype=np.float64)
    primary = np.asarray(primary, dtype=np.float64)
    secondary = np.asarray(secondary, dtype=np.float64)
    idx = np.asarray(idx, dtype=np.float64)
    if not (infeas.shape == primary.shape == secondary.shape == idx.shape
            and infeas.ndim == 1 and infeas.size):
        raise ValueError("argmin_lanes wants four equal-length 1-D lanes")
    if backend == "reference":
        order = np.lexsort((idx, secondary, primary, infeas))
        j = int(order[0])
        return (float(infeas[j]), float(primary[j]),
                float(secondary[j]), int(idx[j]))
    if backend not in ("lax", "pallas"):
        raise ValueError(f"unknown argmin_lanes backend: {backend!r}")
    if not HAVE_JAX:
        raise RuntimeError(f"argmin_lanes backend {backend!r} requires jax")
    with jax.enable_x64(True):
        if backend == "lax":
            w = _argmin_hier(jnp.asarray(infeas), jnp.asarray(primary),
                             jnp.asarray(secondary), jnp.asarray(idx), jnp)
            return (float(w[0]), float(w[1]), float(w[2]), int(w[3]))
        lp = _pad_up(max(infeas.size, 1), LANES)
        x = np.zeros((SUBLANES, lp), dtype=np.float64)
        x[0, :lp] = _PAD_RANK
        x[1, :lp] = np.inf
        x[2, :lp] = np.inf
        x[3, :lp] = _HUGE_IDX
        x[0, :infeas.size] = infeas
        x[1, :infeas.size] = primary
        x[2, :infeas.size] = secondary
        x[3, :infeas.size] = idx
        _refuse_f64_on_tpu("argmin")
        row = np.asarray(_build_argmin_call(lp)(x))[0]
        return (float(row[0]), float(row[1]), float(row[2]), int(row[3]))


def _fold(best, w):
    """Deterministic host fold of chunk winners: plain tuple comparison
    on ``(infeas, primary, secondary, idx)``.  Chunk index ranges are
    disjoint, so ties through the idx component are impossible and the
    fold order cannot matter."""
    w = (float(w[0]), float(w[1]), float(w[2]), float(w[3]))
    return w if best is None or w < best else best


# ------------------------------------------------------------- shared tables
def _engine_tables(engine) -> dict:
    """Per-engine prepared arrays for the fused variants (built once and
    stashed on the engine, with its ``_at`` alloc tables)."""
    tbl = engine.__dict__.get("_pipeline_tables")
    if tbl is not None:
        return tbl
    with span("pipeline.tables"):
        return _build_engine_tables(engine)


def _build_engine_tables(engine) -> dict:
    if engine._at is None:
        from repro.kernels.alloc_scan import pack_alloc_tables
        engine._at = pack_alloc_tables(engine.gg, engine.hw)
    at = engine._at
    lt, dt, st = engine._lt, engine._dt, engine._st
    hw = engine.hw
    n = at.n
    i32 = np.int32
    alloc32 = (at.is_side, at.gin.astype(i32), at.src_size.astype(i32),
               at.main.astype(i32), at.sc.astype(i32),
               at.sc_size.astype(i32), at.in_size.astype(i32),
               at.out_size.astype(i32), at.wr_cand.astype(i32),
               at.spill_ok,
               np.minimum(at.rem0, np.int64(2 ** 31 - 1)).astype(i32),
               at.loc0.astype(i32))
    lanes = _pad_up(max(n, 1), LANES)
    # (1, lanes) broadcast rows for the Pallas enumeration kernel;
    # padded lanes get run -1 so their frame bit is always 0.
    runof_row = np.full((1, lanes), -1, dtype=i32)
    runof_row[0, :n] = engine._run_of
    pos_row = np.zeros((1, lanes), dtype=i32)
    pos_row[0, :n] = engine._pos_of
    dirneg_row = np.zeros((1, lanes), dtype=i32)
    dirneg_row[0, :n] = engine._dir_neg
    # static cost-table rows for the Pallas cost kernel, f64 (exact int
    # embedding); padded lanes are all-zero -> they contribute a 0.0
    # row-latency term and are masked out of every max by scomp == 0.
    tab = np.zeros((2 * SUBLANES, lanes), dtype=np.float64)
    tab[0, :n] = lt.comp
    tab[1, :n] = lt.row
    tab[2, :n] = lt.weight
    tab[3, :n] = lt.side
    tab[4, :n] = dt.row_fm
    tab[5, :n] = st.compute
    tab[6, :n] = st.weight
    tab[7, :n] = st.out_frame
    tab[8, :n] = st.out_row
    tab[9, :n] = st.wr_row
    tbl = {
        "n": n, "lanes": lanes, "alloc32": alloc32,
        "run_of": engine._run_of.astype(i32),
        "pos_of": engine._pos_of.astype(i32),
        "dir_neg": engine._dir_neg,
        "runof_row": runof_row, "pos_row": pos_row,
        "dirneg_row": dirneg_row, "tab": tab,
        "lt_comp": lt.comp, "lt_row": lt.row, "lt_weight": lt.weight,
        "lt_side": lt.side, "dt_rowfm": dt.row_fm.astype(np.float64),
        "st_comp": st.compute, "st_weight": st.weight.astype(np.float64),
        "st_outf": st.out_frame.astype(np.float64),
        "st_outr": st.out_row.astype(np.float64),
        "st_wrr": st.wr_row.astype(np.float64),
        "bpc": float(hw.dram_bytes_per_cycle),
        "goc": float(hw.group_overhead_cycles),
        "budget": int(hw.sram_budget),
        "weight_bytes": int(dt.weight_bytes),
        "row_buff": int(st.row_buff),
    }
    engine._pipeline_tables = tbl
    return tbl


# ---------------------------------------------------------- reference variant
def _run_reference(engine, tbl, prefix, dims, strides, S, chunk,
                   objective):
    """Numpy pipeline: the enumeration/decoding is vectorized, the
    allocator replay is ``alloc_scan_ref`` and the reductions are the
    *very same* ``*_fast_batch`` calls the journal scorer uses, so each
    chunk's keys are bit-identical to the host scorer by construction."""
    from repro.kernels.alloc_scan import alloc_scan_ref
    npfx = len(prefix)
    strides_np = np.asarray(strides, dtype=np.int64)
    dims_np = np.asarray(dims, dtype=np.int64)
    budget = tbl["budget"]
    wb = tbl["weight_bytes"]
    best = None
    for lo in range(0, S, chunk):
        j = np.arange(lo, min(lo + chunk, S), dtype=np.int64)
        suf = (j[:, None] // strides_np[None, :]) % dims_np[None, :]
        if npfx:
            pre = np.broadcast_to(np.asarray(prefix, dtype=np.int64),
                                  (len(j), npfx))
            cuts_arr = np.concatenate([pre, suf], axis=1)
        else:
            cuts_arr = suf
        cut = cuts_arr[:, tbl["run_of"]]
        pos = engine._pos_of[None, :]
        frame = np.where(tbl["dir_neg"][None, :], pos >= cut, pos < cut)
        res = alloc_scan_ref(engine._at, frame)
        io = res.io.astype(np.float64)
        lat = latency_cycles_fast_batch(engine._lt, frame, io, engine.hw)
        fm = dram_fm_fast_batch(engine._dt, frame, res.bfm.tolist())
        cand_terms = [(b[0], b[1], b[2], s, w)
                      for b, s, w in zip(res.buff.tolist(),
                                         res.side_buff.tolist(),
                                         res.wrf.tolist())]
        sram, _ = sram_total_fast_batch(engine._st, frame, cand_terms,
                                        engine.hw,
                                        bram_memo=engine._bram_memo)
        sram = np.asarray(sram, dtype=np.int64)
        feasible = (sram <= budget) & res.feasible
        dram_total = np.asarray(fm, dtype=np.float64) + float(wb)
        infeas, primary, secondary = _keys_np(objective, lat, dram_total,
                                              sram, feasible)
        best = _fold(best, argmin_lanes(infeas, primary, secondary,
                                        j.astype(np.float64)))
    return best


# ---------------------------------------------------------------- lax variant
def _make_fused(tbl, C, npfx, dims, strides, S, objective):
    """One fused XLA computation: decode C indices from ``lo``, build
    frame masks, replay the allocator scan, reduce the three cost models
    in f64 and return the chunk's winner 4-tuple.  Static shape/constant
    closure; cached per (chunk size, prefix length, dims, objective)."""
    from repro.kernels.alloc_scan import _scan_impl
    G = tbl["n"]
    bpc, goc = tbl["bpc"], tbl["goc"]
    budget = float(tbl["budget"])
    wb = float(tbl["weight_bytes"])
    row_buff = float(tbl["row_buff"])

    def fused(lo, pref, run_of, pos_of, dir_neg, alloc32,
              lt_comp, lt_row, lt_weight, lt_side, dt_rowfm,
              st_comp, st_weight, st_outf, st_outr, st_wrr):
        # int32 linear indices (S <= _MAX_SPACE, checked by
        # pipeline_subspace): a TPU has no native int64, and its emulated
        # divide makes the mixed-radix decode below compile for minutes
        j = lo + jnp.arange(C, dtype=jnp.int32)
        parts = []
        if npfx:
            parts.append(jnp.broadcast_to(
                pref[None, :], (C, npfx)))
        for q in range(len(dims)):
            parts.append(((j // strides[q]) % dims[q])[:, None])
        cuts = jnp.concatenate(parts, axis=1)
        cut = cuts[:, run_of]
        pos = pos_of[None, :]
        frame = jnp.where(dir_neg[None, :], pos >= cut, pos < cut)
        io, buff, side_buff, wrf, bfm, feas = _scan_impl(frame.T, *alloc32)
        io64 = io[:, :G].astype(jnp.float64)
        mem = (lt_weight[None, :] + io64) / bpc
        frame_lat = jnp.maximum(lt_comp[None, :], mem) + goc
        per = jnp.where(lt_side[None, :], lt_comp[None, :],
                        jnp.where(frame, frame_lat, lt_row[None, :]))
        # det: sequential left-to-right accumulation over groups -- the
        # exact addition order of the host's np.cumsum latency total
        lat = jax.lax.fori_loop(
            0, G, lambda g, acc: acc + per[:, g],
            jnp.zeros((C,), jnp.float64))
        # det: int-exact f64 terms; association-free
        row_terms = jnp.sum(jnp.where(frame, 0.0, dt_rowfm[None, :]),
                            axis=1)
        dram_total = row_terms + bfm.astype(jnp.float64) + wb
        rowm = st_comp[None, :] & ~frame
        frm = st_comp[None, :] & frame
        wbuff = jnp.max(jnp.where(rowm, st_weight[None, :], 0.0), axis=1)
        outf = jnp.max(jnp.where(frm, st_outf[None, :], 0.0), axis=1)
        outr = jnp.max(jnp.where(rowm, st_outr[None, :], 0.0), axis=1)
        wrr = jnp.max(jnp.where(rowm, st_wrr[None, :], 0.0), axis=1)
        b = buff.astype(jnp.float64)
        sram_total = (row_buff + jnp.maximum(outf, outr)
                      + jnp.maximum(wrr, wrf.astype(jnp.float64))
                      + b[:, 0] + jnp.maximum(b[:, 1], wbuff) + b[:, 2]
                      + side_buff.astype(jnp.float64))
        feasible = (sram_total <= budget) & feas
        if objective == "latency":
            primary, secondary = lat, sram_total
        elif objective == "sram":
            primary, secondary = sram_total, lat
        else:
            primary, secondary = dram_total, lat
        valid = j < S
        infeas = jnp.where(feasible, 0.0, 1.0)
        infeas = jnp.where(valid, infeas, _PAD_RANK)
        idxf = jnp.where(valid, j.astype(jnp.float64), _HUGE_IDX)
        return jnp.stack(_argmin_hier(infeas, primary, secondary,
                                      idxf, jnp))

    return fused


def _shard_fused(fused, mesh):
    """The fused chunk step over a 1-D device mesh (axis ``"d"``): device
    ``i`` scores the chunk starting at ``los[i]`` and returns its winner
    row.  Contiguous linear ranges per device are the disjoint
    partitioning of ``search_pool.partition_space``, expressed on the
    linear index; winners merge with the same deterministic tuple order,
    so results are device-count-invariant."""
    from jax.sharding import PartitionSpec as P

    def per_device(los, *args):
        return fused(los[0], *args)[None, :]

    # check_vma=False: the body is embarrassingly parallel (no
    # collectives), but jax's varying-axes checker cannot see through the
    # alloc scan's carry and rejects it.
    return jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=(P("d"),) + (P(),) * 15,
        out_specs=P("d"), check_vma=False))


def _lax_args(tbl, prefix) -> tuple:
    """The fused step's operands after ``lo``: the prefix cuts, then the
    engine's tables in ``_make_fused``'s parameter order."""
    pref = np.asarray(prefix if prefix else [0], dtype=np.int32)
    return (pref, tbl["run_of"], tbl["pos_of"], tbl["dir_neg"],
            tbl["alloc32"], tbl["lt_comp"], tbl["lt_row"],
            tbl["lt_weight"], tbl["lt_side"], tbl["dt_rowfm"],
            tbl["st_comp"], tbl["st_weight"], tbl["st_outf"],
            tbl["st_outr"], tbl["st_wrr"])


def _device_tables(engine, tables, ndev):
    """``(mesh, placement, device copy of tables)`` for ``ndev`` devices,
    put on the device on the engine's first launch there and kept for its
    life: a launch then uploads only its start index.  One device leaves
    the copies uncommitted, as host operands are, so the jitted step's
    key and lowering do not change; several get them replicated over the
    mesh the sharded step runs on (mesh and placement None on one).
    Called inside the caller's x64 scope, so the f64 cost tables stay
    f64."""
    held = engine.__dict__.setdefault("_pipeline_operands", {})
    got = held.get(ndev)
    if got is None:
        with span("pipeline.upload"):
            mesh = placement = None
            if ndev > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P
                mesh = jax.make_mesh((ndev,), ("d",))
                placement = NamedSharding(mesh, P())
            got = (mesh, placement, jax.device_put(tables, placement))
        held[ndev] = got
    return got


def _run_lax(engine, tbl, prefix, dims, strides, S, chunk, objective):
    cache = engine.__dict__.setdefault("_pipeline_calls", {})
    npfx = len(prefix)
    key = ("lax", chunk, npfx, dims, objective)
    ndev = len(jax.devices())
    pref, *tables = _lax_args(tbl, prefix)
    mesh, placement, tables = _device_tables(engine, tuple(tables), ndev)
    calls = cache.get(key)
    if calls is None:
        fused = _make_fused(tbl, chunk, npfx, dims, strides, S, objective)
        sharded = None if mesh is None else _shard_fused(fused, mesh)
        calls = (jax.jit(fused), sharded)
        cache[key] = calls
        # the first call of a newly built jitted step traces, lowers and
        # loads it
        name = "pipeline.load"
    else:
        name = "pipeline.dispatch"
    jfused, sharded = calls
    args = (jax.device_put(pref, placement),) + tables
    if sharded is None:
        call, step, offsets = jfused, chunk, np.int32(0)
    else:
        # device i scores the chunk at base + i * chunk
        call, step = sharded, chunk * ndev
        offsets = np.arange(ndev, dtype=np.int32) * chunk
    # Pipelined: every launch of the sub-space is enqueued, its winner
    # row's copy to the host started, before any row is waited for, so
    # the device runs the steps back to back.
    outs = []
    for base in range(0, S, step):
        with span(name):
            out = call(base + offsets, *args)
            # a result that is already a host array has nothing to copy
            copy = getattr(out, "copy_to_host_async", None)
            if copy is not None:
                copy()
        name = "pipeline.dispatch"
        outs.append(out)
    best = None
    for out in outs:
        with span("pipeline.wait"):
            rows = np.asarray(out)
        # one row a device, folded in launch order
        for row in np.atleast_2d(rows):
            best = _fold(best, row)
    return best


# ------------------------------------------------------------- pallas variant
if HAVE_JAX:

    def _enum_kernel(meta_ref, pref_ref, runof_ref, pos_ref, dirneg_ref,
                     out_ref, *, nr, npfx, strides, dims, block_b, lanes):
        """Decode one candidate tile's linear indices into frame masks.

        ``cut[run r]`` is either the fixed prefix cut or the mixed-radix
        digit ``(j // stride) % dim``; the mask is then the same
        position/direction comparison as ``_frame_matrix``.  Padded
        lanes carry run -1 and stay 0."""
        i = pl.program_id(0)
        j = (meta_ref[0] + i * block_b
             + jax.lax.broadcasted_iota(jnp.int32, (block_b, lanes), 0))
        runof = runof_ref[...]
        pos = pos_ref[...]
        dneg = dirneg_ref[...] != 0
        cut = jnp.zeros((block_b, lanes), jnp.int32)
        for r in range(nr):
            if r < npfx:
                val = pref_ref[r] + jnp.zeros((block_b, lanes), jnp.int32)
            else:
                q = r - npfx
                val = (j // strides[q]) % dims[q]
            cut = jnp.where(runof == r, val, cut)
        # and/or of comparisons, not a select between bool vectors:
        # Mosaic cannot lower the i8 -> i1 truncation a select emits
        fr = ((dneg & (pos >= cut)) | (~dneg & (pos < cut))) & (runof >= 0)
        out_ref[...] = fr.astype(jnp.int32)

    @functools.lru_cache(maxsize=64)
    def _build_enum_call(nb, block_b, lanes, nr, npfx, strides, dims,
                         interpret):
        kernel = functools.partial(_enum_kernel, nr=nr, npfx=npfx,
                                   strides=strides, dims=dims,
                                   block_b=block_b, lanes=lanes)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb,),
            in_specs=[pl.BlockSpec((1, lanes), lambda i, *_: (0, 0))] * 3,
            out_specs=pl.BlockSpec((block_b, lanes),
                                   lambda i, *_: (i, 0)))
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nb * block_b, lanes),
                                           jnp.int32),
            interpret=interpret)

    def _cost_kernel(meta_ref, frame_ref, io_ref, stats_ref, tab_ref,
                     out_ref, *, block_b, lanes, bpc, goc, budget,
                     wbytes, row_buff, obj):
        """f64 cost reductions + in-block hierarchical argmin.

        One output row per tile: the block winner's
        ``(infeas, primary, secondary, idx)``.  The latency total uses a
        one-hot masked lane sum inside a sequential ``fori_loop`` --
        each step adds exactly one group's term, reproducing the host's
        left-to-right ``np.cumsum`` order bit-for-bit; padded lanes add
        an exact 0.0."""
        i = pl.program_id(0)
        tab = tab_ref[...]
        comp, rowl, wlat = tab[0:1, :], tab[1:2, :], tab[2:3, :]
        sidem = tab[3:4, :] > 0.0
        rowfm = tab[4:5, :]
        scomp = tab[5:6, :] > 0.0
        swt, soutf = tab[6:7, :], tab[7:8, :]
        soutr, swrr = tab[8:9, :], tab[9:10, :]
        frame = frame_ref[...] > 0
        io = io_ref[...]
        mem = (wlat + io) / bpc
        fl = jnp.maximum(comp, mem) + goc
        per = jnp.where(sidem, comp, jnp.where(frame, fl, rowl))
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_b, lanes), 1)

        def body(g, acc):
            # det: one-hot lane mask -> exactly one term per step, added
            # in group order (the host's np.cumsum sequence)
            return acc + jnp.sum(jnp.where(lane == g, per, 0.0),
                                 axis=1, keepdims=True)

        lat = jax.lax.fori_loop(0, lanes, body,
                                jnp.zeros((block_b, 1), jnp.float64))
        # det: int-exact f64 terms; association-free
        rterm = jnp.sum(jnp.where(frame, 0.0, rowfm), axis=1,
                        keepdims=True)
        st = stats_ref[...]
        sl = jax.lax.broadcasted_iota(jnp.int32, (block_b, LANES), 1)

        def col(kk):
            # det: one-hot column extraction, a single nonzero term
            return jnp.sum(jnp.where(sl == kk, st, 0.0), axis=1,
                           keepdims=True)

        b0, b1, b2, side = col(0), col(1), col(2), col(3)
        wrf, bfm = col(4), col(5)
        feas = col(6) > 0.0
        dram = rterm + bfm + wbytes
        wbuff = jnp.max(jnp.where(scomp & ~frame, swt, 0.0), axis=1,
                        keepdims=True)
        outf = jnp.max(jnp.where(scomp & frame, soutf, 0.0), axis=1,
                       keepdims=True)
        outr = jnp.max(jnp.where(scomp & ~frame, soutr, 0.0), axis=1,
                       keepdims=True)
        wrr = jnp.max(jnp.where(scomp & ~frame, swrr, 0.0), axis=1,
                      keepdims=True)
        sram = (row_buff + jnp.maximum(outf, outr)
                + jnp.maximum(wrr, wrf) + b0 + jnp.maximum(b1, wbuff)
                + b2 + side)
        feasible = (sram <= budget) & feas
        j = (meta_ref[0] + i * block_b
             + jax.lax.broadcasted_iota(jnp.int32, (block_b, 1), 0))
        valid = j < meta_ref[1]
        infeas = jnp.where(feasible, 0.0, 1.0)
        infeas = jnp.where(valid, infeas, _PAD_RANK)
        idxf = jnp.where(valid, j.astype(jnp.float64), _HUGE_IDX)
        if obj == "latency":
            primary, secondary = lat, sram
        elif obj == "sram":
            primary, secondary = sram, lat
        else:
            primary, secondary = dram, lat
        w0, w1, w2, w3 = _argmin_hier(infeas, primary, secondary,
                                      idxf, jnp)
        ol = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        out_ref[...] = jnp.where(
            ol == 0, w0, jnp.where(ol == 1, w1, jnp.where(
                ol == 2, w2, jnp.where(ol == 3, w3, 0.0))))

    @functools.lru_cache(maxsize=64)
    def _build_cost_call(nb, block_b, lanes, bpc, goc, budget, wbytes,
                         row_buff, obj):
        kernel = functools.partial(_cost_kernel, block_b=block_b,
                                   lanes=lanes, bpc=bpc, goc=goc,
                                   budget=budget, wbytes=wbytes,
                                   row_buff=row_buff, obj=obj)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb,),
            in_specs=[
                pl.BlockSpec((block_b, lanes), lambda i, *_: (i, 0)),
                pl.BlockSpec((block_b, lanes), lambda i, *_: (i, 0)),
                pl.BlockSpec((block_b, LANES), lambda i, *_: (i, 0)),
                pl.BlockSpec((2 * SUBLANES, lanes),
                             lambda i, *_: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, LANES), lambda i, *_: (i, 0)))
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nb, LANES), jnp.float64),
            interpret=_F64_INTERPRET)

    def _argmin_only_kernel(in_ref, out_ref):
        x = in_ref[...]
        w0, w1, w2, w3 = _argmin_hier(x[0:1, :], x[1:2, :], x[2:3, :],
                                      x[3:4, :], jnp)
        ol = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        out_ref[...] = jnp.where(
            ol == 0, w0, jnp.where(ol == 1, w1, jnp.where(
                ol == 2, w2, jnp.where(ol == 3, w3, 0.0))))

    @functools.lru_cache(maxsize=16)
    def _build_argmin_call(lp):
        return pl.pallas_call(
            _argmin_only_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((SUBLANES, lp), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.float64),
            interpret=_F64_INTERPRET)


def _refuse_f64_on_tpu(stage: str) -> None:
    """Raise on a TPU before a float64 Pallas stage (cost, argmin) runs.

    Mosaic has no float64, so these stages exist only in the Pallas
    interpreter (``_F64_INTERPRET``); on a TPU host they refuse instead of
    silently interpreting on the chip.  Off the TPU the tests run them."""
    if _on_tpu():
        raise NotImplementedError(
            f"pipeline:pallas {stage} stage computes in float64, which "
            f"Mosaic cannot compile for a TPU; use engine='pipeline:lax' "
            f"on TPU hosts")


def _run_pallas(engine, tbl, prefix, dims, strides, S, chunk, objective):
    """Staged Pallas composition: enumeration kernel (i32, compiled on
    TPU) -> ``alloc_scan_pallas`` (i32) -> f64 cost/argmin kernel.  The
    cost stage is float64 for oracle exactness, so this variant runs off
    the TPU only (``_refuse_f64_on_tpu``); the masks passed between
    stages are B x G bitmaps, never candidate tuples."""
    from repro.kernels.alloc_scan import alloc_scan_pallas
    G, lanes = tbl["n"], tbl["lanes"]
    nr = len(prefix) + len(dims)
    block_b = max(SUBLANES, min(256, _pad_up(max(chunk, 1), SUBLANES)))
    bp = _pad_up(max(chunk, 1), block_b)
    nb = bp // block_b
    _refuse_f64_on_tpu("cost")
    cost_call = _build_cost_call(nb, block_b, lanes, tbl["bpc"],
                                 tbl["goc"], float(tbl["budget"]),
                                 float(tbl["weight_bytes"]),
                                 float(tbl["row_buff"]), objective)
    enum_call = _build_enum_call(nb, block_b, lanes, nr, len(prefix),
                                 strides, dims, not _on_tpu())
    pref = np.asarray(list(prefix) if prefix else [0], dtype=np.int32)
    best = None
    for lo in range(0, S, chunk):
        c = min(chunk, S - lo)
        frame_pad = np.asarray(enum_call(
            np.asarray([lo], dtype=np.int32), pref, tbl["runof_row"],
            tbl["pos_row"], tbl["dirneg_row"]))
        res = alloc_scan_pallas(engine._at,
                                frame_pad[:c, :G].astype(bool))
        io_pad = np.zeros((bp, lanes), dtype=np.float64)
        io_pad[:c, :G] = res.io
        stats = np.zeros((bp, LANES), dtype=np.float64)
        stats[:c, 0:3] = res.buff
        stats[:c, 3] = res.side_buff
        stats[:c, 4] = res.wrf
        stats[:c, 5] = res.bfm
        stats[:c, 6] = res.feasible
        with jax.enable_x64(True):
            rows = np.asarray(cost_call(
                np.asarray([lo, S], dtype=np.int32), frame_pad, io_pad,
                stats, tbl["tab"]))
        for row in rows:
            best = _fold(best, row)
    return best


# ------------------------------------------------------------------ entrypoint
def pipeline_subspace(engine, prefix, suffix_dims, objective: str,
                      batch_size: int = DEFAULT_BATCH_SIZE,
                      variant: str = "reference"):
    """Argmin over one sub-space through the fused device pipeline.

    Drop-in for ``branch_bound_subspace``'s return contract:
    ``(CandidateMetrics, pruned)`` with the bit-identical
    ``(key, cuts)``-lexicographic winner.  Every candidate is priced
    in-kernel (no pruning), so ``pruned`` is always 0 and the engine's
    ``evaluations`` is credited with the full enumeration count --
    matching the journal path's ``scored + pruned`` total exactly.  The
    winner itself is re-priced through the engine's exact journal
    scorer, so the returned metrics never depend on kernel arithmetic.
    """
    if objective not in OBJECTIVES:
        raise ValueError(f"unknown objective: {objective!r}")
    if variant not in VARIANTS:
        raise ValueError(f"unknown pipeline variant: {variant!r}")
    if variant != "reference" and not HAVE_JAX:
        raise RuntimeError(f"pipeline variant {variant!r} requires jax "
                           f"(use engine='pipeline:reference')")
    prefix = tuple(int(c) for c in prefix)
    dims = tuple(int(d) + 1 for d in suffix_dims)
    nr = len(engine.runs)
    if len(prefix) + len(dims) != nr:
        raise ValueError(f"prefix ({len(prefix)}) + suffix ({len(dims)}) "
                         f"must cover all {nr} runs")
    S = 1
    for d in dims:
        S *= d
    if S > _MAX_SPACE:
        raise ValueError(f"sub-space of {S} candidates exceeds the "
                         f"pipeline's int32 index range ({_MAX_SPACE})")
    chunk = max(1, int(batch_size))
    # the host fold of launch winners is this span's own time
    with span("pipeline.subspace"):
        before = engine.evaluations

        def finish(cuts):
            with span("pipeline.rescore"):
                [m] = engine.score_batch([cuts], memoize=False)
            engine.evaluations = before + S
            return m, 0

        if S == 1:
            return finish(prefix + (0,) * len(dims))
        tbl = _engine_tables(engine)
        strides = _space_strides(dims)
        if variant == "reference":
            best = _run_reference(engine, tbl, prefix, dims, strides, S,
                                  chunk, objective)
        elif variant == "lax":
            with jax.enable_x64(True):
                best = _run_lax(engine, tbl, prefix, dims, strides, S,
                                chunk, objective)
        else:
            # manages its own x64 scope: the i32 enumeration/allocator
            # stages must trace *without* x64 (weak int literals would
            # promote), only the f64 cost stage runs under it
            best = _run_pallas(engine, tbl, prefix, dims, strides, S,
                               chunk, objective)
        assert best is not None and best[0] < _PAD_RANK
        win = int(best[3])
        return finish(prefix + _decode_index(win, strides, dims))
