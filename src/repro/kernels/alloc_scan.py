"""Scan-style on-device allocator replay (tuples x groups).

The batched candidate scorer (``CutpointEngine.score_batch``) prices B cut
tuples as one set of B x G mask-matrix reductions, but until this module
every batch still paid a *Python* allocator replay per candidate to build
the boundary-I/O matrix and the per-candidate buffer terms.  This module
removes that last serial wall: the sequential allocator of Algorithm 1
(``core/allocator.py::alloc_step``) is re-expressed as a **tensorized
state machine** -- fixed-width integer arrays per candidate, one
data-independent update rule per group -- and the whole replay for a
B-candidate batch runs as a single scan over groups.

State encoding (one row per candidate; ``n`` groups, lane ``n`` is the
``GRAPH_INPUT`` pseudo producer, lane ``n+1`` a write-off sink for padded
fan-in slots -- see ``allocator.state_to_arrays`` for the scalar origin):

* ``rem``  (B, n+2) unmet consumer counts (sink starts huge: never dies)
* ``loc``  (B, n+2) location codes -- buffer id 0..2, ``LOC_SIDE``,
  ``LOC_DRAM`` (graph input and sink are DRAM forever)
* ``live`` (B, 3)   owning gid per physical buffer or ``LIVE_EMPTY``
* ``buff`` (B, 3) / ``side_buff`` (B,)  byte maxima (Algorithm 1)
* ``io``   (B, n+2) per-gid boundary-I/O bytes (reads + boundary writes +
  spill write-outs -- exactly the engine's journal-fed ``_x_io`` rows)
* ``bw``   (B, n+2) boundary-write membership (dedups multi-consumer
  row-side reads of one frame tensor)
* ``bfm`` / ``wrf`` / ``feas`` (B,) running DRAM boundary total, eq. (5)
  frame write-buffer max, and spill feasibility

The per-group update rule computes the side / row / frame branches of
``alloc_step`` as masked vector ops and blends them by the candidate's
frame mask -- no per-candidate control flow, so the same rule runs as

* ``alloc_scan_ref``    -- the numpy reference (exact int64, the oracle
  of record for this module and the production ``engine="device"`` path),
* ``alloc_scan_jax``    -- one ``jax.lax.scan`` over groups (int32),
* ``alloc_scan_pallas`` -- a Pallas TPU kernel, grid = (candidate tiles,
  groups): TPU grids iterate the trailing axis sequentially, so the
  allocator state lives in VMEM scratch across group steps while the
  static per-group tables ride in SMEM via scalar prefetch.  Falls back
  to interpret mode off-TPU, like the other kernels in this package.

All three produce **bit-identical integers** (every quantity is integral
and stays far below 2^31 for the CNN zoo -- int32 is exact, unlike the
float32 scoring kernel in score_batch.py, so the Pallas path here is part
of the exactness contract, enforced by tests/test_alloc_scan.py).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.allocator import (GRAPH_INPUT, LIVE_EMPTY, LOC_DRAM,
                                  LOC_SIDE, NUM_BUFFERS, graph_steps,
                                  init_alloc_state, spill_is_long_path,
                                  state_to_arrays)
from repro.kernels.score_batch import HAVE_JAX, LANES, SUBLANES, _pad_up

if HAVE_JAX:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

# Sink slot's initial consumer count: decremented once per padded fan-in
# slot per step, must never reach zero.
_SINK_REMAINING = 1 << 40


@dataclass(frozen=True)
class AllocScanTables:
    """Static per-graph tables of the tensorized allocator.

    Per-group rows are indexed by gid; fan-in is padded to width ``k``
    with slots pointing at the sink lane (size 0, location DRAM -- every
    effect of a padded slot is provably a no-op, so the update rule needs
    no validity masks)."""
    n: int                     # real group count
    k: int                     # padded fan-in width (>= 1)
    input_idx: int             # == n: GRAPH_INPUT lane
    sink_idx: int              # == n + 1: padded-slot write-off lane
    is_side: np.ndarray        # (G,) bool
    gin: np.ndarray            # (G, K) int32 producer lanes
    src_size: np.ndarray       # (G, K) int64 producer out bytes (pads: 0)
    main: np.ndarray           # (G,) int32 main-path producer lane
    sc: np.ndarray             # (G,) int32 shortcut lane (sink if none)
    sc_size: np.ndarray        # (G,) int64
    in_size: np.ndarray        # (G,) int64
    out_size: np.ndarray       # (G,) int64
    wr_cand: np.ndarray        # (n+2,) int64 eq. (5) frame write candidates
    spill_ok: np.ndarray       # (G,) bool long-path spill tolerated
    rem0: np.ndarray           # (n+2,) int64 initial consumer counts
    loc0: np.ndarray           # (n+2,) int8 initial location codes


@dataclass(frozen=True)
class AllocScanResult:
    """Per-candidate replay outputs, host-side int64 (B leading axis).

    ``io`` / ``buff`` / ``side_buff`` / ``wrf`` / ``bfm`` / ``feasible``
    are, respectively, the engine's ``_x_io`` rows, the replayed
    ``Allocation.buff`` / ``side_buff``, and its ``_x_wrf`` / ``_x_bfm``
    / ``_x_feas`` accumulators -- everything ``score_batch`` extracts
    from a journal replay, for the whole batch at once."""
    io: np.ndarray             # (B, n)
    buff: np.ndarray           # (B, 3)
    side_buff: np.ndarray      # (B,)
    wrf: np.ndarray            # (B,)
    bfm: np.ndarray            # (B,)
    feasible: np.ndarray       # (B,) bool


def pack_alloc_tables(gg, hw) -> AllocScanTables:
    """Resolve one graph's allocator walk into scan tables.

    ``hw`` feeds the eq. (5) write-buffer candidates (``hw.to`` lane
    count); everything else is pure graph topology from
    ``allocator.graph_steps`` plus the exported ``init_alloc_state``."""
    from repro.core.sram import sram_tables

    steps = graph_steps(gg)
    n = len(steps)
    ni, nd = n, n + 1
    k = max(1, max(len(s.gin) for s in steps))

    def lane(src: int) -> int:
        return ni if src == GRAPH_INPUT else src

    is_side = np.zeros(n, dtype=bool)
    gin = np.full((n, k), nd, dtype=np.int32)
    src_size = np.zeros((n, k), dtype=np.int64)
    main = np.full(n, ni, dtype=np.int32)
    sc = np.full(n, nd, dtype=np.int32)
    sc_size = np.zeros(n, dtype=np.int64)
    in_size = np.zeros(n, dtype=np.int64)
    out_size = np.zeros(n, dtype=np.int64)
    spill_ok = np.zeros(n, dtype=bool)
    for g, s in enumerate(steps):
        is_side[g] = s.is_side
        for j, (src, sz) in enumerate(zip(s.gin, s.src_sizes)):
            gin[g, j] = lane(src)
            src_size[g, j] = sz
        if s.gin:
            main[g] = lane(s.gin[0])
        if s.sc_src is not None:
            sc[g] = lane(s.sc_src)
            sc_size[g] = s.sc_size
        in_size[g] = s.in_size
        out_size[g] = s.out_size
        spill_ok[g] = spill_is_long_path(gg, g)

    st = sram_tables(gg, hw)
    wr_cand = np.zeros(n + 2, dtype=np.int64)
    wr_cand[:n] = np.where(st.compute, np.asarray(st.wr_frame), 0)

    init = state_to_arrays(init_alloc_state(gg, lean=True))
    rem0 = np.empty(n + 2, dtype=np.int64)
    rem0[:n] = init["remaining"][:n]
    rem0[ni] = init["remaining"][n]          # graph input (list slot -1)
    rem0[nd] = _SINK_REMAINING
    loc0 = np.full(n + 2, LOC_DRAM, dtype=np.int8)
    loc0[:n] = init["location"][:n]
    loc0[ni] = init["location"][n]
    return AllocScanTables(n=n, k=k, input_idx=ni, sink_idx=nd,
                           is_side=is_side, gin=gin, src_size=src_size,
                           main=main, sc=sc, sc_size=sc_size,
                           in_size=in_size, out_size=out_size,
                           wr_cand=wr_cand, spill_ok=spill_ok,
                           rem0=rem0, loc0=loc0)


# ------------------------------------------------------------- numpy oracle
def _first_free(mask: np.ndarray) -> np.ndarray:
    """Lowest buffer id whose (B, 3) mask column is True, else -1."""
    return np.where(mask[:, 0], 0,
                    np.where(mask[:, 1], 1,
                             np.where(mask[:, 2], 2, -1)))


def alloc_scan_ref(t: AllocScanTables, frame: np.ndarray) -> AllocScanResult:
    """Numpy reference replay: B candidates through all groups, exact.

    ``frame`` is the (B, G) frame-mask matrix.  The loop is over *groups*
    only; every step is a handful of (B,)-vector ops, so the whole batch
    advances in lock-step -- the same data-independent rule the jax scan
    and the Pallas kernel run, with static fan-in slots unrolled."""
    B = frame.shape[0]
    n, ni = t.n, t.input_idx
    NB = NUM_BUFFERS
    rem = np.broadcast_to(t.rem0, (B, n + 2)).copy()
    loc = np.broadcast_to(t.loc0, (B, n + 2)).copy()
    live = np.full((B, NB), LIVE_EMPTY, dtype=np.int64)
    buff = np.zeros((B, NB), dtype=np.int64)
    side_buff = np.zeros(B, dtype=np.int64)
    io = np.zeros((B, n + 2), dtype=np.int64)
    bw = np.zeros((B, n + 2), dtype=bool)
    bfm = np.zeros(B, dtype=np.int64)
    wrf = np.zeros(B, dtype=np.int64)
    feas = np.ones(B, dtype=bool)
    sink = t.sink_idx

    for g in range(n):
        slots = [(int(t.gin[g, j]), int(t.src_size[g, j]))
                 for j in range(t.k) if t.gin[g, j] != sink]
        outsz = int(t.out_size[g])

        if t.is_side[g]:
            # SE side path: side space regardless of mode, consume, free.
            np.maximum(side_buff, outsz, out=side_buff)
            loc[:, g] = LOC_SIDE
            for src, _ in slots:
                rem[:, src] -= 1
            for src, _ in slots:
                if src == ni:
                    continue
                dead = rem[:, src] <= 0
                sl = loc[:, src]
                for i in range(NB):
                    live[:, i] = np.where(
                        dead & (sl == i) & (live[:, i] == src),
                        LIVE_EMPTY, live[:, i])
            continue

        fr = frame[:, g]
        rw = ~fr

        # ---- frame pre-state: operand locations, DRAM reads, fetch slot
        mloc = loc[:, t.main[g]]
        main_in_buf = mloc < NB
        read_bytes = np.zeros(B, dtype=np.int64)
        in_buf = np.zeros((B, NB), dtype=bool)
        for src, sz in slots:
            sl = loc[:, src]
            read_bytes += np.where(sl == LOC_DRAM, sz, 0)
            for i in range(NB):
                in_buf[:, i] |= sl == i
        fetch_b = _first_free(live == LIVE_EMPTY)
        need_fetch = ~main_in_buf & (fetch_b >= 0)
        insz = int(t.in_size[g])
        for i in range(NB):
            cond = fr & ((main_in_buf & (mloc == i))
                         | (need_fetch & (fetch_b == i)))
            buff[:, i] = np.where(cond, np.maximum(buff[:, i], insz),
                                  buff[:, i])
            in_buf[:, i] |= need_fetch & (fetch_b == i)
        if t.sc[g] != sink:
            sloc = loc[:, t.sc[g]]
            scsz = int(t.sc_size[g])
            for i in range(NB):
                cond = fr & (sloc == i)
                buff[:, i] = np.where(cond, np.maximum(buff[:, i], scsz),
                                      buff[:, i])

        # ---- row branch: frame-produced operands cross the boundary
        for src, sz in slots:
            if src == ni:
                continue                 # graph input is never in a buffer
            add = rw & (loc[:, src] < NB) & ~bw[:, src]
            if add.any():
                bw[:, src] |= add
                delta = np.where(add, sz, 0)
                io[:, src] += delta
                bfm += delta
                wrf = np.where(add, np.maximum(wrf, t.wr_cand[src]), wrf)

        # ---- consume inputs
        for src, _ in slots:
            rem[:, src] -= 1

        # ---- frame branch: boundary reads charged to this group
        rb = np.where(fr, read_bytes, 0)
        io[:, g] += rb
        bfm += rb

        # ---- place this group's output
        final = rem[:, g] == 0
        addf = fr & final & ~bw[:, g]
        bw[:, g] |= addf
        delta = np.where(addf, outsz, 0)
        io[:, g] += delta
        bfm += delta
        wrf = np.where(addf, np.maximum(wrf, t.wr_cand[g]), wrf)

        b_out = _first_free((live == LIVE_EMPTY) & ~in_buf)
        main_live = np.zeros(B, dtype=bool)
        for i in range(NB):
            main_live |= (mloc == i) & (live[:, i] == t.main[g])
        reuse = ((b_out < 0) & main_in_buf
                 & (rem[:, t.main[g]] == 0) & main_live)
        b_out = np.where(reuse, mloc, b_out)
        alloc_out = fr & ~final & (b_out >= 0)
        spill = fr & ~final & (b_out < 0)
        add_sp = spill & ~bw[:, g]
        delta = np.where(add_sp, outsz, 0)
        io[:, g] += delta
        bfm += delta
        if not t.spill_ok[g]:
            feas &= ~spill
        for i in range(NB):
            sel = alloc_out & (b_out == i)
            live[:, i] = np.where(sel, g, live[:, i])
            buff[:, i] = np.where(sel, np.maximum(buff[:, i], outsz),
                                  buff[:, i])
        loc[:, g] = np.where(alloc_out, b_out, LOC_DRAM).astype(np.int8)

        # ---- release dead operands (post output claim, as alloc_step)
        for src, _ in slots:
            if src == ni:
                continue
            dead = rem[:, src] <= 0
            sl = loc[:, src]
            for i in range(NB):
                live[:, i] = np.where(
                    dead & (sl == i) & (live[:, i] == src),
                    LIVE_EMPTY, live[:, i])

    return AllocScanResult(io=io[:, :n], buff=buff, side_buff=side_buff,
                           wrf=wrf, bfm=bfm, feasible=feas)


# ------------------------------------------------------------ jax.lax.scan
if HAVE_JAX:

    @jax.jit
    def _scan_impl(frame_t, is_side, gin, src_size, main, sc, sc_size,
                   in_size, out_size, wr_cand, spill_ok, rem0, loc0):
        """One ``lax.scan`` over groups; all arrays int32 (exact: every
        byte quantity stays far below 2^31 for real CNNs)."""
        G, B = frame_t.shape
        NB = NUM_BUFFERS
        k = gin.shape[1]
        i3 = jnp.arange(NB, dtype=jnp.int32)[None, :]     # (1, 3)

        def first_free(mask):                      # (B, 3) -> (B,)
            return jnp.where(mask[:, 0], 0,
                             jnp.where(mask[:, 1], 1,
                                       jnp.where(mask[:, 2], 2, -1)))

        def step(carry, xs):
            (rem, loc, live, buff, side_buff, io, bw, bfm, wrf, feas) = carry
            (fr_col, side_g, gin_g, sz_g, main_g, sc_g, scsz, insz, outsz,
             wrc_g, sok, g) = xs
            ns = ~side_g

            # side branch: side-space max; row/frame blended below by mask
            side_buff = jnp.where(side_g, jnp.maximum(side_buff, outsz),
                                  side_buff)
            fr = fr_col & ns
            rw = ~fr_col & ns

            # ---- frame pre-state
            mloc = loc[:, main_g]
            main_in_buf = mloc < NB
            read_bytes = jnp.zeros(B, jnp.int32)
            in_buf = jnp.zeros((B, NB), bool)
            for j in range(k):
                sl = loc[:, gin_g[j]]
                read_bytes += jnp.where(sl == LOC_DRAM, sz_g[j], 0)
                in_buf = in_buf | (sl[:, None] == i3)
            fetch_b = first_free(live == LIVE_EMPTY)
            need_fetch = ~main_in_buf & (fetch_b >= 0)
            cond_in = fr[:, None] & (
                (main_in_buf[:, None] & (mloc[:, None] == i3))
                | (need_fetch[:, None] & (fetch_b[:, None] == i3)))
            buff = jnp.where(cond_in, jnp.maximum(buff, insz), buff)
            in_buf = in_buf | (need_fetch[:, None]
                               & (fetch_b[:, None] == i3))
            sloc = loc[:, sc_g]
            cond_sc = fr[:, None] & (sloc[:, None] == i3)
            buff = jnp.where(cond_sc, jnp.maximum(buff, scsz), buff)

            # ---- row branch: frame-produced operands cross the boundary
            for j in range(k):
                src = gin_g[j]
                add = rw & (loc[:, src] < NB) & ~bw[:, src]
                delta = jnp.where(add, sz_g[j], 0)
                bw = bw.at[:, src].set(bw[:, src] | add)
                io = io.at[:, src].add(delta)
                bfm += delta
                wrf = jnp.where(add, jnp.maximum(wrf, wr_cand[src]), wrf)

            # ---- consume inputs
            for j in range(k):
                rem = rem.at[:, gin_g[j]].add(-1)

            # ---- frame boundary reads charged to this group
            rb = jnp.where(fr, read_bytes, 0)
            io = io.at[:, g].add(rb)
            bfm += rb

            # ---- place this group's output
            final = rem[:, g] == 0
            addf = fr & final & ~bw[:, g]
            bw = bw.at[:, g].set(bw[:, g] | addf)
            delta = jnp.where(addf, outsz, 0)
            io = io.at[:, g].add(delta)
            bfm += delta
            wrf = jnp.where(addf, jnp.maximum(wrf, wrc_g), wrf)

            b_out = first_free((live == LIVE_EMPTY) & ~in_buf)
            main_live = jnp.any((mloc[:, None] == i3) & (live == main_g),
                                axis=1)
            reuse = ((b_out < 0) & main_in_buf
                     & (rem[:, main_g] == 0) & main_live)
            b_out = jnp.where(reuse, mloc, b_out)
            alloc_out = fr & ~final & (b_out >= 0)
            spill = fr & ~final & (b_out < 0)
            add_sp = spill & ~bw[:, g]
            delta = jnp.where(add_sp, outsz, 0)
            io = io.at[:, g].add(delta)
            bfm += delta
            feas = feas & (~spill | sok)

            sel = alloc_out[:, None] & (b_out[:, None] == i3)
            live = jnp.where(sel, g, live)
            buff = jnp.where(sel, jnp.maximum(buff, outsz), buff)
            loc = loc.at[:, g].set(
                jnp.where(side_g, LOC_SIDE,
                          jnp.where(alloc_out, b_out, LOC_DRAM)))

            # ---- release dead operands (post output claim)
            for j in range(k):
                src = gin_g[j]
                dead = rem[:, src] <= 0
                sl = loc[:, src]
                freed = (dead[:, None] & (sl[:, None] == i3)
                         & (live == src))
                live = jnp.where(freed, LIVE_EMPTY, live)

            return (rem, loc, live, buff, side_buff, io, bw, bfm, wrf,
                    feas), None

        carry = (
            jnp.broadcast_to(rem0, (B, rem0.shape[0])),
            jnp.broadcast_to(loc0, (B, loc0.shape[0])),
            jnp.full((B, NB), LIVE_EMPTY, jnp.int32),
            jnp.zeros((B, NB), jnp.int32),
            jnp.zeros(B, jnp.int32),
            jnp.zeros((B, rem0.shape[0]), jnp.int32),
            jnp.zeros((B, rem0.shape[0]), bool),
            jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.int32),
            jnp.ones(B, bool),
        )
        xs = (frame_t, is_side, gin, src_size, main, sc, sc_size,
              in_size, out_size, wr_cand[:G], spill_ok,
              jnp.arange(G, dtype=jnp.int32))
        carry, _ = jax.lax.scan(step, carry, xs)
        (rem, loc, live, buff, side_buff, io, bw, bfm, wrf, feas) = carry
        return io, buff, side_buff, wrf, bfm, feas

    def alloc_scan_jax(t: AllocScanTables,
                       frame: np.ndarray) -> AllocScanResult:
        """``jax.lax.scan`` replay; bit-identical integers to the numpy
        reference (int32 internally -- exact for realistic byte counts;
        the sink lane's consumer sentinel is clamped to fit, it only has
        to outlast G x K decrements)."""
        i32 = np.int32
        io, buff, side_buff, wrf, bfm, feas = _scan_impl(
            np.ascontiguousarray(frame.T),
            t.is_side, t.gin.astype(i32), t.src_size.astype(i32),
            t.main.astype(i32), t.sc.astype(i32), t.sc_size.astype(i32),
            t.in_size.astype(i32), t.out_size.astype(i32),
            t.wr_cand.astype(i32), t.spill_ok,
            np.minimum(t.rem0, np.int64(2 ** 31 - 1)).astype(i32),
            t.loc0.astype(i32))
        return AllocScanResult(
            io=np.asarray(io, dtype=np.int64)[:, :t.n],
            buff=np.asarray(buff, dtype=np.int64),
            side_buff=np.asarray(side_buff, dtype=np.int64),
            wrf=np.asarray(wrf, dtype=np.int64),
            bfm=np.asarray(bfm, dtype=np.int64),
            feasible=np.asarray(feas, dtype=bool))

else:                                      # pragma: no cover - jax baked in

    def alloc_scan_jax(t, frame):
        raise RuntimeError("jax is not available: alloc_backend='scan' "
                           "requires jax (use alloc_backend='reference')")


# ------------------------------------------------------------ pallas kernel
# acc scratch lane assignment (per candidate row)
_ACC_SIDE = NUM_BUFFERS          # lanes 0..2: buff maxima
_ACC_WRF = NUM_BUFFERS + 1
_ACC_BFM = NUM_BUFFERS + 2
_ACC_FEAS = NUM_BUFFERS + 3
_N_ACC = NUM_BUFFERS + 4

if HAVE_JAX:

    def _alloc_kernel(is_side_s, gin_s, srcsz_s, main_s, sc_s, scsz_s,
                      insz_s, outsz_s, wrc_s, sok_s,
                      frame_ref, rem0_ref, loc0_ref, io_ref, stats_ref,
                      rem_ref, loc_ref, bw_ref, ios_ref, live_ref, acc_ref,
                      *, k: int, block_b: int, lanes: int):
        """One grid step == one group for one candidate tile.

        TPU grids run the trailing axis sequentially, so the allocator
        state persists in VMEM scratch across the group axis; dynamic
        per-gid lanes are addressed with one-hot iota masks (gather =
        masked row sum, scatter = masked select) and the per-group step
        table rides in SMEM via scalar prefetch."""
        t = pl.program_id(1)
        nt = pl.num_programs(1)
        NB = NUM_BUFFERS
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_b, lanes), 1)
        l3 = jax.lax.broadcasted_iota(jnp.int32, (block_b, LANES), 1)

        @pl.when(t == 0)
        def _init():
            rem_ref[...] = jnp.broadcast_to(rem0_ref[...],
                                            (block_b, lanes))
            loc_ref[...] = jnp.broadcast_to(loc0_ref[...],
                                            (block_b, lanes))
            bw_ref[...] = jnp.zeros((block_b, lanes), jnp.int32)
            ios_ref[...] = jnp.zeros((block_b, lanes), jnp.int32)
            live_ref[...] = jnp.full((block_b, LANES), LIVE_EMPTY,
                                     jnp.int32)
            acc_ref[...] = jnp.where(l3 == _ACC_FEAS, 1, 0)

        rem = rem_ref[...]
        loc = loc_ref[...]
        bw = bw_ref[...]
        io = ios_ref[...]
        live = live_ref[...]                 # lanes 0..2 hold owners
        acc = acc_ref[...]

        def colv(x, j):                      # lane j of x, as (B, 1)
            return jnp.sum(jnp.where(lane == j, x, 0), axis=1,
                           keepdims=True)

        def col3(x, j):                      # lane j of a LANES-wide row
            return jnp.sum(jnp.where(l3 == j, x, 0), axis=1,
                           keepdims=True)

        side_g = is_side_s[t] > 0
        main_g = main_s[t]
        sc_g = sc_s[t]
        scsz = scsz_s[t]
        insz = insz_s[t]
        outsz = outsz_s[t]
        wrc_g = wrc_s[t]
        sok = sok_s[t] > 0

        fr_col = colv(frame_ref[...], t) > 0           # (B, 1)
        ns = jnp.logical_not(side_g)
        fr = fr_col & ns
        rw = jnp.logical_not(fr_col) & ns

        # side branch: side-space max
        acc = jnp.where(side_g & (l3 == _ACC_SIDE),
                        jnp.maximum(acc, outsz), acc)

        # ---- frame pre-state
        mloc = colv(loc, main_g)                       # (B, 1)
        main_in_buf = mloc < NB
        read_bytes = jnp.zeros((block_b, 1), jnp.int32)
        in_buf = l3 < 0                                # lanes 0..2 used
        for j in range(k):
            sl = colv(loc, gin_s[t, j])
            read_bytes += jnp.where(sl == LOC_DRAM, srcsz_s[t, j], 0)
            in_buf = in_buf | (sl == l3)
        free = (l3 < NB) & (live == LIVE_EMPTY)
        f0, f1, f2 = col3(free, 0) > 0, col3(free, 1) > 0, col3(free, 2) > 0
        fetch_b = jnp.where(f0, 0, jnp.where(f1, 1, jnp.where(f2, 2, -1)))
        need_fetch = jnp.logical_not(main_in_buf) & (fetch_b >= 0)
        cond_in = fr & ((main_in_buf & (mloc == l3))
                        | (need_fetch & (fetch_b == l3)))
        acc = jnp.where(cond_in & (l3 < NB), jnp.maximum(acc, insz), acc)
        in_buf = in_buf | (need_fetch & (fetch_b == l3))
        sloc = colv(loc, sc_g)
        acc = jnp.where(fr & (sloc == l3) & (l3 < NB),
                        jnp.maximum(acc, scsz), acc)

        # ---- row branch: frame-produced operands cross the boundary
        bfm_add = jnp.zeros((block_b, 1), jnp.int32)
        wrf_new = jnp.zeros((block_b, 1), jnp.int32)
        for j in range(k):
            src = gin_s[t, j]
            sl = colv(loc, src)
            already = colv(bw, src) > 0
            add = rw & (sl < NB) & jnp.logical_not(already)
            delta = jnp.where(add, srcsz_s[t, j], 0)
            bw = jnp.where((lane == src) & add, 1, bw)
            io = jnp.where(lane == src, io + delta, io)
            bfm_add += delta
            wrf_new = jnp.maximum(wrf_new,
                                  jnp.where(add, wrc_s[src], 0))

        # ---- consume inputs
        for j in range(k):
            rem = jnp.where(lane == gin_s[t, j], rem - 1, rem)

        # ---- frame boundary reads charged to this group
        rb = jnp.where(fr, read_bytes, 0)
        io = jnp.where(lane == t, io + rb, io)
        bfm_add += rb

        # ---- place this group's output
        final = colv(rem, t) == 0
        addf = fr & final & jnp.logical_not(colv(bw, t) > 0)
        bw = jnp.where((lane == t) & addf, 1, bw)
        delta = jnp.where(addf, outsz, 0)
        io = jnp.where(lane == t, io + delta, io)
        bfm_add += delta
        wrf_new = jnp.maximum(wrf_new, jnp.where(addf, wrc_g, 0))

        ofree = free & jnp.logical_not(in_buf)
        o0, o1, o2 = col3(ofree, 0) > 0, col3(ofree, 1) > 0, col3(ofree, 2) > 0
        b_out = jnp.where(o0, 0, jnp.where(o1, 1, jnp.where(o2, 2, -1)))
        main_live = jnp.sum(jnp.where((mloc == l3) & (live == main_g),
                                      1, 0), axis=1, keepdims=True) > 0
        reuse = ((b_out < 0) & main_in_buf
                 & (colv(rem, main_g) == 0) & main_live)
        b_out = jnp.where(reuse, mloc, b_out)
        alloc_out = fr & jnp.logical_not(final) & (b_out >= 0)
        spill = fr & jnp.logical_not(final) & (b_out < 0)
        add_sp = spill & jnp.logical_not(colv(bw, t) > 0)
        delta = jnp.where(add_sp, outsz, 0)
        io = jnp.where(lane == t, io + delta, io)
        bfm_add += delta
        feas_kill = spill & jnp.logical_not(sok)

        sel = alloc_out & (b_out == l3) & (l3 < NB)
        live = jnp.where(sel, t, live)
        acc = jnp.where(sel, jnp.maximum(acc, outsz), acc)
        loc_t = jnp.where(side_g, LOC_SIDE,
                          jnp.where(alloc_out, b_out, LOC_DRAM))
        loc = jnp.where(lane == t, loc_t, loc)

        # ---- release dead operands (post output claim)
        for j in range(k):
            src = gin_s[t, j]
            dead = colv(rem, src) <= 0
            sl = colv(loc, src)
            freed = dead & (sl == l3) & (live == src) & (l3 < NB)
            live = jnp.where(freed, LIVE_EMPTY, live)

        # fold the scalar accumulators into their acc lanes
        acc = jnp.where(l3 == _ACC_WRF, jnp.maximum(acc, wrf_new), acc)
        acc = jnp.where(l3 == _ACC_BFM, acc + bfm_add, acc)
        acc = jnp.where((l3 == _ACC_FEAS) & feas_kill, 0, acc)

        rem_ref[...] = rem
        loc_ref[...] = loc
        bw_ref[...] = bw
        ios_ref[...] = io
        live_ref[...] = live
        acc_ref[...] = acc

        @pl.when(t == nt - 1)
        def _emit():
            io_ref[...] = io
            stats_ref[...] = acc

    _ALLOC_CALL_CACHE: dict = {}

    def _build_alloc_call(nb: int, G: int, k: int, block_b: int,
                          lanes: int, interpret: bool):
        from functools import partial
        key = (nb, G, k, block_b, lanes, interpret)
        fn = _ALLOC_CALL_CACHE.get(key)
        if fn is not None:
            return fn
        bp = nb * block_b
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=(nb, G),
            in_specs=[
                pl.BlockSpec((block_b, lanes), lambda i, t, *_: (i, 0)),
                pl.BlockSpec((1, lanes), lambda i, t, *_: (0, 0)),
                pl.BlockSpec((1, lanes), lambda i, t, *_: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_b, lanes), lambda i, t, *_: (i, 0)),
                pl.BlockSpec((block_b, LANES), lambda i, t, *_: (i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_b, lanes), jnp.int32),   # rem
                pltpu.VMEM((block_b, lanes), jnp.int32),   # loc
                pltpu.VMEM((block_b, lanes), jnp.int32),   # bw
                pltpu.VMEM((block_b, lanes), jnp.int32),   # io
                pltpu.VMEM((block_b, LANES), jnp.int32),   # live
                pltpu.VMEM((block_b, LANES), jnp.int32),   # acc
            ],
        )
        call = pl.pallas_call(
            partial(_alloc_kernel, k=k, block_b=block_b, lanes=lanes),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((bp, lanes), jnp.int32),
                jax.ShapeDtypeStruct((bp, LANES), jnp.int32),
            ],
            interpret=interpret,
        )
        fn = _ALLOC_CALL_CACHE[key] = jax.jit(call)
        return fn

    def _alloc_operands(t: AllocScanTables, frame: np.ndarray,
                        block_b: int):
        """The kernel's operands for a (B, G) frame batch -- ten SMEM
        step tables, the padded frame tile and the initial ``rem``/``loc``
        rows -- with its grid geometry ``(nb, block_b, lanes)``."""
        b = frame.shape[0]
        n = t.n
        lanes = _pad_up(n + 2, LANES)
        block_b = max(SUBLANES, min(block_b, _pad_up(max(b, 1), SUBLANES)))
        bp = _pad_up(max(b, 1), block_b)
        fp = np.zeros((bp, lanes), np.int32)
        fp[:b, :n] = frame
        rem0 = np.zeros((1, lanes), np.int32)
        rem0[0, :n + 2] = np.minimum(t.rem0, np.int64(2 ** 31 - 1))
        loc0 = np.full((1, lanes), LOC_DRAM, np.int32)
        loc0[0, :n + 2] = t.loc0
        i32 = np.int32
        scalars = (t.is_side.astype(i32), t.gin.astype(i32),
                   t.src_size.astype(i32), t.main.astype(i32),
                   t.sc.astype(i32), t.sc_size.astype(i32),
                   t.in_size.astype(i32), t.out_size.astype(i32),
                   np.pad(t.wr_cand, (0, lanes - (n + 2))).astype(i32),
                   t.spill_ok.astype(i32))
        return scalars + (fp, rem0, loc0), (bp // block_b, block_b, lanes)

    def alloc_scan_pallas(t: AllocScanTables, frame: np.ndarray,
                          interpret: bool | None = None,
                          block_b: int = 256) -> AllocScanResult:
        """Pallas replay; bit-identical integers to the numpy reference.

        ``interpret=None`` auto-selects: compiled on TPU hosts, Pallas
        interpret mode elsewhere (same kernel body, jax-evaluated)."""
        from repro.kernels.score_batch import _on_tpu
        if interpret is None:
            interpret = not _on_tpu()
        b = frame.shape[0]
        n = t.n
        args, (nb, block_b, lanes) = _alloc_operands(t, frame, block_b)
        fn = _build_alloc_call(nb, n, t.k, block_b, lanes, interpret)
        io, stats = fn(*args)
        io = np.asarray(io, dtype=np.int64)
        stats = np.asarray(stats, dtype=np.int64)
        return AllocScanResult(
            io=io[:b, :n],
            buff=stats[:b, :NUM_BUFFERS],
            side_buff=stats[:b, _ACC_SIDE],
            wrf=stats[:b, _ACC_WRF],
            bfm=stats[:b, _ACC_BFM],
            feasible=stats[:b, _ACC_FEAS] > 0)

else:                                      # pragma: no cover - jax baked in

    def alloc_scan_pallas(t, frame, interpret=None, block_b=256):
        raise RuntimeError("jax is not available: alloc_backend='pallas' "
                           "requires jax (use alloc_backend='reference')")


def alloc_scan(t: AllocScanTables, frame: np.ndarray,
               backend: str = "reference",
               interpret: bool | None = None,
               skip: np.ndarray | None = None) -> AllocScanResult:
    """Run the tensorized allocator replay for a B x G frame-mask batch.

    ``backend`` selects the implementation -- ``"reference"`` (numpy,
    default), ``"scan"`` (``jax.lax.scan``) or ``"pallas"`` -- all three
    bit-identical on integer outputs (tests/test_alloc_scan.py).

    ``skip`` (optional, bool (B,)) masks out batch lanes pruned by the
    branch-and-bound search before any replay work: skipped rows are
    compressed away, the surviving sub-batch runs through the selected
    backend unchanged, and the outputs are scattered back into
    zero-filled full-width arrays (``feasible`` defaults ``True`` on
    skipped lanes so downstream masking stays inert).  The surviving
    rows are bit-identical to an unskipped call on the same sub-batch."""
    if skip is not None:
        skip = np.asarray(skip, dtype=bool)
        b = frame.shape[0]
        if skip.shape != (b,):
            raise ValueError(
                f"skip mask shape {skip.shape} != batch ({b},)")
        n = t.n
        if skip.all():
            return AllocScanResult(
                io=np.zeros((b, n), np.int64),
                buff=np.zeros((b, NUM_BUFFERS), np.int64),
                side_buff=np.zeros(b, np.int64),
                wrf=np.zeros(b, np.int64),
                bfm=np.zeros(b, np.int64),
                feasible=np.ones(b, bool))
        keep = ~skip
        sub = alloc_scan(t, frame[keep], backend=backend,
                         interpret=interpret)
        io = np.zeros((b, n), np.int64)
        buff = np.zeros((b, NUM_BUFFERS), np.int64)
        side_buff = np.zeros(b, np.int64)
        wrf = np.zeros(b, np.int64)
        bfm = np.zeros(b, np.int64)
        feasible = np.ones(b, bool)
        io[keep] = sub.io
        buff[keep] = sub.buff
        side_buff[keep] = sub.side_buff
        wrf[keep] = sub.wrf
        bfm[keep] = sub.bfm
        feasible[keep] = sub.feasible
        return AllocScanResult(io=io, buff=buff, side_buff=side_buff,
                               wrf=wrf, bfm=bfm, feasible=feasible)
    if backend == "reference":
        return alloc_scan_ref(t, frame)
    if backend == "scan":
        return alloc_scan_jax(t, frame)
    if backend == "pallas":
        return alloc_scan_pallas(t, frame, interpret=interpret)
    raise ValueError(f"unknown alloc_scan backend: {backend!r}")
