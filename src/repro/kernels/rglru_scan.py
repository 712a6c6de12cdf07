"""RG-LRU gated linear recurrence -- Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, with the running state h pinned in VMEM scratch
across the sequential chunk grid dimension (the same on-chip state
residency contract as ssd_scan.py).  Within a chunk the recurrence runs as
an unrolled log-depth inclusive scan over the chunk axis.

Layout: a, b [B, S, W] fp32 (gates precomputed by the XLA prologue);
grid (B, S/Q, W/bw) with dimension_semantics (parallel, arbitrary,
parallel) -- wait, state must persist over the S dim, so the grid is
(B, W/bw, S/Q) with the chunk dim innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(a_ref, b_ref, o_ref, h_ref, *, q: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)          # [Q, bw]
    b = b_ref[0].astype(jnp.float32)

    # inclusive scan h_t = a_t h_{t-1} + b_t via log-depth doubling
    # (Blelloch-style on the linear-recurrence monoid)
    prod = a
    acc = b
    shift = 1
    while shift < q:
        prod_s = jnp.roll(prod, shift, axis=0)
        acc_s = jnp.roll(acc, shift, axis=0)
        mask = (jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) >= shift)
        acc = jnp.where(mask, prod * acc_s + acc, acc)
        prod = jnp.where(mask, prod * prod_s, prod)
        shift *= 2
    # fold in the carried state: h_t += (prod over [0..t]) * h_in
    h_in = h_ref[...]                          # [1, bw]
    h = acc + prod * h_in
    o_ref[0] = h.astype(o_ref.dtype)
    h_ref[...] = h[-1:, :]


@functools.partial(jax.jit, static_argnames=("chunk", "block_w",
                                             "interpret"))
def rglru_scan_kernel(a, b, *, chunk: int = 128, block_w: int = 256,
                      interpret: bool = False):
    """a, b [B, S, W] -> h [B, S, W] (fp32 recurrence)."""
    B, S, W = a.shape
    q = min(chunk, S)
    bw = min(block_w, W)
    assert S % q == 0 and W % bw == 0
    kernel = functools.partial(_kernel, q=q)
    return pl.pallas_call(
        kernel,
        grid=(B, W // bw, S // q),
        in_specs=[
            pl.BlockSpec((1, q, bw), lambda i, w, c: (i, c, w)),
            pl.BlockSpec((1, q, bw), lambda i, w, c: (i, c, w)),
        ],
        out_specs=pl.BlockSpec((1, q, bw), lambda i, w, c: (i, c, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
