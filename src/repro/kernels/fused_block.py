"""ShortcutFusion fused residual block -- Pallas TPU kernel.

The paper's frame-reuse mode on the HBM->VMEM hierarchy: the residual
("shortcut") tile x is pinned in VMEM for the whole block

    y = x + [post_norm]( act(n @ Wg) * (n @ Wu) ) @ Wd,   n = rmsnorm(x)

so the stream makes exactly one HBM round-trip per block while the weights
stream through VMEM exactly once (the paper's constraint (10)).  The three
interchangeable buffers of Fig. 6 map to: x tile (shortcut), normalized
tile (input) and the fp32 accumulator (output); weight slabs double-buffer
through the remaining VMEM exactly like the paper's weight blocks.

Grid: (M/bm, F/bf); the ff axis is the sequential 'arbitrary' dimension,
accumulating partial W_down contributions into the VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _act(name: str, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


def _kernel(x_ref, scale_ref, wg_ref, wu_ref, wd_ref, post_ref,
            o_ref, nrm_ref, acc_ref, *, act: str, gated: bool,
            sandwich: bool, eps: float, n_ff: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        x = x_ref[...].astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        n = x * jax.lax.rsqrt(var + eps)
        n = n * (1.0 + scale_ref[...].astype(jnp.float32))
        nrm_ref[...] = n.astype(nrm_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n = nrm_ref[...]
    u = jnp.dot(n, wu_ref[...], preferred_element_type=jnp.float32)
    if gated:
        g = jnp.dot(n, wg_ref[...], preferred_element_type=jnp.float32)
        h = _act(act, g) * u
    else:
        h = _act(act, u)
    acc_ref[...] += jnp.dot(h.astype(n.dtype), wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_ff - 1)
    def _finish():
        y = acc_ref[...]
        if sandwich:
            var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
            y = y * jax.lax.rsqrt(var + eps)
            y = y * (1.0 + post_ref[...].astype(jnp.float32))
        o_ref[...] = (x_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("act", "gated", "sandwich", "block_m",
                              "block_f", "interpret"))
def fused_block(x, scale, w_gate, w_up, w_down, post_scale=None, *,
                act: str = "silu", gated: bool = True,
                sandwich: bool = False, block_m: int = 256,
                block_f: int = 512, eps: float = 1e-6,
                interpret: bool = False):
    """x [M, d] -> [M, d].  w_gate/w_up [d, F], w_down [F, d], scales [d]."""
    M, d = x.shape
    F = w_up.shape[1]
    bm = min(block_m, M)
    bf = min(block_f, F)
    assert M % bm == 0 and F % bf == 0, (M, bm, F, bf)
    n_m, n_ff = M // bm, F // bf
    if post_scale is None:
        post_scale = jnp.zeros_like(scale)

    kernel = functools.partial(_kernel, act=act, gated=gated,
                               sandwich=sandwich, eps=eps, n_ff=n_ff)
    return pl.pallas_call(
        kernel,
        grid=(n_m, n_ff),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),       # x (shortcut)
            pl.BlockSpec((d,), lambda i, j: (0,)),            # pre-norm
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),       # w_gate slab
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),       # w_up slab
            pl.BlockSpec((bf, d), lambda i, j: (j, 0)),       # w_down slab
            pl.BlockSpec((d,), lambda i, j: (0,)),            # post-norm
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, d), x.dtype),                     # normalized x
            pltpu.VMEM((bm, d), jnp.float32),                 # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, scale, w_gate, w_up, w_down, post_scale)
