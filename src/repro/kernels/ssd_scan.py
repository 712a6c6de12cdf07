"""Mamba-2 SSD chunked scan -- Pallas TPU kernel.

The inter-chunk recurrent state [P, N] lives in VMEM scratch and is carried
across the sequential chunk grid dimension -- the on-chip state residency
that core/residency.py plans for SSM blocks (the paper's SE-side-path
analogue).  Within a chunk the quadratic SSD form runs on the MXU.

Layout: per (batch*head) row; B/C are shared across heads within a group
(g groups), mapped via head -> group index maps.
  x  [BH, S, P]   dt [BH, S]   A [BH, 1]   D [BH, 1]
  Bm [BG, S, N]   Cm [BG, S, N]
Grid (BH, S/Q) with dimension_semantics (parallel, arbitrary).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, o_ref,
            state_ref, *, q: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)                   # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)                 # [Q]
    A = a_ref[0, 0].astype(jnp.float32)                # scalar (negative)
    D = d_ref[0, 0].astype(jnp.float32)
    Bm = b_ref[0].astype(jnp.float32)                  # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                  # [Q, N]

    dA = dt * A                                        # [Q]
    cum = jnp.cumsum(dA)                               # [Q]
    # L[i,j] = exp(cum_i - cum_j) for i >= j
    seg = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)

    xdt = x * dt[:, None]                              # [Q, P]
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general((scores * L), xdt,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    # inter-chunk contribution: y_off = (C * exp(cum)) @ state^T
    state = state_ref[...]                             # [P, N]
    Cdec = Cm * jnp.exp(cum)[:, None]
    y_off = jax.lax.dot_general(Cdec, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = (y_diag + y_off + x * D).astype(o_ref.dtype)

    # state' = state * exp(cum[-1]) + sum_q decay_q * xdt_q (x) B_q
    decay = jnp.exp(cum[-1] - cum)                     # [Q]
    state_ref[...] = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        xdt * decay[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "nheads", "interpret"))
def ssd_scan(x, dt, A, D, Bm, Cm, *, chunk: int = 256, nheads: int,
             interpret: bool = False):
    """x [BH,S,P]; dt [BH,S]; A,D [BH,1]; Bm,Cm [BG,S,N] with
    BG = BH/ (heads per group).  Returns y [BH,S,P] (fp32-accurate)."""
    BH, S, P = x.shape
    BG, _, N = Bm.shape
    hg = BH // BG                     # heads per (batch x group) row
    q = min(chunk, S)
    assert S % q == 0
    n_c = S // q

    kernel = functools.partial(_kernel, q=q)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_c),
        in_specs=[
            pl.BlockSpec((1, q, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, q), lambda h, c: (h, c)),
            pl.BlockSpec((1, 1), lambda h, c: (h, 0)),
            pl.BlockSpec((1, 1), lambda h, c: (h, 0)),
            pl.BlockSpec((1, q, N), lambda h, c: (h // hg, c, 0)),
            pl.BlockSpec((1, q, N), lambda h, c: (h // hg, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, P), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, D, Bm, Cm)
