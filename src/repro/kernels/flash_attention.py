"""Flash attention -- Pallas TPU kernel (streaming-mode attention).

Online-softmax over KV tiles with (m, l, acc) persisted in VMEM scratch
across the sequential kv grid dimension.  Supports causal masking, local
(sliding-window) masking, gemma-2 logit soft-capping and GQA via a
head->kv-head index map.  Fully-masked KV tiles are skipped with pl.when.

Layout: caller flattens to q [BH, S, hd], k/v [BKV, T, hd]; grid
(BH, S/bq, T/bk) with dimension_semantics (parallel, parallel, arbitrary).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, n_k: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    delta = q_pos - k_pos

    # Tile-level skip: the whole tile is masked out iff its minimal delta
    # violates causality or its maximal delta falls outside the window.
    run = jnp.bool_(True)
    if causal:
        run &= (i + 1) * bq - 1 - j * bk >= 0          # max q vs min k
    if window:
        run &= (i * bq - ((j + 1) * bk - 1)) < window  # min q vs max k

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= delta >= 0
        if window:
            mask &= delta < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                              "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False):
    """q [B,S,NH,hd]; k,v [B,T,NKV,hd] -> [B,S,NH,hd]."""
    B, S, NH, hd = q.shape
    _, T, NKV, _ = k.shape
    G = NH // NKV
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0
    n_q, n_k = S // bq, T // bk

    qf = q.transpose(0, 2, 1, 3).reshape(B * NH, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * NKV, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * NKV, T, hd)

    kernel = functools.partial(
        _kernel, scale=hd ** -0.5, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, n_k=n_k)

    def kv_map(h, i, j):
        return (h // G, j, 0)

    out = pl.pallas_call(
        kernel,
        grid=(B * NH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * NH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, NH, S, hd).transpose(0, 2, 1, 3)
