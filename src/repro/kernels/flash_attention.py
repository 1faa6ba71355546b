"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax blockwise attention with explicit VMEM tiling:

* grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the innermost axis is
  the softmax reduction — TPU grids execute sequentially, so the running
  (m, l, acc) state lives in VMEM scratch across kv steps.
* BlockSpecs pull (BQ, hd) of Q and (BK, hd) of K/V into VMEM per step; the
  MXU sees (BQ x hd) @ (hd x BK) and (BQ x BK) @ (BK x hd) matmuls with
  128-aligned tiles by default.
* GQA is expressed in the K/V index_map (q head h reads kv head h // group),
  so no KV broadcast is ever materialized.
* Supports causal masking, sliding windows and gemma-style logit softcap.
  Fully-masked kv blocks are handled by masking the *probabilities* (not
  just the scores), keeping the online-softmax state finite.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], block_q: int, block_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                   # (BQ, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (BK, hd)
    v = v_ref[0, 0].astype(jnp.float32)                   # (BK, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # (BQ, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)                           # masked-out -> 0
    alpha = jnp.exp(m_prev - m_new)                       # (BQ, 1)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         scale: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = True) -> jax.Array:
    """q (B,H,S,hd); k,v (B,K,T,hd) with H % K == 0. Returns (B,H,S,hd)."""
    bsz, h, s, hd = q.shape
    _, kv, t, _ = k.shape
    group = h // kv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    scale = hd ** -0.5 if scale is None else scale

    grid = (bsz, h, s // block_q, t // block_k)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, hh, iq, ik: (b, hh, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, hh, iq, ik, g=group: (b, hh // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, hh, iq, ik, g=group: (b, hh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, hh, iq, ik: (b, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
