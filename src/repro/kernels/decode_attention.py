"""Single-token decode attention Pallas TPU kernel.

Decode is memory-bound: the whole KV cache streams HBM -> VMEM once per step
while compute is O(T·hd) per head. The kernel therefore tiles only the KV
sequence: grid = (batch, q_heads, num_kv_blocks), innermost axis reducing
with the same online-softmax VMEM scratch as the prefill kernel. A validity
mask (B, 1, T) expresses both full-cache (`pos <= t`) and ring-buffer sliding
window occupancy, so one kernel serves all cache layouts. The mask keeps a
unit middle axis so that its block (1, 1, BK) ends in two dims the TPU
accepts at any batch: 1 equals the array's dim and BK is a multiple of 128
or the whole T.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, softcap: Optional[float]):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                   # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (BK, hd)
    v = v_ref[0, 0].astype(jnp.float32)                   # (BK, hd)
    valid = mask_ref[0] != 0                              # (1, BK)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (1,BK)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_bhd(q: jax.Array, k: jax.Array, v: jax.Array,
                         mask: jax.Array, *,
                         softcap: Optional[float] = None,
                         scale: Optional[float] = None,
                         block_k: int = 512,
                         interpret: bool = True) -> jax.Array:
    """q (B,H,1,hd); k,v (B,K,T,hd); mask (B,1,T) bool/int. -> (B,H,1,hd)."""
    bsz, h, _, hd = q.shape
    _, kv, t, _ = k.shape
    group = h // kv
    block_k = min(block_k, t)
    assert t % block_k == 0, (t, block_k)
    scale = hd ** -0.5 if scale is None else scale
    mask = mask.astype(jnp.int8)

    grid = (bsz, h, t // block_k)
    kernel = functools.partial(_kernel, scale=scale, softcap=softcap)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, hh, ik: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, hh, ik, g=group: (b, hh // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, hh, ik, g=group: (b, hh // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, hh, ik: (b, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, hh, ik: (b, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, mask)
