"""Paged single-token decode attention Pallas TPU kernel.

Same memory-bound regime as ``decode_attention.py`` but the KV cache lives in
a shared page pool instead of one contiguous (B, T, ...) buffer: each session
owns a page table of physical page indices and the kernel gathers K/V blocks
through it. The page table and per-session lengths ride in as scalar-prefetch
operands so the k/v BlockSpec index maps can compute the HBM -> VMEM DMA
source *before* the kernel body runs — the gather costs nothing extra over
the contiguous kernel's sequential streaming.

Grid = (batch, q_heads, pages); innermost axis reduces with the same
online-softmax VMEM scratch discipline as ``decode_attention._kernel``.
Validity is derived in-kernel from ``lengths`` (pos < length), which masks
both the partially-filled last page and any pad table entries (pad slots
point at physical page 0, the pool's reserved scratch page).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            scale: float, softcap: Optional[float], page_size: int):
    del pt_ref  # consumed by the BlockSpec index maps
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                   # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (page, hd)
    v = v_ref[0, 0].astype(jnp.float32)                   # (page, hd)

    # Validity from the session length: covers the partial last page and any
    # pad entries in the page table (those gather scratch-page garbage, which
    # is neutralized here before it can touch the softmax).
    pos = ik * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                     # (1, page)
    valid = pos < len_ref[b]                              # (1, page)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
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


def paged_decode_attention_bhd(q: jax.Array,
                               k_pages: jax.Array, v_pages: jax.Array,
                               page_table: jax.Array, lengths: jax.Array, *,
                               softcap: Optional[float] = None,
                               scale: Optional[float] = None,
                               interpret: bool = True) -> jax.Array:
    """q (B,H,1,hd); k_pages,v_pages (P,K,page,hd); page_table (B,NP) int32;
    lengths (B,) int32. -> (B,H,1,hd)."""
    bsz, h, _, hd = q.shape
    _, kv, page_size, _ = k_pages.shape
    n_pages = page_table.shape[1]
    group = h // kv
    scale = hd ** -0.5 if scale is None else scale
    page_table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    grid = (bsz, h, n_pages)
    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               page_size=page_size)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd),
                         lambda b, hh, ik, pt, ln: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, hh, ik, pt, ln, g=group:
                         (pt[b, ik], hh // g, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, hh, ik, pt, ln, g=group:
                         (pt[b, ik], hh // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda b, hh, ik, pt, ln: (b, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bsz, h, 1, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(page_table, lengths, q, k_pages, v_pages)
