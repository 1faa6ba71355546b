"""Mamba2 SSD chunked-scan Pallas TPU kernel.

The SSD decomposition (arXiv:2405.21060) splits the sequence into chunks:
a quadratic intra-chunk term (MXU-friendly (L x N) @ (N x L) and (L x L) @
(L x P) matmuls) plus a linear cross-chunk state recurrence. The recurrence
is inherently sequential, which maps perfectly onto the TPU grid: the
innermost grid axis walks chunks in order while the running (P, N) state
persists in VMEM scratch — the HBM round-trip the CUDA implementation needs
between its parallel chunk pass and its recurrence pass disappears.

grid = (batch, heads, num_chunks); per step the kernel pulls one chunk of
x·dt (L, P), decay logits (L,), and B/C (L, N) into VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(xdt_ref, da_ref, b_ref, c_ref, y_ref, state_out_ref, state_scr, *,
            block_l: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0][:, 0, :].astype(jnp.float32)          # (L, P)
    da = da_ref[0][:, 0].astype(jnp.float32)               # (L,)
    b = b_ref[0].astype(jnp.float32)                       # (L, N)
    c = c_ref[0].astype(jnp.float32)                       # (L, N)
    state = state_scr[...]                                 # (P, N)

    da_cum = jnp.cumsum(da)                                # (L,)
    # intra-chunk: scores[i, j] = (c_i . b_j) * exp(da_cum_i - da_cum_j), j <= i
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L, L)
    seg = da_cum[:, None] - da_cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (block_l, block_l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block_l, block_l), 1)
    decay = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    y = jax.lax.dot(scores * decay, xdt,
                    preferred_element_type=jnp.float32)    # (L, P)

    # cross-chunk: y += exp(da_cum) * (c @ state^T)
    y = y + jnp.exp(da_cum)[:, None] * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (L, P)

    # state update: S <- exp(da_sum) S + sum_l exp(da_sum - da_cum_l) xdt_l b_l^T
    da_sum = da_cum[-1]
    w = jnp.exp(da_sum - da_cum)                           # (L,)
    state_scr[...] = jnp.exp(da_sum) * state + jax.lax.dot_general(
        xdt * w[:, None], b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (P, N)

    y_ref[0] = y[:, None, :].astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _finish():
        state_out_ref[0, 0] = state_scr[...].astype(state_out_ref.dtype)


def ssd_scan_kernel(xdt: jax.Array, da: jax.Array, b: jax.Array, c: jax.Array,
                    *, chunk: int, interpret: bool = True
                    ) -> tuple[jax.Array, jax.Array]:
    """xdt (B,S,H,P) = x*dt; da (B,S,H) = dt*a; b,c (B,S,N).

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    bsz, s, h, p = xdt.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    grid = (bsz, h, nc)
    kernel = functools.partial(_kernel, block_l=chunk)

    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bb, hh, ic: (bb, ic, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bb, hh, ic: (bb, ic, hh)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ic: (bb, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ic: (bb, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bb, hh, ic: (bb, ic, hh, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bb, hh, ic: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, h, p), xdt.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xdt, da, b, c)
    return y, state
