"""Jitted public wrappers over the Pallas kernels.

Model code calls these with model-layout tensors ((B, S, H, hd) etc.); the
wrappers transpose to kernel layout, choose block sizes, and compile the
kernel for the TPU. Only on the CPU backend, where the unit tests run, do the
kernels run in interpret mode; any other backend compiles them, so a run on
an accelerator never falls back to the interpreter unseen.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_bhd
from .flash_attention import flash_attention_bhsd
from .paged_attention import paged_decode_attention_bhd
from .rmsnorm import rmsnorm_rows
from .ssd_scan import ssd_scan_kernel


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pick_block(n: int, target: int, align: int) -> int:
    """Block extent along a dim of size n: n itself when n <= target, else
    the largest divisor of n that is <= target and a multiple of ``align``,
    else n. The TPU accepts a block whose last two dims are each a multiple
    of the tile (8 sublanes, 128 lanes) or the whole array dim; ``align`` is
    the tile of the dim the block lands on."""
    if n <= target:
        return n
    for cand in range(target - target % align, 0, -align):
        if n % cand == 0:
            return cand
    return n


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """q (B,S,H,hd); k,v (B,T,K,hd) -> (B,S,H,hd). Model layout in/out."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = _pick_block(qt.shape[2], 128, 8)
    bk = _pick_block(kt.shape[2], 128, 8)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               softcap=softcap, scale=scale, block_q=bq,
                               block_k=bk, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("softcap", "scale"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     mask: jax.Array, softcap: Optional[float] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """q (B,1,H,hd); k,v (B,T,K,hd); mask (B,1,T) or (B,T) -> (B,1,H,hd)."""
    if mask.ndim == 2:
        mask = mask[:, None, :]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bk = _pick_block(kt.shape[2], 512, 128)
    out = decode_attention_bhd(qt, kt, vt, mask, softcap=softcap, scale=scale,
                               block_k=bk, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("softcap", "scale"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None) -> jax.Array:
    """q (B,1,H,hd); k_pages,v_pages (P,page,K,hd); page_table (B,NP) int32;
    lengths (B,) -> (B,1,H,hd). Pad table entries should point at the pool's
    reserved scratch page; validity comes from ``lengths`` alone."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k_pages.transpose(0, 2, 1, 3)
    vt = v_pages.transpose(0, 2, 1, 3)
    out = paged_decode_attention_bhd(qt, kt, vt, page_table, lengths,
                                     softcap=softcap, scale=scale,
                                     interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int) -> tuple[jax.Array, jax.Array]:
    """Same contract as models.ssm.ssd_reference: x (B,S,H,P), dt (B,S,H),
    a (H,), b/c (B,S,N) -> (y (B,S,H,P), final_state (B,H,P,N))."""
    xdt = x * dt[..., None]
    da = dt * a[None, None, :]
    return ssd_scan_kernel(xdt.astype(jnp.float32), da.astype(jnp.float32),
                           b, c, chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "plus_one"))
def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            plus_one: bool = False) -> jax.Array:
    """x (..., D), w (D,)."""
    shape = x.shape
    rows = 1
    for dim in shape[:-1]:
        rows *= dim
    x2 = x.reshape(rows, shape[-1])
    br = _pick_block(rows, 256, 8)
    out = rmsnorm_rows(x2, w, eps=eps, plus_one=plus_one, block_rows=br,
                       interpret=_interpret())
    return out.reshape(shape)
