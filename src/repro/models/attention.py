"""Attention: GQA with RoPE / M-RoPE, sliding window, softcap, qk-norm.

Two data paths:
* ``prefill`` — full-sequence causal (or bidirectional for encoders),
* ``decode`` — one new token against a KV cache. Sliding-window layers use a
  ring-buffer cache of size ``window`` (slot for position p is ``p % window``),
  which is what makes ``long_500k`` decode tractable for SWA architectures.

``cfg.attn_impl`` selects the reference jnp path or the Pallas flash kernels
(kernels/flash_attention.py, kernels/decode_attention.py). The reference path
is the oracle and the dry-run path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import ModelConfig, apply_mrope, apply_rope, rms_norm, softcap

NEG_INF = -2.3819763e38  # ~ -max bf16


# ------------------------------------------------------------------ projections

def qkv_project(cfg: ModelConfig, p, x: jax.Array, positions: Optional[jax.Array],
                mrope_positions: Optional[jax.Array] = None,
                use_rope: bool = True):
    """x: (B,S,D) -> q (B,S,H,hd), k/v (B,S,K,hd), roped + normed."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        if mrope_positions is not None:
            assert cfg.mrope_sections is not None
            q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        elif positions is not None:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def output_project(p, ctx: jax.Array) -> jax.Array:
    """ctx: (B,S,H,hd) -> (B,S,D)."""
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])


# ------------------------------------------------------------------- reference

def _grouped(q: jax.Array, num_kv: int) -> jax.Array:
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, d)


def attend_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     mask: jax.Array, cap: Optional[float],
                     scale: float) -> jax.Array:
    """q (B,S,H,hd), k/v (B,T,K,hd), mask (B?,S,T) or (S,T) bool -> (B,S,H,hd)."""
    num_kv = k.shape[2]
    qg = _grouped(q, num_kv)                                   # (B,S,K,G,hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    scores = softcap(scores, cap)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ctx = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    b, s, kk, g, d = ctx.shape
    return ctx.reshape(b, s, kk * g, d)


def attend_flash_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, window: Optional[int],
                     cap: Optional[float], scale: float,
                     q_offset=0, block_q: int = 256,
                     block_k: int = 1024) -> jax.Array:
    """Blockwise online-softmax attention in pure jnp ("flash in JAX").

    Never materializes (S, T) scores — the lowered graph's transient is one
    (BQ, BK) tile per head — which is what makes 32k/500k shapes *lowerable*
    for the dry-run (the Pallas kernel is the on-TPU twin of this math; this
    path is what GSPMD partitions). q (B,Sq,H,hd); k,v (B,T,K,hd);
    ``q_offset`` is the global position of q[0] (sequence-parallel callers
    pass their shard offset).
    """
    bsz, sq, h, hd = q.shape
    t = k.shape[1]
    kv = k.shape[2]
    g = h // kv
    bq = min(block_q, sq)
    while sq % bq:
        bq -= 1
    bk = min(block_k, t)
    while t % bk:
        bk -= 1
    nq, nk = sq // bq, t // bk

    qb = q.reshape(bsz, nq, bq, kv, g, hd).astype(jnp.float32)
    kb = k.reshape(bsz, nk, bk, kv, hd).astype(jnp.float32)
    vb = v.reshape(bsz, nk, bk, kv, hd).astype(jnp.float32)

    def q_step(_, q_in):
        iq, qblk = q_in                                   # (B,BQ,K,G,hd)
        qpos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(carry, kv_in):
            m, l, acc = carry
            ik, kblk, vblk = kv_in
            s = jnp.einsum("bqkgd,bckd->bkgqc", qblk, kblk) * scale
            s = softcap(s, cap)
            kpos = ik * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(mask[None, None, None], jnp.exp(s - m_new[..., None]),
                          0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + \
                jnp.einsum("bkgqc,bckd->bkgqd", p, vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((bsz, kv, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bsz, kv, g, bq), jnp.float32)
        a0 = jnp.zeros((bsz, kv, g, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), kb.transpose(1, 0, 2, 3, 4),
             vb.transpose(1, 0, 2, 3, 4)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,K,G,BQ,hd)
        return None, out.transpose(0, 3, 1, 2, 4)          # (B,BQ,K,G,hd)

    # checkpoint per q-chunk: backward recomputes the row's online softmax
    # instead of storing every (BQ, BK) tile — the flash-bwd trade.
    _, blocks = jax.lax.scan(jax.checkpoint(q_step), None,
                             (jnp.arange(nq), qb.transpose(1, 0, 2, 3, 4, 5)))
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(bsz, sq, h, hd)
    return out.astype(q.dtype)


def _flash_sharded(q, k, v, *, causal, window, cap, scale):
    """shard_map wrapper: batch over the 'batch' rule axes, q-sequence over
    'act_seq' axes; K/V gathered full per device. Balances prefill compute
    across ``model`` even when head counts don't divide the axis."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (
        _CTX,
        _axis_size,
        _resolve,
    )

    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or rules is None:
        return attend_flash_jnp(q, k, v, causal=causal, window=window,
                                cap=cap, scale=scale)
    spec = _resolve(rules, mesh, ("batch", "act_seq", None, None),
                    tuple(q.shape))
    bspec, sspec = spec[0], spec[1]
    if sspec is None:
        seq_axes: tuple[str, ...] = ()
    else:
        seq_axes = (sspec,) if isinstance(sspec, str) else tuple(sspec)
    s_loc = q.shape[1] // max(_axis_size(mesh, seq_axes), 1)

    def body(ql, kl, vl):
        if seq_axes:
            idx = jax.lax.axis_index(seq_axes[0])
            for ax in seq_axes[1:]:
                idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
            offset = idx * s_loc
        else:
            offset = 0
        return attend_flash_jnp(ql, kl, vl, causal=causal, window=window,
                                cap=cap, scale=scale, q_offset=offset)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, sspec, None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, sspec, None, None),
        check_vma=False)
    return fn(q, k, v)


def causal_mask(s: int, t: int, window: Optional[int],
                offset: int = 0) -> jax.Array:
    """(s, t) bool mask. Query i attends key j iff j <= i+offset and, with a
    window, j > i+offset-window."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


# --------------------------------------------------------------------- prefill

def self_attention_prefill(cfg: ModelConfig, p, x: jax.Array,
                           positions: jax.Array, *,
                           window: Optional[int] = None,
                           causal: bool = True,
                           mrope_positions: Optional[jax.Array] = None,
                           use_rope: bool = True,
                           return_kv: bool = False):
    q, k, v = qkv_project(cfg, p, x, positions, mrope_positions, use_rope)
    scale = cfg.hd ** -0.5
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "chunked" if x.shape[1] >= 2048 else "reference"
    if impl == "pallas":
        from repro.kernels import ops as kops
        ctx = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=cfg.attn_softcap, scale=scale)
    elif impl == "chunked":
        ctx = _flash_sharded(q, k, v, causal=causal, window=window,
                             cap=cfg.attn_softcap, scale=scale)
    else:
        s = x.shape[1]
        if causal:
            mask = causal_mask(s, s, window)
        else:
            mask = jnp.ones((s, s), dtype=bool)
        ctx = attend_reference(q, k, v, mask=mask, cap=cfg.attn_softcap,
                               scale=scale)
    out = output_project(p, ctx)
    if return_kv:
        return out, (k, v)
    return out


def fill_kv_cache(cache: dict, k: jax.Array, v: jax.Array,
                  window: Optional[int]) -> dict:
    """Write prefill K/V (B,S,K,hd) into a fresh decode cache.

    Full caches store positions [0, S); ring caches (length == window) store
    position p at slot p % window — matching self_attention_decode's layout.
    """
    s = k.shape[1]
    length = cache["k"].shape[1]
    if window is not None and length == window and s >= window:
        tail = jnp.arange(s - window, s)
        slots = tail % window
        new_k = cache["k"].at[:, slots].set(k[:, tail].astype(cache["k"].dtype))
        new_v = cache["v"].at[:, slots].set(v[:, tail].astype(cache["v"].dtype))
    else:
        new_k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
        new_v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
    return {"k": new_k, "v": new_v}


def self_attention_verify(cfg: ModelConfig, p, x: jax.Array, cache: dict,
                          t: jax.Array, *,
                          use_rope: bool = True) -> tuple[jax.Array, dict]:
    """K-token cache continuation: the speculative-verify hot path.

    x (B,K,D) holds K known tokens for positions ``t .. t+K-1`` (the
    session's current token plus its draft proposals). Their K/V land in
    the cache with one slice update and all K queries attend the whole
    cache under a per-row causal offset mask — one fused matmul sweep
    with the same math as K sequential :func:`self_attention_decode`
    calls, which would cost K full passes over the weights. Full
    (non-ring, unwindowed) caches only: verification rollback relies on
    slot j never being read by positions < j, which ring buffers break.
    """
    bsz, kk = x.shape[:2]
    positions = jnp.broadcast_to(
        t + jnp.arange(kk, dtype=jnp.int32)[None, :], (bsz, kk))
    q, k_new, v_new = qkv_project(cfg, p, x, positions, None, use_rope)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, t, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, t, axis=1)
    from repro.distributed import constrain as _c
    k = _c(k, "batch", "cache_seq", "kv_heads", "head_dim")
    v = _c(v, "batch", "cache_seq", "kv_heads", "head_dim")
    new_cache = {"k": k, "v": v}

    length = k.shape[1]
    slots = jnp.arange(length, dtype=jnp.int32)
    # query row i sits at position t+i: attend slots <= t+i
    valid = slots[None, :] <= (t + jnp.arange(kk, dtype=jnp.int32))[:, None]
    mask = jnp.broadcast_to(valid[None], (bsz, kk, length))
    ctx = attend_reference(q, k, v, mask=mask, cap=cfg.attn_softcap,
                           scale=cfg.hd ** -0.5)
    return output_project(p, ctx), new_cache


def cross_attention(cfg: ModelConfig, p, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Decoder cross-attn; enc_k/enc_v are pre-projected encoder states."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    t = enc_k.shape[1]
    mask = jnp.ones((x.shape[1], t), dtype=bool)
    ctx = attend_reference(q, enc_k, enc_v, mask=mask, cap=None,
                           scale=cfg.hd ** -0.5)
    return output_project(p, ctx)


# ---------------------------------------------------------------------- decode

def init_kv_cache(batch: int, length: int, num_kv: int, hd: int, dtype
                  ) -> dict:
    return {
        "k": jnp.zeros((batch, length, num_kv, hd), dtype),
        "v": jnp.zeros((batch, length, num_kv, hd), dtype),
    }


def abstract_kv_cache(batch: int, length: int, num_kv: int, hd: int, dtype
                      ) -> dict:
    return {
        "k": jax.ShapeDtypeStruct((batch, length, num_kv, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, length, num_kv, hd), dtype),
    }


def self_attention_decode(cfg: ModelConfig, p, x: jax.Array, cache: dict,
                          t: jax.Array, *, window: Optional[int] = None,
                          mrope_positions: Optional[jax.Array] = None,
                          use_rope: bool = True) -> tuple[jax.Array, dict]:
    """One-token decode. x: (B,1,D); t: scalar int32 current position.

    Full-attention layers use a length-``max_len`` cache indexed by t;
    sliding-window layers use a ring buffer of size ``window`` — slot
    ``t % window`` — so cache memory is O(window), not O(context).
    """
    positions = jnp.full((x.shape[0], 1), t, dtype=jnp.int32)
    q, k_new, v_new = qkv_project(cfg, p, x, positions, mrope_positions,
                                  use_rope)
    # §Perf (confirmed): when kv_heads doesn't divide the model axis the
    # cache stores head_dim-sharded; q must contract over the SAME sharded
    # head_dim or GSPMD all-gathers the whole cache per layer (measured:
    # ~37 GB/device/step on qwen3-8b decode_32k). Mirror the cache's
    # resolved layout onto q.
    from repro.distributed import logical_spec
    cache_spec = logical_spec(
        ("batch", "cache_seq", "kv_heads", "head_dim"),
        tuple(cache["k"].shape))
    if cache_spec and len(cache_spec) == 4 and cache_spec[3] is not None:
        from repro.distributed import constrain as _c0
        q = _c0(q, "batch", None, None, "head_dim")
        k_new = _c0(k_new, "batch", None, None, "head_dim")
        v_new = _c0(v_new, "batch", None, None, "head_dim")

    ring = window is not None and cache["k"].shape[1] == window
    slot = (jnp.mod(t, jnp.int32(window)) if ring else t).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
    # pin updated cache to its storage layout — without this GSPMD has been
    # observed to replicate-and-repartition the whole cache per layer
    # ("involuntary full rematerialization")
    from repro.distributed import constrain as _c
    k = _c(k, "batch", "cache_seq", "kv_heads", "head_dim")
    v = _c(v, "batch", "cache_seq", "kv_heads", "head_dim")
    new_cache = {"k": k, "v": v}

    length = k.shape[1]
    slots = jnp.arange(length, dtype=jnp.int32)
    if ring:
        # slot s holds global position t - ((t - s) mod W); valid iff >= 0
        w = jnp.int32(window)
        slot_pos = t - jnp.mod(t - slots, w)
        valid = slot_pos >= 0
    else:
        valid = slots <= t
        if window is not None:  # windowed mask over a full cache
            valid &= slots > t - jnp.int32(window)
    mask = valid[None, None, :]                                  # (1,1,T)
    mask = jnp.broadcast_to(mask, (x.shape[0], 1, length))

    scale = cfg.hd ** -0.5
    if cfg.attn_impl == "pallas":
        from repro.kernels import ops as kops
        ctx = kops.decode_attention(q, k, v, mask=mask, softcap=cfg.attn_softcap,
                                    scale=scale)
    else:
        ctx = attend_reference(q, k, v, mask=mask, cap=cfg.attn_softcap,
                               scale=scale)
    return output_project(p, ctx), new_cache
