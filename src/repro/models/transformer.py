"""Decoder-only language model assembly for all architecture families.

A model is a sequence of :class:`BlockGroup`\\ s; each group is a run of
structurally identical layers executed as one ``lax.scan`` over stacked
parameters (and stacked caches at decode). Group kinds:

* ``dense``       — attn + SwiGLU MLP (llama3.2 / qwen3 / yi / qwen2-vl)
* ``moe``         — attn + mixture-of-experts MLP (mixtral / qwen3-moe)
* ``gemma_pair``  — [local-SWA layer, global layer] per scan step, sandwich
                    norms + softcaps (gemma2)
* ``mamba2``      — SSD block (mamba2)
* ``hybrid``      — zamba2: one shared-parameter attention block (invoked with
                    per-step LoRA deltas) + ``mamba_per_step`` mamba2 layers
                    per scan step

Scanning keeps the HLO size O(groups), not O(layers) — a 94-layer qwen3-moe
lowered at 512 devices stays tractable — and is what makes remat policies and
per-layer cache threading uniform.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from . import attention as attn
from . import moe as moe_mod
from . import ssm
from .common import (
    DENSE,
    GEMMA_PAIR,
    HYBRID,
    MAMBA2,
    MOE,
    BlockGroup,
    ModelConfig,
    ParamSpec,
    abstract_from_specs,
    axes_from_specs,
    cross_entropy_loss,
    init_from_specs,
    register_param_specs,
    rms_norm,
    softcap,
    swiglu,
)

PS = ParamSpec


# =============================================================== param specs

def _attn_specs(cfg: ModelConfig) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    s = {
        "wq": PS((d, h, hd), ("embed", "heads", "head_dim"), fan_in=d),
        "wk": PS((d, kvh, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wv": PS((d, kvh, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wo": PS((h, hd, d), ("heads", "head_dim", "embed"), fan_in=h * hd),
    }
    if cfg.qk_norm:
        s["q_norm"] = PS((hd,), (None,), init="ones")
        s["k_norm"] = PS((hd,), (None,), init="ones")
    return s


def _mlp_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": PS((d, f), ("embed", "mlp")),
        "w_up": PS((d, f), ("embed", "mlp")),
        "w_down": PS((f, d), ("mlp", "embed")),
    }


def _moe_specs(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    return {
        "router": PS((d, e), ("embed", None), dtype=jnp.float32),
        "w_gate": PS((e, d, f), ("experts", "embed", "mlp"), fan_in=d),
        "w_up": PS((e, d, f), ("experts", "embed", "mlp"), fan_in=d),
        "w_down": PS((e, f, d), ("experts", "mlp", "embed"), fan_in=f),
    }


def _mamba_specs(cfg: ModelConfig) -> dict:
    d, di = cfg.d_model, cfg.ssm_d_inner
    h, n, gn = cfg.ssm_nheads, cfg.ssm_state, cfg.ssm_groups * cfg.ssm_state
    w = cfg.ssm_conv_width
    return {
        "in_z": PS((d, di), ("embed", "ssm_inner")),
        "in_x": PS((d, di), ("embed", "ssm_inner")),
        "in_b": PS((d, gn), ("embed", None)),
        "in_c": PS((d, gn), ("embed", None)),
        "in_dt": PS((d, h), ("embed", "ssm_heads")),
        "conv_x_w": PS((di, w), ("ssm_inner", None)),
        "conv_x_b": PS((di,), ("ssm_inner",), init="zeros"),
        "conv_b_w": PS((gn, w), (None, None)),
        "conv_b_b": PS((gn,), (None,), init="zeros"),
        "conv_c_w": PS((gn, w), (None, None)),
        "conv_c_b": PS((gn,), (None,), init="zeros"),
        "a_log": PS((h,), ("ssm_heads",), init="zeros"),
        "d_skip": PS((h,), ("ssm_heads",), init="ones"),
        "dt_bias": PS((h,), ("ssm_heads",), init="zeros"),
        "norm_w": PS((di,), ("ssm_inner",), init="ones"),
        "out_proj": PS((di, d), ("ssm_inner", "embed")),
    }


def _norm(d: int) -> PS:
    return PS((d,), (None,), init="ones")


def _dense_layer_specs(cfg: ModelConfig, moe: bool) -> dict:
    s = {
        "ln1": _norm(cfg.d_model),
        "ln2": _norm(cfg.d_model),
        "attn": _attn_specs(cfg),
        "mlp": _moe_specs(cfg) if moe else _mlp_specs(cfg),
    }
    if cfg.gemma_norm_plus_one:  # gemma2 sandwich norms
        s["ln1_post"] = _norm(cfg.d_model)
        s["ln2_post"] = _norm(cfg.d_model)
    return s


def _lora_specs(cfg: ModelConfig) -> dict:
    d, h, kvh, hd, r = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd,
                        cfg.shared_attn_lora_rank)
    s = {}
    for name, heads in (("q", h), ("k", kvh), ("v", kvh)):
        s[f"{name}_a"] = PS((d, r), ("embed", None))
        s[f"{name}_b"] = PS((r, heads, hd), (None, "heads", "head_dim"),
                            init="zeros")
    return s


def _group_step_specs(cfg: ModelConfig, g: BlockGroup) -> dict:
    if g.kind == DENSE:
        return _dense_layer_specs(cfg, moe=False)
    if g.kind == MOE:
        return _dense_layer_specs(cfg, moe=True)
    if g.kind == GEMMA_PAIR:
        return {"local": _dense_layer_specs(cfg, moe=False),
                "global": _dense_layer_specs(cfg, moe=False)}
    if g.kind == MAMBA2:
        return {"ln": _norm(cfg.d_model), "mamba": _mamba_specs(cfg)}
    if g.kind == HYBRID:
        step = {
            "mamba_ln": _stack(_norm(cfg.d_model), g.mamba_per_step),
            "mamba": _stack_tree(_mamba_specs(cfg), g.mamba_per_step),
            "attn_ln": _norm(cfg.d_model),
        }
        if cfg.shared_attn_lora_rank:
            step["lora"] = _lora_specs(cfg)
        return step
    raise ValueError(f"unknown group kind {g.kind}")


def _stack(spec: PS, n: int) -> PS:
    return dataclasses.replace(spec, shape=(n, *spec.shape),
                               axes=("layers", *spec.axes))


def _stack_tree(tree, n: int):
    return jax.tree.map(lambda s: _stack(s, n), tree,
                        is_leaf=lambda x: isinstance(x, PS))


def param_specs(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    specs: dict[str, Any] = {
        "embed": PS((v, d), ("vocab", "embed"), fan_in=d),
        "final_norm": _norm(d),
        "groups": [
            _stack_tree(_group_step_specs(cfg, g), g.count)
            for g in cfg.groups
        ],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = PS((d, v), ("embed", "vocab"))
    if any(g.kind == HYBRID for g in cfg.groups):
        specs["shared_attn"] = {
            "attn": _attn_specs(cfg),
            "mlp": _mlp_specs(cfg),
            "ln2": _norm(d),
        }
    return specs


register_param_specs(param_specs)


# ============================================================== layer bodies

def _dense_block(cfg: ModelConfig, g: BlockGroup, p, x, positions, *,
                 window, mrope, is_moe: bool):
    plus1 = cfg.gemma_norm_plus_one
    h = attn.self_attention_prefill(
        cfg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps, plus1),
        positions, window=window, mrope_positions=mrope)
    if "ln1_post" in p:
        h = rms_norm(h, p["ln1_post"], cfg.norm_eps, plus1)
    x = x + h
    x = constrain(x, "batch", "act_seq", "act_embed")
    z = rms_norm(x, p["ln2"], cfg.norm_eps, plus1)
    if is_moe:
        y, aux = moe_mod.moe_block(cfg, p["mlp"], z)
    else:
        y, aux = swiglu(z, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                        p["mlp"]["w_down"]), 0.0
    if "ln2_post" in p:
        y = rms_norm(y, p["ln2_post"], cfg.norm_eps, plus1)
    return x + y, aux


def _fold_lora(p_attn: dict, lora: Optional[dict]) -> dict:
    """Fold per-invocation LoRA deltas into effective qkv weights (zamba2):
    W_eff = W_shared + A @ B. Exact, and lets both prefill and decode reuse
    the standard attention paths."""
    if lora is None:
        return p_attn
    eff = dict(p_attn)
    for name, w in (("q", "wq"), ("k", "wk"), ("v", "wv")):
        delta = jnp.einsum("dr,rhk->dhk", lora[f"{name}_a"],
                           lora[f"{name}_b"]).astype(p_attn[w].dtype)
        eff[w] = p_attn[w] + delta
    return eff


def _shared_attn_block(cfg: ModelConfig, shared, lora, x, xn, positions):
    """zamba2 shared transformer block; x = residual, xn = pre-normed input."""
    p_attn = _fold_lora(shared["attn"], lora)
    h = attn.self_attention_prefill(cfg, p_attn, xn, positions,
                                    window=cfg.sliding_window)
    x = x + h
    z2 = rms_norm(x, shared["ln2"], cfg.norm_eps)
    mlpp = shared["mlp"]
    return x + swiglu(z2, mlpp["w_gate"], mlpp["w_up"], mlpp["w_down"])


# ============================================================ prefill forward

def _group_prefill(cfg: ModelConfig, g: BlockGroup, gp, x, positions, *,
                   mrope, shared):
    """Run one block group via lax.scan over its stacked params."""

    def step(carry, layer_p):
        x, aux = carry
        if g.kind == DENSE:
            x, a = _dense_block(cfg, g, layer_p, x, positions,
                                window=g.window, mrope=mrope, is_moe=False)
        elif g.kind == MOE:
            x, a = _dense_block(cfg, g, layer_p, x, positions,
                                window=g.window, mrope=mrope, is_moe=True)
        elif g.kind == GEMMA_PAIR:
            x, a1 = _dense_block(cfg, g, layer_p["local"], x, positions,
                                 window=cfg.sliding_window, mrope=mrope,
                                 is_moe=False)
            x, a2 = _dense_block(cfg, g, layer_p["global"], x, positions,
                                 window=None, mrope=mrope, is_moe=False)
            a = a1 + a2
        elif g.kind == MAMBA2:
            x = x + ssm.mamba2_prefill(
                cfg, layer_p["mamba"], rms_norm(x, layer_p["ln"], cfg.norm_eps))
            a = 0.0
        elif g.kind == HYBRID:
            xn = rms_norm(x, layer_p["attn_ln"], cfg.norm_eps)
            x = _shared_attn_block(cfg, shared, layer_p.get("lora"), x, xn,
                                   positions)
            for i in range(g.mamba_per_step):
                sub = jax.tree.map(lambda a_: a_[i], layer_p["mamba"])
                ln = layer_p["mamba_ln"][i]
                x = x + ssm.mamba2_prefill(cfg, sub,
                                           rms_norm(x, ln, cfg.norm_eps))
            a = 0.0
        else:
            raise ValueError(g.kind)
        x = constrain(x, "batch", "act_seq", "act_embed")
        return (x, aux + a), None

    carry0 = (x, jnp.float32(0.0))
    if cfg.remat and cfg.remat_policy == "two_level" and \
            g.count % cfg.remat_block == 0 and g.count > cfg.remat_block:
        # nested sqrt-N checkpointing: outer scan over blocks of layers,
        # inner scan over layers within a block; residual footprint drops
        # from O(L) to O(L/G + G) at one extra forward recompute.
        blocks = g.count // cfg.remat_block

        def block_step(carry, block_params):
            return jax.lax.scan(jax.checkpoint(step), carry, block_params)

        gp_blocked = jax.tree.map(
            lambda a: a.reshape(blocks, cfg.remat_block, *a.shape[1:]), gp)
        (x, aux), _ = jax.lax.scan(jax.checkpoint(block_step), carry0,
                                   gp_blocked)
        return x, aux
    if cfg.remat:
        step = jax.checkpoint(step)
    (x, aux), _ = jax.lax.scan(step, carry0, gp)
    return x, aux


def embed_tokens(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.gemma_norm_plus_one:           # gemma scales embeddings
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x.astype(cfg.activation_dtype)


def lm_logits(cfg: ModelConfig, params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.gemma_norm_plus_one)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    # f32 logits are the single biggest training tensor; pin them sharded
    # (act_seq claims 'model' when S divides; decode's S=1 falls back to
    # vocab->model) instead of letting GSPMD replicate.
    return constrain(logits, "batch", "act_seq", "vocab")


def forward(cfg: ModelConfig, params, tokens: jax.Array, *,
            input_embeds: Optional[jax.Array] = None,
            mrope_positions: Optional[jax.Array] = None,
            last_only: bool = False) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits (B,S,V) f32, moe aux loss).

    ``last_only``: project logits for the final position only (serving
    prefill) — avoids materializing the (B,S,V) tensor.
    """
    if input_embeds is not None:
        x = input_embeds.astype(cfg.activation_dtype)
    else:
        x = embed_tokens(cfg, params, tokens)
    x = constrain(x, "batch", "act_seq", "act_embed")
    bsz, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (bsz, s))
    shared = params.get("shared_attn")
    aux_total = jnp.float32(0.0)
    for g, gp in zip(cfg.groups, params["groups"]):
        x, aux = _group_prefill(cfg, g, gp, x, positions,
                                mrope=mrope_positions, shared=shared)
        aux_total = aux_total + aux
    if last_only:
        x = x[:, -1:]
    return lm_logits(cfg, params, x), aux_total


def loss_fn(cfg: ModelConfig, params, batch: dict) -> tuple[jax.Array, dict]:
    logits, aux = forward(
        cfg, params, batch["tokens"],
        input_embeds=batch.get("input_embeds"),
        mrope_positions=batch.get("mrope_positions"))
    ce = cross_entropy_loss(logits, batch["targets"], batch.get("mask"))
    total = ce + cfg.router_aux_coef * aux
    return total, {"ce": ce, "aux": aux}


# =================================================================== caching

def _kv_shapes(cfg: ModelConfig, batch: int, max_len: int, window, dtype):
    length = min(window, max_len) if window is not None else max_len
    return ((batch, length, cfg.num_kv_heads, cfg.hd), dtype)


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int, dtype) -> list:
    """Per-group cache shape trees, mirroring params['groups'] structure."""
    out = []
    for g in cfg.groups:
        if g.kind in (DENSE, MOE):
            sh, dt = _kv_shapes(cfg, batch, max_len, g.window, dtype)
            entry = {"k": ((g.count, *sh), dt), "v": ((g.count, *sh), dt)}
        elif g.kind == GEMMA_PAIR:
            lsh, _ = _kv_shapes(cfg, batch, max_len, cfg.sliding_window, dtype)
            gsh, _ = _kv_shapes(cfg, batch, max_len, None, dtype)
            entry = {
                "local": {"k": ((g.count, *lsh), dtype),
                          "v": ((g.count, *lsh), dtype)},
                "global": {"k": ((g.count, *gsh), dtype),
                           "v": ((g.count, *gsh), dtype)},
            }
        elif g.kind == MAMBA2:
            st = ssm.mamba2_state_shapes(cfg, batch, dtype)
            entry = {k: ((g.count, *sh), dt) for k, (sh, dt) in st.items()}
        elif g.kind == HYBRID:
            st = ssm.mamba2_state_shapes(cfg, batch, dtype)
            sh, dt = _kv_shapes(cfg, batch, max_len, cfg.sliding_window, dtype)
            entry = {
                "mamba": {k: ((g.count, g.mamba_per_step, *s_), d_)
                          for k, (s_, d_) in st.items()},
                "attn": {"k": ((g.count, *sh), dt), "v": ((g.count, *sh), dt)},
            }
        else:
            raise ValueError(g.kind)
        out.append(entry)
    return out


def _map_shapes(tree, fn):
    return jax.tree.map(lambda leaf: fn(*leaf), tree,
                        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                        and isinstance(x[0], tuple))


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or cfg.activation_dtype
    return _map_shapes(cache_shapes(cfg, batch, max_len, dtype), jnp.zeros)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or cfg.activation_dtype
    return _map_shapes(cache_shapes(cfg, batch, max_len, dtype),
                       jax.ShapeDtypeStruct)


def cache_logical_axes(cfg: ModelConfig, batch: int, max_len: int):
    """Logical axes tree matching the cache structure."""
    kv_axes = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    ssm_axes = {
        "ssm": ("layers", "batch", "ssm_heads", None, None),
        "conv_x": ("layers", "batch", None, "ssm_inner"),
        "conv_b": ("layers", "batch", None, None),
        "conv_c": ("layers", "batch", None, None),
    }
    out = []
    for g in cfg.groups:
        if g.kind in (DENSE, MOE):
            entry = {"k": kv_axes, "v": kv_axes}
        elif g.kind == GEMMA_PAIR:
            entry = {"local": {"k": kv_axes, "v": kv_axes},
                     "global": {"k": kv_axes, "v": kv_axes}}
        elif g.kind == MAMBA2:
            entry = dict(ssm_axes)
        elif g.kind == HYBRID:
            entry = {
                "mamba": {k: (v[0], None, *v[1:]) for k, v in ssm_axes.items()},
                "attn": {"k": kv_axes, "v": kv_axes},
            }
        out.append(entry)
    return out


# ===================================================== prefill-with-cache

def _dense_block_cached(cfg: ModelConfig, p, x, positions, fresh_cache, *,
                        window, mrope):
    """Prefill step that also fills the decode cache for this layer."""
    plus1 = cfg.gemma_norm_plus_one
    h, (k, v) = attn.self_attention_prefill(
        cfg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps, plus1),
        positions, window=window, mrope_positions=mrope, return_kv=True)
    new_cache = attn.fill_kv_cache(fresh_cache, k, v, window)
    if "ln1_post" in p:
        h = rms_norm(h, p["ln1_post"], cfg.norm_eps, plus1)
    x = x + h
    z = rms_norm(x, p["ln2"], cfg.norm_eps, plus1)
    if "router" in p["mlp"]:
        y, _ = moe_mod.moe_block(cfg, p["mlp"], z)
    else:
        y = swiglu(z, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    if "ln2_post" in p:
        y = rms_norm(y, p["ln2_post"], cfg.norm_eps, plus1)
    return x + y, new_cache


def _group_prefill_cached(cfg: ModelConfig, g: BlockGroup, gp, gcache, x,
                          positions, *, mrope, shared):
    """Prefill one group while producing its decode cache (scan ys)."""

    def step(x, layer):
        layer_p, fresh = layer
        if g.kind in (DENSE, MOE):
            x, nc = _dense_block_cached(cfg, layer_p, x, positions, fresh,
                                        window=g.window, mrope=mrope)
        elif g.kind == GEMMA_PAIR:
            x, nc_l = _dense_block_cached(cfg, layer_p["local"], x, positions,
                                          fresh["local"],
                                          window=cfg.sliding_window, mrope=mrope)
            x, nc_g = _dense_block_cached(cfg, layer_p["global"], x, positions,
                                          fresh["global"], window=None,
                                          mrope=mrope)
            nc = {"local": nc_l, "global": nc_g}
        elif g.kind == MAMBA2:
            y, st = ssm.mamba2_prefill(
                cfg, layer_p["mamba"], rms_norm(x, layer_p["ln"], cfg.norm_eps),
                return_state=True)
            x = x + y
            nc = jax.tree.map(lambda f, s: s.astype(f.dtype), fresh, st)
        elif g.kind == HYBRID:
            xn = rms_norm(x, layer_p["attn_ln"], cfg.norm_eps)
            p_attn = _fold_lora(shared["attn"], layer_p.get("lora"))
            h, (k, v) = attn.self_attention_prefill(
                cfg, p_attn, xn, positions, window=cfg.sliding_window,
                return_kv=True)
            nc_attn = attn.fill_kv_cache(fresh["attn"], k, v,
                                         cfg.sliding_window)
            x = x + h
            z2 = rms_norm(x, shared["ln2"], cfg.norm_eps)
            mlpp = shared["mlp"]
            x = x + swiglu(z2, mlpp["w_gate"], mlpp["w_up"], mlpp["w_down"])
            new_m = []
            for i in range(g.mamba_per_step):
                sub = jax.tree.map(lambda a_: a_[i], layer_p["mamba"])
                ln = layer_p["mamba_ln"][i]
                fresh_i = jax.tree.map(lambda a_: a_[i], fresh["mamba"])
                y, st = ssm.mamba2_prefill(cfg, sub,
                                           rms_norm(x, ln, cfg.norm_eps),
                                           return_state=True)
                x = x + y
                new_m.append(jax.tree.map(lambda f, s: s.astype(f.dtype),
                                          fresh_i, st))
            nc = {"mamba": jax.tree.map(lambda *a_: jnp.stack(a_), *new_m),
                  "attn": nc_attn}
        else:
            raise ValueError(g.kind)
        x = constrain(x, "batch", "act_seq", "act_embed")
        return x, nc

    x, new_cache = jax.lax.scan(step, x, (gp, gcache))
    return x, new_cache


def prefill(cfg: ModelConfig, params, tokens: jax.Array, max_len: int, *,
            input_embeds: Optional[jax.Array] = None,
            mrope_positions: Optional[jax.Array] = None,
            cache=None, cache_dtype=None):
    """Full-sequence forward that also builds a decode-ready cache.

    Returns (logits (B,S,V) f32, cache at position S).
    """
    if input_embeds is not None:
        x = input_embeds.astype(cfg.activation_dtype)
        bsz, s = x.shape[:2]
    else:
        bsz, s = tokens.shape
        x = embed_tokens(cfg, params, tokens)
    if cache is None:
        cache = init_cache(cfg, bsz, max_len, cache_dtype)
    x = constrain(x, "batch", "act_seq", "act_embed")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (bsz, s))
    shared = params.get("shared_attn")
    new_caches = []
    for g, gp, gc in zip(cfg.groups, params["groups"], cache):
        x, nc = _group_prefill_cached(cfg, g, gp, gc, x, positions,
                                      mrope=mrope_positions, shared=shared)
        new_caches.append(nc)
    return lm_logits(cfg, params, x), new_caches


# ============================================================ decode forward

def _dense_block_decode(cfg: ModelConfig, p, x, cache, t, *, window, mrope):
    plus1 = cfg.gemma_norm_plus_one
    h, new_cache = attn.self_attention_decode(
        cfg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps, plus1), cache, t,
        window=window, mrope_positions=mrope)
    if "ln1_post" in p:
        h = rms_norm(h, p["ln1_post"], cfg.norm_eps, plus1)
    x = x + h
    z = rms_norm(x, p["ln2"], cfg.norm_eps, plus1)
    if "router" in p["mlp"]:
        y, _ = moe_mod.moe_block(cfg, p["mlp"], z)
    else:
        y = swiglu(z, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    if "ln2_post" in p:
        y = rms_norm(y, p["ln2_post"], cfg.norm_eps, plus1)
    return x + y, new_cache


def _group_decode(cfg: ModelConfig, g: BlockGroup, gp, gcache, x, t, *,
                  mrope, shared):
    def step(x, layer):
        layer_p, layer_c = layer
        if g.kind in (DENSE, MOE):
            x, nc = _dense_block_decode(cfg, layer_p, x, layer_c, t,
                                        window=g.window, mrope=mrope)
        elif g.kind == GEMMA_PAIR:
            x, nc_l = _dense_block_decode(cfg, layer_p["local"], x,
                                          layer_c["local"], t,
                                          window=cfg.sliding_window, mrope=mrope)
            x, nc_g = _dense_block_decode(cfg, layer_p["global"], x,
                                          layer_c["global"], t,
                                          window=None, mrope=mrope)
            nc = {"local": nc_l, "global": nc_g}
        elif g.kind == MAMBA2:
            y, nc = ssm.mamba2_decode(
                cfg, layer_p["mamba"],
                rms_norm(x, layer_p["ln"], cfg.norm_eps), layer_c)
            x = x + y
        elif g.kind == HYBRID:
            xa = rms_norm(x, layer_p["attn_ln"], cfg.norm_eps)
            x, nc_attn = _shared_attn_decode(cfg, shared, layer_p.get("lora"),
                                             x, xa, layer_c["attn"], t)
            new_m = []
            for i in range(g.mamba_per_step):
                sub_p = jax.tree.map(lambda a_: a_[i], layer_p["mamba"])
                sub_c = jax.tree.map(lambda a_: a_[i], layer_c["mamba"])
                ln = layer_p["mamba_ln"][i]
                y, nm = ssm.mamba2_decode(cfg, sub_p,
                                          rms_norm(x, ln, cfg.norm_eps), sub_c)
                x = x + y
                new_m.append(nm)
            nc = {"mamba": jax.tree.map(lambda *a_: jnp.stack(a_), *new_m),
                  "attn": nc_attn}
        else:
            raise ValueError(g.kind)
        return x, nc

    x, new_cache = jax.lax.scan(step, x, (gp, gcache))
    return x, new_cache


def _dense_block_verify(cfg: ModelConfig, p, x, cache, t):
    """K-position teacher-forced continuation of one dense/moe block: same
    math as K sequential :func:`_dense_block_decode` calls, one weight
    pass (the speculative-verify hot path). Full caches only."""
    plus1 = cfg.gemma_norm_plus_one
    h, new_cache = attn.self_attention_verify(
        cfg, p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps, plus1), cache, t)
    if "ln1_post" in p:
        h = rms_norm(h, p["ln1_post"], cfg.norm_eps, plus1)
    x = x + h
    z = rms_norm(x, p["ln2"], cfg.norm_eps, plus1)
    if "router" in p["mlp"]:
        y, _ = moe_mod.moe_block(cfg, p["mlp"], z)
    else:
        y = swiglu(z, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    if "ln2_post" in p:
        y = rms_norm(y, p["ln2_post"], cfg.norm_eps, plus1)
    return x + y, new_cache


def _group_verify(cfg: ModelConfig, g: BlockGroup, gp, gcache, x, t):
    """Verify-sweep one group: x (B,K,D) known tokens at positions
    t..t+K-1. Only full-cache attention groups qualify (dense/moe,
    no window) — exactly the gate serving places on paged/speculative
    executors via ``StageExecutor.full_cache``."""
    if g.kind not in (DENSE, MOE) or g.window is not None:
        raise ValueError(
            f"verify sweep needs full-cache attention, got {g.kind}")

    def step(x, layer):
        layer_p, layer_c = layer
        x, nc = _dense_block_verify(cfg, layer_p, x, layer_c, t)
        return x, nc

    x, new_cache = jax.lax.scan(step, x, (gp, gcache))
    return x, new_cache


def _shared_attn_decode(cfg: ModelConfig, shared, lora, x, xn, cache, t):
    p_attn = _fold_lora(shared["attn"], lora)
    h, new_cache = attn.self_attention_decode(
        cfg, p_attn, xn, cache, t, window=cfg.sliding_window)
    x = x + h
    z2 = rms_norm(x, shared["ln2"], cfg.norm_eps)
    mlpp = shared["mlp"]
    return x + swiglu(z2, mlpp["w_gate"], mlpp["w_up"], mlpp["w_down"]), \
        new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens: jax.Array,
                t: jax.Array, *,
                mrope_positions: Optional[jax.Array] = None
                ) -> tuple[jax.Array, Any]:
    """One decode step. tokens (B, 1) int32; t scalar int32 position.

    Returns (logits (B, V) f32, new cache).
    """
    x = embed_tokens(cfg, params, tokens)
    shared = params.get("shared_attn")
    new_caches = []
    for g, gp, gc in zip(cfg.groups, params["groups"], cache):
        x, nc = _group_decode(cfg, g, gp, gc, x, t,
                              mrope=mrope_positions, shared=shared)
        new_caches.append(nc)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, new_caches


# ================================================================ public API

class LanguageModel:
    """Uniform handle over all decoder-only families."""

    def __init__(self, cfg: ModelConfig) -> None:
        self.cfg = cfg

    # params
    def param_specs(self):
        return param_specs(self.cfg)

    def init(self, key: jax.Array):
        return init_from_specs(self.param_specs(), key, self.cfg)

    def abstract_params(self):
        return abstract_from_specs(self.param_specs(), self.cfg)

    def logical_axes(self):
        return axes_from_specs(self.param_specs())

    # compute
    def forward(self, params, tokens, **kw):
        return forward(self.cfg, params, tokens, **kw)

    def prefill(self, params, tokens, max_len, **kw):
        return prefill(self.cfg, params, tokens, max_len, **kw)

    def loss(self, params, batch):
        return loss_fn(self.cfg, params, batch)

    def decode_step(self, params, cache, tokens, t, **kw):
        return decode_step(self.cfg, params, cache, tokens, t, **kw)

    # cache
    def init_cache(self, batch, max_len, dtype=None):
        return init_cache(self.cfg, batch, max_len, dtype)

    def abstract_cache(self, batch, max_len, dtype=None):
        return abstract_cache(self.cfg, batch, max_len, dtype)

    def cache_logical_axes(self, batch, max_len):
        return cache_logical_axes(self.cfg, batch, max_len)
