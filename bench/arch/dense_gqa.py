"""Dense GQA decoder (Llama / Yi / Qwen3): the layer's leaves, the
program's parameter layout and ``ModelConfig``, the plain reference's layer
equations, and the operations and bytes the algorithm needs.

Layer equations (pre-norm, as published for Llama and Qwen3):

    h = RMSNorm(x) ;  q, k, v = h Wq, h Wk, h Wv
    q, k = RMSNorm_hd(q), RMSNorm_hd(k)            (Qwen3 qk-norm only)
    q, k = RoPE(q), RoPE(k)                        (rotate-half, base theta)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo   (GQA: kv heads shared)
    x = x + (silu(h2 Wg) * (h2 Wu)) Wd,  h2 = RMSNorm(x)
    logits = RMSNorm(x) W_head

Counts are from shapes and token counts alone, so they count the same work
whatever implements it: a paged cache, a fused kernel or a donated buffer
does not change them. A multiply-add counts as two operations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from lib.costs import stage_layers
from lib.reference import _mm, _rms, _rope
from lib.weights import NORM


def sizes(cfg: dict) -> dict:
    """The widths of a dense GQA decoder, from a configuration file."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "f": int(cfg["intermediate_size"]), "v": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "qk_norm": bool(cfg["program"]["qk_norm"]),
            "tied": bool(cfg["tie_word_embeddings"])}


def layer_leaves(s: dict, layer: int) -> list[tuple[str, tuple, object]]:
    """(name, shape, fan-in or NORM) of one layer's leaves; every layer
    alike."""
    d, h, kv, hd, f = s["d"], s["h"], s["kv"], s["hd"], s["f"]
    out = [("ln1", (d,), NORM), ("ln2", (d,), NORM),
           ("wq", (d, h, hd), d), ("wk", (d, kv, hd), d),
           ("wv", (d, kv, hd), d), ("wo", (h, hd, d), h * hd),
           ("w_gate", (d, f), d), ("w_up", (d, f), d), ("w_down", (f, d), f)]
    if s["qk_norm"]:
        out += [("q_norm", (hd,), NORM), ("k_norm", (hd,), NORM)]
    return out


def global_leaves(s: dict) -> list[tuple[str, tuple, object]]:
    out = [("embed", (s["v"], s["d"]), s["d"]),
           ("final_norm", (s["d"],), NORM)]
    if not s["tied"]:
        out.append(("lm_head", (s["d"], s["v"]), s["d"]))
    return out


def pack(s: dict, layers: list[dict], g: dict) -> dict:
    """The program's parameter layout: one stacked group of dense layers."""

    def stack(*names):
        return {n: jnp.stack([lay[n] for lay in layers]) for n in names}

    attn = stack("wq", "wk", "wv", "wo",
                 *(("q_norm", "k_norm") if s["qk_norm"] else ()))
    out = {"embed": g["embed"], "final_norm": g["final_norm"],
           "groups": [{**stack("ln1", "ln2"), "attn": attn,
                       "mlp": stack("w_gate", "w_up", "w_down")}]}
    if not s["tied"]:
        out["lm_head"] = g["lm_head"]
    return out


def program_config(cfg: dict, s: dict):
    """The program's ``ModelConfig``."""
    from repro.models import DENSE, BlockGroup, ModelConfig
    prog = cfg["program"]
    if cfg["torch_dtype"] != "bfloat16":
        raise ValueError(f"unsupported dtype {cfg['torch_dtype']}")
    return ModelConfig(
        arch_id=prog["arch"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["h"], num_kv_heads=s["kv"],
        head_dim=s["hd"], d_ff=s["f"], vocab_size=s["v"],
        groups=(BlockGroup(DENSE, s["layers"]),), qk_norm=s["qk_norm"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=s["tied"],
        param_dtype=jnp.bfloat16, activation_dtype=jnp.bfloat16,
        attn_impl=prog["attn_impl"], source_cite=cfg["source"])


def layer(x, p: dict, index, s: dict, cfg: dict, fp8: bool = False):
    """One decoder layer over a whole sequence x (S, D), float32; every
    layer alike, whatever its ``index``."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    n = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    q = _mm("sd,dhk->shk", h, p["wq"], fp8)
    k = _mm("sd,dhk->shk", h, p["wk"], fp8)
    v = _mm("sd,dhk->shk", h, p["wv"], fp8)
    if s["qk_norm"]:
        q = _rms(q, p["q_norm"], eps)
        k = _rms(k, p["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    nh, nkv, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(n, nkv, nh // nkv, hd)
    scores = jnp.einsum("skgd,tkd->kgst", qg, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("kgst,tkd->skgd", probs, v,
                     precision=jax.lax.Precision.HIGHEST).reshape(n, nh, hd)
    x = x + _mm("shk,hkd->sd", ctx, p["wo"], fp8)
    h2 = _rms(x, p["ln2"], eps)
    gate = _mm("sd,df->sf", h2, p["w_gate"], fp8)
    up = _mm("sd,df->sf", h2, p["w_up"], fp8)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["w_down"], fp8)


def head(x, g: dict, s: dict, cfg: dict, fp8: bool = False):
    """Logits (S, V) of hidden rows x (S, D)."""
    w_head = g["embed"].T if s["tied"] else g["lm_head"]
    eps = float(cfg["rms_norm_eps"])
    h = _rms(x, g["final_norm"].astype(jnp.float32), eps)
    return _mm("sd,dv->sv", h, w_head.astype(jnp.float32), fp8)


def layer_params(s: dict) -> int:
    """Matrix parameters of one dense GQA layer (norms left out)."""
    d, h, kv, hd, f = s["d"], s["h"], s["kv"], s["hd"], s["f"]
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f


def attention_flops(s: dict, layers: int, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs: 4 * heads * head_dim
    per pair per layer."""
    return 4 * s["h"] * s["hd"] * pairs * layers


def prefill_flops(s: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens through every layer, causal attention
    over the real positions only, and logits of the last position only."""
    causal_pairs = prompt * (prompt + 1) // 2
    return (2 * s["layers"] * layer_params(s) * prompt
            + attention_flops(s, s["layers"], causal_pairs)
            + 2 * s["d"] * s["v"])


def decode_flops(s: dict, position: int) -> int:
    """One new token at ``position`` (it attends position + 1 keys)."""
    return (2 * s["layers"] * layer_params(s)
            + attention_flops(s, s["layers"], position + 1)
            + 2 * s["d"] * s["v"])


def decode_stage_flops(s: dict, stage: int, stages: int,
                       position: int) -> int:
    """One session's decode step at ``position`` on one stage: its layers,
    and on the last stage the output head."""
    layers = stage_layers(s, stages)[stage]
    n = 2 * layers * layer_params(s) + attention_flops(s, layers, position + 1)
    if stage == stages - 1:
        n += 2 * s["d"] * s["v"]
    return n


def stage_weight_bytes(s: dict, stage: int, stages: int,
                       bytes_per: int = 2) -> int:
    """Weights one decode dispatch of a stage must read once: its layers,
    and on the last stage the final norm and the output head. The
    embedding rows the first stage gathers are counted per token in
    :func:`decode_bytes`, not here."""
    layers = stage_layers(s, stages)[stage]
    d = s["d"]
    n = layers * (layer_params(s) + 2 * d)
    if s["qk_norm"]:
        n += layers * 2 * s["hd"]
    if stage == stages - 1:
        n += d + d * s["v"]
    return n * bytes_per


def decode_kv_bytes(s: dict, stage: int, stages: int, position: int,
                    bytes_per: int = 2) -> int:
    """Cache bytes one session's decode step at ``position`` needs on a
    stage: K and V of positions 0..position read, and one position of each
    written."""
    layers = stage_layers(s, stages)[stage]
    per_pos = 2 * layers * s["kv"] * s["hd"] * bytes_per
    return per_pos * (position + 1) + per_pos


def decode_bytes(s: dict, stage: int, stages: int,
                 positions: list[int], bytes_per: int = 2) -> int:
    """Least bytes of one decode dispatch over sessions at ``positions``."""
    n = stage_weight_bytes(s, stage, stages, bytes_per)
    n += sum(decode_kv_bytes(s, stage, stages, p, bytes_per)
             for p in positions)
    if stage == 0:
        n += len(positions) * s["d"] * bytes_per     # embedding rows
    return n
