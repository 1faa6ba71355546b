"""A tiny mixture-of-experts decoder: the program's ``MOE`` family (GQA
attention, then a routed SwiGLU expert layer), with every expert held here.

Expert layer (as the program's ``models/moe.py`` routes, without capacity):

    h2 = RMSNorm(x) ;  r = softmax(h2 W_router)   (float32)
    top-k experts of r, their weights renormalised to sum to 1
    x = x + sum over those experts e of w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

The program drops a token that overflows an expert's capacity; the
configuration sets ``moe_capacity_factor`` to experts / experts per token,
which gives every expert a slot for every token, so none is dropped and the
reference need not model capacity.

It runs in float32 (the drawn bfloat16 values, widened). In bfloat16 the
program's top-k flips against the float32 reference wherever two experts'
router probabilities lie within rounding of each other: with one seed the
second and third lay 3e-5 apart and the program's top token of a row lay
1.36 below the reference's best, while the program in float32 on the same
weights read a gap of 0 on each of eight seeds.

Counts: a token reads its top-k experts, so ``layer_params`` counts k
experts and the router; a decode dispatch reads at least k experts a
layer, whichever the convoy routes to.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from lib.costs import stage_layers
from lib.reference import _mm, _rms, _rope
from lib.weights import NORM


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "f": int(cfg["moe_intermediate_size"]),
            "e": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "qk_norm": bool(cfg["program"]["qk_norm"])}


def layer_leaves(s: dict, layer: int) -> list[tuple[str, tuple, object]]:
    d, h, kv, hd, f, e = s["d"], s["h"], s["kv"], s["hd"], s["f"], s["e"]
    out = [("ln1", (d,), NORM), ("ln2", (d,), NORM),
           ("wq", (d, h, hd), d), ("wk", (d, kv, hd), d),
           ("wv", (d, kv, hd), d), ("wo", (h, hd, d), h * hd),
           ("router", (d, e), d), ("w_gate", (e, d, f), d),
           ("w_up", (e, d, f), d), ("w_down", (e, f, d), f)]
    if s["qk_norm"]:
        out += [("q_norm", (hd,), NORM), ("k_norm", (hd,), NORM)]
    return out


def global_leaves(s: dict) -> list[tuple[str, tuple, object]]:
    return [("embed", (s["v"], s["d"]), s["d"]),
            ("final_norm", (s["d"],), NORM),
            ("lm_head", (s["d"], s["v"]), s["d"])]


def pack(s: dict, layers: list[dict], g: dict) -> dict:
    """One stacked group of MoE layers, in float32."""

    def stack(*names):
        return {n: jnp.stack([lay[n] for lay in layers]).astype(jnp.float32)
                for n in names}

    mlp = stack("router", "w_gate", "w_up", "w_down")
    attn = stack("wq", "wk", "wv", "wo",
                 *(("q_norm", "k_norm") if s["qk_norm"] else ()))
    return {**{n: g[n].astype(jnp.float32) for n in g},
            "groups": [{**stack("ln1", "ln2"), "attn": attn, "mlp": mlp}]}


def program_config(cfg: dict, s: dict):
    from repro.models import MOE, BlockGroup, ModelConfig
    prog = cfg["program"]
    return ModelConfig(
        arch_id=prog["arch"], family="moe", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["h"], num_kv_heads=s["kv"],
        head_dim=s["hd"], d_ff=s["f"], vocab_size=s["v"],
        groups=(BlockGroup(MOE, s["layers"]),), qk_norm=s["qk_norm"],
        num_experts=s["e"], experts_per_token=s["k"], moe_d_ff=s["f"],
        moe_capacity_factor=s["e"] / s["k"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=False,
        param_dtype=jnp.float32, activation_dtype=jnp.float32,
        attn_impl=prog["attn_impl"], source_cite=cfg["source"])


def layer(x, p: dict, index, s: dict, cfg: dict, fp8: bool = False):
    """One MoE decoder layer over a whole sequence x (S, D), float32."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    n = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    q = _mm("sd,dhk->shk", h, p["wq"], fp8)
    k = _mm("sd,dhk->shk", h, p["wk"], fp8)
    v = _mm("sd,dhk->shk", h, p["wv"], fp8)
    if s["qk_norm"]:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    nh, nkv, hd = q.shape[1], k.shape[1], q.shape[2]
    scores = jnp.einsum("skgd,tkd->kgst", q.reshape(n, nkv, nh // nkv, hd), k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("kgst,tkd->skgd", probs, v,
                     precision=jax.lax.Precision.HIGHEST).reshape(n, nh, hd)
    x = x + _mm("shk,hkd->sd", ctx, p["wo"], fp8)
    h2 = _rms(x, p["ln2"], eps)
    route = jax.nn.softmax(_mm("sd,de->se", h2, p["router"], fp8), axis=-1)
    top, ids = jax.lax.top_k(route, s["k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.sum(top[..., None] * jax.nn.one_hot(ids, s["e"]), axis=1)
    gate = _mm("sd,edf->esf", h2, p["w_gate"], fp8)
    up = _mm("sd,edf->esf", h2, p["w_up"], fp8)
    y = _mm("esf,efd->esd", jax.nn.silu(gate) * up, p["w_down"], fp8)
    return x + jnp.einsum("se,esd->sd", gates, y,
                          precision=jax.lax.Precision.HIGHEST)


def head(x, g: dict, s: dict, cfg: dict, fp8: bool = False):
    eps = float(cfg["rms_norm_eps"])
    h = _rms(x, g["final_norm"].astype(jnp.float32), eps)
    return _mm("sd,dv->sv", h, g["lm_head"].astype(jnp.float32), fp8)


def layer_params(s: dict) -> int:
    """Matrix parameters one token uses in a layer: attention, the router
    and its k experts."""
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    return (d * (h + 2 * kv) * hd + h * hd * d + d * s["e"]
            + s["k"] * 3 * d * s["f"])


def _attention_flops(s: dict, layers: int, pairs: int) -> int:
    return 4 * s["h"] * s["hd"] * pairs * layers


def prefill_flops(s: dict, prompt: int) -> int:
    return (2 * s["layers"] * layer_params(s) * prompt
            + _attention_flops(s, s["layers"], prompt * (prompt + 1) // 2)
            + 2 * s["d"] * s["v"])


def decode_flops(s: dict, position: int) -> int:
    return (2 * s["layers"] * layer_params(s)
            + _attention_flops(s, s["layers"], position + 1)
            + 2 * s["d"] * s["v"])


def decode_stage_flops(s: dict, stage: int, stages: int,
                       position: int) -> int:
    layers = stage_layers(s, stages)[stage]
    n = (2 * layers * layer_params(s)
         + _attention_flops(s, layers, position + 1))
    return n + (2 * s["d"] * s["v"] if stage == stages - 1 else 0)


def stage_weight_bytes(s: dict, stage: int, stages: int,
                       bytes_per: int = 4) -> int:
    """Least weights a decode dispatch reads: its layers with k experts
    each, and on the last stage the head."""
    layers = stage_layers(s, stages)[stage]
    d = s["d"]
    n = layers * (layer_params(s) + 2 * d)
    if s["qk_norm"]:
        n += layers * 2 * s["hd"]
    if stage == stages - 1:
        n += d + d * s["v"]
    return n * bytes_per


def decode_kv_bytes(s: dict, stage: int, stages: int, position: int,
                    bytes_per: int = 4) -> int:
    layers = stage_layers(s, stages)[stage]
    per_pos = 2 * layers * s["kv"] * s["hd"] * bytes_per
    return per_pos * (position + 2)


def decode_bytes(s: dict, stage: int, stages: int,
                 positions: list[int], bytes_per: int = 4) -> int:
    n = stage_weight_bytes(s, stage, stages, bytes_per)
    n += sum(decode_kv_bytes(s, stage, stages, p, bytes_per)
             for p in positions)
    if stage == 0:
        n += len(positions) * s["d"] * bytes_per
    return n
