"""Random weights from ``--seed``, made by the benchmark and not by the
program, so the plain reference can make the very same values on its own.

Every leaf of every layer is drawn from a key folded from the seed, a
stable id of the leaf's name and the layer index. Drawing one layer alone
(the reference, layer by layer) and drawing the whole model in one jitted
call (what the program is given) therefore yield the same values.

Scales keep random-weight attention soft and logits of order 1: projections
are N(0, 1/fan_in); norm weights are 1 + 0.1 N(0, 1), so a path that skips a
norm's weight shows up in the comparison.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

NORM = "norm"


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


def layer_leaves(s: dict) -> list[tuple[str, tuple, object]]:
    """(name, shape, fan-in or NORM) of one layer's leaves."""
    d, h, kv, hd, f = s["d"], s["h"], s["kv"], s["hd"], s["f"]
    out = [("ln1", (d,), NORM), ("ln2", (d,), NORM),
           ("wq", (d, h, hd), d), ("wk", (d, kv, hd), d),
           ("wv", (d, kv, hd), d), ("wo", (h, hd, d), h * hd),
           ("w_gate", (d, f), d), ("w_up", (d, f), d), ("w_down", (f, d), f)]
    if s["qk_norm"]:
        out += [("q_norm", (hd,), NORM), ("k_norm", (hd,), NORM)]
    return out


def global_leaves(s: dict) -> list[tuple[str, tuple, object]]:
    out = [("embed", (s["v"], s["d"]), s["d"]), ("final_norm", (s["d"],), NORM)]
    if not s["tied"]:
        out.append(("lm_head", (s["d"], s["v"]), s["d"]))
    return out


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of any size as two 32-bit words (PRNGKey takes 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def _base_key(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _leaf(base, name: str, layer, shape: tuple, init, dtype):
    key = jax.random.fold_in(
        jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        layer)
    x = jax.random.normal(key, shape, jnp.float32)
    if init == NORM:
        x = 1.0 + 0.1 * x
    else:
        x = x * (1.0 / float(init) ** 0.5)
    return x.astype(dtype)


def make_layer(s: dict, lo, hi, layer, dtype=jnp.bfloat16) -> dict:
    """One layer's leaves by name (traceable in the seed and the layer)."""
    base = _base_key(lo, hi)
    return {n: _leaf(base, n, layer, shp, init, dtype)
            for n, shp, init in layer_leaves(s)}


def make_globals(s: dict, lo, hi, dtype=jnp.bfloat16) -> dict:
    base = _base_key(lo, hi)
    return {n: _leaf(base, n, 0, shp, init, dtype)
            for n, shp, init in global_leaves(s)}


def program_params(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole model in the program's parameter layout (one stacked
    group of dense layers), made on the device in one jitted call."""

    def build(lo, hi):
        layers = [make_layer(s, lo, hi, i, dtype) for i in range(s["layers"])]

        def stack(name):
            return jnp.stack([lay[name] for lay in layers])

        attn = {n: stack(n) for n in ("wq", "wk", "wv", "wo")}
        if s["qk_norm"]:
            attn["q_norm"] = stack("q_norm")
            attn["k_norm"] = stack("k_norm")
        g = make_globals(s, lo, hi, dtype)
        out = {"embed": g["embed"], "final_norm": g["final_norm"],
               "groups": [{"ln1": stack("ln1"), "ln2": stack("ln2"),
                           "attn": attn,
                           "mlp": {n: stack(n) for n in
                                   ("w_gate", "w_up", "w_down")}}]}
        if not s["tied"]:
            out["lm_head"] = g["lm_head"]
        return out

    lo, hi = seed_words(seed)
    return jax.jit(build)(jnp.uint32(lo), jnp.uint32(hi))
