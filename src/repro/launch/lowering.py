"""Dry-run lowering: build + lower + compile every (arch × shape × mesh)
combination, and extract the roofline terms from the compiled artifact.

Pure library (no device-count manipulation) — dryrun.py forces the 512
placeholder devices before importing this; tests use an 8-device mesh.

Step kinds:
* ``train``   — full train_step (fwd + bwd + AdamW), FSDP+TP+sequence-parallel.
* ``prefill`` — serving prefill: last-position logits + decode-ready cache.
* ``decode``  — serve_step: ONE token against a seq_len-deep cache.
"""
from __future__ import annotations

import dataclasses
import re
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.shapes import (
    SHAPES,
    InputShape,
    batch_logical_axes,
    batch_specs,
    decode_specs,
    shape_applicable,
)
from repro.distributed import (
    INFER_RULES,
    LONG_DECODE_RULES,
    TRAIN_RULES,
    axis_rules,
    logical_sharding,
    tree_shardings,
)
from repro.models import build_model
from repro.training import AdamWConfig, abstract_opt_state, make_train_step
from repro.training.optimizer import opt_logical_axes
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16


class SkipCombo(Exception):
    pass


def rules_for(cfg, shape: InputShape) -> dict:
    if shape.kind == "train":
        rules = dict(TRAIN_RULES)
    elif shape.name == "long_500k":
        rules = dict(LONG_DECODE_RULES)
    else:
        rules = dict(INFER_RULES)
    if cfg.num_experts >= 64 and shape.kind != "train":
        # qwen3-moe: 454 GB expert bank cannot be data-replicated at
        # inference; FSDP the expert F dim over 'data' (gathered per layer)
        rules["mlp"] = "data"
    if cfg.family in ("ssm", "hybrid") and shape.kind == "train":
        # §Perf (measured): with ssm_inner tensor-parallel, every layer pays
        # a residual-sized all-reduce (out_proj contraction) — ~390 GB/dev of
        # wire on mamba2 train. A 2.7B model doesn't need TP: go
        # FSDP-everywhere — batch over ALL 256 chips, weights fully sharded
        # over (data, model), no TP contractions at all. Two-level remat
        # bounds the (now seq-unsharded) checkpoint memory.
        rules.update({
            "batch": ("pod", "data", "model"),
            "act_seq": None,
            "embed": ("data", "model"),
            "heads": None, "kv_heads": None, "head_dim": None,
            "mlp": None, "vocab": None,
            "ssm_heads": None, "ssm_inner": "data", "conv": None,
        })
    return rules


def overrides_for(cfg, shape: InputShape) -> dict:
    if shape.kind == "train" and cfg.family in ("ssm", "hybrid"):
        return {"remat_policy": "two_level"}
    return {}


def _decode_max_len(cfg, shape: InputShape) -> int:
    return shape.seq_len


def build_lowered(arch: str, shape_name: str, mesh, *,
                  attn_impl: str = "auto", overrides: Optional[dict] = None):
    """Returns (lowered, meta dict). Raises SkipCombo for sanctioned skips."""
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise SkipCombo(why)
    tuned = dict(overrides_for(cfg, shape))
    tuned.update(overrides or {})
    cfg = cfg.with_(attn_impl=attn_impl, remat=(shape.kind == "train"),
                    **tuned)
    model = build_model(cfg)
    rules = rules_for(cfg, shape)

    with axis_rules(rules, mesh):
        aparams = model.abstract_params()
        p_ax = model.logical_axes()
        p_sh = tree_shardings(aparams, p_ax)

        if shape.kind == "train":
            step = make_train_step(model, AdamWConfig())
            aopt = abstract_opt_state(aparams)
            o_sh = tree_shardings(aopt, opt_logical_axes(p_ax))
            batch = batch_specs(cfg, shape)
            b_sh = tree_shardings(batch, batch_logical_axes(cfg))
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),       # params/opt update in place
            ).lower(aparams, aopt, batch)

        elif shape.kind == "prefill":
            batch = batch_specs(cfg, shape)
            b_sh = tree_shardings(batch, batch_logical_axes(cfg))
            if cfg.family == "audio":
                def fn(p, b):
                    logits, _ = model.forward(p, b["tokens"],
                                              frames=b["frames"])
                    return logits[:, -1]
            else:
                def fn(p, b):
                    logits, _ = model.forward(
                        p, b["tokens"],
                        input_embeds=b.get("input_embeds"),
                        mrope_positions=b.get("mrope_positions"),
                        last_only=True)
                    return logits[:, 0]
            lowered = jax.jit(fn, in_shardings=(p_sh, b_sh)).lower(
                aparams, batch)

        else:  # decode
            kw = decode_specs(cfg, shape, model)
            acache = kw["cache"]
            c_ax = model.cache_logical_axes(shape.global_batch, shape.seq_len)
            c_sh = tree_shardings(acache, c_ax)
            tok_sh = logical_sharding(("batch", None),
                                      tuple(kw["tokens"].shape))
            t_sh = logical_sharding((), ())
            if cfg.family == "vlm":
                mp_sh = logical_sharding((None, "batch", None),
                                         tuple(kw["mrope_positions"].shape))

                def fn(p, c, tk, t, mp):
                    return model.decode_step(p, c, tk, t, mrope_positions=mp)

                lowered = jax.jit(
                    fn, in_shardings=(p_sh, c_sh, tok_sh, t_sh, mp_sh),
                    out_shardings=(None, c_sh),
                    donate_argnums=(1,),     # KV cache updates in place
                ).lower(aparams, acache, kw["tokens"], kw["t"],
                        kw["mrope_positions"])
            else:
                def fn(p, c, tk, t):
                    return model.decode_step(p, c, tk, t)

                lowered = jax.jit(
                    fn, in_shardings=(p_sh, c_sh, tok_sh, t_sh),
                    out_shardings=(None, c_sh),
                    donate_argnums=(1,),     # KV cache updates in place
                ).lower(aparams, acache, kw["tokens"], kw["t"])

    meta = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "devices": mesh.devices.size,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "tokens": shape.global_batch * (shape.seq_len
                                        if shape.kind != "decode" else 1),
    }
    return lowered, meta


# ------------------------------------------------------ collective parsing

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"= (?P<shapes>[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> list[dict]:
    """Per-device wire bytes for every collective op in the compiled HLO."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        result_bytes = _shape_bytes(m.group("shapes"))
        gm = _GROUPS_RE.search(line)
        n = int(gm.group(2)) if gm else 1
        if n <= 1:
            wire = 0.0
        elif op == "all-reduce":
            wire = 2.0 * (n - 1) / n * result_bytes
        elif op == "all-gather":
            wire = (n - 1) / n * result_bytes        # result = gathered
        elif op == "reduce-scatter":
            wire = (n - 1) * result_bytes            # result = one shard
        elif op == "all-to-all":
            wire = (n - 1) / n * result_bytes
        else:                                        # collective-permute
            wire = float(result_bytes)
        out.append({"op": op, "bytes": result_bytes, "group": n,
                    "wire_bytes": wire, "line": line.strip()[:160]})
    return out


def analyze(lowered, compiled, meta: dict) -> dict:
    """Roofline terms (seconds, per device) from the compiled artifact.

    FLOPs/bytes/collectives come from the loop-aware HLO analyzer
    (launch/hlo_cost.py) — XLA's own cost_analysis counts while bodies once,
    which undercounts scanned-layer models by orders of magnitude; its
    numbers are still recorded as ``xla_*`` for reference. Peak memory comes
    from XLA's memory_analysis (loop bodies don't multiply residency).
    """
    from .hlo_cost import analyze_hlo_text

    xla_cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    own = analyze_hlo_text(hlo)

    flops = own["flops"]
    bytes_accessed = own["bytes"]
    wire = own["wire_bytes"]
    by_op = own["collectives_by_op"]

    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_accessed / HBM_BW
    collective_s = wire / ICI_BW
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]

    model_flops = 6 * meta["active_params"] * meta["tokens"]
    if meta["kind"] == "train":
        model_flops *= 1.0           # 6ND already includes fwd+bwd convention
    else:
        model_flops = 2 * meta["active_params"] * meta["tokens"]
    per_dev_model_flops = model_flops / meta["devices"]

    return {
        **meta,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_accessed,
        "collective_wire_bytes_per_dev": wire,
        "collectives_by_op": by_op,
        "n_collectives": own["n_collectives"],
        "xla_flops_per_dev": float(xla_cost.get("flops", 0.0)),
        "xla_bytes_per_dev": float(xla_cost.get("bytes accessed", 0.0)),
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops_per_dev": per_dev_model_flops,
        "useful_flops_ratio": (per_dev_model_flops / flops) if flops else 0.0,
        "argument_bytes_per_dev": mem.argument_size_in_bytes,
        "output_bytes_per_dev": mem.output_size_in_bytes,
        "temp_bytes_per_dev": mem.temp_size_in_bytes,
        "peak_state_bytes_per_dev": mem.argument_size_in_bytes
        + mem.temp_size_in_bytes,
    }


def run_combo(arch: str, shape_name: str, mesh, **kw) -> dict:
    t0 = time.monotonic()
    lowered, meta = build_lowered(arch, shape_name, mesh, **kw)
    t1 = time.monotonic()
    compiled = lowered.compile()
    t2 = time.monotonic()
    result = analyze(lowered, compiled, meta)
    result["lower_s"] = t1 - t0
    result["compile_s"] = t2 - t1
    return result
