"""A tiny benchmark, built as files in a temporary directory, that the
tests run on the CPU: the same harness, a 2-layer model of width 64, and a
few seconds of traffic."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "tiny", "source": "test", "bench_arch": "dense_gqa",
    "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "vocab_size": 256,
    "program": {"arch": "tiny", "attn_impl": "reference", "qk_norm": True},
    "check": {"logit_gap_limit": 0.1},
}

TINY_MIX = {
    "rate_rps": 4.0,
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 24},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 10},
    "sizes_seed": 7,
    "server": {"replicas": [1, 2], "least_loaded": True, "max_len": 40,
               "heartbeat_interval_s": 0.05, "heartbeat_timeout_s": 1.0},
    "events": [],
}


def make(tmp: str, metrics=None, mixes=None) -> str:
    """Lay out ``tmp`` as a checkout: BENCHMARK.json, the tiny config and
    mixes, and copies of the real metric readers, layer modules and lib.
    Returns its root."""
    bench = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("lib", "arch"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub),
                        dirs_exist_ok=True)
    for f in os.listdir(os.path.join(BENCH, "metrics")):
        if f.endswith(".py"):
            shutil.copy(os.path.join(BENCH, "metrics", f),
                        os.path.join(bench, "metrics", f))
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    mixes = mixes or {"tiny-mix": TINY_MIX}
    for name, mix in mixes.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": f"tiny-{m}", "config": "tiny",
                          "traffic": m, "chips": 1, "why": "test"}
                         for m in mixes]
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            if "workloads" in m:
                m["workloads"] = [w["name"] for w in spec["workloads"]]
    spec["per_layer"] += metrics or []
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp
