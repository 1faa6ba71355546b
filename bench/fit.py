#!/usr/bin/env python3
"""Check, without a chip, that a cell's stage programs fit one v5e chip.

    JAX_PLATFORMS=cpu python3 bench/fit.py --workload <cell>

Compiles, for a described v5e (``jax.experimental.topologies``), each
stage's prefill program at the cell's largest prefill bucket and its decode
convoy program at the largest width, from shapes alone, and prints each
program's ``memory_analysis()`` beside the bytes the process keeps resident
(the whole model, held by the model registry, plus every stage's slice).
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lib import spec as S, traffic as T  # noqa: E402


def nbytes(tree) -> int:
    return int(sum(x.size * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(tree)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models import build_model
    from repro.serving import PipelineServer
    from repro.serving.executor import StageExecutor
    from repro.serving.partition import split_stages, stage_params

    bench = S.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    (cfg, arch), mix = bench.config(cell), S.load_json(cell.traffic_file)
    pcfg = arch.program_config(cfg, arch.sizes(cfg))
    max_len = int(mix["server"]["max_len"])
    n_stages = len(mix["server"]["replicas"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    params = build_model(pcfg).abstract_params()
    lengths = T.prompt_lengths(mix, args.seconds)
    bucket = min(StageExecutor._bucket(max(lengths)), max_len)
    width = StageExecutor._width_bucket(inspect.signature(
        PipelineServer).parameters["microbatch_max"].default)
    report = {"cell": cell.name, "whole_model_bytes": nbytes(params),
              "bucket": bucket, "stages": []}
    for spec in split_stages(pcfg, n_stages):
        sp = jax.eval_shape(lambda p: stage_params(pcfg, p, spec), params)
        ex = StageExecutor(pcfg, spec, None, max_len=max_len)
        if spec.first:
            x = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
            step = jax.ShapeDtypeStruct((1, 1), jnp.int32)
        else:
            x = jax.ShapeDtypeStruct((1, bucket, pcfg.d_model), jnp.bfloat16)
            step = jax.ShapeDtypeStruct((1, 1, pcfg.d_model), jnp.bfloat16)
        pre = ex._prefill.lower(on_chip(sp), on_chip(x)).compile()
        cache = jax.eval_shape(lambda p, a: ex._prefill(p, a)[1], sp, x)
        dec = ex._decode_many.lower(
            on_chip(sp), on_chip((cache,) * width), on_chip((step,) * width),
            on_chip(jax.ShapeDtypeStruct((width,), jnp.int32))).compile()
        # embedding, final norm and head are the whole model's own arrays,
        # not copies; only the layer slices are new buffers
        stage = {"stage": spec.index, "param_bytes": nbytes(sp),
                 "sliced_layer_bytes": nbytes(sp["groups"]),
                 "session_cache_bytes": nbytes(cache)}
        for name, c in (("prefill", pre), ("decode", dec)):
            m = c.memory_analysis()
            stage[name] = {k: int(getattr(m, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")}
        report["stages"].append(stage)
        print(json.dumps(stage), flush=True)
    resident = report["whole_model_bytes"] + sum(
        s["sliced_layer_bytes"] for s in report["stages"])
    report["resident_param_bytes"] = resident
    report["largest_prefill_temp_bytes"] = max(
        s["prefill"]["temp_size_in_bytes"] + s["prefill"]["output_size_in_bytes"]
        for s in report["stages"])
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
