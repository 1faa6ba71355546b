"""Dry-run machinery on a reduced 8-device mesh (subprocess so the forced
device count never leaks into other tests), plus unit tests of the
loop-aware HLO analyzer."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze_hlo_text, parse_module

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.launch.lowering import run_combo, SkipCombo
from repro.launch.mesh import make_test_mesh

results = {}
mesh = make_test_mesh()
for arch, shape in [("llama3.2-1b", "train_4k"),
                    ("mamba2-2.7b", "decode_32k"),
                    ("whisper-base", "prefill_32k"),
                    ("qwen3-moe-235b-a22b", "decode_32k")]:
    r = run_combo(arch, shape, mesh)
    results[f"{arch}/{shape}"] = {
        "dominant": r["dominant"],
        "flops": r["hlo_flops_per_dev"],
        "useful": r["useful_flops_ratio"],
        "ncoll": r["n_collectives"],
    }
# sanctioned skip must raise SkipCombo
try:
    run_combo("yi-34b", "long_500k", mesh)
    results["skip"] = "MISSING"
except SkipCombo:
    results["skip"] = "ok"
# multi-pod test mesh lowers too
mesh2 = make_test_mesh(multi_pod=True)
r = run_combo("llama3.2-1b", "decode_32k", mesh2)
results["multipod"] = r["dominant"]
print(json.dumps(results))
"""


def test_dryrun_reduced_mesh():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=900, cwd=".",
        # the child compiles for the forced CPU devices only: it must never
        # load the TPU runtime, which a chip's one process may hold
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert results["skip"] == "ok"
    assert results["multipod"] in ("memory", "compute", "collective")
    for combo, r in results.items():
        if combo in ("skip", "multipod"):
            continue
        assert r["flops"] > 0, combo
        assert 0 < r["useful"] <= 2.0, (combo, r)
        assert r["ncoll"] > 0, combo


def test_hlo_cost_scan_trip_counting():
    def body(c, _):
        return jnp.tanh(c @ c), None

    def f(x):
        return jax.lax.scan(body, x, None, length=7)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(x).compile().as_text()
    r = analyze_hlo_text(txt)
    assert abs(r["flops"] - 7 * 2 * 64 ** 3) / (7 * 2 * 64 ** 3) < 0.01


def test_hlo_cost_dot_flops_exact():
    def g(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 16), jnp.float32)
    txt = jax.jit(g).lower(a, b).compile().as_text()
    r = analyze_hlo_text(txt)
    assert r["flops"] == 2 * 32 * 128 * 16


def test_hlo_parse_handles_tuple_shapes():
    txt = """HloModule m, entry_computation_layout={()->f32[2]{0}}

ENTRY %main (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  %t = (f32[2]{0}, s32[], /*index=2*/f32[4,4]{1,0}) tuple(%p, %p, %p)
  ROOT %g = f32[2]{0} get-tuple-element(%t), index=0
}
"""
    comps, entry = parse_module(txt)
    assert entry is not None
    ops = {o.name: o for o in comps[entry].ops}
    assert ops["t"].opcode == "tuple"
    assert ops["g"].is_root
