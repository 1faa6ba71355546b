"""The dense GQA equations moved out of the harness into
``arch/dense_gqa.py`` unchanged: the weights the program is given, the
reference's logits, every count, and the ``decode_roofline`` and
``step_mfu`` readings are bit for bit the numbers that the harness gave
before the move (``PARENT``, taken from it at these sizes and seeds)."""
import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import trace as TR, weights as W  # noqa: E402
from lib.context import Context  # noqa: E402
from lib.peaks import peaks  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.serve import Record, Window  # noqa: E402
from lib.spec import Benchmark, load_json  # noqa: E402
from lib.traffic import Request  # noqa: E402
from tests_support import BENCH, ROOT, TINY_CONFIG  # noqa: E402

CONFIGS = {
    "qk": TINY_CONFIG,
    "noqk": dict(TINY_CONFIG,
                 program=dict(TINY_CONFIG["program"], qk_norm=False)),
    "yi": load_json(os.path.join(BENCH, "configs", "yi-34b.l4.json")),
}
POSITIONS = (0, 17, 511)
SEEDS = (5, 2**33 + 7)
TINY = ("qk", "noqk")
T0 = 1000.0

PARENT = {
 "qk": {
  "costs": {
   "layer_params": 36864,
   "prefill_flops@0": 180736,
   "decode_flops@0": 180736,
   "stage_weight_bytes@0": 74048,
   "decode_kv_bytes@0,0": 256,
   "decode_bytes@0,0": 75200,
   "stage_weight_bytes@1": 106944,
   "decode_kv_bytes@1,0": 256,
   "decode_bytes@1,0": 107840,
   "prefill_flops@17": 2774528,
   "decode_flops@17": 189440,
   "decode_kv_bytes@0,17": 2432,
   "decode_bytes@0,17": 79552,
   "decode_kv_bytes@1,17": 2432,
   "decode_bytes@1,17": 112192,
   "prefill_flops@511": 142770176,
   "decode_flops@511": 442368,
   "decode_kv_bytes@0,511": 65664,
   "decode_bytes@0,511": 206016,
   "decode_kv_bytes@1,511": 65664,
   "decode_bytes@1,511": 238656
  },
  "params@5": {
   "['embed']": "84946e2d2cee6da9",
   "['final_norm']": "5c8eb8e6a891b07a",
   "['groups'][0]['attn']['k_norm']": "d7a99aa19de87aff",
   "['groups'][0]['attn']['q_norm']": "a4d2ea7f4bffd52a",
   "['groups'][0]['attn']['wk']": "0a4a63e1dbefd92a",
   "['groups'][0]['attn']['wo']": "84cd9fa99694aa31",
   "['groups'][0]['attn']['wq']": "7e2ed2f76a92ce03",
   "['groups'][0]['attn']['wv']": "37d522efb027d034",
   "['groups'][0]['ln1']": "843027ff1a0bd06a",
   "['groups'][0]['ln2']": "0f8a3578d6d155fa",
   "['groups'][0]['mlp']['w_down']": "ee85ea73cc1fdc3f",
   "['groups'][0]['mlp']['w_gate']": "87db2b4bda3596df",
   "['groups'][0]['mlp']['w_up']": "bb55a832538c7e83",
   "['lm_head']": "1a9e14717c809d01"
  },
  "params@8589934599": {
   "['embed']": "a753016337a0e767",
   "['final_norm']": "3da11a9628b84968",
   "['groups'][0]['attn']['k_norm']": "550c0e39b98262e9",
   "['groups'][0]['attn']['q_norm']": "b189254802d47116",
   "['groups'][0]['attn']['wk']": "ba8dc4c4f85bca24",
   "['groups'][0]['attn']['wo']": "e53094716b7d7cbc",
   "['groups'][0]['attn']['wq']": "2f15da0a158284a7",
   "['groups'][0]['attn']['wv']": "202d6433fc9615bc",
   "['groups'][0]['ln1']": "d8e356abdb47ba1c",
   "['groups'][0]['ln2']": "a71a8d467b09e5a5",
   "['groups'][0]['mlp']['w_down']": "d4cadd6c387f3af8",
   "['groups'][0]['mlp']['w_gate']": "bca687ccdd2f887a",
   "['groups'][0]['mlp']['w_up']": "75c214e9d0997850",
   "['lm_head']": "7dcce44620df9f05"
  },
  "logits": {
   "f32": "231575ce8a35ed3d",
   "fp8": "5b866e90b5a8db61"
  },
  "decode_roofline": 152.32885632885635,
  "step_mfu": 1.5651086294416243
 },
 "noqk": {
  "costs": {
   "layer_params": 36864,
   "prefill_flops@0": 180736,
   "decode_flops@0": 180736,
   "stage_weight_bytes@0": 73984,
   "decode_kv_bytes@0,0": 256,
   "decode_bytes@0,0": 75136,
   "stage_weight_bytes@1": 106880,
   "decode_kv_bytes@1,0": 256,
   "decode_bytes@1,0": 107776,
   "prefill_flops@17": 2774528,
   "decode_flops@17": 189440,
   "decode_kv_bytes@0,17": 2432,
   "decode_bytes@0,17": 79488,
   "decode_kv_bytes@1,17": 2432,
   "decode_bytes@1,17": 112128,
   "prefill_flops@511": 142770176,
   "decode_flops@511": 442368,
   "decode_kv_bytes@0,511": 65664,
   "decode_bytes@0,511": 205952,
   "decode_kv_bytes@1,511": 65664,
   "decode_bytes@1,511": 238592
  },
  "params@5": {
   "['embed']": "84946e2d2cee6da9",
   "['final_norm']": "5c8eb8e6a891b07a",
   "['groups'][0]['attn']['wk']": "0a4a63e1dbefd92a",
   "['groups'][0]['attn']['wo']": "84cd9fa99694aa31",
   "['groups'][0]['attn']['wq']": "7e2ed2f76a92ce03",
   "['groups'][0]['attn']['wv']": "37d522efb027d034",
   "['groups'][0]['ln1']": "843027ff1a0bd06a",
   "['groups'][0]['ln2']": "0f8a3578d6d155fa",
   "['groups'][0]['mlp']['w_down']": "ee85ea73cc1fdc3f",
   "['groups'][0]['mlp']['w_gate']": "87db2b4bda3596df",
   "['groups'][0]['mlp']['w_up']": "bb55a832538c7e83",
   "['lm_head']": "1a9e14717c809d01"
  },
  "params@8589934599": {
   "['embed']": "a753016337a0e767",
   "['final_norm']": "3da11a9628b84968",
   "['groups'][0]['attn']['wk']": "ba8dc4c4f85bca24",
   "['groups'][0]['attn']['wo']": "e53094716b7d7cbc",
   "['groups'][0]['attn']['wq']": "2f15da0a158284a7",
   "['groups'][0]['attn']['wv']": "202d6433fc9615bc",
   "['groups'][0]['ln1']": "d8e356abdb47ba1c",
   "['groups'][0]['ln2']": "a71a8d467b09e5a5",
   "['groups'][0]['mlp']['w_down']": "d4cadd6c387f3af8",
   "['groups'][0]['mlp']['w_gate']": "bca687ccdd2f887a",
   "['groups'][0]['mlp']['w_up']": "75c214e9d0997850",
   "['lm_head']": "7dcce44620df9f05"
  },
  "logits": {
   "f32": "669767e035cfa440",
   "fp8": "6721521fa518352e"
  },
  "decode_roofline": 152.22466422466422,
  "step_mfu": 1.5651086294416243
 },
 "yi": {
  "costs": {
   "layer_params": 557842432,
   "prefill_flops@0": 5380358144,
   "decode_flops@0": 5380358144,
   "stage_weight_bytes@0": 2231427072,
   "decode_kv_bytes@0,0": 16384,
   "decode_bytes@0,0": 2231513088,
   "stage_weight_bytes@1": 3148945408,
   "decode_kv_bytes@1,0": 16384,
   "decode_bytes@1,0": 3149002752,
   "prefill_flops@17": 81266425856,
   "decode_flops@17": 5382307840,
   "decode_kv_bytes@0,17": 155648,
   "decode_bytes@0,17": 2231791616,
   "decode_kv_bytes@1,17": 155648,
   "decode_bytes@1,17": 3149281280,
   "prefill_flops@511": 2300901851136,
   "decode_flops@511": 5438963712,
   "decode_kv_bytes@0,511": 4202496,
   "decode_bytes@0,511": 2239885312,
   "decode_kv_bytes@1,511": 4202496,
   "decode_bytes@1,511": 3157374976
  }
 }
}


@pytest.fixture(scope="module")
def arch():
    return Benchmark(ROOT).arch("dense_gqa")


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_are_the_harness_counts(arch, name):
    s = arch.sizes(CONFIGS[name])
    got = {"layer_params": arch.layer_params(s)}
    for p in POSITIONS:
        got[f"prefill_flops@{p}"] = arch.prefill_flops(s, p + 1)
        got[f"decode_flops@{p}"] = arch.decode_flops(s, p)
        for st in (0, 1):
            got[f"stage_weight_bytes@{st}"] = arch.stage_weight_bytes(s, st, 2)
            got[f"decode_kv_bytes@{st},{p}"] = arch.decode_kv_bytes(s, st, 2,
                                                                    p)
            got[f"decode_bytes@{st},{p}"] = arch.decode_bytes(s, st, 2,
                                                              [p, p + 3])
        # the stages' flops make up the whole step's
        assert sum(arch.decode_stage_flops(s, st, 2, p)
                   for st in (0, 1)) == arch.decode_flops(s, p)
    assert got == PARENT[name]["costs"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", TINY)
def test_program_weights_are_the_harness_weights(arch, name, seed):
    s = arch.sizes(CONFIGS[name])
    flat = jax.tree_util.tree_flatten_with_path(
        W.program_params(arch, s, seed))[0]
    got = {jax.tree_util.keystr(k): _digest(v) for k, v in flat}
    assert got == PARENT[name][f"params@{seed}"]


@pytest.mark.parametrize("precision", ("f32", "fp8"))
@pytest.mark.parametrize("name", TINY)
def test_reference_logits_are_the_harness_logits(arch, name, precision):
    s = arch.sizes(CONFIGS[name])
    seq = (np.arange(37, dtype=np.int32) * 7 + 3) % s["v"]
    ref = Reference(arch, CONFIGS[name], 5, precision)
    lg = ref.logits([seq], [np.arange(37)])[0]
    assert _digest(lg) == PARENT[name]["logits"][precision]


def _context(arch, cfg):
    """Two sessions decoding over two stages in a 10 s window, traced as
    ``fixtures/trace_small.json`` (one prefill and two decode programs
    named)."""
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        events = json.load(f)["events"]
    recs = []
    for i, (prompt, n) in enumerate(((5, 4), (9, 3))):
        r = Record(Request(i, 0.1, np.zeros(prompt, np.int32), n))
        r.times = [T0 + 0.5 + 0.1 * j for j in range(n)]
        recs.append(r)
    z = {"decode_steps": 0, "decode_batches": 0, "prefill_calls": 0}
    e0 = {"decode_steps": 3, "decode_batches": 1, "prefill_calls": 1}
    e1 = {"decode_steps": 2, "decode_batches": 1, "prefill_calls": 0}
    win = Window(seconds=10.0, records=recs,
                 counters_start={"executors": [z, z]},
                 counters_end={"executors": [e0, e1]}, compiles=[],
                 setup_s=1.0, t0=T0)
    return Context(cell="c", cfg=cfg, arch=arch, sizes=arch.sizes(cfg),
                   stages=2, mix={}, window=win, trace=TR.reduce(events),
                   peaks=peaks("TPU v5 lite"), setup_s=1.0)


@pytest.mark.parametrize("metric", ("decode_roofline", "step_mfu"))
@pytest.mark.parametrize("name", TINY)
def test_trace_readers_read_what_the_harness_read(arch, name, metric):
    ctx = _context(arch, CONFIGS[name])
    assert Benchmark(ROOT).reader(metric)(ctx) == PARENT[name][metric]
