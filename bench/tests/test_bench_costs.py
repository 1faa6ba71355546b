"""Operation and byte counts of the dense GQA layer module against
hand-worked numbers, and the peak table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import costs  # noqa: E402
from lib.peaks import UnknownDevice, peaks  # noqa: E402
from lib.spec import Benchmark  # noqa: E402
from tests_support import ROOT  # noqa: E402

A = Benchmark(ROOT).arch("dense_gqa")

# d 8, 2 query heads and 1 kv head of 4, ff 16, vocab 10, 2 layers
S = {"d": 8, "h": 2, "kv": 1, "hd": 4, "f": 16, "v": 10, "layers": 2,
     "qk_norm": True, "tied": False}


def test_layer_params_by_hand():
    # q 8*2*4=64, k 32, v 32, o 2*4*8=64, mlp 3*8*16=384
    assert A.layer_params(S) == 576


def test_prefill_flops_by_hand():
    # 3 tokens: 2*2*576*3 = 6912 matmul; causal pairs 6 -> 4*2*4*6*2 = 384
    # attention; last-position logits 2*8*10 = 160
    assert A.prefill_flops(S, 3) == 6912 + 384 + 160


def test_decode_flops_by_hand():
    # position 5 attends 6 keys: 2*2*576 + 4*2*4*6*2 + 160
    assert A.decode_flops(S, 5) == 2304 + 384 + 160
    # per stage of two: one layer each, the head on the last
    assert A.decode_stage_flops(S, 0, 2, 5) == 1152 + 192
    assert A.decode_stage_flops(S, 1, 2, 5) == 1152 + 192 + 160


def test_decode_bytes_by_hand():
    assert costs.stage_layers(S, 2) == [1, 1]
    # stage 0: one layer of 576 + norms 2*8 + qk norms 2*4 = 600 params
    assert A.stage_weight_bytes(S, 0, 2) == 600 * 2
    # stage 1 adds the final norm 8 and the head 80
    assert A.stage_weight_bytes(S, 1, 2) == (600 + 8 + 80) * 2
    # one layer: K and V of 1 head of 4 = 8 values a position, bf16 16
    # bytes; position 5 reads 6 positions, writes one
    assert A.decode_kv_bytes(S, 0, 2, 5) == 16 * 7
    # two sessions on stage 0: weights once, caches each, embedding rows
    assert A.decode_bytes(S, 0, 2, [5, 5]) == 1200 + 2 * 112 + 2 * 16


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_refused():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(UnknownDevice):
        peaks("TPU v99")
