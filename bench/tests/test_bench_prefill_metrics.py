"""The prefill readers on fixed contexts: ``prefill_pad_share`` from the
executors' counters, ``prefill_roofline`` from known prompt lengths and
device times, at ``qwen3-8b.l4``'s sizes with its operations and bytes
counted here by hand."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import measure, spec as S  # noqa: E402
from lib.context import Context  # noqa: E402
from lib.peaks import peaks  # noqa: E402
from lib.serve import Record, Window  # noqa: E402
from lib.traffic import Request  # noqa: E402
from tests_support import ROOT  # noqa: E402

T0 = 1000.0
V5E = peaks("TPU v5 lite")
PROMPTS = (700, 2117, 4096)


def _counters(**stages):
    """Executor counters of two stages: each key maps to a value per
    stage."""
    return {"executors": [{k: v[i] for k, v in stages.items()}
                          for i in range(2)]}


def _window(prompts=PROMPTS, start=None, end=None, first_token_s=1.0):
    records = []
    for i, n in enumerate(prompts):
        r = Record(Request(i, 0.5, np.zeros(n, np.int32), 2))
        r.times = [T0 + first_token_s, T0 + first_token_s + 0.1]
        r.out = np.zeros(2, np.int32)
        records.append(r)
    calls = 2 * len(prompts)
    start = start or _counters(prefill_calls=(0, 0))
    end = end or _counters(prefill_calls=(calls // 2, calls // 2))
    return Window(seconds=10.0, records=records, counters_start=start,
                  counters_end=end, compiles=[], setup_s=1.0, t0=T0)


def _ctx(win, trace=None, device_peaks=V5E):
    bench = S.Benchmark(ROOT)
    cfg, arch = bench.config(bench.cell("qwen3-8b-docqa"))
    return Context(cell="qwen3-8b-docqa", cfg=cfg, arch=arch,
                   sizes=arch.sizes(cfg), stages=2, mix={}, window=win,
                   trace=trace, peaks=device_peaks, setup_s=1.0)


def _read(name, ctx):
    return S.Benchmark(ROOT).reader(name)(ctx)


def _least_s(n: int) -> float:
    """Qwen3-8B at 4 layers, by hand: d 4096, 32 query and 8 KV heads of
    128, ff 12288, vocabulary 151,936, untied."""
    d, h, kv, hd, f, v, layers = 4096, 32, 8, 128, 12288, 151936, 4
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f
    flops = (2 * layers * per_layer * n
             + 4 * h * hd * layers * n * (n + 1) // 2 + 2 * d * v)
    # layers with their norms (two of d, two of hd), final norm and head
    weights = 2 * (layers * (per_layer + 2 * d + 2 * hd) + d + d * v)
    kv_written = 2 * layers * kv * hd * 2 * n
    return max(flops / V5E["bf16_flops"],
               (weights + kv_written) / V5E["hbm_bytes_per_s"])


def _trace(device_s, calls):
    return {"by_label": {"prefill": {"s": device_s, "calls": calls}},
            "window_s": 10.0}


def test_pad_share_is_padding_over_bucketed_positions_on_every_stage():
    start = _counters(prefill_tokens=(100, 100),
                      prefill_positions=(128, 128), prefill_calls=(1, 1))
    end = _counters(prefill_tokens=(100 + 2117 + 700, 100 + 2117 + 700),
                    prefill_positions=(128 + 4096 + 1024, 128 + 4096 + 1024),
                    prefill_calls=(3, 3))
    share = _read("prefill_pad_share", _ctx(_window(start=start, end=end)))
    assert share == pytest.approx(100.0 * (5120 - 2817) / 5120)


def test_pad_share_reads_nothing_without_the_counters_or_a_prefill():
    # a program that keeps no such counters, as before they were added
    assert _read("prefill_pad_share", _ctx(_window())) is None
    idle = _counters(prefill_tokens=(5, 5), prefill_positions=(8, 8),
                     prefill_calls=(1, 1))
    assert _read("prefill_pad_share",
                 _ctx(_window(start=idle, end=idle))) is None


def test_roofline_is_least_time_over_device_time():
    least = sum(_least_s(n) for n in PROMPTS)
    calls = 2 * len(PROMPTS)
    at_least = _read("prefill_roofline", _ctx(_window(),
                                              _trace(least, calls)))
    assert at_least == pytest.approx(100.0)
    assert at_least <= 100.0 + 1e-9
    assert _read("prefill_roofline",
                 _ctx(_window(), _trace(4 * least, calls))) == \
        pytest.approx(25.0)
    # the named calls' time is scaled to the dispatches counted: 19 of 20
    # named, and each as long as the least time of a 20th of the work
    assert _read("prefill_roofline",
                 _ctx(_window(end=_counters(prefill_calls=(10, 10))),
                      _trace(least * 19 / 20, 19))) == pytest.approx(100.0)


def test_roofline_reads_nothing_under_coverage_or_without_peaks():
    least = sum(_least_s(n) for n in PROMPTS)
    calls = 2 * len(PROMPTS)
    short = int(calls * measure.COVERAGE) - 1
    assert short / calls < measure.COVERAGE
    assert _read("prefill_roofline",
                 _ctx(_window(), _trace(least, short))) is None
    assert _read("prefill_roofline", _ctx(_window())) is None
    assert _read("prefill_roofline", _ctx(_window(), _trace(least, calls),
                                          device_peaks=None)) is None
    # no prompt had its first token inside the window
    late = _window(first_token_s=11.0)
    assert _read("prefill_roofline", _ctx(late, _trace(least, calls))) \
        is None
