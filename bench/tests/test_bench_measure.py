"""Tails are timed from the due time and count unfinished and failed
requests; rates are taken over the whole window."""
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import measure  # noqa: E402
from lib.context import Context  # noqa: E402
from lib.serve import Record, Window  # noqa: E402
from lib.spec import Benchmark  # noqa: E402
from lib.traffic import Request  # noqa: E402
from tests_support import ROOT  # noqa: E402

T0 = 1000.0


def _rec(i, due, times, out_len=3, error=None):
    r = Record(Request(i, due, np.zeros(4, np.int32), out_len))
    r.times = [T0 + t for t in times]
    r.error = error
    if len(times) == out_len and error is None:
        r.out = np.zeros(out_len, np.int32)
    return r


def _window(records, seconds=10.0, steps=0, batches=0, prefills=0):
    z = {"decode_steps": 0, "decode_batches": 0, "prefill_calls": 0}
    end = {"decode_steps": steps, "decode_batches": batches,
           "prefill_calls": prefills}
    return Window(seconds=seconds, records=records,
                  counters_start={"executors": [z]},
                  counters_end={"executors": [end]}, compiles=[],
                  setup_s=1.0, t0=T0)


def _ctx(win, trace=None):
    return Context(cell="c", cfg={}, arch=None, sizes={}, stages=1, mix={},
                   window=win,
                   trace=trace, peaks=None, setup_s=win.setup_s)


def _read(name, win, trace=None):
    return Benchmark(ROOT).reader(name)(_ctx(win, trace))


def test_ttft_runs_from_the_due_time_not_the_send():
    # due at 1.0, first token at 1.5: 0.5 s, however late it was sent
    win = _window([_rec(0, 1.0, [1.5, 1.6, 1.7])])
    win.records[0].sent = 1.4
    assert np.allclose(measure.ttfts(win), [0.5])


def test_unfinished_requests_count_with_their_wait_and_failed_as_missing():
    win = _window([_rec(0, 1.0, [1.2, 1.3, 1.4]),
                   _rec(1, 4.0, []),                   # no token by the close
                   _rec(2, 5.0, [], error="boom")])
    assert np.allclose(measure.ttfts(win)[:2], [0.2, 6.0])
    assert math.isinf(measure.ttfts(win)[2])
    # p90 of three values is the largest: a failure, reported as the window
    assert _read("ttft_p90_ms", win) == 10.0 * 1e3


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert measure.percentile(xs, 90) == 90
    assert measure.percentile(xs, 95) == 95
    assert measure.percentile([3.0], 95) == 3.0


def test_rate_is_over_the_whole_window_and_only_inside_it():
    # 5 tokens inside a 10 s window, one after the close
    win = _window([_rec(0, 1.0, [1.1, 1.2, 1.3]),
                   _rec(1, 9.0, [9.5, 9.9, 10.5])])
    assert measure.tokens_in_window(win) == 5
    assert _read("tokens_per_s", win) == 0.5


def test_inter_token_gaps_inside_the_window():
    win = _window([_rec(0, 1.0, [1.1, 1.3, 1.6]),
                   _rec(1, 9.0, [9.5, 9.9, 10.5])])
    gaps = sorted(measure.itls(win))
    assert np.allclose(gaps, [0.2, 0.3, 0.4])
    assert abs(_read("itl_p95_ms", win) - 400.0) < 1e-6


def test_trace_readers_need_labels_that_cover_the_counted_dispatches():
    # 100 decode dispatches of 300 session steps counted; the trace names
    # 98 of them (the window's edges), then only 50 (a label was missed)
    win = _window([], steps=300, batches=100)
    trace = {"by_label": {"decode": {"s": 0.98, "calls": 98}}}
    # 1 s of device time for 100 dispatches over 300 tokens
    assert abs(_read("decode_ms_per_token", win, trace) - 1e3 / 300) < 1e-9
    trace["by_label"]["decode"]["calls"] = 50
    assert _read("decode_ms_per_token", win, trace) is None
    assert _read("decode_ms_per_token", win) is None
