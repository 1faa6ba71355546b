"""The traffic generator: deterministic from the seed, the same work for
every seed, arrivals inside the window."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import traffic as T  # noqa: E402
from tests_support import TINY_MIX  # noqa: E402

BIG_SEED = 2**31 + 12345


def _key(reqs):
    return [(r.due_s, r.out_len, r.prompt.tobytes()) for r in reqs]


def test_schedule_is_deterministic_from_the_seed():
    a = T.schedule(TINY_MIX, 10.0, BIG_SEED, 256)
    b = T.schedule(TINY_MIX, 10.0, BIG_SEED, 256)
    assert _key(a) == _key(b)
    c = T.schedule(TINY_MIX, 10.0, BIG_SEED + 1, 256)
    assert _key(a) != _key(c)


def test_every_seed_offers_the_same_sizes_and_gaps_in_another_order():
    a = T.schedule(TINY_MIX, 10.0, 1, 256)
    b = T.schedule(TINY_MIX, 10.0, 2, 256)
    assert len(a) == len(b) == T.count(TINY_MIX, 10.0) == 40
    size = lambda reqs: sorted((len(r.prompt), r.out_len) for r in reqs)  # noqa
    assert size(a) == size(b)
    gaps = lambda reqs: sorted(np.diff([0.0] + [r.due_s for r in reqs]))  # noqa
    assert np.allclose(gaps(a), gaps(b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_arrivals_fall_due_inside_the_window_in_order():
    reqs = T.schedule(TINY_MIX, 10.0, 3, 256)
    due = [r.due_s for r in reqs]
    assert due == sorted(due)
    assert 0 < due[0] and due[-1] <= 0.98 * 10.0 + 1e-9


def test_lengths_and_tokens_stay_in_their_ranges():
    reqs = T.schedule(TINY_MIX, 10.0, 4, 256)
    p, o = TINY_MIX["prompt_len"], TINY_MIX["output_len"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.out_len <= o["max"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 256
    assert T.prompt_lengths(TINY_MIX, 10.0) == sorted(
        {len(r.prompt) for r in reqs})
