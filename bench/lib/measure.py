"""Arithmetic the metric readers share: percentiles, and the latencies,
tokens and positions of a window, all on the client's clock.

Every request falls due inside the window. Time to first token runs from
the moment a request fell due. A request with no first token when the
window closes counts with the time it had waited by then; a failed request
counts as missing every limit (infinitely late).
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return float(xs[k])


def rel_times(window, r) -> list[float]:
    return [t - window.t0 for t in r.times]


def ttfts(window) -> list[float]:
    out = []
    for r in window.records:
        times = rel_times(window, r)
        if times and times[0] <= window.seconds:
            out.append(times[0] - r.req.due_s)
        elif r.error is not None:
            out.append(math.inf)
        else:
            out.append(window.seconds - r.req.due_s)
    return out


def itls(window) -> list[float]:
    """Gaps between consecutive tokens of a request that ended inside the
    window."""
    out = []
    for r in window.records:
        times = rel_times(window, r)
        out += [b - a for a, b in zip(times, times[1:])
                if b <= window.seconds]
    return out


def tokens_in_window(window) -> int:
    return sum(1 for r in window.records for t in rel_times(window, r)
               if 0.0 <= t <= window.seconds)


def decode_positions(window) -> list[int]:
    """Cache position of every decode step whose token came inside the
    window (every token after a request's first is one decode step)."""
    out = []
    for r in window.records:
        s = len(r.req.prompt)
        for j, t in enumerate(rel_times(window, r)):
            if j > 0 and 0.0 <= t <= window.seconds:
                out.append(s + j - 1)
    return out


def prompts_prefilled(window) -> list[int]:
    """Prompt lengths of requests whose first token came inside the
    window."""
    return [len(r.req.prompt) for r in window.records
            if r.times and 0.0 <= r.times[0] - window.t0 <= window.seconds]


def counter_delta(window, key: str) -> int:
    """A stage executor counter's change over the window, all stages."""
    a, b = window.counters_start, window.counters_end
    return int(sum(e1[key] - e0[key]
                   for e0, e1 in zip(a["executors"], b["executors"])))


def stage_deltas(window, key: str) -> list[int]:
    a, b = window.counters_start, window.counters_end
    return [int(e1[key] - e0[key])
            for e0, e1 in zip(a["executors"], b["executors"])]


#: the trace has to name between this share of the stage dispatches the
#: executors counted over the window and its inverse; the calls on either
#: side of the window's edges make up the difference
COVERAGE = 0.95
COUNTER = {"prefill": "prefill_calls", "decode": "decode_batches"}


def labelled(ctx, kind: str) -> dict | None:
    """Device seconds and calls of the traced window's ``kind`` programs
    (``prefill`` or ``decode``, every stage) beside the dispatches the
    executors counted; None where the trace names too few or too many of
    them, so no reader builds on programs the trace failed to name."""
    if ctx.trace is None:
        return None
    v = ctx.trace["by_label"].get(kind)
    counted = counter_delta(ctx.window, COUNTER[kind])
    if not v or not counted:
        return None
    if not COVERAGE <= v["calls"] / counted <= 1.0 / COVERAGE:
        return None
    return {"s": v["s"], "calls": v["calls"], "counted": counted}


def model_flops(arch, sizes: dict, window) -> float:
    """Operations the window's tokens require: every prompt prefilled in
    the window (real positions, last-position logits) and every decode
    step, as the layer module ``arch`` counts them; work redone after a
    failure is not counted."""
    return (sum(arch.prefill_flops(sizes, s)
                for s in prompts_prefilled(window))
            + sum(arch.decode_flops(sizes, p)
                  for p in decode_positions(window)))
