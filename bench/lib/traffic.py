"""Open-loop traffic from a mix file and a seed.

A mix file (``traffic/<mix>.json``) holds parameters only: the arrival rate,
the prompt and output length distributions and the serving layout. This
one generator reads every mix.

Every seed of a mix gets the same set of request sizes and the same set of
gaps between arrivals, drawn once from the mix's ``sizes_seed``; ``--seed``
orders them and draws the prompt tokens. So runs on different seeds offer
the same work in another order, and the set of prompt lengths, and with it
the set of programs to warm up, is the same in every run.

Arrivals are Poisson: N = round(rate x seconds) exponential gaps, scaled so
that the last request falls due inside the window. Each request is timed
from the moment it fell due, not from when the generator got round to
sending it, so a stall shows in the latency of every request it delays.
(Adapted from the program's ``control/workload.py`` ``OpenLoopGenerator``,
which timed from the send and shed requests past ``max_inflight``.)
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float                   # seconds after the window opens
    prompt: np.ndarray             # (S,) int32
    out_len: int


def _lengths(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = rng.lognormal(np.log(dist["median"]), dist["sigma"], n)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_rps"] * seconds)))


def sizes(mix: dict, seconds: float) -> tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
    """The mix's fixed prompt lengths, output lengths and gaps (seconds)
    for a window of ``seconds``: the same for every ``--seed``."""
    n = count(mix, seconds)
    rng = np.random.default_rng(int(mix["sizes_seed"]))
    prompts = _lengths(rng, mix["prompt_len"], n)
    outs = _lengths(rng, mix["output_len"], n)
    gaps = rng.exponential(1.0, n)
    # the n-th arrival falls due at 0.98 of the window
    gaps *= 0.98 * seconds / gaps.sum()
    return prompts, outs, gaps


def schedule(mix: dict, seconds: float, seed: int, vocab: int
             ) -> list[Request]:
    """The requests of one run: the mix's sizes and gaps in an order drawn
    from ``seed``, with prompt tokens uniform over the vocabulary."""
    prompts, outs, gaps = sizes(mix, seconds)
    rng = np.random.default_rng([int(seed), 0x7EAF])
    order = rng.permutation(len(prompts))
    due = np.cumsum(rng.permutation(gaps))
    return [Request(index=i, due_s=float(due[i]),
                    prompt=rng.integers(0, vocab, int(prompts[j]),
                                        dtype=np.int32),
                    out_len=int(outs[j]))
            for i, j in enumerate(order)]


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    return sorted({int(n) for n in sizes(mix, seconds)[0]})

