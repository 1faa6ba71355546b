"""Decide ``correct``: compare what the timed path served with the plain
reference.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed, is run through the reference
teacher-forced (prompt plus served tokens). The number compared is the
widest gap by which a served token's logit lies below the reference's best
logit of its row. The sample always holds the longest finished request.

The control puts the reference in float8 (``lib.reference``) in the
program's place: at each row of the same sample it reads the gap of the
token the lower precision puts first. A run asked for the control compares
that reading instead of the program's, so it has to come out not correct.

Every request that fell due must finish with as many tokens as it asked
for; one that failed or never finished is an answer that never came.
"""
from __future__ import annotations

import numpy as np

from .reference import Reference


def sample(window, seed: int, min_tokens: int, max_requests: int) -> list:
    """Finished requests to compare: the longest one, then others in an
    order drawn from the seed until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [r for r in window.records
            if r.out is not None and len(r.out) == r.req.out_len]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0xC4EC])
    longest = max(done, key=lambda r: len(r.req.prompt) + len(r.out))
    chosen = [longest]
    for i in rng.permutation(len(done)):
        if (sum(len(r.out) for r in chosen) >= min_tokens
                or len(chosen) >= max_requests):
            break
        if done[int(i)] not in chosen:
            chosen.append(done[int(i)])
    return chosen


def teacher_forced(records: list) -> tuple[list, list, list]:
    """Sequences fed to the reference, the rows that predict each served
    token, and the served tokens."""
    seqs, rows, served = [], [], []
    for r in records:
        s, out = len(r.req.prompt), np.asarray(r.out, np.int32)
        seqs.append(np.concatenate([r.req.prompt, out[:-1]]).astype(np.int32))
        rows.append(np.arange(s - 1, s - 1 + len(out)))
        served.append(out)
    return seqs, rows, served


def widest_gap(logits: list[np.ndarray], tokens: list[np.ndarray]) -> float:
    """Largest (row max - logit of the token) over every row."""
    worst = 0.0
    for lg, tok in zip(logits, tokens):
        gap = lg.max(-1) - lg[np.arange(len(tok)), tok]
        worst = max(worst, float(gap.max()))
    return worst


def control_gap(ref: list[np.ndarray], low: list[np.ndarray]) -> float:
    """The control's reading: at each row, the gap (in the reference) of
    the token the lower precision puts first."""
    return widest_gap(ref, [lg.argmax(-1) for lg in low])


def served_gap(arch, cfg: dict, seed: int, records: list,
               control: bool = False) -> dict:
    """The program's reading (``gap``) over ``records`` and, with
    ``control``, the control's (``control_gap``); ``arch`` is the
    configuration's layer module."""
    seqs, rows, served = teacher_forced(records)
    ref = Reference(arch, cfg, seed).logits(seqs, rows)
    out = {"gap": widest_gap(ref, served), "requests": len(records),
           "tokens": int(sum(len(t) for t in served)),
           "longest": int(max(len(s) for s in seqs) + 1)}
    if control:
        low = Reference(arch, cfg, seed, "fp8").logits(seqs, rows)
        out["control_gap"] = control_gap(ref, low)
    return out


def checks(cfg: dict, window, sampled: dict, control: bool = False) -> dict:
    """Every number compared, each with its limit; ``correct`` holds iff
    each value is at most its limit. With ``control`` the gap compared is
    the control's."""
    failed = sum(1 for r in window.records if r.error is not None)
    short = sum(1 for r in window.records
                if r.out is not None and len(r.out) != r.req.out_len)
    return {
        "failed_requests": {"value": failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
        "logit_gap": {"value": sampled.get("control_gap" if control
                                           else "gap", float("inf")),
                      "limit": cfg["check"]["logit_gap_limit"]},
    }
