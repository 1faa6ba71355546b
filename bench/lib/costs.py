"""Operations and bytes the algorithm needs, from shapes and token counts
alone. They count the same work whatever implements it: a paged cache, a
fused kernel or a donated buffer does not change them.

Sizes are those of ``weights.sizes``: d, h, kv, hd, f, v, layers.
A multiply-add counts as two operations.
"""
from __future__ import annotations


def layer_params(s: dict) -> int:
    """Matrix parameters of one dense GQA layer (norms left out)."""
    d, h, kv, hd, f = s["d"], s["h"], s["kv"], s["hd"], s["f"]
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f


def attention_flops(s: dict, layers: int, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs: 4 * heads * head_dim
    per pair per layer."""
    return 4 * s["h"] * s["hd"] * pairs * layers


def prefill_flops(s: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens through every layer, causal attention
    over the real positions only, and logits of the last position only."""
    causal_pairs = prompt * (prompt + 1) // 2
    return (2 * s["layers"] * layer_params(s) * prompt
            + attention_flops(s, s["layers"], causal_pairs)
            + 2 * s["d"] * s["v"])


def decode_flops(s: dict, position: int) -> int:
    """One new token at ``position`` (it attends position + 1 keys)."""
    return (2 * s["layers"] * layer_params(s)
            + attention_flops(s, s["layers"], position + 1)
            + 2 * s["d"] * s["v"])


def stage_layers(s: dict, stages: int) -> list[int]:
    """Layers per pipeline stage, earlier stages taking the remainder."""
    n = s["layers"]
    return [n // stages + (1 if i < n % stages else 0) for i in range(stages)]


def stage_weight_bytes(s: dict, stage: int, stages: int,
                       bytes_per: int = 2) -> int:
    """Weights one decode dispatch of a stage must read once: its layers,
    and on the last stage the final norm and the output head. The
    embedding rows the first stage gathers are counted per token in
    :func:`decode_kv_bytes`'s caller, not here."""
    layers = stage_layers(s, stages)[stage]
    d = s["d"]
    n = layers * (layer_params(s) + 2 * d)
    if s["qk_norm"]:
        n += layers * 2 * s["hd"]
    if stage == stages - 1:
        n += d + d * s["v"]
    return n * bytes_per


def decode_kv_bytes(s: dict, stage: int, stages: int, position: int,
                    bytes_per: int = 2) -> int:
    """Cache bytes one session's decode step at ``position`` needs on a
    stage: K and V of positions 0..position read, and one position of each
    written."""
    layers = stage_layers(s, stages)[stage]
    per_pos = 2 * layers * s["kv"] * s["hd"] * bytes_per
    return per_pos * (position + 1) + per_pos


def decode_bytes(s: dict, stage: int, stages: int,
                 positions: list[int], bytes_per: int = 2) -> int:
    """Least bytes of one decode dispatch over sessions at ``positions``."""
    n = stage_weight_bytes(s, stage, stages, bytes_per)
    n += sum(decode_kv_bytes(s, stage, stages, p, bytes_per)
             for p in positions)
    if stage == 0:
        n += len(positions) * s["d"] * bytes_per     # embedding rows
    return n
