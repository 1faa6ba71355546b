"""Operations and bytes the algorithm needs are the layer module's
(``arch/<name>.py``: ``layer_params``, ``prefill_flops``, ``decode_flops``,
``decode_stage_flops``, ``stage_weight_bytes``, ``decode_kv_bytes``,
``decode_bytes``), since only it knows the layer. What they share is here.
"""
from __future__ import annotations


def stage_layers(s: dict, stages: int) -> list[int]:
    """Layers per pipeline stage, earlier stages taking the remainder."""
    n = s["layers"]
    return [n // stages + (1 if i < n % stages else 0) for i in range(stages)]
