"""What a metric reader is given."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Context:
    cell: str
    cfg: dict                      # the configuration file
    arch: object                   # its layer module, arch/<name>.py
    sizes: dict                    # arch.sizes(cfg)
    stages: int
    mix: dict                      # the traffic mix file
    window: object                 # serve.Window
    trace: Optional[dict]          # trace.reduce(...) of a traced run
    peaks: Optional[dict]          # peaks.peaks(device_kind)
    setup_s: float
