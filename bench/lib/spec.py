"""Find a cell, its configuration, its layer module, its traffic mix and its
metric readers by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, architecture, mix or metric
sits in a file of its own, so adding one is adding a file:

* ``<bench>/configs/<config>.json`` (the path is the cell's ``file``),
  which names its layer module under ``bench_arch``,
* ``<bench>/arch/<bench_arch>.py``, the one place that knows the layer:
  ``sizes(cfg)`` and, of those sizes ``s``, ``layer_leaves(s, layer)``,
  ``global_leaves(s)``, ``pack(s, layers, globals)`` (the program's
  parameter tree), ``program_config(cfg, s)``, the reference's
  ``layer(x, p, index, s, cfg, fp8)`` (``index`` traced) and
  ``head(x, globals, s, cfg, fp8)``, and the counts ``layer_params``,
  ``prefill_flops``, ``decode_flops``, ``decode_stage_flops``,
  ``stage_weight_bytes``, ``decode_kv_bytes``, ``decode_bytes``,
* ``<bench>/traffic/<traffic>.json``,
* ``<bench>/metrics/<metric>.py``, which defines ``read(ctx)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _name(value: str, what: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise SpecError(f"bad {what} name {value!r}")
    return value


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                       # "end_to_end" | "per_layer"
    workloads: tuple[str, ...] | None
    bound: float | None = None
    layer: str | None = None
    moves: str | None = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    config_file: str                # absolute path
    traffic_file: str               # absolute path


class Benchmark:
    """The parsed ``BENCHMARK.json`` of a checkout rooted at ``root``."""

    def __init__(self, root: str, spec: dict | None = None,
                 bench_dir: str | None = None) -> None:
        self.root = root
        if spec is None:
            with open(os.path.join(root, "BENCHMARK.json")) as f:
                spec = json.load(f)
        self.spec = spec
        self.bench_dir = bench_dir or os.path.join(root, spec["paths"][0])
        self.run_seconds = int(spec["run_seconds"])
        self.configs = {_name(c["name"], "config"): c for c in spec["configs"]}
        self.metrics: dict[str, Metric] = {}
        for kind in ("end_to_end", "per_layer"):
            for m in spec[kind]:
                name = _name(m["name"], "metric")
                if name in self.metrics:
                    raise SpecError(f"metric {name} named twice")
                wl = m.get("workloads")
                self.metrics[name] = Metric(
                    name=name, unit=m["unit"], better=m["better"],
                    source=m["source"], kind=kind,
                    workloads=None if wl is None else tuple(wl),
                    bound=m.get("bound"), layer=m.get("layer"),
                    moves=m.get("moves"))
        self.cells = {}
        for w in spec["workloads"]:
            name = _name(w["name"], "workload")
            cfg = self.configs.get(w["config"])
            if cfg is None:
                raise SpecError(f"cell {name}: unknown config {w['config']}")
            traffic = _name(w["traffic"], "traffic")
            self.cells[name] = Cell(
                name=name, config=w["config"], traffic=traffic,
                chips=int(w["chips"]),
                config_file=os.path.join(root, cfg["file"]),
                traffic_file=os.path.join(self.bench_dir, "traffic",
                                          traffic + ".json"))

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise SpecError(f"unknown workload {name!r}; known: "
                            f"{sorted(self.cells)}")
        return self.cells[name]

    def metrics_for(self, cell: str, kind: str) -> list[Metric]:
        return [m for m in self.metrics.values()
                if m.kind == kind and m.applies_to(cell)]

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
        return self._module("metrics", metric).read

    def arch(self, name: str):
        """The layer module ``arch/<name>.py``."""
        return self._module("arch", _name(name, "arch"))

    def config(self, cell: Cell) -> tuple[dict, object]:
        """A cell's configuration and the layer module it names."""
        cfg = load_json(cell.config_file)
        if "bench_arch" not in cfg:
            raise SpecError(f"{cell.config_file} names no bench_arch")
        return cfg, self.arch(cfg["bench_arch"])

    def _module(self, kind: str, name: str):
        path = os.path.join(self.bench_dir, kind, name + ".py")
        if not os.path.exists(path):
            raise SpecError(f"{kind} {name}: no module at {path}")
        mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def load_json(path: str) -> dict:
    if not os.path.exists(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)
