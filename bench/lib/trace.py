"""Reduce a profiler trace to device busy time, idle gaps and device time
per program.

The trace is read with ``jax.profiler.ProfileData`` into plain event dicts
(``plane``, ``line``, ``name``, ``start_ns``, ``dur_ns``, ``stats``), so
the reduction can be checked on a small recorded fixture.

* Device events are those of the planes named ``/device:<KIND>:<n>``. Busy
  time is the union of the intervals of the per-operation line (``XLA Ops``)
  inside the window, idle gaps its complement.
* Programs are the events of the per-program line (``XLA Modules``), keyed by
  name (on the TPU the name carries the program's id) and, where the event
  carries one, a ``program_id`` stat. The program names each jitted stage
  call ``s<stage>_<call>``, which XLA takes for the module's name; the
  kind of a program (its label) is read from that name (:func:`kind`):
  ``prefill``, or ``decode`` for a single step and a convoy, every stage
  together. Any other program has no kind.
* The window is the host annotation ``bench:window``.
* Each idle gap is named by the shortest host event that spans its middle:
  what the host was doing while the device waited.
"""
from __future__ import annotations

import glob
import os
import re
import warnings
from collections import defaultdict

WINDOW = "bench:window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a stage program's module name, ``jit_s<stage>_<call>``, then the id or
#: nothing; the calls that prefill and that decode
STAGE_PROGRAM = re.compile(
    r"(?<![A-Za-z0-9])jit_s\d+_(prefill|decode|decode_many|decode_paged)"
    r"(?![A-Za-z])")


def _stats(event) -> dict:
    out = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            for k, v in event.stats:
                out[str(k)] = v
        except (TypeError, ValueError):
            pass
    return out


def load(log_dir: str) -> list[dict]:
    """Every event of the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    events = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                events.append({"plane": plane.name, "line": line.name,
                               "name": e.name, "start_ns": float(e.start_ns),
                               "dur_ns": float(e.duration_ns),
                               "stats": (_stats(e)
                                         if line.name == MODULES_LINE
                                         else {})})
    return events


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def program_key(e: dict) -> str:
    pid = e["stats"].get("program_id")
    return e["name"] if pid is None else f"{e['name']}#{pid}"


def union_ns(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _clip(e: dict, lo: float, hi: float) -> tuple[float, float] | None:
    a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
    return (a, b) if b > a else None


def window(events: list[dict]) -> tuple[float, float] | None:
    for e in events:
        if e["name"] == WINDOW and not is_device(e["plane"]):
            return e["start_ns"], e["start_ns"] + e["dur_ns"]
    return None


def kind(name: str) -> str | None:
    """``prefill`` or ``decode`` for a stage program's module name, else
    None."""
    m = STAGE_PROGRAM.search(name)
    if m is None:
        return None
    return "prefill" if m.group(1) == "prefill" else "decode"


def reduce(events: list[dict], top: int = 10) -> dict | None:
    """Busy and idle time in the window, device time per program key and
    per label, and the longest idle gaps. None if the trace has no window
    or no device event in it."""
    win = window(events)
    if win is None:
        return None
    lo, hi = win
    dev = [e for e in events if is_device(e["plane"])]
    planes = sorted({e["plane"] for e in dev})
    ops_line = OPS_LINE if any(e["line"] == OPS_LINE for e in dev) \
        else MODULES_LINE
    intervals_by_plane: dict[str, list] = {p: [] for p in planes}
    for e in dev:
        if e["line"] == ops_line:
            c = _clip(e, lo, hi)
            if c:
                intervals_by_plane[e["plane"]].append(c)
    busy_by_plane = {p: union_ns(iv) for p, iv in intervals_by_plane.items()}
    used = [p for p in planes if busy_by_plane[p] > 0]
    if not used:
        return None
    programs: dict[str, dict] = defaultdict(lambda: {"ns": 0.0, "calls": 0})
    by_label: dict[str, dict] = defaultdict(lambda: {"ns": 0.0, "calls": 0})
    for e in dev:
        if e["line"] != MODULES_LINE or not lo <= e["start_ns"] < hi:
            continue
        key = program_key(e)
        programs[key]["ns"] += e["dur_ns"]
        programs[key]["calls"] += 1
        label = kind(e["name"])
        if label is not None:
            by_label[label]["ns"] += e["dur_ns"]
            by_label[label]["calls"] += 1
    # name only the longest gaps: each by one pass over the host events
    longest = sorted(gaps(intervals_by_plane[used[0]], lo, hi),
                     key=lambda g: g[0] - g[1])[:top]
    host = [e for e in events if not is_device(e["plane"])
            and e["name"] != WINDOW and e["start_ns"] <= hi
            and e["start_ns"] + e["dur_ns"] >= lo]
    idle = []
    for a, b in longest:
        mid = (a + b) / 2
        around = [h for h in host
                  if h["start_ns"] <= mid <= h["start_ns"] + h["dur_ns"]]
        what = (min(around, key=lambda h: h["dur_ns"])["name"]
                if around else "no host event")
        idle.append((what, (b - a) / 1e9))
    ranked = sorted(programs.items(), key=lambda kv: -kv[1]["ns"])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_by_plane[p] for p in used) / len(used) / 1e9,
        "devices": len(used),
        "programs": {k: {"s": v["ns"] / 1e9, "calls": v["calls"],
                         "label": kind(k)} for k, v in ranked},
        "by_label": {k: {"s": v["ns"] / 1e9, "calls": v["calls"]}
                     for k, v in by_label.items()},
        "device_ops": [[(kind(k) or "") + ("/" if kind(k) else "") + k,
                        v["ns"] / 1e9] for k, v in ranked[:top]],
        "idle_gaps": [[w, s] for w, s in idle],
    }


def structure(events: list[dict], per_line: int = 3) -> dict:
    """A short summary of planes, lines and sample events, to look at a
    trace by hand."""
    out: dict = {}
    for e in events:
        line = out.setdefault(e["plane"], {}).setdefault(
            e["line"], {"n": 0, "sample": []})
        line["n"] += 1
        if len(line["sample"]) < per_line:
            line["sample"].append({k: e[k] for k in ("name", "start_ns",
                                                     "dur_ns")}
                                  | {"stats": {k: str(v)[:80] for k, v in
                                               e["stats"].items()}})
    return out
