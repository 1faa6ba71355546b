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
  carries one, a ``program_id`` stat. The benchmark names them itself:
  before the window it sends requests and convoys through the program's
  entry points inside host annotations (``bench:label:...``, see
  :func:`labels`) and takes the program keys of the device events that ran
  inside them. A label is ``prefill`` or ``decode``: the stage programs of
  either kind, every stage together.
* The window is the host annotation ``bench:window``.
* Each idle gap is named by the shortest host event that spans its middle:
  what the host was doing while the device waited.
"""
from __future__ import annotations

import bisect
import glob
import os
import warnings
from collections import defaultdict

WINDOW = "bench:window"
LABEL = "bench:label:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


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


def labels(events: list[dict]) -> dict[str, str]:
    """Program key -> ``prefill`` or ``decode`` from the label annotations.

    ``bench:label:request:<n>`` wraps one request of two tokens through an
    n-stage pipeline: its 2n longest device programs, in order of start,
    are the prefill of each stage and then one decode step of each.
    ``bench:label:decode`` wraps one convoy through one stage: the longest
    device program inside it is that stage's convoy program.
    """
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"],
              e["name"][len(LABEL):])
             for e in events
             if e["name"].startswith(LABEL) and not is_device(e["plane"])]
    modules = sorted((e for e in events if is_device(e["plane"])
                      and e["line"] == MODULES_LINE),
                     key=lambda e: e["start_ns"])
    starts = [e["start_ns"] for e in modules]
    out: dict[str, str] = {}
    for a, b, label in spans:
        inside = modules[bisect.bisect_left(starts, a):
                         bisect.bisect_right(starts, b)]
        if label.startswith("request:"):
            n = int(label.split(":")[1])
            top = sorted(sorted(inside, key=lambda e: -e["dur_ns"])[:2 * n],
                         key=lambda e: e["start_ns"])
            if len(top) != 2 * n:
                continue
            for i, e in enumerate(top):
                out.setdefault(program_key(e),
                               "prefill" if i < n else "decode")
        elif inside:
            longest = max(inside, key=lambda e: e["dur_ns"])
            out.setdefault(program_key(longest), label)
    return out


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
    names = labels(events)
    programs: dict[str, dict] = defaultdict(lambda: {"ns": 0.0, "calls": 0})
    by_label: dict[str, dict] = defaultdict(lambda: {"ns": 0.0, "calls": 0})
    for e in dev:
        if e["line"] != MODULES_LINE or not lo <= e["start_ns"] < hi:
            continue
        key = program_key(e)
        programs[key]["ns"] += e["dur_ns"]
        programs[key]["calls"] += 1
        label = names.get(key)
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
                         "label": names.get(k)} for k, v in ranked},
        "by_label": {k: {"s": v["ns"] / 1e9, "calls": v["calls"]}
                     for k, v in by_label.items()},
        "device_ops": [[(names.get(k) or "") + ("/" if names.get(k) else "")
                        + k, v["ns"] / 1e9] for k, v in ranked[:top]],
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
