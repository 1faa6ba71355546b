"""The program's own host-path counters and ``mw.*`` spans, for the
per-layer metrics that read them.

* :func:`counters` gives the groups a window's counter snapshot holds beside
  ``executors``: ``replicas`` (each replica's decode counters and sums from
  ``PipelineServer.replica_stats``, by worker id) and ``client``
  (``PipelineServer.client_stats``). A program that keeps no such sums
  gives groups without them, and their readers read nothing.
* :func:`reduce` splits a traced window's device idle time by the ``mw.*``
  host span open over it (:func:`idle_by_span`). It needs only the names
  and times of the events ``lib.trace.load`` gives.
"""
from __future__ import annotations

from collections import defaultdict

from . import trace as TR

#: span name prefixes from the lowest layer to the highest
LAYERS = ("mw.exec.", "mw.replica.dispatch", "mw.replica.",
          "mw.client.token", "mw.client.step", "mw.client.session")
SESSION = len(LAYERS) - 1
NO_REQUEST = "no open request"
#: what :func:`counters` keeps of each replica's ``replica_stats`` entry
REPLICA_KEYS = ("decode_steps", "decode_batches", "decode_wait_s_sum",
                "dispatch_s_sum", "exec_s_sum", "polls_empty")


def counters(server) -> dict:
    """The host-path counter groups of ``server``, as plain numbers."""
    client = getattr(server, "client_stats", None)
    return {"replicas": {wid: {k: st[k] for k in REPLICA_KEYS if k in st}
                         for wid, st in server.replica_stats().items()},
            "client": client() if callable(client) else {}}


def replica_delta(window, key: str) -> float | None:
    """A replica counter's change over the window, summed over every
    replica of every stage; None where the snapshot has no such counter."""
    a = window.counters_start.get("replicas", {})
    b = window.counters_end.get("replicas", {})
    if not b or not all(key in st for st in b.values()):
        return None
    return sum(st[key] - a.get(wid, {}).get(key, 0) for wid, st in b.items())


def client_delta(window, key: str) -> float | None:
    """The change of the client's counter ``key`` over the window; None
    where the snapshot has no such counter."""
    a = window.counters_start.get("client", {})
    b = window.counters_end.get("client", {})
    if key not in a or key not in b:
        return None
    return b[key] - a[key]


def layer(name: str) -> int | None:
    """Index of the span's layer in :data:`LAYERS`; None for any other
    event."""
    for i, prefix in enumerate(LAYERS):
        if name.startswith(prefix):
            return i
    return None


def _device_busy(events: list[dict], lo: float, hi: float) -> list | None:
    """Busy intervals of the first device with operations in [lo, hi],
    the device whose gaps ``lib.trace.reduce`` names."""
    dev = [e for e in events if TR.is_device(e["plane"])]
    line = (TR.OPS_LINE if any(e["line"] == TR.OPS_LINE for e in dev)
            else TR.MODULES_LINE)
    by_plane: dict[str, list] = defaultdict(list)
    for e in dev:
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if e["line"] == line and a < hi and b > lo:
            by_plane[e["plane"]].append((max(a, lo), min(b, hi)))
    planes = sorted(by_plane)
    return by_plane[planes[0]] if planes else None


def idle_by_span(events: list[dict]) -> dict[str, float] | None:
    """Device idle seconds of the traced window (``bench:window``) by the
    ``mw.*`` span open over them. Every idle instant goes to one name:
    ``no open request`` where no ``mw.client.session`` is open; else the
    open span of the lowest layer (exec, dispatch, replica, client token,
    client step, session, as :data:`LAYERS` lists them), and of several
    open spans of that layer, the one opened last. The values sum to the
    window's idle time. None without a window or a device operation in
    it."""
    win = TR.window(events)
    if win is None:
        return None
    lo, hi = win
    busy = _device_busy(events, lo, hi)
    if busy is None:
        return None
    marks = []                       # (t, 0 close | 1 open, span index)
    spans = []                       # (layer, start, name)
    for e in events:
        i = None if TR.is_device(e["plane"]) else layer(e["name"])
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if i is None or b <= lo or a >= hi:
            continue
        spans.append((i, a, e["name"]))
        marks += [(a, 1, len(spans) - 1), (b, 0, len(spans) - 1)]
    marks.sort()
    open_by_layer: list[dict[int, float]] = [{} for _ in LAYERS]

    def holder() -> str:
        if not open_by_layer[SESSION]:
            return NO_REQUEST
        opened = next(o for o in open_by_layer if o)
        return spans[max(opened, key=opened.get)][2]

    out: dict[str, float] = defaultdict(float)
    k = 0
    for a, b in TR.gaps(busy, lo, hi):
        t = a
        while k < len(marks) and marks[k][0] <= t:
            _apply(marks[k], spans, open_by_layer)
            k += 1
        while k < len(marks) and marks[k][0] < b:
            out[holder()] += (marks[k][0] - t) / 1e9
            t = marks[k][0]
            _apply(marks[k], spans, open_by_layer)
            k += 1
        out[holder()] += (b - t) / 1e9
    return dict(sorted(((name, s) for name, s in out.items() if s > 0),
                       key=lambda kv: -kv[1]))


def _apply(mark, spans, open_by_layer) -> None:
    _, opens, idx = mark
    i, start, _ = spans[idx]
    if opens:
        open_by_layer[i][idx] = start
    else:
        open_by_layer[i].pop(idx, None)


def reduce(events: list[dict]) -> dict:
    """What a traced run adds to ``lib.trace.reduce``'s result:
    ``idle_by_span`` as ``[[name, seconds], ...]``, largest first; empty
    where :func:`idle_by_span` finds nothing."""
    split = idle_by_span(events)
    return {"idle_by_span": [[k, v] for k, v in (split or {}).items()]}
