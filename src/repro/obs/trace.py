"""Causal spans: one tree per client session, allocation-cheap emission.

The data plane moves one envelope per decode *step*; at thousands of
tokens/s any per-span allocation (a dict, a dataclass, a list append that
reallocates) shows up in the tokens/s A/B. The :class:`Tracer` therefore
preallocates a ring of reusable slot lists and mutates them in place —
recording a span is eight item stores and one index increment, no object
churn. The ring is a *recorder*, not a queue: readers (``spans()``,
``summary()``, artifact writers) materialize dicts on demand, off the hot
path.

Causality is carried by :class:`TraceContext` — ``(trace_id, span_id,
parent_id)`` — stamped on every envelope. The *client* ``generate()`` loop
owns the root context, so the tree survives the session-id changes a
re-prefill causes: PREFILL on the original replica, the RETRY bounce, the
re-prefill under a fresh session id, and the resumed decode all parent back
to the same root.

Sampling (fleet scale): default-on full tracing is the right debugging
default at smoke scale, but at 10k+ concurrent sessions every session
tree churns the ring and the interesting traces (failures, heals, tail
outliers) are overwritten by thousands of boring ones. ``sample_rate``
adds *head sampling with tail-based keep rules*: the keep/drop decision
is minted once at the session root (children inherit it through the
context, across worlds), but an unsampled trace is not discarded
outright — its spans buffer in a small bounded staging area and the trace
is promoted to the ring anyway if it turns out interesting: any span of a
``keep_kinds`` kind (heal/migrate/restore/reprefill by default), any span
whose detail marks an error or RETRY bounce, or any span slower than
``slow_keep_s``. Boring unsampled traces are dropped wholesale when their
root span closes. Tracing cost therefore stays ~flat as sessions grow:
the ring holds every anomalous trace plus a ``sample_rate`` slice of the
healthy ones.

Ring span taxonomy (the ``kind`` strings the summary aggregates over):

======================  ====================================================
``session``             client root — one per ``generate()`` call
``prefill``             stage-side prefill dispatch (KV-cache build)
``ttft``                client-observed prefill round trip (first token)
``decode``              one session's stage-side decode step, from its
                        arrival in the replica's inbox to its forward
``decode_step``         client-observed per-token round trip
``handoff``             prefill→decode pool KV streaming + install
``snapshot``            one background snapshot write (base or delta)
``migrate``             live drain/heal session migration
``restore``             snapshot fetch + install after a kill
``restore_replay``      client-side suffix replay after a restore
``reprefill``           client-side full-history re-prefill (fallback path)
``bootstrap``           warm scale-up (weight fetch + compile warmup)
``heal``                controller heal of one failed replica
======================  ====================================================

Device-clock mirror. While a JAX profiler session records, every span
opened with :meth:`Tracer.open` is also a ``jax.profiler.TraceAnnotation``
named ``mw.<layer>.<what>``, so the profile holds the program's host path
on the host plane beside the device's operations, on one clock. Its stats
carry ``trace_id``, ``span_id``, ``parent_id``, ``stage``, ``worker`` and,
for executor calls and their dispatches, ``width`` (the sessions in the
call). With no profile recording the mirror costs one ``is_enabled()``
check a span. The layer spans go to the mirror and to counters kept by the
code that does the work, not into the ring:

========================  ==================================================
``mw.client.session``     ``generate()`` entry → return (ring ``session``)
``mw.client.step``        envelope sent → response in hand (ring ``ttft``,
                          ``decode_step`` or ``verify_step``)
``mw.client.token``       response in hand → token appended: the logits'
                          copy to the host (which waits for the last
                          stage's program), margin, argmax
``mw.replica.queue``      envelope put in a replica's inbox → taken by a
                          handler (every envelope, convoy mates too)
``mw.replica.gather``     decode handler start → convoy submitted
``mw.replica.dispatch``   executor call submitted → coroutine resumed
``mw.exec.<call>``        the executor call on its worker thread
                          (``prefill``, ``decode``, ``decode_many``, ...)
``mw.replica.forward``    result sent on to the next stage or the client
========================  ==================================================
"""
from __future__ import annotations

import itertools
import random
import time
from collections import OrderedDict, deque
from typing import Iterable, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "TraceContext", "Tracer", "connected_tree",
           "DEFAULT_KEEP_KINDS"]

#: span kinds that always promote an unsampled trace to the ring — the
#: control-plane incidents an operator reconstructs after the fact
DEFAULT_KEEP_KINDS = frozenset({
    "heal", "migrate", "restore", "restore_replay", "reprefill",
})


class TraceContext:
    """Identity of one span: which tree, which node, which parent.

    Immutable by convention; 0 is the nil parent (roots). Rides on
    ``Envelope.trace`` and crosses worlds by value — three ints and the
    head-sampling verdict, no references into the emitting process.
    ``sampled=False`` marks a trace whose spans stage in the tail-keep
    buffer instead of the ring (children inherit the verdict, so one
    decision at the session root governs the whole tree fleet-wide).
    """

    __slots__ = ("trace_id", "span_id", "parent_id", "sampled")

    def __init__(self, trace_id: int, span_id: int, parent_id: int = 0,
                 sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = sampled

    def __repr__(self) -> str:  # debugging only — never on the hot path
        return (f"TraceContext(trace={self.trace_id}, span={self.span_id}, "
                f"parent={self.parent_id}"
                + ("" if self.sampled else ", unsampled") + ")")


class Span:
    """One span opened by :meth:`Tracer.open` and ended by
    :meth:`Tracer.close`. ``t0`` is its monotonic start; ``ctx`` is its own
    context (the parent of its children), or None where it has none: a
    disabled tracer, or a layer span with no traced parent."""

    __slots__ = ("t0", "ctx", "kind", "worker", "_ann")

    def __init__(self, t0: float, ctx: Optional[TraceContext], kind: str,
                 worker: str, ann: Optional[TraceAnnotation]):
        self.t0 = t0
        self.ctx = ctx
        self.kind = kind
        self.worker = worker
        self._ann = ann


# ring slot field offsets (one preallocated list per slot, mutated in place)
_TRACE, _SPAN, _PARENT, _KIND, _WORKER, _T0, _DT, _DETAIL = range(8)


class Tracer:
    """Preallocated span ring. Default-on; ``enabled=False`` turns every
    emission into a cheap early-return so the overhead A/B has a true
    baseline. ``sample_rate < 1.0`` head-samples session roots, with
    tail-based keep rules promoting anomalous unsampled traces (see the
    module docstring)."""

    def __init__(self, capacity: int = 32768, *, enabled: bool = True,
                 sample_rate: float = 1.0,
                 keep_kinds: frozenset = DEFAULT_KEEP_KINDS,
                 slow_keep_s: Optional[float] = None,
                 max_pending_traces: int = 4096,
                 pending_cap: int = 256,
                 seed: int = 0):
        self.enabled = enabled
        self.capacity = capacity
        # one reusable 8-field slot per ring position; item stores only
        self._ring = [[0, 0, 0, "", "", 0.0, 0.0, ""]
                      for _ in range(capacity)]
        self._head = 0          # next slot to overwrite
        self._count = 0         # slots holding live data (<= capacity)
        self.recorded = 0       # spans ever recorded into the ring
        self.dropped = 0        # spans overwritten before being read
        self._ids = itertools.count(1)
        # -- head sampling + tail keep ----------------------------------
        self.sample_rate = sample_rate
        self.keep_kinds = frozenset(keep_kinds)
        self.slow_keep_s = slow_keep_s
        self.max_pending_traces = max_pending_traces
        self.pending_cap = pending_cap
        self._rng = random.Random(seed)
        #: undecided unsampled traces: trace_id -> [keep_flag, spans]
        self._pending: OrderedDict[int, list] = OrderedDict()
        #: recent verdicts for traces whose root already closed, so late
        #: spans (background snapshots, stragglers) of a kept trace still
        #: reach the ring; bounded FIFO
        self._resolved: dict[int, bool] = {}
        self._resolved_order: deque = deque()
        self.sampled_out = 0    # boring unsampled traces discarded
        self.tail_kept = 0      # unsampled traces promoted by a keep rule

    # ------------------------------------------------------------ contexts
    def begin(self, parent: Optional[TraceContext] = None
              ) -> Optional[TraceContext]:
        """Mint a child context (or a root when ``parent`` is None).
        Returns None when disabled so call sites pay one attribute load.
        The head-sampling verdict is decided here, once per root."""
        if not self.enabled:
            return None
        sid = next(self._ids)
        if parent is None:
            sampled = (self.sample_rate >= 1.0
                       or self._rng.random() < self.sample_rate)
            return TraceContext(sid, sid, 0, sampled)
        return TraceContext(parent.trace_id, sid, parent.span_id,
                            parent.sampled)

    # ------------------------------------------------------------ emission
    def record(self, ctx: Optional[TraceContext], kind: str, t0: float,
               dt: float, worker: str = "", detail: str = "") -> None:
        """Store one completed span. No-op on a None context (disabled
        tracer, or an envelope minted before tracing was on). Spans of an
        unsampled trace stage in the tail-keep buffer instead."""
        if ctx is None or not self.enabled:
            return
        if not ctx.sampled:
            self._record_unsampled(ctx, kind, t0, dt, worker, detail)
            return
        self._store(ctx.trace_id, ctx.span_id, ctx.parent_id, kind,
                    worker, t0, dt, detail)

    def _store(self, trace_id: int, span_id: int, parent_id: int,
               kind: str, worker: str, t0: float, dt: float,
               detail: str) -> None:
        slot = self._ring[self._head]
        slot[_TRACE] = trace_id
        slot[_SPAN] = span_id
        slot[_PARENT] = parent_id
        slot[_KIND] = kind
        slot[_WORKER] = worker
        slot[_T0] = t0
        slot[_DT] = dt
        slot[_DETAIL] = detail
        self._head = (self._head + 1) % self.capacity
        if self._count < self.capacity:
            self._count += 1
        else:
            self.dropped += 1
        self.recorded += 1

    # ------------------------------------------------- tail-based sampling
    def _keep_worthy(self, kind: str, dt: float, detail: str) -> bool:
        """Tail keep rules: incident span kinds, error/RETRY details, and
        slow outliers always survive head sampling."""
        if kind in self.keep_kinds:
            return True
        if self.slow_keep_s is not None and dt >= self.slow_keep_s:
            return True
        return "error" in detail or "retry" in detail

    def _record_unsampled(self, ctx: TraceContext, kind: str, t0: float,
                          dt: float, worker: str, detail: str) -> None:
        tid = ctx.trace_id
        verdict = self._resolved.get(tid)
        if verdict is not None:
            if verdict:     # late span of a tail-kept trace: straight in
                self._store(tid, ctx.span_id, ctx.parent_id, kind,
                            worker, t0, dt, detail)
            return
        ent = self._pending.get(tid)
        if ent is None:
            if len(self._pending) >= self.max_pending_traces:
                # decide the oldest undecided trace with what it has —
                # the staging area is bounded, never a leak
                old_tid, old = self._pending.popitem(last=False)
                self._finish_pending(old_tid, old)
            ent = [False, []]           # [keep_flag, spans]
            self._pending[tid] = ent
        if len(ent[1]) < self.pending_cap:
            ent[1].append((tid, ctx.span_id, ctx.parent_id, kind,
                           worker, t0, dt, detail))
        if not ent[0] and self._keep_worthy(kind, dt, detail):
            ent[0] = True
        if ctx.parent_id == 0:          # root closed: decide the tree
            self._pending.pop(tid, None)
            self._finish_pending(tid, ent)

    def _finish_pending(self, tid: int, ent: list) -> None:
        keep, spans = ent
        if keep:
            self.tail_kept += 1
            for s in spans:
                self._store(*s)
        else:
            self.sampled_out += 1
        self._resolved[tid] = keep
        self._resolved_order.append(tid)
        while len(self._resolved_order) > 4096:
            self._resolved.pop(self._resolved_order.popleft(), None)

    # ------------------------------------------------------- open / close
    def open(self, name: str, parent: Optional[TraceContext] = None, *,
             kind: str = "", worker: str = "", stage: int = -1,
             width: int = 0) -> Span:
        """Start a span now, where the work starts. While a JAX profile
        records it is also the annotation ``name`` (``mw.<layer>.<what>``)
        on the profile's clock; with ``kind`` it goes into the ring at
        :meth:`close`, as a child of ``parent`` (a root when ``parent`` is
        None). The returned span always carries its start, so callers keep
        their counters from :meth:`close` whether tracing is on or not.
        Safe from worker threads when ``kind`` is empty."""
        t0 = time.monotonic()
        if not self.enabled:
            return Span(t0, None, "", worker, None)
        mirror = TraceAnnotation.is_enabled()
        ctx = None
        if kind or (mirror and parent is not None):
            ctx = self.begin(parent)
        ann = None
        if mirror:
            stats = {"stage": stage, "worker": worker}
            if ctx is not None:
                stats.update(trace_id=ctx.trace_id, span_id=ctx.span_id,
                             parent_id=ctx.parent_id)
            if width:
                stats["width"] = width
            ann = TraceAnnotation(name, **stats)
            ann.__enter__()
        return Span(t0, ctx, kind, worker, ann)

    def close(self, span: Span, detail: str = "") -> float:
        """End ``span`` where the work ends; returns its seconds. Records
        it into the ring when it was opened with a ``kind``."""
        dt = time.monotonic() - span.t0
        if span._ann is not None:
            span._ann.__exit__(None, None, None)
            span._ann = None
        if span.kind:
            self.record(span.ctx, span.kind, span.t0, dt, span.worker, detail)
        return dt

    def span(self, parent: Optional[TraceContext], kind: str, t0: float,
             worker: str = "", detail: str = "") -> Optional[TraceContext]:
        """Mint a child of ``parent`` and record it closed at now-t0 in one
        call — the common shape for stage-side work that is already done.
        No-op on a None parent: an untraced envelope must not spawn an
        orphan root (roots are minted explicitly via ``begin()``)."""
        if parent is None or not self.enabled:
            return None
        ctx = self.begin(parent)
        self.record(ctx, kind, t0, time.monotonic() - t0, worker, detail)
        return ctx

    # -------------------------------------------------------------- readers
    def _live_slots(self):
        if self._count < self.capacity:
            return self._ring[:self._count]
        # full ring: oldest live slot is at _head
        return self._ring[self._head:] + self._ring[:self._head]

    def spans(self, trace_id: Optional[int] = None) -> list[dict]:
        """Materialize spans as dicts (oldest first), optionally filtered
        to one tree. Reader-side cost only."""
        out = []
        for s in self._live_slots():
            if trace_id is not None and s[_TRACE] != trace_id:
                continue
            out.append({
                "trace_id": s[_TRACE], "span_id": s[_SPAN],
                "parent_id": s[_PARENT], "kind": s[_KIND],
                "worker": s[_WORKER], "t0": s[_T0], "dt": s[_DT],
                "detail": s[_DETAIL],
            })
        return out

    def summary(self) -> dict:
        """Per-kind latency digests over the live ring:
        ``{kind: {count, mean_s, p50_s, p95_s, max_s}}``."""
        by_kind: dict[str, list[float]] = {}
        for s in self._live_slots():
            by_kind.setdefault(s[_KIND], []).append(s[_DT])
        out: dict = {}
        for kind, xs in by_kind.items():
            xs.sort()
            n = len(xs)
            out[kind] = {
                "count": n,
                "mean_s": sum(xs) / n,
                "p50_s": xs[n // 2],
                "p95_s": xs[min(n - 1, int(n * 0.95))],
                "max_s": xs[-1],
            }
        return out

    def clear(self) -> None:
        self._head = 0
        self._count = 0
        self._pending.clear()
        self._resolved.clear()
        self._resolved_order.clear()


def connected_tree(spans: Iterable[dict]) -> bool:
    """True iff ``spans`` form exactly one tree: a single root
    (parent_id == 0) and every other span's parent present in the set.
    The acceptance check for 'no orphan spans, parent links intact'."""
    spans = list(spans)
    if not spans:
        return False
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if s["parent_id"] == 0]
    if len(roots) != 1:
        return False
    return all(s["parent_id"] in ids for s in spans
               if s["parent_id"] != 0)
