"""Observability: causal spans + digests + SLOs + flight recorder + export.

This package is dependency-free within the repo (imports nothing from
``core``/``serving``/``control``) so every layer can import it without
cycles. Five pieces:

* :mod:`~repro.obs.trace` — an allocation-cheap :class:`Tracer` whose
  :class:`TraceContext` rides every :class:`~repro.serving.envelope.Envelope`
  so one session's lifecycle (prefill, per-step decode, handoff, snapshot,
  migration, heal, restore replay) reconstructs as one causal tree; head
  sampling with tail-based keep rules bounds its cost at fleet scale; while
  a JAX profile records, its open spans are mirrored into the profile as
  ``mw.*`` annotations on the device trace's clock;
* :mod:`~repro.obs.sketch` — :class:`LogSketch`, a DDSketch-style
  mergeable quantile sketch with a guaranteed relative-error bound, the
  primitive that makes tail latencies (p95 TTFT, p99 decode) foldable
  across the replica → stage → fleet hierarchy;
* :mod:`~repro.obs.digest` — :class:`StageDigest`, a bounded mergeable
  rollup of replica load samples (sums, (sum, n) means, latency sketches)
  that MetricsHub folds hierarchically instead of iterating raw samples;
* :mod:`~repro.obs.slo` — per-pipeline :class:`SLOSpec`s with
  multi-window burn-rate evaluation (:class:`SLOMonitor`) emitting
  flight-recorder events and the ``slo`` Prometheus group;
* :mod:`~repro.obs.recorder` — a :class:`FlightRecorder` ring buffer of
  structured control-plane events (world lifecycle, scale votes, pin flips,
  deadline expiries, codec fallbacks, SLO alerts) that dumps to JSON on
  failure/heal, rotating old dumps;
* :mod:`~repro.obs.export` — Prometheus text rendering and the shared
  trace-artifact writer the benches and examples use.
"""
from .digest import StageDigest, fold_samples, merge_digests
from .recorder import FlightRecorder, validate_dump
from .sketch import LogSketch
from .slo import (BurnRatePolicy, DEFAULT_BURN_POLICIES, SLOMonitor,
                  SLOSpec, SLOTracker)
from .trace import (DEFAULT_KEEP_KINDS, Span, TraceContext, Tracer,
                    connected_tree)

__all__ = [
    "BurnRatePolicy",
    "DEFAULT_BURN_POLICIES",
    "DEFAULT_KEEP_KINDS",
    "FlightRecorder",
    "LogSketch",
    "SLOMonitor",
    "SLOSpec",
    "SLOTracker",
    "Span",
    "StageDigest",
    "TraceContext",
    "Tracer",
    "connected_tree",
    "fold_samples",
    "merge_digests",
    "validate_dump",
]
