"""Drive the system under test: build the cell's ``PipelineServer`` over
the program configuration and weights that the layer module gives, warm
every program the cell's traffic reaches, run the open-loop window, and
record what the metric readers read.

The program is imported from ``<checkout>/src``; nothing else of it is used
but its public entry points (``PipelineServer.generate``, a stage
executor's ``prefill`` and ``decode_many``) and its counters. Which programs
a prompt length or a convoy size compiles is the program's own business:
the warm-up sends the traffic's shapes through those entry points and lets
the program bucket and pad them as it will.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import traffic as T

#: tokens each warm-up request asks for: its prefill, then one
#: single-session decode step
PROBE_TOKENS = 2


def build_server(pcfg, params, srv: dict):
    """The cell's cluster and ``PipelineServer`` over ``params``."""
    from repro.core import Cluster
    from repro.models import build_model
    from repro.serving import PipelineServer
    cluster = Cluster(heartbeat_interval=srv["heartbeat_interval_s"],
                      heartbeat_timeout=srv["heartbeat_timeout_s"])
    return PipelineServer(cluster, build_model(pcfg), params,
                          replicas=list(srv["replicas"]),
                          max_len=int(srv["max_len"]),
                          least_loaded=bool(srv["least_loaded"]))


def _prompt(length: int, vocab: int) -> np.ndarray:
    return (np.arange(length, dtype=np.int32) % vocab)[None, :]


def _stage_input(server, ex, length: int):
    """A zero input of ``length`` positions for stage ``ex``: token ids on
    the first stage, hidden rows on the others."""
    if ex.spec.first:
        return jnp.zeros((1, length), jnp.int32)
    cfg = server.cfg
    return jnp.zeros((1, length, cfg.d_model), cfg.activation_dtype)


def _convoys(server, length: int) -> None:
    """Each stage's public ``decode_many`` at every convoy size from two to
    the server's ``microbatch_max``, over one cache of ``length``
    positions: the program's own width buckets and convoy padding."""
    for ex in server.stage_executors:
        _, cache = ex.prefill(_stage_input(server, ex, length))
        x = _stage_input(server, ex, 1)
        jax.block_until_ready((cache, x))
        for n in range(2, server.microbatch_max + 1):
            jax.block_until_ready(
                ex.decode_many([cache] * n, [x] * n, [length] * n))


async def warm(server, mix: dict, seconds: float, vocab: int) -> int:
    """Compile or load every program the mix's traffic reaches, through the
    program's own path: one request of each prompt length of the mix
    through ``generate`` (every stage's prefill with the eager programs
    around it, then a single-session decode step), then the convoys of
    every size. Returns the number of requests sent."""
    lengths = T.prompt_lengths(mix, seconds)
    for n in lengths:
        await server.generate(_prompt(n, vocab), PROBE_TOKENS)
    _convoys(server, min(lengths))
    return len(lengths)


@dataclasses.dataclass
class Record:
    req: T.Request
    sent: Optional[float] = None          # seconds after the window opened
    times: list = dataclasses.field(default_factory=list)   # absolute
    out: Optional[np.ndarray] = None
    error: Optional[str] = None
    done: Optional[float] = None


@dataclasses.dataclass
class Window:
    """What one run saw. Times are seconds after the window opened."""
    seconds: float
    records: list
    counters_start: dict
    counters_end: dict
    compiles: list                   # (t, event name) of JAX
    setup_s: float
    t0: float                        # absolute monotonic time of the open


def counters(server) -> dict:
    return {"executors": [dict(e.stats) for e in server.stage_executors]}


class CompileLog:
    """JAX's own compile and cache-load events, with the time they came."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, duration: float, **kw) -> None:
        if name in self.EVENTS:
            self.events.append((time.monotonic(), name))


async def run_window(server, reqs: list, seconds: float, t_start: float,
                     compile_log: CompileLog, grace_s: float,
                     before_open: Optional[Callable[[], Any]] = None,
                     on_open: Callable[[], Any] = lambda: None,
                     on_close: Callable[[], Any] = lambda: None) -> Window:
    """Start the replicas, await ``before_open`` (the warm-up), run the
    open-loop window, let the requests still in flight finish (up to
    ``grace_s``), and stop the cluster."""
    await server.start()
    if before_open is not None:
        await before_open()
    records = [Record(r) for r in reqs]
    on_open()
    t0 = time.monotonic()
    setup_s = t0 - t_start
    c0 = counters(server)

    async def one(rec: Record) -> None:
        await asyncio.sleep(max(0.0, t0 + rec.req.due_s - time.monotonic()))
        rec.sent = time.monotonic() - t0
        try:
            out = await server.generate(rec.req.prompt[None, :],
                                        rec.req.out_len,
                                        token_times=rec.times)
            rec.out = np.asarray(out)[0]
        except Exception as e:  # noqa: BLE001 — a failed request is data
            rec.error = repr(e)
        rec.done = time.monotonic() - t0

    tasks = [asyncio.ensure_future(one(r)) for r in records]
    try:
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t_end = time.monotonic()
        on_close()
        c1 = counters(server)
        compiles = [(t - t0, n) for t, n in compile_log.events
                    if t0 <= t <= t_end]
        pending = [t for t in tasks if not t.done()]
        if pending:
            await asyncio.wait(pending, timeout=grace_s)
    finally:
        for t in tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        server.cluster.shutdown()
        await asyncio.sleep(0)
    for r in records:
        if r.out is None and r.error is None:
            r.error = "unfinished after the grace period"
    return Window(seconds=t_end - t0, records=records, counters_start=c0,
                  counters_end=c1, compiles=compiles, setup_s=setup_s, t0=t0)
