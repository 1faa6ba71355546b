"""MultiWorld pipeline server — the paper's Fig. 2 with real models.

Topology: the model is split into N stages (serving/partition.py); each stage
has one or more replica workers; every (upstream replica, downstream replica)
pair gets its own pairwise world, as does every (client, stage-0 replica) and
(last-stage replica, client) pair. Worlds are fault domains: a replica death
breaks only its edges; upstream routers drop the broken worlds and keep
serving through the survivors; ``add_replica`` performs online instantiation
(new worker + fresh worlds) without touching any existing world.

Generative data plane (beyond the paper's one-shot batches): every payload on
every edge is a typed :class:`~repro.serving.envelope.Envelope`. The client
drives autoregressive generation with ``generate()``:

* PREFILL carries the full token history through the pipeline; each stage
  builds a per-session KV cache over its own layer slice and *pins* the
  downstream world it picked, so the session's decode steps follow one route.
* DECODE carries one token per step along the pinned route. Each replica runs
  a continuous-batching micro-scheduler: compatible queued decode steps (same
  per-session batch shape, arbitrary positions) coalesce into one fused
  ``decode_many`` dispatch, with a max-wait knob (``microbatch_wait_s``)
  bounding the latency paid for batching.
* A replica that has lost a session's state — it is draining, the session
  was never prefilled here, or its pinned downstream edge died — answers
  RETRY toward the client, which re-prefills the full history (prompt + all
  tokens generated so far) on a survivor: at-least-once, state rebuilt,
  zero client-visible token loss.
* FINISH releases per-stage session state along the pinned route.

State transfer (repro.statexfer) upgrades the recovery paths so RETRY +
full re-prefill is the *fallback*, not the norm:

* planned drain hands every open session off live — the MigrationManager
  freezes it at a step boundary (new steps pile into ``held``), streams its
  KV snapshot to a same-stage survivor, flips the pins, and releases the
  held steps into the survivor's inbox: zero re-prefill, token-identical;
* an unplanned kill restores from the SnapshotStore's background snapshots
  and the client replays only the tokens since the latest snapshot;
* a deadline-expired envelope is dropped at the stage boundary with a
  FINISH(error) propagated to the client instead of being served late.

Disaggregated prefill/decode pools (role-specialized replicas): a stage's
replica count may be given as ``{"prefill": p, "decode": d}`` instead of an
int, splitting the stage into a prefill pool (serves PREFILL/SCORE — long,
compute-bound, compile-heavy dispatches) and a decode pool (serves DECODE —
short, latency-bound, batch-hungry steps), each scalable on its own signal.
The two pools meet at the *handoff*: a prefill replica builds the session's
stage-slice KV cache, streams it to a placement-ranked decode-pool home over
the statexfer chunked codec (HANDOFF envelopes), and stitches the decode
route's pins onto that home — so every subsequent decode step bypasses the
prefill pool entirely, and a burst of long prompts can no longer convoy
decode microbatches behind prefill dispatches. ``role='both'`` (the default
for int counts) keeps the colocated behavior bit-identical: caches install
locally and no handoff ever runs. A failed handoff unwinds to RETRY + full
re-prefill on the prefill pool — never a new failure mode.

Multi-model, multi-tenant pool (the consolidation refactor): the pipeline
can host several registered models on one elastic replica set instead of
one-model-one-server. A :class:`~repro.serving.registry.ModelRegistry`
tracks which models exist and where they are resident (refcounted by open
sessions, LRU-evictable); ``load_model``/``unload_model``/``swap_model``
drive the LOAD/UNLOAD/SWAP envelope protocol (statexfer.bootstrap) that
streams a model's stage weights from a resident peer — or cold from the
registry store — *without the replica ever leaving rotation*. Every
envelope carries its model tag; routers restrict rotation to replicas with
the model resident; executors are keyed per (model, stage, role) so compile
caches and KV pools never mix models. Tenancy rides the same envelopes: the
decode micro-scheduler arbitrates batch slots across tenants by weighted
deficit round-robin (``tenant_weights``), and the client keys TTFT/decode
latency sketches per tenant so per-tenant SLO policies have real signals.
Defaults (no registry, no tags, one implicit tenant) preserve single-model
behavior bit-for-bit.

Elastic control hooks (consumed by repro.control):

* ``remove_replica`` — scale-down: stop routing to the replica, *unpin* its
  sessions (their next decode step triggers relocation via RETRY or the
  client's own pin check), drain its inbox/in-flight work/adjacent channels
  to zero, then tear down its worlds in one event-loop tick.
* per-replica load counters (queue depth, in-flight, wait/service time,
  tokens out, open sessions) — the raw signals MetricsHub turns into EWMAs.
* ``failed_replicas`` — watchdog-sourced failure view for the heal loop.
"""
from __future__ import annotations

import asyncio
import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Cluster,
    WorldBrokenError,
    WorldNotFoundError,
    WorldSpec,
)
from repro.core.online import OnlineInstantiator
from repro.obs import FlightRecorder, LogSketch, Span, Tracer
from repro.statexfer import (
    INT8,
    MigrationManager,
    SnapshotStore,
    WarmBootstrap,
    argmax_margin,
    cache_nbytes,
)
from .envelope import (
    Envelope,
    Kind,
    ROLE_BOTH,
    ROLE_CAPABLE,
    ROLE_DECODE,
    ROLE_DRAFT,
    ROLE_PREFILL,
)
from .executor import StageExecutor
from .partition import split_stages, stage_params
from .registry import ModelRegistry, ResidencyError
from .router import ReplicaRouter

CLIENT = "client"


def _edge(name: str, up: str, down: str) -> str:
    return f"{name}:{up}->{down}"


@dataclasses.dataclass
class _Session:
    """Per-stage decode state for one open generation request."""

    cache: Any
    batch: int
    step: int            # last position decoded at this stage
    touched: float       # monotonic; TTL reaping of orphaned state
    #: TraceContext of the step that installed this state — migration,
    #: snapshot, and heal spans for the session parent here, keeping the
    #: control-plane work inside the session's causal tree
    trace: Any = None
    #: registered model this session runs (None = the pipeline default);
    #: decode steps resolve their executor — and batch-mates — through it
    model: Optional[str] = None
    #: tenant whose traffic this session is (fair-scheduler accounting)
    tenant: Optional[str] = None


class _SessionLost(Exception):
    """Client-side marker: pinned state gone; re-prefill on a survivor."""


class _Replica:
    def __init__(self, server: "PipelineServer", worker_id: str,
                 stage: int, role: str = ROLE_BOTH) -> None:
        self.server = server
        self.worker_id = worker_id
        self.stage = stage
        #: which pool this replica serves: ``both`` (colocated default),
        #: ``prefill`` (builds caches, hands them off, never decodes), or
        #: ``decode`` (receives caches over the handoff, serves every step)
        self.role = role
        #: models resident on this replica — the routing tag its upstream
        #: edges carry and the unit load_model/unload_model/swap_model
        #: mutate. Always contains at least the pipeline's default model;
        #: further models join via the LOAD protocol after wiring.
        self.resident: set[str] = {server.default_model}
        self.worker = server.cluster.worker(worker_id)
        #: compute executor for the *default* model — shared per
        #: (stage, role) unless WarmBootstrap installed a fresh per-replica
        #: executor (new-process simulation). Non-default models resolve
        #: through :meth:`executor_for` to per-(model, stage, role)
        #: executors shared at the server.
        self.executor = server.role_executor(stage, role)
        self.upstream: list[str] = []          # world names we recv on
        #: (world, upstream router that routes onto it) — scale-down needs to
        #: know exactly which rotation each inbound edge lives in
        self.upstream_edges: list[tuple[str, ReplicaRouter]] = []
        self.router = ReplicaRouter()          # downstream worlds we send on
        self.router.set_load_probe(server._edge_load)
        self.router.set_drop_listener(server._forget_edge)
        self.inbox: asyncio.Queue = asyncio.Queue()
        #: envelopes popped during decode coalescing that must be served
        #: before the next inbox read (ordering across kinds)
        self._stash: deque = deque()
        #: open generation sessions whose stage-slice KV cache lives here
        self.sessions: dict[int, _Session] = {}
        #: sessions frozen mid-migration: sid -> held (env, t_enq) items,
        #: released into the survivor's inbox once the handoff installs
        self.held: dict[int, list] = {}
        #: sessions handed off from here: sid -> survivor replica; late
        #: arrivals (already in our channels when the pins flipped) are
        #: forwarded instead of bounced into a useless re-prefill
        self.migrated: dict[int, "_Replica"] = {}
        #: sessions with a decode step currently executing/coalescing — the
        #: MigrationManager waits for a step boundary before snapshotting
        self.active: set[int] = set()
        #: persistent prefill<->decode handoff worlds this replica is an
        #: endpoint of (steady-state KV transfer channels; torn down with
        #: the replica)
        self.handoff_worlds: set[str] = set()
        self._pumps: dict[str, asyncio.Task] = {}
        self._run_task: Optional[asyncio.Task] = None
        self._reap_task: Optional[asyncio.Task] = None
        self.draining = False
        self._last_reap = time.monotonic()
        # -- load/latency counters polled by control.MetricsHub ------------
        self.processed = 0
        self.inflight = 0
        self.wait_s_sum = 0.0        # inbox sojourn
        self.service_s_sum = 0.0     # compute + downstream send
        self.parked = 0              # sends parked on an empty rotation
        self.tokens_out = 0          # decode tokens produced (B per step)
        self.decode_batches = 0      # fused decode dispatches
        self.decode_steps = 0        # decode envelopes served
        # -- decode host path, seconds summed over the ``decode_steps`` and
        #    ``decode_batches`` above: each envelope's inbox arrival to its
        #    convoy's submit (queue + gather); each convoy's executor call
        #    submit to the coroutine's resume, and that call's own time on
        #    its worker thread (the rest is the thread hop) --------------
        self.decode_wait_s_sum = 0.0
        self.dispatch_s_sum = 0.0
        self.exec_s_sum = 0.0
        self.retries_sent = 0        # sessions bounced back for re-prefill
        self.expired = 0             # envelopes dropped past their deadline
        # -- per-kind latency split (MetricsHub turns the deltas into TTFT
        #    vs per-token decode EWMAs — the per-role policies' signals) ---
        self.prefills = 0            # prefills served (incl. handoff time)
        self.prefill_s_sum = 0.0     # wall time of served prefills
        self.decode_s_sum = 0.0      # wall time of fused decode dispatches
        self.handoffs_out = 0        # prefills handed to the decode pool
        # -- mergeable latency distributions: one O(1) sketch insert per
        #    dispatch; MetricsHub folds these into the stage/fleet digests
        #    so p95 TTFT / p99 decode survive aggregation (means cannot) --
        self.ttft_sketch = LogSketch()
        self.decode_sketch = LogSketch()
        # -- speculative decoding counters (control-plane acceptance
        #    signal: MetricsHub folds proposed/accepted deltas into the
        #    per-replica acceptance EWMA that SpecDecodePolicy votes on) --
        self.spec_verifies = 0       # fused VERIFY dispatches served here
        self.spec_proposed = 0       # draft tokens offered to verification
        self.spec_accepted = 0       # draft tokens verification accepted
        self.spec_proposals = 0      # PROPOSE rounds served (draft pool)
        # -- weighted-deficit fair scheduler state (multi-tenant decode) --
        #: tenant -> remaining deficit credits for batch-slot arbitration
        self._credits: dict[str, float] = {}
        #: tenant -> decode steps served (the fairness test's ground truth)
        self.tenant_served: dict[str, int] = {}
        #: id(envelope) -> its open ``mw.replica.queue`` span, from the
        #: inbox put to the handler that takes it
        self._queue_spans: dict[int, Span] = {}

    def queue_depth(self) -> int:
        return (self.inbox.qsize() + len(self._stash) + self.inflight
                + sum(len(h) for h in self.held.values()))

    def executor_for(self, model: Optional[str]) -> StageExecutor:
        """The compute executor for ``model`` at this replica's stage/role.
        None or the default model hit ``self.executor`` (which may be a
        replica-private warm-bootstrap executor); other resident models
        share the server's per-(model, stage, role) executor — a session's
        cache must only ever meet the executor that owns its weights."""
        server = self.server
        if model is None or model == server.default_model:
            return self.executor
        return server.model_executor(model, self.stage, self.role)

    def install_session(self, sid: int, cache: Any, batch: int,
                        step: int, trace: Any = None,
                        model: Optional[str] = None,
                        tenant: Optional[str] = None) -> None:
        """Adopt migrated/restored decode state at a step boundary. A paged
        wire payload is installed page-by-page into this executor's pool
        (deduping against pages it already holds); anything else passes
        through unchanged."""
        ex = self.executor_for(model)
        cache = ex.adopt_cache(cache)
        old = self.sessions.pop(sid, None)
        if old is not None:
            if old.cache is not cache:
                self.executor_for(old.model).release_cache(old.cache)
            self.server.registry.release(
                self.worker_id, old.model or self.server.default_model)
        self.sessions[sid] = _Session(cache=cache, batch=batch, step=step,
                                      touched=time.monotonic(), trace=trace,
                                      model=model, tenant=tenant)
        # the session pins its model's residency here until dropped
        self.server.registry.acquire(
            self.worker_id, model or self.server.default_model)

    def drop_session(self, sid: int) -> None:
        """Forget a session AND return its stage cache to the executor —
        for a paged handle that decrements page refcounts (shared prefix
        pages survive while siblings still hold them); contiguous caches
        just lose their last reference."""
        sess = self.sessions.pop(sid, None)
        if sess is not None:
            self.executor_for(sess.model).release_cache(sess.cache)
            self.server.registry.release(
                self.worker_id, sess.model or self.server.default_model)

    def open_sessions(self) -> int:
        return len(self.sessions)

    def watch_upstream(self, world: str, router: ReplicaRouter) -> None:
        self.upstream.append(world)
        self.upstream_edges.append((world, router))
        self._pumps[world] = self.worker.spawn(self._pump(world))

    def drop_upstream(self, world: str) -> None:
        task = self._pumps.pop(world, None)
        if task is not None and not task.done():
            task.cancel()
        if world in self.upstream:
            self.upstream.remove(world)
        self.upstream_edges = [(w, r) for w, r in self.upstream_edges
                               if w != world]

    async def _pump(self, world: str) -> None:
        comm = self.worker.comm
        tracer = self.server.tracer
        try:
            while True:
                payload = await comm.recv(0, world)
                self._queue_spans[id(payload)] = tracer.open(
                    "mw.replica.queue", payload.trace,
                    worker=self.worker_id, stage=self.stage)
                await self.inbox.put((payload, time.monotonic()))
        except (WorldBrokenError, WorldNotFoundError, asyncio.CancelledError):
            return

    def _taken(self, env: Envelope) -> None:
        """A handler has ``env``: close its ``mw.replica.queue`` span."""
        span = self._queue_spans.pop(id(env), None)
        if span is not None:
            self.server.tracer.close(span)

    def _exec(self, name: str, width: int, fn, *args):
        """Run one executor call on its worker thread inside the span
        ``name`` (``mw.exec.<call>``); a decode call's host seconds go to
        ``exec_s_sum``. One call at a time per replica: its serve loop
        awaits each."""
        tracer = self.server.tracer
        span = tracer.open(name, worker=self.worker_id, stage=self.stage,
                           width=width)
        try:
            return fn(*args)
        finally:
            dt = tracer.close(span)
            if name.startswith("mw.exec.decode"):
                self.exec_s_sum += dt

    # ------------------------------------------------------------- serve loop
    async def run(self) -> None:
        ex = self.executor
        loop = asyncio.get_event_loop()
        while True:
            if self._stash:
                env, t_enq = self._stash.popleft()
            else:
                env, t_enq = await self.inbox.get()
            self._taken(env)
            t0 = time.monotonic()
            self.wait_s_sum += t0 - t_enq
            self.inflight += 1
            try:
                await self._dispatch(ex, loop, env, t0, t_enq)
            except asyncio.CancelledError:
                raise
            except (WorldBrokenError, WorldNotFoundError):
                pass   # per-send handling already rerouted or retried
            except Exception as e:  # noqa: BLE001 — a failed stage dispatch
                # must not kill the serve loop; bounce the session so the
                # client rebuilds state elsewhere. This is the flight
                # recorder's "unhandled failure" dump trigger: whatever led
                # here is a bug or a torn dependency worth a timeline.
                rec = self.server.recorder
                rec.record("unhandled_failure", worker=self.worker_id,
                           env_kind=int(env.kind), session=env.session_id,
                           error=repr(e))
                rec.dump("unhandled_failure", worker=self.worker_id)
                self.drop_session(env.session_id)
                if env.kind in (Kind.PREFILL, Kind.DECODE, Kind.VERIFY,
                                Kind.PROPOSE):
                    await self._send_retry(env)
            finally:
                self.inflight -= 1
            self._maybe_reap(t0)

    async def _dispatch(self, ex: StageExecutor, loop, env: Envelope,
                        t0: float, t_enq: float) -> None:
        sid = env.session_id
        if env.kind in (Kind.DECODE, Kind.FINISH, Kind.VERIFY):
            target = self.migrated.get(sid)
            if target is not None:
                # session handed off after this envelope was already sent
                # toward us — forward to its new home instead of bouncing
                if env.kind is Kind.FINISH:
                    self.migrated.pop(sid, None)
                target.inbox.put_nowait((env, t0))
                return
            if sid in self.held:
                self.held[sid].append((env, t0))
                return
        if env.expired(t0):
            await self._expire(env)
            return
        kind = env.kind
        if kind in (Kind.HANDOFF, Kind.LOAD, Kind.UNLOAD, Kind.SWAP):
            # handoff/residency chunks travel dedicated pairwise worlds
            # consumed by their own receive loops (MigrationManager /
            # WarmBootstrap); one in a serve inbox is a misroute — drop it
            # rather than decode it
            return
        if kind in (Kind.SCORE, Kind.PREFILL, Kind.DECODE, Kind.VERIFY):
            name = env.model or self.server.default_model
            if name not in self.resident:
                # routed here before a swap/unload retagged the rotation —
                # bounce rather than run foreign weights
                if kind in (Kind.DECODE, Kind.PREFILL, Kind.VERIFY):
                    await self._send_retry(env)
                return
            ex = self.executor_for(env.model)
            self.server.registry.touch(self.worker_id, name)
        if kind is Kind.RETRY:
            # stateless pass-through toward the client — any healthy path
            await self._forward_routed(env)
        elif kind is Kind.FINISH:
            await self._finish_session(env)
        elif kind is Kind.SCORE:
            y = await loop.run_in_executor(None, ex.score, env.payload)
            if await self._forward_routed(
                    dataclasses.replace(env, payload=y)) is not None:
                self.processed += 1
                self.service_s_sum += time.monotonic() - t0
        elif kind is Kind.PREFILL:
            await self._handle_prefill(ex, loop, env, t0)
        elif kind is Kind.PROPOSE:
            await self._handle_propose(loop, env, t0)
        elif kind is Kind.VERIFY:
            await self._handle_verify(ex, loop, env, t0)
        else:
            await self._handle_decode(ex, loop, env, t0, t_enq)

    async def _handle_prefill(self, ex: StageExecutor, loop, env: Envelope,
                              t0: float) -> None:
        if self.draining:
            await self._send_retry(env)
            return
        server = self.server
        tracer = server.tracer
        span = tracer.open("mw.replica.dispatch", env.trace,
                           worker=self.worker_id, stage=self.stage, width=1)
        try:
            y, cache = await loop.run_in_executor(
                None, self._exec, "mw.exec.prefill", 1, ex.prefill,
                env.payload)
        finally:
            tracer.close(span)
        if server._is_last(self.stage):
            y = y[:, -1]              # client only needs last-position logits
        sid = env.session_id
        batch = int(env.payload.shape[0])
        # -- decode home: where this session's stage slice will live -------
        # A 'both' replica keeps the cache (the colocated path, unchanged).
        # A prefill-pool replica streams it to a placement-ranked decode
        # peer over the statexfer chunked codec and pins the decode route
        # there; with no decode-capable peer (e.g. the only decode replica
        # just died and the heal is still in flight) it degrades to serving
        # the session locally rather than livelocking the client in RETRY.
        home: "_Replica" = self
        if self.role == ROLE_PREFILL and sid >= 0:
            peer = server._pick_decode_peer(self.stage, exclude=self,
                                            nbytes=cache_nbytes(cache),
                                            model=env.model)
            if peer is not None:
                ok = await server.migrations.handoff_prefill(
                    self, peer, sid, cache, batch, env.step,
                    trace=env.trace, model=env.model, tenant=env.tenant)
                # either way the prefill side is done with this cache: the
                # bytes are on the wire (or abandoned) — return its pages
                # to the prefill pool instead of stranding them
                ex.release_cache(cache)
                if not ok:
                    # mid-handoff failure: unwind to the at-least-once
                    # discipline — RETRY bounces the client into a full
                    # re-prefill on the prefill pool
                    await self._send_retry(env)
                    return
                home = peer
                self.handoffs_out += 1
        if home is self:
            self.sessions[sid] = _Session(
                cache=cache, batch=batch, step=env.step,
                touched=time.monotonic(), trace=env.trace,
                model=env.model, tenant=env.tenant)
            server.registry.acquire(self.worker_id,
                                    env.model or server.default_model)
        else:
            # a step routed at us before the pins stitched (or a straggler
            # in our channels) forwards in-process to the decode home
            self.migrated[sid] = home
            if server._is_last(self.stage):
                client_edge = _edge(server.name, home.worker_id, CLIENT)
                if client_edge in home.router.healthy():
                    home.router.pin(sid, client_edge)
        server._pin_upstream(self, env, home)
        world = await self._forward_routed(
            dataclasses.replace(env, payload=y, home=home.worker_id))
        if world is None:            # expired while parked — orphan reaped
            home.drop_session(sid)
            self.migrated.pop(sid, None)
            return
        if home is self and self.router.pinned(sid) is None:
            # colocated downstream pin — unless the next stage's handoff
            # already stitched the decode route onto its own decode home
            self.router.pin(sid, world)
        self.processed += 1
        dt = time.monotonic() - t0
        self.service_s_sum += dt
        self.prefill_s_sum += dt
        self.prefills += 1
        self.ttft_sketch.insert(dt)
        server.tracer.span(env.trace, "prefill", t0, self.worker_id)

    async def _handle_decode(self, ex: StageExecutor, loop, env: Envelope,
                             t0: float, t_enq: float) -> None:
        """Continuous-batching micro-scheduler: serve this decode step fused
        with every compatible queued step (same per-session batch shape and
        model, any position), waiting up to ``microbatch_wait_s`` for
        stragglers when more sessions are open than are in hand. Batch
        slots are arbitrated across tenants by weighted deficit round-robin
        (see :meth:`_pull_compatible`). ``t_enq`` is the step's arrival in
        the inbox."""
        sess0 = self.sessions.get(env.session_id)
        if self.draining or sess0 is None:
            self.drop_session(env.session_id)
            await self._send_retry(env)
            return
        # the session's own model is authoritative for the executor — a
        # replayed or untagged step must never run foreign weights
        ex = self.executor_for(sess0.model)
        batch: list[Envelope] = [env]
        #: session id -> its step's arrival in the inbox
        arrived = {env.session_id: t_enq}
        self.active.add(env.session_id)
        max_n = self.server.microbatch_max
        deadline = t0 + self.server.microbatch_wait_s
        tr = self.server.tracer
        try:
            gather = tr.open("mw.replica.gather", env.trace,
                             worker=self.worker_id, stage=self.stage)
            while len(batch) < max_n:
                pulled = self._pull_compatible(env, max_n - len(batch), batch,
                                               arrived)
                if pulled:
                    continue
                if (len(self.sessions) <= len(batch)
                        or time.monotonic() >= deadline):
                    break
                await asyncio.sleep(0)
            tr.close(gather)

            # a concurrent teardown/reap may have dropped a session between
            # the compatibility check and now — bounce those, fuse the rest
            live: list[tuple[Envelope, _Session]] = []
            for e in batch:
                sess = self.sessions.get(e.session_id)
                if sess is None:
                    await self._send_retry(e)
                else:
                    live.append((e, sess))
            if not live:
                return
            n = len(live)
            t_sub = time.monotonic()
            for e, _ in live:
                self.decode_wait_s_sum += t_sub - arrived[e.session_id]
            span = tr.open("mw.replica.dispatch", env.trace,
                           worker=self.worker_id, stage=self.stage, width=n)
            try:
                outs = await loop.run_in_executor(
                    None, self._exec,
                    "mw.exec.decode_many" if n > 1 else "mw.exec.decode",
                    n, ex.decode_many,
                    [s.cache for _, s in live],
                    [e.payload for e, _ in live],
                    [e.step for e, _ in live])
            except Exception:  # noqa: BLE001 — a failed fused dispatch must
                # bounce EVERY coalesced session, not just the first: the
                # batch-mates were already pulled off the inbox and would
                # otherwise stall their clients a full step_timeout
                for e, _ in live:
                    self.drop_session(e.session_id)
                    await self._send_retry(e)
                return
            finally:
                dt = tr.close(span)
            now = time.monotonic()
            self.dispatch_s_sum += dt
            self.decode_batches += 1
            for (e, sess), (y, new_cache) in zip(live, outs):
                sess.cache = new_cache
                sess.step = e.step
                sess.touched = now
                self.decode_steps += 1
                self.tokens_out += sess.batch
                t_name = e.tenant or "default"
                self.tenant_served[t_name] = (
                    self.tenant_served.get(t_name, 0) + 1)
                await self._forward_pinned(dataclasses.replace(e, payload=y))
                tr.span(e.trace, "decode", arrived[e.session_id],
                        self.worker_id)
                self.processed += 1
            dt = time.monotonic() - t0
            self.service_s_sum += dt
            self.decode_s_sum += dt
            self.decode_sketch.insert(dt)
        finally:
            # coalesced extras were pulled out of the inbox by this handler;
            # the run loop only balances the first envelope's inflight count
            self.inflight -= len(batch) - 1
            for e in batch:
                self.active.discard(e.session_id)

    async def _handle_propose(self, loop, env: Envelope, t0: float) -> None:
        """Draft side of speculative decoding. The payload is the session's
        FULL committed history (B, S): draft state is disposable by
        construction — a fresh, healed, or re-picked draft replica simply
        re-prefills the history locally, so a draft-pool kill never costs
        a single *target*-pool token. Known sessions integrate only the
        tokens committed since the last round. Replies with ``spec_k``
        greedy draft-model proposals (B, k) int32."""
        if self.draining:
            await self._send_retry(env)
            return
        ex = self.executor          # always the draft-model executor
        sid = env.session_id
        hist = jnp.asarray(env.payload, jnp.int32)
        s = int(hist.shape[1])
        bsz = int(hist.shape[0])
        # proposal i is written at slot s+i-1; clamp k so the last write
        # stays inside the draft cache (k=1 writes nothing beyond history)
        k = max(1, min(int(env.spec_k) or 1, ex.max_len - s + 1))
        sess = self.sessions.get(sid)

        def _propose():
            cache = sess.cache if sess is not None else None
            done = sess.step if sess is not None else 0
            if cache is None or done < 1 or done > s:
                # unknown/stale session: rebuild the draft cache from the
                # full history, then let the rollout re-feed the last
                # token (an exact no-op rewrite for full caches) so the
                # integrate+propose path below is the only compute shape
                _, cache = ex.prefill(hist)
                done = s - 1
            elif done >= s:
                done = s - 1    # replayed round: idempotent re-decode
            # ONE fused dispatch: integrate hist[done:] and roll out k
            # greedy proposals (see StageExecutor.propose_rollout)
            props, cache = ex.propose_rollout(cache, hist[:, done:],
                                              done, k)
            return np.asarray(props), cache

        try:
            props, cache = await loop.run_in_executor(None, _propose)
        except Exception:  # noqa: BLE001 — degrade, never fail the client
            self.drop_session(sid)
            await self._send_retry(env)
            return
        now = time.monotonic()
        if sess is not None:
            sess.cache, sess.step, sess.touched = cache, s, now
        else:
            self.sessions[sid] = _Session(
                cache=cache, batch=bsz, step=s, touched=now,
                trace=env.trace, tenant=env.tenant)
            self.server.registry.acquire(self.worker_id,
                                         self.server.default_model)
        self.spec_proposals += 1
        self.server.tracer.span(env.trace, "propose", t0, self.worker_id)
        await self._forward_routed(
            dataclasses.replace(env, payload=props, spec_k=k))
        self.processed += 1
        self.service_s_sum += time.monotonic() - t0

    async def _handle_verify(self, ex: StageExecutor, loop, env: Envelope,
                             t0: float) -> None:
        """Target side of speculative decoding: integrate the session's
        current token plus its k draft proposals in ONE fused dispatch
        (``verify_many``), coalescing compatible queued VERIFYs exactly
        like decode steps. The last stage judges the accepted prefix by
        greedy argmax — token j's logits saw precisely the verified tokens
        before it, so the committed block (accepted proposals + one bonus
        target token) is bit-identical to plain decode. Intermediate
        stages forward K hidden columns with the proposal block riding
        ``spec_tokens``."""
        sess0 = self.sessions.get(env.session_id)
        if self.draining or sess0 is None:
            self.drop_session(env.session_id)
            await self._send_retry(env)
            return
        ex = self.executor_for(sess0.model)
        batch: list[Envelope] = [env]
        self.active.add(env.session_id)
        max_n = self.server.microbatch_max
        deadline = t0 + self.server.microbatch_wait_s
        try:
            while len(batch) < max_n:
                pulled = self._pull_compatible(env, max_n - len(batch), batch)
                if pulled:
                    continue
                if (len(self.sessions) <= len(batch)
                        or time.monotonic() >= deadline):
                    break
                await asyncio.sleep(0)
            live: list[tuple[Envelope, _Session]] = []
            for e in batch:
                sess = self.sessions.get(e.session_id)
                if sess is None:
                    await self._send_retry(e)
                else:
                    live.append((e, sess))
            if not live:
                return
            try:
                outs = await loop.run_in_executor(
                    None, ex.verify_many,
                    [s.cache for _, s in live],
                    [e.payload for e, _ in live],
                    [e.step for e, _ in live])
            except Exception:  # noqa: BLE001 — bounce every coalesced
                # session, same discipline as a failed fused decode
                for e, _ in live:
                    self.drop_session(e.session_id)
                    await self._send_retry(e)
                return
            now = time.monotonic()
            self.decode_batches += 1
            last = self.server._is_last(self.stage)
            tr = self.server.tracer
            for (e, sess), (y, new_cache) in zip(live, outs):
                if last:
                    toks = np.asarray(e.spec_tokens
                                      if e.spec_tokens is not None
                                      else e.payload)
                    props = toks[:, 1:]
                    g = np.argmax(np.asarray(y), axis=-1)   # (B, K) greedy
                    k = props.shape[1]
                    m = 0
                    while m < k and bool(np.all(props[:, m] == g[:, m])):
                        m += 1
                    committed = g[:, :m + 1].astype(np.int32)
                    # roll rejected-suffix pages back before anything else
                    # can observe the handle (paged mode; contiguous no-op)
                    new_cache = ex.commit_verify(new_cache, e.step + m + 1)
                    sess.step = e.step + m
                    self.spec_verifies += 1
                    self.spec_proposed += k * sess.batch
                    self.spec_accepted += m * sess.batch
                    self.decode_steps += m + 1
                    self.tokens_out += sess.batch * (m + 1)
                    reply = dataclasses.replace(e, payload=committed,
                                                spec_tokens=None)
                else:
                    # acceptance is judged downstream; keep this stage's
                    # cursor conservative (re-integration of the accepted
                    # suffix is an idempotent rewrite for full caches)
                    sess.step = e.step
                    reply = dataclasses.replace(
                        e, payload=y,
                        spec_tokens=(e.spec_tokens
                                     if e.spec_tokens is not None
                                     else np.asarray(e.payload)))
                sess.cache = new_cache
                sess.touched = now
                t_name = e.tenant or "default"
                self.tenant_served[t_name] = (
                    self.tenant_served.get(t_name, 0) + 1)
                tr.span(e.trace, "verify", t0, self.worker_id)
                await self._forward_pinned(reply)
                self.processed += 1
            dt = time.monotonic() - t0
            self.service_s_sum += dt
            self.decode_s_sum += dt
            self.decode_sketch.insert(dt)
        finally:
            self.inflight -= len(batch) - 1
            for e in batch:
                self.active.discard(e.session_id)

    def _pull_compatible(self, proto: Envelope, n: int,
                         batch: list[Envelope],
                         arrived: Optional[dict] = None) -> int:
        """Drain queued envelopes: coalesce compatible DECODEs into ``batch``
        (counting them in-flight so drain can't observe a false empty),
        stash everything else in arrival order. ``arrived``, when given,
        gets each pulled step's inbox arrival by session id.

        Multi-tenant arbitration (weighted deficit round-robin): when more
        compatible steps are queued than batch slots remain, the slots are
        not first-come-first-served — each backlogged tenant holds a credit
        balance refilled in proportion to its weight
        (``server.tenant_weights``, default 1.0), one credit buys one slot,
        and the richest backlogged tenant is served first. Steps that lose
        the arbitration go to the stash, where the serve loop picks them up
        next round with their credits accrued — bounded latency for light
        tenants under a heavy tenant's flood, full batches when only one
        tenant is backlogged. A single-tenant pipeline always selects
        everything, byte-identical to the pre-tenancy scheduler."""
        in_batch = {e.session_id for e in batch}
        now = time.monotonic()
        lead = self.sessions.get(proto.session_id)
        lead_model = lead.model if lead is not None else proto.model
        #: tenant -> compatible candidates, arrival order preserved
        cands: dict[str, deque] = {}
        while True:
            try:
                item = self.inbox.get_nowait()
            except asyncio.QueueEmpty:
                break
            env, t_enq = item
            sess = self.sessions.get(env.session_id)
            if (env.kind is proto.kind and sess is not None
                    and env.session_id not in self.held
                    and env.session_id not in self.migrated
                    and sess.model == lead_model
                    and env.payload.shape == proto.payload.shape
                    and not env.expired(now)):
                cands.setdefault(env.tenant or "default",
                                 deque()).append(item)
            else:
                self._stash.append(item)
        pulled = 0
        weights = self.server.tenant_weights
        cap = float(self.server.microbatch_max)
        while pulled < n and any(cands.values()):
            backlogged = [t for t, q in cands.items() if q]
            pick = max(backlogged, key=lambda t: self._credits.get(t, 0.0))
            if self._credits.get(pick, 0.0) < 1.0:
                # deficit round: every *backlogged* tenant earns its
                # weight (idle tenants accrue nothing — no stale credit
                # stockpiles), capped at one full batch worth
                for t in backlogged:
                    w = float(weights.get(t, 1.0))
                    self._credits[t] = min(
                        self._credits.get(t, 0.0) + w, w * cap)
                pick = max(backlogged,
                           key=lambda t: self._credits.get(t, 0.0))
            env, t_enq = cands[pick].popleft()
            if env.session_id in in_batch:
                # a session already has a step in hand; its duplicate
                # waits for the next round
                self._stash.append((env, t_enq))
                continue
            self._credits[pick] = self._credits.get(pick, 0.0) - 1.0
            self._taken(env)
            self.wait_s_sum += time.monotonic() - t_enq
            if arrived is not None:
                arrived[env.session_id] = t_enq
            self.inflight += 1
            batch.append(env)
            in_batch.add(env.session_id)
            self.active.add(env.session_id)
            pulled += 1
        # arbitration losers go back in front of future inbox work
        for q in cands.values():
            self._stash.extend(q)
        return pulled

    # ------------------------------------------------------------ forwarding
    async def _forward_routed(self, env: Envelope) -> Optional[str]:
        """Send via the rotation (SCORE/PREFILL/RETRY). Parks on an empty
        rotation until the controller heals a downstream replica; drops the
        envelope if its deadline passes while parked. PREFILL/SCORE honor
        the envelope's role tag, so a split downstream stage receives them
        in its prefill pool — and its model tag, so a multi-model stage
        receives them on a replica with the model resident. Returns the
        world used (None if dropped)."""
        comm = self.worker.comm
        fwd = env.kind in (Kind.PREFILL, Kind.SCORE)
        role = env.role if fwd else None
        model = env.model if fwd else None
        span = self.server.tracer.open("mw.replica.forward", env.trace,
                                       worker=self.worker_id,
                                       stage=self.stage)
        try:
            while True:
                if env.expired(time.monotonic()):
                    self.expired += 1
                    return None
                world = self.router.try_pick(
                    least_loaded=self.server.least_loaded, role=role,
                    model=model)
                if world is None:
                    # Every routable downstream world is gone. Dying here
                    # would drop the in-flight payload and kill this serve
                    # loop for good — park instead and retry once the
                    # controller adds/heals a downstream replica.
                    self.parked += 1
                    if ((role is not None or model is not None)
                            and self.router.healthy()):
                        # worlds exist, just none role/model-capable: the
                        # controller is growing that pool (or a load/swap is
                        # in flight) — the any-world event is already set, so
                        # poll instead of waiting on it
                        await asyncio.sleep(0.005)
                    else:
                        await self.router.wait_healthy()
                    continue
                try:
                    await comm.send(env, 1, world)
                    return world
                except WorldBrokenError:
                    self.router.mark_broken(world)
                except WorldNotFoundError:
                    self.router.remove(world)
        finally:
            self.server.tracer.close(span)

    async def _forward_pinned(self, env: Envelope) -> None:
        """Send a decode result along the session's pinned route; if the pin
        is gone (downstream death, drain, or fencing), bounce the session
        back to the client — but keep the *local* stage slice: this stage's
        cache is still consistent, and the client's restore path (racing
        the controller's live heal of the downstream stage) rebuilds the
        route from exactly this state with zero recompute. If the client
        instead gives up and re-prefills, it sweeps the partial route with
        a FINISH; the TTL reap is the backstop."""
        world = self.router.pinned(env.session_id)
        if world is None:
            await self._send_retry(env)
            return
        span = self.server.tracer.open("mw.replica.forward", env.trace,
                                       worker=self.worker_id,
                                       stage=self.stage)
        try:
            await self.worker.comm.send(env, 1, world)
            return
        except WorldBrokenError:
            self.router.mark_broken(world)
        except WorldNotFoundError:
            self.router.remove(world)
        finally:
            self.server.tracer.close(span)
        await self._send_retry(env)

    async def _expire(self, env: Envelope) -> None:
        """Deadline enforcement at the stage boundary: the client has given
        up on this step, so burn no compute on it — drop local session
        state and propagate FINISH(error) toward the client (cleaning up
        downstream stage state on the way) instead of silently eating it."""
        self.expired += 1
        self.server.recorder.record(
            "deadline_expired", worker=self.worker_id,
            session=env.session_id, step=env.step)
        if (env.kind not in (Kind.PREFILL, Kind.DECODE, Kind.VERIFY)
                or env.session_id < 0):
            return
        self.drop_session(env.session_id)
        fin = Envelope(req_id=env.req_id, session_id=env.session_id,
                       kind=Kind.FINISH, step=env.step,
                       error=f"deadline exceeded at {self.worker_id} "
                             f"(step {env.step})", trace=env.trace)
        world = self.router.pinned(env.session_id)
        self.router.unpin(env.session_id)
        if world is not None:
            try:
                await self.worker.comm.send(fin, 1, world)
                return
            except (WorldBrokenError, WorldNotFoundError):
                pass
        await self._forward_routed(fin)

    async def _send_retry(self, env: Envelope) -> None:
        self.retries_sent += 1
        self.router.unpin(env.session_id)
        await self._forward_routed(Envelope(
            req_id=env.req_id, session_id=env.session_id, kind=Kind.RETRY,
            step=env.step, trace=env.trace))

    async def _finish_session(self, env: Envelope) -> None:
        self.drop_session(env.session_id)
        if self.server._is_last(self.stage):
            self.server.session_margins.pop(env.session_id, None)
        world = self.router.pinned(env.session_id)
        self.router.unpin(env.session_id)
        if env.error is not None:
            # server-initiated FINISH (deadline drop): must reach the client,
            # not stop at the last stage like a client FINISH does — route it
            # on even when this stage never pinned the session
            if world is not None:
                try:
                    await self.worker.comm.send(env, 1, world)
                    return
                except (WorldBrokenError, WorldNotFoundError):
                    pass
            await self._forward_routed(env)
            return
        if world is None or self.server._is_last(self.stage):
            return
        try:
            # best-effort: a lost FINISH only delays reaping to the TTL sweep
            await self.worker.comm.send(env, 1, world)
        except (WorldBrokenError, WorldNotFoundError):
            pass

    def _maybe_reap(self, now: float) -> None:
        """Drop session state orphaned by lost FINISHes or dead clients."""
        if now - self._last_reap < 1.0:
            return
        self._last_reap = now
        ttl = self.server.session_ttl_s
        for sid in [s for s, sess in self.sessions.items()
                    if now - sess.touched > ttl]:
            self.drop_session(sid)
            self.router.unpin(sid)
            if self.server._is_last(self.stage):
                self.server.session_margins.pop(sid, None)
        # forwarding stubs for handed-off sessions: once the decode home no
        # longer holds the session (FINISHed/reaped/moved on), the stub is
        # garbage — a long-lived prefill replica would otherwise keep one
        # per prefill it ever served
        for sid in [s for s, tgt in self.migrated.items()
                    if s not in tgt.sessions and s not in tgt.held
                    and s not in tgt.migrated]:
            del self.migrated[sid]

    async def reap_loop(self) -> None:
        """Periodic TTL sweep: an *idle* replica (rerouted traffic, fenced
        upstream) never re-enters run()'s dispatch path, so without this its
        orphaned per-session KV caches would be held forever."""
        try:
            while True:
                await asyncio.sleep(1.0)
                self._maybe_reap(time.monotonic())
        except asyncio.CancelledError:
            return


class PipelineServer:
    """Build/serve/heal a replicated stage pipeline on a MultiWorld cluster."""

    def __init__(self, cluster: Cluster, model, params,
                 replicas: list, *, name: str = "pipe",
                 least_loaded: bool = False, max_len: int = 256,
                 paged: bool = False, page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 microbatch_max: int = 8, microbatch_wait_s: float = 0.002,
                 session_ttl_s: float = 60.0,
                 snapshot_interval_s: Optional[float] = None,
                 snapshot_codec: str = "fp",
                 restore_grace_s: float = 0.5,
                 tracing: bool = True,
                 trace_capacity: int = 32768,
                 trace_sample_rate: float = 1.0,
                 trace_slow_keep_s: Optional[float] = None,
                 flightrec_capacity: int = 4096,
                 dump_dir: Optional[str] = None,
                 registry: Optional[ModelRegistry] = None,
                 default_model: str = "default",
                 max_resident_models: Optional[int] = None,
                 tenant_weights: Optional[dict] = None,
                 draft_model=None, draft_params=None,
                 spec_k: int = 4) -> None:
        self.cluster = cluster
        self.model = model
        self.cfg = model.cfg
        self.name = name
        #: which models this pool can serve and where they are resident —
        #: the (model, params) passed above is registered as
        #: ``default_model``; further models join via ``register_model`` +
        #: ``load_model``/``swap_model``. A shared registry may be passed
        #: in (several pipelines on one model store).
        self.default_model = default_model
        self.registry = registry or ModelRegistry(
            max_resident=max_resident_models)
        if default_model not in self.registry.entries:
            self.registry.register(default_model, model, params)
        #: tenant -> weight for the decode micro-scheduler's weighted
        #: deficit round-robin; unlisted tenants weigh 1.0
        self.tenant_weights: dict[str, float] = dict(tenant_weights or {})
        #: sid -> model / tenant of every client-side open session (the
        #: restore path and per-tenant accounting read these; single-model
        #: untagged sessions never enter them)
        self.session_models: dict[int, str] = {}
        self.session_tenants: dict[int, str] = {}
        #: client-observed per-tenant latency distributions + counters,
        #: folded into MetricsHub tenant tails each poll
        self.tenant_sketches: dict[str, dict[str, LogSketch]] = {}
        self.tenant_tokens: dict[str, int] = {}
        self.tenant_sessions: dict[str, int] = {}
        #: completed residency swaps (controller-driven model A -> B)
        self.swaps_total = 0
        # replica spec per stage: an int builds that many colocated
        # ('both') replicas — the pre-disaggregation behavior, unchanged —
        # while {"prefill": p, "decode": d} splits the stage into
        # role-specialized pools
        self.replica_roles: list[dict[str, int]] = []
        # -- speculative decoding (draft role) -----------------------------
        #: the small proposer model served by ``draft``-role replicas, and
        #: the default k-token proposal budget per round (``generate``'s
        #: ``spec_k=`` overrides per call; 0 disables speculation). With no
        #: draft model the pipeline never speculates, bit-identical to the
        #: pre-draft behavior.
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.spec_k = int(spec_k) if draft_model is not None else 0
        #: client-side speculation totals (MetricsHub's ``spec`` group)
        self.spec_fallbacks_total = 0   # rounds degraded to plain decode
        self.spec_rounds_total = 0      # verify round-trips completed
        self.spec_proposed_total = 0    # draft tokens sent to verification
        self.spec_accepted_total = 0    # draft tokens verification accepted
        for spec in replicas:
            if isinstance(spec, dict):
                roles = {r: int(n) for r, n in spec.items() if int(n) > 0}
                bad = set(roles) - {ROLE_BOTH, ROLE_PREFILL, ROLE_DECODE,
                                    ROLE_DRAFT}
                if bad:
                    raise ValueError(f"unknown replica roles {sorted(bad)}")
                if ROLE_DRAFT in roles and draft_model is None:
                    raise ValueError(
                        "draft replicas need draft_model/draft_params")
                if not any(r in (ROLE_BOTH, ROLE_PREFILL) for r in roles):
                    # a decode-only stage could never serve a PREFILL: the
                    # role-aware rotation would park every new session
                    raise ValueError(
                        "stage needs at least one prefill-capable "
                        f"(prefill/both) replica: {roles}")
                self.replica_roles.append(roles)
            else:
                self.replica_roles.append({ROLE_BOTH: int(spec)})
        self.replica_counts = [sum(r.values()) for r in self.replica_roles]
        self.n_stages = len(replicas)
        self.least_loaded = least_loaded
        self.max_len = max_len
        #: paged KV mode: every stage executor allocates its cache out of a
        #: PagePool (shared prompt-prefix pages, page-granular state
        #: transfer) instead of per-session contiguous buffers
        self.paged = paged
        self.page_size = page_size
        self.pool_pages = pool_pages
        #: continuous-batching knobs: how many decode steps one dispatch may
        #: fuse, and how long to hold the first step for stragglers
        self.microbatch_max = microbatch_max
        self.microbatch_wait_s = microbatch_wait_s
        self.session_ttl_s = session_ttl_s
        #: how long a bounced client keeps retrying the cheap restore path
        #: while an alive-but-fenced replica still holds its session live —
        #: the controller's live heal is racing to move that state to a
        #: survivor, and waiting a few control ticks costs far less than
        #: recomputing the whole history
        self.restore_grace_s = restore_grace_s
        self.stage_specs = split_stages(self.cfg, self.n_stages)
        self.stage_param_sets = [stage_params(self.cfg, params, s)
                                 for s in self.stage_specs]
        #: one executor per stage, shared by the stage's replicas so they
        #: share one jit cache (compile once, serve everywhere)
        self.stage_executors = [
            StageExecutor(self.cfg, spec, sp, max_len=max_len,
                          paged=paged, page_size=page_size,
                          pool_pages=pool_pages)
            for spec, sp in zip(self.stage_specs, self.stage_param_sets)]
        #: role-specialized executors, created lazily per (stage, role) and
        #: shared within the pool — a split pool must NOT share the 'both'
        #: executor's jit cache, or "prefill replicas skip decode-bucket
        #: compiles" would be vacuously true
        self._role_executors: dict[tuple[int, str], StageExecutor] = {}
        #: non-default registered models: executors keyed per
        #: (model, stage, role) — compile caches and KV pools must never
        #: mix models — and per-model stage splits, both built lazily on
        #: first load and shared by every replica hosting the model
        self._model_executors: dict[tuple[str, int, str], StageExecutor] = {}
        self._model_stages: dict[str, tuple[list, list]] = {}
        self.instantiator = OnlineInstantiator(cluster)
        #: state-transfer subsystem: live handoff + restore, background
        #: snapshots (opt-in via snapshot_interval_s), warm scale-up
        self.migrations = MigrationManager(self)
        self.snapshots: Optional[SnapshotStore] = (
            SnapshotStore(self, interval_s=snapshot_interval_s,
                          codec=snapshot_codec)
            if snapshot_interval_s is not None else None)
        self.bootstrap = WarmBootstrap(self)
        self.replicas: list[list[_Replica]] = [[] for _ in replicas]
        self.client = cluster.worker(CLIENT)
        self.client_router = ReplicaRouter()   # worlds to stage-0 replicas
        self.client_router.set_load_probe(self._edge_load)
        self.client_router.set_drop_listener(self._forget_edge)
        self._responses: dict[int, asyncio.Future] = {}
        #: req_id -> entry world an in-flight round-trip was sent on, so a
        #: world-break fails the waiter immediately instead of letting it
        #: sit out the full step timeout (during which an otherwise-healthy
        #: session idles toward the TTL reap)
        self._response_worlds: dict[int, str] = {}
        self._req_ids = itertools.count()
        self._session_ids = itertools.count(1)
        self._uid = itertools.count()
        self._collectors: dict[str, asyncio.Task] = {}
        #: downstream edge world -> receiving replica (load probing, drain)
        self._world_to_replica: dict[str, _Replica] = {}
        #: worlds the watchdog has fenced anywhere in the pipeline
        self.broken_worlds: set[str] = set()
        #: (t, kind, detail) scale/heal/drain timeline for Fig.5-style plots
        self.events: list[tuple[float, str, str]] = []
        #: causal span tracer — default-ON; ``tracing=False`` is the A/B
        #: baseline the generate bench's overhead gate measures against.
        #: ``trace_sample_rate < 1`` head-samples session roots with
        #: tail-based keep rules (errors/heals/retries/slow outliers always
        #: survive) so tracing cost stays flat at fleet session counts
        self.tracer = Tracer(trace_capacity, enabled=tracing,
                             sample_rate=trace_sample_rate,
                             slow_keep_s=trace_slow_keep_s)
        #: flight recorder: bounded ring of structured control-plane events,
        #: dumped to JSON (under ``dump_dir`` when set) on any unhandled
        #: failure, every heal, or an explicit ``recorder.dump()``
        self.recorder = FlightRecorder(flightrec_capacity, name=name,
                                       dump_dir=dump_dir)
        # pool pressure events (page_alloc_failure) land in the flight
        # recorder's timeline next to the heals/drains they may explain
        for _ex in self.stage_executors:
            _ex.on_event = self.recorder.record
        #: deadline drops carried over from retired replicas — folded in at
        #: teardown so cumulative counters survive scale-down exactly
        self.expired_retired = 0
        #: sid -> running-min relative argmax gap observed at the last
        #: stage; the int8 snapshot path reads this to decide, per session,
        #: whether quantization noise could flip a greedy token
        self.session_margins: dict[int, float] = {}
        #: client-observed per-kind latencies, drained by MetricsHub into
        #: the TTFT / per-token-decode EWMAs the per-role policies consume
        self.ttft_log: list[float] = []
        self.decode_lat_log: list[float] = []
        #: seconds from a response in hand to its tokens appended, summed
        #: (the logits' copy to the host waits for the last stage's program)
        self.token_host_s_sum = 0.0
        self._wired_managers: set[str] = set()
        self._wire_manager(self.client.manager, self.client_router)

    def _is_last(self, stage: int) -> bool:
        return stage == self.n_stages - 1

    # ------------------------------------------------------- int8 margins
    def _margins_wanted(self) -> bool:
        """Track per-session argmax gaps only when an int8 state path can
        consume them — the partition over the vocab axis is cheap but not
        free, and fp snapshots never look at it."""
        return ((self.snapshots is not None
                 and self.snapshots.codec == INT8)
                or self.migrations.codec == INT8)

    def _note_margin(self, sid: int, logits: np.ndarray) -> None:
        """Fold one step's logits into the session's running-min relative
        argmax gap (the int8 codec's parity-margin signal). Called on the
        *client* path, which has already materialized the last-stage logits
        host-side for its own argmax — tracking here costs one extra O(V)
        partition per token and keeps the replicas' serve loops free of
        device syncs."""
        if sid < 0 or not self._margins_wanted():
            return
        m = argmax_margin(logits)
        old = self.session_margins.get(sid)
        self.session_margins[sid] = m if old is None else min(old, m)

    @staticmethod
    def _note_latency(log: list, dt: float) -> None:
        """Append one client-observed latency sample; the logs are drained
        by MetricsHub each poll, so cap the tail for hub-less runs."""
        log.append(dt)
        if len(log) > 4096:
            del log[:2048]

    def _note_tenant(self, tenant: Optional[str], kind: str,
                     dt: float) -> None:
        """Fold one client-observed latency into the tenant's mergeable
        sketch (``kind`` is 'ttft' or 'decode') — the per-tenant SLO
        policies read tails from these, and sketches survive aggregation
        where means cannot. Untagged traffic records nothing."""
        if tenant is None:
            return
        sk = self.tenant_sketches.setdefault(
            tenant, {"ttft": LogSketch(), "decode": LogSketch()})
        sk[kind].insert(dt)

    def role_executor(self, stage: int, role: str = ROLE_BOTH
                      ) -> StageExecutor:
        """The pool executor for (stage, role): the stage-shared one for
        'both' (unchanged behavior), a lazily built role-specialized one —
        own jit cache, role-filtered warm replay — for split pools."""
        if role == ROLE_BOTH:
            return self.stage_executors[stage]
        key = (stage, role)
        ex = self._role_executors.get(key)
        if ex is None:
            if role == ROLE_DRAFT:
                # the whole draft model as one stage: draft replicas talk
                # only to the client, never to pipeline peers, so there is
                # no stage split to share — and no paged pool (draft
                # caches are throwaway contiguous buffers)
                if self.draft_model is None:
                    raise ValueError(
                        "draft role requires draft_model/draft_params")
                ex = StageExecutor.for_model(
                    self.draft_model, self.draft_params,
                    max_len=self.max_len, role=ROLE_DRAFT)
            else:
                ex = StageExecutor(self.cfg, self.stage_specs[stage],
                                   self.stage_param_sets[stage],
                                   max_len=self.max_len, role=role,
                                   paged=self.paged,
                                   page_size=self.page_size,
                                   pool_pages=self.pool_pages)
            ex.on_event = self.recorder.record
            self._role_executors[key] = ex
        return ex

    # ------------------------------------------------------- model registry
    def register_model(self, name: str, model, params) -> None:
        """Make another model servable by this pool (registry store entry;
        no replica hosts it until ``load_model``/``swap_model``)."""
        self.registry.register(name, model, params)
        self._model_stages.pop(name, None)

    def model_stages(self, name: str) -> tuple[list, list]:
        """(stage_specs, stage_param_sets) of a registered model under this
        pipeline's stage split — each model partitions its own layer count
        over the same number of stages the pool runs."""
        cached = self._model_stages.get(name)
        if cached is not None:
            return cached
        if name == self.default_model:
            out = (self.stage_specs, self.stage_param_sets)
        else:
            entry = self.registry.get(name)
            specs = split_stages(entry.cfg, self.n_stages)
            out = (specs, [stage_params(entry.cfg, entry.params, s)
                           for s in specs])
        self._model_stages[name] = out
        return out

    def model_executor(self, name: str, stage: int,
                       role: str = ROLE_BOTH) -> StageExecutor:
        """The shared executor for a non-default model at (stage, role) —
        its own jit cache and KV pool, lazily built from the registry
        store's stage slice."""
        if name == self.default_model:
            return self.role_executor(stage, role)
        key = (name, stage, role)
        ex = self._model_executors.get(key)
        if ex is None:
            entry = self.registry.get(name)
            specs, psets = self.model_stages(name)
            ex = StageExecutor(entry.cfg, specs[stage], psets[stage],
                               max_len=self.max_len, role=role,
                               paged=self.paged, page_size=self.page_size,
                               pool_pages=self.pool_pages)
            ex.on_event = self.recorder.record
            self._model_executors[key] = ex
        return ex

    def _edge_load(self, world: str) -> float:
        """Router load probe: queue depth of the replica behind an edge.
        A fenced, retired, dead, or draining replica scores infinite — the
        probe must never make a world it cannot serve look least loaded
        (client edges have no replica mapping and score neutral)."""
        rep = self._world_to_replica.get(world)
        if rep is None:
            return 0.0
        if (world in self.broken_worlds or not rep.worker.alive
                or rep.draining or rep not in self.replicas[rep.stage]):
            return float("inf")
        return float(rep.queue_depth())

    def _forget_edge(self, world: str) -> None:
        """Drop-listener for every router: a world gracefully retired from
        a rotation loses its replica mapping at once, so no stale probe
        target outlives the retirement (the load-probe prune)."""
        self._world_to_replica.pop(world, None)

    def decode_replicas(self, stage: int, exclude=None,
                        model: Optional[str] = None) -> list["_Replica"]:
        """Replicas able to hold and serve decode state at ``stage`` —
        with ``model=``, only those hosting that model's weights."""
        name = model or None
        return [r for r in self.replicas[stage]
                if r is not exclude and r.worker.alive and not r.draining
                and r.role not in (ROLE_PREFILL, ROLE_DRAFT)
                and (name is None or name in r.resident)]

    def _pick_decode_peer(self, stage: int, exclude: "_Replica",
                          nbytes: int,
                          model: Optional[str] = None
                          ) -> Optional["_Replica"]:
        """The decode-pool home for a freshly prefilled session: ranked by
        (queue load + placement cost of the KV bytes about to move), the
        same ranking every other state-moving chooser uses."""
        peers = self.decode_replicas(stage, exclude=exclude, model=model)
        if not peers:
            return None
        return self.migrations._rank(exclude.worker_id, peers, nbytes)

    def _replica_by_id(self, worker_id: Optional[str],
                       stage: Optional[int] = None) -> Optional["_Replica"]:
        if worker_id is None:
            return None
        stages = [stage] if stage is not None else range(self.n_stages)
        for si in stages:
            for rep in self.replicas[si]:
                if rep.worker_id == worker_id:
                    return rep
        return None

    def _pin_upstream(self, receiver: "_Replica", env: Envelope,
                      home: "_Replica") -> None:
        """Stitch the decode route pool-to-pool during the PREFILL pass:
        the upstream stage's decode home (or the client) pins this session
        onto ``home``'s edge — not onto the prefill replica that merely
        built the cache. For colocated ('both') hops this pins exactly the
        edge the PREFILL travelled on, so the wiring is identical to the
        pre-disaggregation pins; races lose to the ``migrated`` in-process
        forwarding stub, never to a stuck session."""
        sid = env.session_id
        if sid < 0:
            return
        if receiver.stage == 0:
            router, src = self.client_router, CLIENT
        else:
            up = self._replica_by_id(env.home, stage=receiver.stage - 1)
            if up is None:
                return   # upstream home already gone; restore path covers it
            router, src = up.router, up.worker_id
        edge = _edge(self.name, src, home.worker_id)
        if edge in router.healthy():
            router.pin(sid, edge)

    def _event(self, kind: str, detail: str) -> None:
        self.events.append((time.monotonic(), kind, detail))
        # long-lived servers must not grow the timeline forever (the plots
        # only ever read the recent window); the flight recorder keeps the
        # same events in its own bounded ring for crash dumps
        if len(self.events) > 8192:
            del self.events[:4096]
        self.recorder.record(kind, detail=detail)

    # ------------------------------------------------------------------ build
    async def start(self) -> None:
        for si, roles in enumerate(self.replica_roles):
            for role, count in roles.items():
                for _ in range(count):
                    await self.add_replica(si, role=role)
        if self.snapshots is not None:
            # ride on the client worker so Cluster.shutdown reaps the task
            self.snapshots.start(spawn=self.client.spawn)

    def _wire_manager(self, manager, router: Optional[ReplicaRouter]) -> None:
        """Fault listeners: fenced worlds leave the router rotation (dropping
        any session pins) and are recorded in ``broken_worlds`` (the
        controller's failure signal)."""
        if manager.worker_id in self._wired_managers:
            return
        self._wired_managers.add(manager.worker_id)

        def cb(world: str, reason: str) -> None:
            if router is not None:
                router.mark_broken(world)
            self.broken_worlds.add(world)
            # poison in-flight client round-trips on the fenced world: the
            # reply will never come, and waiting out the step timeout can
            # cost more than the failure itself (the session's other state
            # idles toward the TTL reap meanwhile)
            for rid, sent in list(self._response_worlds.items()):
                if sent != world:
                    continue
                fut = self._responses.get(rid)
                if fut is not None and not fut.done():
                    fut.set_exception(WorldBrokenError(world))
            self._event("world_broken", world)

        manager.on_world_broken(cb)

        def world_ev(t: float, kind: str, world: str) -> None:
            # world lifecycle into the flight recorder: create ("init_done")
            # and remove, per endpoint manager. Fencing ("broken") is
            # already recorded via the break listener above.
            if kind in ("init_done", "removed"):
                self.recorder.record(f"world_{kind}", world=world,
                                     worker=manager.worker_id)

        manager.on_event(world_ev)

    async def add_replica(self, stage: int, *, role: str = ROLE_BOTH,
                          warm: bool = False,
                          fresh_executor: bool = False,
                          near: Optional[str] = None,
                          host: Optional[str] = None,
                          models: Optional[list] = None) -> str:
        """Online instantiation of one replica (paper Fig. 2c / §4.2).

        ``role`` selects the pool the replica joins: ``both`` (colocated
        default), ``prefill``, or ``decode``. The role decides which pool
        executor it shares, how upstream routers may route to it, and which
        slice of a peer's shape profile a warm bootstrap replays.

        ``warm=True`` runs the WarmBootstrap first: stage weights are
        fetched from a peer replica over the wire and the peer's served
        shape profile is pre-compiled, all before the replica enters any
        routing rotation — so its first real request hits warm caches.
        ``fresh_executor=True`` additionally gives it its own
        :class:`StageExecutor` (a new worker process would not share the
        peers' jit cache; this models that).

        Placement: ``host=`` pins the new worker to a topology host
        explicitly; ``near=`` places it on another worker's host (the heal
        path passes the failed replica, so its migrated state stays
        on-host); otherwise the topology's placement policy decides. The
        worker is placed *before* the warm bootstrap so the peer choice can
        price the weight bytes it is about to move.

        ``models=`` pre-loads registered non-default models onto the new
        replica (the heal path passes the victim's resident set, so a
        replacement hosts exactly what the dead replica did); each is
        streamed over the LOAD protocol from a resident peer once the
        replica is wired.
        """
        tag = "" if role == ROLE_BOTH else f"{role}-"
        worker_id = f"{self.name}-s{stage}-{tag}r{next(self._uid)}"
        if host is not None:
            self.cluster.topology.place_on(worker_id, host)
        self.cluster.worker(worker_id, near=near)
        rep = _Replica(self, worker_id, stage, role=role)
        self.registry.load(worker_id, self.default_model)
        if role == ROLE_DRAFT:
            # Draft replicas are a client-facing proposer pool, not a
            # pipeline stage: they run the whole draft model against the
            # session's committed history, so they need exactly one
            # client->replica edge (PROPOSE in) and one replica->client
            # edge (proposals out) — no stage peers, no handoff, no warm
            # bootstrap (there is no same-weights pipeline peer to fetch
            # from, and the first prefill compiles the one shape needed).
            w_in = _edge(self.name, CLIENT, worker_id)
            w_out = _edge(self.name, worker_id, CLIENT)
            await self.instantiator.instantiate([
                WorldSpec.pair(w_in, CLIENT, worker_id),
                WorldSpec.pair(w_out, worker_id, CLIENT)])
            rep.watch_upstream(w_in, self.client_router)
            self._world_to_replica[w_in] = rep
            self.client_router.add(w_in, role=ROLE_DRAFT,
                                   models=rep.resident)
            rep.router.add(w_out, role=ROLE_BOTH)
            self._watch_client_world(w_out)
            self._wire_manager(rep.worker.manager, rep.router)
            rep._run_task = rep.worker.spawn(rep.run())
            rep._reap_task = rep.worker.spawn(rep.reap_loop())
            self.replicas[stage].append(rep)
            self._event("add_replica", worker_id)
            return worker_id
        if warm:
            report = await self.bootstrap.bootstrap(
                stage, worker_id, fresh_executor=fresh_executor, role=role)
            rep.executor = report["executor"]
            self._event("warm_bootstrap",
                        f"{worker_id} <- {report['peer']} "
                        f"({report['bytes']}B, warm {report['warm_s']:.3f}s)")
        specs: list[WorldSpec] = []
        #: (world, router to register it in, peer replica or None for client)
        upstream_edges: list[tuple[str, ReplicaRouter, Optional[_Replica]]] = []
        down_watchers: list[tuple[str, Optional[_Replica]]] = []

        if stage == 0:
            w = _edge(self.name, CLIENT, worker_id)
            specs.append(WorldSpec.pair(w, CLIENT, worker_id))
            upstream_edges.append((w, self.client_router, None))
        else:
            for up in self.replicas[stage - 1]:
                if (not up.worker.alive or up.draining
                        or up.role == ROLE_DRAFT):
                    continue
                w = _edge(self.name, up.worker_id, worker_id)
                specs.append(WorldSpec.pair(w, up.worker_id, worker_id))
                upstream_edges.append((w, up.router, up))
        if stage == self.n_stages - 1:
            w = _edge(self.name, worker_id, CLIENT)
            specs.append(WorldSpec.pair(w, worker_id, CLIENT))
            down_watchers.append((w, None))
        else:
            for down in self.replicas[stage + 1]:
                if (not down.worker.alive or down.draining
                        or down.role == ROLE_DRAFT):
                    continue
                w = _edge(self.name, worker_id, down.worker_id)
                specs.append(WorldSpec.pair(w, worker_id, down.worker_id))
                down_watchers.append((w, down))

        await self.instantiator.instantiate(specs)

        # A peer snapshotted above may have been drained/healed away while
        # the rendezvous was in flight — wiring it now would route payloads
        # into a torn-down replica. Re-check and discard the fresh world
        # instead (None peer = the client, which never goes away).
        def _gone(peer: Optional[_Replica], adjacent: list[_Replica]) -> bool:
            return peer is not None and (peer not in adjacent
                                         or not peer.worker.alive
                                         or peer.draining)

        for world, router, up in upstream_edges:
            if _gone(up, self.replicas[stage - 1] if stage else []):
                self._remove_world_everywhere(world)
                continue
            rep.watch_upstream(world, router)
            self._world_to_replica[world] = rep
            # the rotation learns the receiver's role and resident models,
            # so PREFILLs can be steered into the prefill pool and onto a
            # replica that hosts their model
            router.add(world, role=rep.role, models=rep.resident)
        for world, down in down_watchers:
            if _gone(down, self.replicas[stage + 1]
                     if stage < self.n_stages - 1 else []):
                self._remove_world_everywhere(world)
                continue
            rep.router.add(world,
                           role=ROLE_BOTH if down is None else down.role,
                           models=None if down is None else down.resident)
            if down is None:
                self._watch_client_world(world)
            else:
                down.watch_upstream(world, rep.router)
                self._world_to_replica[world] = down

        # replica-side fault listener: broken downstream worlds leave rotation
        self._wire_manager(rep.worker.manager, rep.router)

        rep._run_task = rep.worker.spawn(rep.run())
        rep._reap_task = rep.worker.spawn(rep.reap_loop())
        self.replicas[stage].append(rep)
        # non-default residency (the heal path restores the victim's set):
        # streamed over the LOAD protocol now that the replica is wired
        for m in dict.fromkeys(models or ()):
            if m != self.default_model:
                await self.load_model(worker_id, m, warm=warm)
        self._event("add_replica", worker_id)
        return worker_id

    # ----------------------------------------------------- model residency
    def _retag_replica(self, rep: _Replica) -> None:
        """Push a replica's current resident set onto every upstream
        rotation edge — the routing side of a residency change, applied
        the instant the registry flips so no pick can land a model on a
        replica that no longer (or does not yet) host it."""
        for world, router in rep.upstream_edges:
            router.set_models(world, rep.resident)

    async def load_model(self, worker_id: str, name: str, *,
                         warm: bool = True) -> dict:
        """Hot-load a registered model onto a live replica without it ever
        leaving rotation: stage weights stream from a same-stage resident
        peer as LOAD envelopes (cold from the registry store when no peer
        hosts the model), the registry marks residency (LRU-evicting
        refcount-zero models past ``max_resident_models``), and every
        upstream rotation retags. Returns the bootstrap report."""
        rep = self._replica_by_id(worker_id)
        if rep is None:
            raise KeyError(f"no replica {worker_id}")
        self.registry.get(name)
        if name in rep.resident:
            return {"source": "resident", "bytes": 0, "peer": None}
        report = await self.bootstrap.load_model(rep, name, warm=warm)
        evicted = self.registry.load(worker_id, name)
        for m in evicted:
            rep.resident.discard(m)
            self._event("model_evict", f"{worker_id} -= {m} (LRU)")
        rep.resident.add(name)
        self._retag_replica(rep)
        self._event("model_load",
                    f"{worker_id} += {name} [{report['source']}] "
                    f"({report['bytes']}B)")
        return report

    async def unload_model(self, worker_id: str, name: str, *,
                           force: bool = False,
                           migrate: bool = True) -> None:
        """Retire a model's residency on one replica. Open sessions of that
        model are first live-migrated to another resident replica
        (``migrate=True``); whatever cannot move is dropped so its client
        re-prefills on a capable survivor — unless the registry refuses
        (sessions still pinned and ``force=False``). The default model
        cannot be unloaded (it is the pipeline's identity)."""
        if name == self.default_model:
            raise ResidencyError(
                f"cannot unload the pipeline's default model {name!r}")
        rep = self._replica_by_id(worker_id)
        if rep is None:
            raise KeyError(f"no replica {worker_id}")
        if name not in rep.resident:
            return
        sids = [sid for sid, sess in rep.sessions.items()
                if (sess.model or self.default_model) == name]
        if sids and migrate:
            for sid in sids:
                await self.migrations.migrate_session(rep, sid)
        # stragglers (migration failed / raced in): bounce to re-prefill —
        # client-invisible at-least-once recovery, not a failure
        for sid in [s for s in sids if s in rep.sessions]:
            rep.drop_session(sid)
        self.registry.unload(worker_id, name, force=force)
        rep.resident.discard(name)
        self._retag_replica(rep)
        self._event("model_unload", f"{worker_id} -= {name}")

    async def swap_model(self, worker_id: str, from_name: str,
                         to_name: str, *, warm: bool = True) -> dict:
        """Swap one replica's residency ``from_name`` -> ``to_name`` under
        traffic: stream the incoming model in (SWAP-headed LOAD stream with
        an UNLOAD trailer on the wire), migrate the incumbent model's open
        sessions to other resident replicas, then retire the outgoing
        residency. Refuses up front when the swap would strand open
        sessions with nowhere to go (no other replica at this stage hosts
        ``from_name``) — the controller treats that as "hold"."""
        rep = self._replica_by_id(worker_id)
        if rep is None:
            raise KeyError(f"no replica {worker_id}")
        if from_name not in rep.resident:
            raise ResidencyError(
                f"{worker_id} does not host {from_name!r}")
        retiring = from_name != self.default_model
        incumbent = [sid for sid, sess in rep.sessions.items()
                     if (sess.model or self.default_model) == from_name]
        if retiring and incumbent and not self.decode_replicas(
                rep.stage, exclude=rep, model=from_name):
            raise ResidencyError(
                f"swap {from_name!r}->{to_name!r} on {worker_id} would "
                f"strand {len(incumbent)} open session(s): no other "
                f"replica at stage {rep.stage} hosts {from_name!r}")
        report = await self.bootstrap.load_model(
            rep, to_name, warm=warm, swap_from=from_name)
        evicted = self.registry.load(worker_id, to_name)
        for m in evicted:
            rep.resident.discard(m)
        rep.resident.add(to_name)
        if not retiring:
            # the default model can never retire (untagged traffic must
            # stay routable) — swapping "from" it just adds the target;
            # incumbent sessions stay put and keep serving
            self._retag_replica(rep)
        else:
            # advertise the incoming model at once, but stop NEW sessions
            # of the outgoing one from landing while incumbents migrate
            # off (pinned decode steps bypass rotation picks, so open
            # sessions keep flowing through the whole window)
            advertise = set(rep.resident) - {from_name}
            for world, router in rep.upstream_edges:
                router.set_models(world, advertise)
            for sid in incumbent:
                if sid in rep.sessions:
                    await self.migrations.migrate_session(rep, sid)
            # sweep stragglers — failed migrations and prefills that raced
            # the retag: bounce to re-prefill (at-least-once recovery,
            # client-invisible), so the registry sees zero refs below
            for sid, sess in list(rep.sessions.items()):
                if (sess.model or self.default_model) == from_name:
                    rep.drop_session(sid)
            self.registry.unload(worker_id, from_name)
            rep.resident.discard(from_name)
            self._retag_replica(rep)
        self.swaps_total += 1
        self._event("model_swap",
                    f"{worker_id}: {from_name} -> {to_name} "
                    f"[{report['source']}]")
        return report

    # ------------------------------------------------------------- scale-down
    async def remove_replica(self, stage: int,
                             worker_id: Optional[str] = None, *,
                             role: Optional[str] = None,
                             drain: bool = True,
                             timeout: float = 30.0,
                             migrate: bool = True) -> str:
        """Retire one replica of ``stage``.

        ``drain=True`` (scale-down): first hand every open session off live
        to a same-stage survivor (``migrate=True``, the state-transfer
        path: zero re-prefill, steps held during the handoff and released
        on the survivor), then stop routing to it — which also unpins any
        session that could *not* be migrated, so those relocate through the
        client's re-prefill fallback — then wait until its inbox, in-flight
        work, and adjacent transport channels are all empty, then tear its
        worlds down. Zero request/token loss by construction.
        ``migrate=False`` restores the PR 2 behavior (every open session
        pays a full re-prefill); bench_migrate measures the difference.
        ``drain=False`` (heal): the replica is already dead; just unhook the
        bookkeeping and purge its (broken) worlds so a replacement can be
        instantiated cleanly.

        ``role=`` restricts the victim choice to that pool (the per-role
        scale-down path). Whatever selected the victim, a drain refuses to
        remove the last replica *capable* of a role the victim serves —
        draining the last prefill-capable replica would strand every new
        session, and the last decode-capable one every open session, even
        if other pools still have capacity.
        """
        reps = self.replicas[stage]
        if worker_id is not None:
            rep = next((r for r in reps if r.worker_id == worker_id), None)
            if rep is None:
                raise KeyError(f"no replica {worker_id} in stage {stage}")
        else:
            live = [r for r in reps if r.worker.alive and not r.draining
                    and (role is None or r.role == role)]
            if not live:
                raise RuntimeError(
                    f"stage {stage} has no removable replica"
                    + (f" in role {role!r}" if role else ""))
            rep = min(live, key=lambda r: (r.open_sessions(),
                                           r.queue_depth()))
        if drain:
            others = [r for r in reps if r is not rep
                      and r.worker.alive and not r.draining]
            for cap in (ROLE_PREFILL, ROLE_DECODE):
                if rep.role in ROLE_CAPABLE[cap] and not any(
                        r.role in ROLE_CAPABLE[cap] for r in others):
                    raise RuntimeError(
                        f"refusing to drain the last healthy "
                        f"{cap}-capable replica of stage {stage}")

        rep.draining = True
        self._event("drain_begin", rep.worker_id)
        # 1. live handoff: move every open session's KV state to a survivor
        #    and flip its pins — the client never notices. Sessions that
        #    can't move (no survivor, transfer failure) fall through to the
        #    re-prefill path when their pins drop in step 2.
        #    Draft sessions never migrate: their caches are draft-model
        #    state no decode/prefill survivor could serve, and the client
        #    rebuilds them from the committed history in one PROPOSE —
        #    sessions degrade to plain decode, they do not relocate.
        if drain and migrate and rep.sessions and rep.role != ROLE_DRAFT:
            await self.migrations.migrate_replica_sessions(rep)
        # 2. stop routing new work to it (no new picks can reach these
        #    worlds once removed; an already-picked send has already been
        #    appended to the channel — the drain wait below flushes it).
        #    Removing also drops session pins: open sessions relocate via
        #    the client's re-prefill path instead of waiting forever.
        for world, router in rep.upstream_edges:
            router.remove(world)
        # 2. drain to zero
        if drain:
            await self._drain(rep, timeout)
        # 3. teardown in one event-loop tick
        self._teardown_replica(rep)
        self._event("remove_replica", rep.worker_id)
        return rep.worker_id

    async def _drain(self, rep: _Replica, timeout: float) -> None:
        transport = self.cluster.transport
        deadline = time.monotonic() + timeout

        def flushed() -> bool:
            # broken worlds are excluded: their pump (ours or the peer's)
            # is dead, so whatever sits in those channels can never flush —
            # waiting on them turned every heal-drain of a fenced replica
            # into a guaranteed full-timeout stall. Payloads wedged in a
            # broken world are already lost to the at-least-once resend
            # path, exactly as if the world had been torn down.
            return (rep.inbox.empty() and not rep._stash
                    and rep.inflight == 0
                    and all(transport.pending(w) == 0
                            for w in rep.upstream
                            if w not in self.broken_worlds)
                    and all(transport.pending(w) == 0
                            for w in rep.router.worlds
                            if w not in self.broken_worlds))

        while True:
            # A pump can be suspended on a fairness yield *between* popping a
            # payload off the channel and enqueueing it (neither place counts
            # it) — one scheduler pass lets any such pump land its payload,
            # so only two consecutive flushed observations prove empty.
            if flushed():
                await asyncio.sleep(0)
                if flushed():
                    return
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"drain of {rep.worker_id} exceeded {timeout}s "
                    f"(queue={rep.queue_depth()})")
            await asyncio.sleep(0.005)

    def _teardown_replica(self, rep: _Replica) -> None:
        """Unhook a replica and remove its worlds on every member in one
        synchronous pass — no await between key deletions, so no watchdog
        cycle can observe a half-removed world and fence it spuriously."""
        for task in (rep._run_task, rep._reap_task):
            if task is not None and not task.done():
                task.cancel()
        for sid in list(rep.sessions):
            rep.drop_session(sid)   # paged pages go back to the pool
        rep.held.clear()
        rep.migrated.clear()
        self.expired_retired += rep.expired
        for world in list(rep.upstream):
            rep.drop_upstream(world)
            self._world_to_replica.pop(world, None)
            self._remove_world_everywhere(world)
        for world in list(rep.router.worlds):
            down = self._world_to_replica.pop(world, None)
            if down is not None:
                down.drop_upstream(world)
            collector = self._collectors.pop(world, None)
            if collector is not None and not collector.done():
                collector.cancel()
            rep.router.remove(world)
            self._remove_world_everywhere(world)
        for world in rep.handoff_worlds:
            # persistent handoff channels die with either endpoint; the
            # partner's set keeps a stale name, which is harmless — peers
            # are only ever picked among live replicas
            self._remove_world_everywhere(world)
        rep.handoff_worlds.clear()
        if rep in self.replicas[rep.stage]:
            self.replicas[rep.stage].remove(rep)
        # reclaim the worker: stop its watchdog task and drop it from the
        # cluster registry, or every scale/heal cycle leaks one worker whose
        # heartbeat loop ticks forever
        worker = self.cluster.workers.pop(rep.worker_id, None)
        if worker is not None:
            worker.kill()
            worker.manager.shutdown()
        self.cluster.topology.forget(rep.worker_id)
        # its residencies and session refcounts die with it
        self.registry.drop_worker(rep.worker_id)
        # its worlds and channels are gone with it — drop the transport's
        # death record too, or the map grows one entry per heal forever
        self.cluster.transport.forget_dead(rep.worker_id)
        # the dedup guard is keyed by worker id; a retired id must not
        # block re-wiring if a future replica ever reuses the name
        self._wired_managers.discard(rep.worker_id)

    def _remove_world_everywhere(self, world: str) -> None:
        for worker in list(self.cluster.workers.values()):
            if world in worker.manager.worlds:
                worker.manager.remove_world(world)
        # a torn-down world can never break again — keeping it in the
        # fenced set would grow one entry per kill for the process lifetime
        # (and _drain/_edge_load only consult it for *live* worlds)
        self.broken_worlds.discard(world)

    # ---------------------------------------------------------------- serving
    def _watch_client_world(self, world: str) -> None:
        self._collectors[world] = self.client.spawn(self._collect(world))

    async def _collect(self, world: str) -> None:
        comm = self.client.comm
        try:
            while True:
                env = await comm.recv(0, world)
                fut = self._responses.pop(env.req_id, None)
                if fut is not None and not fut.done():
                    fut.set_result(env)
        except (WorldBrokenError, WorldNotFoundError, asyncio.CancelledError):
            return

    async def _roundtrip(self, env: Envelope, world: str,
                         timeout: float) -> Envelope:
        """Send one envelope to an entry world, await its response envelope.
        Marks the world broken/removed in the client rotation on send
        failure before re-raising."""
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self._responses[env.req_id] = fut
        self._response_worlds[env.req_id] = world
        try:
            await self.client.comm.send(env, 1, world)
            return await asyncio.wait_for(fut, timeout)
        except WorldBrokenError:
            self.client_router.mark_broken(world)
            raise
        except WorldNotFoundError:
            self.client_router.remove(world)
            raise
        finally:
            self._responses.pop(env.req_id, None)
            self._response_worlds.pop(env.req_id, None)
            if fut.done() and not fut.cancelled():
                # the break callback may poison the future while the send
                # itself is raising — consume the exception so asyncio
                # doesn't log it as never-retrieved
                fut.exception()

    async def _restore_replay(self, sid: int, out: list, s0: int,
                              step_timeout: float, *,
                              count_failures: bool = True,
                              parent=None) -> bool:
        """Unplanned-loss recovery, cheap path: rebuild the session's route
        from live survivor state + background snapshots
        (``MigrationManager.restore_session``), then replay only the decode
        steps since the oldest restored cursor — the client still holds
        every generated token, and greedy decode is deterministic, so the
        replayed responses are discarded. Returns True when the session is
        live and caught up; False sends the caller to full re-prefill."""
        t_r = time.monotonic()
        t0 = await self.migrations.restore_session(
            sid, count_failures=count_failures, parent=parent)
        if t0 is None:
            return False
        replayed = 0
        rctx = None
        t_step = t_r
        try:
            # positions t0+1 .. s0+len(out)-2 were generated but lost from
            # every cache; feeding out[k] at position s0+k re-integrates it
            for k in range(t0 + 1 - s0, len(out) - 1):
                world = self.client_router.pinned(sid)
                if world is None:
                    return False
                t_step = time.monotonic()
                rctx = self.tracer.begin(parent)
                env = Envelope(
                    next(self._req_ids), sid, Kind.DECODE, step=s0 + k,
                    deadline=time.monotonic() + step_timeout,
                    payload=out[k][:, None], role=ROLE_DECODE,
                    trace=rctx, model=self.session_models.get(sid),
                    tenant=self.session_tenants.get(sid))
                resp = await self._roundtrip(env, world, step_timeout)
                # the replay ctx rode an envelope a stage may have spanned
                # under — record it even on a bad response so no stage span
                # is left parentless
                self.tracer.record(rctx, "decode_step", t_step,
                                   time.monotonic() - t_step, CLIENT,
                                   "replay")
                rctx = None
                if resp.kind is not Kind.DECODE:
                    return False
                replayed += 1
        except (WorldBrokenError, WorldNotFoundError, asyncio.TimeoutError):
            self.tracer.record(rctx, "decode_step", t_step,
                               time.monotonic() - t_step, CLIENT,
                               "replay_error")
            return False
        finally:
            self.migrations.recomputed_tokens += replayed
        self.tracer.span(parent, "restore_replay", t_r, CLIENT,
                         f"replayed={replayed}")
        return True

    def _live_heal_possible(self, sid: int) -> bool:
        """True while an alive-but-fenced replica still holds this session's
        state live — the controller's heal loop will live-migrate that state
        to a survivor, so a bounced client should wait a grace window and
        re-try the cheap restore path instead of re-prefilling immediately."""
        for stage in range(self.n_stages):
            failed = self.failed_replicas(stage)
            if not failed:
                continue
            for rep in self.replicas[stage]:
                if (rep.worker_id in failed and rep.worker.alive
                        and (sid in rep.sessions or sid in rep.held)):
                    return True
        return False

    async def _restore_with_grace(self, sid: int, out: list, s0: int,
                                  step_timeout: float,
                                  parent=None) -> bool:
        """Cheap-path recovery with a heal grace window: keep re-trying
        restore while a live heal can still deliver this session's state to
        a survivor (see :meth:`_live_heal_possible`); give up to the
        re-prefill fallback as soon as that hope is gone or the window
        closes. The probes suppress the failure counter — one bounce is
        one logical recovery event, counted once on final failure."""
        deadline = time.monotonic() + self.restore_grace_s
        while True:
            if await self._restore_replay(sid, out, s0, step_timeout,
                                          count_failures=False,
                                          parent=parent):
                return True
            if not (self._live_heal_possible(sid)
                    and time.monotonic() < deadline):
                self.migrations.restore_failures += 1
                return False
            await asyncio.sleep(0.02)

    async def _propose_draft(self, sid: int, hist: np.ndarray, k: int,
                             step_timeout: float,
                             tenant: Optional[str]) -> Optional[np.ndarray]:
        """One PROPOSE round against the session's pinned draft replica
        (picked from the draft pool and pinned on first use, so one
        replica accumulates the session's draft cache). ANY failure — no
        draft pool, a draining pool answering RETRY, a killed world, a
        timeout — returns None and unpins, degrading this round to plain
        decode with zero client-visible impact. Draft traffic rides the
        negated session id so the statexfer restore/snapshot machinery
        (keyed on the real sid) never confuses draft-model state with a
        target-model stage slice."""
        key = ("draft", sid)
        world = self.client_router.pinned(key)
        if world is None:
            world = self.client_router.try_pick(self.least_loaded,
                                                role=ROLE_DRAFT)
            if world is None:
                return None
            self.client_router.pin(key, world)
        env = Envelope(next(self._req_ids), -sid, Kind.PROPOSE,
                       step=hist.shape[1] - 1,
                       deadline=time.monotonic() + step_timeout,
                       payload=jnp.asarray(hist, jnp.int32), spec_k=k,
                       role=ROLE_DRAFT, tenant=tenant)
        try:
            resp = await self._roundtrip(env, world, step_timeout)
        except (WorldBrokenError, WorldNotFoundError, asyncio.TimeoutError):
            self.client_router.unpin(key)
            return None
        if resp.kind is not Kind.PROPOSE or resp.payload is None:
            self.client_router.unpin(key)
            return None
        props = np.asarray(resp.payload)
        if props.ndim != 2 or props.shape[1] < 1:
            self.client_router.unpin(key)
            return None
        return props[:, :k].astype(np.int32)

    async def _finish_draft(self, sid: int) -> None:
        """Release the session's draft-side state (pin + draft replica's
        cache); best-effort — the draft TTL reap is the backstop."""
        key = ("draft", sid)
        world = self.client_router.pinned(key)
        self.client_router.unpin(key)
        if world is not None:
            try:
                await self.client.comm.send(
                    Envelope(next(self._req_ids), -sid, Kind.FINISH, step=0),
                    1, world)
            except (WorldBrokenError, WorldNotFoundError):
                pass

    async def _abandon_session(self, sid: int) -> None:
        """The client is giving up on this session id for good (re-prefill
        under a fresh one follows). Surviving stages deliberately kept their
        slices alive for the restore path — sweep what the remaining pins
        can still reach with a best-effort FINISH so that state is released
        now rather than at the TTL reap."""
        world = self.client_router.pinned(sid)
        self.client_router.unpin(sid)
        if world is not None:
            try:
                await self.client.comm.send(
                    Envelope(next(self._req_ids), sid, Kind.FINISH, step=0),
                    1, world)
            except (WorldBrokenError, WorldNotFoundError):
                pass
        await self._finish_draft(sid)
        self.session_margins.pop(sid, None)
        self.session_models.pop(sid, None)
        self.session_tenants.pop(sid, None)

    async def _pick_entry(self, timeout: float,
                          role: Optional[str] = None,
                          model: Optional[str] = None) -> Optional[str]:
        deadline = time.monotonic() + timeout
        while True:
            world = self.client_router.try_pick(self.least_loaded, role=role,
                                                model=model)
            if world is not None:
                return world
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            # the any-world event may already be set while the role's pool
            # is empty (controller still growing it) — bound each wait and
            # re-check the role-filtered rotation
            try:
                await asyncio.wait_for(self.client_router.wait_healthy(),
                                       min(0.05, remaining))
            except asyncio.TimeoutError:
                pass

    async def submit(self, tokens: np.ndarray, *, timeout: float = 30.0,
                     retries: int = 2,
                     model: Optional[str] = None) -> jax.Array:
        """Score a token batch through the pipeline; returns logits (B,S,V).

        Beyond-paper nicety: at-least-once redispatch — if a replica dies
        with the request in flight, the client re-sends after ``timeout``.
        A fully-empty stage-0 rotation (every entry replica down) parks the
        attempt until the controller heals one, instead of failing fast.
        ``model=`` scores under a non-default registered model (the route
        restricts to replicas hosting it).
        """
        x = jnp.asarray(tokens, jnp.int32)
        if model is not None:
            self.registry.get(model)   # fail fast, with a suggestion
        last_err: Optional[Exception] = None
        for _ in range(retries + 1):
            world = await self._pick_entry(timeout, role=ROLE_PREFILL,
                                           model=model)
            if world is None:
                last_err = asyncio.TimeoutError("no healthy entry replica")
                continue
            env = Envelope(next(self._req_ids), -1, Kind.SCORE, payload=x,
                           role=ROLE_PREFILL, model=model)
            try:
                resp = await self._roundtrip(env, world, timeout)
                return resp.payload
            except (WorldBrokenError, WorldNotFoundError,
                    asyncio.TimeoutError) as e:
                last_err = e
        raise RuntimeError(f"request failed after {retries + 1} attempts: "
                           f"{last_err}")

    async def generate(self, prompts: np.ndarray, max_new_tokens: int, *,
                       step_timeout: float = 10.0, max_restarts: int = 32,
                       token_times: Optional[list] = None,
                       model: Optional[str] = None,
                       tenant: Optional[str] = None,
                       spec_k: Optional[int] = None) -> np.ndarray:
        """Greedy autoregressive generation through the pipeline.

        prompts (B, S) int32 -> (B, max_new_tokens) int32: the same greedy
        computation as single-engine ``ServeEngine.generate`` at
        temperature 0. In f32 the tokens are identical; in bf16 on a TPU
        the convoy width changes the matmul tiling, which can swap
        near-tied logits, so parity there is judged on logits.

        Fault story: the session's per-stage KV caches live on the replicas
        that prefilled it. If any of them dies or drains mid-generation, the
        pipeline answers RETRY (or the client's pin check fails, or the step
        times out) and the client re-prefills prompt + everything generated
        so far on surviving replicas — at-least-once recovery with zero
        token loss, since generated tokens only ever live client-side.

        ``model=`` generates under a non-default registered model: routing,
        executors, and recovery all follow the tag, so parity holds against
        that model's own single engine. ``tenant=`` attributes the session
        to a tenant for fair scheduling and per-tenant latency sketches.

        ``spec_k=`` overrides the pipeline's speculative-decoding budget
        for this session (None = pipeline default; 0 = plain decode). With
        a draft pool present, each decode round PROPOSEs k draft tokens
        and VERIFYs them in one batched target dispatch — greedy argmax of
        the target logits at every position, so the output stays token-
        identical to plain decode. Any draft failure silently degrades the
        round to plain decode.
        """
        k_cfg = self.spec_k if spec_k is None else int(spec_k)
        seq = jnp.asarray(prompts, jnp.int32)
        bsz, s0 = seq.shape
        assert s0 + max_new_tokens <= self.max_len, \
            f"{s0}+{max_new_tokens} exceeds pipeline max_len {self.max_len}"
        if model is not None:
            # unknown tags fail fast with a closest-match suggestion
            # instead of parking on a rotation no replica will ever join
            self.registry.get(model)
        if tenant is not None:
            self.tenant_sessions[tenant] = (
                self.tenant_sessions.get(tenant, 0) + 1)
        out: list[np.ndarray] = []
        sid: Optional[int] = None
        hist_len = s0
        base = 0        # tokens already inside the current prefill history
        restarts = 0
        tracer = self.tracer
        # the *client* owns the session's root span: a re-prefill changes
        # the session id but not the trace, so RETRY bounces, restores, and
        # the resumed decode all reconstruct under one tree
        session = tracer.open("mw.client.session", kind="session",
                              worker=CLIENT)
        root = session.ctx
        #: last client step span sent but not yet closed — the failure
        #: handler closes it, so a stage-side child span never outlives an
        #: unrecorded parent (timeouts would otherwise orphan the subtree)
        pending: Optional[Span] = None
        while len(out) < max_new_tokens:
            try:
                if sid is None:
                    # (re-)prefill the full history on any healthy entry
                    hist = (seq if not out else
                            jnp.concatenate([seq, jnp.stack(out, 1)], 1))
                    hist_len = hist.shape[1]
                    base = len(out)
                    world = await self._pick_entry(step_timeout,
                                                   role=ROLE_PREFILL,
                                                   model=model)
                    if world is None:
                        raise _SessionLost("no healthy entry replica")
                    sid = next(self._session_ids)
                    if model is not None:
                        self.session_models[sid] = model
                    if tenant is not None:
                        self.session_tenants[sid] = tenant
                    pending = tracer.open("mw.client.step", root,
                                          kind="ttft", worker=CLIENT)
                    env = Envelope(
                        next(self._req_ids), sid, Kind.PREFILL,
                        step=hist_len - 1,
                        deadline=time.monotonic() + step_timeout,
                        payload=hist, role=ROLE_PREFILL, trace=pending.ctx,
                        model=model, tenant=tenant)
                    resp = await self._roundtrip(env, world, step_timeout)
                    if resp.kind is Kind.RETRY:
                        tracer.close(pending, "retry")
                        pending = None
                        raise _SessionLost("prefill bounced")
                    if resp.kind is Kind.FINISH:
                        raise _SessionLost(resp.error or "server finished")
                    dt = tracer.close(pending)
                    pending = None
                    self._note_latency(self.ttft_log, dt)
                    self._note_tenant(tenant, "ttft", dt)
                    if self.client_router.pinned(sid) is None:
                        # a split stage-0 already stitched the pin onto the
                        # session's decode home during the prefill pass —
                        # only the colocated path pins the entry world here
                        self.client_router.pin(sid, world)
                else:
                    world = self.client_router.pinned(sid)
                    if world is None:
                        raise _SessionLost("entry replica gone")
                    # speculative round: k bounded so even full acceptance
                    # (k proposals + the bonus token) cannot overshoot the
                    # requested generation length
                    k_round = min(k_cfg, max_new_tokens - len(out) - 1)
                    props = None
                    if k_round >= 1:
                        hist_now = np.concatenate(
                            [np.asarray(seq)] +
                            [np.asarray(t)[:, None] for t in out], axis=1)
                        props = await self._propose_draft(
                            sid, hist_now, k_round, step_timeout, tenant)
                        if props is None:
                            # degrade: this round rides the plain DECODE
                            # path below; the next round re-picks a draft
                            self.spec_fallbacks_total += 1
                    if props is not None:
                        pending = tracer.open("mw.client.step", root,
                                              kind="verify_step",
                                              worker=CLIENT)
                        payload = np.concatenate(
                            [np.asarray(out[-1])[:, None], props],
                            axis=1).astype(np.int32)
                        env = Envelope(
                            next(self._req_ids), sid, Kind.VERIFY,
                            step=hist_len + (len(out) - base) - 1,
                            deadline=time.monotonic() + step_timeout,
                            payload=jnp.asarray(payload), spec_k=k_round,
                            role=ROLE_DECODE, trace=pending.ctx, model=model,
                            tenant=tenant)
                        resp = await self._roundtrip(env, world,
                                                     step_timeout)
                        if resp.kind is Kind.RETRY:
                            tracer.close(pending, "retry")
                            pending = None
                            raise _SessionLost("verify bounced")
                        if resp.kind is Kind.FINISH:
                            raise _SessionLost(
                                resp.error or "server finished")
                        dt = tracer.close(pending)
                        pending = None
                        self._note_latency(self.decode_lat_log, dt)
                        self._note_tenant(tenant, "decode", dt)
                        token = tracer.open("mw.client.token", root,
                                            worker=CLIENT)
                        # (B, m+1) accepted prefix + bonus token — every
                        # column is the target model's own greedy argmax,
                        # so appending the whole block preserves parity
                        committed = np.asarray(resp.payload)
                        self.spec_rounds_total += 1
                        self.spec_proposed_total += k_round
                        self.spec_accepted_total += committed.shape[1] - 1
                        t_now = time.monotonic()
                        for j in range(committed.shape[1]):
                            out.append(committed[:, j].astype(np.int32))
                            if tenant is not None:
                                self.tenant_tokens[tenant] = (
                                    self.tenant_tokens.get(tenant, 0)
                                    + bsz)
                            if token_times is not None:
                                token_times.append(t_now)
                        self.token_host_s_sum += tracer.close(token)
                        continue
                    # position of the fed token: history end + tokens
                    # generated since that history was prefilled
                    pending = tracer.open("mw.client.step", root,
                                          kind="decode_step", worker=CLIENT)
                    env = Envelope(
                        next(self._req_ids), sid, Kind.DECODE,
                        step=hist_len + (len(out) - base) - 1,
                        deadline=time.monotonic() + step_timeout,
                        payload=out[-1][:, None], role=ROLE_DECODE,
                        trace=pending.ctx, model=model, tenant=tenant)
                    resp = await self._roundtrip(env, world, step_timeout)
                    if resp.kind is Kind.RETRY:
                        tracer.close(pending, "retry")
                        pending = None
                        raise _SessionLost("decode bounced")
                    if resp.kind is Kind.FINISH:
                        raise _SessionLost(resp.error or "server finished")
                    dt = tracer.close(pending)
                    pending = None
                    self._note_latency(self.decode_lat_log, dt)
                    self._note_tenant(tenant, "decode", dt)
                # greedy pick on the host: the logits are tiny (B,V) and a
                # jax dispatch per token per session would dominate the
                # client loop at smoke scale. The copy waits for the last
                # stage's program, so the token span holds that wait too.
                token = tracer.open("mw.client.token", root, worker=CLIENT)
                logits = np.asarray(resp.payload)
                self._note_margin(sid, logits)
                tok = np.argmax(logits, axis=-1).astype(np.int32)
                out.append(tok)
                if tenant is not None:
                    self.tenant_tokens[tenant] = (
                        self.tenant_tokens.get(tenant, 0) + bsz)
                if token_times is not None:
                    token_times.append(time.monotonic())
                self.token_host_s_sum += tracer.close(token)
            except (_SessionLost, asyncio.TimeoutError,
                    WorldBrokenError, WorldNotFoundError) as e:
                if pending is not None:
                    # the step died without a response; close its span so
                    # any stage-side child recorded before the failure
                    # still parents back into the tree
                    tracer.close(pending, f"error={type(e).__name__}")
                    pending = None
                restarts += 1
                if restarts > max_restarts:
                    tracer.close(session, f"error={type(e).__name__}")
                    raise RuntimeError(
                        f"generation failed after {max_restarts} session "
                        f"restarts: {e}") from e
                if sid is not None:
                    if out and await self._restore_with_grace(
                            sid, out, s0, step_timeout, parent=root):
                        # session restored + caught up: resume decoding with
                        # the step arithmetic re-anchored to the raw prompt
                        hist_len, base = s0, 0
                        continue
                    await self._abandon_session(sid)
                    if out:
                        self.migrations.reprefills_total += 1
                        self.migrations.recomputed_tokens += s0 + len(out)
                        # zero-length marker span: the recovery fell through
                        # to the full re-prefill path (the PREFILL that
                        # follows carries its own ttft span under root)
                        tracer.span(root, "reprefill", time.monotonic(),
                                    CLIENT, str(e))
                sid = None           # forces re-prefill with full history
        if sid is not None:
            world = self.client_router.pinned(sid)
            self.client_router.unpin(sid)
            if world is not None:
                env = Envelope(next(self._req_ids), sid, Kind.FINISH,
                               step=hist_len + (len(out) - base) - 1)
                try:
                    await self.client.comm.send(env, 1, world)
                except (WorldBrokenError, WorldNotFoundError):
                    pass
            await self._finish_draft(sid)
            if self.snapshots is not None:
                # eager snapshot GC; the background sweep + TTL are backstops
                self.snapshots.drop_session(sid)
            self.session_margins.pop(sid, None)
            self.session_models.pop(sid, None)
            self.session_tenants.pop(sid, None)
        tracer.close(session, f"tokens={len(out)} restarts={restarts}")
        return np.stack([np.asarray(t) for t in out], axis=1)

    # ------------------------------------------------------------------ intro
    def healthy_replicas(self, stage: int,
                         role: Optional[str] = None) -> list[str]:
        out = []
        for rep in self.replicas[stage]:
            if not rep.worker.alive or rep.draining:
                continue
            if role is not None and rep.role != role:
                continue
            out.append(rep.worker_id)
        return out

    def failed_replicas(self, stage: int) -> list[str]:
        """Heal candidates: replicas the watchdog has cut off — every
        upstream edge fenced, so no traffic can reach them (or the worker
        is outright dead)."""
        out = []
        for rep in self.replicas[stage]:
            if rep.draining:
                continue
            dead = not rep.worker.alive
            cut_off = bool(rep.upstream) and all(
                w in self.broken_worlds for w in rep.upstream)
            if dead or cut_off:
                out.append(rep.worker_id)
        return out

    def open_sessions(self, stage: int) -> int:
        return sum(r.open_sessions() for r in self.replicas[stage]
                   if r.worker.alive)

    def replica_stats(self) -> dict[str, dict[str, Any]]:
        """Introspection snapshot of the raw per-replica load counters
        (MetricsHub reads the ``_Replica`` attributes directly; this is the
        public debugging/dashboard view of the same signals)."""
        out: dict[str, dict[str, Any]] = {}
        for stage, reps in enumerate(self.replicas):
            for rep in reps:
                out[rep.worker_id] = {
                    "stage": stage,
                    "role": rep.role,
                    "alive": rep.worker.alive,
                    "draining": rep.draining,
                    "queue_depth": rep.queue_depth(),
                    "inflight": rep.inflight,
                    "processed": rep.processed,
                    "wait_s_sum": rep.wait_s_sum,
                    "service_s_sum": rep.service_s_sum,
                    "parked": rep.parked,
                    "tokens_out": rep.tokens_out,
                    "open_sessions": rep.open_sessions(),
                    "decode_batches": rep.decode_batches,
                    "decode_steps": rep.decode_steps,
                    "decode_wait_s_sum": rep.decode_wait_s_sum,
                    "dispatch_s_sum": rep.dispatch_s_sum,
                    "exec_s_sum": rep.exec_s_sum,
                    "polls_empty": rep.worker.comm.polls_empty,
                    "retries_sent": rep.retries_sent,
                    "expired": rep.expired,
                    "held_sessions": len(rep.held),
                    "migrated_away": len(rep.migrated),
                    "prefills": rep.prefills,
                    "handoffs_out": rep.handoffs_out,
                    "models": sorted(rep.resident),
                    "tenant_served": dict(rep.tenant_served),
                    "spec_verifies": rep.spec_verifies,
                    "spec_proposed": rep.spec_proposed,
                    "spec_accepted": rep.spec_accepted,
                    "spec_proposals": rep.spec_proposals,
                }
        return out

    def client_stats(self) -> dict[str, float]:
        """The client's host-path counters, cumulative: ``token_host_s_sum``
        and the empty polls of its communicator (``replica_stats`` has each
        replica's)."""
        return {"token_host_s_sum": self.token_host_s_sum,
                "polls_empty": self.client.comm.polls_empty}
