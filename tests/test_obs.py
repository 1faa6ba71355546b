"""Observability subsystem: causal spans, flight recorder, export surface.

The acceptance bar (ISSUE 6): a generate session that loses its decode
replica mid-generation reconstructs as ONE connected trace tree — RETRY
bounce, snapshot restore (or re-prefill), and the resumed decode all parent
back to the client's root span, with no orphans; default-on tracing stays
within the overhead budget (gated in bench_generate); flight-recorder dumps
are schema-versioned; retired replicas leave no per-id state behind.
"""
import asyncio
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.configs import get_smoke
from repro.control import MetricsHub
from repro.core import Cluster, FailureKind
from repro.models import DENSE, BlockGroup, build_model
from repro.obs import (
    FlightRecorder,
    TraceContext,
    Tracer,
    connected_tree,
    validate_dump,
)
from repro.obs.export import render_prometheus, write_trace_artifact
from repro.serving import PipelineServer

CFG = get_smoke("llama3.2-1b").with_(num_layers=2,
                                     groups=(BlockGroup(DENSE, 2),))
MODEL = build_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))


def _prompts(n, seq=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (1, seq)) for _ in range(n)]


async def _warm(server, sessions=4):
    ps = _prompts(sessions, seed=99)
    for _ in range(2):
        await asyncio.gather(*(server.generate(p, 3, step_timeout=120.0)
                               for p in ps))
    for seq in (12, 20):
        await server.generate(_prompts(1, seq=seq, seed=90 + seq)[0], 2,
                              step_timeout=120.0)


async def _wait_open(server, stage, n, timeout=15.0):
    deadline = time.monotonic() + timeout
    while sum(r.open_sessions() for r in server.replicas[stage]) < n:
        if time.monotonic() > deadline:
            break
        await asyncio.sleep(0.005)


# --------------------------------------------------------------- tracer unit
def test_tracer_ring_summary_and_overflow():
    tr = Tracer(capacity=4)
    root = tr.begin()
    assert (root.trace_id, root.parent_id) == (root.span_id, 0)
    child = tr.begin(root)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    for i in range(6):                       # 6 records through a 4-slot ring
        tr.record(tr.begin(root), "decode_step", 0.0, 0.01 * (i + 1))
    assert tr.recorded == 6 and tr.dropped == 2
    spans = tr.spans()
    assert len(spans) == 4                   # oldest two overwritten
    assert [round(s["dt"], 2) for s in spans] == [0.03, 0.04, 0.05, 0.06]
    s = tr.summary()["decode_step"]
    assert s["count"] == 4 and s["max_s"] == pytest.approx(0.06)
    # spans() filtered to one tree only sees that tree
    assert all(x["trace_id"] == root.trace_id
               for x in tr.spans(root.trace_id))


def test_tracer_disabled_and_orphan_guard():
    tr = Tracer(enabled=False)
    assert tr.begin() is None
    tr.record(None, "session", 0.0, 1.0)     # no-op, no raise
    assert tr.recorded == 0 and tr.spans() == []
    on = Tracer()
    # span() on a None parent must NOT mint an orphan root: untraced
    # envelopes (tracing toggled off upstream) stay invisible
    assert on.span(None, "prefill", time.monotonic()) is None
    assert on.recorded == 0


def _mirrored(log_dir) -> list[dict]:
    """The ``mw.*`` host events of the profile written under ``log_dir``."""
    import glob
    import warnings
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("mw.", "outer")):
                    with warnings.catch_warnings():
                        # the binding's stats type warns on introspection
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                    out.append({"name": e.name, "t0": e.start_ns,
                                "t1": e.start_ns + e.duration_ns,
                                "stats": stats})
    return sorted(out, key=lambda e: e["t0"])


def test_tracer_mirrors_open_spans_into_the_profile(tmp_path):
    """Spans held open across the awaits of interleaved coroutines come
    out of a profile with their names, stats and times, inside the
    annotation around them; the layer spans stay out of the ring."""
    tr = Tracer()

    async def step(i, root):
        s = tr.open("mw.client.step", root, kind="decode_step",
                    worker="client")
        await asyncio.sleep(0.002 * (3 - i))
        q = tr.open("mw.replica.queue", s.ctx, worker=f"w{i}", stage=i)
        await asyncio.sleep(0.001)
        tr.close(q)
        tr.close(s)

    async def main():
        session = tr.open("mw.client.session", kind="session",
                          worker="client")
        await asyncio.gather(*(step(i, session.ctx) for i in range(3)))
        await asyncio.get_running_loop().run_in_executor(
            None, lambda: tr.close(tr.open("mw.exec.decode_many", stage=1,
                                           worker="w1", width=3)))
        tr.close(session)
        return session.ctx

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("outer"):
            root = asyncio.run(main())
    ev = _mirrored(tmp_path)
    outer = [e for e in ev if e["name"] == "outer"][0]
    ev = [e for e in ev if e["name"] != "outer"]
    names = sorted(e["name"] for e in ev)
    assert names == (["mw.client.session"] + ["mw.client.step"] * 3
                     + ["mw.exec.decode_many"] + ["mw.replica.queue"] * 3)
    assert all(outer["t0"] <= e["t0"] < e["t1"] <= outer["t1"] for e in ev)
    sess = [e for e in ev if e["name"] == "mw.client.session"][0]
    assert sess["stats"]["span_id"] == root.span_id
    assert sess["stats"]["worker"] == "client"
    steps = {e["stats"]["span_id"]: e for e in ev
             if e["name"] == "mw.client.step"}
    # interleaved: the step started first waits longest, so ends last
    assert len(steps) == 3
    for q in (e for e in ev if e["name"] == "mw.replica.queue"):
        s = steps[q["stats"]["parent_id"]]
        assert s["t0"] <= q["t0"] < q["t1"] <= s["t1"]
        assert q["stats"]["trace_id"] == root.trace_id
        assert q["stats"]["worker"] == f"w{q['stats']['stage']}"
    for s in steps.values():
        assert s["stats"]["parent_id"] == root.span_id
        assert sess["t0"] <= s["t0"] < s["t1"] <= sess["t1"]
    ex = [e for e in ev if e["name"] == "mw.exec.decode_many"][0]
    assert (ex["stats"]["stage"], ex["stats"]["width"]) == (1, 3)
    assert "trace_id" not in ex["stats"]
    # the ring keeps its kinds; the layer spans are the mirror's alone
    assert sorted(s["kind"] for s in tr.spans()) == (["decode_step"] * 3
                                                     + ["session"])


def test_tracer_mirrors_nothing_without_a_profile_or_when_off(tmp_path):
    on, off = Tracer(), Tracer(enabled=False)
    before = on.open("mw.client.session", kind="session")
    assert on.close(before) >= 0.0           # opened with no profile
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("outer"):
            span = off.open("mw.client.step", kind="decode_step")
            time.sleep(0.001)
            assert off.close(span) >= 0.001   # counters still get time
    assert [e["name"] for e in _mirrored(tmp_path)] == ["outer"]
    assert off.spans() == [] and on.recorded == 1


def test_connected_tree_detects_orphans_and_forests():
    def mk(span, parent, trace=1):
        return {"trace_id": trace, "span_id": span, "parent_id": parent,
                "kind": "x", "worker": "", "t0": 0.0, "dt": 0.0,
                "detail": ""}
    assert connected_tree([mk(1, 0), mk(2, 1), mk(3, 1), mk(4, 2)])
    assert not connected_tree([mk(1, 0), mk(3, 2)])          # orphan parent
    assert not connected_tree([mk(1, 0), mk(2, 0)])          # two roots
    assert not connected_tree([])


# ------------------------------------------------------- flight recorder unit
def test_flight_recorder_dump_schema(tmp_path):
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path), name="t")
    for i in range(12):
        rec.record("scale_decision", stage=0, delta=1, reason=f"vote {i}")
    assert len(rec) == 8 and rec.recorded == 12
    d = rec.dump("unhandled_failure", worker="w1",
                 oddball=object())            # coerced to str at dump time
    assert validate_dump(d)
    assert d["dropped"] == 4
    assert d["reason"] == "unhandled_failure"
    assert all(ev["kind"] == "scale_decision" for ev in d["events"])
    assert isinstance(d["context"]["oddball"], str)
    assert rec.dumps_total == 1 and rec.last_dump is d
    assert list(rec.dump_log) == [d]
    # the file landed and round-trips
    with open(d["path"]) as f:
        assert validate_dump(json.load(f))
    # tampering breaks validation
    assert not validate_dump({**d, "schema": "flightrec/v0"})
    assert not validate_dump({k: v for k, v in d.items() if k != "events"})


# ----------------------------------------------------------- export surface
def test_render_prometheus_format():
    text = render_prometheus({
        "latency": {"ttft_s": 0.25, "skip_me": "not-a-number"},
        "stage": {"replicas": {"0": 2, "1": 3}},
    }, prefix="repro")
    assert "# TYPE repro_latency_ttft_s gauge" in text
    assert "repro_latency_ttft_s 0.25" in text
    assert 'repro_stage_replicas{id="0"} 2' in text
    assert 'repro_stage_replicas{id="1"} 3' in text
    assert "skip_me" not in text


def test_trace_artifact_writer(tmp_path):
    tr = Tracer()
    tr.record(tr.begin(), "session", 0.0, 1.0)
    rec = FlightRecorder()
    rec.record("pin_flip", session=7)
    path = str(tmp_path / "TRACE_t.json")
    art = write_trace_artifact(path, suite="t", tracer=tr, recorder=rec,
                               extra={"phases": {"a": {}}})
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["schema"] == "trace/v1"
    assert on_disk["suite"] == "t"
    assert on_disk["span_summary"]["session"]["count"] == 1
    assert on_disk["flight_events"] == 1
    assert art["spans_recorded"] == 1


def test_bench_json_schema(tmp_path):
    from benchmarks.common import write_bench_json
    rows = [("x_tokens_per_s", 10.0, "d1"), ("y_p50_ms", 2.0, ""),
            ("z_bytes", 3.0, ""), ("w_speedup", 2.5, ""),
            ("q_recover_s/variant", 0.5, "per-variant row")]
    doc = write_bench_json(str(tmp_path / "BENCH_t.json"), suite="t",
                           rows=rows, raw={"k": "v"}, tiny=True)
    with open(tmp_path / "BENCH_t.json") as f:
        on_disk = json.load(f)
    assert on_disk == json.loads(json.dumps(doc, default=str))
    assert doc["schema"] == "bench/v1" and doc["suite"] == "t"
    assert doc["tiny"] is True and "git_rev" in doc and "wall_clock" in doc
    m = doc["metrics"]
    assert m["x_tokens_per_s"] == {"value": 10.0, "unit": "tokens/s",
                                   "derived": "d1"}
    assert m["y_p50_ms"]["unit"] == "ms"
    assert m["z_bytes"]["unit"] == "bytes"
    assert m["w_speedup"]["unit"] == "ratio"
    assert m["q_recover_s/variant"]["unit"] == "s"   # unit from metric part
    assert doc["raw"] == {"k": "v"}


# ----------------------------------------------- end-to-end: recovery trace
def test_kill_recovery_yields_one_connected_trace(arun):
    """Kill the decode replica mid-generation (snapshots on): every
    session's RETRY bounce, restore (or re-prefill) and resumed decode must
    reconstruct as ONE tree under the client root — no orphan spans."""
    async def scenario():
        cluster = Cluster(heartbeat_interval=0.01, heartbeat_timeout=0.08)
        server = PipelineServer(cluster, MODEL, PARAMS, [1, 2], max_len=64,
                                snapshot_interval_s=0.05)
        await server.start()
        sessions, new_tokens = 3, 8
        await _warm(server, sessions)
        ps = _prompts(sessions, seed=2)
        tasks = [asyncio.ensure_future(
            server.generate(p, new_tokens, step_timeout=3.0))
            for p in ps]
        await _wait_open(server, 1, sessions)
        await server.snapshots.sweep()
        victim = max((r for r in server.replicas[1] if r.worker.alive),
                     key=lambda r: r.open_sessions())
        cluster.kill(victim.worker_id, FailureKind.SILENT_HANG)
        outs = await asyncio.gather(*tasks)
        assert all(o.shape == (1, new_tokens) for o in outs)

        tracer = server.tracer
        roots = [s for s in tracer.spans() if s["kind"] == "session"]
        # warm-up + measured sessions each own exactly one root
        assert len(roots) >= sessions
        recovery_kinds = {"restore", "restore_replay", "reprefill"}
        recovered_trees = 0
        for root in roots:
            tree = tracer.spans(root["trace_id"])
            assert connected_tree(tree), \
                f"trace {root['trace_id']} has orphans: {tree}"
            kinds = {s["kind"] for s in tree}
            assert {"ttft", "prefill"} <= kinds, kinds
            if kinds & recovery_kinds:
                recovered_trees += 1
                # the resumed decode rides the SAME tree as the recovery
                assert "decode_step" in kinds or "decode" in kinds
        assert recovered_trees >= 1, \
            "kill recovered without any recovery span reaching a trace"
        # bounced steps surface in-tree, not as losses: some client span
        # carries the retry/error detail
        details = {s["detail"] for s in tracer.spans()}
        assert any(d.startswith(("retry", "error=")) for d in details), \
            details
        m = server.migrations.stats()
        assert m["restores_total"] + m["reprefills_total"] >= 1
        cluster.shutdown()

    arun(scenario(), timeout=300.0)


# ------------------------------------------------- retired-state regression
def test_retired_replicas_leave_no_per_id_state(arun):
    """Scale/heal cycles must not grow per-world or per-replica maps:
    hub EWMAs, event mirrors, broken-world sets, manager wiring, and the
    transport's dead-set all evict retired ids."""
    async def scenario():
        cluster = Cluster(heartbeat_interval=0.01, heartbeat_timeout=0.08)
        server = PipelineServer(cluster, MODEL, PARAMS, [1, 1], max_len=64,
                                snapshot_interval_s=0.05)
        await server.start()
        hub = MetricsHub(server)
        await _warm(server, 2)
        hub.poll()
        # two add/drain cycles plus one kill/teardown cycle
        retired = []
        for _ in range(2):
            wid = await server.add_replica(1)
            await server.generate(_prompts(1, seed=5)[0], 3,
                                  step_timeout=120.0)
            hub.poll()
            await server.remove_replica(1, wid, drain=True, timeout=30.0)
            retired.append(wid)
        wid = await server.add_replica(1)
        cluster.kill(wid, FailureKind.SILENT_HANG)
        # let the watchdogs fence it, then tear it down like a heal would
        deadline = time.monotonic() + 10.0
        while wid not in server.failed_replicas(1):
            assert time.monotonic() < deadline, "fence never landed"
            await asyncio.sleep(0.01)
        await server.remove_replica(1, wid, drain=False)
        retired.append(wid)
        hub.poll()

        live = {r.worker_id for reps in server.replicas for r in reps}
        for d in (hub._prev, hub._tput, hub._lat, hub._toks,
                  hub._ttft, hub._declat):
            assert set(d) <= live, f"hub kept retired state: {set(d) - live}"
        assert hub._subscribed <= set(server.cluster.workers)
        for wid in retired:
            assert wid not in server._wired_managers
            assert wid not in server.cluster.transport._dead, \
                "teardown left the transport dead-set entry behind"
        # no fenced world of a torn-down replica lingers
        for world in server.broken_worlds:
            assert any(world in w.manager.worlds
                       for w in cluster.workers.values()), \
                f"broken_worlds kept a removed world {world}"
        # bounded event mirrors: the trim paths engage past the cap
        for _ in range(9000):
            server._event("synthetic", "x")
        assert len(server.events) <= 8192
        mgr = next(iter(cluster.workers.values())).manager
        for _ in range(9000):
            mgr._event("synthetic", "w")
        assert len(mgr.events) <= 8192
        cluster.shutdown()

    arun(scenario(), timeout=300.0)


# ------------------------------------------------ stable program names
@pytest.mark.parametrize("stage", [0, 1])
def test_stage_programs_carry_their_stage_and_call_in_their_name(stage):
    """XLA takes the jitted function's name for the program's module name,
    which the device trace shows: ``s<stage>_<call>``, not ``<lambda>``."""
    import jax.numpy as jnp
    from repro.serving.executor import StageExecutor
    from repro.serving.partition import (split_stages, stage_init_cache,
                                         stage_params)
    spec = split_stages(CFG, 2)[stage]
    ex = StageExecutor(CFG, spec, stage_params(CFG, PARAMS, spec),
                       max_len=16)
    x = (jnp.zeros((1, 8), jnp.int32) if spec.first else
         jnp.zeros((1, 8, CFG.d_model), CFG.activation_dtype))
    step = x[:, :1]
    cache = stage_init_cache(CFG, spec, 1, 16)
    assert f"@jit_s{stage}_prefill" in ex._prefill.lower(
        ex.sparams, x).as_text()
    assert f"@jit_s{stage}_decode_many" in ex._decode_many.lower(
        ex.sparams, (cache, cache), (step, step),
        jnp.zeros(2, jnp.int32)).as_text()


# ------------------------------------------------ host-path counters
def test_host_path_counters_count_what_the_pipeline_served(arun):
    """A ``[1, 2]`` pipeline's host-path sums, as ``replica_stats`` and
    ``client_stats`` give them: each is zero where its count
    (``decode_steps``, ``decode_batches``, tokens returned) is, and grows
    with it; the executor call lies inside its dispatch; the empty polls
    are every communicator's of the cluster."""
    keys = ("decode_steps", "decode_batches", "decode_wait_s_sum",
            "dispatch_s_sum", "exec_s_sum", "polls_empty", "stage")

    def snap(server):
        reps = {wid: {k: st[k] for k in keys}
                for wid, st in server.replica_stats().items()}
        polls = sum(w.comm.polls_empty
                    for w in server.cluster.workers.values())
        return reps, server.client_stats(), polls

    async def scenario():
        cluster = Cluster()
        server = PipelineServer(cluster, MODEL, PARAMS, [1, 2], max_len=64)
        await server.start()
        await _warm(server, 3)
        snaps, outs = [snap(server)], []
        for seed in (5, 6):
            outs.append(await asyncio.gather(*(
                server.generate(p, 5, step_timeout=120.0)
                for p in _prompts(3, seed=seed))))
            snaps.append(snap(server))
        cluster.shutdown()
        return snaps, outs

    snaps, outs = arun(scenario(), timeout=300.0)
    for (reps0, client0, _), (reps1, client1, polls1) in zip(snaps,
                                                             snaps[1:]):
        assert polls1 == client1["polls_empty"] + sum(
            st["polls_empty"] for st in reps1.values())
        assert client1["polls_empty"] >= client0["polls_empty"] >= 0
        assert client1["token_host_s_sum"] > client0["token_host_s_sum"]
        for wid, st1 in reps1.items():
            d = {k: st1[k] - reps0[wid][k] for k in keys}
            assert all(st1[k] >= 0 for k in keys)
            assert (d["decode_wait_s_sum"] > 0) == (d["decode_steps"] > 0)
            assert (d["dispatch_s_sum"] > 0) == (d["decode_batches"] > 0)
            assert (d["exec_s_sum"] > 0) == (d["decode_batches"] > 0)
            # the call on its worker thread lies inside its dispatch
            assert d["exec_s_sum"] <= d["dispatch_s_sum"]
            assert d["polls_empty"] >= 0
        # every stage served each of the three sessions' four decode steps
        for stage in (0, 1):
            assert sum(st1["decode_steps"] - reps0[w]["decode_steps"]
                       for w, st1 in reps1.items()
                       if st1["stage"] == stage) == 3 * 4
    assert all(o.shape == (1, 5) for batch in outs for o in batch)


# ------------------------------------------------------- hub export smoke
def test_metricshub_prometheus_and_trace_summary(arun):
    async def scenario():
        cluster = Cluster()
        server = PipelineServer(cluster, MODEL, PARAMS, [1, 1], max_len=64)
        await server.start()
        hub = MetricsHub(server)
        await server.generate(_prompts(1, seed=7)[0], 4, step_timeout=120.0)
        hub.poll()
        ts = hub.trace_summary()
        assert ts["session"]["count"] >= 1
        assert ts["ttft"]["count"] >= 1 and ts["ttft"]["p50_s"] > 0
        assert ts["decode_step"]["count"] >= 1
        text = hub.export_prometheus()
        assert "# TYPE repro_obs_spans_recorded gauge" in text
        assert 'repro_stage_replicas{id="0"} 1' in text
        assert "repro_span_session_count" in text
        assert "repro_executor_decode_steps" in text
        assert "repro_migration_migrations_total" in text
        cluster.shutdown()

    arun(scenario(), timeout=300.0)
