"""Paged KV-cache benchmark: the PagePool + paged decode path A/B'd
against the contiguous per-session cache baseline.

Three phases, mirroring the acceptance gates (ISSUE 7):

* **capacity** — sessions resident at *equal cache memory*. All sessions
  share one long system prompt; the paged pool maps the shared prefix to
  one physical copy (radix-trie page reuse), so a byte budget that holds K
  contiguous sessions must hold >= 1.5x K paged sessions (the gate). The
  run also exercises the exhaustion edge: the first session past capacity
  degrades to a contiguous cache (flight ``page_alloc_failure``), never
  crashes.
* **bytes** — state-transfer cost on a split prefill/decode stage, paged
  vs contiguous: the prefill->decode handoff and the background snapshot
  ship only a session's *used pages* instead of the whole ``max_len``
  buffer. Gates: paged handoff bytes and per-snapshot bytes strictly below
  contiguous, with greedy token parity across the handoff in both modes.
* **parity** — unplanned kill with background snapshots on, paged mode:
  sessions restore from page-granular snapshots (pages install directly
  into the survivor's pool) and finish with exact greedy tokens.

  PYTHONPATH=src python -m benchmarks.bench_paged [--tiny] [--json OUT]

``--tiny`` shrinks sequence lengths and session counts for CI smoke; every
gate above is structural (memory accounting, byte counts, token equality),
so they hold in tiny mode too.
"""
from __future__ import annotations

import argparse
import asyncio
import time

import jax
import numpy as np

from repro.configs import get_smoke
from repro.core import Cluster, FailureKind
from repro.models import DENSE, BlockGroup, build_model
from repro.serving import PipelineServer, ServeEngine, StageExecutor
from repro.serving.kvpool import PagedCacheHandle
from repro.serving.partition import split_stages, stage_params
from repro.statexfer import cache_nbytes

from .common import (collect_obs, run_async, trace_path_for,
                     write_bench_json, write_trace_json)


def _build():
    cfg = get_smoke("llama3.2-1b").with_(num_layers=2,
                                         groups=(BlockGroup(DENSE, 2),))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _shared_prefix_prompts(cfg, n, *, system, tail, seed):
    """n prompts sharing one ``system``-token system prompt followed by a
    ``tail``-token unique suffix each."""
    rng = np.random.default_rng(seed)
    sys_ids = rng.integers(0, cfg.vocab_size, (1, system))
    return [np.concatenate(
        [sys_ids, rng.integers(0, cfg.vocab_size, (1, tail))], axis=1)
        for _ in range(n)]


# ------------------------------------------------------------------ capacity

def _capacity_scenario(tiny: bool) -> dict:
    """Executor-level residency under one byte budget. The contiguous
    baseline's capacity is the budget by construction (every session owns a
    full ``max_len`` cache); the paged pool is sized to exactly that many
    bytes and admits sessions until the free list runs dry."""
    cfg, model, params = _build()
    max_len = 64 if tiny else 512
    page = 8 if tiny else 16
    system = 32 if tiny else 256
    tail = page                      # one unique full page per session
    budget_sessions = 2 if tiny else 4
    pages_per_seq = max_len // page

    spec = split_stages(cfg, 1)[0]
    sp = stage_params(cfg, params, spec)
    ex = StageExecutor(cfg, spec, sp, max_len=max_len, paged=True,
                       page_size=page,
                       pool_pages=budget_sessions * pages_per_seq + 1)
    events: list = []
    ex.on_event = lambda kind, **f: events.append((kind, f))
    ex_contig = StageExecutor(cfg, spec, sp, max_len=max_len)

    prompts = _shared_prefix_prompts(
        cfg, 4 * budget_sessions * pages_per_seq, system=system, tail=tail,
        seed=1)
    _, contig_cache = ex_contig.prefill(jax.numpy.asarray(prompts[0]))
    contig_bytes = cache_nbytes(contig_cache)

    resident = []
    degraded = False
    for x in prompts:
        out, cache = ex.prefill(jax.numpy.asarray(x))
        if not isinstance(cache, PagedCacheHandle):
            degraded = True          # pool exhausted at prefill: contiguous
            break
        t = x.shape[1]
        for _ in range(2):           # a couple of live decode steps each
            last = np.asarray(out)
            last = last[:, -1] if last.ndim == 3 else last  # prefill (B,1,V)
            tok = last.argmax(-1).astype(np.int32).reshape(1, 1)
            out, cache = ex.decode(cache, jax.numpy.asarray(tok), t)
            t += 1
        if not isinstance(cache, PagedCacheHandle):
            degraded = True          # exhausted mid-decode: degraded, alive
            break
        resident.append(cache)
    # equal-memory accounting: the pool's usable pages hold exactly the
    # bytes of ``budget_sessions`` contiguous caches (page_nbytes is known
    # once the first install binds the leaf shapes)
    pool = ex._ensure_pool()
    pool_bytes = (pool.num_pages - 1) * pool.page_nbytes
    assert pool_bytes == budget_sessions * contig_bytes, \
        (pool_bytes, budget_sessions, contig_bytes)
    stats = pool.stats()
    out = {
        "max_len": max_len, "page_size": page,
        "system_prompt_tokens": system,
        "budget_sessions_contiguous": budget_sessions,
        "cache_bytes_contiguous": contig_bytes,
        "pool_bytes": pool_bytes,
        "resident_sessions_paged": len(resident),
        "capacity_ratio": len(resident) / budget_sessions,
        "prefix_pages_reused": stats["prefix_pages_reused"],
        "page_alloc_failures": stats["page_alloc_failures"],
        "alloc_failure_events": sum(1 for k, _ in events
                                    if k == "page_alloc_failure"),
        "hit_capacity_gracefully": degraded,
        "paged_degrades": ex.stats["paged_degrades"],
    }
    for h in resident:
        ex.release_cache(h)
    assert pool.stats()["kv_pages_used"] == 0, pool.stats()
    return out


# --------------------------------------------------------------------- bytes

async def _bytes_scenario(paged: bool, tiny: bool) -> dict:
    """Split prefill/decode stage: every session's KV crosses the wire once
    (handoff) and is snapshotted while open. Counts the bytes each path
    moves and checks greedy parity against the single engine."""
    cfg, model, params = _build()
    engine = ServeEngine(model, params, max_len=64)
    cluster = Cluster()
    server = PipelineServer(cluster, model, params,
                            [{"prefill": 1, "decode": 1}], max_len=64,
                            paged=paged, page_size=8,
                            snapshot_interval_s=3600.0)   # manual sweeps
    await server.start()
    sessions = 2 if tiny else 4
    new_tokens = 6 if tiny else 12
    ps = _shared_prefix_prompts(cfg, sessions, system=8, tail=8, seed=2)
    wants = [engine.generate(p, new_tokens) for p in ps]
    tasks = [asyncio.ensure_future(
        server.generate(p, new_tokens, step_timeout=120.0)) for p in ps]
    deadline = time.monotonic() + 60.0
    while sum(r.open_sessions() for r in server.replicas[0]) < sessions:
        assert time.monotonic() < deadline, "sessions never opened"
        await asyncio.sleep(0.005)
    swept = await server.snapshots.sweep()
    outs = await asyncio.gather(*tasks)
    parity = all(np.array_equal(w, g) for w, g in zip(wants, outs))
    m = server.migrations.stats()
    out = {
        "paged": paged,
        "sessions": sessions,
        "token_parity": parity,
        "handoffs": m["handoffs_total"],
        "handoff_failures": m["handoff_failures"],
        "handoff_bytes": m["handoff_bytes_total"],
        "handoff_bytes_per_session": m["handoff_bytes_total"]
        / max(m["handoffs_total"], 1),
        "snapshots_taken": swept,
        "snapshot_bytes_per_snapshot": server.snapshots.snapshot_bytes_total
        / max(swept, 1),
        "obs": collect_obs(server),
    }
    cluster.shutdown()
    return out


# -------------------------------------------------------------- kill/restore

async def _restore_scenario(tiny: bool) -> dict:
    """Unplanned kill in paged mode: page-granular snapshots restore into
    the survivor's pool and sessions finish token-exact."""
    cfg, model, params = _build()
    engine = ServeEngine(model, params, max_len=64)
    cluster = Cluster(heartbeat_interval=0.01, heartbeat_timeout=0.08)
    server = PipelineServer(cluster, model, params, [1, 2], max_len=64,
                            paged=True, page_size=8,
                            snapshot_interval_s=0.05)
    await server.start()
    sessions = 3 if tiny else 6
    new_tokens = 8 if tiny else 16
    ps = _shared_prefix_prompts(cfg, sessions, system=8, tail=8, seed=3)
    # warm both compile paths off-clock (two rounds of real traffic)
    for _ in range(2):
        await asyncio.gather(*(server.generate(p, 3, step_timeout=120.0)
                               for p in ps))
    wants = [engine.generate(p, new_tokens) for p in ps]
    tasks = [asyncio.ensure_future(server.generate(p, new_tokens,
                                                   step_timeout=3.0))
             for p in ps]
    deadline = time.monotonic() + 20.0
    while sum(r.open_sessions() for r in server.replicas[1]) < sessions:
        if time.monotonic() > deadline:
            break
        await asyncio.sleep(0.005)
    await server.snapshots.sweep()
    victim = max((r for r in server.replicas[1] if r.worker.alive),
                 key=lambda r: r.open_sessions())
    t0 = time.monotonic()
    cluster.kill(victim.worker_id, FailureKind.SILENT_HANG)
    outs = await asyncio.gather(*tasks)
    recover_s = time.monotonic() - t0
    m = server.migrations.stats()
    out = {
        "sessions": sessions,
        "token_parity": all(np.array_equal(w, g)
                            for w, g in zip(wants, outs)),
        "recover_s": recover_s,
        "restores": m["restores_total"],
        "reprefills": m["reprefills_total"],
        "recovered_tokens": m["recovered_tokens"],
        "obs": collect_obs(server),
    }
    cluster.shutdown()
    return out


async def _scenario(tiny: bool) -> dict:
    return {
        "capacity": _capacity_scenario(tiny),
        "bytes_contiguous": await _bytes_scenario(paged=False, tiny=tiny),
        "bytes_paged": await _bytes_scenario(paged=True, tiny=tiny),
        "restore": await _restore_scenario(tiny),
    }


def run(tiny: bool = False, json_path: str | None = None
        ) -> list[tuple[str, float, str]]:
    r = run_async(_scenario(tiny))
    cap, co, pg, rs = (r["capacity"], r["bytes_contiguous"],
                       r["bytes_paged"], r["restore"])
    rows = [
        ("paged_capacity_ratio", cap["capacity_ratio"],
         f"{cap['resident_sessions_paged']} paged sessions in a "
         f"{cap['budget_sessions_contiguous']}-contiguous-session budget "
         f"({cap['system_prompt_tokens']}-token shared system prompt)"),
        ("paged_prefix_pages_reused", float(cap["prefix_pages_reused"]),
         "physical pages deduplicated by the prefix trie"),
        ("paged_handoff_bytes/paged", pg["handoff_bytes_per_session"],
         "prefill->decode KV handoff, per session"),
        ("paged_handoff_bytes/contiguous", co["handoff_bytes_per_session"],
         "prefill->decode KV handoff, per session"),
        ("paged_snapshot_bytes/paged", pg["snapshot_bytes_per_snapshot"],
         "background snapshot of an open session"),
        ("paged_snapshot_bytes/contiguous", co["snapshot_bytes_per_snapshot"],
         "background snapshot of an open session"),
        ("paged_restore_recovered_tokens", float(rs["recovered_tokens"]),
         f"{rs['restores']} sessions restored from page-granular snapshots"),
        ("paged_restore_recover_s", rs["recover_s"],
         "kill -> every paged session finished"),
    ]
    # acceptance gates (ISSUE 7)
    assert cap["capacity_ratio"] >= 1.5, \
        (f"paged capacity {cap['capacity_ratio']:.2f}x < 1.5x at equal "
         f"cache memory: {cap}")
    assert cap["hit_capacity_gracefully"] and cap["paged_degrades"] >= 0, cap
    assert cap["page_alloc_failures"] >= 1, \
        f"capacity run never exercised the exhaustion edge: {cap}"
    assert cap["alloc_failure_events"] >= 1, \
        f"pool exhaustion raised no flight event: {cap}"
    assert cap["prefix_pages_reused"] > 0, cap
    assert pg["token_parity"] and co["token_parity"], (pg, co)
    assert pg["handoff_failures"] == 0 and co["handoff_failures"] == 0
    assert pg["handoffs"] >= pg["sessions"], pg
    assert pg["handoff_bytes_per_session"] \
        < co["handoff_bytes_per_session"], \
        (f"paged handoff moved {pg['handoff_bytes_per_session']:.0f}B/session"
         f", contiguous {co['handoff_bytes_per_session']:.0f}B — page "
         f"granularity must be strictly smaller")
    assert pg["snapshot_bytes_per_snapshot"] \
        < co["snapshot_bytes_per_snapshot"], (pg, co)
    assert rs["token_parity"], \
        "greedy parity lost across kill + page-granular snapshot restore"
    assert rs["restores"] >= 1, rs
    if json_path:
        phases = {k: v.pop("obs", {}) for k, v in r.items()
                  if isinstance(v, dict) and "obs" in v}
        write_bench_json(json_path, suite="paged", rows=rows, raw=r,
                         tiny=tiny)
        write_trace_json(trace_path_for(json_path, "paged"),
                         suite="paged", phases=phases)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: short sequences, few sessions")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write rows + raw results as JSON artifact")
    args = ap.parse_args()
    for name, value, derived in run(tiny=args.tiny, json_path=args.json):
        print(f"{name},{value:.4f},{derived}")
