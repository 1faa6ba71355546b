"""Smoke test of the elastic serving path on one TPU chip, at Llama-3.2-1B's
published size: 16 layers, d_model 2048, 32 query and 8 KV heads of 64,
d_ff 8192, vocabulary 128256, bf16, random weights from a fixed seed.

Run it from the repository root on a machine with a TPU:

    python chip_smoke.py

Three phases, each through the system's own entry points:

A. ``ServeEngine``: prefill a batch of 2 prompts, then 16 greedy decode steps
   (reference attention).
B. ``PipelineServer(replicas=[1, 2])``, the paper's Fig. 2 shape: two stages,
   the decode stage replicated. Every prefill bucket and decode convoy width
   the traffic can reach is compiled first. Then 8 concurrent ``generate``
   sessions on the healthy pipeline, which must see no retry, no expired
   envelope and no fenced world; 8 more with one stage-1 replica hung
   mid-generation; and 4 after ``add_replica(1)``. No session may fail.
C. ``ServeEngine`` with ``attn_impl="pallas"``: flash prefill and the decode
   kernel compiled for the chip, teacher-forced with phase A's tokens, and
   its logits compared with phase A's.

Outputs are judged on logits, not tokens: with random weights the top logits
of a row sit close together, and bf16 rounding that differs between batch
shapes can swap them. Every token of phases A and B must score, under the
teacher-forced ``ServeEngine.score`` logits, within ``LOGIT_RTOL`` of its
row's maximum.

Each phase prints one ``phase X: {...}`` line. The last line of a passing run
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed check raises and the process exits non-zero. On a JAX backend other
than the TPU the script exits with status 1 before it runs anything.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import Cluster, FailureKind  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import PipelineServer, ServeEngine, StageExecutor  # noqa: E402

ARCH = "llama3.2-1b"
SEED = 0
MAX_LEN = 512
#: prompt lengths are drawn from this inclusive range
PROMPT_LENS = (100, 200)
ENGINE_BATCH = 2
#: the token the prefill yields plus 16 decode steps
ENGINE_NEW = 17
PIPE_SESSIONS = 8
ADD_SESSIONS = 4
PIPE_NEW = 24
#: per-step client deadline. Every shape is compiled before traffic, so a
#: healthy step takes milliseconds; a step lost inside the hung replica is
#: given up after this long and the session re-prefills on a survivor.
STEP_TIMEOUT_S = 30.0
#: every scored history is right-padded to this length, so one compiled
#: program scores them all; causal attention keeps the padding out of every
#: real position's logits
SCORE_LEN = 256
#: logit tolerance, as a fraction of the largest |logit| of the row. bf16
#: keeps 8 significant bits, so one rounding moves a value by up to 2^-8 of
#: it. Weights, activations, the residual stream and the logits are all
#: bf16, and the paths compared here (full forward against cached decode, a
#: lone session against a convoy of 8, reference attention against the
#: Pallas kernels) round and sum in different orders over 16 layers. Eight
#: such steps of the row's largest logit are allowed.
LOGIT_RTOL = 2.0 ** -5


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _devices(tree) -> list[str]:
    return sorted({str(d) for leaf in jax.tree.leaves(tree)
                   for d in leaf.devices()})


def _nbytes(tree) -> int:
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(tree)))


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def random_prompts(rng: np.random.Generator, vocab: int, n: int
                   ) -> list[np.ndarray]:
    lo, hi = PROMPT_LENS
    return [rng.integers(0, vocab, (1, int(rng.integers(lo, hi + 1))),
                         dtype=np.int32) for _ in range(n)]


def worst_gap(logits: np.ndarray, chosen: np.ndarray) -> dict:
    """logits (n, V) rows that chose ``chosen`` (n,). The gap of a row is
    its maximum less the chosen token's logit; its tolerance is LOGIT_RTOL
    of the row's largest |logit|. Returns the row of largest gap/tol."""
    rows = np.arange(len(chosen))
    gap = logits.max(-1) - logits[rows, chosen]
    tol = LOGIT_RTOL * np.abs(logits).max(-1)
    i = int(np.argmax(gap / tol))
    return {"ratio": float(gap[i] / tol[i]), "gap": float(gap[i]),
            "tol": float(tol[i])}


def _worse(a: dict | None, b: dict) -> dict:
    return b if a is None or b["ratio"] > a["ratio"] else a


def score_gap(engine: ServeEngine, prompt: np.ndarray,
              generated: np.ndarray) -> dict:
    """Worst gap of one session's generated tokens under the teacher-forced
    ``ServeEngine.score`` logits of its prompt plus generated history."""
    hist = np.concatenate([prompt, generated])
    _check(len(hist) <= SCORE_LEN, f"history of {len(hist)} > {SCORE_LEN}")
    padded = np.zeros((1, SCORE_LEN), np.int32)
    padded[0, :len(hist)] = hist
    s, n = len(prompt), len(generated)
    logits = engine.score(padded)[0, s - 1:s - 1 + n]
    return worst_gap(logits, generated)


def teacher_forced_rows(engine: ServeEngine, prompts: np.ndarray,
                        tokens: np.ndarray):
    """Logits (B, n, V) of the engine's prefill and decode path when fed
    ``tokens`` (B, n): row j is what the path predicts for token j. Also
    returns the session cache after the last step."""
    ex = engine.executor
    logits, cache = ex.prefill(jnp.asarray(prompts))
    rows = [np.asarray(logits[:, -1], np.float32)]
    t = prompts.shape[1]
    for j in range(tokens.shape[1] - 1):
        step, cache = ex.decode(cache, jnp.asarray(tokens[:, j:j + 1]),
                                t + j)
        rows.append(np.asarray(step, np.float32))
    return np.stack(rows, axis=1), cache


# ------------------------------------------------------------------ phase A
def phase_engine(model, params, prompts: np.ndarray, n_new: int,
                 max_len: int):
    """Returns the engine, its generated tokens (B, n_new), the logits
    rows (B, n_new, V) that chose them, and the phase report."""
    engine = ServeEngine(model, params, max_len=max_len)
    tokens = engine.generate(prompts, n_new)
    _check(tokens.shape == (prompts.shape[0], n_new),
           f"engine generated {tokens.shape}")
    compile_s = engine.stats["first_call_compile_s"]
    rows, cache = teacher_forced_rows(engine, prompts, tokens)
    _check(bool(np.isfinite(rows).all()), "non-finite engine logits")
    t0 = time.monotonic()
    worst = None
    for prompt, gen in zip(prompts, tokens):
        worst = _worse(worst, score_gap(engine, prompt, gen))
    score_s = time.monotonic() - t0
    _check(worst["ratio"] <= 1.0, f"engine token off its row max: {worst}")
    report = {
        "sessions": int(prompts.shape[0]),
        "prompt_len": int(prompts.shape[1]),
        "tokens": int(tokens.size),
        "decode_steps": engine.stats["decode_steps"],
        "param_bytes": _nbytes(engine.executor.sparams),
        "param_devices": _devices(engine.executor.sparams),
        "cache_devices": _devices(cache),
        "compile_s": compile_s,
        "score_s_incl_compile": score_s,
        "worst_score_gap": worst,
    }
    return engine, tokens, rows, report


# ------------------------------------------------------------------ phase B
def warm_profile(cfg, stage: int, max_len: int, lengths: range,
                 convoy_max: int) -> dict:
    """Every prefill bucket a history of one of ``lengths`` tokens lands in,
    and every decode convoy width up to ``convoy_max``, in the form
    ``StageExecutor.warm`` replays."""
    buckets = sorted({min(StageExecutor._bucket(n), max_len)
                      for n in lengths})
    if stage == 0:
        shapes = [((1, b), "int32") for b in buckets]
    else:
        dtype = str(np.dtype(cfg.activation_dtype))
        shapes = [((1, b, cfg.d_model), dtype) for b in buckets]
    widths = [1]
    while widths[-1] < convoy_max:
        widths.append(widths[-1] * 2)
    return {"prefill": shapes, "widths": widths}


def fault_counters(server: PipelineServer) -> dict:
    reps = [r for stage in server.replicas for r in stage]
    mig = server.migrations
    return {
        "retries": sum(r.retries_sent for r in reps),
        "expired": sum(r.expired for r in reps) + server.expired_retired,
        "fences": len(server.broken_worlds),
        "reprefills": mig.reprefills_total,
        "restores": mig.restores_total,
    }


async def _serve(server: PipelineServer, name: str, prompts: list,
                 n_new: int, step_timeout: float, report: dict,
                 during=None) -> list:
    """Run one ``generate`` session per prompt concurrently and record the
    pass in ``report[name]``. ``during``, if given, is awaited with the
    per-session token clocks and the tasks while they run. Returns the
    (prompt, output) pairs; raises if any session failed."""
    before = fault_counters(server)
    t0 = time.monotonic()
    clocks: list[list] = [[] for _ in prompts]
    tasks = [asyncio.ensure_future(server.generate(
        p, n_new, step_timeout=step_timeout, token_times=c))
        for p, c in zip(prompts, clocks)]
    if during is not None:
        await during(clocks, tasks)
    results = await asyncio.gather(*tasks, return_exceptions=True)
    failed = [repr(r) for r in results if isinstance(r, BaseException)]
    after = fault_counters(server)
    report[name] = {"sessions": len(prompts), "failed": len(failed),
                    "wall_s": time.monotonic() - t0,
                    **{k: after[k] - before[k] for k in after}}
    _check(not failed, f"{name} pass failed sessions: {failed}")
    return list(zip(prompts, results))


async def _passes(server: PipelineServer, healthy: list, killed: list,
                  added: list, n_new: int, step_timeout: float,
                  report: dict) -> list:
    """The healthy, kill and add passes in order; the cluster is shut down
    however they end."""
    try:
        await server.start()
        out = await _serve(server, "healthy", healthy, n_new, step_timeout,
                           report)
        clean = report["healthy"]
        _check(clean["retries"] == 0 and clean["expired"] == 0
               and clean["fences"] == 0 and clean["reprefills"] == 0,
               f"healthy warm pass was not clean: {clean}")

        victim = server.replicas[1][0].worker_id

        async def hang_victim(clocks, tasks):
            # once a quarter of the tokens are out, every replica holds
            # sessions: note where their caches sit, then hang one replica
            want = len(clocks) * n_new // 4
            while (sum(map(len, clocks)) < want
                   and not all(t.done() for t in tasks)):
                await asyncio.sleep(0.005)
            for stage, reps in enumerate(server.replicas):
                held = [sess.cache for r in reps
                        for sess in r.sessions.values()]
                report["stages"][stage]["cache_devices"] = _devices(held)
            server.cluster.kill(victim, FailureKind.SILENT_HANG)

        out += await _serve(server, "kill", killed, n_new, step_timeout,
                            report, during=hang_victim)
        report["kill"]["victim"] = victim
        new_replica = await server.add_replica(1)
        out += await _serve(server, "add", added, n_new, step_timeout, report)
        report["add"]["new_replica"] = new_replica
        return out
    finally:
        server.cluster.shutdown()


def phase_pipeline(model, params, healthy: list, killed: list, added: list,
                   n_new: int, max_len: int, score_engine: ServeEngine,
                   step_timeout: float = STEP_TIMEOUT_S) -> dict:
    """Serve the ``healthy`` prompts on the whole pipeline, the ``killed``
    ones while a stage-1 replica hangs, and the ``added`` ones after a new
    stage-1 replica joins; score every token they got with
    ``score_engine``. Prompts are (1, S) int32."""
    cluster = Cluster(heartbeat_interval=0.05, heartbeat_timeout=1.0)
    server = PipelineServer(cluster, model, params, replicas=[1, 2],
                            max_len=max_len, least_loaded=True)
    # compile before the replicas start: their receive loops poll the event
    # loop without pause and would hold the interpreter lock against it
    prompts = healthy + killed + added
    lengths = range(min(p.shape[1] for p in prompts),
                    max(p.shape[1] for p in prompts) + n_new + 1)
    t0 = time.monotonic()
    warmed = sum(ex.warm(warm_profile(model.cfg, stage, max_len, lengths,
                                      server.microbatch_max))
                 for stage, ex in enumerate(server.stage_executors))
    report: dict = {
        "warm": {"compile_s": time.monotonic() - t0, "dispatches": warmed},
        "stages": [{"param_bytes": _nbytes(ex.sparams),
                    "param_devices": _devices(ex.sparams)}
                   for ex in server.stage_executors]}
    results = asyncio.run(_passes(server, healthy, killed, added, n_new,
                                    step_timeout, report))
    worst = None
    for prompt, out in results:
        _check(out.shape == (1, n_new), f"pipeline generated {out.shape}")
        worst = _worse(worst, score_gap(score_engine, prompt[0], out[0]))
    report["tokens"] = sum(int(out.size) for _, out in results)
    report["worst_score_gap"] = worst
    _check(worst["ratio"] <= 1.0, f"pipeline token off its row max: {worst}")
    return report


# ------------------------------------------------------------------ phase C
def phase_pallas(cfg, params, prompts: np.ndarray, tokens: np.ndarray,
                 ref_rows: np.ndarray, max_len: int) -> dict:
    """Phase A's engine path with the Pallas attention kernels, fed phase
    A's tokens; every logit must lie within LOGIT_RTOL of the largest |logit|
    of the reference row."""
    engine = ServeEngine(build_model(cfg.with_(attn_impl="pallas")), params,
                         max_len=max_len)
    t0 = time.monotonic()
    rows, cache = teacher_forced_rows(engine, prompts, tokens)
    wall_s = time.monotonic() - t0
    _check(bool(np.isfinite(rows).all()), "non-finite Pallas logits")
    diff = np.abs(rows - ref_rows).max(-1)
    tol = LOGIT_RTOL * np.abs(ref_rows).max(-1)
    i = np.unravel_index(np.argmax(diff / tol), diff.shape)
    worst = {"ratio": float(diff[i] / tol[i]), "diff": float(diff[i]),
             "tol": float(tol[i])}
    _check(worst["ratio"] <= 1.0, f"Pallas logits off the reference: {worst}")
    return {
        "rows": int(diff.size),
        "param_bytes": _nbytes(engine.executor.sparams),
        "param_devices": _devices(engine.executor.sparams),
        "cache_devices": _devices(cache),
        "compile_s": engine.executor.stats["first_call_compile_s"],
        "wall_s_incl_compile": wall_s,
        "worst_logit_diff": worst,
    }


# --------------------------------------------------------------------- main
def use_compile_cache() -> str:
    """Keep compiled programs where JAX_COMPILATION_CACHE_DIR says (JAX
    reads it itself), else at one fixed path inside the checkout: the path
    is part of the cache key, so it must not move between runs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _emit(name: str, report: dict) -> None:
    print(f"{name}: {json.dumps(report)}", flush=True)


def main() -> int:
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; "
              "nothing was run", file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    cfg = get_config(ARCH)
    _emit("setup", {"device_kind": dev.device_kind,
                    "devices": len(jax.devices()), "arch": ARCH,
                    "layers": cfg.num_layers, "d_model": cfg.d_model,
                    "vocab": cfg.vocab_size, "compile_cache": cache_dir})
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)

    lo, hi = PROMPT_LENS
    prompts = rng.integers(0, cfg.vocab_size,
                           (ENGINE_BATCH, int(rng.integers(lo, hi + 1))),
                           dtype=np.int32)
    engine, tokens, ref_rows, report = phase_engine(model, params, prompts,
                                                    ENGINE_NEW, MAX_LEN)
    report["peak_bytes_in_use"] = _peak_bytes()
    _emit("phase A", {"device_kind": dev.device_kind, **report})

    report = phase_pipeline(
        model, params, random_prompts(rng, cfg.vocab_size, PIPE_SESSIONS),
        random_prompts(rng, cfg.vocab_size, PIPE_SESSIONS),
        random_prompts(rng, cfg.vocab_size, ADD_SESSIONS), PIPE_NEW, MAX_LEN,
        score_engine=engine)
    report["peak_bytes_in_use"] = _peak_bytes()
    _emit("phase B", {"device_kind": dev.device_kind, **report})
    del engine

    report = phase_pallas(cfg, params, prompts, tokens, ref_rows, MAX_LEN)
    report["peak_bytes_in_use"] = _peak_bytes()
    _emit("phase C", {"device_kind": dev.device_kind, **report})

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
