#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration (``configs/<config>.json``, whose layer
module ``arch/<bench_arch>.py`` lays out the program's parameters, weights
made on the device from ``--seed``), the cell's ``PipelineServer``, warms
every program the cell's traffic (``traffic/<mix>.json``) reaches, offers
that traffic open loop for ``--seconds``, then checks a sample of what was
served against the plain reference. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit. The checks are also the
last lines of standard error.

Exits 1 without a result when JAX finds no TPU, or fewer chips than the cell
asks for, or when a metric BENCHMARK.json lists for the cell cannot be read
(a traced run whose stage programs the trace does not name, say).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import check, faults, measure, serve, spec as S  # noqa: E402
from lib import trace as TR, traffic as T  # noqa: E402
from lib import weights as W  # noqa: E402
from lib.context import Context  # noqa: E402
from lib.peaks import peaks  # noqa: E402

#: requests whose served tokens are compared with the reference: at least
#: this many served tokens, at most this many requests
SAMPLE_TOKENS = 256
SAMPLE_REQUESTS = 8
#: how long requests still in flight when the window closes may take
GRACE_S = 60.0


def log(what: str, t_start: float = T_START) -> None:
    print(f"bench: {time.monotonic() - t_start:8.2f}s {what}",
          file=sys.stderr, flush=True)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path inside the checkout (the path is part of
    the cache key). Small programs are cached too, so a second run loads
    every program it needs."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _check_layout(model, params) -> None:
    import jax
    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise RuntimeError("benchmark weights do not match the program's "
                           "parameter layout")


class MissingMetric(RuntimeError):
    """A metric that BENCHMARK.json lists for the cell could not be read."""


def run(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
        bench: S.Benchmark | None = None, require_tpu: bool = True,
        strict: bool = True, t_start: float = T_START, control: bool = False,
        fault: str | None = None) -> dict:
    """One run of one cell; returns the result object.

    ``control`` compares the control's reading (the reference in float8 in
    the program's place) instead of the program's, and ``fault`` plants one
    of ``lib.faults`` under the timed path: either run has to come out not
    correct. With ``strict``, a metric listed for the cell that its reader
    cannot read is an error (:class:`MissingMetric`)."""
    import jax
    import repro  # noqa: F401  the system under test, from <checkout>/src
    devices = jax.devices()
    bench = bench or S.Benchmark(root)
    cell = bench.cell(workload)
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoAccelerator(f"JAX devices {devices}: cell {workload} needs "
                            f"{cell.chips} TPU chip(s)")
    dev = devices[0]
    peak = peaks(dev.device_kind) if require_tpu else None
    if dev.platform == "tpu":
        use_compile_cache(root)
    compile_log = serve.CompileLog()
    cfg, arch = bench.config(cell)
    mix = S.load_json(cell.traffic_file)
    sizes = arch.sizes(cfg)
    pcfg = arch.program_config(cfg, sizes)
    reqs = T.schedule(mix, seconds, seed, sizes["v"])
    from repro.models import build_model
    params = W.program_params(arch, sizes, seed)
    _check_layout(build_model(pcfg), params)
    log("weights made", t_start)
    server = serve.build_server(pcfg, params, mix["server"])
    del params
    log("pipeline built", t_start)

    trace_dir, annotation = None, []

    async def before_open():
        n = await serve.warm(server, mix, seconds, sizes["v"])
        log(f"warm ({n} prompt lengths)", t_start)
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_opts())

    def on_open():
        if trace:
            annotation.append(jax.profiler.TraceAnnotation(TR.WINDOW))
            annotation[0].__enter__()

    def on_close():
        if annotation:
            annotation[0].__exit__(None, None, None)

    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    with faults.planted(fault):
        win = asyncio.run(serve.run_window(
            server, reqs, seconds, t_start, compile_log, GRACE_S,
            before_open=before_open,
            on_open=on_open, on_close=on_close))
    log("window closed", t_start)
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        events = TR.load(trace_dir)
        reduced = TR.reduce(events)
        if os.environ.get("BENCH_TRACE_DUMP"):
            with open(os.environ["BENCH_TRACE_DUMP"], "w") as f:
                json.dump({"structure": TR.structure(events),
                           "reduced": reduced}, f, indent=1, default=str)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log("trace read", t_start)
    n_stages = server.n_stages
    del server
    gc.collect()

    chosen = check.sample(win, seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    sampled = check.served_gap(arch, cfg, seed, chosen,
                                     control) if chosen else {}
    log("reference compared", t_start)
    ctx = Context(cell=cell.name, cfg=cfg, arch=arch, sizes=sizes,
                  stages=n_stages,
                  mix=mix, window=win, trace=reduced, peaks=peak,
                  setup_s=win.setup_s)
    kind = "per_layer" if trace else "end_to_end"
    metrics, missing = {}, []
    for m in bench.metrics_for(cell.name, kind):
        value = bench.reader(m.name)(ctx)
        if value is None:
            missing.append(m.name)
        else:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    if missing and strict:
        named = reduced and {k: v["calls"]
                             for k, v in reduced["by_label"].items()}
        counted = {k: measure.counter_delta(win, c)
                   for k, c in measure.COUNTER.items()}
        raise MissingMetric(
            f"cell {cell.name}: no reading of {missing}; calls named in the "
            f"trace {named}, dispatches counted {counted}; top programs "
            f"{reduced and reduced['device_ops']}")
    checks = check.checks(cfg, win, sampled, control)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak,
              "memory_limit_bytes": stats.get("bytes_limit")}
    if trace and reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(win.records),
        "failed": sum(1 for r in win.records if r.error is not None),
        "metrics": metrics, "device": device}
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["sample"] = {k: v for k, v in sampled.items()
                        if k not in ("gap", "control_gap")}
    if control:
        result["readings"] = {"program_gap": sampled.get("gap"),
                              "control_gap": sampled.get("control_gap")}
    result["checks"] = checks
    return result


def _profile_opts():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    except MissingMetric as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
