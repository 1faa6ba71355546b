#!/usr/bin/env python3
"""Find a cell's knee once, by a sweep of request rates on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1,2,3,4

One process: the weights are made once; for each rate a fresh cluster and
``PipelineServer`` are built over them, warmed (every program is in the
compile cache after the first), driven for ``--seconds`` at that rate, and
stopped. Each rate prints one JSON line: tokens/s, the TTFT and inter-token
tails, how many requests were still unfinished at the window's close, and
the ratio of the TTFT median in the window's last third to its first third
(a backlog that grows all through the window shows as a ratio well above 1).
No output is compared with the reference here.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as R  # noqa: E402
from lib import measure, serve, spec as S, traffic as T  # noqa: E402
from lib import weights as W  # noqa: E402


def summarize(win) -> dict:
    ttft = measure.ttfts(win)
    due = [r.req.due_s for r in win.records]
    third = win.seconds / 3
    early = [t for t, d in zip(ttft, due) if d < third]
    late = [t for t, d in zip(ttft, due) if d >= 2 * third]
    itl = measure.itls(win)
    return {
        "requests": len(win.records),
        "failed": sum(1 for r in win.records if r.error is not None),
        "unfinished_at_close": sum(
            1 for r in win.records
            if not (r.out is not None and r.done <= win.seconds)),
        "tokens_per_s": measure.tokens_in_window(win) / win.seconds,
        "ttft_p50_ms": 1e3 * measure.percentile(ttft, 50),
        "ttft_p90_ms": 1e3 * measure.percentile(ttft, 90),
        "itl_p50_ms": 1e3 * measure.percentile(itl, 50) if itl else None,
        "itl_p95_ms": 1e3 * measure.percentile(itl, 95) if itl else None,
        "late_over_early_ttft": (measure.percentile(late, 50)
                                 / measure.percentile(early, 50)
                                 if early and late else None),
        "convoy_width": (measure.counter_delta(win, "decode_steps")
                         / max(1, measure.counter_delta(win,
                                                        "decode_batches"))),
        "compiles_in_window": len(win.compiles),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 1
    R.use_compile_cache(ROOT)
    log = serve.CompileLog()
    bench = S.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    (cfg, arch), mix0 = bench.config(cell), S.load_json(cell.traffic_file)
    sizes = arch.sizes(cfg)
    pcfg = arch.program_config(cfg, sizes)
    params = W.program_params(arch, sizes, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(mix0, rate_rps=rate)
        t0 = time.monotonic()
        server = serve.build_server(pcfg, params, mix["server"])
        reqs = T.schedule(mix, args.seconds, args.seed, sizes["v"])
        win = asyncio.run(serve.run_window(
            server, reqs, args.seconds, t0, log, 30.0,
            before_open=lambda: serve.warm(server, mix, args.seconds,
                                           sizes["v"])))
        del server
        gc.collect()
        row = {"rate_rps": rate, "setup_s": win.setup_s, **summarize(win)}
        print(json.dumps(row), flush=True)
        # well past the knee: the backlog grew all through the window
        if (row["late_over_early_ttft"] or 0) > 4 and \
                row["unfinished_at_close"] > row["requests"] / 3:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
