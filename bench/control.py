#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap`` limit, and the faults it has to
catch, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--faults token_altered,state_unchanged,...] \\
        [--fault-seconds <s>]

Each seed is one benchmark run (``run.run``) at the cell's own size and
load with the control switched on: the sample of what the window served is
compared with the plain reference, which gives the program's reading, and
the reference in float8 is put in the program's place on the same sample,
which gives the control's reading. That run compares the control's reading
against the limit, so it has to come out not correct. Each fault
(``lib.faults``) is one more run on the first seed, with the fault planted
under the timed path; it too has to come out not correct. One JSON line per
run. The benchmark's own runs never run the control or a fault.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import run as R  # noqa: E402
from lib import faults  # noqa: E402


def readings(root: str, workload: str, seconds: float, seeds: list[int],
             fault_names: list[str], fault_seconds: float, *,
             bench=None, require_tpu: bool = True):
    """Yield one dict per run: the control on each seed, then each fault on
    the first seed."""
    runs = [(seed, None, seconds) for seed in seeds]
    runs += [(seeds[0], f, fault_seconds) for f in fault_names]
    for seed, fault, secs in runs:
        t0 = time.monotonic()
        out = {"seed": seed, "run": fault or "control", "seconds": secs}
        try:
            res = R.run(root, workload, seed, secs, False, bench=bench,
                        require_tpu=require_tpu, strict=False, t_start=t0,
                        control=fault is None, fault=fault)
        except Exception:  # noqa: BLE001 — a crash is a failed reading
            out["error"] = traceback.format_exc()[-2000:]
        else:
            out.update(correct=res["correct"], attempted=res["attempted"],
                       failed=res["failed"], sample=res["sample"],
                       checks=res["checks"])
            out.update(res.get("readings", {}))
        out["wall_s"] = time.monotonic() - t0
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",")]
    names = [f for f in args.faults.split(",") if f]
    unknown = set(names) - set(faults.FAULTS)
    if unknown:
        ap.error(f"unknown faults {sorted(unknown)}")
    for out in readings(ROOT, args.workload, args.seconds, seeds, names,
                        args.fault_seconds):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
