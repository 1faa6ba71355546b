"""The harness end to end on the CPU at a tiny size: a run is correct, a
cell, configuration, mix and metric are found by name as files only, the
command refuses a CPU backend, and a run whose timed path is broken
underneath comes out not correct."""
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as R  # noqa: E402
from lib import faults, spec as S  # noqa: E402
from tests_support import BENCH, ROOT, TINY_MIX, make  # noqa: E402

SECONDS = 3.0

EXTRA_METRIC = '''
def read(ctx):
    return float(len(ctx.window.records))
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with the tiny config, two mixes and one extra metric,
    all added as files; no file of the harness is edited."""
    root = str(tmp_path_factory.mktemp("checkout"))
    slow = dict(TINY_MIX, rate_rps=2.0)
    make(root, mixes={"tiny-mix": TINY_MIX, "tiny-slow": slow},
         metrics=[{"name": "requests_seen", "unit": "requests",
                   "better": "higher", "source": "host_clock",
                   "layer": "client", "moves": "tokens_per_s",
                   "workloads": ["tiny-tiny-slow"]}])
    with open(os.path.join(root, "bench", "metrics", "requests_seen.py"),
              "w") as f:
        f.write(EXTRA_METRIC)
    return root


def _run(root, cell, seed=11, trace=False, fault=None, strict=True,
         seconds=SECONDS):
    return R.run(root, cell, seed, seconds, trace, bench=S.Benchmark(root),
                 require_tpu=False, strict=strict, t_start=time.monotonic(),
                 fault=fault)


def test_cells_configs_mixes_and_metrics_are_found_by_name(checkout):
    bench = S.Benchmark(checkout)
    cell = bench.cell("tiny-tiny-slow")
    assert cell.traffic_file.endswith(os.path.join("traffic",
                                                   "tiny-slow.json"))
    assert cell.config_file.endswith(os.path.join("configs", "tiny.json"))
    names = [m.name for m in bench.metrics_for("tiny-tiny-slow", "per_layer")]
    assert "requests_seen" in names
    assert "requests_seen" not in [
        m.name for m in bench.metrics_for("tiny-tiny-mix", "per_layer")]
    with pytest.raises(S.SpecError):
        bench.cell("no-such-cell")


def test_a_tiny_run_is_correct_and_reports_its_metrics(checkout):
    # the CPU backend has no device plane: the trace metrics read nothing
    res = _run(checkout, "tiny-tiny-slow", trace=True, strict=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 6
    assert res["metrics"]["requests_seen"]["value"] == 6.0
    assert res["metrics"]["compiles_in_window"]["value"] == 0.0
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["limit"] == 0.1
    # every end-to-end metric read strict: half the tiny mix's load, so the
    # slow pipeline on a CPU still delivers some tokens two by two in the
    # window for the inter-token gaps
    res = _run(checkout, "tiny-tiny-slow", seed=12, seconds=2 * SECONDS)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p90_ms",
                                   "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_timed_path_is_not_correct(checkout, fault):
    # correct is what this checks; a loaded CPU may leave the 3 s window
    # without an inter-token gap to read, which the strict run would raise
    res = _run(checkout, "tiny-tiny-mix", seed=13, fault=fault, strict=False)
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"], res["checks"]


def test_a_listed_metric_that_reads_nothing_fails_the_run(checkout):
    with pytest.raises(R.MissingMetric, match="step_mfu"):
        _run(checkout, "tiny-tiny-slow", seed=14, trace=True)


def test_the_command_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "yi34b-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was run" in p.stderr


def test_a_checkout_without_the_program_fails(tmp_path):
    for name in ("BENCHMARK.json",):
        with open(os.path.join(ROOT, name)) as src, \
                open(tmp_path / name, "w") as dst:
            dst.write(src.read())
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi34b-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
