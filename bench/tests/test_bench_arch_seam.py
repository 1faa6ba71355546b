"""A configuration brings its own layer equations as files only: a tiny
mixture-of-experts (``fixtures/arch/tiny_moe.py``, with its config and
mix) added to a checkout runs through ``run.run`` and comes out correct
against its own reference, and not correct with a token altered. The
harness itself names no architecture, and a configuration that names no
module, or a missing one, is refused."""
import json
import os
import re
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as R  # noqa: E402
from lib import spec as S  # noqa: E402
from tests_support import BENCH, TINY_CONFIG, make  # noqa: E402

FIXTURES = os.path.join(HERE, "fixtures")
CELL = "tiny-moe-chat"
#: words of one architecture that only its layer module may use
LAYER_WORDS = re.compile(r'family="dense"|w_gate|silu|3 \* d \* f|DENSE')
EXPERT = re.compile("expert", re.IGNORECASE)


def _add_config(root, name, cfg):
    with open(os.path.join(root, "bench", "configs", name + ".json"),
              "w") as f:
        json.dump(cfg, f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The tiny dense checkout, plus the fixture's layer module, config and
    mix copied in as files, and BENCHMARK.json naming them: no file of the
    harness is edited. Also a config naming a missing module and one naming
    none."""
    root = make(str(tmp_path_factory.mktemp("moe")))
    for sub in ("arch", "configs", "traffic"):
        shutil.copytree(os.path.join(FIXTURES, sub),
                        os.path.join(root, "bench", sub), dirs_exist_ok=True)
    _add_config(root, "missing", dict(TINY_CONFIG, bench_arch="no_such"))
    _add_config(root, "unnamed", {k: v for k, v in TINY_CONFIG.items()
                                  if k != "bench_arch"})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for name, traffic in (("tiny-moe", "tiny-moe-chat"),
                          ("missing", "tiny-mix"), ("unnamed", "tiny-mix")):
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
        spec["workloads"].append({"name": f"{name}-chat", "config": name,
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(root, cell, seed, fault=None):
    # correct is what these runs check, not the metrics: the tiny MoE's
    # steps take seconds inside a pipeline on the CPU, so a 3 s window may
    # hold no two tokens of one request and no inter-token gap to read
    return R.run(root, cell, seed, 3.0, False, bench=S.Benchmark(root),
                 require_tpu=False, strict=False, t_start=time.monotonic(),
                 fault=fault)


def test_a_moe_configuration_added_as_files_runs_correct(checkout):
    res = _run(checkout, CELL, 31)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 12
    assert res["sample"]["tokens"] > 0


def test_a_moe_token_altered_is_not_correct(checkout):
    res = _run(checkout, CELL, 32, fault="token_altered")
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"], res["checks"]


def test_a_moe_configuration_brings_its_own_counts(checkout):
    arch = S.Benchmark(checkout).arch("tiny_moe")
    s = arch.sizes(S.load_json(os.path.join(
        checkout, "bench", "configs", "tiny-moe.json")))
    # attention 64*(4+4)*16 + 4*16*64 = 12288; router 64*4; 2 of 4 experts
    # of 3*64*128
    assert arch.layer_params(s) == 12288 + 256 + 2 * 24576
    assert sum(arch.decode_stage_flops(s, st, 2, 9)
               for st in (0, 1)) == arch.decode_flops(s, 9)


@pytest.mark.parametrize("cell, message", [
    ("missing-chat", os.path.join("bench", "arch", "no_such.py")),
    ("unnamed-chat", "names no bench_arch")])
def test_a_config_without_its_module_is_refused(checkout, cell, message):
    with pytest.raises(S.SpecError, match=re.escape(message)):
        _run(checkout, cell, 33)


def _harness_files(*dirs):
    for d in dirs:
        path = os.path.join(BENCH, d)
        if os.path.isfile(path):
            yield path
            continue
        for name in sorted(os.listdir(path)):
            if name.endswith(".py"):
                yield os.path.join(path, name)


@pytest.mark.parametrize("words, where", [
    (LAYER_WORDS, ("lib", "metrics", "run.py", "fit.py", "sweep.py",
                   "control.py")),
    (EXPERT, ("lib", "metrics"))])
def test_the_harness_names_no_architecture(words, where):
    found = []
    for path in _harness_files(*where):
        with open(path) as f:
            found += [f"{path}:{i}" for i, line in enumerate(f, 1)
                      if words.search(line)]
    assert found == []
