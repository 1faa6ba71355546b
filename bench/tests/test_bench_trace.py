"""Trace reduction: busy time as a union, idle gaps named by what the host
did, device time per program of the kind its module name says."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import trace as TR  # noqa: E402


@pytest.fixture
def events():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        return json.load(f)["events"]


def test_union_and_gaps():
    assert TR.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert TR.gaps([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30),
                                                            (40, 50)]


def test_window_busy_and_idle(events):
    r = TR.reduce(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    # ops in the window: [1000,1300] [1500,1600] [1800,1850] [1950,2000]
    assert r["busy_s"] == pytest.approx(500e-9)
    # [1300,1500]: the shortest host event over its middle (1400) is the
    # send, not the wait around it; [1600,1800] and [1850,1950] have none
    got = sorted((round(s * 1e9), w) for w, s in r["idle_gaps"])
    assert got == [(100, "no host event"), (200, "envelope send"),
                   (200, "no host event")]


def test_programs_are_named_by_the_label_probes(events):
    # the stage programs' module names give their kind; the eager pad and
    # broadcast have none
    r = TR.reduce(events)
    assert r["by_label"]["prefill"] == {"s": pytest.approx(300e-9),
                                        "calls": 1}
    assert r["by_label"]["decode"] == {"s": pytest.approx(150e-9),
                                       "calls": 2}
    assert r["programs"]["jit_pad"]["label"] is None
    assert r["programs"]["jit_s0_decode_many#13"]["label"] == "decode"
    top = [name for name, _ in r["device_ops"]]
    assert top[0] == "prefill/jit_s0_prefill#11"


@pytest.mark.parametrize("name, kind", [
    ("jit_s0_prefill", "prefill"),
    ("jit_s12_prefill(8123)", "prefill"),
    ("jit_s1_decode", "decode"),
    ("jit_s1_decode_4868979686324340714_", "decode"),
    ("jit_s0_decode_many(7607214138940593126)", "decode"),
    ("jit_s3_decode_paged", "decode"),
    ("jit_s1_verify_many", None),
    ("jit_s0_score", None),
    ("jit_s0_propose", None),
    ("jit_s0_decoder", None),
    ("jit_pad", None),
    ("jit__lambda", None),
])
def test_a_program_has_the_kind_its_module_name_says(name, kind):
    assert TR.kind(name) == kind


def test_no_window_or_no_device_gives_nothing(events):
    assert TR.reduce([e for e in events if e["name"] != TR.WINDOW]) is None
    assert TR.reduce([e for e in events
                      if not e["plane"].startswith("/device")]) is None


def test_a_trace_recorded_on_the_cpu_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(TR.WINDOW):
            jax.jit(lambda a: a @ a)(jnp.ones((64, 64))).block_until_ready()
    events = TR.load(str(tmp_path))
    assert TR.window(events) is not None
    # the CPU backend has no device plane: nothing to reduce
    assert TR.reduce(events) is None
    assert "/host:CPU" in TR.structure(events)
