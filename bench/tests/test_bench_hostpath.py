"""The program's host-path counters and ``mw.*`` spans as the per-layer
readers read them: deltas over a window, every replica summed; the
window's idle time split by the open span of the lowest layer; nothing read
from a program that keeps neither."""
import asyncio
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import hostpath, trace as TR  # noqa: E402
from lib.context import Context  # noqa: E402
from lib.serve import Record, Window  # noqa: E402
from lib.spec import Benchmark  # noqa: E402
from lib.traffic import Request  # noqa: E402
from tests_support import ROOT  # noqa: E402

T0 = 1000.0
READERS = ("decode_wait_ms", "dispatch_hop_ms", "token_host_ms",
           "empty_polls_per_token", "idle_open_share")


@pytest.fixture
def events():
    with open(os.path.join(HERE, "fixtures", "trace_spans.json")) as f:
        return json.load(f)["events"]


def _window(start, end, tokens=12):
    """A 10 s window whose three requests delivered ``tokens`` tokens."""
    per = tokens // 3
    records = []
    for i in range(3):
        r = Record(Request(i, 1.0, np.zeros(4, np.int32), per))
        r.times = [T0 + 2.0 + 0.1 * j for j in range(per)]
        records.append(r)
    return Window(seconds=10.0, records=records, counters_start=start,
                  counters_end=end, compiles=[], setup_s=1.0, t0=T0)


def _read(name, win, trace=None):
    ctx = Context(cell="c", cfg={}, arch=None, sizes={}, stages=2, mix={},
                  window=win,
                  trace=trace, peaks=None, setup_s=win.setup_s)
    return Benchmark(ROOT).reader(name)(ctx)


def _rep(wait, steps, disp, exec_s, batches, polls):
    return {"decode_wait_s_sum": wait, "decode_steps": steps,
            "dispatch_s_sum": disp, "exec_s_sum": exec_s,
            "decode_batches": batches, "polls_empty": polls}


START = {"executors": [{}],
         "replicas": {"s0": _rep(1.0, 10, 2.0, 1.5, 5, 400)},
         "client": {"token_host_s_sum": 1.0, "polls_empty": 600}}
END = {"executors": [{}],
       # replica s1-b joined inside the window: all of its count is new
       "replicas": {"s0": _rep(1.5, 20, 2.6, 1.9, 9, 700),
                    "s1-b": _rep(0.3, 6, 0.4, 0.3, 3, 100)},
       "client": {"token_host_s_sum": 1.06, "polls_empty": 800}}


def test_counter_readers_take_deltas_over_every_replica():
    win = _window(START, END)
    # waits: (0.5 + 0.3) s over (10 + 6) envelopes
    assert _read("decode_wait_ms", win) == pytest.approx(50.0)
    # hop: dispatch (0.6 + 0.4) less exec (0.4 + 0.3) over (4 + 3) calls
    assert _read("dispatch_hop_ms", win) == pytest.approx(1e3 * 0.3 / 7)
    # 0.06 s over the 12 tokens delivered in the window
    assert _read("token_host_ms", win) == pytest.approx(5.0)
    # (300 + 100) replicas' and 200 client's empty polls over 12 tokens
    assert _read("empty_polls_per_token", win) == pytest.approx(50.0)


def test_a_program_without_the_counters_or_spans_reads_nothing(events):
    old = {"executors": [{"decode_batches": 0}]}
    win = _window(old, old)
    assert all(_read(name, win) is None for name in READERS)
    # a program whose replicas count steps but keep none of the sums
    bare = {"executors": [{}], "client": {},
            "replicas": {"s0": {"decode_steps": 4, "decode_batches": 2}}}
    win = _window(bare, bare)
    assert all(_read(name, win) is None for name in READERS)
    # a window in which nothing was dispatched reads nothing either
    idle = _window(START, START, tokens=0)
    assert _read("decode_wait_ms", idle) is None
    assert _read("dispatch_hop_ms", idle) is None
    assert _read("token_host_ms", idle) is None
    # a trace reduced without the spans
    assert _read("idle_open_share", win, TR.reduce(events)) is None


def test_idle_time_goes_to_the_open_span_of_the_lowest_layer(events):
    split = hostpath.idle_by_span(events)
    ns = {name: round(s * 1e9) for name, s in split.items()}
    # first gap [1200, 1500]: step 1200-1250 and 1440-1450, queue to the
    # executor call at 1280, the call to 1350, then the forward (opened
    # after the gather, so it wins while both are open) to 1420, the
    # gather alone to 1440, the session alone from 1450. Second gap
    # [1600, 1900]: the token, the dispatch under it from 1640, the
    # sessions alone 1680-1850, then no session open
    assert ns == {"mw.client.session": 220, "mw.exec.decode_many": 70,
                  "mw.replica.forward": 70, "mw.client.step": 60,
                  "no open request": 50, "mw.client.token": 40,
                  "mw.replica.dispatch": 40, "mw.replica.queue": 30,
                  "mw.replica.gather": 20}
    reduced = TR.reduce(events)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(split.values()) == pytest.approx(idle)
    assert list(split)[0] == "mw.client.session"        # largest first


def test_idle_open_share_reads_the_split_of_a_traced_run(events):
    reduced = TR.reduce(events)
    reduced.update(hostpath.reduce(events))
    assert reduced["idle_by_span"][0] == ["mw.client.session",
                                          pytest.approx(220e-9)]
    # idle 600 ns of the 1000 ns window, 50 of them with no request open
    value = _read("idle_open_share", _window(START, END), reduced)
    assert value == pytest.approx(55.0)
    assert hostpath.reduce([e for e in events
                            if e["name"] != TR.WINDOW]) == {
        "idle_by_span": []}


def test_layers_order_the_span_names():
    order = ["mw.exec.prefill", "mw.replica.dispatch", "mw.replica.queue",
             "mw.client.token", "mw.client.step", "mw.client.session"]
    assert [hostpath.layer(n) for n in order] == list(range(6))
    assert hostpath.layer("np.asarray") is None
    assert hostpath.layer(TR.WINDOW) is None


def test_the_readers_read_a_live_pipeline_on_the_cpu():
    """The counter groups of a tiny ``[1, 2]`` pipeline, snapshot around
    a few requests, read positive and consistent."""
    import jax
    from repro.configs import get_smoke
    from repro.core import Cluster
    from repro.models import DENSE, BlockGroup, build_model
    from repro.serving import PipelineServer
    cfg = get_smoke("llama3.2-1b").with_(num_layers=2,
                                         groups=(BlockGroup(DENSE, 2),))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [np.arange(8, dtype=np.int32)[None, :] + i for i in range(3)]

    async def scenario():
        cluster = Cluster()
        server = PipelineServer(cluster, model, params, [1, 2], max_len=32)
        await server.start()
        for p in prompts:                        # compile outside the window
            await server.generate(p, 2, step_timeout=120.0)
        start = {"executors": [{}], **hostpath.counters(server)}
        outs = await asyncio.gather(*(server.generate(p, 4,
                                                      step_timeout=120.0)
                                      for p in prompts))
        end = {"executors": [{}], **hostpath.counters(server)}
        cluster.shutdown()
        return start, end, outs

    start, end, outs = asyncio.run(asyncio.wait_for(scenario(), 300.0))
    assert len(end["replicas"]) == 3
    win = _window(start, end, tokens=sum(o.size for o in outs))
    assert (end["client"]["token_host_s_sum"]
            > start["client"]["token_host_s_sum"])
    assert sum(end["replicas"][w]["decode_steps"]
               - start["replicas"][w]["decode_steps"]
               for w in end["replicas"]) == 2 * 3 * 3
    for name in READERS[:4]:
        assert _read(name, win) >= 0.0, name
    assert _read("decode_wait_ms", win) > 0.0
    assert _read("token_host_ms", win) > 0.0
