"""Qwen3's layer at a tiny size on the CPU, through the served path.

A Qwen3-shaped model (per-head qk-norm, untied head, rope theta 1e6, eps
1e-6, float32; layer module ``dense_gqa``) is served through a ``[1, 2]``
``PipelineServer`` with prompts on both sides of a prefill bucket's edge.
The first token's logits (the last stage's prefill, which computes the
head on the last real row only) and every decode step's logits agree with
the plain reference on the same seeded weights; the float8 control does
not. The last-row prefill gives row ``s - 1`` of the full-sequence logits,
and the executors count the real and bucketed positions they prefill.
"""
import asyncio
import glob
import os
import sys
import warnings

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import spec as S, weights as W  # noqa: E402
from lib.reference import Reference  # noqa: E402
from tests_support import ROOT, TINY_CONFIG  # noqa: E402

#: Qwen3-8B's published settings at widths a CPU runs in seconds; two
#: stages of two layers, as ``qwen3-8b.l4`` lays them out
QWEN3_TINY = dict(TINY_CONFIG, name="qwen3-tiny", num_hidden_layers=4,
                  intermediate_size=192, rope_theta=1000000.0,
                  rms_norm_eps=1e-6, tie_word_embeddings=False,
                  program={"arch": "qwen3-tiny", "attn_impl": "reference",
                           "qk_norm": True})
#: 9 and 16 share the bucket of 16, 17 opens the bucket of 32, 100 pads
#: to 128
LENGTHS = (9, 16, 17, 100)
NEW_TOKENS = 4
MAX_LEN = 128
SEED = 2 ** 33 + 17
#: float32 on both sides, the reference at the highest matmul precision:
#: what is left is the order of summation (and the program's padded,
#: cached attention), some 1e-6 on logits of order 1
ATOL = 1e-4
#: the float8 control puts its logits this far off the reference at least;
#: it is read on the same rows
CONTROL_MIN = 1e-2


def _arch():
    return S.Benchmark(ROOT).arch("dense_gqa")


def _mirrored(log_dir, name):
    """Stats of every ``name`` annotation in the profile under
    ``log_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        out.append(dict(e.stats))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Each prompt served alone, in turn, through a ``[1, 2]`` pipeline:
    the tokens, every logits row the last stage handed on (its prefill's,
    then each decode step's), each executor's counters, and the stats of
    the ``mw.exec.prefill`` spans of a profile over the whole run."""
    import jax
    import jax.numpy as jnp
    from repro.core import Cluster
    from repro.models import build_model
    from repro.serving import PipelineServer

    arch = _arch()
    s = arch.sizes(QWEN3_TINY)
    pcfg = arch.program_config(QWEN3_TINY, s).with_(
        param_dtype=jnp.float32, activation_dtype=jnp.float32)
    # the reference draws the same bfloat16 values and widens them
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          W.program_params(arch, s, SEED))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, s["v"], n, dtype=np.int32) for n in LENGTHS]
    log_dir = tmp_path_factory.mktemp("profile")

    async def scenario():
        cluster = Cluster()
        server = PipelineServer(cluster, build_model(pcfg), params, [1, 2],
                                max_len=MAX_LEN)
        last = server.stage_executors[-1]
        rows: list = []
        prefill, decode_many = last.prefill, last.decode_many

        def record_prefill(x):
            out, cache = prefill(x)
            rows.append([np.asarray(out)[0, -1]])
            return out, cache

        def record_decode_many(caches, xs, ts):
            res = decode_many(caches, xs, ts)
            rows[-1] += [np.asarray(y)[0] for y, _ in res]
            return res

        last.prefill, last.decode_many = record_prefill, record_decode_many
        await server.start()
        outs = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the serving loop polls: no calls
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        try:
            for p in prompts:
                out = await server.generate(p[None, :], NEW_TOKENS,
                                            step_timeout=120.0)
                outs.append(np.asarray(out)[0])
        finally:
            jax.profiler.stop_trace()
            cluster.shutdown()
            last.prefill, last.decode_many = prefill, decode_many
        stats = [dict(e.stats) for e in server.stage_executors]
        return outs, rows, stats, server

    outs, rows, stats, server = asyncio.run(
        asyncio.wait_for(scenario(), 600.0))
    return {"prompts": prompts, "outs": outs,
            "rows": [np.stack(r) for r in rows], "stats": stats,
            "server": server, "s": s,
            "spans": _mirrored(log_dir, "mw.exec.prefill")}


def _reference_rows(served, precision):
    seqs, rows = [], []
    for p, out in zip(served["prompts"], served["outs"]):
        seqs.append(np.concatenate([p, out[:-1]]).astype(np.int32))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
    return Reference(_arch(), QWEN3_TINY, SEED, precision).logits(seqs, rows)


def test_served_logits_agree_with_the_reference_at_every_step(served):
    ref = _reference_rows(served, "f32")
    assert [r.shape for r in served["rows"]] == [
        (NEW_TOKENS, served["s"]["v"])] * len(LENGTHS)
    for n, got, want, out in zip(LENGTHS, served["rows"], ref,
                                 served["outs"]):
        np.testing.assert_allclose(got, want, rtol=0, atol=ATOL,
                                   err_msg=f"prompt of {n}")
        np.testing.assert_array_equal(got.argmax(-1), out)


def test_the_float8_control_fails_the_same_comparison(served):
    low = _reference_rows(served, "fp8")
    worst = max(float(np.abs(got - want).max())
                for got, want in zip(served["rows"], low))
    assert worst > CONTROL_MIN > ATOL


@pytest.mark.parametrize("length", LENGTHS)
def test_last_row_prefill_is_row_s_minus_1_of_the_full_logits(served,
                                                              length):
    """The last stage's prefill against the full-sequence logits of the
    same padded hidden rows (the teacher-forced ``score`` path, which
    still projects every row)."""
    import jax.numpy as jnp
    first, last = served["server"].stage_executors
    prompt = served["prompts"][LENGTHS.index(length)]
    hidden, _ = first.prefill(jnp.asarray(prompt[None, :]))
    assert hidden.shape[1] == length
    out, _ = last.prefill(hidden)
    assert out.shape == (1, 1, served["s"]["v"])
    bucket = last.prefill_len(length)
    full = last.score(jnp.pad(hidden, [(0, 0), (0, bucket - length),
                                       (0, 0)]))
    assert full.shape[1] == bucket
    # the same float32 rows through the same head: only the matmul's
    # order of summation over one row or many differs, some 1e-7
    np.testing.assert_allclose(np.asarray(out)[0, 0],
                               np.asarray(full)[0, length - 1],
                               rtol=0, atol=1e-5)


def test_executors_count_real_and_bucketed_prefill_positions(served):
    buckets = [16, 16, 32, 128]
    assert [served["server"].stage_executors[0].prefill_len(n)
            for n in LENGTHS] == buckets
    for stats in served["stats"]:
        assert stats["prefill_calls"] == len(LENGTHS)
        assert stats["prefill_tokens"] == sum(LENGTHS)
        assert stats["prefill_positions"] == sum(buckets)


def test_prefill_spans_carry_tokens_and_bucket(served):
    got = sorted((e["stage"], e["tokens"], e["bucket"])
                 for e in served["spans"])
    assert got == sorted((stage, n, b) for stage in (0, 1)
                         for n, b in zip(LENGTHS, (16, 16, 32, 128)))
