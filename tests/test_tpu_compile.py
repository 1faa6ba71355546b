"""Compiles for a described TPU v5e, at Llama-3.2-1B widths.

Interpret-mode tests run a kernel's body on the CPU and never meet the TPU's
compiler, which refuses blocks that break its tiling rule and programs that
do not fit the chip. These tests hand the installed TPU compiler the served
path's kernels and one whole decode stage, for a chip that is described and
not attached: nothing runs, so they check that the chip would accept the
programs, not what they compute.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU runtime, and pytest-xdist
workers all import this file. Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import build_model
from repro.serving.executor import StageExecutor
from repro.serving.partition import (
    split_stages,
    stage_init_cache,
    stage_params,
)

CFG = get_config("llama3.2-1b")
H, K, HD, D = CFG.num_heads, CFG.num_kv_heads, CFG.hd, CFG.d_model
BF16 = jnp.bfloat16
MAX_LEN = 512
V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host. The ops wrappers pick interpret
    mode from the backend, which is the CPU here, so the fixture steers them
    to the compiled kernel and clears JAX's trace caches on both sides. The
    persistent compilation cache stays off: a program compiled for a chip
    that is not attached cannot be read back from it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    s = 1024
    q = _spec((1, s, H, HD), BF16, one_chip)
    kv = _spec((1, s, K, HD), BF16, one_chip)
    _assert_kernel(_compile(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True), q, kv, kv))


@pytest.mark.parametrize("batch", [1, 8])
def test_decode_attention_compiles(one_chip, batch):
    q = _spec((batch, 1, H, HD), BF16, one_chip)
    kv = _spec((batch, 1024, K, HD), BF16, one_chip)
    mask = _spec((batch, 1, 1024), jnp.bool_, one_chip)
    _assert_kernel(_compile(
        lambda q, k, v, m: ops.decode_attention(q, k, v, mask=m),
        q, kv, kv, mask))


def test_paged_decode_attention_compiles(one_chip):
    batch, page, pages_per_seq = 8, 16, 1024 // 16
    q = _spec((batch, 1, H, HD), BF16, one_chip)
    pool = _spec((batch * pages_per_seq + 1, page, K, HD), BF16, one_chip)
    table = _spec((batch, pages_per_seq), jnp.int32, one_chip)
    lengths = _spec((batch,), jnp.int32, one_chip)
    _assert_kernel(_compile(ops.paged_decode_attention,
                            q, pool, pool, table, lengths))


def test_rmsnorm_compiles(one_chip):
    x = _spec((8, 256, D), BF16, one_chip)
    w = _spec((D,), BF16, one_chip)
    _assert_kernel(_compile(ops.rmsnorm, x, w))


@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
def test_decode_stage_compiles(one_chip, attn_impl):
    """The last stage of a two-stage split (8 layers plus the tied head) as
    the fused ``decode_many`` program of an 8-session convoy, the served
    path's widest decode dispatch."""
    cfg = CFG.with_(attn_impl=attn_impl)
    spec = split_stages(cfg, 2)[1]
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    sparams = jax.eval_shape(lambda p: stage_params(cfg, p, spec), params)
    ex = StageExecutor(cfg, spec, sparams, max_len=MAX_LEN)
    cache = jax.eval_shape(lambda: stage_init_cache(cfg, spec, 1, MAX_LEN))
    width = 8
    compiled = ex._decode_many.lower(
        _on(sparams, one_chip),
        tuple(_on(cache, one_chip) for _ in range(width)),
        tuple(_spec((1, 1, D), BF16, one_chip) for _ in range(width)),
        _spec((width,), jnp.int32, one_chip)).compile()
    if attn_impl == "pallas":
        _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
