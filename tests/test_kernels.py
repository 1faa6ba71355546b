"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 128, 4, 1, 128),     # MQA, wide head
    (2, 64, 2, 2, 32),       # small, block < 128
])
def test_flash_attention_causal(b, s, h, kv, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (b, s, h, hd), dtype)
    k = rand(ks[1], (b, s, kv, hd), dtype)
    v = rand(ks[2], (b, s, kv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [32, 96])
def test_flash_attention_sliding_window(window):
    b, s, h, kv, hd = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (rand(kk, (b, s, hh, hd), jnp.float32)
               for kk, hh in zip(ks, (h, kv, kv)))
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    b, s, h, kv, hd = 1, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (rand(kk, (b, s, hh, hd), jnp.float32)
               for kk, hh in zip(ks, (h, kv, kv)))
    out = ops.flash_attention(q, k, v, causal=True, softcap=50.0)
    want = ref.flash_attention_ref(q, k, v, causal=True, softcap=50.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    b, s, h, kv, hd = 2, 128, 4, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (rand(kk, (b, s, hh, hd), jnp.float32)
               for kk, hh in zip(ks, (h, kv, kv)))
    out = ops.flash_attention(q, k, v, causal=False)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,target,align,want", [
    (96, 128, 8, 96),        # fits: the whole dim
    (256, 128, 8, 128),
    (1536, 512, 128, 512),
    (640, 512, 128, 128),    # 512, 384 and 256 do not divide 640
    (150, 128, 8, 150),      # no multiple of 8 divides 150: the whole dim
])
def test_pick_block_keeps_tpu_tiling(n, target, align, want):
    """A block dim is the whole array dim or a multiple of its tile that
    divides the dim: the rule the TPU compiler holds the last two to."""
    assert ops._pick_block(n, target, align) == want


# ------------------------------------------------------------ decode attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,kv,hd", [
    (2, 512, 4, 4, 64),
    (1, 1024, 8, 2, 128),
    (4, 256, 4, 1, 64),
])
def test_decode_attention(b, t, h, kv, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(ks[0], (b, 1, h, hd), dtype)
    k = rand(ks[1], (b, t, kv, hd), dtype)
    v = rand(ks[2], (b, t, kv, hd), dtype)
    # ragged validity: row i valid up to t//(i+2)
    pos = jnp.arange(t)[None, :]
    mask = pos <= jnp.asarray([t // (i + 2) for i in range(b)])[:, None]
    out = ops.decode_attention(q, k, v, mask=mask)
    want = ref.decode_attention_ref(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_decode_attention_ring_occupancy_mask():
    """Ring-buffer style mask: every slot valid (steady-state SWA)."""
    b, t, h, kv, hd = 2, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (rand(kk, (b, tt, hh, hd), jnp.float32)
               for kk, (tt, hh) in zip(ks, ((1, h), (t, kv), (t, kv))))
    mask = jnp.ones((b, t), bool)
    out = ops.decode_attention(q, k, v, mask=mask, softcap=30.0)
    want = ref.decode_attention_ref(q, k, v, mask, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------- ssd scan

@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 32, 16, 16),
    (2, 128, 4, 64, 32, 32),
    (1, 256, 2, 64, 64, 64),
    (2, 64, 8, 64, 128, 16),   # mamba2-like head/state dims
])
def test_ssd_scan(b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = rand(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(rand(ks[1], (b, s, h), jnp.float32))
    a = -jnp.exp(rand(ks[2], (h,), jnp.float32) * 0.5)
    bmat = rand(ks[3], (b, s, n), jnp.float32) * 0.5
    cmat = rand(ks[4], (b, s, n), jnp.float32) * 0.5
    y, st = ops.ssd_scan(x, dt, a, bmat, cmat, chunk=chunk)
    y_ref, st_ref = ref.ssd_scan_ref(x, dt, a, bmat, cmat, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_scan_chunk_invariance():
    """y must not depend on the chunking (associativity of the recurrence)."""
    b, s, h, p, n = 1, 128, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = rand(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(rand(ks[1], (b, s, h), jnp.float32))
    a = -jnp.exp(rand(ks[2], (h,), jnp.float32) * 0.5)
    bmat = rand(ks[3], (b, s, n), jnp.float32) * 0.5
    cmat = rand(ks[4], (b, s, n), jnp.float32) * 0.5
    y16, _ = ops.ssd_scan(x, dt, a, bmat, cmat, chunk=16)
    y64, _ = ops.ssd_scan(x, dt, a, bmat, cmat, chunk=64)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64),
                               rtol=1e-4, atol=1e-4)


def test_ssd_scan_matches_step_recurrence():
    """Chunked kernel == token-by-token ssd_step recurrence."""
    from repro.models.ssm import ssd_step
    b, s, h, p, n = 1, 32, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = rand(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(rand(ks[1], (b, s, h), jnp.float32))
    a = -jnp.exp(rand(ks[2], (h,), jnp.float32) * 0.5)
    bmat = rand(ks[3], (b, s, n), jnp.float32) * 0.5
    cmat = rand(ks[4], (b, s, n), jnp.float32) * 0.5
    y, st = ops.ssd_scan(x, dt, a, bmat, cmat, chunk=8)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    for i in range(s):
        state, yi = ssd_step(state, x[:, i], dt[:, i], a, bmat[:, i],
                             cmat[:, i])
        np.testing.assert_allclose(np.asarray(yi), np.asarray(y[:, i]),
                                   rtol=1e-3, atol=1e-3, err_msg=f"i={i}")
    np.testing.assert_allclose(np.asarray(state), np.asarray(st),
                               rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------ rmsnorm

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(64, 128), (4, 32, 256), (512, 64)])
@pytest.mark.parametrize("plus_one", [False, True])
def test_rmsnorm(shape, dtype, plus_one):
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    x = rand(ks[0], shape, dtype)
    w = rand(ks[1], shape[-1:], dtype)
    out = ops.rmsnorm(x, w, plus_one=plus_one)
    want = ref.rmsnorm_ref(x, w, plus_one=plus_one)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))
