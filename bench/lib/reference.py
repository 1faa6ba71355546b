"""The plain reference: a dense GQA decoder (Llama / Yi / Qwen3 layer
equations) in straightforward ``jax.numpy`` and float32 at the highest
matmul precision. It imports nothing of the program and takes nothing the
program has made: it draws its weights from the seed itself
(``lib.weights``), one layer at a time, so it fits beside nothing.

Layer equations (pre-norm, as published for Llama and Qwen3):

    h = RMSNorm(x) ;  q, k, v = h Wq, h Wk, h Wv
    q, k = RMSNorm_hd(q), RMSNorm_hd(k)            (Qwen3 qk-norm only)
    q, k = RoPE(q), RoPE(k)                        (rotate-half, base theta)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo   (GQA: kv heads shared)
    x = x + (silu(h2 Wg) * (h2 Wu)) Wd,  h2 = RMSNorm(x)
    logits = RMSNorm(x) W_head

``precision="fp8"`` is the control: every weight and every activation that
enters a matrix product is rounded to float8 e4m3 with a per-tensor (weight)
or per-row (activation) scale, the step below bfloat16 that a later change
might take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

F8_MAX = 448.0
#: sequences are right-padded to a multiple of this many tokens, so a few
#: compiled shapes serve every length; causal attention keeps the padding
#: out of every real row
PAD_TO = 256


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale taken over ``axis`` (None: all)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, w, fp8: bool):
    if fp8:
        a = _fp8(a, -1)
        w = _fp8(w, None)
    return jnp.einsum(eq, a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, heads, hd); rotate-half convention."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs      # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, p: dict, *, eps: float, theta: float, qk_norm: bool,
          fp8: bool = False):
    """One decoder layer over a whole sequence x (S, D), float32."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    s = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    q = _mm("sd,dhk->shk", h, p["wq"], fp8)
    k = _mm("sd,dhk->shk", h, p["wk"], fp8)
    v = _mm("sd,dhk->shk", h, p["wv"], fp8)
    if qk_norm:
        q = _rms(q, p["q_norm"], eps)
        k = _rms(k, p["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    nh, nkv, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(s, nkv, nh // nkv, hd)
    scores = jnp.einsum("skgd,tkd->kgst", qg, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("kgst,tkd->skgd", probs, v,
                     precision=jax.lax.Precision.HIGHEST).reshape(s, nh, hd)
    x = x + _mm("shk,hkd->sd", ctx, p["wo"], fp8)
    h2 = _rms(x, p["ln2"], eps)
    gate = _mm("sd,df->sf", h2, p["w_gate"], fp8)
    up = _mm("sd,df->sf", h2, p["w_up"], fp8)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["w_down"], fp8)


def head(x, final_norm, w_head, *, eps: float, fp8: bool = False):
    """Logits (S, V) of hidden rows x (S, D)."""
    h = _rms(x, final_norm.astype(jnp.float32), eps)
    return _mm("sd,dv->sv", h, w_head.astype(jnp.float32), fp8)


class Reference:
    """Teacher-forced logits of whole sequences, computed layer by layer:
    one layer's weights are drawn, every sequence passes through it, and
    they are dropped before the next layer is drawn."""

    def __init__(self, cfg: dict, seed: int, precision: str = "f32") -> None:
        if precision not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.s = W.sizes(cfg)
        self.seed = W.seed_words(seed)
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.fp8 = precision == "fp8"
        s = self.s
        self._layer_w = jax.jit(
            lambda lo, hi, i: W.make_layer(s, lo, hi, i))
        self._globals = jax.jit(lambda lo, hi: W.make_globals(s, lo, hi))
        self._layer = jax.jit(functools.partial(
            layer, eps=self.eps, theta=self.theta, qk_norm=s["qk_norm"],
            fp8=self.fp8))
        self._head = jax.jit(functools.partial(head, eps=self.eps,
                                               fp8=self.fp8))

    def logits(self, seqs: list[np.ndarray], rows: list[np.ndarray]
               ) -> list[np.ndarray]:
        """For each token sequence (S,), the float32 logits (n, V) at the
        positions ``rows`` (n,) of it: row j predicts token j + 1."""
        lo, hi = (jnp.uint32(w) for w in self.seed)
        g = self._globals(lo, hi)
        padded = [np.pad(seq, (0, -len(seq) % PAD_TO)) for seq in seqs]
        xs = [g["embed"][jnp.asarray(seq)].astype(jnp.float32)
              for seq in padded]
        w_head = g["embed"].T if self.s["tied"] else g["lm_head"]
        final_norm = g["final_norm"]
        del g
        for i in range(self.s["layers"]):
            p = self._layer_w(lo, hi, jnp.int32(i))
            xs = [self._layer(x, p) for x in xs]
            del p
        out = []
        with jax.default_matmul_precision("highest"):
            for x, r in zip(xs, rows):
                out.append(np.asarray(self._head(x[jnp.asarray(r)],
                                                 final_norm, w_head)))
        return out
