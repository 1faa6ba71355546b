"""The plain reference: the layer equations of the configuration's layer
module (``arch/<name>.py`` ``layer`` and ``head``) in straightforward
``jax.numpy`` and float32 at the highest matmul precision. It imports
nothing of the program and takes nothing the program has made: it draws its
weights from the seed itself (``lib.weights``), one layer at a time, so it
fits beside nothing.

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


class Reference:
    """Teacher-forced logits of whole sequences, computed layer by layer:
    one layer's weights are drawn, every sequence passes through it, and
    they are dropped before the next layer is drawn."""

    def __init__(self, arch, cfg: dict, seed: int,
                 precision: str = "f32") -> None:
        if precision not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.arch = arch
        self.s = s = arch.sizes(cfg)
        self.seed = W.seed_words(seed)
        eqs = dict(s=s, cfg=cfg, fp8=precision == "fp8")
        # leaves static, layer index traced: one program per kind of layer
        self._draw = jax.jit(W.draw, static_argnums=0)
        self._layer = jax.jit(functools.partial(arch.layer, **eqs))
        self._head = jax.jit(functools.partial(arch.head, **eqs))

    def logits(self, seqs: list[np.ndarray], rows: list[np.ndarray]
               ) -> list[np.ndarray]:
        """For each token sequence (S,), the float32 logits (n, V) at the
        positions ``rows`` (n,) of it: row j predicts token j + 1."""
        lo, hi = (jnp.uint32(w) for w in self.seed)
        arch, s = self.arch, self.s
        g = self._draw(tuple(arch.global_leaves(s)), lo, hi, 0)
        padded = [np.pad(seq, (0, -len(seq) % PAD_TO)) for seq in seqs]
        xs = [g["embed"][jnp.asarray(seq)].astype(jnp.float32)
              for seq in padded]
        for i in range(s["layers"]):
            p = self._draw(tuple(arch.layer_leaves(s, i)), lo, hi,
                           jnp.int32(i))
            xs = [self._layer(x, p, jnp.int32(i)) for x in xs]
            del p
        out = []
        with jax.default_matmul_precision("highest"):
            for x, r in zip(xs, rows):
                out.append(np.asarray(self._head(x[jnp.asarray(r)], g)))
        return out
