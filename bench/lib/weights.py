"""Random weights from ``--seed``, made by the benchmark and not by the
program, so the plain reference can make the very same values on its own.

Every leaf of every layer is drawn from a key folded from the seed, a
stable id of the leaf's name and the layer index. Drawing one layer alone
(the reference, layer by layer) and drawing the whole model in one jitted
call (what the program is given) therefore yield the same values. Which
leaves a layer has, and how the program lays them out, is the layer
module's (``arch/<name>.py``: ``layer_leaves``, ``global_leaves``, ``pack``).

Scales keep random-weight attention soft and logits of order 1: projections
are N(0, 1/fan_in); norm weights are 1 + 0.1 N(0, 1), so a path that skips a
norm's weight shows up in the comparison.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

NORM = "norm"


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of any size as two 32-bit words (PRNGKey takes 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def _base_key(lo, hi):
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _leaf(base, name: str, layer, shape: tuple, init, dtype):
    key = jax.random.fold_in(
        jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        layer)
    x = jax.random.normal(key, shape, jnp.float32)
    if init == NORM:
        x = 1.0 + 0.1 * x
    else:
        x = x * (1.0 / float(init) ** 0.5)
    return x.astype(dtype)


def draw(leaves, lo, hi, index, dtype=jnp.bfloat16) -> dict:
    """The leaves ``(name, shape, fan-in or NORM)`` of layer ``index`` (0
    for the globals) by name; traceable in the seed and the index."""
    base = _base_key(lo, hi)
    return {n: _leaf(base, n, index, shp, init, dtype)
            for n, shp, init in leaves}


def program_params(arch, s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole model in the program's parameter layout (``arch.pack``),
    made on the device in one jitted call."""

    def build(lo, hi):
        layers = [draw(arch.layer_leaves(s, i), lo, hi, i, dtype)
                  for i in range(s["layers"])]
        return arch.pack(s, layers, draw(arch.global_leaves(s), lo, hi, 0,
                                         dtype))

    lo, hi = seed_words(seed)
    return jax.jit(build)(jnp.uint32(lo), jnp.uint32(hi))
