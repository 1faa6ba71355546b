"""Faults planted under the timed path, to show that ``correct`` catches
them: each wraps the stage executor's ``decode_many``, the call every decode
step of the window goes through.

* ``token_altered``: the last stage's logits of a convoy's first session
  are rolled along the vocabulary, so the token is altered where it is
  produced.
* ``state_unchanged``: a step returns the caches it was given, so no step
  writes its position.
* ``half_batch_left_out``: only the first half of a convoy is computed;
  the rest get the first session's output and keep their caches.
* ``exchange_left_out``: a stage after the first decodes zeros instead of
  the hidden rows the previous stage sent it.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


def _token_altered(self, orig, caches, xs, ts):
    outs = orig(self, caches, xs, ts)
    if not self.spec.last:
        return outs
    y, c = outs[0]
    return [(jnp.roll(y, 7, axis=-1), c)] + list(outs[1:])


def _state_unchanged(self, orig, caches, xs, ts):
    return [(y, c0) for (y, _), c0 in zip(orig(self, caches, xs, ts), caches)]


def _half_batch_left_out(self, orig, caches, xs, ts):
    n = len(caches)
    if n < 2:
        return orig(self, caches, xs, ts)
    k = n - n // 2
    half = orig(self, caches[:k], xs[:k], ts[:k])
    return list(half) + [(half[0][0], c) for c in caches[k:]]


def _exchange_left_out(self, orig, caches, xs, ts):
    if not self.spec.first:
        xs = [jnp.zeros_like(x) for x in xs]
    return orig(self, caches, xs, ts)


FAULTS = {"token_altered": _token_altered,
          "state_unchanged": _state_unchanged,
          "half_batch_left_out": _half_batch_left_out,
          "exchange_left_out": _exchange_left_out}


@contextlib.contextmanager
def planted(name: str | None):
    """Plant fault ``name`` (None: none) for the duration of the block."""
    if name is None:
        yield
        return
    from repro.serving.executor import StageExecutor
    fault, orig = FAULTS[name], StageExecutor.decode_many

    def broken(self, caches, xs, ts):
        return fault(self, orig, caches, xs, ts)

    StageExecutor.decode_many = broken
    try:
        yield
    finally:
        StageExecutor.decode_many = orig
