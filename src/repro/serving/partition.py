"""Pipeline-stage partitioning of a LanguageModel.

MultiWorld's serving story (paper Fig. 2) is a model split into stages, one
worker per stage (replicas for bottleneck stages), one world per edge. This
module produces the per-stage compute: contiguous slices of scan steps
across the model's block groups, with embedding on the first stage and the
LM head on the last.

Works for every decoder-only family (dense / moe / gemma-pair / mamba2 /
hybrid): a "unit" is one scan step of one group, so hybrid units keep their
shared-attention invocation with their mamba run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import ModelConfig
from repro.models import transformer as tfm
from repro.models.common import BlockGroup


@dataclasses.dataclass(frozen=True)
class StageSpec:
    index: int
    n_stages: int
    #: per source group: (group_idx, lo, hi) half-open slice of scan steps
    slices: tuple[tuple[int, int, int], ...]

    @property
    def first(self) -> bool:
        return self.index == 0

    @property
    def last(self) -> bool:
        return self.index == self.n_stages - 1


def split_stages(cfg: ModelConfig, n_stages: int) -> list[StageSpec]:
    units = [(gi, step) for gi, g in enumerate(cfg.groups)
             for step in range(g.count)]
    assert len(units) >= n_stages, (len(units), n_stages)
    per = [len(units) // n_stages + (1 if i < len(units) % n_stages else 0)
           for i in range(n_stages)]
    specs = []
    cursor = 0
    for i, n in enumerate(per):
        chunk = units[cursor:cursor + n]
        cursor += n
        slices: list[tuple[int, int, int]] = []
        for gi, step in chunk:
            if slices and slices[-1][0] == gi and slices[-1][2] == step:
                slices[-1] = (gi, slices[-1][1], step + 1)
            else:
                slices.append((gi, step, step + 1))
        specs.append(StageSpec(i, n_stages, tuple(slices)))
    return specs


def stage_params(cfg: ModelConfig, params: Any, spec: StageSpec) -> dict:
    """Extract the param subtree a stage needs (its slice + heads/embeds)."""
    out: dict = {"groups": [
        jax.tree.map(lambda a: a[lo:hi], params["groups"][gi])
        for gi, lo, hi in spec.slices
    ]}
    needs_shared = any(cfg.groups[gi].kind == "hybrid"
                       for gi, _, _ in spec.slices)
    if needs_shared and "shared_attn" in params:
        out["shared_attn"] = params["shared_attn"]
    if spec.first or cfg.tie_embeddings and spec.last:
        out["embed"] = params["embed"]
    if spec.last:
        out["final_norm"] = params["final_norm"]
        if not cfg.tie_embeddings:
            out["lm_head"] = params["lm_head"]
    return out


def _stage_groups(cfg: ModelConfig, spec: StageSpec) -> list[BlockGroup]:
    return [dataclasses.replace(cfg.groups[gi], count=hi - lo)
            for gi, lo, hi in spec.slices]


def stage_forward(cfg: ModelConfig, spec: StageSpec, sparams: dict,
                  x: jax.Array, *, tokens_in: bool) -> jax.Array:
    """Prefill compute for one stage. First stage takes tokens (B,S) int32;
    others take hidden states (B,S,D). Last stage returns logits."""
    if tokens_in:
        x = tfm.embed_tokens(cfg, sparams, x)
    bsz, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                 (bsz, s))
    shared = sparams.get("shared_attn")
    for g, gp in zip(_stage_groups(cfg, spec), sparams["groups"]):
        x, _ = tfm._group_prefill(cfg, g, gp, x, positions,
                                  mrope=None, shared=shared)
    if spec.last:
        return tfm.lm_logits(cfg, sparams, x)
    return x


def stage_prefill(cfg: ModelConfig, spec: StageSpec, sparams: dict,
                  x: jax.Array, max_len: int, *, tokens_in: bool,
                  length: Optional[jax.Array] = None):
    """Prefill + decode-cache build for one stage. ``x`` may be right-padded
    past the ``length`` real positions (default: all of them). The last
    stage returns the logits (B,1,V) of row ``length - 1`` only, the one
    row a caller reads; the others return every position's hidden state."""
    if tokens_in:
        x = tfm.embed_tokens(cfg, sparams, x)
    bsz, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                 (bsz, s))
    shared = sparams.get("shared_attn")
    cache = stage_init_cache(cfg, spec, bsz, max_len)
    new_cache = []
    for g, gp, gc in zip(_stage_groups(cfg, spec), sparams["groups"], cache):
        x, nc = tfm._group_prefill_cached(cfg, g, gp, gc, x, positions,
                                          mrope=None, shared=shared)
        new_cache.append(nc)
    if spec.last:
        n = s if length is None else length
        last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
        return tfm.lm_logits(cfg, sparams, last), new_cache
    return x, new_cache


def stage_decode(cfg: ModelConfig, spec: StageSpec, sparams: dict, cache,
                 x: jax.Array, t: jax.Array, *, tokens_in: bool):
    """One-token decode for one stage; x is (B,1) tokens or (B,1,D) hidden."""
    if tokens_in:
        x = tfm.embed_tokens(cfg, sparams, x)
    shared = sparams.get("shared_attn")
    new_cache = []
    for g, gp, gc in zip(_stage_groups(cfg, spec), sparams["groups"], cache):
        x, nc = tfm._group_decode(cfg, g, gp, gc, x, t, mrope=None,
                                  shared=shared)
        new_cache.append(nc)
    if spec.last:
        return tfm.lm_logits(cfg, sparams, x)[:, 0], new_cache
    return x, new_cache


def stage_verify(cfg: ModelConfig, spec: StageSpec, sparams: dict, cache,
                 x: jax.Array, t: jax.Array, *, tokens_in: bool):
    """K-token teacher-forced continuation for one stage (speculative
    verification): x is (B,K) known tokens or (B,K,D) hidden for positions
    ``t..t+K-1``. One fused weight pass with the same math as K sequential
    :func:`stage_decode` calls. Last stage returns (B,K,V) logits — one row
    per verified position. Full-cache (dense/moe, unwindowed) stages only.
    """
    if tokens_in:
        x = tfm.embed_tokens(cfg, sparams, x)
    new_cache = []
    for g, gp, gc in zip(_stage_groups(cfg, spec), sparams["groups"], cache):
        x, nc = tfm._group_verify(cfg, g, gp, gc, x, t)
        new_cache.append(nc)
    if spec.last:
        return tfm.lm_logits(cfg, sparams, x), new_cache
    return x, new_cache


def stage_init_cache(cfg: ModelConfig, spec: StageSpec, batch: int,
                     max_len: int, dtype=None):
    sub = dataclasses.replace(cfg, groups=tuple(_stage_groups(cfg, spec)))
    return tfm.init_cache(sub, batch, max_len, dtype)


def stage_cache_seq_axes(cfg: ModelConfig, spec: StageSpec):
    """Per-leaf index of the decode-sequence axis of the stage cache tree
    (-1 for leaves without one). This is the structural ground truth the
    delta-snapshot codec slices along — a size-match heuristic is ambiguous
    whenever another axis happens to equal ``max_len`` (e.g. head_dim 64
    with a 64-token cache)."""
    sub = dataclasses.replace(cfg, groups=tuple(_stage_groups(cfg, spec)))
    axes = tfm.cache_logical_axes(sub, 1, 1)

    def _is_names(x) -> bool:
        return isinstance(x, tuple) and bool(x) and x[0] == "layers"

    return jax.tree.map(
        lambda names: (names.index("cache_seq")
                       if "cache_seq" in names else -1),
        axes, is_leaf=_is_names)
