"""Mixture-of-Experts block with capacity-based local dispatch.

Two sharding modes, both exposed as first-class configs (the MoE layout is a
§Perf lever):

* ``tensor`` — every device holds an F/|model| slice of *every* expert;
  tokens stay data-sharded; combine = psum over ``model``. Right when
  num_experts does not divide the model axis (mixtral: 8 experts, 16-way TP).
* ``expert`` — each device owns num_experts/|model| full experts; tokens are
  replicated across ``model``, each rank computes only its owned experts'
  assignments; combine = psum over ``model``. Right for large expert counts
  (qwen3-moe: 128 experts -> 8 per device).

Dispatch is sort-based (argsort by expert id + static per-expert capacity
buffers + batched ``ecd,edf`` einsums), NOT one-hot einsums and NOT
``lax.ragged_dot``: one-hot dispatch adds O(T·E·C·D) fake FLOPs, and
ragged_dot's portable lowering computes *every* group densely (measured: HLO
FLOPs scale linearly with group count), which would corrupt the roofline by
16x for 128 experts. The sort is always device-local (inside shard_map), so
no sharded-axis sort ever reaches GSPMD.

Capacity-overflow tokens are dropped GShard-style (their expert contribution
is zero; the residual stream still carries them). ``capacity_factor``
controls the trade-off.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import current_mesh, current_rules
from .common import ModelConfig

def _local_moe(cfg: ModelConfig, x, router_w, w_gate, w_up, w_down,
               *, e_offset, e_local, capacity, model_axis: Optional[str],
               pmean_axes: tuple[str, ...] = (), scatter_seq: bool = False):
    """Per-device MoE. x: (b_loc, s, D). Expert weights are local slices:
    w_gate/w_up (e_local, D, F_loc), w_down (e_local, F_loc, D)."""
    b, s, d = x.shape
    k = cfg.experts_per_token
    e_global = cfg.num_experts
    xf = x.reshape(b * s, d)
    t = b * s

    # -- routing (replicated math: identical on every model rank) ----------
    logits = (xf @ router_w).astype(jnp.float32)               # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)                     # (T, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights.astype(x.dtype)

    # load-balance aux loss (Switch-style), computed on the full router
    counts = jnp.zeros((e_global,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(t * k, 1)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e_global * jnp.sum(frac_tokens * frac_probs)

    # -- ownership filter (expert mode drops non-owned choices) ------------
    flat_ids = ids.reshape(-1)                                  # (T*k,)
    local_ids = flat_ids - e_offset
    owned = (local_ids >= 0) & (local_ids < e_local)
    sort_key = jnp.where(owned, local_ids, e_local)             # dropped -> tail

    # -- sort-based dispatch ------------------------------------------------
    order = jnp.argsort(sort_key)                               # stable
    sorted_ids = sort_key[order]                                # (T*k,)
    starts = jnp.searchsorted(sorted_ids, jnp.arange(e_local),
                              side="left")
    pos = jnp.arange(t * k) - starts[jnp.clip(sorted_ids, 0, e_local - 1)]
    valid = (sorted_ids < e_local) & (pos < capacity)
    slot = jnp.where(valid, sorted_ids * capacity + pos, e_local * capacity)

    # slot -> source choice index (sentinel row = t*k)
    buf_choice = jnp.full((e_local * capacity + 1,), t * k, jnp.int32)
    buf_choice = buf_choice.at[slot].set(order.astype(jnp.int32),
                                         mode="drop")
    buf_choice = buf_choice[:-1]
    buf_tok = jnp.minimum(buf_choice // k, t)                   # sentinel -> pad row
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xbuf = x_pad[buf_tok].reshape(e_local, capacity, d)         # (E_l, C, D)

    # -- expert computation (honest FLOPs: E_l x C x D x F_loc) ------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xbuf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xbuf, w_up)
    ybuf = jnp.einsum("ecf,efd->ecd", h, w_down)                # (E_l, C, D)

    # -- combine: weighted scatter-add straight into (T, D) ------------------
    # §Perf: folding the routing weight in before the scatter removes two
    # (T*k, D) temporaries vs the unsort-reshape-reduce formulation.
    y_flat = ybuf.reshape(e_local * capacity, d)
    w_sorted = weights.reshape(-1)[order]
    w_eff = jnp.where(valid, w_sorted, 0).astype(x.dtype)
    y_sorted = y_flat[jnp.minimum(slot, e_local * capacity - 1)] \
        * w_eff[:, None]                                        # (T*k, D)
    tok_sorted = jnp.minimum(order // k, t - 1)
    y = jnp.zeros((t, d), x.dtype).at[tok_sorted].add(y_sorted)

    if model_axis is not None:
        if scatter_seq:
            # §Perf: the combine is followed by a sequence-sharded residual
            # add, so reduce-scatter along seq instead of all-reduce — half
            # the wire, and the result lands already sharded (Megatron-SP).
            y3 = y.reshape(b, s, d)
            y = jax.lax.psum_scatter(y3, model_axis, scatter_dimension=1,
                                     tiled=True)
            if pmean_axes:
                aux = jax.lax.pmean(aux, pmean_axes)
            return y, aux
        y = jax.lax.psum(y, model_axis)
    if pmean_axes:
        aux = jax.lax.pmean(aux, pmean_axes)
    return y.reshape(b, s, d), aux


def moe_block(cfg: ModelConfig, p, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """p: router (D,E), w_gate/w_up (E,D,F), w_down (E,F,D). Returns (y, aux)."""
    mesh = current_mesh()
    rules = current_rules()
    mode = "expert" if cfg.num_experts % _model_size(mesh) == 0 and \
        _model_size(mesh) > 1 else "tensor"

    if mesh is None or "model" not in mesh.axis_names or \
            mesh.shape["model"] == 1:
        cap = _capacity(cfg, x.shape[0] * x.shape[1], cfg.num_experts)
        return _local_moe(cfg, x, p["router"], p["w_gate"], p["w_up"],
                          p["w_down"], e_offset=0, e_local=cfg.num_experts,
                          capacity=cap, model_axis=None)

    m = mesh.shape["model"]
    # batch sharding for tokens: follow the 'batch' rule if divisible
    bspec = _batch_spec(rules, mesh, x.shape[0])
    dp = _spec_size(mesh, bspec)
    t_loc = (x.shape[0] // dp) * x.shape[1]
    # sequence-sharded residual stream outside -> reduce-scatter the combine
    seq_target = (rules or {}).get("act_seq")
    scatter_seq = (seq_target == "model" and x.shape[1] % m == 0)
    out_seq_spec = "model" if scatter_seq else None

    if mode == "expert":
        e_local = cfg.num_experts // m
        cap = _capacity(cfg, t_loc, cfg.num_experts)
        w_specs = (P("model", None, None), P("model", None, None),
                   P("model", None, None))

        def body(xl, rw, wg, wu, wd):
            off = jax.lax.axis_index("model") * e_local
            return _local_moe(cfg, xl, rw, wg, wu, wd, e_offset=off,
                              e_local=e_local, capacity=cap,
                              model_axis="model", scatter_seq=scatter_seq,
                              pmean_axes=tuple(mesh.axis_names))
    else:
        e_local = cfg.num_experts
        cap = _capacity(cfg, t_loc, cfg.num_experts)
        w_specs = (P(None, None, "model"), P(None, None, "model"),
                   P(None, "model", None))

        def body(xl, rw, wg, wu, wd):
            return _local_moe(cfg, xl, rw, wg, wu, wd, e_offset=0,
                              e_local=e_local, capacity=cap,
                              model_axis="model", scatter_seq=scatter_seq,
                              pmean_axes=tuple(mesh.axis_names))

    xspec = P(bspec, None, None)
    yspec = P(bspec, out_seq_spec, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(None, None), *w_specs),
        out_specs=(yspec, P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _capacity(cfg: ModelConfig, t_loc: int, e_global: int) -> int:
    raw = t_loc * cfg.experts_per_token / e_global * cfg.moe_capacity_factor
    return max(8, int(math.ceil(raw)))


def _model_size(mesh) -> int:
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


def _batch_spec(rules, mesh, batch: int):
    target = (rules or {}).get("batch")
    if target is None:
        return None
    names = (target,) if isinstance(target, str) else tuple(target)
    names = tuple(n for n in names if n in mesh.axis_names)
    total = 1
    for n in names:
        total *= mesh.shape[n]
    if not names or batch % total != 0:
        return None
    return names if len(names) > 1 else names[0]


def _spec_size(mesh, spec) -> int:
    if spec is None:
        return 1
    names = (spec,) if isinstance(spec, str) else spec
    total = 1
    for n in names:
        total *= mesh.shape[n]
    return total
