"""Logical-axis sharding rules (MaxText-style) for the production meshes.

Model code annotates params/activations with *logical* axis names
(ParamSpec.axes, ``constrain``). A rule set maps logical names to mesh axes;
``axis_rules(rules, mesh)`` installs the mapping for the duration of a trace.
Outside any context (smoke tests on one CPU device) every helper degrades to
a no-op, so model code never branches on distribution.

Resolution is **divisibility-aware**: an axis whose dimension does not divide
its target mesh axes is skipped *without consuming* the mesh axis, so a later
axis can claim it. This is how GQA KV caches fall back from kv_heads->model
(zamba2: kv=32 over 16 ranks) to head_dim->model (llama/qwen/yi/gemma: kv<16)
with one annotation, and how odd vocabularies (50280, 51865) stay replicated
while clean ones shard.

Rule sets:

* ``TRAIN_RULES``   — batch over (pod, data); tensor parallel over ``model``;
  *sequence-parallel residual stream* (act_seq->model) so per-layer remat
  checkpoints stay O(S/16); FSDP over ``data`` via the ``embed`` dim of
  weights (required: yi-34b AdamW state would not fit data-replicated).
* ``INFER_RULES``   — params replicated over ``data``, TP over ``model``;
  act_seq->model balances prefill compute even when heads don't divide.
* ``LONG_DECODE_RULES`` — batch=1 long-context decode: KV-cache *sequence*
  over ``data``, heads/head_dim over ``model``; batch replicated.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, tuple[str, ...], None]
AxisRules = dict[str, MeshAxes]

TRAIN_RULES: AxisRules = {
    "batch": ("pod", "data"),
    "seq": None,
    "act_seq": "model",       # sequence-parallel residual stream
    "embed": "data",          # FSDP: weight d_model dim sharded over data
    "act_embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": "model",      # claimed only when kv_heads does not divide
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "layers": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "ssm_inner": "model",
    "conv": "model",
    "cache_seq": None,
    "frames": None,
}

INFER_RULES: AxisRules = dict(TRAIN_RULES, embed=None)

LONG_DECODE_RULES: AxisRules = dict(INFER_RULES, batch=None, act_seq=None,
                                    cache_seq="data")


class _Ctx(threading.local):
    def __init__(self) -> None:
        self.rules: Optional[AxisRules] = None
        self.mesh: Optional[Mesh] = None


_CTX = _Ctx()


@contextlib.contextmanager
def axis_rules(rules: AxisRules, mesh: Mesh):
    prev = (_CTX.rules, _CTX.mesh)
    _CTX.rules, _CTX.mesh = rules, mesh
    try:
        yield
    finally:
        _CTX.rules, _CTX.mesh = prev


def current_rules() -> Optional[AxisRules]:
    return _CTX.rules


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axis_size(mesh: Mesh, names: tuple[str, ...]) -> int:
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def _resolve(rules: AxisRules, mesh: Mesh, axes: tuple[Optional[str], ...],
             shape: Optional[tuple[int, ...]] = None) -> P:
    """Map logical axes to a PartitionSpec.

    Drops mesh axes the mesh lacks (e.g. 'pod' on the single-pod mesh),
    never assigns one mesh axis twice, and — when ``shape`` is given — skips
    (without consuming) mesh axes that do not divide the dimension.
    """
    used: set[str] = set()
    spec: list = []
    for i, ax in enumerate(axes):
        target = rules.get(ax) if ax is not None else None
        if target is None:
            spec.append(None)
            continue
        names = (target,) if isinstance(target, str) else tuple(target)
        names = tuple(n for n in names if n in mesh.axis_names and n not in used)
        if shape is not None and names:
            # largest prefix of the requested axes that divides the dim
            while names and shape[i] % _axis_size(mesh, names) != 0:
                names = names[:-1]
        used.update(names)
        if not names:
            spec.append(None)
        elif len(names) == 1:
            spec.append(names[0])
        else:
            spec.append(names)
    return P(*spec)


def logical_spec(axes: tuple[Optional[str], ...],
                 shape: Optional[tuple[int, ...]] = None) -> P:
    if _CTX.rules is None or _CTX.mesh is None:
        return P()
    return _resolve(_CTX.rules, _CTX.mesh, axes, shape)


def logical_sharding(axes: tuple[Optional[str], ...],
                     shape: Optional[tuple[int, ...]] = None
                     ) -> Optional[NamedSharding]:
    if _CTX.rules is None or _CTX.mesh is None:
        return None
    return NamedSharding(_CTX.mesh, _resolve(_CTX.rules, _CTX.mesh, axes,
                                             shape))


def tree_logical_sharding(axes_tree):
    """Map a pytree of logical-axes tuples to NamedShardings (or None).

    Shape-unaware (no divisibility skipping); prefer ``tree_shardings``.
    """
    if _CTX.rules is None or _CTX.mesh is None:
        return None
    return jax.tree.map(
        lambda axes: logical_sharding(tuple(axes)),
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))


def tree_shardings(shaped_tree, axes_tree):
    """Divisibility-aware shardings: ``shaped_tree`` leaves carry .shape
    (arrays or ShapeDtypeStructs), ``axes_tree`` the congruent logical axes."""
    if _CTX.rules is None or _CTX.mesh is None:
        return None

    def one(leaf, axes):
        axes = tuple(axes)
        assert len(axes) == len(leaf.shape), (axes, leaf.shape)
        return logical_sharding(axes, tuple(leaf.shape))

    axes_leaves = jax.tree.leaves(axes_tree,
                                  is_leaf=lambda x: isinstance(x, tuple))
    shaped_leaves, treedef = jax.tree.flatten(shaped_tree)
    assert len(axes_leaves) == len(shaped_leaves), \
        (len(axes_leaves), len(shaped_leaves))
    return jax.tree.unflatten(
        treedef, [one(l, a) for l, a in zip(shaped_leaves, axes_leaves)])


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a context."""
    if _CTX.rules is None or _CTX.mesh is None:
        return x
    spec = _resolve(_CTX.rules, _CTX.mesh, tuple(axes), tuple(x.shape))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_CTX.mesh, spec))
