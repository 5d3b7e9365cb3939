"""Logical-axis sharding: rule tables + mesh context + constraints.

Every tensor in the repo carries *logical* axis names (``"batch"``,
``"heads"``, ``"kv_seq"`` ...) instead of mesh axes.  A *rule table* maps
logical names to mesh axes; :func:`mesh_axes_for` resolves one tensor's
logical axes against a table with two safety rails:

  * divisibility — a dim that does not divide evenly over its mesh axes
    falls back to replication (trailing mesh axes are dropped first, so a
    two-axis rule can degrade to one axis before giving up);
  * no double use — a mesh axis consumed by an earlier dim of the same
    tensor is unavailable to later dims (first dim wins).

Rule tables (all derive from :data:`DEFAULT_RULES`):

  * TRAIN_RULES — TP over `model` + FSDP: the weight ``embed`` dim shards
    over `data` (ZeRO-style), gathered per layer inside the scan.
  * SERVE_RULES — decode: weights TP over `model`; the KV cache/synopsis
    ``kv_seq`` axis shards over `model` — each shard is one paper
    "component" of the scatter-gather structure.
  * LONG_RULES  — long_500k: ``kv_seq`` spreads over ``(data, model)``
    (the cache is the dominant allocation), batch keeps only `pod`.

The active (mesh, rules) pair is installed with :func:`use_mesh`;
:func:`constrain` is then a logical-axes ``with_sharding_constraint`` that
no-ops when no mesh is installed (single-device tests) or when the target
axes are currently *manual* (inside a ``shard_map`` body — see
:func:`manual_axes`).
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

AxisRule = Union[None, str, Tuple[str, ...]]

DEFAULT_RULES: Dict[str, AxisRule] = {
    "batch": ("pod", "data"),
    "embed": None,            # weight FSDP dim — replicated unless training
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "expert": "model",
    "ssm_heads": "model",
    "layers": None,
    "kv_seq": None,
    "ssm_state": None,
}

TRAIN_RULES: Dict[str, AxisRule] = {**DEFAULT_RULES, "embed": "data"}

# Serving: the cache sequence axis takes `model`; the cache head axis must
# stay unsharded or it would claim `model` first (leading dims win).
SERVE_RULES: Dict[str, AxisRule] = {
    **DEFAULT_RULES, "kv_heads": None, "kv_seq": "model",
}

# long_500k: the KV cache dominates memory — spread its sequence axis over
# both data and model; batch parallelism keeps only the pod axis.
LONG_RULES: Dict[str, AxisRule] = {
    **DEFAULT_RULES, "batch": ("pod",), "kv_heads": None,
    "kv_seq": ("data", "model"),
}


class _Ctx(threading.local):

  def __init__(self):
    self.mesh = None
    self.rules: Optional[Dict[str, AxisRule]] = None
    self.manual: frozenset = frozenset()


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh, rules: Dict[str, AxisRule]):
  """Install (mesh, rules) as the ambient sharding context."""
  prev = (_CTX.mesh, _CTX.rules)
  _CTX.mesh, _CTX.rules = mesh, dict(rules)
  try:
    yield mesh
  finally:
    _CTX.mesh, _CTX.rules = prev


@contextlib.contextmanager
def manual_axes(axes):
  """Mark mesh axes as manual (inside a ``shard_map`` body): `constrain`
  stops emitting constraints that mention them."""
  prev = _CTX.manual
  _CTX.manual = prev | frozenset(axes)
  try:
    yield
  finally:
    _CTX.manual = prev


def current_mesh():
  return _CTX.mesh


def current_rules() -> Optional[Dict[str, AxisRule]]:
  return _CTX.rules


def rules_dict() -> Dict[str, AxisRule]:
  """The active rule table, or DEFAULT_RULES when none is installed."""
  return dict(_CTX.rules if _CTX.rules is not None else DEFAULT_RULES)


def tp_size(mesh) -> int:
  return int(mesh.shape.get("model", 1)) if mesh is not None else 1


def dp_size(mesh) -> int:
  if mesh is None:
    return 1
  n = 1
  for a in ("pod", "data"):
    n *= int(mesh.shape.get(a, 1))
  return n


def _axis_size(mesh, axes: Tuple[str, ...]) -> int:
  return math.prod(int(mesh.shape[a]) for a in axes)


def mesh_axes_for(logical_axes: Sequence[Optional[str]], mesh,
                  rules: Dict[str, AxisRule],
                  shape: Optional[Sequence[int]] = None) -> P:
  """Resolve logical axes -> PartitionSpec with divisibility + no-reuse
  fallbacks.  ``mesh`` only needs a ``.shape`` mapping (tests use fakes)."""
  used: set = set()
  entries = []
  for d, name in enumerate(logical_axes):
    target = rules.get(name) if name is not None else None
    if target is None:
      entries.append(None)
      continue
    axes = (target,) if isinstance(target, str) else tuple(target)
    axes = tuple(a for a in axes if a in mesh.shape and a not in used)
    # Drop trailing mesh axes until the dim divides evenly.
    while axes and shape is not None and \
        shape[d] % _axis_size(mesh, axes) != 0:
      axes = axes[:-1]
    if not axes:
      entries.append(None)
      continue
    used.update(axes)
    entries.append(axes[0] if len(axes) == 1 else axes)
  return P(*entries)


def named_sharding(logical_axes, mesh, rules,
                   shape: Optional[Sequence[int]] = None) -> NamedSharding:
  return NamedSharding(
      mesh, mesh_axes_for(logical_axes, mesh, rules, shape=shape))


def _is_axes_leaf(x: Any) -> bool:
  return x is None or (
      isinstance(x, tuple)
      and all(e is None or isinstance(e, str) for e in x))


def tree_shardings(axes_tree, mesh, rules, shapes_tree):
  """NamedSharding tree from a logical-axes tree + shape (or array) tree."""
  def one(ax, sds):
    ax = ax if ax is not None else (None,) * len(sds.shape)
    return named_sharding(ax, mesh, rules, shape=tuple(sds.shape))
  return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=_is_axes_leaf)


def _strip_manual(target: AxisRule, manual: frozenset) -> AxisRule:
  if target is None or not manual:
    return target
  axes = (target,) if isinstance(target, str) else tuple(target)
  axes = tuple(a for a in axes if a not in manual)
  if not axes:
    return None
  return axes[0] if len(axes) == 1 else axes


def constrain(x, logical_axes, rules: Optional[Dict[str, AxisRule]] = None):
  """``with_sharding_constraint`` by logical axis names.  No-op without an
  installed mesh, and manual (shard_map) axes are stripped first."""
  mesh = _CTX.mesh
  if mesh is None:
    return x
  r = dict(rules) if rules is not None else rules_dict()
  if _CTX.manual:
    r = {k: _strip_manual(v, _CTX.manual) for k, v in r.items()}
  spec = mesh_axes_for(logical_axes, mesh, r, shape=tuple(x.shape))
  if all(e is None for e in spec):
    return x
  return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
