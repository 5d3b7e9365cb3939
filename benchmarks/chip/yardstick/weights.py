"""Random weights from the seed, made on the device.

The benchmark draws the weights itself, and its reference draws them again
from the same seed, so the two read the same numbers and neither takes
them from the other.  The tree has the layout the system expects: the
structure and shapes come from ``jax.eval_shape`` of the system's
initializer (no value of it is computed), and every leaf is drawn here by
its name:

  embed               truncated normal (+-2 sd), sd 1; sd d^-1/2 where the
                      embeddings are tied (the table is the unembedding too)
  unembed             sd d^-1/2
  attn.wq, wk, wv     sd d^-1/2 (the input width of the projection)
  attn.wo             sd (H hd)^-1/2
  mlp.w1, w3          sd d^-1/2;  mlp.w2  sd d_ff^-1/2
  ln1, ln2, final_norm  0 (norm gains are offsets from 1)

so that every projection keeps its input's scale.  Each of these rules
holds for a leaf of its name at the rank listed (per layer: 1 for the
norms, 2 for ``embed``, ``unembed`` and ``mlp.*``, 3 for ``attn.*``).
Any other leaf takes its rule from the configuration file's
``"weight_rules"``, keyed by the leaf's name or by the end of its path
(``"moe/w1"``: the longest key that ends the path wins), which gives one
of

  "gain"              0 (an offset from 1)
  "fan_in"            sd shape[0]^-1/2
  "fan_in2"           sd (shape[0] shape[1])^-1/2, as ``wo``
  {"sd": x}           sd x, e.g. a gain drawn away from 1, so that a gain
                      read in the wrong layout shows in the check

(shapes of one layer, with no layer axis; every draw truncated at +-2
sd).  A leaf of a base name at another rank, such as a mixture of
experts' (E, d, f) ``moe/w1``, is not a base leaf: its rule comes from
the file, by its path.  A file rule that names a base name, that ends
the path of a leaf the base rules take, or that ends no leaf's path, is
an error, and so is a leaf with no rule: a configuration that brings a
leaf brings its rule.  Leaf i is drawn from ``fold_in(key, i)``; a leaf
stacked over layers (under ``blocks``) draws layer l from
``fold_in(fold_in(key, i), l)``, so that one layer can be drawn again
alone.  Draws are float32, cast in the same program to the served type.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
  """A threefry key from a non-negative seed of any size."""
  state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
  return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                  impl="threefry2x32")


# The base leaves' rules and the rank (of one layer's leaf) each holds
# for; ``embed`` has its own (see ``_base_std``).
BASE_RULES = {"ln1": ("gain", 1), "ln2": ("gain", 1),
              "final_norm": ("gain", 1), "unembed": ("fan_in", 2),
              "wq": ("fan_in", 3), "wk": ("fan_in", 3), "wv": ("fan_in", 3),
              "wo": ("fan_in2", 3), "w1": ("fan_in", 2),
              "w3": ("fan_in", 2), "w2": ("fan_in", 2)}


def _rule_std(rule, shape: Tuple[int, ...]) -> float:
  if rule == "gain":
    return 0.0
  if rule == "fan_in" and len(shape) >= 1:
    return shape[0] ** -0.5
  if rule == "fan_in2" and len(shape) >= 2:
    return (shape[0] * shape[1]) ** -0.5
  if isinstance(rule, dict) and set(rule) == {"sd"} and \
      type(rule["sd"]) in (int, float) and rule["sd"] >= 0:
    return float(rule["sd"])
  raise ValueError(f"weight rule {rule!r} does not apply to shape {shape}: "
                   'one of "gain", "fan_in", "fan_in2", {"sd": x >= 0}')


def _base_std(name: str, shape: Tuple[int, ...],
              tied: bool) -> Optional[float]:
  """Standard deviation of a base leaf of one layer (no layer axis), or
  None where the base rules do not take the leaf."""
  if name == "embed" and len(shape) == 2:
    # A tied table is also the unembedding, and takes its scale.
    return shape[1] ** -0.5 if tied else 1.0
  if name in BASE_RULES and BASE_RULES[name][1] == len(shape):
    return _rule_std(BASE_RULES[name][0], shape)
  return None


def _file_key(names, rules: Dict) -> Optional[str]:
  """The longest key of ``rules`` that ends the leaf's path."""
  tails = ("/".join(names[-k:]) for k in range(len(names), 0, -1))
  return next((t for t in tails if t in rules), None)


def _one(key, std: float, shape, dtype):
  if std == 0.0:
    return jnp.zeros(shape, dtype)
  x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
  return (std * x).astype(dtype)


class Weights:
  """The seed's weights for a tree of shapes (ShapeDtypeStruct leaves)."""

  def __init__(self, shapes: Any, seed: int, dtype,
               rules: Optional[Dict] = None):
    rules = rules or {}
    base = sorted(set(rules) & (set(BASE_RULES) | {"embed"}))
    if base:
      raise ValueError(f"weight_rules may not override the base leaves' "
                       f"rules: {base}")
    flat, self.treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [[p.key for p in path] for path, _ in flat]
    self.tied = not any(n[-1] == "unembed" for n in names)
    # (index, names, shape, stacked, sd of one layer's leaf); a stacked
    # leaf's layer has its shape without the leading layer axis.
    self.specs, used = [], set()
    for i, (n, (_, leaf)) in enumerate(zip(names, flat)):
      shape, stacked = tuple(leaf.shape), n[0] == "blocks"
      layer = shape[1:] if stacked else shape
      std = _base_std(n[-1], layer, self.tied and not stacked)
      key = _file_key(n, rules)
      if std is not None and key is not None:
        raise ValueError(f"weight_rules[{key!r}] names {'/'.join(n)}, a "
                         f"base leaf: its rule may not be overridden")
      if std is None:
        if key is None:
          raise KeyError(f"no weight rule for leaf {'/'.join(n)} {layer}: "
                         'the configuration file gives it under '
                         '"weight_rules", by its name or the end of its '
                         'path')
        std = _rule_std(rules[key], layer)
        used.add(key)
      self.specs.append((i, n, shape, stacked, std))
    unused = sorted(set(rules) - used)
    if unused:
      raise ValueError(f"weight_rules give no leaf of the tree its rule: "
                       f"{unused}")
    self.key = key_from_seed(seed)
    self.dtype = dtype
    self._layers: Dict[int, Dict] = {}

  def full(self) -> Any:
    """The whole tree, as the system takes it, in one jitted call."""

    @jax.jit
    def draw(key):
      out = []
      for i, _, shape, stacked, std in self.specs:
        k = jax.random.fold_in(key, i)
        if stacked:
          out.append(jax.vmap(lambda l, k=k, s=shape[1:], std=std: _one(
              jax.random.fold_in(k, l), std, s, self.dtype))(
                  jnp.arange(shape[0])))
        else:
          out.append(_one(k, std, shape, self.dtype))
      return out

    return jax.tree_util.tree_unflatten(self.treedef, draw(self.key))

  def top(self) -> Dict[str, jax.Array]:
    """The leaves outside the layer stack, by name."""
    specs = [s for s in self.specs if not s[3]]

    @jax.jit
    def draw(key):
      return {names[-1]: _one(jax.random.fold_in(key, i), std, shape,
                              self.dtype)
              for i, names, shape, _, std in specs}

    return draw(self.key)

  def layer(self, layer: int) -> Dict:
    """One layer's leaves, nested as under ``blocks/<position>``; drawn
    once, then kept."""
    if layer in self._layers:
      return self._layers[layer]
    specs = [s for s in self.specs if s[3]]
    out: Dict = {}
    for (_, names, _, _, _), val in zip(specs, _draw_layer(
        self.key, jnp.int32(layer),
        tuple((i, std, shape[1:]) for i, _, shape, _, std in specs),
        self.dtype)):
      node = out
      for n in names[2:-1]:
        node = node.setdefault(n, {})
      node[names[-1]] = val
    self._layers[layer] = out
    return out


def _draw_layer_impl(key, layer, specs, dtype):
  return [_one(jax.random.fold_in(jax.random.fold_in(key, i), layer), std,
               shape, dtype) for i, std, shape in specs]


_draw_layer = jax.jit(_draw_layer_impl, static_argnums=(2, 3))
