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

so that every projection keeps its input's scale.  A leaf of another name
is an error: a configuration that brings one brings its rule.  Leaf i is
drawn from ``fold_in(key, i)``; a leaf stacked over layers (under
``blocks``) draws layer l from ``fold_in(fold_in(key, i), l)``, so that
one layer can be drawn again alone.  Draws are float32, cast in the same
program to the served type.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
  """A threefry key from a non-negative seed of any size."""
  state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
  return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                  impl="threefry2x32")


def _std(name: str, shape: Tuple[int, ...], tied: bool) -> float:
  """Standard deviation of a leaf of one layer (no layer axis)."""
  if name in ("ln1", "ln2", "final_norm"):
    return 0.0
  if name == "embed":
    # A tied table is also the unembedding, and takes its scale.
    return shape[1] ** -0.5 if tied else 1.0
  if name in ("unembed", "wq", "wk", "wv", "w1", "w3", "w2"):
    return shape[0] ** -0.5
  if name == "wo":
    return (shape[0] * shape[1]) ** -0.5
  raise KeyError(f"no weight rule for leaf {name!r} {shape}")


def _one(key, name: str, shape, dtype, tied: bool = False):
  std = _std(name, shape, tied)
  if std == 0.0:
    return jnp.zeros(shape, dtype)
  x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
  return (std * x).astype(dtype)


class Weights:
  """The seed's weights for a tree of shapes (ShapeDtypeStruct leaves)."""

  def __init__(self, shapes: Any, seed: int, dtype):
    flat, self.treedef = jax.tree_util.tree_flatten_with_path(shapes)
    self.specs = []
    for i, (path, leaf) in enumerate(flat):
      names = [p.key for p in path]
      self.specs.append((i, names, tuple(leaf.shape), names[0] == "blocks"))
    self.key = key_from_seed(seed)
    self.dtype = dtype
    self.tied = not any(names[-1] == "unembed" for _, names, _, _ in
                        self.specs)
    self._layers: Dict[int, Dict] = {}

  def full(self) -> Any:
    """The whole tree, as the system takes it, in one jitted call."""

    @jax.jit
    def draw(key):
      out = []
      for i, names, shape, stacked in self.specs:
        k = jax.random.fold_in(key, i)
        if stacked:
          out.append(jax.vmap(lambda l, k=k, n=names[-1], s=shape[1:]: _one(
              jax.random.fold_in(k, l), n, s, self.dtype))(
                  jnp.arange(shape[0])))
        else:
          out.append(_one(k, names[-1], shape, self.dtype, self.tied))
      return out

    return jax.tree_util.tree_unflatten(self.treedef, draw(self.key))

  def top(self) -> Dict[str, jax.Array]:
    """The leaves outside the layer stack, by name."""
    specs = [s for s in self.specs if not s[3]]

    @jax.jit
    def draw(key):
      return {names[-1]: _one(jax.random.fold_in(key, i), names[-1], shape,
                              self.dtype, self.tied)
              for i, names, shape, _ in specs}

    return draw(self.key)

  def layer(self, layer: int) -> Dict:
    """One layer's leaves, nested as under ``blocks/<position>``; drawn
    once, then kept."""
    if layer in self._layers:
      return self._layers[layer]
    specs = [s for s in self.specs if s[3]]
    out: Dict = {}
    for (i, names, shape, _), val in zip(specs, _draw_layer(
        self.key, jnp.int32(layer), tuple((i, names[-1], shape[1:])
                                          for i, names, shape, _ in specs),
        self.dtype)):
      node = out
      for n in names[2:-1]:
        node = node.setdefault(n, {})
      node[names[-1]] = val
    self._layers[layer] = out
    return out


def _draw_layer_impl(key, layer, specs, dtype):
  return [_one(jax.random.fold_in(jax.random.fold_in(key, i), layer), name,
               shape, dtype) for i, name, shape in specs]


_draw_layer = jax.jit(_draw_layer_impl, static_argnums=(2, 3))
