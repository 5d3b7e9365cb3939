"""The weights a configuration file's rules draw, and the model fields a
file holds the program to.

Leaves beyond the base set (here the per-head query and key norms of a
qk-norm model) draw by the file's ``weight_rules``; the base leaves draw
bit for bit as they did before a file could carry rules, compared with a
copy of that code; and ``arch`` gives a configuration without fields
beyond the base the same dict as before, compared with a copy of it.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yardstick import harness
from yardstick import weights as wts

SEED = 2 ** 31 + 77
NEMO = "mistral-nemo-12b-d4"
L, D, H, HKV, HD, F, V, E = 2, 64, 8, 4, 64, 128, 256, 4


# -- the draws before configuration files carried rules, copied ---------------

def _old_std(name, shape, tied):
  if name in ("ln1", "ln2", "final_norm"):
    return 0.0
  if name == "embed":
    return shape[1] ** -0.5 if tied else 1.0
  if name in ("unembed", "wq", "wk", "wv", "w1", "w3", "w2"):
    return shape[0] ** -0.5
  if name == "wo":
    return (shape[0] * shape[1]) ** -0.5
  raise KeyError(f"no weight rule for leaf {name!r} {shape}")


def _old_one(key, name, shape, dtype, tied=False):
  std = _old_std(name, shape, tied)
  if std == 0.0:
    return jnp.zeros(shape, dtype)
  x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
  return (std * x).astype(dtype)


def _old_full(shapes, seed, dtype):
  flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
  specs = []
  for i, (path, leaf) in enumerate(flat):
    names = [p.key for p in path]
    specs.append((i, names, tuple(leaf.shape), names[0] == "blocks"))
  tied = not any(names[-1] == "unembed" for _, names, _, _ in specs)

  @jax.jit
  def draw(key):
    out = []
    for i, names, shape, stacked in specs:
      k = jax.random.fold_in(key, i)
      if stacked:
        out.append(jax.vmap(lambda l, k=k, n=names[-1], s=shape[1:]: _old_one(
            jax.random.fold_in(k, l), n, s, dtype))(jnp.arange(shape[0])))
      else:
        out.append(_old_one(k, names[-1], shape, dtype, tied))
    return out

  return jax.tree_util.tree_unflatten(treedef, draw(wts.key_from_seed(seed)))


def _old_arch(cfg):
  return {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
          "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
          "d_ff": cfg.d_ff, "vocab": cfg.vocab, "n_layers": cfg.n_layers,
          "rope_theta": float(cfg.rope_theta), "norm_eps": float(cfg.norm_eps),
          "parallel_block": bool(cfg.parallel_block),
          "tie_embeddings": bool(cfg.tie_embeddings),
          "cluster_size": cfg.synopsis.cluster_size,
          "recent": cfg.synopsis.recent}


# -- shapes --------------------------------------------------------------------

def _s(*shape):
  return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def _shapes(tied=False, qk_norm=False):
  """A small tree in the system's layout; with ``qk_norm``, per-head
  query and key norm gains beside the projections."""
  attn = {"wq": _s(L, D, H, HD), "wk": _s(L, D, HKV, HD),
          "wv": _s(L, D, HKV, HD), "wo": _s(L, H, HD, D)}
  if qk_norm:
    attn.update(q_norm=_s(L, H, HD), k_norm=_s(L, HKV, HD))
  tree = {"blocks": {"pos0": {
              "attn": attn, "ln1": _s(L, D), "ln2": _s(L, D),
              "mlp": {"w1": _s(L, D, F), "w2": _s(L, F, D),
                      "w3": _s(L, D, F)}}},
          "embed": _s(V, D), "final_norm": _s(D)}
  if not tied:
    tree["unembed"] = _s(D, V)
  return tree


def _nemo():
  bench = harness.benchmark()
  entry = next(c for c in bench["configs"] if c["name"] == NEMO)
  return json.loads((harness.CHECKOUT / entry["file"]).read_text())


def _f32(x):
  return np.asarray(x.astype(jnp.float32))


# -- rules from the file -------------------------------------------------------

# A truncated normal within +-2 sd has a standard deviation 0.8796 sd.
TRUNC = 0.87962566


@pytest.mark.parametrize("rule,sd", [
    ("gain", 0.0), ("fan_in", H ** -0.5), ("fan_in2", (H * HD) ** -0.5),
    ({"sd": 0.1}, 0.1)])
def test_a_leaf_beyond_the_base_draws_by_its_file_rule(rule, sd):
  w = wts.Weights(_shapes(qk_norm=True), SEED, jnp.bfloat16,
                  rules={"q_norm": rule, "k_norm": {"sd": 0.1}})
  std = {n[-1]: s for _, n, _, _, s in w.specs}
  assert std["q_norm"] == pytest.approx(sd)
  assert std["k_norm"] == pytest.approx(0.1)
  attn = w.full()["blocks"]["pos0"]["attn"]
  q = _f32(attn["q_norm"])
  assert q.shape == (L, H, HD)
  if sd == 0.0:
    assert not q.any()
  else:
    assert q.std() == pytest.approx(TRUNC * sd, rel=0.1)
    assert np.abs(q).max() <= 2 * sd * 1.01
  k = _f32(attn["k_norm"])
  assert k.std() == pytest.approx(TRUNC * 0.1, rel=0.1)
  assert np.abs(k).max() <= 0.2 * 1.01
  # The reference draws a layer again alone: the same numbers.
  for layer in range(L):
    again = w.layer(layer)["attn"]
    np.testing.assert_array_equal(_f32(again["q_norm"]), q[layer])
    np.testing.assert_array_equal(_f32(again["k_norm"]), k[layer])


@pytest.mark.parametrize("rules", [None, {"q_norm": "gain"}])
def test_a_leaf_with_no_rule_is_an_error(rules):
  with pytest.raises(KeyError, match="weight_rules"):
    wts.Weights(_shapes(qk_norm=True), SEED, jnp.bfloat16, rules=rules)


@pytest.mark.parametrize("rules", [
    {"q_norm": "gain", "k_norm": "gain", "ln1": {"sd": 0.1}},
    {"q_norm": "gain", "k_norm": "gain", "embed": "fan_in"},
    {"q_norm": "gain", "k_norm": "gain", "v_norm": "gain"},
    {"q_norm": "xavier", "k_norm": "gain"},
    {"q_norm": {"sd": -0.1}, "k_norm": "gain"},
    {"q_norm": {"sd": 0.1, "mean": 1.0}, "k_norm": "gain"}])
def test_a_rule_that_overrides_a_base_leaf_or_is_malformed_is_an_error(rules):
  with pytest.raises(ValueError):
    wts.Weights(_shapes(qk_norm=True), SEED, jnp.bfloat16, rules=rules)


# -- a base name at another rank, as a mixture of experts' -------------------

def _moe_shapes():
  """Experts that reuse the base names w1, w3, w2 at rank 3 (E, d, f),
  beside the dense mlp's rank-2 leaves of the same names."""
  tree = _shapes()
  tree["blocks"]["pos0"]["moe"] = {
      "router": _s(L, D, E), "w1": _s(L, E, D, F), "w3": _s(L, E, D, F),
      "w2": _s(L, E, F, D)}
  return tree


MOE_RULES = {"router": "fan_in", "moe/w1": {"sd": D ** -0.5},
             "moe/w3": {"sd": D ** -0.5}, "moe/w2": {"sd": F ** -0.5}}


def test_a_base_name_at_another_rank_draws_by_its_path_rule():
  w = wts.Weights(_moe_shapes(), SEED, jnp.bfloat16, rules=MOE_RULES)
  std = {"/".join(n[2:]): s for _, n, _, stacked, s in w.specs if stacked}
  assert std["moe/router"] == D ** -0.5
  assert std["moe/w1"] == std["moe/w3"] == std["mlp/w1"] == D ** -0.5
  assert std["moe/w2"] == std["mlp/w2"] == F ** -0.5
  moe = w.full()["blocks"]["pos0"]["moe"]
  assert _f32(moe["w1"]).std() == pytest.approx(TRUNC * D ** -0.5, rel=0.1)
  assert _f32(moe["w2"]).std() == pytest.approx(TRUNC * F ** -0.5, rel=0.1)


@pytest.mark.parametrize("leaf", ["moe/w1", "moe/w3", "moe/w2"])
def test_a_base_name_at_another_rank_with_no_rule_is_an_error(leaf):
  rules = {k: v for k, v in MOE_RULES.items() if k != leaf}
  with pytest.raises(KeyError, match=f"{leaf}.*weight_rules"):
    wts.Weights(_moe_shapes(), SEED, jnp.bfloat16, rules=rules)


@pytest.mark.parametrize("extra", [
    {"w1": {"sd": 0.1}}, {"mlp/w1": "fan_in"}, {"pos0/mlp/w2": "fan_in"},
    {"pos0/moe/w1": {"sd": 0.1}}])
def test_a_path_rule_for_a_base_leaf_or_for_no_leaf_is_an_error(extra):
  # A bare base name; the path of a base leaf; a longer key that leaves
  # the shorter one without a leaf.
  with pytest.raises(ValueError):
    wts.Weights(_moe_shapes(), SEED, jnp.bfloat16,
                rules=dict(MOE_RULES, **extra))


# -- today's leaves draw as before ---------------------------------------------

def test_the_base_leaves_of_mistral_nemo_keep_their_sd():
  from repro.models import transformer as tf  # noqa: PLC0415

  conf = _nemo()
  cfg = harness.model_config(conf)
  # Shapes only: no value of the 12B tree is computed.
  shapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                          jax.random.PRNGKey(0))
  w = wts.Weights(shapes, SEED, cfg.dtype, rules=conf.get("weight_rules"))
  assert len(w.specs) == 12
  for _, names, shape, stacked, std in w.specs:
    layer = shape[1:] if stacked else shape
    assert std == _old_std(names[-1], layer, w.tied and not stacked), names


@pytest.mark.parametrize("tied", [False, True])
def test_the_base_leaves_draw_bit_for_bit_as_before(tied):
  shapes = _shapes(tied=tied)
  w = wts.Weights(shapes, SEED, jnp.bfloat16)
  new, old = w.full(), _old_full(shapes, SEED, jnp.bfloat16)
  for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(new)[0],
                          jax.tree_util.tree_leaves(old)):
    np.testing.assert_array_equal(_f32(a), _f32(b), err_msg=str(path))
  top = w.top()
  for name in top:
    np.testing.assert_array_equal(_f32(top[name]), _f32(old[name]))
  block = old["blocks"]["pos0"]
  again = w.layer(1)
  for path, a in jax.tree_util.tree_flatten_with_path(again)[0]:
    b = block
    for p in path:
      b = b[p.key]
    np.testing.assert_array_equal(_f32(a), _f32(b[1]), err_msg=str(path))


# -- model fields --------------------------------------------------------------

def test_mistral_nemo_arch_is_as_before():
  conf = _nemo()
  cfg = harness.model_config(conf)
  assert list(harness.arch(cfg, conf).items()) == list(
      _old_arch(cfg).items())
