"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + no NaNs (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, list_archs
from repro.configs.shapes import SHAPES, input_specs
from repro.models import common as cm
from repro.models import transformer as tf
from repro.serve import kv_cache as kvc
from repro.serve.prefill import make_prefill_step
from repro.serve.serve_step import make_serve_step
from repro.train.optimizer import OptConfig
from repro.train.train_step import init_train_state, make_train_step

ARCHS = list_archs()
B, S = 2, 32


def _batch(cfg, key=jax.random.PRNGKey(0)):
  tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
  labels = jnp.roll(tokens, -1, axis=1)
  fe = None
  if cfg.frontend == "vision_stub":
    fe = jnp.ones((B, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
  if cfg.encoder is not None:
    fe = jnp.ones((B, cfg.encoder.source_len, cfg.frontend_dim),
                  jnp.bfloat16)
  return tokens, labels, fe


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
  cfg = get_config(arch, smoke=True)
  params, _ = cm.split(tf.init_model(jax.random.PRNGKey(0), cfg))
  tokens, labels, fe = _batch(cfg)
  h, aux = tf.hidden_states(params, cfg, tokens, fe)
  text = S + (cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0)
  assert h.shape == (B, text, cfg.d_model)
  assert np.isfinite(np.asarray(h, np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_finite(arch):
  cfg = get_config(arch, smoke=True)
  opt_cfg = OptConfig(total_steps=10)
  state, _ = init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg)
  tokens, labels, fe = _batch(cfg)
  batch = {"tokens": tokens, "labels": labels}
  if fe is not None:
    batch["frontend_embeds"] = fe
  step = jax.jit(make_train_step(cfg, opt_cfg))
  state2, metrics = step(state, batch)
  assert np.isfinite(float(metrics["loss"]))
  assert np.isfinite(float(metrics["grad_norm"]))
  # params actually changed
  d0 = jax.tree.leaves(state["params"])[0]
  d1 = jax.tree.leaves(state2["params"])[0]
  assert not np.allclose(np.asarray(d0), np.asarray(d1))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_exact_finite(arch):
  cfg = get_config(arch, smoke=True)
  params, _ = cm.split(tf.init_model(jax.random.PRNGKey(0), cfg))
  params = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
  cache = kvc.init_cache(cfg, B, 64, synopsis=False)
  step = jax.jit(make_serve_step(cfg, mode="exact"))
  logits, new_state = step(params, cache,
                           jnp.zeros((B, 1), jnp.int32))
  assert logits.shape == (B, cfg.vocab)
  assert np.isfinite(np.asarray(logits, np.float32)).all()
  assert int(new_state["pos"][0]) == 65


@pytest.mark.parametrize("arch", [a for a in ARCHS if a != "mamba2-370m"])
def test_decode_synopsis_finite(arch):
  cfg = get_config(arch, smoke=True)
  params, _ = cm.split(tf.init_model(jax.random.PRNGKey(0), cfg))
  params = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
  cache = kvc.init_cache(cfg, B, 64, synopsis=True)
  step = jax.jit(make_serve_step(cfg, mode="synopsis", i_max=2))
  logits, _ = step(params, cache, jnp.zeros((B, 1), jnp.int32))
  assert logits.shape == (B, cfg.vocab)
  assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ["llama3-8b", "whisper-medium",
                                  "jamba-v0.1-52b", "deepseek-v2-236b"])
def test_prefill_emits_cache(arch):
  cfg = get_config(arch, smoke=True)
  params, _ = cm.split(tf.init_model(jax.random.PRNGKey(0), cfg))
  params = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
  tokens, _, fe = _batch(cfg)
  logits, cache = jax.jit(make_prefill_step(cfg))(params, tokens, fe)
  assert logits.shape == (B, cfg.vocab)
  na = kvc.n_attn_positions(cfg)
  if na:
    text = S + (cfg.frontend_tokens
                if cfg.frontend == "vision_stub" else 0)
    assert cache["k"].shape[0] == cfg.n_blocks
    assert cache["k"].shape[4] == text
  if kvc.n_ssm_positions(cfg):
    assert "ssd_state" in cache


def test_full_configs_match_assignment():
  expect = {
      "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
      "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
      "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
      "smollm-135m": (30, 576, 9, 3, 1536, 49152),
      "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
      "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
      "deepseek-v2-236b": (60, 5120, 128, 128, 0, 102400),
      "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
      "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
      "mamba2-370m": (48, 1024, 1, 1, 0, 50280),
  }
  for arch, (L, d, H, Hkv, ff, V) in expect.items():
    c = get_config(arch)
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (L, d, H, Hkv, ff, V), arch
  assert get_config("deepseek-v2-236b").moe.num_experts == 160
  assert get_config("deepseek-v2-236b").moe.top_k == 6
  assert get_config("deepseek-v2-236b").mla.kv_lora_rank == 512
  assert get_config("arctic-480b").moe.num_experts == 128
  assert get_config("jamba-v0.1-52b").moe.num_experts == 16
  assert get_config("mamba2-370m").ssm.d_state == 128


def test_shapes_table():
  assert SHAPES["train_4k"].seq_len == 4096
  assert SHAPES["train_4k"].global_batch == 256
  assert SHAPES["prefill_32k"].global_batch == 32
  assert SHAPES["decode_32k"].global_batch == 128
  assert SHAPES["long_500k"].seq_len == 524288
  cfg = get_config("llama3-8b")
  sp = input_specs(cfg, SHAPES["train_4k"])
  assert sp["tokens"].shape == (256, 4096)


def test_init_params_draws_stacked_layers_in_cfg_dtype():
  """One jitted init: every leaf in ``cfg.dtype``, the same tree for the
  same seed, and stacked layer i holds the draw of layer i's own key."""
  cfg = get_config("llama3-8b", smoke=True)
  key = jax.random.PRNGKey(3)
  params = tf.init_params(key, cfg)
  assert {p.dtype for p in jax.tree.leaves(params)} == {jnp.dtype(cfg.dtype)}
  again = tf.init_params(key, cfg)
  jax.tree.map(np.testing.assert_array_equal, params, again)
  other = tf.init_params(jax.random.PRNGKey(4), cfg)
  assert not np.array_equal(np.asarray(params["embed"], np.float32),
                            np.asarray(other["embed"], np.float32))
  # init_model's key schedule: pattern position 0 draws from split 1.
  layer_keys = jax.random.split(jax.random.split(key, 16)[1], cfg.n_blocks)
  for i, k in enumerate(layer_keys):
    one, _ = cm.split(tf._init_layer(k, cfg, cfg.block_pattern[0]))
    want = jax.tree.map(lambda p: p.astype(cfg.dtype), one)
    got = jax.tree.map(lambda p, i=i: p[i], params["blocks"]["pos0"])
    jax.tree.map(np.testing.assert_array_equal, got, want)
