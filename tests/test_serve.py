"""Serving-path integration: prefill -> synopsis build -> decode, plus the
AccuracyTrader accuracy/budget trade and incremental synopsis updates."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import common as cm
from repro.models import transformer as tf
from repro.serve import kv_cache as kvc
from repro.serve import synopsis_kv as skv
from repro.serve.prefill import make_prefill_step
from repro.serve.serve_step import make_serve_step

B, S = 2, 128


@pytest.fixture(scope="module")
def llama():
  cfg = get_config("llama3-8b", smoke=True)
  params, _ = cm.split(tf.init_model(jax.random.PRNGKey(0), cfg))
  params = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
  tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
  _, cache = jax.jit(make_prefill_step(cfg))(params, tokens)
  syn_cache = jax.jit(lambda c: skv.build(c, cfg))(cache)
  return cfg, params, cache, syn_cache


def test_synopsis_full_budget_equals_exact(llama):
  cfg, params, cache, syn_cache = llama
  M = S // cfg.synopsis.cluster_size
  nt = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab)
  lg_e, _ = jax.jit(make_serve_step(cfg, mode="exact"))(params, cache, nt)
  lg_s, _ = jax.jit(make_serve_step(cfg, mode="synopsis", i_max=M))(
      params, syn_cache, nt)
  np.testing.assert_allclose(np.asarray(lg_s, np.float32),
                             np.asarray(lg_e, np.float32),
                             rtol=5e-2, atol=5e-2)


def test_accuracy_improves_with_budget(llama):
  cfg, params, cache, syn_cache = llama
  M = S // cfg.synopsis.cluster_size
  nt = jax.random.randint(jax.random.PRNGKey(3), (B, 1), 0, cfg.vocab)
  lg_e, _ = jax.jit(make_serve_step(cfg, mode="exact"))(params, cache, nt)
  p_e = jax.nn.softmax(lg_e.astype(jnp.float32), -1)
  errs = []
  for i_max in (0, M // 2, M):
    lg, _ = jax.jit(make_serve_step(cfg, mode="synopsis", i_max=i_max))(
        params, syn_cache, nt)
    p = jax.nn.softmax(lg.astype(jnp.float32), -1)
    errs.append(float(0.5 * jnp.abs(p - p_e).sum(-1).mean()))
  assert errs[-1] < 1e-3                   # full budget == exact
  assert errs[0] >= errs[1] - 1e-4         # more budget, no worse


def test_synopsis_centroids_are_cluster_means(llama):
  cfg, params, cache, syn_cache = llama
  C = cfg.synopsis.cluster_size
  k = np.asarray(syn_cache["k"], np.float32)
  ks = np.asarray(syn_cache["k_syn"], np.float32)
  nb, na, b, h, s, d = k.shape
  got = k.reshape(nb, na, b, h, s // C, C, d).mean(5)
  np.testing.assert_allclose(ks, got, rtol=2e-2, atol=2e-2)


def test_synopsis_preserves_token_set(llama):
  cfg, params, cache, syn_cache = llama
  # the permuted cache holds exactly the same rows as the original
  k0 = np.asarray(cache["k"], np.float32)[0, 0, 0, 0]
  k1 = np.asarray(syn_cache["k"], np.float32)[0, 0, 0, 0]
  s0 = np.sort(k0.sum(-1))
  s1 = np.sort(k1.sum(-1))
  np.testing.assert_allclose(s0, s1, rtol=1e-3, atol=1e-3)


def test_append_and_absorb_recent(llama):
  cfg, params, cache, syn_cache = llama
  C = cfg.synopsis.cluster_size
  R = cfg.synopsis.recent
  nb = cfg.n_blocks
  na = kvc.n_attn_positions(cfg)
  Hkv, Dk = syn_cache["k"].shape[3], syn_cache["k"].shape[5]
  cur = syn_cache
  for i in range(R):
    kd = jnp.full((nb, na, B, Hkv, 1, Dk), float(i), cfg.dtype)
    cur = skv.append_recent(cur, kd, kd)
  assert int(cur["recent_len"][0]) == R
  absorbed = skv.absorb_recent(cur, cfg)
  assert absorbed["k"].shape[4] == S + R
  assert absorbed["k_syn"].shape[4] == (S + R) // C
  assert int(absorbed["recent_len"][0]) == 0
  # new centroids = means of the absorbed recents
  newc = np.asarray(absorbed["k_syn"], np.float32)[0, 0, 0, 0, S // C:]
  exp = np.asarray(cur["recent_k"], np.float32)[0, 0, 0, 0].reshape(
      R // C, C, -1).mean(1)
  np.testing.assert_allclose(newc, exp, rtol=2e-2, atol=2e-2)


def test_decode_then_absorb_consistency(llama):
  """Tokens attended via the recent buffer before absorb must still be
  attended (via synopsis clusters) after absorb."""
  cfg, params, cache, syn_cache = llama
  R = cfg.synopsis.recent
  step = jax.jit(make_serve_step(cfg, mode="synopsis", i_max=2))
  cur = syn_cache
  tok = jnp.zeros((B, 1), jnp.int32)
  for _ in range(R):
    lg, st = step(params, cur, tok)
    cur = skv.append_recent(cur, st["k_delta"], st["v_delta"])
    cur["pos"] = st["pos"]
  lg_before, _ = step(params, cur, tok)
  absorbed = skv.absorb_recent(cur, cfg)
  M2 = absorbed["k_syn"].shape[4]
  lg_after, _ = jax.jit(make_serve_step(cfg, mode="synopsis", i_max=M2))(
      params, absorbed, tok)
  # full-budget after absorb == exact over all S+R tokens; the before
  # variant (budget 2 + recent exact) should be a coarse version of it.
  p1 = jax.nn.softmax(lg_before.astype(jnp.float32), -1)
  p2 = jax.nn.softmax(lg_after.astype(jnp.float32), -1)
  tv = float(0.5 * jnp.abs(p1 - p2).sum(-1).mean())
  assert tv < 0.5


@pytest.mark.parametrize("argv,smoke", [([], True), (["--smoke"], True),
                                        (["--no-smoke"], False)])
def test_serve_smoke_flag_switches(argv, smoke):
  from repro.launch.serve import make_parser
  assert make_parser().parse_args(argv).smoke is smoke


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
  """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the helper
  reports it and sets nothing."""
  from repro.launch.compile_cache import enable_compile_cache
  monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
  before = jax.config.jax_compilation_cache_dir
  assert enable_compile_cache() == str(tmp_path)
  assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
  import pathlib
  from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                          enable_compile_cache)
  monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
  root = pathlib.Path(__file__).resolve().parents[1]
  assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
  assert ".jax_cache/" in (root / ".gitignore").read_text().split()
  before = jax.config.jax_compilation_cache_dir
  try:
    assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
  finally:
    jax.config.update("jax_compilation_cache_dir", before)
