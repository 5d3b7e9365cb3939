"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e.

Each case lowers and compiles one kernel with ``impl="pallas"`` semantics
(no interpreter) against a *described* ``v5e:2x2`` chip, at the shapes
``chip_smoke.py`` serves: llama3-8b widths (32 heads over 8 KV heads,
head_dim 128, C=128), two slots, a 32768-token prompt (M=256 clusters),
a budget of 32 clusters.  Mosaic refuses here what interpret mode
accepts — an untiled block, a VMEM or SMEM overrun — so these guard the
kernels' tiling without a chip.  Nothing runs; each kernel compiles in
about a second or two.  Two more cases compile the whole prefill and
serve-step programs of the 8-layer cut and check that each fits one
v5e's HBM.

The topology is described inside a module fixture only: libtpu may be
loaded by one process at a time, and every xdist worker imports this
file."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.kernels.block_gather_attention import block_gather_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.fused_synopsis import fused_synopsis_score_attention
from repro.kernels.synopsis_build import segment_build
from repro.models import transformer as tf
from repro.serve import kv_cache as kvc
from repro.serve.prefill import make_prefill_step
from repro.serve.serve_step import make_serve_step

CFG = get_config("llama3-8b")
SLOTS, PROMPT, LAYERS, BUDGET = 2, 32768, 8, 32
H, HKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.hd
C = CFG.synopsis.cluster_size
M = PROMPT // C
E = 144            # recent ring (128) + the new token, padded to 16 rows
SM = D ** -0.5


@pytest.fixture(scope="module")
def one_chip():
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  from jax.experimental import topologies  # noqa: PLC0415
  from jax.experimental.compilation_cache import compilation_cache  # noqa
  from jax.sharding import SingleDeviceSharding  # noqa: PLC0415
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  # A compile for a described chip cannot be read back from the
  # persistent cache without one: keep the cache out of these compiles.
  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
  compiled = jax.jit(fn).lower(*args).compile()
  assert "tpu_custom_call" in compiled.as_text()   # the kernel, not XLA
  return compiled


BF, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
Q = ((SLOTS, H, D), BF)
KV = ((SLOTS, HKV, PROMPT, D), BF)
SYN = ((SLOTS, HKV, M, D), BF)

CASES = {
    "flash_prefill": (
        lambda q, k, v: flash_prefill(q, k, v, sm_scale=SM),
        ((1, PROMPT, H, D), BF), ((1, PROMPT, HKV, D), BF),
        ((1, PROMPT, HKV, D), BF)),
    "segment_build": (
        lambda k, v, p: segment_build(k, v, p, cluster_size=C),
        ((LAYERS, HKV, PROMPT, D), BF), ((LAYERS, HKV, PROMPT, D), BF),
        ((LAYERS, PROMPT), I32)),
    "segment_build_int8_kv": (
        lambda k, v, p: segment_build(k, v, p, cluster_size=C,
                                      quant="int8+kv"),
        ((LAYERS, HKV, PROMPT, D), BF), ((LAYERS, HKV, PROMPT, D), BF),
        ((LAYERS, PROMPT), I32)),
    "fused_synopsis_score_attention": (
        lambda q, ks, vs, cb: fused_synopsis_score_attention(
            q, ks, vs, cb, sm_scale=SM),
        Q, SYN, SYN, ((SLOTS, M), F32)),
    "fused_synopsis_score_attention_int8": (
        lambda q, ks, vs, cb, a, b: fused_synopsis_score_attention(
            q, ks, vs, cb, sm_scale=SM, k_scale=a, v_scale=b),
        Q, ((SLOTS, HKV, M, D), I8), ((SLOTS, HKV, M, D), I8),
        ((SLOTS, M), F32), ((SLOTS, HKV, M), F32), ((SLOTS, HKV, M), F32)),
    "block_gather_attention_epilogue_extras": (
        lambda q, k, v, sel, ksel, vsel, sb, ek, ev, eb:
        block_gather_attention(
            q, k, v, sel, cluster_size=C, sm_scale=SM, k_sel=ksel,
            v_sel=vsel, sel_bias=sb, extras_k=ek, extras_v=ev,
            extras_bias=eb),
        Q, KV, KV, ((SLOTS, HKV, BUDGET), I32),
        ((SLOTS, HKV, BUDGET, D), BF), ((SLOTS, HKV, BUDGET, D), BF),
        ((SLOTS, HKV, BUDGET), F32), ((SLOTS, HKV, E, D), BF),
        ((SLOTS, HKV, E, D), BF), ((SLOTS, E), F32)),
    "block_gather_attention_full_budget_int8_kv": (
        lambda q, k, v, sel, ksel, vsel, sb, a, b: block_gather_attention(
            q, k, v, sel, cluster_size=C, sm_scale=SM, k_sel=ksel,
            v_sel=vsel, sel_bias=sb, kv_k_scale=a, kv_v_scale=b),
        Q, ((SLOTS, HKV, PROMPT, D), I8), ((SLOTS, HKV, PROMPT, D), I8),
        ((SLOTS, HKV, M), I32), ((SLOTS, HKV, M, D), F32),
        ((SLOTS, HKV, M, D), F32), ((SLOTS, HKV, M), F32),
        ((SLOTS, HKV, M), F32), ((SLOTS, HKV, M), F32)),
    "flash_decode": (
        lambda q, k, v, b: flash_decode(q, k, v, b, sm_scale=SM),
        Q, KV, KV, ((SLOTS, HKV, PROMPT), F32)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
  fn, *shapes = CASES[name]
  _compile(fn, *(jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                 for s, dt in shapes))


HBM_BYTES = 16 * 2 ** 30          # one v5e


@pytest.mark.parametrize("program", ["prefill", "serve_step"])
def test_step_program_compiles_and_fits_v5e(program, one_chip):
  cfg = dataclasses.replace(CFG, n_layers=LAYERS)
  on_chip = lambda tree: jax.tree.map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
      tree)
  params = on_chip(jax.eval_shape(lambda k: tf.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
  if program == "prefill":
    fn = make_prefill_step(cfg, impl="pallas")
    args = (params, on_chip(jax.ShapeDtypeStruct((1, PROMPT), I32)))
  else:
    fn = make_serve_step(cfg, mode="synopsis", i_max=BUDGET, impl="pallas")
    args = (params,
            on_chip(kvc.cache_specs(cfg, SLOTS, PROMPT, synopsis=True)),
            on_chip(jax.ShapeDtypeStruct((SLOTS, 1), I32)))
  mem = _compile(fn, *args).memory_analysis()
  used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
          + mem.temp_size_in_bytes)
  assert used < HBM_BYTES, used
