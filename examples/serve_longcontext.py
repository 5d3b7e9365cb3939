"""Long-context decode with synopsis attention (the long_500k cell's
mechanism, demo-sized for CPU).

Prefills a prompt with llama-family smoke config, builds the KV synopsis
(offline module), then decodes with AccuracyTrader attention at several
budgets, comparing next-token distributions against exact attention —
the LM analogue of the paper's accuracy-loss tables.

  PYTHONPATH=src python examples/serve_longcontext.py [--seq 512]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.models import transformer as tf
from repro.serve import synopsis_kv as skv
from repro.serve.kv_cache import n_attn_positions
from repro.serve.prefill import make_prefill_step
from repro.serve.serve_step import make_serve_step


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", default="llama3-8b")
  ap.add_argument("--seq", type=int, default=512)
  ap.add_argument("--batch", type=int, default=2)
  ap.add_argument("--tokens", type=int, default=8)
  args = ap.parse_args()

  cfg = get_config(args.arch, smoke=True)
  assert n_attn_positions(cfg) > 0, "synopsis attention needs attention"
  key = jax.random.PRNGKey(0)
  params = tf.init_params(key, cfg)

  B, S = args.batch, args.seq
  prompt = jax.random.randint(key, (B, S), 0, cfg.vocab)
  print(f"prefill {S} tokens ({cfg.name})...")
  _, cache = jax.jit(make_prefill_step(cfg))(params, prompt)
  print("building synopsis (offline module): "
        f"C={cfg.synopsis.cluster_size}, M={S // cfg.synopsis.cluster_size}")
  syn_cache = jax.jit(lambda c: skv.build(c, cfg))(cache)

  M = S // cfg.synopsis.cluster_size
  exact_step = jax.jit(make_serve_step(cfg, mode="exact"))
  nt = jax.random.randint(jax.random.PRNGKey(7), (B, 1), 0, cfg.vocab)

  print(f"\n{'i_max':>6s} {'kv rows touched':>16s} {'TV-dist to exact':>17s} "
        f"{'argmax match':>13s}")
  lg_ex, _ = exact_step(params, cache, nt)
  p_ex = jax.nn.softmax(lg_ex.astype(jnp.float32), -1)
  for i_max in [0, 1, 2, M // 2, M]:
    step = jax.jit(make_serve_step(cfg, mode="synopsis", i_max=i_max))
    lg, _ = step(params, syn_cache, nt)
    p = jax.nn.softmax(lg.astype(jnp.float32), -1)
    tv = float(0.5 * jnp.abs(p - p_ex).sum(-1).mean())
    match = float((jnp.argmax(lg, -1) == jnp.argmax(lg_ex, -1)).mean())
    rows = M + i_max * cfg.synopsis.cluster_size
    print(f"{i_max:6d} {rows:10d}/{S:5d} {tv:17.4f} {100*match:12.0f}%")

  print("\nAt the long_500k production shape the same mechanism touches "
        "S/C + i_max*C + R\nrows instead of 524288 — see "
        "artifacts/dryrun/*long_500k* for the roofline.")


if __name__ == "__main__":
  main()
