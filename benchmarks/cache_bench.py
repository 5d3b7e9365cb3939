"""Corpus-cache sweep: admission p99 + aggregate QPS under Zipf-repeated
corpora, hit-rate sweep, and the prefix-extension delta-replay ratio
(DESIGN.md §12; BENCH_cache.json).

The A/B arm serves the identical 100%-repeat trace (one corpus, every
admission after the first is an exact content hit) with the cache on vs
off, policy ``fixed`` so the budget stream — and therefore accuracy — is
deterministic: the loss delta between the arms must be exactly zero
while the hit path cuts the per-request admission wall (write-only
instead of prefill + build + write).  Admissions run serial
(``overlap_admission=False``) so each request's wall is individually
measurable; each arm is measured on its SECOND window — the first warms
the cache (and matches the off arm's thermal state), the second runs at
100% hit rate.

The hit-rate sweep varies the Zipf pool size K (K=1 -> ~100% repeats;
K > capacity -> eviction churn and a sub-1.0 hit rate) under the
accuracytrader policy — the measured hit-rate vs admission-tail curve
committed to EXPERIMENTS.md §Cache.

  PYTHONPATH=src:. python -m benchmarks.cache_bench \
      --json BENCH_cache.json            # committed baseline
  PYTHONPATH=src:. python -m benchmarks.cache_bench --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Optional, Sequence


def _run_two_windows(eng, rate: float, duration_s: float, seed: int,
                     zipf_corpora: int) -> Dict:
  """Warm window then measured window on the identical trace seed: the
  measured window starts with every corpus resident (100% hit rate when
  the pool fits capacity), and the off arm gets the same warm host."""
  from repro.serve.engine import run_open_loop
  run_open_loop(eng, rate_per_s=rate, duration_s=duration_s,
                seed=seed, zipf_corpora=zipf_corpora)
  return run_open_loop(eng, rate_per_s=rate, duration_s=duration_s,
                       seed=seed, zipf_corpora=zipf_corpora)


def cache_sweep(*,
                rate: float = 400.0,
                pools: Sequence[int] = (1, 4, 16, 64),
                n_slots: int = 4,
                prompt_len: int = 128,
                max_new_tokens: int = 8,
                deadline_ms: float = 60.0,
                duration_s: float = 1.0,
                capacity: int = 16,
                arch: str = "llama3-8b",
                impl: Optional[str] = None,
                seed: int = 2) -> Dict:
  from repro.configs.registry import get_config
  from repro.serve.engine import CacheConfig, EngineConfig, ServingEngine

  cfg = get_config(arch, smoke=True)
  C = cfg.synopsis.cluster_size
  out: Dict = {"config": {
      "arch": arch, "n_slots": n_slots, "prompt_len": prompt_len,
      "max_new_tokens": max_new_tokens, "deadline_ms": deadline_ms,
      "duration_s": duration_s, "rate_per_s": rate, "capacity": capacity,
      "pools": list(pools), "seed": seed,
      "trace_seed_rule": "seed*1000 + pool_index"}}

  def engine(policy, cache_on):
    cache = CacheConfig(capacity=capacity, delta_unit=C) if cache_on \
        else None
    return ServingEngine(cfg, EngineConfig(
        n_slots=n_slots, prompt_len=prompt_len,
        max_new_tokens=max_new_tokens, deadline_ms=deadline_ms,
        policy=policy, fixed_budget=1, impl=impl, seed=seed,
        overlap_admission=False, cache=cache))

  # -- A/B arm: 100% repeats, deterministic budgets, cache on vs off ------
  ab = {}
  for on in (True, False):
    eng = engine("fixed", on)
    out["config"]["impl"] = eng.impl
    s = _run_two_windows(eng, rate, duration_s, seed * 1000,
                         zipf_corpora=1)
    name = "cache_on" if on else "cache_off"
    ab[name] = {k: round(float(v), 3) for k, v in s.items()
                if not isinstance(v, dict)}
    print(f"cache_ab_{name},{s['admission_p50'] * 1e3:.1f},"
          f"adm_p99={s['admission_p99']:.2f}ms p99={s['p99']:.1f}ms "
          f"goodput={s['goodput_per_s']:.1f}/s "
          f"loss={s['accuracy_loss_pct']:.3f}% "
          f"prefills={s['prefills']:.0f} served={s['served_n']:.0f}"
          + (f" hit_rate={s['cache_hit_rate']:.2f}" if on else ""))
  out["ab"] = ab

  # -- hit-rate sweep: Zipf pool size K vs admission tail -----------------
  rows = {}
  for pi, K in enumerate(pools):
    eng = engine("accuracytrader", True)
    s = _run_two_windows(eng, rate, duration_s, seed * 1000 + pi,
                         zipf_corpora=int(K))
    rows[str(K)] = {k: round(float(v), 3) for k, v in s.items()
                    if not isinstance(v, dict)}
    print(f"cache_pool{K},{s['admission_p50'] * 1e3:.1f},"
          f"hit_rate={s['cache_hit_rate']:.3f} "
          f"adm_p99={s['admission_p99']:.2f}ms p99={s['p99']:.1f}ms "
          f"loss={s['accuracy_loss_pct']:.2f}% "
          f"entries={s['cache_entries']:.0f} "
          f"evictions={s['cache_evictions']:.0f}")
  out["hit_rate_sweep"] = rows

  # -- delta replay: extend-step cost vs full rebuild ---------------------
  out["delta"] = _delta_ratio(cfg, prompt_len, impl=impl, seed=seed)

  on, off = ab["cache_on"], ab["cache_off"]
  out["check"] = {
      "admission_p99_on": on["admission_p99"],
      "admission_p99_off": off["admission_p99"],
      "goodput_on": on["goodput_per_s"],
      "goodput_off": off["goodput_per_s"],
      "loss_on": on["accuracy_loss_pct"],
      "loss_off": off["accuracy_loss_pct"],
      "hit_rate_on": on["cache_hit_rate"],
      # Hit-path admission must beat the miss path on the tail, at
      # equal-or-better aggregate QPS and an exactly-zero loss delta
      # (fixed budgets: both arms score identically by construction).
      "hit_beats_miss_p99": bool(
          on["admission_p99"] < off["admission_p99"]),
      "qps_no_worse": bool(
          on["goodput_per_s"] >= off["goodput_per_s"]),
      "zero_loss_delta": bool(
          on["accuracy_loss_pct"] == off["accuracy_loss_pct"]),
      "full_hit_rate": bool(on["cache_hit_rate"] == 1.0),
  }
  return out


def _delta_ratio(cfg, prompt_len: int, *, impl=None, seed=2,
                 iters: int = 5) -> Dict:
  """Measured wall of the prefix-extension delta replay (extend step +
  incremental build over E tokens) vs the full rebuild (prefill + build
  over P+E) it replaces — the append-only-session win."""
  import jax
  import jax.numpy as jnp
  import numpy as np

  from repro.models import transformer as tf
  from repro.serve import synopsis_kv as skv
  from repro.serve.prefill import make_extend_step, make_prefill_step

  # Half/half split: both halves keep power-of-two cluster counts, which
  # the balanced-kd clustering requires.
  E = prompt_len // 2
  P = prompt_len - E
  params = tf.init_params(jax.random.PRNGKey(seed), cfg)
  rng = np.random.default_rng(seed)
  toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, prompt_len)), jnp.int32)
  prefill = jax.jit(make_prefill_step(cfg, impl=impl))
  build = jax.jit(lambda c: skv.build(c, cfg, impl=impl))
  extend = jax.jit(make_extend_step(cfg, impl=impl))
  ext_build = jax.jit(
      lambda a, k, v: skv.extend_synopsis(a, k, v, cfg, impl=impl))

  _, pre = prefill(params, toks[:, :P])
  arena = build(pre)

  def full():
    _, c = prefill(params, toks)
    return build(c)

  def delta():
    _, (k_new, v_new) = extend(params, toks[:, P:], arena["k"],
                               arena["v"], jnp.int32(P))
    return ext_build(arena, k_new, v_new)

  def timed(fn):
    jax.block_until_ready(fn())                      # compile
    ts = []
    for _ in range(iters):
      t0 = time.perf_counter()
      jax.block_until_ready(fn())
      ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))

  full_ms, delta_ms = timed(full), timed(delta)
  ratio = full_ms / delta_ms if delta_ms > 0 else 0.0
  print(f"cache_delta_replay,{delta_ms * 1e3:.1f},"
        f"full={full_ms:.2f}ms delta={delta_ms:.2f}ms "
        f"speedup={ratio:.2f}x (P={P} E={E})")
  return {"P": P, "E": E, "full_ms": round(full_ms, 3),
          "delta_ms": round(delta_ms, 3), "speedup": round(ratio, 2)}


def main(argv: Optional[Sequence[str]] = None) -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--json", default=None, metavar="PATH",
                  help="dump the sweep as a JSON baseline "
                       "(e.g. BENCH_cache.json)")
  ap.add_argument("--smoke", action="store_true",
                  help="tiny sweep for CI: short windows, small pools")
  ap.add_argument("--impl", default=None,
                  choices=["auto", "pallas", "xla", "interpret"])
  args = ap.parse_args(argv)

  print("name,us_per_call,derived")
  t0 = time.perf_counter()
  if args.smoke:
    res = cache_sweep(rate=200.0, pools=(1, 4, 16), n_slots=2,
                      prompt_len=64, max_new_tokens=4, deadline_ms=40.0,
                      duration_s=0.5, capacity=16, impl=args.impl)
  else:
    res = cache_sweep(impl=args.impl)
  from benchmarks.common import bench_meta
  res["meta"] = bench_meta(wall_s=round(time.perf_counter() - t0, 1),
                           smoke=bool(args.smoke))
  if args.json:
    with open(args.json, "w") as f:
      json.dump(res, f, indent=1, sort_keys=True)
    print(f"# wrote {args.json}")
  c = res["check"]
  assert c["hit_beats_miss_p99"], (
      "cache-hit admissions must beat the miss path on p99: "
      f"on={c['admission_p99_on']}ms off={c['admission_p99_off']}ms")
  assert c["qps_no_worse"], (
      f"cache on must not cost QPS: on={c['goodput_on']}/s "
      f"off={c['goodput_off']}/s")
  assert c["zero_loss_delta"], (
      "cache hits must be accuracy-neutral (shared arena == fresh "
      f"build): loss on={c['loss_on']}% off={c['loss_off']}%")
  assert c["full_hit_rate"], (
      f"the 100%-repeat arm should fully hit: {c['hit_rate_on']}")


if __name__ == "__main__":
  main()
