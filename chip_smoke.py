#!/usr/bin/env python3
"""Bring-up smoke of the serving path on a TPU.

One chip (the default): llama3-8b at its published widths, cut to 8 of
its 32 layers, with random bf16 weights drawn from ``--seed``, served by
``ServingEngine`` — prefill, synopsis build, slot write and the budgeted
two-stage decode, all through the Pallas kernels.  Two slots, 32768-token
prompts (M=256 clusters of C=128), the ``accuracytrader`` policy, 16 new
tokens per request.  It then compares, against the XLA reference path:
the engine's serve-step logits on the served cache at budgets M and 32
(both sides at full f32 matmul precision, see PARITY_PRECISION); the
decode attention of every layer on the same step, both paths given the
same inputs; and on one fresh prompt, prefill attention on the first
layer's queries and keys and the synopsis build of the prefilled cache.

  python chip_smoke.py

Four chips (``--chips 4``): only the multi-chip tiers, each against the
stacked execution of the same math on one chip, on the served cache —
the component tier with N=4 on a ``("component",)`` mesh, and the fleet
tier with R=2 x N=2 on a ``("replica", "component")`` mesh, at 8192-token
prompts (M=64).

  python chip_smoke.py --chips 4

Timings are printed for bring-up only: they are not a benchmark.  The
last line of standard output is one JSON object naming the device; the
script exits non-zero and prints no such line when no TPU is found, when
the kernels would resolve to anything but ``pallas``, or when any phase
fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import statistics
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LAYERS = 8               # depth cut: 8 of llama3-8b's 32 layers
SLOTS = 2
PROMPT = 32768           # M = 256 clusters of C = 128
NEW_TOKENS = 16
REQUESTS = 4
ARRIVAL_GAP_MS = 500.0   # staggered: later requests admit beside a decode
DEADLINE_MS = 30000.0
BUDGET = 32              # the partial-refinement budget compared
TIER_TOKENS = 4
# The tiers' weights and slot pool are not placed on the mesh: they start
# on the first chip, and each mesh step copies them to all four.  A
# quarter of the one-chip prompt (M = 64) leaves room for both copies.
TIER_PROMPT = PROMPT // 4
# The random-weight model attends sharply (keys reach ~117 in magnitude)
# and amplifies a rounding difference layer by layer.  On a v5e, at the
# default matmul precision (f32 operands enter the MXU as bf16, and the
# two paths round at different places) the serve-step logits after 8
# layers agree on no row's top-1 token; at full f32 precision the serve
# step agrees, but the 32768-token prefill's last-token logits still do
# not, because its two paths sum over the keys in different orders.  That
# is the conditioning of the model, not an error of either path.  So the
# kernels are checked layer by layer with both paths given the same
# inputs (the XLA path's activations), where nothing is amplified; the
# serve-step logits are compared at full precision (PARITY_PRECISION).
PARITY_PRECISION = "highest"
# Logits: casting an attention output to bf16 (8 significant bits) can
# round one way on one side and the other way on the other; such one-ulp
# flips (2^-8 relative) carry through the 8 residual layers into the
# logits.  We allow 2^-5 of the largest reference logit: four ulps at that
# magnitude.  A wrong kernel moves logits by O(1) of their scale.
LOGIT_RTOL = 2.0 ** -5
# Decode attention per layer, at the served (default) precision: the
# paths may round different softmax weights to bf16 (2^-9 relative each),
# which moves an output by about 2^-9 of the values it averages.  We allow
# 2^-7 of the layer's largest reference output.  A wrong cluster, mask or
# scale moves outputs by O(1) of their scale.
ATTN_RTOL = 2.0 ** -7
# Prefill attention returns bf16: one ulp at the largest output is up to
# 2^-7 of it, and the paths' different f32 sums can land an output one
# ulp apart.  We allow two such ulps.
PREFILL_RTOL = 2.0 ** -6
# Centroids: both sides average the same bf16 rows in f32, in a different
# order, and round the mean to bf16, which can land one ulp apart; one ulp
# is at most 2^-7 of the largest centroid value.  Sorted rows are copies:
# they must match exactly.
CENTROID_RTOL = 2.0 ** -7


class CompileCounter:
  """Counts the programs JAX builds (jax.monitoring): each is either
  compiled or read back from the persistent compile cache."""

  # JAX records this duration for every program it builds, cache hit or not.
  BUILD_EVENT = "/jax/core/compile/backend_compile_duration"

  def __init__(self, jax):
    self.n = 0
    self.secs = 0.0
    self.hits = 0
    jax.monitoring.register_event_duration_secs_listener(self._duration)
    jax.monitoring.register_event_listener(self._event)

  def _duration(self, event, secs, **_):
    if event == self.BUILD_EVENT:
      self.n += 1
      self.secs += secs

  def _event(self, event, **_):
    if event == "/jax/compilation_cache/cache_hits":
      self.hits += 1

  def line(self, what: str) -> str:
    return (f"[timing] {what}: {self.n} programs in {self.secs:.1f} s — "
            f"{self.n - self.hits} compiled, {self.hits} read from the "
            f"persistent cache (not a benchmark)")


def fail(msg: str) -> None:
  print(f"[fail] {msg}", file=sys.stderr, flush=True)
  sys.exit(1)


def check_tokens(reqs, n_new: int, vocab: int) -> None:
  for r in reqs:
    new = r.tokens[1:]                 # tokens[0] comes from the prefill
    if r.dropped or len(new) != n_new or \
        not all(0 <= t < vocab for t in r.tokens):
      fail(f"request {r.rid}: dropped={r.dropped} tokens={r.tokens}")


def _deviation(got, want):
  """max|got - want|, max|want|, whether both are finite, and the share
  of rows whose argmax agrees — reduced on the device."""
  import jax.numpy as jnp  # noqa: PLC0415
  g, w = got.astype(jnp.float32), want.astype(jnp.float32)
  return (jnp.max(jnp.abs(g - w)), jnp.max(jnp.abs(w)),
          jnp.isfinite(g).all() & jnp.isfinite(w).all(),
          jnp.mean(jnp.argmax(g, -1) == jnp.argmax(w, -1)))


def compare(name: str, got, want, rtol: Optional[float]) -> float:
  """Max |got - want| against the reference ``want``; fails on a
  non-finite value or a deviation past ``rtol`` x max|want|.  With
  ``rtol=None`` the deviation is reported only."""
  import jax  # noqa: PLC0415
  got, want = jax.device_put((got, want), jax.devices()[0])
  dev, scale, finite, top1 = (
      x.item() for x in jax.jit(_deviation)(got, want))
  if not finite:
    fail(f"{name}: non-finite values")
  if rtol is None:
    print(f"[info] {name}: max|d|={dev!r} max|ref|={scale!r} "
          f"argmax_agree={top1!r} (reported, not checked)", flush=True)
    return dev
  tol = rtol * scale
  print(f"[parity] {name}: max|d|={dev!r} tol={tol!r} "
        f"({rtol!r} x max|ref| {scale!r}) argmax_agree={top1!r}",
        flush=True)
  if not dev <= tol:
    fail(f"{name}: max|d| {dev} > {tol}")
  return dev


def _both_attention(q, csl, *, kernels, i_max, cluster_size, sm_scale,
                    cap=None, self_kv=None, **_):
  """A serve step's attention that carries the XLA reference's output and
  reports how far the ``kernels`` impl's output on the same inputs is
  from it (a step ``attention_fn``: the report leaves the step per
  layer)."""
  import jax.numpy as jnp  # noqa: PLC0415
  from repro.serve.serve_step import synopsis_decode_attention  # noqa

  got, want = (synopsis_decode_attention(
      q, csl, i_max=i_max, cluster_size=cluster_size, sm_scale=sm_scale,
      cap=cap, self_kv=self_kv, impl=i) for i in (kernels, "xla"))
  return want, {"parity_dev": jnp.max(jnp.abs(got - want)),
                "parity_ref": jnp.max(jnp.abs(want))}


def check_layers(name: str, dev, ref, rtol: float) -> None:
  """Per-layer max|got - want| against ``rtol`` x that layer's max|want|."""
  import numpy as np  # noqa: PLC0415
  dev, ref = np.asarray(dev).ravel(), np.asarray(ref).ravel()
  if not (np.isfinite(dev).all() and np.isfinite(ref).all()):
    fail(f"{name}: non-finite values")
  ratio = dev / ref
  print(f"[parity] {name}: per layer max|d|/max|ref| "
        f"{[float(r) for r in ratio]} tol={rtol!r}", flush=True)
  if not (ratio <= rtol).all():
    fail(f"{name}: layer {int(np.argmax(ratio))} off by "
         f"{float(ratio.max())} of its scale > {rtol}")


def admission_parity(jax, eng, cfg, seed: int, prompt: int) -> None:
  """Admission on one fresh prompt, against the XLA reference: prefill
  attention on the first layer's real queries and keys, and the
  synopsis-build kernel on the engine's prefilled cache under one seeded
  permutation."""
  import jax.numpy as jnp  # noqa: PLC0415
  from repro.kernels import ops  # noqa: PLC0415
  from repro.models import attention as attn_lib  # noqa: PLC0415
  from repro.models import transformer as tf  # noqa: PLC0415
  from repro.models.layers import rms_norm  # noqa: PLC0415

  tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, prompt),
                              0, cfg.vocab, jnp.int32)

  @jax.jit
  def first_layer_qkv(params, tokens):
    lp = jax.tree.map(lambda a: a[0], params["blocks"]["pos0"])
    h = rms_norm(tf.embed_tokens(params, cfg, tokens), lp["ln1"],
                 cfg.norm_eps)
    return attn_lib.qkv(h, lp["attn"], cfg, jnp.arange(prompt))

  qkv = first_layer_qkv(eng.params, tokens)
  got, want = (ops.prefill_attention(*qkv, sm_scale=cfg.hd ** -0.5,
                                     cap=cfg.attn_softcap, impl=i)
                for i in (eng.impl, "xla"))
  del qkv
  compare(f"prefill attention {eng.impl} vs xla, layer 0", got, want,
          PREFILL_RTOL)
  del got, want

  cache = eng._prefill(eng.params, tokens)[1]
  nb, na, B, Hkv, S, D = cache["k"].shape
  N = nb * na * B
  k = cache["k"].reshape(N, Hkv, S, D)
  v = cache["v"].reshape(N, Hkv, S, D)
  del cache
  perm = jax.vmap(lambda key: jax.random.permutation(key, S))(
      jax.random.split(jax.random.PRNGKey(seed), N)).astype(jnp.int32)
  C = cfg.synopsis.cluster_size
  got = ops.synopsis_build(k, v, perm, cluster_size=C, impl=eng.impl)
  want = ops.synopsis_build(k, v, perm, cluster_size=C, impl="xla")
  names = ("sorted k", "sorted v", "k centroids", "v centroids", "counts")
  rtols = (0.0, 0.0, CENTROID_RTOL, CENTROID_RTOL, 0.0)
  for name, g, w, rtol in zip(names, got, want, rtols):
    compare(f"synopsis build {eng.impl} vs xla, {name}", g, w, rtol)


def serve_one_chip(jax, cfg, seed: int, counter: CompileCounter,
                   prompt: int = PROMPT, impl: str = "pallas") -> None:
  """The engine phase and the pallas-vs-xla parity phase."""
  from repro.models import transformer as tf  # noqa: PLC0415
  from repro.serve.engine import (EngineConfig, ServingEngine,  # noqa
                                  make_requests)
  from repro.serve.serve_step import make_serve_step  # noqa: PLC0415

  t0 = time.perf_counter()
  params = jax.block_until_ready(
      tf.init_params(jax.random.PRNGKey(seed), cfg))
  t_weights = time.perf_counter() - t0
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=SLOTS, prompt_len=prompt, max_new_tokens=NEW_TOKENS,
      deadline_ms=DEADLINE_MS, policy="accuracytrader", impl=impl,
      seed=seed), params=params)
  t_setup = time.perf_counter() - t0
  if eng.impl != impl:
    fail(f"engine kernels resolved to {eng.impl!r}, not {impl!r}")
  print(f"[engine] impl={eng.impl} slots={SLOTS} prompt={prompt} "
        f"M={eng.M} new_tokens={NEW_TOKENS} policy=accuracytrader "
        f"buckets={eng.buckets}", flush=True)
  print(f"[timing] set-up {t_setup:.1f} s: weights {t_weights:.1f} s, "
        f"engine build + warm-up {t_setup - t_weights:.1f} s "
        f"(not a benchmark)", flush=True)
  print(counter.line("set-up"), flush=True)

  n0 = counter.n
  reqs = make_requests([i * ARRIVAL_GAP_MS for i in range(REQUESTS)],
                       prompt, NEW_TOKENS, cfg.vocab, seed=seed)
  t0 = time.perf_counter()
  summary = eng.run(reqs)
  t_run = time.perf_counter() - t0
  if len(eng.completed) != REQUESTS:
    fail(f"{len(eng.completed)} of {REQUESTS} requests completed")
  check_tokens(eng.completed, NEW_TOKENS, cfg.vocab)
  steps = [ms for _, ms, _ in eng.step_log]
  budgets = sorted({b for b, _, _ in eng.step_log})
  print(f"[engine] served {REQUESTS} requests, {NEW_TOKENS} decoded "
        f"tokens each, in {t_run:.1f} s; {len(steps)} decode steps, "
        f"budgets used {budgets}, compiles inside the window "
        f"{counter.n - n0}", flush=True)
  for r in sorted(eng.completed, key=lambda r: r.rid):
    print(f"[engine] request {r.rid}: tokens {r.tokens[1:]}", flush=True)
  print(f"[timing] admission p50 {summary['admission_p50']:.1f} ms "
        f"(serial admissions), decode step median "
        f"{statistics.median(steps):.2f} ms (not a benchmark)", flush=True)

  # Parity on the served cache: both lanes hold a finished request's
  # synopsis arena plus its 16-token recent ring.  The jitted steps trace
  # anew under each matmul precision.
  args = (eng.params, eng.cache, eng.tok)
  for budget in (eng.M, BUDGET):
    step = eng._step_fn(budget)
    ref = jax.jit(make_serve_step(cfg, mode="synopsis", i_max=budget,
                                  impl="xla"))
    compare(f"serve step {impl} vs xla, budget {budget}, default matmul "
            f"precision", step(*args)[0], ref(*args)[0], None)
    with jax.default_matmul_precision(PARITY_PRECISION):
      got, want = step(*args)[0], ref(*args)[0]
    compare(f"serve step {impl} vs xla, budget {budget}", got, want,
            LOGIT_RTOL)
    forced = jax.jit(make_serve_step(
        cfg, mode="synopsis", i_max=budget, impl="xla",
        attention_fn=functools.partial(_both_attention, kernels=impl)))
    state = forced(*args)[1]
    check_layers(f"decode attention {impl} vs xla, budget {budget}",
                 state["parity_dev"], state["parity_ref"], ATTN_RTOL)
  del args
  eng.cache = None      # the slot pool is done with: make room for admission
  admission_parity(jax, eng, cfg, seed, prompt)


def tiers_four_chips(jax, cfg, seed: int, prompt: int = TIER_PROMPT,
                     impl: str = "pallas") -> None:
  """Component tier N=4 and fleet tier R=2 x N=2 on real meshes, each
  against its stacked execution on one chip."""
  from repro.models import transformer as tf  # noqa: PLC0415
  from repro.serve.cluster import ClusterConfig, ClusterStepBackend  # noqa
  from repro.serve.engine import (EngineConfig, ServingEngine,  # noqa
                                  make_requests)
  from repro.serve.fleet import FleetConfig, FleetStepBackend  # noqa

  params = jax.block_until_ready(
      tf.init_params(jax.random.PRNGKey(seed), cfg))
  one = jax.devices()[0]
  tiers = [
      ("component tier N=4", ClusterStepBackend,
       dict(n_components=4), ClusterConfig),
      ("fleet tier R=2 x N=2", FleetStepBackend,
       dict(n_components=2, replicas=2), FleetConfig),
  ]
  for name, backend_cls, shape, cfg_cls in tiers:
    t0 = time.perf_counter()
    backend = backend_cls(cfg_cls(**shape, seed=seed, use_mesh=True))
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=SLOTS, prompt_len=prompt, max_new_tokens=TIER_TOKENS,
        deadline_ms=DEADLINE_MS, policy="fixed", fixed_budget=BUDGET,
        impl=impl, seed=seed), params=params, backend=backend)
    mesh = backend.mesh
    print(f"[{name}] mesh {dict(mesh.shape)} over "
          f"{[d.id for d in mesh.devices.flat]}; set-up "
          f"{time.perf_counter() - t0:.1f} s (not a benchmark)", flush=True)
    eng.run(make_requests([0.0] * SLOTS, prompt, TIER_TOKENS, cfg.vocab,
                          seed=seed))
    check_tokens(eng.completed, TIER_TOKENS, cfg.vocab)
    print(f"[{name}] served {SLOTS} requests x {TIER_TOKENS} tokens: "
          f"{[r.tokens[1:] for r in eng.completed]}", flush=True)

    fe = backend.full_mode()
    if backend_cls is FleetStepBackend:
      # Read shard 0 from its replica row: the copies must be real.
      fe = fe.at[1, 0].set(1)
    stacked = backend_cls(cfg_cls(**shape, seed=seed, use_mesh=False))
    stacked.bind(eng)
    args = jax.device_put((eng.params, eng.cache, eng.tok, fe), one)
    with jax.default_matmul_precision(PARITY_PRECISION):
      got, _ = eng._step_fn(BUDGET)(eng.params, eng.cache, eng.tok, fe)
      want, _ = stacked.step_fn(BUDGET)(*args)
    compare(f"{name} mesh vs stacked on one chip, budget {BUDGET}",
            got, want, LOGIT_RTOL)
    del eng, backend, stacked, args, got, want   # free this tier's pool


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  ap.add_argument("--seed", type=int, default=0,
                  help="seed of the random weights and the prompts")
  ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                  help="4: run only the component and fleet tiers "
                       "across four chips")
  args = ap.parse_args()

  import jax  # noqa: PLC0415
  devs = jax.devices()
  if devs[0].platform != "tpu":
    fail(f"no TPU: JAX found {devs[0].platform} devices only")
  if len(devs) < args.chips:
    fail(f"--chips {args.chips} needs {args.chips} chips, found "
         f"{len(devs)}")
  from repro.kernels.ops import resolve_impl  # noqa: PLC0415
  from repro.launch.compile_cache import enable_compile_cache  # noqa
  if resolve_impl("auto") != "pallas":
    fail(f"kernels resolve to {resolve_impl('auto')!r} on this device")
  cache_dir = enable_compile_cache()
  counter = CompileCounter(jax)

  from repro.configs.registry import get_config  # noqa: PLC0415
  cfg = dataclasses.replace(get_config("llama3-8b"), n_layers=LAYERS)
  print(f"[config] llama3-8b at published widths: d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} kv head_dim={cfg.hd} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} C={cfg.synopsis.cluster_size}; "
        f"depth cut to {cfg.n_layers} of "
        f"{get_config('llama3-8b').n_layers} layers; "
        f"{jax.numpy.dtype(cfg.dtype).name} random weights, seed "
        f"{args.seed}", flush=True)
  print(f"[device] {devs[0].device_kind} x {len(devs)}; compile cache at "
        f"{cache_dir}", flush=True)

  if args.chips == 4:
    tiers_four_chips(jax, cfg, args.seed)
  else:
    serve_one_chip(jax, cfg, args.seed, counter)
  print(counter.line("whole run"), flush=True)
  print(json.dumps({"ok": True, "device": {
      "platform": devs[0].platform, "kind": devs[0].device_kind,
      "count": len(devs)}}), flush=True)


if __name__ == "__main__":
  main()
