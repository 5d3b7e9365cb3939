"""Serving driver: prefill -> synopsis build -> deadline-budgeted decode.

The AccuracyTrader loop: each decode batch picks its refinement budget
from the calibrated latency model and the configured deadline; new tokens
accumulate in the recent buffer and are absorbed into the synopsis when
it fills (the paper's low-priority incremental update).

All three stages run through the kernel suite behind one ``--impl``
switch (prefill attention, synopsis build, decode attention — DESIGN.md
§4/§6).  With ``--batches N --pipeline`` the driver overlaps batch i's
synopsis build with batch i+1's prefill: both stages are single jitted
programs and the loop never calls ``jax.block_until_ready`` between
dispatches, so the runtime's async dispatch queue pipelines them (the
paper's low-priority offline module running behind the online path).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --prompt-len 256 --tokens 32 --deadline-ms 50

  # pipelined prefill/build over 4 prompt batches:
  PYTHONPATH=src python -m repro.launch.serve --batches 4 --pipeline

With ``--engine`` the driver instead runs the deadline-driven
continuous-batching engine (`repro.serve.engine`, DESIGN.md §8) over an
arrival trace: requests admit/retire in shared cache slots mid-flight and
every budget decision is calibrated by measured step latencies.

  # paper Tables 1-2 load sweep (measured):
  PYTHONPATH=src python -m repro.launch.serve --engine --trace cf_rates

  # diurnal Sogou-shaped hours (Fig 7a):
  PYTHONPATH=src python -m repro.launch.serve --engine \
      --trace sogou_hourly --hours 3,9,21
"""
from __future__ import annotations

import argparse
import time


def _apply_quant(cfg, quant: str):
  """Swap the synopsis quantization spec into the model config
  (DESIGN.md §15).  "none" returns cfg unchanged — the bit-identical
  control arm."""
  if not quant or quant == "none":
    return cfg
  import dataclasses
  return dataclasses.replace(
      cfg, synopsis=dataclasses.replace(cfg.synopsis, quant=quant))


def _engine_main(args):
  """Continuous-batching engine over an arrival trace (DESIGN.md §8);
  with ``--cluster N`` the decode steps run the multi-component
  scatter-gather tier (DESIGN.md §9) across N components."""
  import json

  from repro.configs.registry import get_config
  from repro.control import AdmissionConfig, parse_slo_classes
  from repro.dist.topology import MeshUnavailable
  from repro.serve.engine import EngineConfig, ServingEngine, run_open_loop
  from repro.serve.resilience import parse_fault_spec
  from repro.serving.workload import CF_RATES, hour_rate

  cfg = get_config(args.arch, smoke=args.smoke)
  cfg = _apply_quant(cfg, args.quant)
  C = cfg.synopsis.cluster_size
  prompt_len = max(C, (args.prompt_len // C) * C)
  max_new = min(args.tokens, cfg.synopsis.recent)
  faults = parse_fault_spec(args.faults)
  backend = None
  if args.fleet:
    from repro.serve.fleet import FleetConfig, FleetStepBackend
    backend = FleetStepBackend(FleetConfig(
        n_components=args.cluster, skew=args.skew, alloc=args.alloc,
        route=args.route, replicas=max(1, args.replicas),
        predictor=args.predictor or "ewma"))
  elif args.cluster:
    from repro.serve.cluster import ClusterConfig, ClusterStepBackend
    backend = ClusterStepBackend(ClusterConfig(
        n_components=args.cluster, skew=args.skew, alloc=args.alloc,
        route=args.route, replicas=args.replicas,
        predictor=args.predictor or "ewma",
        faults=faults, recovery=not args.no_recovery,
        retries=args.retries))
  admission = None
  if args.admission != "off":
    admission = AdmissionConfig(
        order=args.admission, shed=not args.no_shed,
        shed_margin=args.shed_margin,
        classes=parse_slo_classes(args.slo_classes))
  cache = None
  if args.cache_capacity > 0 and not args.no_cache:
    from repro.serve.corpus_cache import CacheConfig
    cache = CacheConfig(capacity=args.cache_capacity, delta_unit=C)
  try:
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=args.n_slots, prompt_len=prompt_len,
        max_new_tokens=max_new, deadline_ms=args.deadline_ms,
        policy=args.policy, impl=args.impl,
        predictor=args.predictor or "affine", admission=admission,
        cache=cache, contract=args.contract, epsilon=args.epsilon),
        backend=backend)
  except MeshUnavailable as e:
    raise SystemExit(f"error: {e}") from None
  print(f"[engine] impl={eng.impl!r} policy={args.policy} "
        f"slots={args.n_slots} prompt={prompt_len} tokens={max_new} "
        f"M={eng.M} buckets={eng.buckets} deadline={args.deadline_ms}ms"
        + (f" contract={args.contract} eps={args.epsilon}"
           if args.contract != "deadline" else "")
        + (f" cache={args.cache_capacity}" if cache is not None else ""))
  if backend is not None:
    import jax
    mesh = "mesh" if backend.mesh is not None else "stacked"
    tier = "fleet" if args.fleet else "cluster"
    print(f"[{tier}] N={args.cluster} ({mesh}, {len(jax.devices())} "
          f"devices) counts={backend.topo.counts} alloc={args.alloc} "
          f"route={args.route} skew={args.skew} R={args.replicas} "
          f"predictor={args.predictor or 'ewma'}")

  if args.trace == "cf_rates":
    points = [(f"rate{r}", r * args.rate_scale) for r in CF_RATES]
  else:
    hours = [int(h) for h in args.hours.split(",")]
    points = [(f"hour{h:02d}", hour_rate(h) * args.rate_scale)
              for h in hours]
  slo_of = None
  if admission is not None and admission.classes:
    names = [c.name for c in admission.classes]
    slo_of = lambda rid: names[rid % len(names)]  # noqa: E731
  results = {}
  for name, rate in points:
    s = run_open_loop(eng, rate_per_s=rate, duration_s=args.duration,
                      seed=0, slo_of=slo_of,
                      zipf_corpora=args.zipf_corpora)
    results[name] = {
        "rate_per_s": rate,
        **{k: round(float(v), 3) for k, v in s.items()
           if not isinstance(v, dict)},
        **({"classes": s["classes"]} if "classes" in s else {})}
    print(f"[{name}] rate={rate:6.1f}/s n={s['n']:4.0f} "
          f"p50={s['p50']:7.1f}ms p99={s['p99']:7.1f}ms "
          f"p999={s['p999']:7.1f}ms loss={s['accuracy_loss_pct']:5.2f}% "
          f"miss={s['deadline_miss_pct']:5.1f}% "
          f"budget={s['mean_budget']:.2f}"
        + (f" shed={s['shed_pct']:.1f}% goodput={s['goodput_per_s']:.1f}/s"
           if "shed_pct" in s else "")
        + (f" pred={s.get('pred_loss_mean', 0.0):.4f} "
           f"band_cov={s.get('band_cover_pct', 0.0):.0f}% "
           f"freed={s.get('freed_budget_mean', 0.0):.2f}"
           if args.contract != "deadline" else ""))
    print(f"[engine] {name} clock_lag={s['clock_lag_ms']:.1f}ms "
          f"compiles={s['compiles']}")
    if backend is not None and getattr(backend, "fault_stats", None) \
        and any(backend.fault_stats.values()):
      print(f"  [faults] {backend.fault_stats}")
  out = {"trace": args.trace, "policy": args.policy, "results": results}
  if backend is not None:
    exp = backend.export()
    out["cluster"] = {
        "n_components": args.cluster, "skew": args.skew,
        "alloc": args.alloc, "route": args.route,
        "counts": list(backend.topo.counts),
        "comp_ms_full": [round(float(v), 4)
                         for v in exp.step_ms_per_component(100)],
    }
    print(f"[cluster] measured per-component ms at full budget: "
          f"{out['cluster']['comp_ms_full']}")
  if args.autoscale:
    out["autoscale"] = _autoscale_main(args, backend)
  if args.json:
    with open(args.json, "w") as f:
      json.dump(out, f, indent=1, sort_keys=True)
    print(f"# wrote {args.json}")


def _autoscale_main(args, backend):
  """Elastic sizing over the 24-hour diurnal trace (DESIGN.md §14): the
  autoscaler decides each hour's (components, replicas) grid from the
  fleet's measured export, and the discrete-event simulator replays the
  window at that size (the counterfactual round-trip) — cheap enough to
  cover all 24 hours where real engine windows would not be."""
  if backend is None:
    raise SystemExit("--autoscale requires --fleet (or --cluster N)")
  from repro.control import Autoscaler, AutoscalerConfig
  from repro.serving.service import (ScaledFleetExport, ScatterGatherService,
                                     ServiceConfig)
  from repro.serving.workload import hour_rate

  exp = backend.export()
  n_max, r_max = args.cluster, max(1, args.replicas)
  asc = Autoscaler(AutoscalerConfig(
      p99_target_ms=args.p99_target, max_components=n_max,
      max_replicas=r_max, slots=args.n_slots),
      ScaledFleetExport(exp, n_max, r_max).step_model)
  print(f"[autoscale] p99 target {args.p99_target}ms, grid up to "
        f"{n_max}x{r_max}, 24 sogou hours x rate_scale={args.rate_scale}")
  size = None
  windows = []
  cost_auto = cost_static = 0
  for h in range(24):
    rate = hour_rate(h) * args.rate_scale
    size = asc.decide(rate, size)
    sim = ScatterGatherService(
        ServiceConfig(n_components=size.n_components,
                      deadline_ms=args.deadline_ms, seed=h),
        step_backend=ScaledFleetExport(exp, size.n_components,
                                       size.replicas))
    s = sim.run_open_loop(rate, args.duration)
    cost_auto += size.devices
    cost_static += n_max * r_max
    windows.append({"hour": h, "rate_per_s": round(rate, 2),
                    "n": size.n_components, "r": size.replicas,
                    "p99_ms": round(float(s["p99"]), 2)})
    print(f"[hour{h:02d}] rate={rate:6.1f}/s grid="
          f"{size.n_components}x{size.replicas} p99={s['p99']:7.1f}ms")
  print(f"[autoscale] component-hours: autoscaled={cost_auto} "
        f"static-peak={cost_static}")
  return {"p99_target_ms": args.p99_target, "windows": windows,
          "component_hours": cost_auto,
          "component_hours_static": cost_static}


def make_parser() -> argparse.ArgumentParser:
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", default="llama3-8b")
  ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                  default=True,
                  help="the arch's reduced smoke config (default); "
                       "--no-smoke selects its published config")
  ap.add_argument("--batch", type=int, default=2)
  ap.add_argument("--prompt-len", type=int, default=256)
  ap.add_argument("--tokens", type=int, default=32)
  ap.add_argument("--batches", type=int, default=1,
                  help="number of sequence batches to prefill + build")
  ap.add_argument("--pipeline", action="store_true",
                  help="overlap batch i's synopsis build with batch i+1's "
                       "prefill (block-free dispatch, one jitted program "
                       "per stage)")
  ap.add_argument("--mode", default="synopsis",
                  choices=["exact", "synopsis"])
  ap.add_argument("--impl", default=None,
                  choices=["auto", "pallas", "xla", "interpret"],
                  help="kernel implementation for prefill, synopsis build "
                       "and decode attention; default: the config's "
                       "synopsis.impl (auto = Pallas kernels on TPU, XLA "
                       "reference elsewhere)")
  ap.add_argument("--deadline-ms", type=float, default=50.0)
  ap.add_argument("--contract", default="deadline",
                  choices=["deadline", "error_bounded",
                           "deadline_with_bound"],
                  help="serving contract (DESIGN.md §13): error_bounded "
                       "answers early once the online estimator predicts "
                       "loss <= --epsilon; deadline_with_bound attaches "
                       "a calibrated loss band to every answer")
  ap.add_argument("--epsilon", type=float, default=0.02,
                  help="error_bounded loss target ε (0 = exact path)")
  ap.add_argument("--engine", action="store_true",
                  help="run the deadline-driven continuous-batching "
                       "engine over an arrival trace (DESIGN.md §8) "
                       "instead of the single-batch demo loop")
  ap.add_argument("--cluster", type=int, default=0, metavar="N",
                  help="run decode steps on the N-component scatter-"
                       "gather tier (DESIGN.md §9; implies --engine): "
                       "shard_map over a component mesh of N devices "
                       "(host devices are forced on CPU); on an "
                       "accelerator with fewer than N chips it exits "
                       "with an error")
  ap.add_argument("--fleet", action="store_true",
                  help="run the materialized-replica fleet tier "
                       "(DESIGN.md §14; implies --engine, needs "
                       "--cluster N): a (replica, component) 2-D mesh "
                       "where each of --replicas rows holds a real copy "
                       "of every shard and the gather reads each "
                       "shard's fastest-predicted holder")
  ap.add_argument("--autoscale", action="store_true",
                  help="after the trace sweep, run the elastic "
                       "autoscaler over the 24-hour sogou trace "
                       "(DESIGN.md §14): per hour, size the "
                       "(components, replicas) grid against "
                       "--p99-target using the measured export + the "
                       "simulator counterfactual, and report "
                       "component-hours vs static peak sizing")
  ap.add_argument("--p99-target", type=float, default=50.0,
                  help="autoscaler latency target (ms)")
  ap.add_argument("--skew", type=float, default=0.0,
                  help="Zipf exponent over component corpus shares "
                       "(hot components own more clusters)")
  ap.add_argument("--alloc", default="mass",
                  choices=["mass", "topk", "gain"],
                  help="frontend refinement-budget allocation across "
                       "components: proportional to synopsis relevance "
                       "mass, or pure global top-k")
  ap.add_argument("--route", default="fixed", choices=["fixed", "rotate"],
                  help="per-slot cluster->component routing (rotate "
                       "spreads skewed ranges across components)")
  ap.add_argument("--replicas", type=int, default=1, metavar="R",
                  help="shard copies on the component ring (R >= 2 "
                       "enables hedged reissue: a gather predicted to "
                       "straggle is reissued to the shard's replica and "
                       "the earlier completion counts — DESIGN.md §10)")
  ap.add_argument("--faults", default=None, metavar="SPEC",
                  help="inject component faults into the cluster tier "
                       "(DESIGN.md §11): comma-separated key=value pairs, "
                       "e.g. 'crash=1@8,stall_rate=0.02,seed=3' (crash "
                       "entries are comp@step joined by +); default: none")
  ap.add_argument("--no-recovery", action="store_true",
                  help="disable the gather-side recovery ladder (retry to "
                       "replica, stage-1 fallback): a dead component's "
                       "shard stalls and is dropped — the baseline a "
                       "resilient tier is compared against")
  ap.add_argument("--retries", type=int, default=1, metavar="K",
                  help="max gather-side retries per component per step "
                       "(exponential backoff to ring replicas; 1 = the "
                       "legacy single zero-delay hedge)")
  ap.add_argument("--admission", default="off",
                  choices=["off", "fifo", "edf", "slack"],
                  help="queue-aware predictive admission for --engine "
                       "(DESIGN.md §11): ready-queue ordering (edf = "
                       "earliest deadline first, slack = least "
                       "predicted slack) with predictive shedding; "
                       "off = the legacy FIFO queue, no shedding")
  ap.add_argument("--slo-classes", default=None, metavar="SPEC",
                  help="SLO classes for --admission, "
                       "'name:deadline_ms[@rate_per_s[/burst]]' joined "
                       "by commas, e.g. 'interactive:80@60,batch:400'; "
                       "requests round-robin across classes")
  ap.add_argument("--shed-margin", type=float, default=1.0,
                  help="shed a request at admission when its predicted "
                       "completion exceeds deadline * margin")
  ap.add_argument("--no-shed", action="store_true",
                  help="keep the admission ordering but never shed")
  ap.add_argument("--predictor", default=None,
                  help="control-plane latency predictor: affine | ewma | "
                       "quantile[:pct] (quantile makes deadlines target "
                       "a percentile of the measured per-bucket step "
                       "times; default: affine for the engine "
                       "controller, ewma for the cluster tier)")
  ap.add_argument("--trace", default="cf_rates",
                  choices=["cf_rates", "sogou_hourly"],
                  help="arrival-rate source for --engine")
  ap.add_argument("--policy", default="accuracytrader",
                  choices=["basic", "partial", "accuracytrader", "fixed"])
  ap.add_argument("--n-slots", type=int, default=2,
                  help="engine batch lanes (max resident requests)")
  ap.add_argument("--duration", type=float, default=1.0,
                  help="seconds of arrivals per engine measurement window")
  ap.add_argument("--rate-scale", type=float, default=1.0,
                  help="multiplier on the trace's req/s rates (size the "
                       "load to the host: the paper's rates target a "
                       "110-VM cluster)")
  ap.add_argument("--hours", default="3,9,21",
                  help="comma-separated hours of day for --trace "
                       "sogou_hourly (0-23; 24 aliases 0)")
  ap.add_argument("--cache-capacity", type=int, default=0, metavar="K",
                  help="corpus-cache resident-arena target (DESIGN.md "
                       "§12): admission consults a content-addressed "
                       "synopsis cache before prefill; 0 disables "
                       "(bit-identical control arm)")
  ap.add_argument("--no-cache", action="store_true",
                  help="force the cache off regardless of "
                       "--cache-capacity (the true control arm)")
  ap.add_argument("--zipf-corpora", type=int, default=0, metavar="K",
                  help="draw --engine prompts from a pool of K corpora "
                       "with Zipf popularity instead of fresh random "
                       "prompts (the workload the corpus cache serves); "
                       "0 = unique corpora")
  ap.add_argument("--quant", default="none",
                  choices=["none", "int8", "fp8", "int8+kv", "fp8+kv"],
                  help="quantize the synopsis arena (DESIGN.md §15): "
                       "int8/fp8 centroids with per-centroid scales; the "
                       "'+kv' variants also store the sorted corpus KV "
                       "quantized with per-cluster-block scales — scales "
                       "ride into the stage-1/stage-2 kernels, no f32 "
                       "copies; none = bit-identical control arm")
  ap.add_argument("--json", default=None, metavar="PATH",
                  help="write the --engine sweep results as JSON")
  return ap


def main():
  ap = make_parser()
  args = ap.parse_args()

  if args.fleet and not args.cluster:
    ap.error("--fleet needs --cluster N (the component count; "
             "--replicas R sets the replica rows)")
  if args.cluster:
    # The mesh wants one device per component — times the replica rows
    # under --fleet (the 2-D grid) — so on a CPU host force placeholder
    # devices BEFORE jax initialises (same mechanism as launch/dryrun.py).
    # The flag only sizes the CPU platform: an accelerator host serves
    # the mesh from its chips or refuses (topology.MeshUnavailable).
    # No-op if the user already set the flag.
    from repro.dist.topology import force_host_devices
    force_host_devices(args.cluster * (max(1, args.replicas)
                                       if args.fleet else 1))

  from repro.launch.compile_cache import enable_compile_cache
  enable_compile_cache()
  if args.engine or args.cluster:
    return _engine_main(args)

  import jax
  import jax.numpy as jnp

  from repro.configs.registry import get_config
  from repro.control import BudgetController, make_predictor
  from repro.kernels.ops import resolve_impl
  from repro.models import transformer as tf
  from repro.serve import synopsis_kv as skv
  from repro.serve.kv_cache import n_attn_positions
  from repro.serve.prefill import make_prefill_step
  from repro.serve.serve_step import make_serve_step

  cfg = get_config(args.arch, smoke=args.smoke)
  cfg = _apply_quant(cfg, args.quant)
  key = jax.random.PRNGKey(0)
  params = tf.init_params(key, cfg)

  impl = resolve_impl(args.impl if args.impl else cfg.synopsis.impl)
  print(f"[impl] prefill/build/decode kernels via {impl!r}")

  B, S = args.batch, args.prompt_len
  mode = args.mode if n_attn_positions(cfg) else "exact"
  prompts = [jax.random.randint(jax.random.fold_in(key, bi), (B, S), 0,
                                cfg.vocab) for bi in range(args.batches)]
  prefill_fn = jax.jit(make_prefill_step(cfg, impl=impl))
  build_fn = jax.jit(lambda c: skv.build(c, cfg, impl=impl))

  # Prefill -> synopsis-build over all batches.  Pipelined: dispatch the
  # next prefill, then enqueue the previous batch's build behind it —
  # no block_until_ready until every stage of every batch is in flight.
  t0 = time.time()
  logits_per_batch, cache_per_batch = [], []
  if args.pipeline and mode == "synopsis":
    pending = None
    for bi in range(args.batches):
      lg, cache = prefill_fn(params, prompts[bi])         # async dispatch
      if pending is not None:
        cache_per_batch.append(build_fn(pending))         # overlaps prefill
      logits_per_batch.append(lg)
      pending = cache
    cache_per_batch.append(build_fn(pending))
    jax.block_until_ready((logits_per_batch, cache_per_batch))
  else:
    for bi in range(args.batches):
      lg, cache = prefill_fn(params, prompts[bi])
      if mode == "synopsis":
        cache = build_fn(cache)
      jax.block_until_ready((lg, cache))
      logits_per_batch.append(lg)
      cache_per_batch.append(cache)
  dt = time.time() - t0
  stages = "prefill+build" if mode == "synopsis" else "prefill"
  lane = "pipelined" if (args.pipeline and mode == "synopsis") else "serial"
  print(f"[{stages}] {args.batches} batch(es) x {S} tokens in {dt:.2f}s "
        f"({lane})")
  if mode == "synopsis":
    M = S // cfg.synopsis.cluster_size
    print(f"[synopsis] M={M} clusters of C={cfg.synopsis.cluster_size}")

  # The decode demo below consumes batch 0 only — drop the other
  # batches' caches so N full KV caches don't stay resident for the
  # whole generation loop.
  logits, cache = logits_per_batch[0], cache_per_batch[0]
  del logits_per_batch, cache_per_batch
  # --predictor applies here too (the demo loop's budget controller);
  # the affine default keeps the old demo calibration constants.
  pspec = args.predictor or "affine"
  pkw = {"base": 5.0, "slope": 1.0, "alpha": 0.1} \
      if pspec.startswith("affine") else {}
  ctrl = BudgetController(make_predictor(pspec, **pkw),
                          buckets=(0, 1, 2, 4, 8, 16, 32),
                          i_max_cap=cfg.synopsis.i_max or 32)

  steps = {}
  tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
  out_tokens = [tok]
  for i in range(args.tokens):
    budget = ctrl.budget_for(args.deadline_ms) if mode == "synopsis" else 0
    if (mode, budget) not in steps:
      steps[(mode, budget)] = jax.jit(
          make_serve_step(cfg, mode=mode, i_max=budget, impl=impl))
    t0 = time.time()
    logits, st = steps[(mode, budget)](params, cache, tok)
    jax.block_until_ready(logits)
    dt = (time.time() - t0) * 1e3
    if mode == "synopsis":
      ctrl.observe(budget, dt)
      cache = skv.append_recent(cache, st["k_delta"], st["v_delta"])
      cache["pos"] = st["pos"]
      if int(cache["recent_len"][0]) >= cfg.synopsis.recent:
        cache = jax.jit(lambda c: skv.absorb_recent(c, cfg, impl=impl))(
            cache)
        print(f"[update] absorbed recent buffer -> "
              f"M={cache['k_syn'].shape[4]}")
    else:
      cache["pos"] = st["pos"]
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out_tokens.append(tok)
    print(f"[decode {i:3d}] budget={budget:3d} {dt:7.1f}ms")
  print("generated:", jnp.concatenate(out_tokens, 1)[0].tolist())


if __name__ == "__main__":
  main()
