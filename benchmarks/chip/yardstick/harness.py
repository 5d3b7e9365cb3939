"""One run of one cell: set-up, the measured window, metrics, the check.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own that this module finds by the name
``BENCHMARK.json`` gives:

  configs/<config>.json     the configuration as run (see ``model_config``),
                            with the weight rules of leaves beyond the
                            base (``weight_rules``, see ``weights.py``)
                            and the model fields it is held to
                            (``fields``, see ``arch``)
  traffic/<traffic>.json    the mix (see ``traffic.py``)
  cells/<workload>.json     the cell's correctness limit and sample size
  metrics/<metric>.py       a per-layer metric's reader: ``read(rec)``
  references/<ref>.py       a configuration's plain reference, which
                            lists the fields beyond the base it computes
                            (``FIELDS``, see ``reference``)
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from yardstick import peaks, stats, trace, traffic, wallclock
from yardstick import weights as wts

CHIP = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
TRACE_DIR = CHIP / "out" / "trace"
# Engine methods a window wraps: the serve-step programs (faults, in the
# tests), and those the traced run annotates.
WRAPPED = ("_step_fn", "_decode_step", "_admit", "_admit_overlapped",
           "_dispatch_admission", "_retire")


def log(msg: str) -> None:
  print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _load_module(path: pathlib.Path):
  spec = importlib.util.spec_from_file_location(path.stem, path)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def benchmark() -> Dict:
  return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
  name: str
  chips: int
  conf: Dict                 # configs/<config>.json
  mix: Dict                  # traffic/<traffic>.json
  check: Dict                # cells/<workload>.json
  end_to_end: List[str]      # metric names this cell reports, trace off
  per_layer: List[str]       # ... and with the trace on


def load_cell(name: str, bench: Optional[Dict] = None) -> Cell:
  bench = bench or benchmark()
  cells = {w["name"]: w for w in bench["workloads"]}
  if name not in cells:
    raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
  w = cells[name]
  conf = next(c for c in bench["configs"] if c["name"] == w["config"])

  def reports(m):
    return name in m.get("workloads", [name])

  return Cell(
      name=name, chips=int(w["chips"]),
      conf=json.loads((CHECKOUT / conf["file"]).read_text()),
      mix=traffic.load_mix(w["traffic"]),
      check=json.loads((CHIP / "cells" / f"{name}.json").read_text()),
      end_to_end=[m["name"] for m in bench["end_to_end"] if reports(m)],
      per_layer=[m["name"] for m in bench["per_layer"] if reports(m)])


def model_config(conf: Dict):
  """The registry's configuration, cut as the file says, and checked
  against the file's own numbers: each key of ``fields`` against the
  attribute of the configuration run that it names."""
  from repro.configs.registry import get_config  # noqa: PLC0415

  cfg = dataclasses.replace(get_config(conf["registry"]), **conf["replace"])
  ours = arch(cfg, conf)
  for key, field in conf["fields"].items():
    if conf[key] != ours[field]:
      raise ValueError(f"{conf['name']}: {key}={conf[key]} in the file, "
                       f"{ours[field]} in the configuration run")
  return cfg


def _base_arch(cfg) -> Dict:
  """The 13 numbers every reference and cost function reads."""
  return {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
          "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
          "d_ff": cfg.d_ff, "vocab": cfg.vocab, "n_layers": cfg.n_layers,
          "rope_theta": float(cfg.rope_theta),
          "norm_eps": float(cfg.norm_eps),
          "parallel_block": bool(cfg.parallel_block),
          "tie_embeddings": bool(cfg.tie_embeddings),
          "cluster_size": cfg.synopsis.cluster_size,
          "recent": cfg.synopsis.recent}


def arch(cfg, conf: Dict) -> Dict:
  """The numbers the reference and the cost functions read: the base
  (``_base_arch``), and each other attribute of ``cfg`` that the file's ``fields``
  name, by its name in the program (a configuration's reference reads
  what its architecture adds there)."""
  out = _base_arch(cfg)
  for field in conf["fields"].values():
    if field in out:
      continue
    if not hasattr(cfg, field):
      raise ValueError(f"{conf['name']}: the file's fields name "
                       f"{field!r}, which the configuration run lacks")
    out[field] = getattr(cfg, field)
  return out


def reference(conf: Dict, cfg):
  """The configuration's plain reference module.  It lists in ``FIELDS``
  the fields beyond the base that it computes; a field the file names
  beyond the base and the reference does not list is an error, since it
  would be handed on and not acted on."""
  mod = _load_module(CHIP / "references" / f"{conf['reference']}.py")
  extra = set(arch(cfg, conf)) - set(_base_arch(cfg))
  missing = sorted(extra - set(getattr(mod, "FIELDS", ())))
  if missing:
    raise ValueError(f"{conf['name']}: reference {conf['reference']!r} "
                     f"does not compute the fields {missing}")
  return mod


class NoChip(RuntimeError):
  pass


def device_check(chips: int):
  import jax  # noqa: PLC0415

  devs = jax.devices()
  if devs[0].platform != "tpu":
    raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
  if len(devs) < chips:
    raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
  return devs


@dataclasses.dataclass
class Setup:
  cell: Cell
  cfg: object
  arch: Dict
  engine: object
  weights: wts.Weights                 # draws them again for the reference
  corpora: List[np.ndarray]
  seed: int
  setup_s: float


def setup(cell: Cell, seed: int, t_start: float, impl: str = "pallas",
          on_chip: bool = True) -> Setup:
  """Weights from the seed, the engine with every program of the cell's
  traffic warm, and the mix's corpora admitted into the corpus cache."""
  import jax  # noqa: PLC0415
  from repro.launch.compile_cache import enable_compile_cache  # noqa
  from repro.models import transformer as tf  # noqa: PLC0415
  from repro.serve.corpus_cache import CacheConfig  # noqa: PLC0415
  from repro.serve.engine import (EngineConfig, EngineRequest,  # noqa
                                  ServingEngine)

  if on_chip:
    device_check(cell.chips)
    enable_compile_cache()
    # Every program goes to the persistent cache, however fast it compiled.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
  cfg = model_config(cell.conf)
  reference(cell.conf, cfg)
  mix = cell.mix
  shapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                          jax.random.PRNGKey(0))
  draws = wts.Weights(shapes, seed, cfg.dtype,
                      rules=cell.conf.get("weight_rules"))
  params = jax.block_until_ready(draws.full())
  log(f"weights drawn at {time.perf_counter() - t_start:.1f} s")
  ecfg = EngineConfig(
      n_slots=mix["slots"], prompt_len=mix["prompt"]["tokens"],
      max_new_tokens=mix["output_tokens"]["max"] - 1,
      deadline_ms=float(mix["deadline_ms"]), policy=mix["policy"],
      impl=impl, cache=CacheConfig(capacity=mix["corpus_cache"]))
  engine = ServingEngine(cfg, ecfg, params=params)
  log(f"engine built and warm at {time.perf_counter() - t_start:.1f} s")
  if on_chip and engine.impl != "pallas":
    raise NoChip(f"kernels resolve to {engine.impl!r}, not 'pallas'")
  pool = traffic.corpora(mix, cfg.vocab, seed)
  if pool:
    # Each corpus is admitted (a miss), then hit once while the other
    # decodes, so the hit path's programs are warm before the window.
    warm = [EngineRequest(rid=-10 - i, arrival_ms=float(i),
                          prompt=pool[i % len(pool)], max_new_tokens=2)
            for i in range(2 * len(pool))]
    engine.run(warm)
    engine.reset()
  jax.block_until_ready(engine.cache)
  log(f"set-up done at {time.perf_counter() - t_start:.1f} s")
  return Setup(cell=cell, cfg=cfg, arch=arch(cfg, cell.conf),
               engine=engine, weights=draws, corpora=pool, seed=seed,
               setup_s=time.perf_counter() - t_start)


@dataclasses.dataclass
class RunRecord:
  """What one window left behind, for the metrics and the check."""
  arch: Dict
  M: int
  n_slots: int
  seconds: float
  requests: List[traffic.Request]
  served: Dict[int, object]          # rid -> EngineRequest
  clock: wallclock.WallClock
  steps: List                        # engine.step_log: (budget, ms, active)
  prefills: int
  peak: Dict[str, float]
  trace: Optional[Dict] = None       # trace.reduce(...)


def _annotate(engine, names):
  """Open a profiler annotation ``bench.<name>`` around engine methods."""
  import jax  # noqa: PLC0415

  for name in names:
    inner = getattr(engine, name)

    def wrapped(*a, _inner=inner, _label=f"bench.{name.strip('_')}", **k):
      with jax.profiler.TraceAnnotation(_label):
        return _inner(*a, **k)

    setattr(engine, name, wrapped)


def run_window(s: Setup, seconds: float, traced: bool,
               fault: Optional[Callable] = None) -> RunRecord:
  """Offer the mix's requests for ``seconds`` and wait for every one.
  ``fault`` (tests only) wraps the engine's serve-step programs."""
  import jax  # noqa: PLC0415
  from repro.serve.engine import EngineRequest  # noqa: PLC0415

  engine = s.engine
  reqs = traffic.generate(s.cell.mix, s.cfg.vocab, s.seed, seconds)
  served = {r.rid: EngineRequest(rid=r.rid, arrival_ms=r.arrival_ms,
                                 prompt=r.prompt,
                                 max_new_tokens=r.out_tokens - 1)
            for r in reqs}
  engine.reset()
  if fault is not None:
    inner = engine._step_fn
    engine._step_fn = lambda budget: fault(inner(budget))
  clock = wallclock.WallClock(engine, served)
  if traced:
    _annotate(engine, WRAPPED[1:])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
  t_run = time.perf_counter()
  clock.start()
  try:
    if traced:
      with jax.profiler.TraceAnnotation(trace.WINDOW):
        engine.run(list(served.values()))
    else:
      engine.run(list(served.values()))
  finally:
    if traced:
      jax.profiler.stop_trace()
    # What this window wrapped on the engine goes with it.
    for name in WRAPPED:
      engine.__dict__.pop(name, None)
  clock.close()
  log(f"window of {seconds} s ran in {time.perf_counter() - t_run:.1f} s "
      f"wall, W {clock.end_w / 1e3:.1f} s; {len(engine.step_log)} steps")
  dev = jax.devices()[0]
  rec = RunRecord(
      arch=s.arch, M=engine.M, n_slots=engine.ecfg.n_slots,
      seconds=seconds, requests=reqs, served=served, clock=clock,
      steps=list(engine.step_log), prefills=engine.prefills,
      peak=peaks.peaks(dev.device_kind) if dev.platform == "tpu" else {})
  if traced:
    t0 = time.perf_counter()
    rec.trace = trace.reduce(trace.load(str(TRACE_DIR)))
    log(f"trace read in {time.perf_counter() - t0:.1f} s")
  return rec


# -- end-to-end metrics (the harness's own clock) ---------------------------

def latencies(rec: RunRecord, stamps: Dict[int, float]) -> List[float]:
  """Per request sent: stamp - arrival; a request with no stamp counts
  above every one that has one."""
  worst = max([rec.clock.end_w] + list(stamps.values()))
  return [stamps.get(r.rid, 2.0 * worst) - r.arrival_ms
          for r in rec.requests]


def end_to_end(rec: RunRecord, setup_s: float) -> Dict[str, Dict]:
  T = rec.seconds * 1e3
  done = sum(1 for r in rec.requests if rec.clock.admit_w.get(r.rid, T + 1)
             <= T)
  done += sum(active for (_, _, active), w in zip(rec.steps,
                                                  rec.clock.step_w)
              if w <= T)
  return {
      "latency_p95_ms": {"value": stats.percentile(
          latencies(rec, rec.clock.retire_w), 95), "unit": "ms"},
      "ttft_p50_ms": {"value": stats.percentile(
          latencies(rec, rec.clock.admit_w), 50), "unit": "ms"},
      "ttft_p95_ms": {"value": stats.percentile(
          latencies(rec, rec.clock.admit_w), 95), "unit": "ms"},
      "tokens_per_s": {"value": done / rec.seconds, "unit": "tokens/s"},
      "setup_s": {"value": setup_s, "unit": "s"},
  }


def per_layer(rec: RunRecord, names: List[str]) -> Dict[str, Dict]:
  bench = {m["name"]: m for m in benchmark()["per_layer"]}
  out = {}
  for name in names:
    val = _load_module(CHIP / "metrics" / f"{name}.py").read(rec)
    if val is not None:
      out[name] = {"value": float(val), "unit": bench[name]["unit"]}
  return out


def breakdown(rec: RunRecord) -> Optional[Dict]:
  if rec.trace is None:
    return None
  return {"device_ops": trace.top(rec.trace["op_time"]),
          "idle_gaps": trace.top(rec.trace["idle_by_host"])}


# -- correctness -------------------------------------------------------------

def sample(rec: RunRecord, seed: int, n: int) -> List[int]:
  """rids of n finished requests drawn from the seed, with the longest."""
  done = [r for r in rec.requests if r.rid in rec.clock.retire_w]
  if not done:
    return []
  longest = max(done, key=lambda r: (r.out_tokens, -r.rid))
  rest = [r.rid for r in done if r.rid != longest.rid]
  rng = np.random.default_rng([int(seed), 9])
  pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
  return [longest.rid] + [rest[i] for i in sorted(pick)]


def compared(served, M: int) -> np.ndarray:
  """Which of a served request's tokens the check compares: the first,
  which admission produced, and every one a decode step produced at
  budget M.  At budget M stage 2 refines every cluster and takes back
  each centroid's stage-1 term, so the step's attention is exact over the
  cache and only rounding separates it from the reference; below M the
  synopsis approximation, the system's own semantics, moves the logits
  about as far as the control's rounding does."""
  return np.asarray([True] + [b >= M for b in served.budgets], bool)


def gaps(s: Setup, rec: RunRecord, rids: List[int],
         control: bool = False) -> Dict[str, Dict]:
  """How far below the reference's best logit the compared tokens of the
  sampled requests lie: the widest gap, the share that is not the
  reference's first choice, how many tokens were compared and how many
  distinct contexts (prompt and tokens before) they came from.  With
  ``control``, the same of the tokens that the fp8 reference puts first
  at those positions.  Requests that served the same prompt the same
  tokens are scored once, over the union of their compared positions."""
  import jax.numpy as jnp  # noqa: PLC0415

  ref_mod = reference(s.cell.conf, s.cfg)
  runs = {"served": ref_mod.Reference(s.arch, s.weights)}
  if control:
    runs["control"] = ref_mod.Reference(s.arch, s.weights, fp8=True)
  by_prompt: Dict[bytes, Dict[tuple, np.ndarray]] = {}
  prompts: Dict[bytes, np.ndarray] = {}
  for rid in rids:
    req = rec.served[rid]
    key = req.prompt.tobytes()
    prompts[key] = req.prompt
    seqs = by_prompt.setdefault(key, {})
    toks = tuple(req.tokens)
    mask = compared(req, rec.M)
    seqs[toks] = seqs[toks] | mask if toks in seqs else mask
  out = {name: [] for name in runs}
  contexts = set()
  for key, seqs in by_prompt.items():
    prompt = prompts[key]
    kvs = {name: r.prefill(prompt) for name, r in runs.items()}
    for toks, mask in seqs.items():
      contexts.update((key, toks[:i]) for i in np.flatnonzero(mask))
      rows = {}
      for name, r in runs.items():
        kv, first = kvs[name]
        rest = r.extend(kv, toks[:-1], len(prompt)) if len(toks) > 1 \
            else jnp.zeros((0, first.shape[0]), first.dtype)
        rows[name] = jnp.concatenate([first[None], rest])[mask]
      ref = rows["served"]
      best = jnp.max(ref, -1)
      picks = {"served": jnp.asarray(np.asarray(toks)[mask])}
      if control:
        picks["control"] = jnp.argmax(rows["control"], -1)
      for name, pick in picks.items():
        out[name].append(np.asarray(
            best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]))
    del kvs

  def summary(g):
    g = np.concatenate(g) if g else np.zeros(0)
    return {"widest": float(g.max()) if g.size else 0.0,
            "not_first": float((g > 0).mean()) if g.size else 0.0,
            "tokens": int(g.size), "contexts": len(contexts)}

  return {name: summary(g) for name, g in out.items()}


def free_engine(s: Setup) -> None:
  """Drop the engine, its weights, slot pool and corpus arenas, so that
  the reference (which draws its own weights) has the chip's memory."""
  s.engine = None
  gc.collect()


def check(s: Setup, rec: RunRecord, control: bool = False) -> Dict:
  """The comparison that decides ``correct``: every request of the
  window finished with all its tokens, and no compared token of the
  sampled requests lies further below the plain reference's best logit
  than the cell's limit.  With ``control`` the fp8 reference, put in the
  program's place, is judged by the same numbers (``control``); it has to
  come out not correct."""
  short = [r.rid for r in rec.requests
           if len(rec.served[r.rid].tokens) != r.out_tokens]
  rids = sample(rec, s.seed, s.cell.check["sample_requests"])
  t0 = time.perf_counter()
  g = gaps(s, rec, rids, control=control)
  log(f"reference over {len(rids)} requests in "
      f"{time.perf_counter() - t0:.1f} s")
  limit = float(s.cell.check["served_logit_gap_max_limit"])

  def verdict(widest, n_short):
    numbers = {"served_logit_gap_max": [widest, limit],
               "requests_short": [float(n_short), 0.0]}
    return {"correct": widest <= limit and not n_short and bool(rids),
            "numbers": numbers}

  out = verdict(g["served"]["widest"], len(short))
  out.update(sampled_requests=len(rids), gaps=g)
  if control:
    out["control"] = verdict(g["control"]["widest"], 0)
  return out


def report_numbers(numbers: Dict) -> None:
  for name, (value, limit) in numbers.items():
    print(f"{name} {value!r} limit {limit!r}", file=sys.stderr)
