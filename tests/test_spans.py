"""The serving engine's spans and counters (`repro.serve.spans`): every
span of the table under the profiler with its stats and inside its parent,
the engine's wall clock W against the benchmark's outside stamps
(`benchmarks/chip/yardstick/wallclock.py`), and the compile counter that
holds the warm-up to its claim."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.registry import get_config
from repro.serve import spans
from repro.serve.engine import (CacheConfig, EngineConfig, EngineRequest,
                                ServingEngine)

ROOT = pathlib.Path(__file__).resolve().parents[1]
PROMPT = 64


def _wallclock():
  path = ROOT / "benchmarks" / "chip" / "yardstick" / "wallclock.py"
  spec = importlib.util.spec_from_file_location("wallclock", path)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


@pytest.fixture(scope="module")
def cfg():
  return get_config("llama3-8b", smoke=True)


@pytest.fixture(scope="module")
def engine(cfg):
  """Two lanes, the deadline controller, a corpus cache with delta
  replay: every span of the table can open."""
  return ServingEngine(cfg, EngineConfig(
      n_slots=2, prompt_len=PROMPT, max_new_tokens=4, deadline_ms=60.0,
      policy="accuracytrader", impl="xla",
      cache=CacheConfig(capacity=8, delta_unit=cfg.synopsis.cluster_size)))


def _corpora(cfg, n=2, seed=3):
  rng = np.random.default_rng(seed)
  return [rng.integers(0, cfg.vocab, PROMPT, dtype=np.int32)
          for _ in range(n)]


def _window(cfg, rid0=0):
  """Two serial admissions at 0 ms (corpus A, corpus B), then arrivals
  that find one lane free while the other decodes (overlapped), each a
  corpus-cache hit."""
  a, b = _corpora(cfg)
  spec = [(0.0, a, 2), (0.0, b, 4), (1.0, b, 3), (2.0, a, 2), (3.0, a, 4)]
  return [EngineRequest(rid=rid0 + i, arrival_ms=t, prompt=p,
                        max_new_tokens=n)
          for i, (t, p, n) in enumerate(spec)]


def _host_events(trace_dir):
  """(name, start_ns, end_ns, stats, line) of every engine span."""
  paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
  data = ProfileData.from_file(str(paths[-1]))
  out = []
  for plane in data.planes:
    if not plane.name.startswith("/host:"):
      continue
    for li, line in enumerate(plane.lines):
      for e in line.events:
        if e.name.startswith("engine."):
          out.append((e.name, e.start_ns, e.end_ns, dict(e.stats),
                      (plane.name, li)))
  return out


def test_every_span_appears_with_its_stats_inside_its_parent(
    cfg, engine, tmp_path):
  engine.reset()
  reqs = _window(cfg)
  # Corpus A's first half is cached, so its first admission replays only
  # the delta; B misses, and every later admission hits.
  half = reqs[0].prompt[:PROMPT // 2]
  logits, c1 = engine._prefill(engine.params, jnp.asarray(half)[None])
  engine.corpus_cache.publish(half, engine._build(c1),
                              jnp.argmax(logits, -1).astype(jnp.int32))
  jax.profiler.start_trace(str(tmp_path))
  try:
    engine.run(reqs)
  finally:
    jax.profiler.stop_trace()
  events = _host_events(tmp_path)
  seen = {}
  for name, _, _, stats, _ in events:
    assert name in spans.SPANS, name
    seen.setdefault(name, []).append(stats)
  assert set(seen) == set(spans.SPANS)
  for name, (_, keys) in spans.SPANS.items():
    for stats in seen[name]:
      assert set(stats) == set(keys), (name, stats)
      assert all(isinstance(v, int) for v in stats.values())
  # Children lie inside a span of their parent, on the same thread.
  for name, s0, e0, _, line in events:
    parent = spans.SPANS[name][0]
    if parent is None:
      continue
    assert any(n == parent and ln == line and s <= s0 and e0 <= e
               for n, s, e, _, ln in events), (name, s0)
  # The spans of one request share its rid: a serial admission opens
  # one engine.admit, an overlapped one two; every request retires once.
  admits = seen["engine.admit"]
  for r in reqs:
    mine = [st for st in admits if st["rid"] == r.rid]
    assert len(mine) in (1, 2)
    assert all(st["overlapped"] == (len(mine) == 2) for st in mine)
    assert sum(st["rid"] == r.rid for st in seen["engine.retire"]) == 1
  assert {st["overlapped"] for st in admits} == {0, 1}
  steps = [st["step"] for st in seen["engine.decode_step"]]
  assert steps == list(range(len(engine.step_log)))


def test_wall_stamps_agree_with_the_benchmark_clock(cfg, engine):
  engine.reset()
  reqs = _window(cfg, rid0=100)
  wallclock = _wallclock()
  clock = wallclock.WallClock(engine, {r.rid: r for r in reqs})
  clock.start()
  try:
    engine.run(reqs)
  finally:
    clock.close()
    engine.__dict__.pop("_dispatch_admission", None)
  for r in reqs:
    assert r.dispatch_w_ms == pytest.approx(clock.dispatch_w[r.rid], abs=1.0)
    assert r.first_w_ms == pytest.approx(clock.admit_w[r.rid], abs=1.0)
    assert r.finish_w_ms == pytest.approx(clock.retire_w[r.rid], abs=1.0)
    # The lag is host time the clock had not counted: never negative, and
    # never more than the request's whole wait, since it was let in only
    # once the clock had passed its arrival.
    assert 0.0 <= r.clock_lag_ms <= r.dispatch_w_ms - r.arrival_ms + 1e-9
  assert engine.busy_ms == pytest.approx(
      sum(ms for _, ms, _ in engine.step_log)
      + sum(r.admit_wall_ms for r in reqs))
  # At the window's end, after the last retire and before the benchmark
  # closed its clock, which it does once run() has returned the summary.
  lag = engine.summary()["clock_lag_ms"]
  assert lag == engine.wall_ms() - engine.now_ms
  assert max(r.finish_w_ms for r in reqs) - engine.now_ms <= lag \
      <= clock.end_w - engine.now_ms
  engine.reset()
  assert (engine.busy_ms, engine.compiles, engine.wall_ms()) == (0, 0, 0.0)


def test_warm_window_compiles_nothing(cfg):
  """The warm-up compiles every program a window dispatches; a budget it
  did not warm compiles inside the window, and the counter sees it."""
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=2, prompt_len=32, max_new_tokens=2, policy="fixed",
      fixed_budget=1, impl="xla"))
  assert eng._warm_buckets() == (1,) and 2 in eng.buckets

  def window():
    rng = np.random.default_rng(5)
    return [EngineRequest(rid=i, arrival_ms=float(i), max_new_tokens=2,
                          prompt=rng.integers(0, cfg.vocab, 32,
                                              dtype=np.int32))
            for i in range(4)]

  assert eng.run(window())["compiles"] == 0
  eng.ecfg.fixed_budget = 2
  eng.reset(reset_controller=True)
  assert eng.compiles == 0
  assert eng.run(window())["compiles"] >= 1
