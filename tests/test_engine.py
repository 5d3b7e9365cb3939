"""Continuous-batching serving engine (DESIGN.md §8): slot admit/retire
invariants, deadline->budget monotonicity, xla-vs-interpret parity through
the full engine loop, the budget-0 stage-1 floor, and the measured-latency
delegation into the discrete-event simulator."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.deadline import BudgetController, LatencyModel
from repro.serve import synopsis_kv as skv
from repro.serve.cluster import ClusterConfig, ClusterStepBackend
from repro.serve.engine import (CacheConfig, EngineConfig, EngineRequest,
                                MeasuredStepBackend, ServingEngine,
                                make_requests, make_zipf_requests,
                                run_open_loop)
from repro.serving.latency import ComponentModel
from repro.serving.service import ScatterGatherService, ServiceConfig

N_SLOTS, PROMPT, NEW = 2, 64, 4


@pytest.fixture(scope="module")
def cfg():
  return get_config("llama3-8b", smoke=True)


@pytest.fixture(scope="module")
def engine(cfg):
  return ServingEngine(cfg, EngineConfig(
      n_slots=N_SLOTS, prompt_len=PROMPT, max_new_tokens=NEW,
      deadline_ms=60.0, policy="accuracytrader", impl="xla"))


def _deterministic_requests(cfg, arrivals):
  return make_requests(arrivals, PROMPT, NEW, cfg.vocab, seed=7)


def test_slot_admit_retire_invariants(cfg, engine):
  engine.reset()
  reqs = _deterministic_requests(cfg, [0.0, 0.0, 0.0, 2.0, 2.0, 250.0])
  engine.run(reqs)

  assert len(engine.completed) == len(reqs)
  admits = {r: [] for r in range(len(reqs))}
  occupied = {}
  for kind, rid, slot, t in engine.events:
    assert 0 <= slot < N_SLOTS
    if kind == "admit":
      assert slot not in occupied, "admit into an occupied slot"
      occupied[slot] = rid
      admits[rid].append(t)
    else:
      assert occupied.get(slot) == rid, "retire of a non-resident request"
      del occupied[slot]
    assert len(occupied) <= N_SLOTS
  assert not occupied, "every admitted request retires"
  for r in reqs:
    assert len(admits[r.rid]) == 1, "each request admitted exactly once"
    assert r.admit_ms >= r.arrival_ms      # no time travel
    assert r.finish_ms > r.admit_ms
    assert len(r.tokens) == NEW + 1        # prefill token + NEW decodes
    assert len(r.budgets) == NEW
    assert 0.0 <= r.accuracy <= 1.0
  # The late arrival found an idle engine: it queued for ~no time.
  late = next(r for r in reqs if r.arrival_ms == 250.0)
  assert late.queue_ms < 50.0


def test_budget_monotone_in_deadline(cfg, engine):
  # Controller law (deterministic): tighter deadline => never more
  # clusters, whatever the calibrated model says.
  ctrl = BudgetController(LatencyModel(base=2.0, slope=0.5),
                          buckets=engine.buckets, i_max_cap=engine.M)
  for b, lat in [(0, 2.0), (2, 3.1), (4, 4.2), (4, 4.0), (2, 3.0)]:
    ctrl.observe(b, lat)
  budgets = [ctrl.budget_for(d) for d in np.linspace(0.0, 50.0, 200)]
  assert budgets == sorted(budgets)
  assert budgets[0] == engine.buckets[0]

  # Through the engine loop: a tight deadline's mean budget never exceeds
  # a loose one's on the same trace.
  means = {}
  for deadline in (2.0, 500.0):
    engine.reset()
    engine.ecfg.deadline_ms = deadline
    engine.run(_deterministic_requests(cfg, [0.0, 1.0, 2.0, 3.0]))
    means[deadline] = np.mean([b for b, _, _ in engine.step_log])
  engine.ecfg.deadline_ms = 60.0
  assert means[2.0] <= means[500.0]
  assert means[500.0] == engine.M          # unloaded loose run refines all


def test_xla_interpret_token_parity(cfg):
  toks = {}
  for impl in ("xla", "interpret"):
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=2, prompt_len=32, max_new_tokens=2, policy="fixed",
        fixed_budget=1, impl=impl))
    reqs = make_requests([0.0, 0.0, 4.0], 32, 2, cfg.vocab, seed=11)
    eng.run(reqs)
    toks[impl] = [r.tokens for r in sorted(reqs, key=lambda r: r.rid)]
  assert toks["xla"] == toks["interpret"]


def test_admission_overlap_token_parity(cfg):
  """Overlapping admission with resident decode changes dispatch order,
  never results: tokens match the serial-admission engine exactly, and
  slot invariants hold (each arrival admitted once, lanes cycle)."""
  toks = {}
  for overlap in (True, False):
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=2, prompt_len=32, max_new_tokens=2, policy="fixed",
        fixed_budget=1, impl="xla", overlap_admission=overlap))
    reqs = make_requests([0.0, 0.0, 1.0, 2.0, 3.0], 32, 2, cfg.vocab,
                         seed=13)
    eng.run(reqs)
    toks[overlap] = [r.tokens for r in sorted(reqs, key=lambda r: r.rid)]
    occupied = {}
    for kind, rid, slot, _ in eng.events:
      if kind == "admit":
        assert slot not in occupied
        occupied[slot] = rid
      else:
        assert occupied.pop(slot) == rid
    assert not occupied
    for r in reqs:
      assert len(r.tokens) == 3 and r.admit_ms >= r.arrival_ms
  assert toks[True] == toks[False]


def test_stage1_always_produced_at_budget_zero(cfg):
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=2, prompt_len=PROMPT, max_new_tokens=NEW, policy="fixed",
      fixed_budget=0, impl="xla"))
  reqs = _deterministic_requests(cfg, [0.0, 0.0, 1.0])
  eng.run(reqs)
  floor = eng.accuracy_fn(0.0)
  for r in reqs:
    assert r.budgets == [0] * NEW
    assert len(r.tokens) == NEW + 1        # a result ALWAYS comes back
    assert all(0 <= t < cfg.vocab for t in r.tokens)
    assert r.accuracy == pytest.approx(floor)
  s = eng.summary()
  assert s["accuracy_loss_pct"] == pytest.approx(100.0 * (1.0 - floor))


def test_append_recent_slots_per_slot_positions():
  nb, na, B, H, R, D = 1, 1, 3, 1, 4, 2
  cache = {
      "recent_k": jnp.zeros((nb, na, B, H, R, D)),
      "recent_v": jnp.zeros((nb, na, B, H, R, D)),
      "recent_len": jnp.array([0, 2, 3], jnp.int32),
  }
  delta = jnp.arange(B, dtype=jnp.float32).reshape(1, 1, B, 1, 1, 1) + 1.0
  delta = jnp.broadcast_to(delta, (nb, na, B, H, 1, D))
  active = jnp.array([True, False, True])
  out = skv.append_recent_slots(cache, delta, 2.0 * delta, active)
  rk = np.asarray(out["recent_k"])[0, 0, :, 0, :, 0]          # (B, R)
  np.testing.assert_allclose(rk[0], [1.0, 0, 0, 0])           # slot 0 @ 0
  np.testing.assert_allclose(rk[1], [0, 0, 0, 0])             # inactive
  np.testing.assert_allclose(rk[2], [0, 0, 0, 3.0])           # slot 2 @ 3
  np.testing.assert_array_equal(np.asarray(out["recent_len"]), [1, 2, 4])
  np.testing.assert_allclose(np.asarray(out["recent_v"])[0, 0, 2, 0, 3, 0],
                             6.0)
  # Full ring: neither writes nor advances.
  out2 = skv.append_recent_slots(out, delta, delta,
                                 jnp.array([False, False, True]))
  assert int(out2["recent_len"][2]) == 4
  np.testing.assert_allclose(np.asarray(out2["recent_k"]),
                             np.asarray(out["recent_k"]))


def test_partial_drops_at_deadline_and_frees_lane(cfg):
  """Partial execution sheds a request still resident at its deadline:
  the lane frees mid-flight and the skipped result scores 0."""
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=1, prompt_len=PROMPT, max_new_tokens=NEW, deadline_ms=1.0,
      policy="partial", impl="xla"))
  reqs = _deterministic_requests(cfg, [0.0, 0.0, 0.0])
  eng.run(reqs)
  assert len(eng.completed) == len(reqs)      # dropped, not stuck
  for r in reqs:
    assert r.accuracy == 0.0                  # all missed the 1 ms deadline
    assert len(r.tokens) < NEW + 1            # decode abandoned mid-flight
  occupied = set()
  for kind, rid, slot, _ in eng.events:       # lanes still cycle cleanly
    occupied.add(slot) if kind == "admit" else occupied.discard(slot)
  assert not occupied


@pytest.mark.parametrize("where", ["local", "cluster"])
def test_admission_follows_the_scheduling_clock(cfg, where):
  """Host time outside the measured walls (a 5 ms sleep in every retire)
  does not pile up as admission delay on the local path: arrivals spaced
  wider than a request's service find the engine idle and are let in as
  W reaches them, and ``admit_ms`` / ``finish_ms`` are W stamps.  Under
  a cluster backend the loop keeps scheduling on ``now_ms``, which
  advances by the backend's modelled parallel completion."""
  backend = ClusterStepBackend(ClusterConfig(
      n_components=2, seed=0, use_mesh=False)) if where == "cluster" else None
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=2, prompt_len=32, max_new_tokens=2, policy="fixed",
      fixed_budget=1, impl="xla"), backend=backend)
  delay_ms = 5.0
  retire = eng._retire

  def slow_retire(slot):
    time.sleep(delay_ms / 1e3)
    retire(slot)

  eng._retire = slow_retire
  reqs = make_requests([500.0 * i for i in range(10)], 32, 2, cfg.vocab,
                       seed=29)
  eng.run(reqs)
  assert len(eng.completed) == len(reqs)
  if where == "local":
    step_ms = max(ms for _, ms, _ in eng.step_log)
    for r in reqs:
      assert r.arrival_ms <= r.dispatch_w_ms
      assert r.dispatch_w_ms - r.arrival_ms < step_ms + delay_ms, r.rid
      assert r.admit_ms == pytest.approx(r.dispatch_w_ms, abs=1.0)
      assert r.finish_ms == pytest.approx(r.finish_w_ms, abs=1.0)
    return
  # One request resident at a time, so the step log splits into each
  # request's own steps, at the parallel completion the backend modelled.
  n = reqs[0].max_new_tokens
  for k, r in enumerate(reqs):
    assert r.admit_ms == r.arrival_ms      # the idle skip lands on it
    steps = [ms for _, ms, _ in eng.step_log[k * n:(k + 1) * n]]
    assert r.finish_ms == pytest.approx(
        r.admit_ms + r.admit_wall_ms + sum(steps))
  assert eng.now_ms == pytest.approx(reqs[-1].finish_ms)


def test_hybrid_ssm_state_advances_per_step():
  """Regression: decode must write conv/ssd deltas back per slot — with
  the states frozen at prefill, one and two decode steps would leave
  identical SSM state."""
  jcfg = get_config("jamba-v0.1-52b", smoke=True)
  states = {}
  for n_new in (1, 2):
    eng = ServingEngine(jcfg, EngineConfig(
        n_slots=1, prompt_len=64, max_new_tokens=n_new, policy="fixed",
        fixed_budget=1, impl="xla"))
    eng.run(make_requests([0.0], 64, n_new, jcfg.vocab, seed=3))
    states[n_new] = np.asarray(eng.cache["ssd_state"])
  assert not np.allclose(states[1], states[2])


def test_measured_backend_feeds_simulator(engine):
  backend = MeasuredStepBackend(engine, iters=1, full_items=100)
  assert set(backend.table) == set(engine.buckets)
  assert all(v > 0 for v in backend.table.values())
  # Simulator budgets (out of full_items=100) rescale onto engine buckets
  # (out of M) instead of collapsing onto the top bucket.
  assert backend.step_ms(200) == backend.table[engine.buckets[-1]]
  assert backend.step_ms(0) == backend.table[0]
  mid = min(engine.buckets, key=lambda b: abs(b - 0.5 * engine.M))
  assert backend.step_ms(50) == backend.table[mid]

  # The component queue serves in exactly the measured time when asked.
  comp = ComponentModel(seed=0, interference=0.0, straggler_prob=0.0)
  done = comp.submit(10.0, 5, service_ms=7.5)
  assert done == pytest.approx(17.5)

  svc = ScatterGatherService(
      ServiceConfig(n_components=8, technique="accuracytrader",
                    deadline_ms=100.0, seed=0),
      step_backend=backend)
  s = svc.run_open_loop(20.0, 1.0)
  assert s["n"] > 0 and s["p999"] > 0.0
  assert 0.0 <= s["accuracy_loss_pct"] <= 100.0


def test_run_open_loop_summary_fields(engine):
  s = run_open_loop(engine, rate_per_s=30.0, duration_s=0.3, seed=5)
  for k in ("p50", "p99", "p999", "accuracy_loss_pct",
            "deadline_miss_pct", "mean_budget", "queue_p99", "steps"):
    assert k in s
  assert s["n"] == len(engine.completed)


def _zipf_trace(cfg, n=8, n_corpora=3, seed=17):
  return make_zipf_requests([float(2 * i) for i in range(n)], 32, 2,
                            cfg.vocab, n_corpora=n_corpora, seed=seed)


def test_cache_token_and_loss_parity(cfg):
  """The corpus cache is a pure latency optimisation: a Zipf-repeated
  trace produces identical per-request tokens and loss with the cache on
  vs off, under both the xla and interpret kernels — and with it on,
  every repeat hits, so prefills == cache misses == unique corpora."""
  C = cfg.synopsis.cluster_size
  results = {}
  for impl in ("xla", "interpret"):
    for cache_on in (True, False):
      eng = ServingEngine(cfg, EngineConfig(
          n_slots=2, prompt_len=32, max_new_tokens=2, policy="fixed",
          fixed_budget=1, impl=impl,
          cache=CacheConfig(capacity=8, delta_unit=C) if cache_on
          else None))
      reqs = _zipf_trace(cfg)
      eng.run(reqs)
      s = eng.summary()
      results[(impl, cache_on)] = (
          [r.tokens for r in sorted(reqs, key=lambda r: r.rid)],
          s["accuracy_loss_pct"], s)
  toks0, loss0, _ = results[("xla", False)]
  for toks, loss, _ in results.values():
    assert toks == toks0
    assert loss == loss0
  uniq = len({r.prompt.tobytes() for r in _zipf_trace(cfg)})
  _, _, s_on = results[("xla", True)]
  assert s_on["prefills"] == s_on["cache_misses"] == uniq
  assert s_on["cache_hits"] == len(toks0) - uniq
  assert s_on["cache_hit_rate"] == pytest.approx(1.0 - uniq / len(toks0))


def test_cache_delta_replay_admission(cfg):
  """A corpus strictly prefix-extending a cached entry replays only the
  KV delta: no full prefill, the extended corpus is itself published,
  and a repeat of it is an exact hit."""
  C = cfg.synopsis.cluster_size
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=1, prompt_len=PROMPT, max_new_tokens=2, policy="fixed",
      fixed_budget=1, impl="xla",
      cache=CacheConfig(capacity=8, delta_unit=C)))
  reqs = make_requests([0.0], PROMPT, 2, cfg.vocab, seed=21)
  prefix = reqs[0].prompt[:PROMPT // 2]          # 2 kd clusters (C=16)
  logits, c1 = eng._prefill(eng.params, jnp.asarray(prefix)[None])
  first = jnp.argmax(logits, -1).astype(jnp.int32)
  eng.corpus_cache.publish(prefix, eng._build(c1), first)

  eng.run(reqs)
  st = eng.corpus_cache.stats()
  assert st["delta_hits"] == 1 and st["misses"] == 0
  assert eng.prefills == 0                       # no full prefill ran
  assert len(reqs[0].tokens) == 3
  assert st["entries"] == 2                      # prefix + extended corpus

  eng.run(make_requests([0.0], PROMPT, 2, cfg.vocab, seed=21))
  st = eng.corpus_cache.stats()
  assert st["hits"] == 1 and st["delta_hits"] == 1 and eng.prefills == 0


def test_cache_disabled_is_control_arm(cfg):
  """capacity=0 (and cache=None) is a true no-op: identical tokens and
  deterministic summary fields, and no cache_* keys leak into the
  summary — so --no-cache benches a clean control arm."""
  outs = {}
  for name, cache in (("none", None), ("zero", CacheConfig(capacity=0))):
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=2, prompt_len=32, max_new_tokens=2, policy="fixed",
        fixed_budget=1, impl="xla", cache=cache))
    reqs = _zipf_trace(cfg, n=4, n_corpora=2, seed=19)
    eng.run(reqs)
    outs[name] = ([r.tokens for r in sorted(reqs, key=lambda r: r.rid)],
                  eng.summary())
  toks_none, s_none = outs["none"]
  toks_zero, s_zero = outs["zero"]
  assert toks_none == toks_zero
  assert set(s_none) == set(s_zero)
  assert not any(k.startswith("cache_") for k in s_none)
  for k in ("prefills", "served_n", "accuracy_loss_pct", "n"):
    assert s_none[k] == s_zero[k]


def test_cache_survives_reset_windows(cfg):
  """Entries persist across measurement windows (reset() drops pins and
  counters, not arenas — warm state is the point); the second window of
  an identical trace runs at 100% hit rate with zero prefills."""
  C = cfg.synopsis.cluster_size
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=2, prompt_len=32, max_new_tokens=2, policy="fixed",
      fixed_budget=1, impl="xla",
      cache=CacheConfig(capacity=8, delta_unit=C)))
  eng.run(_zipf_trace(cfg, n=4, n_corpora=1, seed=23))
  assert eng.corpus_cache.stats()["misses"] == 1
  eng.reset()
  assert eng.corpus_cache.stats() == {
      "hits": 0, "misses": 0, "delta_hits": 0, "evictions": 0,
      "entries": 1, "bytes": eng.corpus_cache.nbytes, "hit_rate": 0.0}
  eng.run(_zipf_trace(cfg, n=4, n_corpora=1, seed=23))
  st = eng.corpus_cache.stats()
  assert st["hits"] == 4 and st["misses"] == 0 and eng.prefills == 0


def test_engine_rejects_inapplicable_configs(cfg):
  with pytest.raises(ValueError):
    ServingEngine(get_config("mamba2-370m", smoke=True),
                  EngineConfig(prompt_len=64))   # no KV cache to synopsize
  with pytest.raises(ValueError):
    ServingEngine(cfg, EngineConfig(prompt_len=65))  # not cluster-aligned
  with pytest.raises(ValueError):
    ServingEngine(cfg, EngineConfig(
        prompt_len=64, max_new_tokens=cfg.synopsis.recent + 1))
