"""Multi-component scatter-gather serving tier (DESIGN.md §9): topology
partition laws, budget-allocation monotonicity in relevance mass, the
global-top-k merge equalling the single-component reference on a
concatenated corpus, the partial-gather stage-1 fallback, per-slot corpus
routing round-trips, and the cluster engine end to end (incl. the
measured per-component export feeding the simulator)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.dist.topology import (ComponentTopology, MeshUnavailable,
                                 make_component_mesh, make_fleet_mesh,
                                 zipf_weights)
from repro.serve.cluster import (MODE_DROP, MODE_FULL, MODE_STAGE1,
                                 ClusterConfig, ClusterStepBackend,
                                 allocate_budget, make_cluster_attention)
from repro.serve.engine import EngineConfig, ServingEngine, run_open_loop
from repro.serving.latency import ComponentModel
from repro.serving.service import ScatterGatherService, ServiceConfig

B, Hkv, G, D, S, C = 2, 2, 2, 16, 256, 16
H, M = Hkv * G, S // C
SM = float(1.0 / np.sqrt(D))


# -- topology ----------------------------------------------------------------

def test_topology_partition_laws():
  for n, skew in [(1, 0.0), (4, 0.0), (4, 1.2), (7, 0.9), (16, 2.0)]:
    topo = ComponentTopology.plan(16, n, skew)
    assert sum(topo.counts) == 16
    assert all(c >= 1 for c in topo.counts)
    assert topo.m_max == max(topo.counts)
    assert len(topo.offsets) == n and topo.offsets[0] == 0
    owner = topo.cluster_owner()
    assert owner.shape == (16,)
    assert (np.diff(owner) >= 0).all()          # contiguous ranges
  # Zipf skew: rank-0 owns the most; uniform when skew == 0.
  skewed = ComponentTopology.plan(32, 4, 1.2)
  assert list(skewed.counts) == sorted(skewed.counts, reverse=True)
  assert skewed.counts[0] > skewed.counts[-1]
  assert set(ComponentTopology.plan(32, 4, 0.0).counts) == {8}
  w = zipf_weights(5, 1.0)
  assert w.sum() == pytest.approx(1.0) and (np.diff(w) < 0).all()
  with pytest.raises(ValueError):
    ComponentTopology.plan(4, 8)               # more components than corpus


def test_allocate_budget_monotone_in_mass():
  rng = np.random.default_rng(0)
  for _ in range(20):
    mass = jnp.asarray(rng.uniform(0.1, 10.0, (1, 1, 6)))
    caps = jnp.full((1, 1, 6), 8)
    out = np.asarray(allocate_budget(mass, 12, caps))[0, 0]
    m = np.asarray(mass)[0, 0]
    order = np.argsort(m)
    assert (np.diff(out[order]) >= 0).all(), (m, out)   # monotone in mass
    assert out.sum() <= 12 and (out <= 8).all() and (out >= 0).all()
  # Exactly proportional when it divides evenly.
  out = np.asarray(allocate_budget(
      jnp.asarray([[1.0, 2.0, 1.0]]), 8, jnp.full((1, 3), 8)))[0]
  assert list(out) == [2, 4, 2]
  # A budget covering the whole corpus saturates every cap, however
  # skewed the mass — the `basic` full gather must stay exact.
  out = np.asarray(allocate_budget(
      jnp.asarray([[10.0, 1.0]]), 8, jnp.asarray([[4, 4]])))[0]
  assert list(out) == [4, 4]


# -- attention parity --------------------------------------------------------

def _mk_inputs(seed=0):
  ks = jax.random.split(jax.random.PRNGKey(seed), 8)
  q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
  cache = {
      "k": jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32),
      "v": jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32),
      "recent_k": jax.random.normal(ks[3], (B, Hkv, 16, D), jnp.float32),
      "recent_v": jax.random.normal(ks[4], (B, Hkv, 16, D), jnp.float32),
      "recent_len": jnp.full((B,), 5, jnp.int32),
      "counts": jnp.full((B, M), float(C)),
  }
  cache["k_syn"] = cache["k"].reshape(B, Hkv, M, C, D).mean(3)
  cache["v_syn"] = cache["v"].reshape(B, Hkv, M, C, D).mean(3)
  self_kv = jax.random.normal(ks[5], (B, Hkv, 1, D), jnp.float32)
  return q, cache, (self_kv, self_kv)


def _scatter(cache, topo):
  """Reference host-side scatter of a (B, Hkv, S, D) corpus slice into the
  padded per-component layout the tier uses."""
  Mp = topo.m_max
  out = {k: cache[k] for k in ("recent_k", "recent_v", "recent_len")}
  for name, unit in (("k", C), ("v", C), ("k_syn", 1), ("v_syn", 1)):
    parts = []
    for c in range(topo.n_components):
      off, cnt = topo.offsets[c] * unit, topo.counts[c] * unit
      sl = cache[name][:, :, off:off + cnt]
      if Mp * unit - cnt:
        sl = jnp.pad(sl, [(0, 0), (0, 0), (0, Mp * unit - cnt), (0, 0)])
      parts.append(sl)
    out[name] = jnp.stack(parts, axis=2)
  parts = []
  for c in range(topo.n_components):
    sl = cache["counts"][:, topo.offsets[c]:topo.offsets[c] + topo.counts[c]]
    if Mp - topo.counts[c]:
      sl = jnp.pad(sl, [(0, 0), (0, Mp - topo.counts[c])])
    parts.append(sl)
  out["counts"] = jnp.stack(parts, axis=1)
  return out


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("n,skew", [(2, 0.0), (4, 0.0), (4, 1.2)])
def test_global_topk_merge_equals_single_component(impl, n, skew):
  """alloc="topk" with every component gathered must reproduce the
  single-component reference on the concatenated corpus: the two-level
  top-k selects the same global clusters and the per-component partial
  merges compose to the same online softmax (<= 1e-5 f32)."""
  from repro.serve.serve_step import synopsis_decode_attention
  q, cache, self_kv = _mk_inputs()
  ref = synopsis_decode_attention(q, cache, i_max=4, cluster_size=C,
                                  sm_scale=SM, self_kv=self_kv, impl="xla")
  topo = ComponentTopology.plan(M, n, skew)
  csl = _scatter(cache, topo)
  csl["fe_mode"] = jnp.full((n,), MODE_FULL, jnp.int32)
  attn = make_cluster_attention(topo, alloc="topk", mesh=None)
  got, aux = attn(q, csl, i_max=4, cluster_size=C, sm_scale=SM,
                  self_kv=self_kv, impl=impl)
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
  # The global top-4 is fully covered across components.
  assert float(np.asarray(aux["fe_cover"]).sum()) == pytest.approx(4.0)
  assert np.asarray(aux["fe_mass"]).sum() == pytest.approx(1.0, abs=1e-5)


def test_partial_gather_stage1_floor_for_skipped():
  """A component marked STAGE1 contributes exactly its synopsis partial
  (manual composition check); a DROPped component contributes nothing."""
  q, cache, self_kv = _mk_inputs(seed=3)
  n = 4
  topo = ComponentTopology.plan(M, n, 0.0)
  csl = _scatter(cache, topo)
  attn = make_cluster_attention(topo, alloc="topk", mesh=None)

  def run(mode):
    c = dict(csl)
    c["fe_mode"] = jnp.asarray(mode, jnp.int32)
    out, _ = attn(q, c, i_max=4, cluster_size=C, sm_scale=SM,
                  self_kv=self_kv, impl="xla")
    return out

  # Skipping component 1's refinement really changes the result (its
  # stage-1 partial stands in for the refined clusters it owned).
  mode = np.full((n,), MODE_FULL)
  mode[1] = MODE_STAGE1
  got = run(mode)
  full = run(np.full((n,), MODE_FULL))
  assert float(jnp.abs(got - full).max()) > 1e-6   # refinement really lost
  # Budget 0 on every component == stage-1-only on every component.
  got_b0 = run(np.full((n,), MODE_STAGE1))
  c0 = dict(csl)
  c0["fe_mode"] = jnp.full((n,), MODE_FULL, jnp.int32)
  out0, _ = attn(q, c0, i_max=0, cluster_size=C, sm_scale=SM,
                 self_kv=self_kv, impl="xla")
  np.testing.assert_allclose(np.asarray(got_b0), np.asarray(out0),
                             atol=1e-5)
  # DROP removes a component's contribution entirely: dropping ALL
  # components leaves exactly the frontend-owned extras — exact attention
  # over the valid recent-ring tokens + the new token's self-KV.
  from repro.kernels import ref as kref
  got_d = run(np.full((n,), MODE_DROP))
  rl = int(cache["recent_len"][0])
  ke = jnp.concatenate([cache["recent_k"][:, :, :rl], self_kv[0]], axis=2)
  ve = jnp.concatenate([cache["recent_v"][:, :, :rl], self_kv[1]], axis=2)
  ref_d, _, _ = kref.flash_decode_ref(q, ke, ve, sm_scale=SM)
  np.testing.assert_allclose(np.asarray(got_d), np.asarray(ref_d),
                             atol=1e-5)


# -- engine integration ------------------------------------------------------

@pytest.fixture(scope="module")
def cluster_engine():
  cfg = get_config("llama3-8b", smoke=True)
  backend = ClusterStepBackend(ClusterConfig(
      n_components=2, seed=0, use_mesh=False))
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=2, prompt_len=64, max_new_tokens=3, deadline_ms=60.0,
      policy="accuracytrader", impl="xla"), backend=backend)
  return eng, backend


def test_cluster_engine_end_to_end(cluster_engine):
  eng, backend = cluster_engine
  s = run_open_loop(eng, rate_per_s=30.0, duration_s=0.4, seed=5)
  assert s["n"] > 0 and s["n"] == len(eng.completed)
  for r in eng.completed:
    assert len(r.step_acc) == len(r.budgets)
    assert all(0.0 <= a <= 1.0 for a in r.step_acc)
    assert 0.0 <= r.accuracy <= 1.0
  assert backend.predictor.table()              # calibrated something
  assert all(v > 0 for v in backend.predictor.table().values())


def test_cluster_export_feeds_simulator(cluster_engine):
  eng, backend = cluster_engine
  if not backend.predictor.table():
    run_open_loop(eng, rate_per_s=30.0, duration_s=0.3, seed=5)
  exp = backend.export()
  vec = exp.step_ms_per_component(50)
  assert vec.shape == (2,) and (vec > 0).all()
  assert exp.step_ms(50) == pytest.approx(float(vec.max()))
  # More budget never means a smaller attributed parallel time.
  assert exp.step_ms(100) >= exp.step_ms(0) - 1e-9

  # ComponentModel indexes its own entry from a per-component vector.
  comp = ComponentModel(seed=0, comp_id=1, interference=0.0,
                        straggler_prob=0.0)
  done = comp.submit(10.0, 5, service_ms=np.asarray([3.0, 7.5]))
  assert done == pytest.approx(17.5)

  svc = ScatterGatherService(
      ServiceConfig(n_components=2, technique="accuracytrader",
                    deadline_ms=100.0, seed=0), step_backend=exp)
  s = svc.run_open_loop(20.0, 1.0)
  assert s["n"] > 0 and 0.0 <= s["accuracy_loss_pct"] <= 100.0


def test_cluster_partial_policy_sheds_components():
  """Under an impossible deadline the partial tier drops components (and
  requests), while accuracytrader's stage-1 floor keeps accuracy near
  the synopsis level — the Tables 1-2 ordering, in miniature."""
  cfg = get_config("llama3-8b", smoke=True)
  losses = {}
  for policy in ("partial", "accuracytrader"):
    backend = ClusterStepBackend(ClusterConfig(
        n_components=2, seed=0, use_mesh=False))
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=1, prompt_len=64, max_new_tokens=3, deadline_ms=0.5,
        policy=policy, impl="xla"), backend=backend)
    s = run_open_loop(eng, rate_per_s=30.0, duration_s=0.3, seed=5)
    losses[policy] = s["accuracy_loss_pct"]
  assert losses["partial"] > losses["accuracytrader"]
  floor = 100.0 * (1.0 - 0.93)
  assert losses["accuracytrader"] <= floor + 1.0


def test_scatter_route_roundtrip():
  """The backend's jitted scatter+write routes every cluster of a slot's
  corpus to exactly one component (counts conserved), for both fixed and
  rotated routing."""
  cfg = get_config("llama3-8b", smoke=True)
  for route in ("fixed", "rotate"):
    backend = ClusterStepBackend(ClusterConfig(
        n_components=2, skew=1.2, route=route, use_mesh=False))
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=2, prompt_len=64, max_new_tokens=2, policy="fixed",
        fixed_budget=1, impl="xla"), backend=backend)
    eng.reset()
    from repro.serve.engine import make_requests
    reqs = make_requests([0.0, 0.0], 64, 2, cfg.vocab, seed=9)
    eng._admit(reqs[0], 0)
    eng._admit(reqs[1], 1)
    counts = np.asarray(eng.cache["counts"])    # (nb, na, B, N, Mp)
    for slot in range(2):
      # Token conservation: M clusters of C tokens each, routed once.
      assert counts[0, 0, slot].sum() == eng.M * cfg.synopsis.cluster_size
      assert (counts[0, 0, slot] > 0).sum() == eng.M
    if route == "rotate":
      # Slot 1's ownership is slot 0's rolled by one component.
      c0 = (counts[0, 0, 0] > 0).sum(-1)
      c1 = (counts[0, 0, 1] > 0).sum(-1)
      assert list(np.roll(c0, 1)) == list(c1)


def test_backend_rejects_bad_configs():
  cfg = get_config("llama3-8b", smoke=True)
  with pytest.raises(ValueError):
    ServingEngine(cfg, EngineConfig(n_slots=1, prompt_len=64,
                                    max_new_tokens=2, impl="xla"),
                  backend=ClusterStepBackend(ClusterConfig(
                      n_components=2, alloc="nope")))
  with pytest.raises(ValueError):
    # more components than the corpus has clusters (M = 64/16 = 4)
    ServingEngine(cfg, EngineConfig(n_slots=1, prompt_len=64,
                                    max_new_tokens=2, impl="xla"),
                  backend=ClusterStepBackend(ClusterConfig(
                      n_components=8, use_mesh=False)))


# -- corpus cache on the cluster tier ----------------------------------------

def test_cache_shared_arena_shards_identically():
  """A cache hit maps the shared arena into its slot lane through the
  same jitted scatter+write a private build uses: one corpus admitted to
  slot 0 (miss) and slot 1 (hit) yields bit-identical per-component
  lanes, and both match a cache-off engine's two private builds."""
  from repro.serve import kv_cache as kvc
  from repro.serve.engine import CacheConfig, make_requests
  cfg = get_config("llama3-8b", smoke=True)
  Cs = cfg.synopsis.cluster_size
  lanes = {}
  for cache_on in (True, False):
    backend = ClusterStepBackend(ClusterConfig(
        n_components=2, skew=1.2, seed=0, use_mesh=False))
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=2, prompt_len=64, max_new_tokens=2, policy="fixed",
        fixed_budget=1, impl="xla",
        cache=CacheConfig(capacity=8, delta_unit=Cs) if cache_on
        else None), backend=backend)
    eng.reset()
    reqs = make_requests([0.0, 0.0], 64, 2, cfg.vocab, seed=9)
    reqs[1].prompt = reqs[0].prompt.copy()       # the same corpus twice
    eng._admit(reqs[0], 0)
    eng._admit(reqs[1], 1)
    if cache_on:
      st = eng.corpus_cache.stats()
      assert st["misses"] == 1 and st["hits"] == 1
      assert eng.prefills == 1                   # slot 1 skipped prefill
    lanes[cache_on] = {name: np.asarray(eng.cache[name])
                       for name in kvc.ARENA_LEAVES if name in eng.cache}
  for name in lanes[True]:
    # Within the cache-on engine: the hit-mapped lane == the built lane.
    np.testing.assert_array_equal(lanes[True][name][:, :, 0],
                                  lanes[True][name][:, :, 1], err_msg=name)
    # Across engines: the shared arena scatters exactly like a private
    # build (the cache stores pre-scatter canonical state).
    np.testing.assert_array_equal(lanes[True][name], lanes[False][name],
                                  err_msg=name)


def test_cache_with_crashed_component_recovery():
  """A shard whose state came from a shared cache arena rides the same
  recovery ladder as a private one: with a component crashed the whole
  window and a 100%-repeat trace, availability stays 100%, the dead
  shard falls back to stage-1, and the repeats still hit the cache."""
  from repro.serve.engine import CacheConfig
  from repro.serve.resilience import FaultSpec
  cfg = get_config("llama3-8b", smoke=True)
  backend = ClusterStepBackend(ClusterConfig(
      n_components=2, replicas=1, seed=0, use_mesh=False,
      faults=FaultSpec(crash=((0, 1),), seed=5)))
  eng = ServingEngine(cfg, EngineConfig(
      n_slots=2, prompt_len=64, max_new_tokens=2, deadline_ms=60.0,
      policy="accuracytrader", impl="xla",
      cache=CacheConfig(capacity=8,
                        delta_unit=cfg.synopsis.cluster_size)),
      backend=backend)
  s = run_open_loop(eng, rate_per_s=30.0, duration_s=0.4, seed=3,
                    zipf_corpora=1)
  assert s["n"] > 0
  assert s["availability_pct"] == 100.0
  assert s["cache_hits"] > 0 and s["cache_misses"] == 1.0
  assert backend.fault_stats["stage1_fallbacks"] > 0
  assert backend.fault_stats["dropped"] == 0


# -- shard_map execution (multi-device, subprocess) --------------------------

_SHARDED_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.dist.topology import ComponentTopology, make_component_mesh
from repro.serve.cluster import make_cluster_attention, MODE_FULL, MODE_STAGE1

B, Hkv, G, D, S, C = 2, 2, 2, 16, 256, 16
H, M = Hkv * G, S // C
ks = jax.random.split(jax.random.PRNGKey(0), 8)
q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
cache = {
    "k": jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32),
    "v": jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32),
    "recent_k": jax.random.normal(ks[3], (B, Hkv, 16, D), jnp.float32),
    "recent_v": jax.random.normal(ks[4], (B, Hkv, 16, D), jnp.float32),
    "recent_len": jnp.full((B,), 5, jnp.int32),
    "counts": jnp.full((B, M), float(C)),
}
cache["k_syn"] = cache["k"].reshape(B, Hkv, M, C, D).mean(3)
cache["v_syn"] = cache["v"].reshape(B, Hkv, M, C, D).mean(3)
kd = jax.random.normal(ks[5], (B, Hkv, 1, D), jnp.float32)
sm = float(1.0 / np.sqrt(D))

def scatter(cache, topo):
    Mp = topo.m_max
    out = {k: cache[k] for k in ("recent_k", "recent_v", "recent_len")}
    for name, unit in (("k", C), ("v", C), ("k_syn", 1), ("v_syn", 1)):
        parts = []
        for c in range(topo.n_components):
            off, cnt = topo.offsets[c] * unit, topo.counts[c] * unit
            sl = cache[name][:, :, off:off + cnt]
            if Mp * unit - cnt:
                sl = jnp.pad(sl, [(0, 0), (0, 0), (0, Mp * unit - cnt),
                                  (0, 0)])
            parts.append(sl)
        out[name] = jnp.stack(parts, axis=2)
    parts = []
    for c in range(topo.n_components):
        sl = cache["counts"][:, topo.offsets[c]:topo.offsets[c]
                             + topo.counts[c]]
        if Mp - topo.counts[c]:
            sl = jnp.pad(sl, [(0, 0), (0, Mp - topo.counts[c])])
        parts.append(sl)
    out["counts"] = jnp.stack(parts, axis=1)
    return out

res = {}
for name, n, skew, alloc in [("u_topk", 8, 0.0, "topk"),
                             ("z_mass", 8, 1.1, "mass")]:
    topo = ComponentTopology.plan(M, n, skew)
    mesh = make_component_mesh(n)
    assert mesh is not None
    csl = scatter(cache, topo)
    mode = np.full((n,), MODE_FULL); mode[1] = MODE_STAGE1
    csl["fe_mode"] = jnp.asarray(mode, jnp.int32)
    sharded = make_cluster_attention(topo, alloc=alloc, mesh=mesh)
    stacked = make_cluster_attention(topo, alloc=alloc, mesh=None)
    got = jax.jit(lambda q, c, s: sharded(
        q, c, i_max=4, cluster_size=C, sm_scale=sm, self_kv=s,
        impl="xla")[0])(q, csl, (kd, kd))
    want, _ = stacked(q, csl, i_max=4, cluster_size=C, sm_scale=sm,
                      self_kv=(kd, kd), impl="xla")
    res[name] = float(np.abs(np.asarray(got) - np.asarray(want)).max())
print("RESULT:" + json.dumps(res))
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_sharded_cluster_equals_stacked():
  """The shard_map execution over 8 placeholder devices (one per
  component) must equal the stacked single-device execution — incl. a
  skewed partition with padded shards and a partial-gather mode vector."""
  import json
  import os
  import subprocess
  import sys
  env = dict(os.environ)
  env["PYTHONPATH"] = "src"
  p = subprocess.run([sys.executable, "-c", _SHARDED_PROG],
                     capture_output=True, text=True, env=env, timeout=600,
                     cwd=os.path.dirname(os.path.dirname(__file__)))
  assert p.returncode == 0, p.stderr[-3000:]
  line = [l for l in p.stdout.splitlines() if l.startswith("RESULT:")][0]
  res = json.loads(line[len("RESULT:"):])
  for k, err in res.items():
    assert err < 1e-5, (k, res)


_CACHE_SHARDED_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.configs.registry import get_config
from repro.serve import kv_cache as kvc
from repro.serve.cluster import ClusterConfig, ClusterStepBackend
from repro.serve.engine import (CacheConfig, EngineConfig, ServingEngine,
                                make_requests)

cfg = get_config("llama3-8b", smoke=True)
Cs = cfg.synopsis.cluster_size
res = {}
for name, mesh in (("mesh", True), ("stacked", False)):
    backend = ClusterStepBackend(ClusterConfig(
        n_components=8, seed=0, use_mesh=mesh))
    eng = ServingEngine(cfg, EngineConfig(
        n_slots=2, prompt_len=128, max_new_tokens=2, policy="fixed",
        fixed_budget=1, impl="xla",
        cache=CacheConfig(capacity=8, delta_unit=Cs)), backend=backend)
    eng.reset()
    reqs = make_requests([0.0, 0.0], 128, 2, cfg.vocab, seed=9)
    reqs[1].prompt = reqs[0].prompt.copy()
    eng._admit(reqs[0], 0)     # miss: private build, scattered to 8 shards
    eng._admit(reqs[1], 1)     # hit: shared arena, same scatter+write
    st = eng.corpus_cache.stats()
    assert st["misses"] == 1 and st["hits"] == 1, st
    res[name] = max(
        float(np.abs(np.asarray(eng.cache[l]).astype(np.float32)[:, :, 0]
                     - np.asarray(eng.cache[l]).astype(np.float32)[:, :, 1]
                     ).max())
        for l in kvc.ARENA_LEAVES if l in eng.cache)
print("RESULT:" + json.dumps(res))
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_cache_shared_arena_shards_identically_sharded():
  """The shard_map (8 placeholder devices) and stacked executions both
  write a cache-hit's shared arena bit-identically to the private build
  it deduplicates — the slot-1 lane equals the slot-0 lane exactly."""
  import json
  import os
  import subprocess
  import sys
  env = dict(os.environ)
  env["PYTHONPATH"] = "src"
  p = subprocess.run([sys.executable, "-c", _CACHE_SHARDED_PROG],
                     capture_output=True, text=True, env=env, timeout=600,
                     cwd=os.path.dirname(os.path.dirname(__file__)))
  assert p.returncode == 0, p.stderr[-3000:]
  line = [l for l in p.stdout.splitlines() if l.startswith("RESULT:")][0]
  res = json.loads(line[len("RESULT:"):])
  for k, err in res.items():
    assert err == 0.0, (k, res)


@pytest.mark.parametrize("make", [
    lambda n, use: make_component_mesh(n, use),
    lambda n, use: make_fleet_mesh(n, 1, use),
], ids=["component", "fleet"])
def test_tier_mesh_stacked_only_when_asked_or_short_cpu(make, monkeypatch):
  """A tier runs stacked when asked (use_mesh=False) or on a CPU host
  short of devices; asked for a mesh, or on an accelerator, a host short
  of devices is an error naming both counts."""
  n = len(jax.devices()) + 1
  assert make(n, False) is None
  assert make(n, None) is None                 # CPU host: stacked
  with pytest.raises(MeshUnavailable, match=rf"needs {n} devices"):
    make(n, True)
  assert make(1, None).devices.size == 1
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  with pytest.raises(MeshUnavailable,
                     match=rf"needs {n} devices .* found {n - 1} tpu"):
    make(n, None)
  assert make(n, False) is None
