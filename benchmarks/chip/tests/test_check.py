"""The check that decides ``correct``, on a small engine on the CPU.

The harness's look for a chip is skipped (``on_chip=False``, XLA kernels);
the rest of a run is driven as ``run.py`` drives it.  A sound run passes;
the fp8 control, judged by the same numbers, does not; and each fault a
served cell can have, planted under the timed path, turns ``correct``
false.
"""
import dataclasses
import time
import types

import jax.numpy as jnp
import pytest

from repro.models.common import SynopsisConfig
from yardstick import harness

SEED = 2 ** 31 + 77
# Widest gap of the compared tokens in this small cell, four seeds of
# each mix (1, 2, 3, 2**31 + 77): sound 0 to 0.0138; the fp8 control
# 0.085 to 0.170; a state left unchanged 0.226 to 0.486, the other two
# faults 2.3 and more.
LIMIT = 0.04


def _cell(kind):
  conf = {"name": "tiny", "registry": "pixtral-12b", "fields": {},
          "reference": "dense_gqa",
          "replace": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                      "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                      "vocab": 8192, "frontend": None, "frontend_tokens": 0,
                      "frontend_dim": 0,
                      "synopsis": SynopsisConfig(cluster_size=16, i_max=2,
                                                 recent=32)}}
  # Short prompts, so that the few tokens a request is served are a large
  # share of what it attends to, and a lost ring entry shows.
  mix = {"prompt": {"kind": kind, "tokens": 32, "corpora": 2,
                    "zipf_alpha": 1.1},
         "output_tokens": {"min": 8, "max": 16}, "slots": 4,
         "policy": "accuracytrader", "deadline_ms": 200.0,
         "rate_per_s": 10.0, "corpus_cache": 2 if kind == "corpora" else 0}
  return harness.Cell(name="tiny", chips=1, conf=conf, mix=mix,
                      check={"sample_requests": 8,
                             "served_logit_gap_max_limit": LIMIT},
                      end_to_end=["latency_p95_ms", "setup_s"],
                      per_layer=["refined_pct"])


@pytest.fixture(scope="module", params=["corpora", "fresh"])
def tiny(request):
  return harness.setup(_cell(request.param), SEED, time.perf_counter(),
                       impl="xla", on_chip=False)


def _token_altered(step):
  """Each served token is the one after the model's choice."""
  def faulty(*args):
    logits, st = step(*args)
    return jnp.roll(logits, 1, axis=-1), st
  return faulty


def _state_unchanged(step):
  """The step returns the lanes' state as it got it: no new key or value
  in the ring, no position advanced."""
  def faulty(params, cache, tok, *rest):
    logits, st = step(params, cache, tok, *rest)
    return logits, {**st, "k_delta": jnp.zeros_like(st["k_delta"]),
                    "v_delta": jnp.zeros_like(st["v_delta"]),
                    "pos": cache["pos"]}
  return faulty


def _half_batch(step):
  """Only the second half of the lanes is computed; the first half,
  where a lightly loaded engine puts its requests, reuses it."""
  def faulty(*args):
    logits, st = step(*args)
    h = logits.shape[0] // 2
    return jnp.concatenate([logits[h:2 * h], logits[h:]]), st
  return faulty


def test_a_sound_run_is_correct_and_the_fp8_control_is_not(tiny):
  rec = harness.run_window(tiny, 2.0, traced=False)
  ck = harness.check(tiny, rec, control=True)
  assert ck["correct"], ck
  assert not ck["control"]["correct"], ck
  served, low = ck["gaps"]["served"], ck["gaps"]["control"]
  assert served["tokens"] == low["tokens"] >= 30
  assert low["widest"] >= 3 * served["widest"]


# A field beyond the base set, which arch() passes on to the reference.
WINDOW = {"fields": {"sliding_window": "sliding_window"},
          "sliding_window": 4096}


def _with_window(s):
  conf = dict(s.cell.conf, **WINDOW)
  return dataclasses.replace(s, cell=dataclasses.replace(s.cell, conf=conf),
                             arch=harness.arch(s.cfg, conf))


def test_a_field_beyond_the_base_reaches_the_reference(tiny, monkeypatch):
  seen = []
  load = harness._load_module

  def spy(path):
    ref = load(path).Reference
    return types.SimpleNamespace(
        FIELDS=("sliding_window",),
        Reference=lambda a, w, **k: seen.append((a, w)) or ref(a, w, **k))

  monkeypatch.setattr(harness, "_load_module", spy)
  s = _with_window(tiny)
  harness.gaps(s, None, [])
  (a, w), = seen
  assert a["sliding_window"] == 4096 == tiny.cfg.sliding_window
  assert w is tiny.weights


def test_a_field_the_reference_does_not_compute_is_an_error(tiny):
  s = _with_window(tiny)
  # At set-up, before any weight is drawn, and again at the check.
  with pytest.raises(ValueError, match="sliding_window"):
    harness.setup(s.cell, SEED, time.perf_counter(), impl="xla",
                  on_chip=False)
  with pytest.raises(ValueError, match="sliding_window"):
    harness.gaps(s, None, [])


@pytest.mark.parametrize("change,field", [
    (dict(WINDOW, sliding_window=1024), "sliding_window"),
    ({"fields": {"use_qk_norm": "use_qk_norm"}, "use_qk_norm": True},
     "use_qk_norm")])
def test_a_field_the_configuration_run_does_not_match_is_an_error(
    change, field):
  conf = dict(_cell("fresh").conf, **change)
  with pytest.raises(ValueError, match=field):
    harness.model_config(conf)


def test_only_tokens_at_full_budget_and_the_first_are_compared():
  req = types.SimpleNamespace(tokens=[5, 6, 7, 8], budgets=[2, 0, 2])
  assert harness.compared(req, 2).tolist() == [True, True, False, True]


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_a_fault_under_the_timed_path_is_caught(tiny, fault):
  rec = harness.run_window(tiny, 2.0, traced=False, fault=fault)
  ck = harness.check(tiny, rec)
  assert not ck["correct"], ck
