"""The seeded traffic: a seed repeats its requests exactly, and every seed
gets the same work in another order."""
import collections

import numpy as np
import pytest

from yardstick import traffic

# The benchmark's mix, and the same with a fresh prompt per request.
MIXES = ("corpora", "fresh")
BIG = 2 ** 31 + 12345


def _small(kind):
  mix = traffic.load_mix("corpus-qa-32k")
  mix["prompt"] = dict(mix["prompt"], tokens=64)
  if kind == "fresh":
    mix["prompt"] = {"kind": "fresh", "tokens": 64}
  return mix


@pytest.mark.parametrize("name", MIXES)
def test_a_seed_repeats_exactly(name):
  mix = _small(name)
  a = traffic.generate(mix, 1000, BIG, 20.0)
  b = traffic.generate(mix, 1000, BIG, 20.0)
  assert [(r.rid, r.arrival_ms, r.corpus, r.out_tokens) for r in a] == \
      [(r.rid, r.arrival_ms, r.corpus, r.out_tokens) for r in b]
  assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work(name):
  mix = _small(name)
  a = traffic.generate(mix, 1000, 7, 20.0)
  b = traffic.generate(mix, 1000, BIG, 20.0)
  assert len(a) == len(b) == round(mix["rate_per_s"] * 20.0)
  assert sorted(r.out_tokens for r in a) == sorted(r.out_tokens for r in b)
  assert collections.Counter(r.corpus for r in a) == \
      collections.Counter(r.corpus for r in b)
  gaps = [np.sort(np.diff([0.0] + [r.arrival_ms for r in x])) for x in (a, b)]
  np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
  assert [r.arrival_ms for r in a] != [r.arrival_ms for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_requests_fit_the_window_and_the_mix(name):
  mix = _small(name)
  reqs = traffic.generate(mix, 1000, 3, 20.0)
  t = [r.arrival_ms for r in reqs]
  assert t == sorted(t) and 0.0 < t[0] and t[-1] < 20000.0
  lo, hi = mix["output_tokens"]["min"], mix["output_tokens"]["max"]
  assert {r.out_tokens for r in reqs} <= set(range(lo, hi + 1))
  assert all(r.prompt.shape == (64,) and r.prompt.max() < 1000
             for r in reqs)


def test_corpus_popularity_follows_zipf():
  mix = _small("corpora")
  reqs = traffic.generate(mix, 1000, 5, 50.0)
  counts = collections.Counter(r.corpus for r in reqs)
  w = np.arange(1, mix["prompt"]["corpora"] + 1) ** -mix["prompt"]["zipf_alpha"]
  want = len(reqs) * w / w.sum()
  assert all(abs(counts[i] - want[i]) < 1.0 for i in range(len(w)))
  pool = traffic.corpora(mix, 1000, 5)
  assert all(np.array_equal(r.prompt, pool[r.corpus]) for r in reqs)
