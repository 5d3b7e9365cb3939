"""One general generator for every traffic mix: reads a mix's parameters
from ``traffic/<name>.json`` and draws the window's requests from a seed.

A mix's file holds:

  prompt          {"kind": "corpora", "tokens": S, "corpora": n,
                   "zipf_alpha": a}   requests ask about one of n shared
                  corpora of S tokens, chosen with Zipf(a) popularity; or
                  {"kind": "fresh", "tokens": S}   every request has a
                  prompt of its own.
  output_tokens   {"min": a, "max": b}   tokens served per request,
                  uniform over [a, b]: the first from admission, the rest
                  from decode steps.
  rate_per_s      open-loop arrival rate (Poisson).
  slots, policy, deadline_ms, corpus_cache   the engine's settings.
  drain           true: the run waits for every request of the window.

The work does not depend on the seed, only its order does.  Every seed
gives the same number of requests, the same multiset of inter-arrival
gaps (the exponential distribution's quantiles at the midpoints of n equal
slices), the same multiset of output lengths and the same number of
requests per corpus; the seed shuffles each of them and draws the token
ids.  So runs with different seeds differ by where the work falls, not by
how much of it there is.  Token ids come from numpy's PCG64 seeded with
the whole seed, of any size.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Request:
  """One generated request: when it is due (ms into the window), its
  prompt, which corpus it asks (-1 for a fresh prompt) and how many
  tokens it is served."""
  rid: int
  arrival_ms: float
  prompt: np.ndarray
  corpus: int
  out_tokens: int


def load_mix(name: str) -> Dict:
  path = ROOT / "traffic" / f"{name}.json"
  return json.loads(path.read_text())


def _rng(seed: int, stream: int) -> np.random.Generator:
  """Independent stream ``stream`` of a non-negative seed of any size."""
  return np.random.default_rng([int(seed), stream])


def arrivals_ms(rate_per_s: float, seconds: float,
                rng: np.random.Generator) -> np.ndarray:
  """Open-loop arrivals inside [0, seconds): n = round(rate x seconds)
  requests whose gaps are the exponential quantiles, shuffled."""
  n = max(int(round(rate_per_s * seconds)), 1)
  u = (np.arange(n) + 0.5) / n
  gaps = -np.log1p(-u) / rate_per_s * 1e3
  gaps = rng.permutation(gaps)
  t = np.cumsum(gaps)
  # The quantile gaps sum to about n / rate; scale them so the last
  # arrival falls inside the window.
  return t * (seconds * 1e3 * n / (n + 1)) / t[-1]


def out_lengths(lo: int, hi: int, n: int,
                rng: np.random.Generator) -> np.ndarray:
  """n lengths spread evenly over [lo, hi], shuffled."""
  span = hi - lo + 1
  return rng.permutation(lo + (np.arange(n) * span) // n)


def corpus_picks(n_corpora: int, alpha: float, n: int,
                 rng: np.random.Generator) -> np.ndarray:
  """Zipf(alpha) popularity as fixed counts (largest remainder), shuffled."""
  w = np.arange(1, n_corpora + 1, dtype=np.float64) ** -alpha
  share = n * w / w.sum()
  counts = np.floor(share).astype(int)
  for i in np.argsort(-(share - counts))[: n - counts.sum()]:
    counts[i] += 1
  return rng.permutation(np.repeat(np.arange(n_corpora), counts))


def corpora(mix: Dict, vocab: int, seed: int) -> List[np.ndarray]:
  """The shared corpora of a ``corpora`` mix (empty for fresh prompts)."""
  p = mix["prompt"]
  if p["kind"] != "corpora":
    return []
  rng = _rng(seed, 1)
  return [rng.integers(0, vocab, p["tokens"], dtype=np.int32)
          for _ in range(p["corpora"])]


def generate(mix: Dict, vocab: int, seed: int,
             seconds: float) -> List[Request]:
  """The window's requests, sorted by arrival."""
  t = arrivals_ms(mix["rate_per_s"], seconds, _rng(seed, 2))
  n = len(t)
  lens = out_lengths(mix["output_tokens"]["min"],
                     mix["output_tokens"]["max"], n, _rng(seed, 3))
  p = mix["prompt"]
  if p["kind"] == "corpora":
    pool = corpora(mix, vocab, seed)
    picks = corpus_picks(p["corpora"], p["zipf_alpha"], n, _rng(seed, 4))
    prompts = [pool[c] for c in picks]
  elif p["kind"] == "fresh":
    rng = _rng(seed, 5)
    picks = np.full(n, -1)
    prompts = [rng.integers(0, vocab, p["tokens"], dtype=np.int32)
               for _ in range(n)]
  else:
    raise ValueError(f"unknown prompt kind {p['kind']!r}")
  return [Request(rid=i, arrival_ms=float(t[i]), prompt=prompts[i],
                  corpus=int(picks[i]), out_tokens=int(lens[i]))
          for i in range(n)]
