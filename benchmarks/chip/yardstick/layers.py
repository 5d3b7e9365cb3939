"""Reductions behind the per-layer metrics; each ``metrics/<name>.py``
calls one of these.  A reduction that finds nothing to read returns None,
and the harness then leaves the metric out of the line.
"""
from __future__ import annotations

from typing import Optional

from yardstick import costs, stats, trace

# Programs of the served path, by the jitted function's name as the
# trace's "XLA Modules" line shows it.
SERVE_STEP = "jit_serve_step"


def _window_steps(rec):
  """Step-log entries that ended inside the window."""
  T = rec.seconds * 1e3
  return [s for s, w in zip(rec.steps, rec.clock.step_w) if w <= T]


def queue_wait_p50_ms(rec) -> Optional[float]:
  """Median wait from arrival to the dispatch of the admission."""
  waits = [rec.clock.dispatch_w[r.rid] - r.arrival_ms
           for r in rec.requests if r.rid in rec.clock.dispatch_w]
  return stats.percentile(waits, 50) if waits else None


def refined_pct(rec) -> Optional[float]:
  """Clusters refined over clusters ranked, over every token the window's
  decode steps served."""
  steps = _window_steps(rec)
  avail = sum(rec.M * a for _, _, a in steps)
  if not avail:
    return None
  return 100.0 * sum(min(b, rec.M) * a for b, _, a in steps) / avail


def module_ms(rec, names) -> Optional[float]:
  """Device time per execution of the programs whose names contain one
  of ``names``."""
  t = rec.trace
  if t is None:
    return None
  hit = [k for k in t["module_time"] if any(n in k for n in names)]
  count = sum(t["module_count"][k] for k in hit)
  if not count:
    return None
  return 1e3 * sum(t["module_time"][k] for k in hit) / count


def kernel_roofline_pct(rec, kernel: str, cost_fn) -> Optional[float]:
  """Least time the kernel's calls need over the time they took."""
  t = rec.trace
  if t is None or not rec.peak:
    return None
  took = trace.matching(t["op_time"], kernel)
  calls = [c for c in cost_fn(rec)]
  if not took or not calls:
    return None
  need = sum(costs.roofline_s(c, rec.peak) for c in calls)
  return 100.0 * need / took


def block_gather_calls(rec):
  """One call per layer of every decode step in the traced run."""
  for b, _, _ in rec.steps:
    for _ in range(rec.arch["n_layers"]):
      yield costs.block_gather_attention(rec.arch, rec.n_slots, b)


def mfu_pct(rec) -> Optional[float]:
  """Model operations of the traced run's work (the tokens every decode
  step served at its budget, and every prefill) over the traced window's
  length times the chip's peak."""
  t = rec.trace
  if t is None or not rec.peak:
    return None
  S = rec.requests[0].prompt.shape[0]
  flops = sum(a * costs.decode_token_flops(rec.arch, rec.M, b)
              for b, _, a in rec.steps)
  flops += rec.prefills * costs.prefill_flops(rec.arch, S)
  return 100.0 * flops / (t["window_s"] * rec.peak["bf16_flops"])


def idle_pct(rec) -> Optional[float]:
  t = rec.trace
  if t is None:
    return None
  return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
