"""Device idle under the engine's own spans, and the engine's clock lag.

``ServingEngine`` opens host spans named ``engine.*`` (``repro.serve.
spans``) with ``jax.profiler.TraceAnnotation``, so a traced run has them
on the profiler's clock beside the device's operations.  ``load`` reads
the ``.xplane.pb`` of that run once per file and keeps, as plain data
(which ``tests/`` also records):

  {"devices": [{"ops": [[name, start_ns, dur_ns], ...]}, ...],
   "host": [[name, start_ns, dur_ns, {stat: value}], ...]}

the events of each device plane's "XLA Ops" line, and the host events
named ``engine.*``, with their stats, and ``bench.window``.  The window
is the last ``bench.window``; a span counts if it starts inside it, and
is cut at its end, as ``trace.reduce`` counts operations.  Device idle is
the time inside an interval that no operation covers, averaged over the
devices.  A reduction that finds nothing to read returns None.
"""
from __future__ import annotations

import collections
import functools
import pathlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from yardstick import stats, trace

ENGINE = "engine."
DECODE_STEP = "engine.decode_step"
ADMIT = "engine.admit"


def load(trace_dir: str) -> Optional[Dict]:
  """The newest ``.xplane.pb`` under ``trace_dir``, read once."""
  paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
  if not paths:
    return None
  st = paths[-1].stat()
  return _read(str(paths[-1]), st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=1)
def _read(path: str, _mtime_ns: int, _size: int) -> Dict:
  from jax.profiler import ProfileData  # noqa: PLC0415

  data = ProfileData.from_file(path)
  out = {"devices": [], "host": []}
  for plane in data.planes:
    if trace.DEVICE_PLANE.match(plane.name):
      out["devices"].append({"ops": [
          [trace.short_name(e.name), int(e.start_ns), int(e.duration_ns)]
          for line in plane.lines if line.name == trace.OPS_LINE
          for e in line.events]})
    elif plane.name.startswith("/host:"):
      for line in plane.lines:
        out["host"] += [
            [e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats)]
            for e in line.events
            if e.name.startswith(ENGINE) or e.name == trace.WINDOW]
  return out


def _window(t: Dict) -> Optional[Tuple[int, int]]:
  wins = [(s, s + d) for n, s, d, _ in t["host"] if n == trace.WINDOW]
  return wins[-1] if wins and t["devices"] else None


class _Busy:
  """Device busy time inside any interval of the window, per device:
  the union of the operations that start in the window, summed up to a
  point by binary search."""

  def __init__(self, t: Dict, w0: int, w1: int):
    self.devices = []
    for dev in t["devices"]:
      u = trace._union([(s, s + d) for _, s, d in
                        trace._clip(dev["ops"], w0, w1)])
      starts = np.asarray([s for s, _ in u], np.int64)
      ends = np.asarray([e for _, e in u], np.int64)
      before = np.concatenate([[0], np.cumsum(ends - starts)])
      self.devices.append((starts, ends, before))

  def _upto(self, dev, x: np.ndarray) -> np.ndarray:
    starts, ends, before = dev
    if not len(starts):
      return np.zeros(x.shape, np.int64)
    i = np.searchsorted(starts, x, side="right")
    j = np.maximum(i - 1, 0)
    part = np.clip(np.minimum(x, ends[j]) - starts[j], 0, None)
    return np.where(i > 0, before[j] + part, 0)

  def busy(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """ns busy inside each [a, b), averaged over the devices."""
    return sum(self._upto(d, b) - self._upto(d, a)
               for d in self.devices) / len(self.devices)


def _spans(t: Dict, name: str, w0: int, w1: int):
  """(start, dur, stats) of the spans ``name`` in the window."""
  host = t["host"]
  mine = [(i, e[1], e[2]) for i, e in enumerate(host) if e[0] == name]
  return [(s, d, host[i][3]) for i, s, d in trace._clip(mine, w0, w1)]


def idle_under(t: Dict, name: str) -> Optional[Tuple[float, List[Dict]]]:
  """Device idle ms inside the spans ``name`` of the window, and the
  stats of those spans; None when the window holds none."""
  win = _window(t)
  if win is None:
    return None
  found = _spans(t, name, *win)
  if not found:
    return None
  u = trace._union([(s, s + d) for s, d, _ in found])
  a = np.asarray([s for s, _ in u], np.int64)
  b = np.asarray([e for _, e in u], np.int64)
  idle = float(np.sum(b - a) - np.sum(_Busy(t, *win).busy(a, b)))
  return idle / 1e6, [st for _, _, st in found]


def by_span(t: Dict) -> Optional[Dict[str, Dict[str, float]]]:
  """Per span name over the window: how many, host seconds inside them,
  host seconds outside their child spans (self), and the device's busy
  and idle seconds while each was the innermost span open."""
  win = _window(t)
  if win is None:
    return None
  w0, w1 = win
  ev = sorted(((s, min(s + d, w1), n) for n, s, d, _ in t["host"]
               if n.startswith(ENGINE) and w0 <= s < w1),
              key=lambda e: (e[0], -e[1]))
  if not ev:
    return None
  a = np.asarray([s for s, _, _ in ev], np.int64)
  b = np.asarray([e for _, e, _ in ev], np.int64)
  busy = _Busy(t, w0, w1).busy(a, b)
  host = (b - a).astype(np.float64)
  own_host, own_busy = host.copy(), busy.copy()
  stack: List[int] = []
  for i, (s, e, _) in enumerate(ev):
    while stack and ev[stack[-1]][1] <= s:
      stack.pop()
    if stack and e <= ev[stack[-1]][1]:
      own_host[stack[-1]] -= host[i]
      own_busy[stack[-1]] -= busy[i]
    stack.append(i)
  out: Dict[str, Dict[str, float]] = collections.defaultdict(
      lambda: {"count": 0, "host_s": 0.0, "self_s": 0.0, "busy_s": 0.0,
               "idle_s": 0.0})
  for i, (_, _, n) in enumerate(ev):
    o = out[n]
    o["count"] += 1
    o["host_s"] += host[i] / 1e9
    o["self_s"] += own_host[i] / 1e9
    o["busy_s"] += own_busy[i] / 1e9
    o["idle_s"] += (own_host[i] - own_busy[i]) / 1e9
  return dict(out)


# -- the per-layer metrics ---------------------------------------------------

def _traced(rec) -> Optional[Dict]:
  if rec.trace is None:
    return None
  from yardstick import harness  # noqa: PLC0415

  return load(str(harness.TRACE_DIR))


def step_idle_ms(rec) -> Optional[float]:
  """Device idle per decode step: inside the window's
  ``engine.decode_step`` spans, over their count."""
  t = _traced(rec)
  got = idle_under(t, DECODE_STEP) if t is not None else None
  if got is None:
    return None
  idle_ms, found = got
  return idle_ms / len(found)


def admit_idle_ms(rec) -> Optional[float]:
  """Device idle per admission: inside the window's ``engine.admit``
  spans, over the admissions they belong to (an overlapped admission
  opens two, its dispatch and its bookkeeping, under one ``rid``)."""
  t = _traced(rec)
  got = idle_under(t, ADMIT) if t is not None else None
  if got is None:
    return None
  idle_ms, found = got
  return idle_ms / len({st["rid"] for st in found})


def clock_lag_p50_ms(rec) -> Optional[float]:
  """Median, over the window's admitted requests, of the host time the
  engine's clock had not counted when it dispatched the admission
  (``EngineRequest.clock_lag_ms``)."""
  lags = [r.clock_lag_ms for r in rec.served.values()
          if getattr(r, "dispatch_w_ms", -1.0) >= 0.0]
  return stats.percentile(lags, 50) if lags else None
