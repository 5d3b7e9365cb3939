"""From the profiler's trace to device time, idle time and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the reduction needs as plain data (which ``tests/`` also records):

  {"devices": [{"ops": [[name, start_ns, dur_ns], ...],
                "modules": [[name, start_ns, dur_ns], ...]}, ...],
   "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the events of a device plane's "XLA Ops" line (one per HLO
operation that ran, Pallas kernels under the kernel's own name; kept as
the operation's name and result type),
``modules`` those of its "XLA Modules" line (one per program execution,
named after the jitted function), and ``host`` the benchmark's own
annotations (names starting ``bench.``) on the host threads.  Device and
host events share the profiler's clock.

``reduce`` takes that data and the traced window (the host span named
``bench.window``) and returns, averaged over the devices used: busy
seconds (the union of the op intervals inside the window), the window's
length, time and count per op name (an operation that contains others,
such as a loop, counts only through its body) and per program, and the
device's idle time attributed to the innermost host annotation that was
open at the middle of each idle gap.
"""
from __future__ import annotations

import collections
import pathlib
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OUTSIDE = "host: no engine call open"


def load(trace_dir: str) -> Dict:
  from jax.profiler import ProfileData  # noqa: PLC0415

  paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
  if not paths:
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
  data = ProfileData.from_file(str(paths[-1]))
  out = {"devices": [], "host": []}
  for plane in data.planes:
    if DEVICE_PLANE.match(plane.name):
      dev = {"ops": [], "modules": []}
      for line in plane.lines:
        key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
        if key:
          dev[key] += [[short_name(e.name), int(e.start_ns),
                        int(e.duration_ns)] for e in line.events]
      out["devices"].append(dev)
    elif plane.name.startswith("/host:"):
      for line in plane.lines:
        out["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events if e.name.startswith("bench.")]
  return out


def short_name(hlo: str) -> str:
  """``%fusion.147 = f32[8,33792]{1,0:T(8,128)} fusion(...)`` ->
  ``fusion.147 f32[8,33792]``: the operation and the type it returns."""
  if " = " not in hlo:
    return hlo
  name, rest = hlo.split(" = ", 1)
  first = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0].rstrip(",")
  return f"{name.lstrip('%')} {first}"


def _leaves(events):
  """Events that contain no other event (a ``while`` or a call spans the
  operations of its body on the same line)."""
  ev = sorted(events, key=lambda e: (e[1], -e[2]))
  return [e for i, e in enumerate(ev)
          if i + 1 == len(ev) or ev[i + 1][1] >= e[1] + e[2]]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
  merged: List[Tuple[int, int]] = []
  for s, e in sorted(intervals):
    if merged and s <= merged[-1][1]:
      merged[-1] = (merged[-1][0], max(merged[-1][1], e))
    else:
      merged.append((s, e))
  return merged


def _clip(events, lo: int, hi: int):
  """Events that start inside [lo, hi), cut at hi."""
  return [(n, s, min(d, hi - s)) for n, s, d in events if lo <= s < hi]


def _host_names(host, times: List[float]) -> List[str]:
  """Innermost benchmark annotation open at each of the sorted times."""
  spans = sorted((s, s + d, d, n) for n, s, d in host if n != WINDOW)
  out, open_, i = [], [], 0
  for t in times:
    while i < len(spans) and spans[i][0] <= t:
      open_.append(spans[i])
      i += 1
    open_ = [sp for sp in open_ if sp[1] > t]
    out.append(min(open_, key=lambda sp: sp[2])[3] if open_ else OUTSIDE)
  return out


def reduce(trace: Dict) -> Optional[Dict]:
  """Per-window device summary; None when no device ran in the window."""
  wins = [(s, d) for n, s, d in trace["host"] if n == WINDOW]
  if not wins or not trace["devices"]:
    return None
  w0, wd = wins[-1]
  w1 = w0 + wd
  n_dev = len(trace["devices"])
  busy = 0.0
  op_time: Dict[str, float] = collections.defaultdict(float)
  op_count: Dict[str, int] = collections.defaultdict(int)
  mod_time: Dict[str, float] = collections.defaultdict(float)
  mod_count: Dict[str, int] = collections.defaultdict(int)
  idle_by: Dict[str, float] = collections.defaultdict(float)
  for dev in trace["devices"]:
    ops = _clip(dev["ops"], w0, w1)
    for name, _, d in _leaves(ops):
      op_time[name] += d / 1e9 / n_dev
      op_count[name] += 1
    for name, _, d in _clip(dev["modules"], w0, w1):
      mod_time[name] += d / 1e9 / n_dev
      mod_count[name] += 1
    spans = _union([(s, s + d) for _, s, d in ops])
    busy += sum(e - s for s, e in spans) / 1e9 / n_dev
    edges = [w0] + [t for s, e in spans for t in (s, e)] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    names = _host_names(trace["host"], [(a + b) / 2 for a, b in gaps])
    for (a, b), name in zip(gaps, names):
      idle_by[name] += (b - a) / 1e9 / n_dev
  if busy <= 0.0:
    return None
  return {"busy_s": busy, "window_s": wd / 1e9,
          "op_time": dict(op_time), "op_count": dict(op_count),
          "module_time": dict(mod_time), "module_count": dict(mod_count),
          "idle_by_host": dict(idle_by)}


def top(table: Dict[str, float], n: int = 10) -> List[List]:
  return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def matching(table: Dict[str, float], pattern: str) -> float:
  """Sum of the entries whose name contains ``pattern``."""
  return sum(v for k, v in table.items() if pattern in k)
