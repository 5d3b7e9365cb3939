#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

from the root of a checkout, on a machine with a TPU.  It draws the
weights and the traffic from ``--seed``, builds ``ServingEngine`` with
Pallas kernels and warms every program the cell's traffic uses (set-up),
offers the traffic open-loop for ``--seconds`` and waits for every
request, then checks the served tokens against the plain reference.

``--trace 0`` reports the cell's end-to-end metrics (``BENCHMARK.json``),
read on the harness's wall clock; ``--trace 1`` runs under the profiler
and reports the per-layer metrics, the device's busy time and a
breakdown.

The numbers compared and their limits are the last lines of standard
error; the last line of standard output is one JSON object.  Without a
TPU, with fewer chips than the cell asks for, or with kernels that do not
resolve to Pallas, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
  p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  p.add_argument("--workload", required=True)
  p.add_argument("--seed", type=int, required=True)
  p.add_argument("--seconds", type=float, required=True)
  p.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = p.parse_args(argv)
  if args.seed < 0:
    p.error("--seed must be a whole number >= 0")

  from yardstick import harness  # noqa: PLC0415

  cell = harness.load_cell(args.workload)
  try:
    s = harness.setup(cell, args.seed, T_START)
  except harness.NoChip as e:
    print(f"run.py: {e}", file=sys.stderr)
    return 2
  rec = harness.run_window(s, args.seconds, traced=bool(args.trace))

  import jax  # noqa: PLC0415

  devs = jax.devices()
  device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs[:cell.chips])}
  if args.trace:
    if rec.trace is None:
      print("run.py: the trace shows no device operation", file=sys.stderr)
      return 3
    device["busy_s"] = rec.trace["busy_s"]
    device["window_s"] = rec.trace["window_s"]
    metrics = harness.per_layer(rec, cell.per_layer)
  else:
    e2e = harness.end_to_end(rec, s.setup_s)
    metrics = {k: e2e[k] for k in cell.end_to_end}
  result = {"attempted": len(rec.requests)}
  harness.free_engine(s)
  ck = harness.check(s, rec)
  result.update({
      "correct": ck["correct"],
      "failed": int(ck["numbers"]["requests_short"][0]),
      "metrics": metrics, "device": device})
  if args.trace:
    result["breakdown"] = harness.breakdown(rec)
  result["compared"] = {"requests": ck["sampled_requests"],
                        **ck["gaps"]["served"]}
  result["checked"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in ck["numbers"].items()}
  harness.report_numbers(ck["numbers"])
  print(json.dumps(result))
  return 0


if __name__ == "__main__":
  sys.exit(main())
