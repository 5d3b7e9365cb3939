"""The reduction from the profiler's trace to device busy time, idle gaps
and per-op time: on a hand-made trace with known answers, and on a small
trace recorded on a TPU v5e (``data/trace_small.json``)."""
import json
import pathlib

import numpy as np
import pytest

from yardstick import trace

DATA = pathlib.Path(__file__).parent / "data" / "trace_small.json"
MS = 1_000_000


def _handmade():
  # Window 0-10 ms.  Ops: a loop over 1-4 ms whose body runs 1-2 ms and
  # 2-4 ms, one at 6-7 ms, and one that starts before the window (not
  # counted).  The host is inside bench.decode_step from 0.5 to 4.5 ms
  # and bench.admit from 5 to 9 ms.
  return {"devices": [{
      "ops": [["while.2 (s32[])", 1 * MS, 3 * MS], ["fusion.1 f32[8]", 1 * MS, MS],
              ["block_gather_attention.3 f32[8,8,4,128]", 2 * MS, 2 * MS],
              ["fusion.1 f32[8]", 6 * MS, 1 * MS], ["early", -MS, MS // 2]],
      "modules": [["jit_serve_step(1)", 1 * MS, 3 * MS]]}],
      "host": [["bench.window", 0, 10 * MS],
               ["bench.decode_step", MS // 2, 4 * MS],
               ["bench.admit", 5 * MS, 4 * MS]]}


def test_handmade_trace():
  r = trace.reduce(_handmade())
  assert r["window_s"] == pytest.approx(0.010)
  assert r["busy_s"] == pytest.approx(0.004)        # 1-4 ms and 6-7 ms
  assert r["op_time"] == pytest.approx({
      "fusion.1 f32[8]": 0.002,
      "block_gather_attention.3 f32[8,8,4,128]": 0.002})
  assert r["op_count"] == {"fusion.1 f32[8]": 2,
                           "block_gather_attention.3 f32[8,8,4,128]": 1}
  assert r["module_time"] == pytest.approx({"jit_serve_step(1)": 0.003})
  # Idle: 0-1 ms (in decode_step), 4-6 ms (midpoint 5 ms: admit),
  # 7-10 ms (midpoint 8.5 ms: admit).
  assert r["idle_by_host"] == pytest.approx({"bench.decode_step": 0.001,
                                             "bench.admit": 0.005})
  assert trace.matching(r["op_time"], "gather") == pytest.approx(0.002)


def test_no_window_or_no_device_reads_nothing():
  t = _handmade()
  assert trace.reduce({**t, "host": t["host"][1:]}) is None
  assert trace.reduce({**t, "devices": []}) is None


def _busy_by_timeline(ops, w0, w1, step_ns=1000):
  """Busy time by brute force on a 1 us grid."""
  grid = np.zeros((w1 - w0) // step_ns + 1, bool)
  for _, s, d in ops:
    if w0 <= s < w1:
      grid[(s - w0) // step_ns:(min(s + d, w1) - w0) // step_ns] = True
  return grid.sum() * step_ns / 1e9


def test_recorded_trace():
  t = json.loads(DATA.read_text())
  r = trace.reduce(t)
  w = [(s, d) for n, s, d in t["host"] if n == trace.WINDOW][0]
  ops = t["devices"][0]["ops"]
  assert r["busy_s"] == pytest.approx(
      _busy_by_timeline(ops, w[0], w[0] + w[1]), abs=2e-5)
  assert 0.0 < r["busy_s"] < r["window_s"]
  assert sum(r["idle_by_host"].values()) == pytest.approx(
      r["window_s"] - r["busy_s"], rel=1e-6)
  # Leaves only: the ops' time adds up to no more than the busy time.
  assert sum(r["op_time"].values()) <= r["busy_s"] * (1 + 1e-9)
  assert trace.matching(r["op_time"], "block_gather_attention") > 0.0
  assert any("serve_step" in k for k in r["module_time"])


def test_short_names():
  assert trace.short_name(
      "%fusion.147 = f32[8,33792]{1,0:T(8,128)S(1)} fusion(bf16[3] %a)") \
      == "fusion.147 f32[8,33792]"
  assert trace.short_name("%flash_prefill.6 = bf16[1,8,2048,12,128]{4,3} "
                          "custom-call(...)") == \
      "flash_prefill.6 bf16[1,8,2048,12,128]"
  assert trace.short_name("jit_serve_step(123)") == "jit_serve_step(123)"
