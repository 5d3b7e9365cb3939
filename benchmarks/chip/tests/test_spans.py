"""The reduction from the engine's spans in the profiler's trace to device
idle per decode step and per admission, and the engine's clock lag: on a
hand-made trace with known answers, and on a small trace recorded on a
TPU v5e (``data/trace_spans_small.json``: the device operations and the
``engine.*`` and ``bench.*`` host events of a ``--trace 1`` run of
``nemo12b-corpus-qa`` that start between an overlapped admission in the
middle of the window and the end of the third decode step after it, with
``bench.window`` set to that stretch)."""
import json
import pathlib
import types

import numpy as np
import pytest

from yardstick import spans, trace

DATA = pathlib.Path(__file__).parent / "data" / "trace_spans_small.json"
MS = 1_000_000


def _handmade(n_devices=1):
  # Window 0-20 ms.  Device ops at 2-5 ms (with one inside it), 7-8 ms
  # and 12-16 ms; one ends before the window.  Host: an overlapped
  # admission of rid 1 (its dispatch at 0.5-1.5 ms, its bookkeeping at
  # 9-10 ms) around step 0 (1.5-9 ms: dispatch, sync, then tokens with a
  # retire inside); step 1 at 11-17 ms; a serial admission of rid 2 at
  # 17-19.5 ms with its slot write at 17-18 ms; step 2 from 19.5 ms, cut
  # at the window's end; a step before the window.
  ops = [["fusion.1 f32[8]", 2 * MS, 3 * MS], ["fusion.2 f32[8]", 3 * MS, MS],
         ["fusion.1 f32[8]", 7 * MS, MS], ["copy.3 bf16[4]", 12 * MS, 4 * MS],
         ["early", -MS, MS // 2]]
  return {"devices": [{"ops": ops}] * n_devices, "host": [
      ["bench.window", 0, 20 * MS, {}],
      ["engine.decode_step", -2 * MS, MS, {"step": 9}],
      ["engine.admit", MS // 2, MS, {"rid": 1, "slot": 0, "overlapped": 1}],
      ["engine.decode_step", 3 * MS // 2, 15 * MS // 2, {"step": 0}],
      ["engine.step.dispatch", 3 * MS // 2, MS, {"budget": 8, "active": 1}],
      ["engine.step.sync", 5 * MS // 2, 6 * MS, {}],
      ["engine.step.tokens", 17 * MS // 2, MS // 2, {}],
      ["engine.retire", 87 * MS // 10, MS // 5, {"rid": 0, "slot": 1}],
      ["engine.admit", 9 * MS, MS, {"rid": 1, "slot": 0, "overlapped": 1}],
      ["engine.decode_step", 11 * MS, 6 * MS, {"step": 1}],
      ["engine.admit", 17 * MS, 5 * MS // 2,
       {"rid": 2, "slot": 1, "overlapped": 0}],
      ["engine.admit.write", 17 * MS, MS, {}],
      ["engine.decode_step", 39 * MS // 2, 5 * MS // 2, {"step": 2}]]}


def _traced_rec():
  """A traced run, as the metrics see it: ``rec.trace`` is set."""
  return types.SimpleNamespace(trace={"busy_s": 1.0}, served={})


@pytest.mark.parametrize("n_devices", [1, 2])
def test_handmade_idle_under_spans(n_devices):
  t = _handmade(n_devices)
  # Steps: 7.5 ms with 4 busy, 6 ms with 4 busy, 0.5 ms (cut) idle.
  idle, found = spans.idle_under(t, spans.DECODE_STEP)
  assert idle == pytest.approx(3.5 + 2.0 + 0.5)
  assert [st["step"] for st in found] == [0, 1, 2]
  # Admissions: rid 1 idle 1 + 1 ms, rid 2 idle 2.5 ms.
  idle, found = spans.idle_under(t, spans.ADMIT)
  assert idle == pytest.approx(4.5)
  assert sorted({st["rid"] for st in found}) == [1, 2]


def test_handmade_by_span():
  r = spans.by_span(_handmade())
  assert r["engine.decode_step"] == pytest.approx(
      {"count": 3, "host_s": 0.014, "self_s": 0.0065, "busy_s": 0.004,
       "idle_s": 0.0025})
  assert r["engine.step.sync"] == pytest.approx(
      {"count": 1, "host_s": 0.006, "self_s": 0.006, "busy_s": 0.0035,
       "idle_s": 0.0025})
  assert r["engine.step.tokens"] == pytest.approx(
      {"count": 1, "host_s": 0.0005, "self_s": 0.0003, "busy_s": 0.0,
       "idle_s": 0.0003})
  assert r["engine.retire"]["idle_s"] == pytest.approx(0.0002)
  assert r["engine.admit"] == pytest.approx(
      {"count": 3, "host_s": 0.0045, "self_s": 0.0035, "busy_s": 0.0,
       "idle_s": 0.0035})
  # Innermost attribution splits the window's idle among the spans and
  # the time under none (0-0.5 ms, 10-11 ms).
  idle = sum(v["idle_s"] for v in r.values())
  assert idle + 0.0015 == pytest.approx(0.020 - 0.008)


def test_metrics_read_the_handmade_trace(monkeypatch):
  t = _handmade()
  monkeypatch.setattr(spans, "_traced", lambda rec: t)
  assert spans.step_idle_ms(_traced_rec()) == pytest.approx(6.0 / 3)
  assert spans.admit_idle_ms(_traced_rec()) == pytest.approx(4.5 / 2)


def test_nothing_to_read_gives_none(monkeypatch):
  t = _handmade()
  assert spans.idle_under({**t, "host": t["host"][1:]}, spans.ADMIT) is None
  assert spans.idle_under({**t, "devices": []}, spans.ADMIT) is None
  assert spans.by_span({**t, "host": t["host"][1:]}) is None
  # A program without the engine's spans: the trace has only the window.
  bare = {**t, "host": t["host"][:1]}
  monkeypatch.setattr(spans, "_traced", lambda rec: bare)
  assert spans.step_idle_ms(_traced_rec()) is None
  assert spans.admit_idle_ms(_traced_rec()) is None
  assert spans.by_span(bare) is None
  # An untraced run reads no trace.
  untraced = types.SimpleNamespace(trace=None, served={})
  monkeypatch.undo()
  assert spans.step_idle_ms(untraced) is None
  assert spans.admit_idle_ms(untraced) is None


def test_clock_lag_median_over_admitted_requests():
  served = {i: types.SimpleNamespace(dispatch_w_ms=w, clock_lag_ms=lag)
            for i, (w, lag) in enumerate([(10.0, 3.0), (20.0, 5.0),
                                          (-1.0, -1.0), (40.0, 9.0)])}
  rec = types.SimpleNamespace(trace=None, served=served)
  assert spans.clock_lag_p50_ms(rec) == pytest.approx(5.0)
  # Requests from a program without the counter, or none admitted.
  old = {0: types.SimpleNamespace(rid=0)}
  assert spans.clock_lag_p50_ms(types.SimpleNamespace(served=old)) is None
  assert spans.clock_lag_p50_ms(types.SimpleNamespace(served={})) is None


def _idle_by_timeline(ops, intervals, w0, w1, step_ns=1000):
  """Idle time inside the intervals by brute force on a 1 us grid."""
  busy = np.zeros((w1 - w0) // step_ns + 1, bool)
  for _, s, d in ops:
    if w0 <= s < w1:
      busy[(s - w0) // step_ns:(min(s + d, w1) - w0) // step_ns] = True
  inside = np.zeros_like(busy)
  for s, e in intervals:
    inside[(s - w0) // step_ns:(min(e, w1) - w0) // step_ns] = True
  return (inside & ~busy).sum() * step_ns / 1e6


def test_recorded_trace():
  t = json.loads(DATA.read_text())
  w0, w1 = spans._window(t)
  ops = t["devices"][0]["ops"]
  for name in (spans.DECODE_STEP, spans.ADMIT):
    idle, found = spans.idle_under(t, name)
    assert found
    ivs = [(s, s + d) for n, s, d, _ in t["host"] if n == name and
           w0 <= s < w1]
    assert idle == pytest.approx(_idle_by_timeline(ops, ivs, w0, w1),
                                 abs=0.02)
  r = spans.by_span(t)
  assert set(r) <= set(
      n for n, _, _, _ in t["host"] if n.startswith(spans.ENGINE))
  assert {spans.DECODE_STEP, spans.ADMIT, "engine.step.sync"} <= set(r)
  # The same idle, put down to the engine's spans and to the benchmark's
  # annotations around the engine's methods (``trace.reduce``): the two
  # agree to within a tenth.
  old = trace.reduce({"devices": [{"ops": ops, "modules": []}],
                      "host": [[n, s, d] for n, s, d, _ in t["host"]
                               if n.startswith("bench.")]})
  old_idle = sum(old["idle_by_host"].get(f"bench.{n}", 0.0)
                 for n in ("decode_step", "admit_overlapped", "admit"))
  new_idle = sum(spans.idle_under(t, n)[0]
                 for n in (spans.DECODE_STEP, spans.ADMIT)) / 1e3
  assert new_idle == pytest.approx(old_idle, rel=0.1)
