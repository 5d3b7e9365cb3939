"""The wall-clock accounting, on a stub engine whose clock the test sets."""
import types

import pytest

from yardstick import wallclock


class StubEngine:
  """Has what the wall clock needs, and nothing of the real engine."""

  def __init__(self):
    self.events, self.completed, self.step_log = [], [], []
    self.now_ms = 0.0
    self.run = self.reset = self._decode_step = None
    self._admit = self._admit_overlapped = None

  def _dispatch_admission(self, req, slot, cache):
    return ("first", cache)


class FakeTime:
  def __init__(self):
    self.t = 100.0

  def perf_counter(self):
    return self.t


@pytest.fixture
def clocked(monkeypatch):
  fake = FakeTime()
  monkeypatch.setattr(wallclock, "time", fake)
  eng = StubEngine()
  reqs = {0: types.SimpleNamespace(rid=0, admit_wall_ms=40.0),
          1: types.SimpleNamespace(rid=1, admit_wall_ms=0.0)}
  clock = wallclock.WallClock(eng, reqs)
  clock.start()
  return eng, clock, fake, reqs


def test_busy_time_is_wall_and_skipped_idle_is_added(clocked):
  eng, clock, fake, reqs = clocked
  # The engine jumps its clock over 500 ms of idle to the first arrival.
  eng.now_ms = 500.0
  eng._dispatch_admission(reqs[0], 0, "cache")
  assert clock.dispatch_w[0] == pytest.approx(500.0)
  # A serial admission: the engine measured 40 ms, the wall took 45.
  fake.t += 0.045
  eng.now_ms += 40.0
  eng.events.append(("admit", 0, 0, eng.now_ms))
  assert clock.admit_w[0] == pytest.approx(545.0)
  # A decode step the engine measured at 20 ms; 3 ms of host time around
  # it never reached the engine's clock, and counts here.
  fake.t += 0.023
  eng.now_ms += 20.0
  eng.step_log.append((64, 20.0, 1))
  assert clock.step_w == [pytest.approx(568.0)]
  eng.completed.append(reqs[0])
  eng.events.append(("retire", 0, 0, eng.now_ms))
  assert clock.retire_w[0] == pytest.approx(568.0)
  # Idle again: the engine jumps to 2000 ms; the wall moves 1 ms.
  fake.t += 0.001
  eng.now_ms = 2000.0
  eng._dispatch_admission(reqs[1], 0, "cache")
  assert clock.dispatch_w[1] == pytest.approx(569.0 + (2000.0 - 560.0))


def test_overlapped_admission_adds_no_busy_time_of_its_own(clocked):
  eng, clock, fake, reqs = clocked
  fake.t += 0.030
  eng.now_ms += 25.0                  # the step's measured time covers it
  eng.step_log.append((8, 25.0, 2))
  eng.events.append(("admit", 1, 1, eng.now_ms))
  assert clock.admit_w[1] == pytest.approx(30.0)
  assert clock.busy_ms == pytest.approx(25.0)


def test_close_keeps_the_end_and_drops_the_engine(clocked):
  eng, clock, fake, _ = clocked
  fake.t += 2.0
  clock.close()
  assert clock.end_w == pytest.approx(2000.0) and clock.engine is None


def test_an_engine_without_the_lists_is_refused():
  eng = StubEngine()
  del eng.step_log
  with pytest.raises(AttributeError, match="step_log"):
    wallclock.WallClock(eng, {})


def test_an_unknown_event_is_refused(clocked):
  eng, _, _, _ = clocked
  with pytest.raises(ValueError, match="shed"):
    eng.events.append(("shed", 0, -1, 0.0))
