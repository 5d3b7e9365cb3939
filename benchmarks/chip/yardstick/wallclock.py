"""Wall-clock stamps on the engine's window, as a client would see it.

``ServingEngine.run`` keeps a hybrid clock: it advances by the wall time
it measured itself around each decode step and each serial admission, and
it jumps over idle time to the next arrival instead of waiting for it.
Host time outside its measured regions never reaches that clock.  So no
end-to-end time is read from it.  Instead the engine's ``events``,
``completed`` and ``step_log`` lists are replaced by lists that stamp each
``append`` with

    W = (perf_counter() - t0) + (now_ms - busy_ms)

where ``busy_ms`` is what the engine measured (the sum of the step log's
times and of the serial admissions' walls): ``now_ms - busy_ms`` is the
idle time the engine skipped, which a real server would have spent
waiting.  W is then the time since the window opened on a server that
waits for its arrivals.  Admission dispatches are stamped the same way,
for the queue wait.  The engine has no real-time submit interface, so
this is the nearest the benchmark can come to one without editing it.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

REQUIRED = ("events", "completed", "step_log", "now_ms", "run", "reset",
            "_dispatch_admission", "_decode_step", "_admit",
            "_admit_overlapped")


class _Stamped(list):
  def __init__(self, clock: "WallClock", kind: str):
    super().__init__()
    self.clock, self.kind = clock, kind

  def append(self, item):
    super().append(item)
    self.clock._stamp(self.kind, item)


class WallClock:
  """Stamps one window of ``engine.run`` (call after ``engine.reset``).

  ``admit_w[rid]``, ``retire_w[rid]``: W of the request's first token and
  of its last; ``dispatch_w[rid]``: W when its admission was dispatched;
  ``step_w[i]``: W at the end of step i of ``engine.step_log``."""

  def __init__(self, engine, walls: Dict[int, object]):
    missing = [n for n in REQUIRED if not hasattr(engine, n)]
    if missing:
      raise AttributeError(f"engine lacks {missing}: the wall clock "
                           "cannot stamp its window")
    self.engine = engine
    self.requests = walls          # rid -> EngineRequest
    self.busy_ms = 0.0
    self.t0: Optional[float] = None
    self.end_w: Optional[float] = None
    self.admit_w: Dict[int, float] = {}
    self.retire_w: Dict[int, float] = {}
    self.dispatch_w: Dict[int, float] = {}
    self.step_w: List[float] = []
    engine.events = _Stamped(self, "event")
    engine.completed = _Stamped(self, "completed")
    engine.step_log = _Stamped(self, "step")
    inner = engine._dispatch_admission

    def dispatch(req, slot, cache):
      self.dispatch_w[req.rid] = self.now()
      return inner(req, slot, cache)

    engine._dispatch_admission = dispatch

  def start(self) -> None:
    self.t0 = time.perf_counter()

  def close(self) -> None:
    """End of the run: keep its W and let go of the engine."""
    self.end_w = self.now()
    self.engine = None

  def now(self) -> float:
    """W in ms."""
    return ((time.perf_counter() - self.t0) * 1e3
            + self.engine.now_ms - self.busy_ms)

  def _stamp(self, kind: str, item) -> None:
    if kind == "step":
      self.busy_ms += float(item[1])
      self.step_w.append(self.now())
    elif kind == "event":
      what, rid = item[0], item[1]
      if what == "admit":
        self.busy_ms += float(self.requests[rid].admit_wall_ms)
        self.admit_w[rid] = self.now()
      elif what == "retire":
        self.retire_w[rid] = self.now()
      else:
        raise ValueError(f"unexpected engine event {what!r}")
