"""Device idle inside the engine's ``engine.decode_step`` spans, per step
(trace)."""
from yardstick import spans


def read(rec):
  return spans.step_idle_ms(rec)
