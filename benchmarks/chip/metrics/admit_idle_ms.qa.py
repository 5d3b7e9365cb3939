"""Device idle inside the engine's ``engine.admit`` spans, per admission
(trace)."""
from yardstick import spans


def read(rec):
  return spans.admit_idle_ms(rec)
