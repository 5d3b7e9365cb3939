"""Median host time the engine's clock had not counted when it dispatched
a request's admission (engine counter ``EngineRequest.clock_lag_ms``)."""
from yardstick import spans


def read(rec):
  return spans.clock_lag_p50_ms(rec)
