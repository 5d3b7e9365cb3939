"""Device time per execution of the serve-step program (trace)."""
from yardstick import layers


def read(rec):
  return layers.module_ms(rec, (layers.SERVE_STEP,))
