"""Model operations of the traced run's work over its window times the chip's
bf16 peak."""
from yardstick import layers


def read(rec):
  return layers.mfu_pct(rec)
