"""Share of the traced window in which no operation ran on the device."""
from yardstick import layers


def read(rec):
  return layers.idle_pct(rec)
