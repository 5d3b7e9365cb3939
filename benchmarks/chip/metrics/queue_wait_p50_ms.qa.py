"""Median time from a request's arrival to the dispatch of its admission
(harness stamps)."""
from yardstick import layers


def read(rec):
  return layers.queue_wait_p50_ms(rec)
