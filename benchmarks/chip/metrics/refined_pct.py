"""Clusters refined over clusters ranked, over every token the window's
decode steps served (engine step log)."""
from yardstick import layers


def read(rec):
  return layers.refined_pct(rec)
