"""The serving engine's host spans, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``.  With no profiler running
one costs about a microsecond, so the engine opens them always; under
``jax.profiler.start_trace`` they land in the same trace as the device's
operations, on the same clock, so each stretch of device idle time can be
put down to what the host was doing then.  There is no other output.

A span's stats are Python ints known when it opens.  None is a device
value, and opening a span never waits for the device.

``SPANS`` names every span the engine opens, the span it lies inside
(None: none), and its stats.  The spans of one request share its ``rid``.
``engine.retire`` lies inside ``engine.step.tokens`` on the decode path
and on its own where the ``partial`` policy sheds at the deadline.  An
overlapped step picks its budget before its admissions are dispatched,
outside every span; ``engine.step.budget`` times the steps that pick
their own.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax

SPANS: Dict[str, Tuple[Optional[str], Tuple[str, ...]]] = {
    # the whole ServingEngine._decode_step; ``step``: its index in the
    # window's step log
    "engine.decode_step": (None, ("step",)),
    "engine.step.budget": ("engine.decode_step", ()),
    # serve step, argmax, ring append and the masked writes, dispatched
    "engine.step.dispatch": ("engine.decode_step", ("budget", "active")),
    "engine.step.sync": ("engine.decode_step", ()),       # block_until_ready
    "engine.step.tokens": ("engine.decode_step", ()),     # readback, per slot
    "engine.retire": (None, ("rid", "slot")),
    # one admission: a serial one whole; an overlapped one twice, its
    # dispatch before the step and its bookkeeping after it
    "engine.admit": (None, ("rid", "slot", "overlapped")),
    "engine.admit.lookup": ("engine.admit", ()),          # corpus cache
    "engine.admit.extend": ("engine.admit", ()),          # delta replay
    "engine.admit.prefill": ("engine.admit", ()),
    "engine.admit.build": ("engine.admit", ()),
    "engine.admit.write": ("engine.admit", ()),           # slot write
    "engine.admit.sync": ("engine.admit", ()),            # serial only
}

# What jax.monitoring reports once for every XLA program built or fetched
# from the persistent compilation cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **stats: int) -> jax.profiler.TraceAnnotation:
  """Open the engine span ``name`` (a key of ``SPANS``) with its stats."""
  if name not in SPANS:
    raise KeyError(f"no engine span {name!r}")
  return jax.profiler.TraceAnnotation(name, **stats)
