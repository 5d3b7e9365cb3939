"""Stage 2's kernel: least time its calls need at v5e peaks over the device
time they took (trace)."""
from yardstick import layers


def read(rec):
  return layers.kernel_roofline_pct(rec, "block_gather_attention",
                                   layers.block_gather_calls)
