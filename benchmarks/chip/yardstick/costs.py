"""Operations and bytes that the served work needs, from its shapes.

Closed forms after the program's ``analysis/costmodel.py`` (a matrix
product of M x K by K x N is 2MKN operations; bf16 values are 2 bytes),
kept here so that the yardstick does not move with the program.  ``a`` is
a configuration's architecture: d_model, n_heads, n_kv_heads, head_dim,
d_ff, vocab, n_layers, cluster_size, recent.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def token_matmul_flops(a: Dict) -> float:
  """Projections and feed-forward of every layer, and the unembedding,
  for one token."""
  d, hd = a["d_model"], a["head_dim"]
  per_layer = 2.0 * (d * hd * (2 * a["n_heads"] + 2 * a["n_kv_heads"])
                     + 3 * d * a["d_ff"])
  return a["n_layers"] * per_layer + 2.0 * d * a["vocab"]


def synopsis_rows(a: Dict, M: int, budget: int) -> int:
  """Key rows one decode query attends to per layer: M centroids, the
  budget's clusters of C tokens, the recent ring and the token itself."""
  return M + budget * a["cluster_size"] + a["recent"] + 1


def decode_token_flops(a: Dict, M: int, budget: int) -> float:
  """One served token of a decode step at ``budget``."""
  attn = 4.0 * a["n_heads"] * a["head_dim"] * synopsis_rows(a, M, budget)
  return token_matmul_flops(a) + a["n_layers"] * attn


def prefill_flops(a: Dict, S: int) -> float:
  """A prompt of S tokens: every layer over every token, causal
  attention, and the logits of the last token."""
  d, hd = a["d_model"], a["head_dim"]
  per_tok = 2.0 * (d * hd * (2 * a["n_heads"] + 2 * a["n_kv_heads"])
                   + 3 * d * a["d_ff"])
  attn = flash_prefill(a, S)["flops"]
  return a["n_layers"] * (S * per_tok + attn) + 2.0 * d * a["vocab"]


def flash_prefill(a: Dict, S: int) -> Dict[str, float]:
  """One layer's causal prefill attention for one prompt: scores and
  values over the S(S+1)/2 visible pairs; q, k, v read and o written."""
  H, Hkv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
  pairs = S * (S + 1) / 2.0
  return {"flops": 4.0 * H * hd * pairs,
          "bytes": BF16 * S * hd * (2 * H + 2 * Hkv)}


def block_gather_attention(a: Dict, B: int, budget: int) -> Dict[str, float]:
  """One layer's stage 2 for B lanes: exact attention over the budget's
  clusters, the recent ring and the token itself, per KV head; reads
  those key and value rows and the queries, writes f32 partials."""
  H, Hkv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
  rows = budget * a["cluster_size"] + a["recent"] + 1
  return {"flops": 4.0 * B * H * hd * rows,
          "bytes": BF16 * B * Hkv * rows * hd * 2 + BF16 * B * H * hd
                   + 4.0 * B * H * (hd + 2)}


def roofline_s(cost: Dict[str, float], peak: Dict[str, float]) -> float:
  """The least time the chip could take: the larger of operations over
  peak bf16 FLOP/s and bytes over peak HBM bytes/s."""
  return max(cost["flops"] / peak["bf16_flops"],
             cost["bytes"] / peak["hbm_bytes_per_s"])
