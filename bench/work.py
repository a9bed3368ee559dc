"""Operations and bytes the served programs need, counted from shapes.

These count the work the *operation* needs, whatever implements it, so
a later change of kernel or fusion is measured against the same work:

* a whole prefill step: every projection of every layer for ``b × S``
  tokens, causal attention over the lower triangle, the head for each
  sequence's last position; bytes are the weights read once, the K/V
  written for ``S`` positions and the logits;
* a whole decode step at write position ``p``: the projections for ``b``
  tokens, attention over ``p + 1`` cached positions, the head for every
  row; bytes are the weights, the K/V read at ``p + 1`` positions and
  written at one, and the logits;
* the attention kernels alone: the same attention work, with the bytes
  of q, k, v read and the output written.

Weights and caches are in the served dtype; logits are float32.
"""

from __future__ import annotations

from dataclasses import dataclass

from bench.reference import Dims

LOGIT_BYTES = 4


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """The least time the chip could take: the larger bound."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)


def _itemsize(dm: Dims) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dm.dtype]


def layer_params(dm: Dims) -> int:
    attn = dm.d * (dm.heads + 2 * dm.kv_heads) * dm.head_dim \
        + dm.heads * dm.head_dim * dm.d
    mlp = (3 if dm.gated else 2) * dm.d * dm.ff
    return attn + mlp


def weight_bytes(dm: Dims) -> float:
    """Layers and head as one step reads them (a tied head is the
    embedding, read once)."""
    return (dm.layers * layer_params(dm) + dm.d * dm.vocab) * _itemsize(dm)


def kv_bytes_per_position(dm: Dims) -> float:
    """K and V of one position of one sequence, all layers."""
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * _itemsize(dm)


def attention(dm: Dims, b: int, q_len: int, kv_from: int) -> Work:
    """Causal attention of ``q_len`` queries at positions
    ``kv_from .. kv_from + q_len - 1`` over every key at or before each,
    for all layers: QK^T and PV, 2 FLOPs per multiply-add."""
    keys = q_len * kv_from + q_len * (q_len + 1) // 2
    flops = 4.0 * b * dm.heads * dm.head_dim * keys * dm.layers
    it = _itemsize(dm)
    q_o = 2 * b * q_len * dm.heads * dm.head_dim * it
    kv = 2 * b * (kv_from + q_len) * dm.kv_heads * dm.head_dim * it
    return Work(flops, float((q_o + kv) * dm.layers))


def prefill(dm: Dims, b: int, s: int) -> Work:
    dense = 2.0 * b * s * dm.layers * layer_params(dm) + 2.0 * b * dm.d * dm.vocab
    att = attention(dm, b, s, 0)
    byt = weight_bytes(dm) + b * s * kv_bytes_per_position(dm) \
        + b * dm.vocab * LOGIT_BYTES
    return Work(dense + att.flops, float(byt))


def decode(dm: Dims, b: int, pos: int) -> Work:
    dense = 2.0 * b * (dm.layers * layer_params(dm) + dm.d * dm.vocab)
    att = attention(dm, b, 1, pos)
    byt = weight_bytes(dm) + b * (pos + 2) * kv_bytes_per_position(dm) \
        + b * dm.vocab * LOGIT_BYTES
    return Work(dense + att.flops, float(byt))


def flash_attention(dm: Dims, b: int, s: int) -> Work:
    return attention(dm, b, s, 0)


def decode_attention(dm: Dims, b: int, pos: int) -> Work:
    return attention(dm, b, 1, pos)


__all__ = ["Work", "attention", "decode", "decode_attention",
           "flash_attention", "kv_bytes_per_position", "layer_params",
           "prefill", "weight_bytes"]
