"""Model operations per token, counted from the configuration's shapes.

What the algorithm needs, whatever implements it: every matmul parameter
costs 2 operations a token forward and 6 forward and backward; attention
costs 4·d operations per (query head, attended key) forward and 8·d more
backward (dV, dP, dQ, dK; the recomputed scores are not counted); MoBA's
routing costs 2·d per (query head, earlier block) for the centroid scores,
forward only (the selection has no gradient).  Recomputation is never
counted, and neither is the embedding lookup.
"""
from __future__ import annotations

import numpy as np

from bench.model import head_dim, layer_kinds


def keys_attended(c: dict, kind: str, pos: np.ndarray) -> np.ndarray:
    """Keys one query head at absolute position ``pos`` attends to."""
    pos = np.asarray(pos, np.int64)
    if kind == "swa":
        return np.minimum(c["sliding_window"], pos + 1)
    bs, k = c["moba_block_size"], c["moba_top_k"]
    return pos % bs + 1 + np.minimum(k - 1, pos // bs) * bs


def blocks_scored(c: dict, kind: str, pos: np.ndarray) -> np.ndarray:
    """Earlier blocks whose centroid a MoBA query scores."""
    pos = np.asarray(pos, np.int64)
    if kind != "moba":
        return np.zeros_like(pos)
    return pos // c["moba_block_size"]


def layer_matmul_params(c: dict) -> int:
    d, h, hkv = (c["hidden_size"], c["num_attention_heads"],
                 c["num_key_value_heads"])
    dh, ff = head_dim(c), c["intermediate_size"]
    return d * (h + 2 * hkv) * dh + h * dh * d + 3 * d * ff


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def attention_flops(c: dict, pos: np.ndarray, backward: bool) -> float:
    """Attention and routing operations of every layer for queries at
    positions ``pos`` (summed over them)."""
    h, dh = c["num_attention_heads"], head_dim(c)
    per_key = 12 * dh if backward else 4 * dh
    total = 0.0
    for kind in layer_kinds(c):
        total += h * (per_key * float(np.sum(keys_attended(c, kind, pos)))
                      + 2 * dh * float(np.sum(blocks_scored(c, kind, pos))))
    return total


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Operations of one training step on ``batch`` rows of ``seq``
    tokens (forward and backward)."""
    n = batch * seq
    mm = 6.0 * n * (c["num_hidden_layers"] * layer_matmul_params(c)
                    + head_params(c))
    return mm + batch * attention_flops(c, np.arange(seq), backward=True)


def serve_flops(c: dict, positions: np.ndarray, n_heads_out: int) -> float:
    """Forward operations of processing tokens at ``positions`` (prompt
    and output tokens alike), of which ``n_heads_out`` need logits."""
    mm = 2.0 * len(positions) * c["num_hidden_layers"] \
        * layer_matmul_params(c) + 2.0 * n_heads_out * head_params(c)
    return mm + attention_flops(c, positions, backward=False)
