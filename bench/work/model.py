"""Model operations per token, counted from the configuration's shapes.

What the algorithm needs, whatever implements it: every matmul parameter
costs 2 operations a token forward and 6 forward and backward; attention
costs 2·d_qk + 2·d_v operations per (query head, attended key) forward
(the scores and the weighted values) and 4·(d_qk + d_v) more backward
(dV, dP, dQ, dK; the recomputed scores are not counted); MoBA's routing
costs 2·d_qk per (query head, earlier block) for the centroid scores,
forward only (the selection has no gradient).  Recomputation is never
counted, and neither is the embedding lookup.

The architecture module (``bench/arch/<arch>.py``) gives the matmul
parameters a token runs through (``active_matmul_params``) and one entry
per layer whose work grows with the position (``attention_layers``):
an :class:`AttentionLayer` for softmax attention (``moba``, ``swa``,
``dense``), or an object of the module's own for any other sequence
layer (linear attention, a state-space scan), with a ``kind`` and a
``flops(pos, backward)`` of its own.  The kernels' work reads the
``moba`` entries.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.model import arch


class AttentionLayer(NamedTuple):
    """One attention layer as the work counts see it."""
    kind: str                       # "moba", "swa" or "dense" (causal)
    heads: int                      # query heads
    kv_heads: int
    d_qk: int                       # width of a query and key head
    d_v: int                        # width of a value head
    window: int | None = None       # swa: keys a query reaches back
    block_size: int | None = None   # moba
    top_k: int | None = None        # moba: blocks a query attends to

    def flops(self, pos: np.ndarray, backward: bool) -> float:
        """Operations of this layer's attention and routing for queries
        at positions ``pos`` (summed over them)."""
        per_key = (6 if backward else 2) * (self.d_qk + self.d_v)
        return self.heads * (per_key * float(np.sum(keys_attended(self, pos)))
                             + 2 * self.d_qk
                             * float(np.sum(blocks_scored(self, pos))))


def keys_attended(a: AttentionLayer, pos: np.ndarray) -> np.ndarray:
    """Keys one query head at absolute position ``pos`` attends to."""
    pos = np.asarray(pos, np.int64)
    if a.kind == "swa":
        return np.minimum(a.window, pos + 1)
    if a.kind == "moba":
        bs, k = a.block_size, a.top_k
        return pos % bs + 1 + np.minimum(k - 1, pos // bs) * bs
    if a.kind == "dense":
        return pos + 1
    raise ValueError(f"no key count for attention kind {a.kind!r}")


def blocks_scored(a: AttentionLayer, pos: np.ndarray) -> np.ndarray:
    """Earlier blocks whose centroid a MoBA query scores."""
    pos = np.asarray(pos, np.int64)
    if a.kind != "moba":
        return np.zeros_like(pos)
    return pos // a.block_size


def moba_layers(c: dict) -> list[AttentionLayer]:
    return [a for a in arch(c).attention_layers(c) if a.kind == "moba"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def attention_flops(c: dict, pos: np.ndarray, backward: bool) -> float:
    """Attention and routing operations of every layer for queries at
    positions ``pos`` (summed over them)."""
    return sum((a.flops(pos, backward) for a in arch(c).attention_layers(c)),
               0.0)


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Operations of one training step on ``batch`` rows of ``seq``
    tokens (forward and backward)."""
    n = batch * seq
    mm = 6.0 * n * (arch(c).active_matmul_params(c) + head_params(c))
    return mm + batch * attention_flops(c, np.arange(seq), backward=True)


def serve_flops(c: dict, positions: np.ndarray, n_heads_out: int) -> float:
    """Forward operations of processing tokens at ``positions`` (prompt
    and output tokens alike), of which ``n_heads_out`` need logits."""
    mm = 2.0 * len(positions) * arch(c).active_matmul_params(c) \
        + 2.0 * n_heads_out * head_params(c)
    return mm + attention_flops(c, positions, backward=False)
