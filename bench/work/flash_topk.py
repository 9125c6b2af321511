"""Work of the Flash TopK routing kernel for one training step.

Operations: 2·d_qk per (query head, earlier block) of every MoBA layer,
the q·centroid scores; forward only (the selection has no gradient),
counted once per layer even where rematerialisation runs the kernel
twice.  Bytes: q and the key-block centroids in the compute dtype (2
bytes) in, the selected block ids (int32) out, each moved once.
"""
from __future__ import annotations

import numpy as np

from bench.work.model import blocks_scored, moba_layers

# the kernel's op in a TPU trace, by the name its Pallas call gives it
MATCH = r"^%flash_topk(?:\.\d+)? = "


def work(c: dict, batch: int, seq: int) -> tuple[float, float]:
    flops = nbytes = 0.0
    for a in moba_layers(c):
        h, hkv, dqk = a.heads, a.kv_heads, a.d_qk
        blocks = float(np.sum(blocks_scored(a, np.arange(seq))))
        flops += batch * h * 2 * dqk * blocks
        nb = -(-seq // a.block_size)
        nbytes += batch * (seq * h * dqk * 2 + hkv * nb * dqk * 2
                           + seq * h * a.top_k * 4)
    return flops, nbytes
