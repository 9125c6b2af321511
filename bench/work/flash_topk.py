"""Work of the Flash TopK routing kernel for one training step.

Operations: 2·d per (query head, earlier block) of every MoBA layer, the
q·centroid scores; forward only (the selection has no gradient), counted
once per layer even where rematerialisation runs the kernel twice.
Bytes: q and the key-block centroids in the compute dtype (2 bytes) in,
the selected block ids (int32) out, each moved once.
"""
from __future__ import annotations

import numpy as np

from bench.model import head_dim, layer_kinds
from bench.work.model import blocks_scored

# the kernel's op in a TPU trace, by the name its Pallas call gives it
MATCH = r"^%flash_topk(?:\.\d+)? = "


def work(c: dict, batch: int, seq: int) -> tuple[float, float]:
    h, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    layers = layer_kinds(c).count("moba")
    blocks = float(np.sum(blocks_scored(c, "moba", np.arange(seq))))
    flops = layers * batch * h * 2 * d * blocks
    nb = -(-seq // c["moba_block_size"])
    per_layer = batch * (seq * h * d * 2 + hkv * nb * d * 2
                         + seq * h * c["moba_top_k"] * 4)
    return flops, float(layers * per_layer)
