"""Work of the FlashMoBA forward kernel for one training step.

Operations: 4·d per (query head, attended key) of every MoBA layer, the
keys being the query's own block up to itself and ``top_k - 1`` earlier
blocks.  Bytes: q, k, v and o in the compute dtype (2 bytes) and the
float32 log-sum-exp, each moved once; not the tiles a grid re-reads.
Counted once per layer even where rematerialisation runs the kernel
twice.
"""
from __future__ import annotations

import numpy as np

from bench.model import head_dim, layer_kinds
from bench.work.model import keys_attended

# the kernel's op in a TPU trace: a Mosaic custom call with a scalar-
# prefetched block table, returning the partial outputs and two (.., 1)
# softmax statistics
MATCH = (r"^%\S+ = \(f32\[\d+,\d+,\d+\]\{[^}]*\}, f32\[\d+,\d+,1\]"
         r"\{[^}]*\}, f32\[\d+,\d+,1\]\{[^}]*\}\) custom-call\(s32"
         r".*tpu_custom_call")


def work(c: dict, batch: int, seq: int) -> tuple[float, float]:
    h, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    layers = layer_kinds(c).count("moba")
    keys = float(np.sum(keys_attended(c, "moba", np.arange(seq))))
    flops = layers * batch * h * 4 * d * keys
    per_layer = batch * seq * (2 * (2 * h + 2 * hkv) * d + 4 * h)
    return flops, float(layers * per_layer)
