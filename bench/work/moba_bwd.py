"""Work of the FlashMoBA backward kernel for one training step.

Operations: 8·d per (query head, attended key) of every MoBA layer (dV,
dP, dQ, dK; the recomputed scores are not counted).  Bytes: q, k, v, dO
in and dQ, dK, dV out in the compute dtype (2 bytes), and the float32
log-sum-exp and dO·O row sums, each moved once.
"""
from __future__ import annotations

import numpy as np

from bench.model import head_dim, layer_kinds
from bench.work.model import keys_attended

# the kernel's op in a TPU trace: a Mosaic custom call with a scalar-
# prefetched block table, returning dq partials and blocked dk, dv
MATCH = (r"^%\S+ = \(f32\[\d+,\d+,\d+\]\{[^}]*\}, "
         r"f32\[\d+,\d+,\d+,\d+\]\{[^}]*\}, f32\[\d+,\d+,\d+,\d+\]"
         r"\{[^}]*\}\) custom-call\(s32.*tpu_custom_call")


def work(c: dict, batch: int, seq: int) -> tuple[float, float]:
    h, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    layers = layer_kinds(c).count("moba")
    keys = float(np.sum(keys_attended(c, "moba", np.arange(seq))))
    flops = layers * batch * h * 8 * d * keys
    per_layer = batch * seq * (2 * (3 * h + 4 * hkv) * d + 2 * 4 * h)
    return flops, float(layers * per_layer)
