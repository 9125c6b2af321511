"""Work of the FlashMoBA forward kernel for one training step.

Operations: 2·d_qk + 2·d_v per (query head, attended key) of every MoBA
layer, the keys being the query's own block up to itself and
``top_k - 1`` earlier blocks.  Bytes: q, k, v and o in the compute dtype
(2 bytes) and the float32 log-sum-exp, each moved once; not the tiles a
grid re-reads.  Counted once per layer even where rematerialisation runs
the kernel twice.
"""
from __future__ import annotations

import numpy as np

from bench.work.model import keys_attended, moba_layers

# the kernel's op in a TPU trace: a Mosaic custom call with a scalar-
# prefetched block table, returning the partial outputs and two (.., 1)
# softmax statistics
MATCH = (r"^%\S+ = \(f32\[\d+,\d+,\d+\]\{[^}]*\}, f32\[\d+,\d+,1\]"
         r"\{[^}]*\}, f32\[\d+,\d+,1\]\{[^}]*\}\) custom-call\(s32"
         r".*tpu_custom_call")


def work(c: dict, batch: int, seq: int) -> tuple[float, float]:
    flops = nbytes = 0.0
    for a in moba_layers(c):
        h, hkv, dqk, dv = a.heads, a.kv_heads, a.d_qk, a.d_v
        keys = float(np.sum(keys_attended(a, np.arange(seq))))
        flops += batch * h * 2 * (dqk + dv) * keys
        nbytes += batch * seq * (2 * (h + hkv) * (dqk + dv) + 4 * h)
    return flops, nbytes
