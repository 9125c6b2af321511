"""Work of the FlashMoBA backward kernel for one training step.

Operations: 4·(d_qk + d_v) per (query head, attended key) of every MoBA
layer (dV, dP, dQ, dK; the recomputed scores are not counted).  Bytes:
q, k, v, dO in and dQ, dK, dV out in the compute dtype (2 bytes), and
the float32 log-sum-exp and dO·O row sums, each moved once.
"""
from __future__ import annotations

import numpy as np

from bench.work.model import keys_attended, moba_layers

# the kernel's op in a TPU trace: a Mosaic custom call with a scalar-
# prefetched block table, returning dq partials and blocked dk, dv
MATCH = (r"^%\S+ = \(f32\[\d+,\d+,\d+\]\{[^}]*\}, "
         r"f32\[\d+,\d+,\d+,\d+\]\{[^}]*\}, f32\[\d+,\d+,\d+,\d+\]"
         r"\{[^}]*\}\) custom-call\(s32.*tpu_custom_call")


def work(c: dict, batch: int, seq: int) -> tuple[float, float]:
    flops = nbytes = 0.0
    for a in moba_layers(c):
        h, hkv, dqk, dv = a.heads, a.kv_heads, a.d_qk, a.d_v
        keys = float(np.sum(keys_attended(a, np.arange(seq))))
        flops += batch * h * 4 * (dqk + dv) * keys
        # q, dQ, k and dK at d_qk; dO, v and dV at d_v
        nbytes += batch * seq * (2 * (h * (2 * dqk + dv)
                                      + hkv * 2 * (dqk + dv)) + 2 * 4 * h)
    return flops, nbytes
