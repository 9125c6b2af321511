"""Work of the paged MoBA decode kernel for one decode step.

Per active sequence of post-append length L and every MoBA layer:
operations 2·d_qk + 2·d_v per (query head, attended key); bytes the
query and the output in the compute dtype, and the keys and values the
selected pages hold up to L, once per kv head (2 bytes each).  Routing on
the centroid cache runs outside the kernel, so the centroids are not its
bytes.
"""
from __future__ import annotations

import numpy as np

from bench.work.model import keys_attended, moba_layers

# the kernel's op in a TPU trace: a Mosaic custom call with scalar-
# prefetched page ids, returning one bf16 (rows, heads, d) output
MATCH = (r"^%\S+ = bf16\[\d+,\d+,\d+\]\{[^}]*\} custom-call\(s32"
         r".*tpu_custom_call")


def work(c: dict, kv_lens) -> tuple[float, float]:
    lens = np.asarray(kv_lens, np.int64)
    flops = nbytes = 0.0
    for a in moba_layers(c):
        h, hkv, dqk, dv = a.heads, a.kv_heads, a.d_qk, a.d_v
        keys = float(np.sum(keys_attended(a, lens - 1)))
        flops += h * 2 * (dqk + dv) * keys
        nbytes += len(lens) * h * (dqk + dv) * 2 + hkv * keys * (dqk + dv) * 2
    return flops, nbytes
