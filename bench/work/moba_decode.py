"""Work of the paged MoBA decode kernel for one decode step.

Per active sequence of post-append length L and every MoBA layer:
operations 4·d per (query head, attended key); bytes the query and the
output in the compute dtype, and the keys and values the selected pages
hold up to L, once per kv head (2 bytes each).  Routing on the centroid
cache runs outside the kernel, so the centroids are not its bytes.
"""
from __future__ import annotations

import numpy as np

from bench.model import head_dim, layer_kinds
from bench.work.model import keys_attended

# the kernel's op in a TPU trace: a Mosaic custom call with scalar-
# prefetched page ids, returning one bf16 (rows, heads, d) output
MATCH = (r"^%\S+ = bf16\[\d+,\d+,\d+\]\{[^}]*\} custom-call\(s32"
         r".*tpu_custom_call")


def work(c: dict, kv_lens) -> tuple[float, float]:
    h, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    layers = layer_kinds(c).count("moba")
    lens = np.asarray(kv_lens, np.int64)
    keys = float(np.sum(keys_attended(c, "moba", lens - 1)))
    flops = layers * h * 4 * d * keys
    nbytes = layers * (len(lens) * 2 * h * d * 2 + hkv * keys * 2 * d * 2)
    return flops, float(nbytes)
