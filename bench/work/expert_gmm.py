"""Work of the grouped expert matmuls (``expert_gmm``) for one training
step.

Per routed (token, held expert) pair, each of an expert's three
matrices (gate and up d×f, down f×d) costs 2·d·f operations forward and
4·d·f more backward (the input's and the weight's gradients;
recomputation not counted): 18·d·f a pair.  Pairs: tokens · top_k ·
held / experts, what balanced routing sends to the experts held here.
Bytes: the held experts' weights in the compute dtype (2 bytes), and
each pair's row in and row out (d each), moved once.

The architecture module gives its expert layers (``expert_layers(c)``:
entries with ``d``, ``f``, ``experts``, ``held`` and ``top_k``); one
without them has no such work.
"""
from __future__ import annotations

from bench.model import arch

# the kernel's op in a TPU trace, by the name its Pallas calls give it
MATCH = r"^%expert_gmm(?:\.\d+)? = "


def work(c: dict, batch: int, seq: int) -> tuple[float, float]:
    flops = nbytes = 0.0
    for x in getattr(arch(c), "expert_layers", lambda c: [])(c):
        pairs = batch * seq * x.top_k * x.held / x.experts
        flops += pairs * 18 * x.d * x.f
        nbytes += 2 * (3 * x.held * x.d * x.f + pairs * 2 * x.d)
    return flops, nbytes
