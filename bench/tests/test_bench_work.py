"""Work counts (``bench/work``) against counts made by hand, one query
and one key at a time, at a small shape."""
from types import SimpleNamespace
from typing import NamedTuple

import numpy as np
import pytest

from bench.work import model as W
from bench.work import flash_topk, moba_bwd, moba_decode, moba_fwd

C = {"name": "small", "arch": "moba_transformer", "hidden_size": 32,
     "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
     "intermediate_size": 48, "vocab_size": 100, "num_hidden_layers": 4,
     "layer_pattern": ["swa", "moba"], "sliding_window": 5,
     "moba_block_size": 4, "moba_top_k": 3}


def by_hand_keys(kind, t):
    """Keys query ``t`` attends to, listed one by one."""
    if kind == "dense":
        return list(range(t + 1))
    if kind == "swa":
        return [s for s in range(t + 1) if t - s < C["sliding_window"]]
    bs, k = C["moba_block_size"], C["moba_top_k"]
    own = t // bs
    earlier = list(range(own))[-(k - 1):] if k > 1 and own else []
    blocks = earlier + [own]
    return [s for s in range(t + 1) if s // bs in blocks]


@pytest.mark.parametrize("kind", ["swa", "moba", "dense"])
def test_keys_attended(kind):
    a = W.AttentionLayer(kind, 4, 2, 8, 8, window=C["sliding_window"],
                         block_size=C["moba_block_size"],
                         top_k=C["moba_top_k"])
    for t in range(23):
        assert W.keys_attended(a, t) == len(by_hand_keys(kind, t))


def test_train_flops_by_hand():
    seq, d, h = 10, 8, 4
    mm = (32 * (4 + 2 * 2) * 8 + 4 * 8 * 32 + 3 * 32 * 48)
    want = 6 * seq * (4 * mm + 32 * 100)
    for kind in C["layer_pattern"] * 2:
        for t in range(seq):
            want += h * 12 * d * len(by_hand_keys(kind, t))
            if kind == "moba":
                want += h * 2 * d * (t // 4)
    assert W.train_flops(C, 1, seq) == want


class LinearLayer(NamedTuple):
    """A layer of an architecture's own, not softmax attention: a state
    of (d, d) a head, read and written once a token."""
    kind: str
    heads: int
    d: int

    def flops(self, pos, backward):
        return (3 if backward else 1) * self.heads * 4 * self.d ** 2 \
            * len(np.asarray(pos))


def test_a_layer_of_its_own_counts_its_own_work(monkeypatch):
    """An entry of ``attention_layers`` that is not an
    :class:`AttentionLayer` is counted by its own ``flops``, beside the
    softmax layers; the kernels' work reads only the ``moba`` entries."""
    net = W.arch(C)
    layers = net.attention_layers(C)[:1] + [LinearLayer("linear", 4, 8)]
    monkeypatch.setattr(W, "arch", lambda c: SimpleNamespace(
        attention_layers=lambda c: layers,
        active_matmul_params=net.active_matmul_params))
    seq, d, h = 10, 8, 4
    want = 6 * seq * (4 * (32 * (4 + 2 * 2) * 8 + 4 * 8 * 32 + 3 * 32 * 48)
                      + 32 * 100)
    for t in range(seq):
        want += h * 12 * d * len(by_hand_keys("swa", t))
        want += 3 * h * 4 * d * d
    assert W.train_flops(C, 1, seq) == want
    assert moba_fwd.work(C, 1, seq) == (0.0, 0.0)


@pytest.mark.parametrize("dqk,dv", [(8, 8), (12, 8)])
def test_kernel_work_by_hand(monkeypatch, dqk, dv):
    """Each product at its own width: q and k at ``dqk``, v and o at
    ``dv`` (an architecture whose query and key heads are wider than its
    value heads, as latent attention's are)."""
    net = W.arch(C)
    monkeypatch.setattr(W, "arch", lambda c: SimpleNamespace(
        attention_layers=lambda c: [
            a._replace(d_qk=dqk, d_v=dv) for a in net.attention_layers(c)]))
    seq, h, hkv, bs, k = 10, 4, 2, 4, 3
    keys = sum(len(by_hand_keys("moba", t)) for t in range(seq))
    flops, nbytes = moba_fwd.work(C, 2, seq)
    # 2 moba layers, 2 rows: q.k scores and p.v products; q, k, v, o in
    # bf16 and the float32 log-sum-exp
    assert flops == 2 * 2 * h * (2 * dqk + 2 * dv) * keys
    assert nbytes == 2 * 2 * seq * (
        2 * (h * dqk + hkv * dqk + hkv * dv + h * dv) + 4 * h)
    flops, nbytes = moba_bwd.work(C, 1, seq)
    # dV = P'dO and dP = dO v' at dv, dQ = dS k and dK = dS'q at dqk;
    # q, dQ, dO, k, dK, v, dV in bf16, the log-sum-exp and dO.o in float32
    assert flops == 2 * h * (2 * dv + 2 * dv + 2 * dqk + 2 * dqk) * keys
    assert nbytes == 2 * seq * (
        2 * (2 * h * dqk + h * dv + 2 * hkv * dqk + 2 * hkv * dv) + 8 * h)
    flops, nbytes = flash_topk.work(C, 1, seq)
    earlier = sum(t // bs for t in range(seq))
    nb = -(-seq // bs)
    assert flops == 2 * h * 2 * dqk * earlier
    assert nbytes == 2 * (seq * h * dqk * 2 + hkv * nb * dqk * 2
                          + seq * h * k * 4)
    lens = [1, 4, 9, 13]
    flops, nbytes = moba_decode.work(C, lens)
    kd = [len(by_hand_keys("moba", n - 1)) for n in lens]
    assert flops == 2 * h * (2 * dqk + 2 * dv) * sum(kd)
    # q in and o out per sequence, the attended keys and values per kv head
    assert nbytes == 2 * (len(lens) * 2 * (h * dqk + h * dv)
                          + hkv * sum(kd) * 2 * (dqk + dv))


def test_serve_flops_counts_logits_only_where_served():
    pos = np.array([0, 1, 2, 7])
    a = W.serve_flops(C, pos, 1)
    b = W.serve_flops(C, pos, 2)
    assert b - a == 2 * 32 * 100
