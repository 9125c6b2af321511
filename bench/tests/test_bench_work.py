"""Work counts (``bench/work``) against counts made by hand, one query
and one key at a time, at a small shape."""
import numpy as np
import pytest

from bench.work import model as W
from bench.work import moba_bwd, moba_decode, moba_fwd

C = {"name": "small", "hidden_size": 32, "head_dim": 8,
     "num_attention_heads": 4, "num_key_value_heads": 2,
     "intermediate_size": 48, "vocab_size": 100, "num_hidden_layers": 4,
     "layer_pattern": ["swa", "moba"], "sliding_window": 5,
     "moba_block_size": 4, "moba_top_k": 3}


def by_hand_keys(kind, t):
    """Keys query ``t`` attends to, listed one by one."""
    if kind == "swa":
        return [s for s in range(t + 1) if t - s < C["sliding_window"]]
    bs, k = C["moba_block_size"], C["moba_top_k"]
    own = t // bs
    earlier = list(range(own))[-(k - 1):] if k > 1 and own else []
    blocks = earlier + [own]
    return [s for s in range(t + 1) if s // bs in blocks]


@pytest.mark.parametrize("kind", ["swa", "moba"])
def test_keys_attended(kind):
    for t in range(23):
        assert W.keys_attended(C, kind, t) == len(by_hand_keys(kind, t))


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


def test_kernel_work_by_hand():
    seq, d, h, hkv = 10, 8, 4, 2
    keys = sum(len(by_hand_keys("moba", t)) for t in range(seq))
    flops, nbytes = moba_fwd.work(C, 2, seq)
    assert flops == 2 * 2 * h * 4 * d * keys           # 2 moba layers
    assert nbytes == 2 * 2 * seq * (2 * (2 * h + 2 * hkv) * d + 4 * h)
    flops, nbytes = moba_bwd.work(C, 1, seq)
    assert flops == 2 * h * 8 * d * keys
    assert nbytes == 2 * seq * (2 * (3 * h + 4 * hkv) * d + 8 * h)
    lens = [1, 4, 9, 13]
    flops, nbytes = moba_decode.work(C, lens)
    k = [len(by_hand_keys("moba", n - 1)) for n in lens]
    assert flops == 2 * h * 4 * d * sum(k)
    assert nbytes == 2 * (len(lens) * 2 * h * d * 2 + hkv * sum(k) * 4 * d)


def test_serve_flops_counts_logits_only_where_served():
    pos = np.array([0, 1, 2, 7])
    a = W.serve_flops(C, pos, 1)
    b = W.serve_flops(C, pos, 2)
    assert b - a == 2 * 32 * 100
