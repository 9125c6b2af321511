"""The architecture, a part of the benchmark found by name in
``bench/arch``: moving the one transformer into
``bench/arch/moba_transformer.py`` left its weights, its reference's
readings and every work count bit for bit as they were (the constants
were read before the move); a new architecture is one new file; and a
configuration that names a missing one is stopped before a run."""
import hashlib
from pathlib import Path

import numpy as np
import pytest

from bench import model, run, train
from bench.tests.test_bench_checks import LAYOUTS, run_small, small_cell
from bench.work import flash_topk, moba_bwd, moba_decode, moba_fwd
from bench.work import model as W

SEED = 2**33 + 5
# sha256 of every weight leaf of the small training cell's configuration
# (path, dtype, shape and bytes, in path order), drawn from SEED
WEIGHTS = {
    "qwen3":
        "e75016580139dd720a49c18bc74a705b8d7d2694f7b71d0f45d2d570c26511b2",
    "moba-340m":
        "1a27168351ab724b88c33888402119f0988cd7307655c308e089bb78a7589906",
}
# the reference's three losses on the small training cell, seed 3 ...
LOSSES = {
    "qwen3": [5.546313285827637, 5.564671516418457, 5.577963829040527],
    "moba-340m": [6.0369181632995605, 6.097682952880859, 6.09657621383667],
}
# ... and the sha256 of its per-layer first-gradient norms, as WEIGHTS
GRADS = {
    "qwen3":
        "e4dd27a2586f471f601a69ed591a278f64bf2f3c3742f02041516ef4d3a2580c",
    "moba-340m":
        "fd2cd9643a8a1709d91dfeefb86aa671aeef80e9f2dbf4dbd7d67f5d6dfd01c7",
}
# operations and (operations, bytes) at 1x8192; decode at KV_LENS
KV_LENS = [1, 129, 4096, 8192]
WORK = {
    "moba-340m": {
        "train_flops": 18166623240192.0,
        "serve_flops": 5522963562496.0,
        "moba_fwd": (364199804928.0, 811597824.0),
        "moba_bwd": (728399609856.0, 1421869056.0),
        "flash_topk": (6341787648.0, 253231104.0),
        "moba_decode": (107053056.0, 107249664.0),
    },
    "qwen3-0.6b": {
        "train_flops": 34422216720384.0,
        "serve_flops": 8945050320896.0,
        "moba_fwd": (1699599089664.0, 2833252352.0),
        "moba_bwd": (3399198179328.0, 4726980608.0),
        "flash_topk": (29595009024.0, 1060634624.0),
        "moba_decode": (499580928.0, 250707968.0),
    },
}
# an architecture whose training objective is more than the rows' cross
# entropy: experts, and their router's auxiliary loss over the batch (at
# 64 tokens a batch, which the program's expert layer routes drop-free)
FIXTURES = Path(__file__).resolve().parent / "arch"
MOE = {"num_experts": 4, "num_experts_per_tok": 2,
       "moe_intermediate_size": 32, "router_aux_loss_coef": 0.5}
# a second architecture: the transformer's functions under another name
AGAIN = '''"""The transformer of moba_transformer.py, under another name."""
from bench.arch.moba_transformer import (  # noqa: F401
    active_matmul_params, attention_layers, decays, head, hidden,
    param_specs, program_config)
'''


def digest(flat: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(flat):
        a = np.ascontiguousarray(flat[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_weights_are_as_before_the_move(layout):
    w = model.make_weights(small_cell(layout)["config"], SEED)
    assert digest(train.flat_host(w)) == WEIGHTS[layout]


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_reference_reads_as_before_the_move(layout):
    cell = small_cell(layout)
    ref = train.reference_readings(cell["config"], cell["traffic"], 3)
    assert ref["losses"] == LOSSES[layout]
    assert digest(ref["grads"]) == GRADS[layout]


@pytest.mark.parametrize("name", list(WORK))
def test_work_is_as_before_the_move(name):
    c = model.load_config(name)
    assert {
        "train_flops": W.train_flops(c, 1, 8192),
        "serve_flops": W.serve_flops(c, np.arange(8192), 1),
        "moba_fwd": moba_fwd.work(c, 1, 8192),
        "moba_bwd": moba_bwd.work(c, 1, 8192),
        "flash_topk": flash_topk.work(c, 1, 8192),
        "moba_decode": moba_decode.work(c, KV_LENS),
    } == WORK[name]


def test_an_architecture_is_one_new_file(tmp_path, monkeypatch):
    cell = small_cell("moba-340m")
    (tmp_path / "transformer_again.py").write_text(AGAIN)
    monkeypatch.setattr(model, "ARCH", tmp_path)
    moved = dict(cell["config"], arch="transformer_again")
    with pytest.raises(FileNotFoundError):   # only the new directory
        model.arch(cell["config"])
    assert model.arch(moved).__file__ == str(tmp_path
                                             / "transformer_again.py")
    line = run_small(dict(cell, config=moved))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_a_missing_architecture_stops_resolve(monkeypatch):
    load = model.load_config
    monkeypatch.setattr(model, "load_config",
                        lambda name: dict(load(name), arch="no_such_arch"))
    with pytest.raises(SystemExit, match="add bench/arch/no_such_arch.py"):
        run.resolve("moba-340m.train_8k")


@pytest.mark.parametrize("own_objective", [True, False])
def test_an_architecture_brings_its_objective(monkeypatch, own_objective):
    """The small expert transformer of ``bench/tests/arch`` is correct
    through ``run.run_cell`` with its own ``batch_loss``; with the generic
    mean of the rows' losses in its place, which leaves the auxiliary loss
    out, it is not."""
    cell = small_cell("moba-340m")
    monkeypatch.setattr(model, "ARCH", FIXTURES)
    cell = dict(cell, config=dict(cell["config"], arch="small_moe", **MOE),
                traffic=dict(cell["traffic"], batch=2, seq=32))
    if not own_objective:
        monkeypatch.delattr(model.arch(cell["config"]), "batch_loss")
    line = run_small(cell)
    assert line["correct"] is own_objective, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
