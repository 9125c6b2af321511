"""Mellum2-12B-A2.5B as one chip of an 8-way expert-parallel deployment
(``bench/arch/mellum_moe.py``): the configuration keeps the published
widths, its weights are the program's layout, its work counts are as
worked out by hand, and a whole small cell of the architecture is correct
through ``run.run_cell`` where the float8 control and an unchanged state
are not."""
import jax
import numpy as np
import pytest

from bench import model, run, train
from bench.tests.test_bench_checks import ADAMW, run_small, unchanged_state
from bench.work import expert_gmm, flash_topk, moba_bwd, moba_fwd
from bench.work import model as W

CELL = "mellum2-12b-a2.5b-ep8.train_4k"
SMALL = {
    "name": "small-mellum", "arch": "mellum_moe", "hidden_size": 64,
    "head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 8, "num_key_value_heads": 1,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4,
    "num_experts": 2, "router_num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "router_aux_loss_coef": 0.001,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 1e4,
                           "factor": 4, "original_max_position_embeddings": 32,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}},
    "sliding_window": 32, "tie_word_embeddings": False, "vocab_size": 256,
    "moba_block_size": 16, "moba_top_k": 2,
    "compute_dtype": "bfloat16", "param_dtype": "float32"}


def small_cell(**over):
    traffic = {"kind": "train", "batch": 2, "seq": 128, "remat": True,
               "attn_backend": "flash:interpret", "adamw": ADAMW}
    return dict(run.resolve(CELL), config=dict(SMALL, **over),
                traffic=traffic)


def test_configuration_keeps_the_published_widths():
    c = model.load_config("mellum2-12b-a2.5b-ep8")
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["sliding_window"]) == (2304, 32, 4, 128, 896, 8, 1024)
    assert (c["router_num_experts"], c["num_experts"],
            c["num_hidden_layers"], c["vocab_size"]) == (64, 8, 4, 12288)
    assert c["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    cfg = model.arch(c).program_config(c)
    assert cfg.layer_pattern == ("swa", "swa", "swa", "moba")
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k) == (64, 8, 8)


def test_weights_are_the_programs_layout_and_size():
    """Attention 21,233,664, router 147,456 and 8 experts of 6,193,152 a
    layer, 70.9M; embedding and head 56.6M: 340M parameters."""
    c = model.load_config("mellum2-12b-a2.5b-ep8")
    specs = model.arch(c).param_specs(c)
    model.check_layout(c, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s[0], np.float32),
        model._nest(specs), is_leaf=lambda s: isinstance(s, tuple)))
    total = sum(int(np.prod(shape)) for shape, _ in specs.values())
    layer = 21_233_664 + 147_456 + 4_608 + 8 * 6_193_152
    assert total == 4 * layer + 2 * 2304 * 12288 + 2304


def test_work_by_hand():
    """At 1x4096: 4 layers of 4096·8·8/64 = 4096 pairs at 18·2304·896
    operations; the held experts' weights and 2 rows a pair in bf16."""
    c = model.load_config("mellum2-12b-a2.5b-ep8")
    flops, nbytes = expert_gmm.work(c, 1, 4096)
    assert flops == 4 * 4096 * 18 * 2304 * 896
    assert nbytes == 4 * 2 * (3 * 8 * 2304 * 896 + 4096 * 2 * 2304)
    # a token: q/k/v/o, the router and one expert's worth (8·8/64)
    layer = 2304 * 40 * 128 + 4096 * 2304 + 2304 * 64 + 3 * 2304 * 896
    assert model.arch(c).active_matmul_params(c) == 4 * layer
    mm = 6.0 * 4096 * (4 * layer + 2304 * 12288)
    assert W.train_flops(c, 1, 4096) == mm + W.attention_flops(
        c, np.arange(4096), backward=True)
    # the MoBA kernels see one layer of 32 q / 4 kv heads of 128
    assert [a.kind for a in W.moba_layers(c)] == ["moba"]
    for kernel in (moba_fwd, moba_bwd, flash_topk):
        assert kernel.work(c, 1, 4096)[0] > 0
    # an architecture without expert layers has no grouped-matmul work
    assert expert_gmm.work(model.load_config("moba-340m"), 1, 8192) \
        == (0.0, 0.0)


def test_reference_yarn_is_the_programs():
    from repro.configs.base import YarnRope
    from repro.models.layers import rope_freqs
    c = model.load_config("mellum2-12b-a2.5b-ep8")
    full = c["rope_parameters"]["full_attention"]
    freqs, scale = model.arch(c).inv_freq(128, full)
    yarn = YarnRope(16.0, 8192, 32.0, 1.0, full["attention_factor"])
    np.testing.assert_allclose(np.asarray(freqs),
                               np.asarray(rope_freqs(128, 5e5, yarn)),
                               rtol=1e-6)
    assert scale == full["attention_factor"]


def test_small_cell_is_correct():
    line = run_small(small_cell())
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_small_cell_control_is_not_correct():
    cell = small_cell()
    ref = train.reference_readings(cell["config"], cell["traffic"], 3)
    low = train.reference_readings(cell["config"], cell["traffic"], 3,
                                   low=True)
    checks = train.compare(low, ref, cell["limits"])
    assert any(v > lim for _, v, lim in checks), checks


def test_small_cell_unchanged_state_is_not_correct(monkeypatch):
    from repro.launch import steps
    monkeypatch.setattr(steps, "make_train_step",
                        unchanged_state(steps.make_train_step))
    line = run_small(small_cell())
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("held", [1, 8])
def test_small_cell_holds_any_share(held):
    """One expert of eight held, and all eight (the uncut layer), the
    program computing in float32 so that its rounding is no part of the
    reading: each share reads far inside the cell's limits."""
    line = run_small(small_cell(num_experts=held, compute_dtype="float32"))
    assert line["correct"] is True, line["checks"]
    assert all(c["value"] < c["limit"] / 10
               for c in line["checks"].values()), line["checks"]
