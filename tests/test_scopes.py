"""The names the train step's device work carries into a profiler trace.

Every op of a compiled program keeps the ``jax.named_scope`` path it was
traced under in its ``op_name`` metadata, which a profile's HLO carries
beside the device ops, so device time can be attributed by it.  These
tests compile the train step of the moba-340m smoke configuration on
the flash backend (interpret mode on the CPU) and read the paths back,
in the forward pass, its rematerialisation and the backward pass.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import TrainConfig
from repro.launch import steps as S
from repro.models import transformer as T
from repro.optim import adamw

# the scopes this model's step runs under (PERF.md lists the whole
# vocabulary, with attn_dense, attn_cross and moe for other layouts)
LAYER_SCOPES = ("embed", "attn_swa", "attn_moba", "attend", "mlp",
                "lm_head", "loss", "optimizer")
MOBA_FWD = ("moba_centroids", "moba_topk", "moba_layout", "moba_fwd",
            "moba_merge")
MOBA_BWD = ("moba_bwd_prep", "moba_bwd", "moba_bwd_reduce")


def segments(op_name: str) -> list[str]:
    """The path's segments with JAX's transformation wrappers taken off:
    ``transpose(jvp(attn_moba))`` reads ``attn_moba``."""
    return [re.sub(r"^(?:[\w.]+\()*(.*?)\)*$", r"\1", s)
            for s in op_name.split("/")]


# four SWA chunks of 128 (window 32 rounded up), and no other width of
# the smoke model equals it, so an (n, n) score would show by its shape
SEQ_LEN = 512


@pytest.fixture(scope="module")
def hlo_text():
    cfg = configs.get_smoke_config("moba-340m")
    tcfg = TrainConfig(global_batch_size=1, seq_len=SEQ_LEN, total_steps=10,
                       warmup_steps=2)
    step = jax.jit(S.make_train_step(cfg, tcfg, backend="flash",
                                     remat=True))
    params = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(adamw.adamw_init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((1, SEQ_LEN + 1), jnp.int32)}
    return step.lower(params, state, batch).compile().as_text()


@pytest.fixture(scope="module")
def op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    return "backward" if "transpose(" in op_name else "forward"


def test_segments_take_off_the_wrappers():
    assert segments("jit(f)/transpose(jvp(attn_moba))/attend/kbwd/cos") \
        == ["f", "attn_moba", "attend", "kbwd", "cos"]
    assert segments("a/checkpoint/rematted_computation/attn_swa/dot") \
        == ["a", "checkpoint", "rematted_computation", "attn_swa", "dot"]


def test_every_scope_is_in_the_compiled_step(op_names):
    seen = {s for n in op_names for s in segments(n)}
    missing = set(LAYER_SCOPES + MOBA_FWD + MOBA_BWD) - seen
    assert not missing


def test_moba_stages_run_inside_the_moba_attention_core(op_names):
    for n in op_names:
        segs = segments(n)
        stages = set(segs) & set(MOBA_FWD + MOBA_BWD)
        if stages:
            assert "attn_moba" in segs and "attend" in segs, n
            assert segs.index("attn_moba") < segs.index("attend"), n


@pytest.mark.parametrize("phase", ["forward", "remat", "backward"])
def test_moba_stages_in_each_phase(op_names, phase):
    """The forward stages appear in the forward pass and again where the
    backward pass recomputes it; the backward stages under the
    transpose."""
    seen = {s for n in op_names if phase_of(n) == phase
            for s in segments(n)}
    want = MOBA_BWD if phase == "backward" else MOBA_FWD
    assert set(want) <= seen


def test_kernels_carry_their_names(op_names):
    """A Pallas call's own ``name`` is a segment under its stage."""
    pairs = {("moba_centroids", "centroids"), ("moba_topk", "flash_topk"),
             ("moba_fwd", "moba_fwd"), ("moba_bwd", "moba_bwd")}
    found = set()
    for n in op_names:
        segs = segments(n)
        for a, b in zip(segs, segs[1:]):
            if (a, b) in pairs:
                found.add((a, b))
    assert found == pairs


@pytest.mark.parametrize("phase", ["forward", "remat", "backward"])
def test_swa_attention_runs_on_the_band_in_each_phase(op_names, phase):
    assert any(phase_of(n) == phase
               and "/attn_swa/attend/swa_band/" in "/".join(segments(n))
               for n in op_names)


def test_no_swa_op_holds_a_score_over_the_whole_sequence(hlo_text):
    full = re.compile(rf"\b{SEQ_LEN},{SEQ_LEN}\]")
    for line in hlo_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and "attn_swa" in segments(m.group(1)):
            assert not full.search(line), line


# ------------------------------------------------------- the expert layer
MOE_SCOPES = ("route", "dispatch", "expert_gmm", "combine")
MOE_SEQ = 64


def mellum_step(cfg):
    tcfg = TrainConfig(global_batch_size=1, seq_len=MOE_SEQ, total_steps=10,
                       warmup_steps=2)
    return jax.jit(S.make_train_step(cfg, tcfg, backend="flash",
                                     remat=True))


@pytest.fixture(scope="module")
def moe_op_names():
    cfg = configs.get_smoke_config("mellum2-12b-a2.5b")
    params = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(adamw.adamw_init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((1, MOE_SEQ + 1), jnp.int32)}
    text = mellum_step(cfg).lower(params, state, batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("name", MOE_SCOPES + ("kernel expert_gmm",))
def test_expert_layer_names_in_the_compiled_step(moe_op_names, name):
    """Each stage of the expert layer sits under ``moe``, forward and
    backward; the grouped matmuls' Pallas calls carry ``expert_gmm``
    under their stage."""
    pair = (("expert_gmm", "expert_gmm") if name.startswith("kernel")
            else ("moe", name))
    for phase in ("forward", "backward"):
        assert any(phase_of(n) == phase
                   and "/".join(pair) in "/".join(segments(n))
                   for n in moe_op_names), (name, phase)


def test_the_step_counts_each_held_experts_pairs():
    """Under a fixed routing (a zero router ties every expert, and the top
    2 are experts 0 and 1, the two held), the step's ``expert_load``
    gives each expert layer's held experts every token."""
    cfg = configs.get_smoke_config("mellum2-12b-a2.5b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    for slot in params["blocks"].values():
        slot["moe"]["router"] = jnp.zeros_like(slot["moe"]["router"])
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (1, MOE_SEQ + 1), 0,
                                          cfg.vocab_size)}
    _, _, out = mellum_step(cfg)(params, adamw.adamw_init(params), batch)
    load = np.asarray(out["expert_load"])
    assert load.shape == (cfg.num_layers, cfg.moe.held)
    np.testing.assert_array_equal(load, MOE_SEQ)
