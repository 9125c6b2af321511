"""The correctness check at a size a test run holds, with the training
cell's own limits: the program comes out correct, and the float8
control in its place and faults planted under the timed path (the
harness's look for a chip skipped) come out not correct, in both layer
layouts of the benchmark's configurations.  The serving driver, which no
cell uses yet (PERF.md, Open questions), is held to a limit of this
file's own at this size."""
import time

import jax
import pytest

from bench import run, serve, train

TINY = {"name": "tiny", "arch": "moba_transformer", "hidden_size": 64,
        "head_dim": 16, "intermediate_size": 128,
        "max_position_embeddings": 512,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e4,
        "sliding_window": 32, "vocab_size": 256, "moba_block_size": 16,
        "moba_top_k": 2, "moba_key_conv_width": 0,
        "compute_dtype": "bfloat16", "param_dtype": "float32"}
CELL = "moba-340m.train_8k"
# the two layer layouts of the configurations in bench/configs
LAYOUTS = {
    "qwen3": dict(tie_word_embeddings=True, qk_norm=True,
                  layer_pattern=["moba"], rope_on_moba=True),
    "moba-340m": dict(tie_word_embeddings=False, qk_norm=False,
                      layer_pattern=["swa", "moba"], rope_on_moba=False),
}
SERVE_MIXES = ["decode_8k", "longdoc"]
# served tokens of the small model lie within 0.02 of the reference's
# best logit; an altered token lies about 1 below it
SMALL_SERVE_GAP = 0.1
ADAMW = {"learning_rate": 6e-4, "warmup_steps": 100, "total_steps": 10000,
         "weight_decay": 0.1, "beta1": 0.9, "beta2": 0.95, "grad_clip": 1.0}


def small_cell(layout: str) -> dict:
    """The training cell at a small size, with its own limits."""
    traffic = {"kind": "train", "batch": 2, "seq": 128, "remat": True,
               "attn_backend": "flash:interpret", "adamw": ADAMW}
    return dict(run.resolve(CELL), config=dict(TINY, **LAYOUTS[layout]),
                traffic=traffic)


def serve_cell(mix: str) -> dict:
    """A serving mix's arrival pattern at a small size."""
    real = run.load_json(run.BENCH / "traffic" / f"{mix}.json")
    traffic = dict(real, fill=min(real["fill"], 2),
                   rate_per_s=4.0 if real["rate_per_s"] else 0.0,
                   prompt={"dist": real["prompt"]["dist"], "min": 40,
                           "max": 100},
                   output={"dist": "uniform", "min": 8, "max": 16},
                   check_tokens=32,
                   engine={"max_seqs": 4, "max_seq_len": 128,
                           "prefill_chunk": 64, "max_prefill_batch": 1,
                           "attn_backend": "flash:interpret"})
    return {"workload": {"name": f"small.{mix}", "chips": 1},
            "config": dict(TINY, **LAYOUTS["qwen3"]),
            "traffic": traffic, "limits": {"logit_gap": SMALL_SERVE_GAP},
            "end_to_end": [], "per_layer": [], "readers": {}}


def run_small(cell, seed=2**31 + 7, seconds=1.0):
    return run.run_cell(cell, seed, seconds, False, jax.devices())


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_train_program_is_correct(layout):
    line = run_small(small_cell(layout))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_train_control_is_not_correct(layout):
    cell = small_cell(layout)
    ref = train.reference_readings(cell["config"], cell["traffic"], 3)
    low = train.reference_readings(cell["config"], cell["traffic"], 3,
                                   low=True)
    checks = train.compare(low, ref, cell["limits"])
    assert any(v > lim for _, v, lim in checks), checks


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_serve_check_tells_program_from_control(mix):
    cell = serve_cell(mix)
    out = serve.run(cell, 5, 1.0, None, time.perf_counter())
    assert out["failed"] == 0 and out["check_inputs"]
    prog = out["checks"][0][1]
    low = serve.reference_gap(cell["config"], 5, out["check_inputs"],
                              low=True)
    assert prog <= SMALL_SERVE_GAP < low, (prog, low)


def unchanged_state(make):
    def maker(*a, **kw):
        step = make(*a, **kw)

        def frozen(params, opt_state, batch):
            _, _, out = step(params, opt_state, batch)
            return params, opt_state, out
        return frozen
    return maker


def half_batch(make):
    def maker(*a, **kw):
        step = make(*a, **kw)

        def half(params, opt_state, batch):
            rows = batch["tokens"].shape[0] // 2
            return step(params, opt_state,
                        dict(batch, tokens=batch["tokens"][:rows]))
        return half
    return maker


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_train_fault_is_not_correct(monkeypatch, layout, fault):
    from repro.launch import steps
    monkeypatch.setattr(steps, "make_train_step",
                        fault(steps.make_train_step))
    line = run_small(small_cell(layout))
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_serve_altered_token_is_not_correct(monkeypatch, mix):
    from repro.launch import steps
    make = steps.make_paged_decode_step

    def altered(cfg, *a, **kw):
        step = make(cfg, *a, **kw)

        def decode(*args):
            tok, caches = step(*args)
            return (tok + 1) % cfg.vocab_size, caches
        return decode

    monkeypatch.setattr(steps, "make_paged_decode_step", altered)
    line = run_small(serve_cell(mix))
    assert line["correct"] is False, line["checks"]
