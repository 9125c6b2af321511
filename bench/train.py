"""Training driver: the program's jitted train step, timed for a window.

The traffic file gives the job: ``batch`` rows of ``seq`` tokens, the
attention backend, rematerialisation and AdamW's settings.  Tokens are
uniform over the vocabulary, drawn on the host from (seed, step), so
every row of every step differs.

Set-up builds the one step object with its state, drives it through the
first three steps with the window's own call and feed, and reads what
the check needs from the state as it goes: the losses, the per-layer
norms of the first gradient (Adam's first moment after one step, over
1 - beta1) and of the parameters' change after three steps.  The window
then runs the same object on.  Afterwards the program's state is freed
and the plain reference (``bench/reference.py``) makes the same three
steps from the same weights and batches.

Numbers read: ``loss_gap`` (largest |loss - reference| of the three
steps), ``grad_gap`` and ``update_gap`` (the worst per-layer |norm -
reference norm| over the larger of the reference norm and the median
one), and ``grad_error`` (the worst per-layer norm of the difference
between the program's first gradient, as Adam's first moment holds it,
and the reference's, over the same).  Leaves whose reference gradient
is under a thousandth of the median are left out: they move by
round-off alone.  The numbers that ``bench/limits/<workload>.json``
gives a limit are compared; ``PERF.md`` says why the others are not.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

CHECK_STEPS = 3


def tokens(traffic: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, (traffic["batch"], traffic["seq"] + 1),
                        dtype=np.int32)


def leaf_gap(got: dict, want: dict, keep: dict) -> float:
    """Worst |got - want| over max(want, median of want), per layer."""
    med = float(np.median(np.concatenate(list(want.values()))))
    worst = 0.0
    for name, w in want.items():
        g, k = np.asarray(got[name]), keep[name]
        gap = np.abs(g - w) / np.maximum(w, med)
        if np.any(k):
            worst = max(worst, float(np.max(gap[k])))
    return worst


def layer_norms(x: np.ndarray, stacked: bool) -> np.ndarray:
    x = x.astype(np.float32).reshape(x.shape[0] if stacked else 1, -1)
    return np.sqrt(np.einsum("ij,ij->i", x, x, dtype=np.float64))


def leaf_error(got: dict, want: dict, keep: dict) -> float:
    """Worst ||got - want|| over max(||want||, median ||want||), per
    layer of every kept leaf, of two trees of full host arrays."""
    norms, diffs = {}, {}
    for k, w in want.items():
        stacked = k.startswith("blocks/")
        norms[k] = layer_norms(w, stacked)
        diffs[k] = layer_norms(np.asarray(got[k], np.float32) - w, stacked)
    med = float(np.median(np.concatenate(list(norms.values()))))
    return max(float(np.max(diffs[k][keep[k]]
                            / np.maximum(norms[k][keep[k]], med)))
               for k in norms if np.any(keep[k]))


def kept(grad_norms: dict) -> dict:
    med = float(np.median(np.concatenate(list(grad_norms.values()))))
    return {k: v >= 1e-3 * med for k, v in grad_norms.items()}


def to_host(tree) -> dict:
    import jax
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(tree)
            .items()}


def flat_host(tree) -> dict:
    """``{path: host array}`` of a tree on the device."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {"/".join(str(getattr(p, "key", p)) for p in path):
            np.asarray(x) for path, x in flat}


def reference_readings(c: dict, traffic: dict, seed: int, low: bool = False
                       ) -> dict:
    """Losses, first-gradient and three-step-change norms of the plain
    reference (``low``: its float8 control) for the check's batches."""
    import jax
    import jax.numpy as jnp
    from bench import model, reference as R
    opt = traffic["adamw"]
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda p, m, v, t, i: R.adamw_step(c, opt, p, m, v, t,
                                                          i, low),
                       donate_argnums=(0, 1, 2))
        params = model.make_weights(c, seed)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grads = [], None
        for i in range(CHECK_STEPS):
            batch = jnp.asarray(tokens(traffic, c["vocab_size"], seed, i))
            params, m, v, loss, gn = step(params, m, v, batch,
                                          jnp.asarray(i, jnp.int32))
            losses.append(float(loss))
            if i == 0:
                grads, first = to_host(gn), flat_host(m)
        del m, v
        p0 = model.make_weights(c, seed)
        change = to_host(jax.jit(lambda a, b: R.leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(params, p0))
    return {"losses": losses, "grads": grads, "change": change,
            "first": first}


def readings(prog: dict, ref: dict) -> dict:
    keep = kept(ref["grads"])
    return {
        "loss_gap": max(abs(a - b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": leaf_gap(prog["grads"], ref["grads"], keep),
        "update_gap": leaf_gap(prog["change"], ref["change"], keep),
        "grad_error": leaf_error(prog["first"], ref["first"], keep),
    }


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """(name, reading, limit) of every number ``limits`` holds."""
    got = readings(prog, ref)
    return [(name, got[name], limit) for name, limit in limits.items()]


def run(cell: dict, seed: int, seconds: float, trace_dir, t_start: float
        ) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from bench import model, reference as R
    from repro.configs.base import TrainConfig
    from repro.core import backends as B
    from repro.launch import steps as S
    from repro.optim import adamw

    c, tr = cell["config"], cell["traffic"]
    opt = tr["adamw"]
    if opt["warmup_steps"] <= CHECK_STEPS:
        raise ValueError("the check's steps must lie inside the warm-up")
    cfg = model.arch(c).program_config(c)
    tcfg = TrainConfig(global_batch_size=tr["batch"], seq_len=tr["seq"],
                       learning_rate=opt["learning_rate"],
                       warmup_steps=opt["warmup_steps"],
                       total_steps=opt["total_steps"],
                       weight_decay=opt["weight_decay"],
                       beta1=opt["beta1"], beta2=opt["beta2"],
                       grad_clip=opt["grad_clip"])
    backend = B.resolve_backend_spec(tr["attn_backend"])
    step = jax.jit(S.make_train_step(cfg, tcfg, backend=backend,
                                     remat=tr["remat"]),
                   donate_argnums=(0, 1))
    norms = jax.jit(R.leaf_norms)
    change = jax.jit(lambda a, b: R.leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))

    params = model.make_weights(c, seed)
    model.check_layout(c, params)
    state = adamw.adamw_init(params)
    prog = {"losses": []}

    def feed(i):
        with TraceAnnotation("bench.data"):
            return {"tokens": jnp.asarray(tokens(tr, c["vocab_size"], seed,
                                                 i))}

    for i in range(CHECK_STEPS):
        params, state, out = step(params, state, feed(i))
        prog["losses"].append(float(out["loss"]))
        if i == 0:
            prog["grads"] = {k: v / (1 - opt["beta1"])
                             for k, v in to_host(norms(state.mu)).items()}
            prog["first"] = flat_host(state.mu)
    p0 = model.make_weights(c, seed)
    prog["change"] = to_host(change(params, p0))
    del p0
    setup_s = time.perf_counter() - t_start

    trace = (jax.profiler.trace(str(trace_dir)) if trace_dir
             else contextlib.nullcontext())
    done, bad = 0, 0
    with trace:
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while True:
                batch = feed(CHECK_STEPS + done)
                with TraceAnnotation("bench.train_step"):
                    params, state, out = step(params, state, batch)
                    loss = float(out["loss"])
                done += 1
                bad += not np.isfinite(loss)
                t1 = time.perf_counter()
                if t1 - t0 >= seconds:
                    break
    window_s = t1 - t0
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use",
                                                       0)
    del params, state, out, step
    gc.collect()

    ref = reference_readings(c, tr, seed)
    tok = tr["batch"] * tr["seq"]
    return {
        "end_to_end": {"train_tokens_per_s": done * tok / window_s,
                       "setup_s": setup_s},
        "attempted": done, "failed": bad,
        "memory_peak_bytes": int(peak),
        "checks": compare(prog, ref, cell["limits"]),
        "record": {"steps": done, "batch": tr["batch"], "seq": tr["seq"],
                   "window_s": window_s},
    }
