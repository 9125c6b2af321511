"""Chip smoke: serve and train qwen3-0.6b with MoBA on every attention
layer, at full width in bf16, on the Pallas kernels of a TPU.

  python chip_smoke.py              # one chip: serve phase, train phase
  python chip_smoke.py --chips 4    # four chips: sharded serving only

* serve: the paged engine (``flash:compiled``) prefills 4 seeded prompts
  of 1024-2048 tokens and decodes 32 tokens each; on the filled pool one
  decode step of the Pallas kernel is checked against the XLA paged path
  (``core.moba.moba_paged_decode_attention``).
* train: MoBA attention of one full-width layer (output and vjp, seeded
  inputs) on the FlashMoBA kernels is checked against the ``xla``
  backend; then 3 steps of ``launch.train.train`` on the kernels with
  remat, whose losses must be finite and whose first loss and grad norm
  must match the ``xla`` backend's from the same init and data.
* ``--chips 4``: the same requests through ``ShardedEngine(n_shards=4)``
  and a single-host ``Engine`` in one process; the shard pools must sit
  on 4 distinct devices and the greedy tokens must be identical.

Weights and prompts are random, made from ``--seed``.  Without a TPU the
script exits non-zero before any phase.  A failed phase raises, so the
exit code is non-zero and no verdict is printed; otherwise the last line
of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import backends as B  # noqa: E402
from repro.core.moba import moba_paged_decode_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving.engine import Engine, EngineConfig  # noqa: E402
from repro.serving.sharded import ShardedEngine  # noqa: E402

ARCH = "qwen3-0.6b"
BACKEND = "flash:compiled"
N_REQUESTS, PROMPT_RANGE, GEN = 4, (1024, 2048), 32
N_SHARDS = 4
# the largest batch x seq whose train step fits one v5e (16 GiB) on both
# backends, read off compiled.memory_analysis() for a described v5e
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 1, 4096, 3
# kernel vs XLA decode: both upcast to fp32 and select the same pages, so
# they differ by reduction order plus the bf16 rounding of the output
# (2^-8 relative); 4 bf16 ulps of the largest output element
DECODE_ULPS = 4
# one layer, flash vs xla, ||flash - xla|| / ||xla|| of the output and
# of dq, dk, dv: on a v5e the worst was 6.5e-3 (dk); a planted wrong
# block set or a dropped diagonal in moba_bwd read 0.14 or more
LAYER_RTOL = 2e-2
# first train step, flash vs xla from the same init and batch: on a v5e
# the loss differed by 1.3e-4 and the grad norm by 2.4e-4 relative
LOSS_ATOL = 1e-3
GNORM_RTOL = 1e-3


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    return dev


def prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1, N_REQUESTS)
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in lens]


def engine_config(cfg) -> EngineConfig:
    ps = cfg.attention.moba.block_size
    return EngineConfig(max_seqs=N_REQUESTS,
                        max_seq_len=-(-(PROMPT_RANGE[1] + GEN) // ps) * ps,
                        attn_backend=BACKEND)


def decode_agreement(eng: Engine, reqs, seed: int) -> float:
    """Max |kernel - XLA| of one MoBA decode step on the engine's filled
    layer-0 pool, with seeded queries for every slot."""
    attn = eng.cfg.attention
    layer0 = jax.tree.map(lambda x: x[0], eng.caches["slot_0"])
    table = jnp.asarray(eng.sched.block_table)
    kv_len = np.zeros(eng.ecfg.max_seqs, np.int32)
    for r in reqs:
        kv_len[r.slot] = r.cache_len
    kv_len = jnp.asarray(kv_len)
    q = jax.random.normal(
        jax.random.PRNGKey(seed),
        (eng.ecfg.max_seqs, eng.cfg.num_heads, 1, eng.cfg.head_dim),
        jnp.float32).astype(eng.cfg.dtype)
    out = B.get("flash").moba_paged_decode(attn, q, layer0, table, kv_len)
    ref = moba_paged_decode_attention(
        q, layer0["pages_k"], layer0["pages_v"], layer0["centroids"],
        table, kv_len, attn.moba, scale=attn.scale)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    diff = float(np.max(np.abs(out - ref)))
    tol = DECODE_ULPS * 2.0 ** -8 * float(np.max(np.abs(ref)))
    print(f"serve: decode kernel vs XLA max|diff| {diff:.3e} "
          f"(tolerance {tol:.3e}, max|ref| {np.max(np.abs(ref)):.3e})",
          flush=True)
    if not np.all(np.isfinite(out)) or diff > tol:
        raise AssertionError(f"decode kernel disagrees with the XLA paged "
                             f"path: max|diff| {diff} > {tol}")
    return diff


def serve_phase(cfg, seed: int) -> None:
    params = T.init_lm(jax.random.PRNGKey(seed), cfg)
    eng = Engine(cfg, params, engine_config(cfg))
    reqs = [eng.make_request(p, max_new_tokens=GEN)
            for p in prompts(cfg.vocab_size, seed)]
    print(f"serve: prompt lengths {[len(r.prompt) for r in reqs]}",
          flush=True)
    for r in reqs:
        pre = eng.prefill(r)
        if pre is None or not eng.insert(pre):
            raise AssertionError(f"request {r.rid} could not be admitted")
    decode_agreement(eng, reqs, seed)
    while eng.has_work():
        eng.generate_step()
    for r in reqs:
        if len(r.out) != GEN or not r.done:
            raise AssertionError(f"request {r.rid} returned {len(r.out)} "
                                 f"of {GEN} tokens")
    st = eng.stats
    print(f"serve: {st['prefill_tokens']} prefill tokens, "
          f"{st['decode_tokens']} decode tokens over "
          f"{st['decode_steps']} steps", flush=True)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def layer_errors(cfg, seed: int) -> dict[str, float]:
    """``rel_err`` of the FlashMoBA kernels against the ``xla`` backend
    on one layer's MoBA attention at the train step's batch and length:
    the output, and dq, dk, dv of its vjp for a seeded cotangent.  This
    reads block centroids, ``flash_topk``, ``moba_fwd`` and
    ``moba_bwd``."""
    attn = cfg.attention
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def normal(key, heads):
        shape = (TRAIN_BATCH, heads, TRAIN_SEQ, cfg.head_dim)
        return jax.random.normal(key, shape, jnp.float32).astype(cfg.dtype)

    q, k, v = (normal(keys[0], cfg.num_heads),
               normal(keys[1], cfg.num_kv_heads),
               normal(keys[2], cfg.num_kv_heads))
    cot = normal(keys[3], cfg.num_heads)

    def out_and_grads(backend):
        def fn(q, k, v):
            out, vjp = jax.vjp(
                lambda *x: backend.moba_prefill(attn, *x), q, k, v)
            return (out, *vjp(cot))
        return jax.jit(fn)(q, k, v)

    got = out_and_grads(B.get(B.resolve_backend_spec(BACKEND)))
    want = out_and_grads(B.get("xla"))
    return {name: rel_err(g, w)
            for name, g, w in zip(("out", "dq", "dk", "dv"), got, want)}


def train_metrics(backend: str, steps: int, seed: int) -> list[dict]:
    """Per-step metrics (loss, grad_norm, lr) of ``launch.train.train``."""
    record = []
    train(ARCH, attn_backend=backend, steps=steps, batch=TRAIN_BATCH,
          seq=TRAIN_SEQ, smoke=False, remat=True, seed=seed, log_every=1,
          on_step=lambda step, metrics: record.append(metrics))
    return record


def train_phase(cfg, seed: int) -> None:
    """Measures everything first, then raises listing every check that
    failed."""
    failed = []
    for name, err in layer_errors(cfg, seed).items():
        print(f"train: layer {name} flash vs xla rel err {err!r} "
              f"(tolerance {LAYER_RTOL})", flush=True)
        if not err <= LAYER_RTOL:
            failed.append(f"layer {name} rel err {err}")
    flash = train_metrics(BACKEND, TRAIN_STEPS, seed)
    losses = [m["loss"] for m in flash]
    print(f"train: flash losses {losses}", flush=True)
    if len(losses) != TRAIN_STEPS or not np.all(np.isfinite(losses)):
        failed.append(f"flash training losses {losses}")
    f, x = flash[0], train_metrics("xla", 1, seed)[0]
    dloss = abs(f["loss"] - x["loss"])
    dgnorm = abs(f["grad_norm"] - x["grad_norm"]) / x["grad_norm"]
    print(f"train: step 0 loss flash {f['loss']!r} xla {x['loss']!r} "
          f"|diff| {dloss!r} (tolerance {LOSS_ATOL}); grad norm flash "
          f"{f['grad_norm']!r} xla {x['grad_norm']!r} rel diff {dgnorm!r} "
          f"(tolerance {GNORM_RTOL})", flush=True)
    if not dloss <= LOSS_ATOL:
        failed.append(f"step 0 loss differs from xla by {dloss}")
    if not dgnorm <= GNORM_RTOL:
        failed.append(f"step 0 grad norm differs from xla by {dgnorm} "
                      f"relative")
    if failed:
        raise AssertionError("train phase: " + "; ".join(failed))


def serve_tokens(eng, reqs_prompts):
    reqs = [eng.submit(p, max_new_tokens=GEN) for p in reqs_prompts]
    eng.run()
    return [list(r.out) for r in reqs]


def sharded_phase(cfg, seed: int) -> None:
    if len(jax.devices()) < N_SHARDS:
        raise SystemExit(f"--chips {N_SHARDS} needs {N_SHARDS} devices; "
                         f"JAX found {len(jax.devices())}")
    params = T.init_lm(jax.random.PRNGKey(seed), cfg)
    ps = prompts(cfg.vocab_size, seed)
    ecfg = engine_config(cfg)
    single = serve_tokens(Engine(cfg, params, ecfg), ps)
    eng = ShardedEngine(cfg, params, ecfg, n_shards=N_SHARDS)
    sharded = serve_tokens(eng, ps)
    pools = eng.caches["slot_0"]["pages_k"]
    devs = pools.sharding.device_set
    print(f"sharded: page pools {pools.shape} on devices "
          f"{sorted(d.id for d in devs)}", flush=True)
    if len(devs) != N_SHARDS:
        raise AssertionError(f"shard pools on {len(devs)} devices, "
                             f"expected {N_SHARDS}")
    if sharded != single:
        raise AssertionError(f"sharded tokens differ from single-host: "
                             f"{sharded} vs {single}")
    print(f"sharded: {N_SHARDS}-shard greedy tokens match the single-host "
          f"engine for all {len(ps)} requests", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = require_tpu()
    print(f"device: {dev.device_kind}, {len(jax.devices())} device(s); "
          f"compile cache {enable_compile_cache()}", flush=True)
    cfg = configs.get_config(ARCH)
    phases = ([("sharded", lambda: sharded_phase(cfg, args.seed))]
              if args.chips == 4 else
              [("serve", lambda: serve_phase(cfg, args.seed)),
               ("train", lambda: train_phase(cfg, args.seed))])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        # the engine's scheduler hook makes a reference cycle: free its
        # pool and weights before the next phase sizes itself to the chip
        gc.collect()
        print(f"{name}: phase took {time.perf_counter() - t0:.1f}s "
              f"(compilation included)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
