"""End-to-end training driver with fault-tolerant checkpoint/auto-resume.

CPU-scale usage (runs a real training loop on synthetic data):

  PYTHONPATH=src python -m repro.launch.train --arch moba-340m --smoke \
      --steps 50 --batch 8 --seq 512 --ckpt-dir /tmp/run1 --resume auto

The same driver drives the production mesh when devices exist — sharding
comes from the same rules as the dry-run.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.distributed.monitor import HeartbeatMonitor
from repro.configs.base import TrainConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import steps as S
from repro.launch.compile_cache import compile_counts, enable_compile_cache
from repro.models import transformer as T
from repro.optim import adamw


def train(arch: str, steps: int = 50, batch: int = 8, seq: int = 512,
          smoke: bool = True, attn_backend: str = "sparse",
          moba_impl: Optional[str] = None,
          ckpt_dir: str = "", resume: str = "none",
          save_interval: int = 20, lr: float = 6e-4, seed: int = 0,
          microbatch: int = 0, log_every: int = 10,
          block_size: int = 0, top_k: int = 0, key_conv_width: int = 0,
          remat: bool = False, on_step=None, stop_at_step: int = 0,
          total_steps_override: int = 0):
    if moba_impl is not None:
        raise ValueError(
            f"train(moba_impl=...) was removed; pass "
            f"attn_backend={moba_impl!r} instead (same values — see "
            f"core.backends.resolve_backend_spec)")
    kw = {}
    if block_size:
        kw["block_size"] = block_size
    if top_k:
        kw["top_k"] = top_k
    if key_conv_width:
        kw["key_conv_width"] = key_conv_width
    cfg = (configs.get_smoke_config(arch) if smoke
           else configs.get_config(arch, **kw))
    horizon = total_steps_override or steps
    tcfg = TrainConfig(global_batch_size=batch, seq_len=seq,
                       learning_rate=lr, total_steps=horizon,
                       warmup_steps=max(horizon // 10, 1), seed=seed,
                       microbatch=microbatch)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed))

    params = T.init_lm(jax.random.PRNGKey(seed), cfg)
    opt_state = adamw.adamw_init(params)
    start_step = 0
    mgr: Optional[CheckpointManager] = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep=3)
        if resume in ("auto", "latest") and mgr.latest_step() is not None:
            tree = {"params": params, "mu": opt_state.mu,
                    "nu": opt_state.nu}
            tree, extra, ck_step = mgr.restore(tree)
            params = tree["params"]
            opt_state = adamw.AdamWState(
                jnp.asarray(ck_step, jnp.int32), tree["mu"], tree["nu"])
            start_step = extra.get("data_step", ck_step)
            print(f"[resume] restored step {ck_step} from {ckpt_dir}")

    # full spec strings allowed, e.g. "flash:compiled,flat,kb_tile=64" —
    # options apply process-wide to the named backend instance
    from repro.core import backends as B
    backend = B.resolve_backend_spec(attn_backend, default="sparse")
    step_fn = jax.jit(S.make_train_step(cfg, tcfg, backend=backend,
                                        remat=remat),
                      donate_argnums=(0, 1))

    extras = {}
    if cfg.family == "vlm":
        extras["cross_kv"] = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, (batch, cfg.num_image_tokens, cfg.d_model)), cfg.dtype)
    if cfg.family == "encdec":
        extras["src_embeds"] = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, (batch, cfg.num_audio_frames, cfg.d_model)), cfg.dtype)

    losses = []
    t0 = time.time()
    monitor = HeartbeatMonitor(
        on_straggler=lambda st, dt, med: print(
            f"[monitor] straggler step {st}: {dt:.2f}s vs median "
            f"{med:.2f}s"))
    end = min(stop_at_step, steps) if stop_at_step else steps
    for step in range(start_step, end):
        batch_np = data.batch_at(step)
        b = {"tokens": jnp.asarray(batch_np["tokens"])}
        b.update(extras)
        params, opt_state, metrics = step_fn(params, opt_state, b)
        loss = float(metrics["loss"])
        losses.append(loss)
        monitor.beat(step)
        if on_step:
            on_step(step, {k: float(x) for k, x in metrics.items()
                           if k != "expert_load"})
        if step % log_every == 0 or step == steps - 1:
            dt = time.time() - t0
            load = ""
            if "expert_load" in metrics:
                el = np.asarray(metrics["expert_load"])
                load = f"expert_load max {el.max()} mean {el.mean():.1f} "
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"lr {float(metrics.get('lr', 0)):.2e} "
                  f"gnorm {float(metrics.get('grad_norm', 0)):7.3f} "
                  f"{load}[{dt:6.1f}s]")
        if mgr and ((step + 1) % save_interval == 0 or step == end - 1):
            mgr.save(step + 1,
                     {"params": params, "mu": opt_state.mu,
                      "nu": opt_state.nu},
                     extra={"data_step": step + 1,
                            "loss": loss, "arch": arch})
    if mgr:
        mgr.wait()
    if monitor.straggler_steps:
        print(f"[monitor] summary: {monitor.summary()}")
    print(f"[compile] {compile_counts()}")
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="moba-340m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--moba-impl", default=None,
                    help=argparse.SUPPRESS)   # removed: structured error
    ap.add_argument("--attn-backend", default="sparse",
                    help="backend spec, e.g. sparse | flash:compiled | "
                         "flash:flat | flash:grouped,kb_tile=64 "
                         "(see core.backends.resolve_backend_spec)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--save-interval", type=int, default=20)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--block-size", type=int, default=0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--key-conv", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.moba_impl is not None:
        ap.error(f"--moba-impl was removed; use "
                 f"--attn-backend {args.moba_impl} (same values)")
    train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
          smoke=args.smoke, attn_backend=args.attn_backend,
          ckpt_dir=args.ckpt_dir, resume=args.resume,
          save_interval=args.save_interval, lr=args.lr, seed=args.seed,
          microbatch=args.microbatch, block_size=args.block_size,
          top_k=args.top_k, key_conv_width=args.key_conv)


if __name__ == "__main__":
    main()
