"""Step-function builders + input/parameter sharding specs shared by
train.py, serve.py and dryrun.py."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ASSIGNED_SHAPES, ModelConfig, TrainConfig
from repro.distributed import sharding as shmod
from repro.models import api
from repro.models import transformer as T
from repro.optim import adamw


# ------------------------------------------------------------- step makers
def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    backend: str = "sparse", remat: bool = True,
                    unroll: bool = False, accum_in_loss: bool = False):
    """``accum_in_loss``: gradient accumulation expressed INSIDE the loss
    (scan over rematted microbatch chunks) so the cross-data gradient
    reduction happens ONCE per step instead of once per microbatch —
    measured 2.35 TB → 147 GB of grad all-reduce on llama-90B train_4k."""
    lr_fn = adamw.cosine_schedule(tcfg)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return T.lm_loss(p, batch, cfg, backend=backend,
                             remat=remat, unroll=unroll)

        if accum_in_loss and tcfg.microbatch and tcfg.microbatch > 1:
            m = tcfg.microbatch
            mb = jax.tree.map(
                lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]),
                batch)

            def accum_loss(p):
                @jax.checkpoint
                def body(carry, batch_i):
                    l, _ = T.lm_loss(p, batch_i, cfg, backend=backend,
                                     remat=remat, unroll=unroll)
                    return carry + l / m, None

                total, _ = jax.lax.scan(body,
                                        jnp.zeros((), jnp.float32), mb)
                return total, {}

            (loss, metrics), grads = jax.value_and_grad(
                accum_loss, has_aux=True)(params)
        elif tcfg.microbatch and tcfg.microbatch > 1:
            m = tcfg.microbatch

            def micro(batch_i):
                def lf(p):
                    return T.lm_loss(p, batch_i, cfg, backend=backend,
                                     remat=remat, unroll=unroll)
                return jax.value_and_grad(lf, has_aux=True)(params)

            mb = jax.tree.map(
                lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]),
                batch)

            def acc(carry, batch_i):
                (l, a), g = micro(batch_i)
                cl, cg = carry
                return (cl + l / m,
                        jax.tree.map(lambda x, y: x + y / m, cg, g)), None

            # derive the accumulator from params so it inherits the
            # FSDP sharding: per-microbatch grad sync then lowers to a
            # shard-sized reduce-scatter instead of a full all-reduce.
            zero_g = jax.tree.map(
                lambda p: (p * 0).astype(jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc, (jnp.zeros((), jnp.float32), zero_g), mb)
            metrics = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        params, opt_state, om = adamw.adamw_update(params, grads,
                                                   opt_state, tcfg, lr_fn)
        out = {"loss": loss}
        out.update(om)
        if "expert_load" in metrics:
            # (expert layers, held): pairs routed to each held expert
            out["expert_load"] = metrics["expert_load"]
        return params, opt_state, out

    return train_step


def make_prefill_step(cfg: ModelConfig, backend: str = "sparse",
                      unroll: bool = False):
    def prefill_step(params, tokens, caches, cross_kv=None,
                     src_embeds=None):
        ck = cross_kv
        if cfg.num_encoder_layers and src_embeds is not None:
            ck = T.apply_encoder(params, src_embeds, cfg,
                                 backend=backend, unroll=unroll)
        logits, new_caches = T.prefill(params, tokens, cfg, caches,
                                       backend=backend, cross_kv=ck,
                                       unroll=unroll)
        return logits[:, -1:], new_caches

    return prefill_step


def make_decode_step(cfg: ModelConfig, backend: str = "reference",
                     unroll: bool = False):
    def decode_step(params, token, caches, cross_kv=None, src_embeds=None):
        ck = cross_kv
        if cfg.num_encoder_layers and src_embeds is not None:
            # encoder output is precomputed at prefill in real serving; the
            # stub keeps the decode cell self-contained.
            ck = T.apply_encoder(params, src_embeds, cfg,
                                 backend=backend, unroll=unroll)
        logits, new_caches = T.decode_step(params, token, cfg, caches,
                                           backend=backend, cross_kv=ck,
                                           unroll=unroll)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return next_tok, new_caches

    return decode_step


def _as_route_map(route_map):
    """Freeze a profile's ``{"slot_i": (n_groups, H)}`` head budgets into
    int32 device constants.  Embedded in the step closures (not traced
    arguments), they are jit/shard_map-replicated constants — every step
    replays exactly the profile's routing decisions (DESIGN.md §8)."""
    if route_map is None:
        return None
    return {k: jnp.asarray(v, jnp.int32) for k, v in route_map.items()}


def make_paged_prefill_step(cfg: ModelConfig, backend: str = "reference",
                            chunked: bool = False, route_map=None):
    """Ragged prefill into a paged cache: tokens (B, L) right-padded with
    per-row valid length ``q_len``; rows with q_len == 0 are padding.
    ``kv_len`` gives each row's pre-step cache length (all zeros for
    one-shot prefill; chunk offsets under chunked prefill) and ``slots``
    maps prefill rows to scheduler sequence slots (for the per-slot
    key-conv ring buffer; -1 on padding rows).  ``chunked=True``
    (static) selects the chunk-aware attention path that sees earlier
    chunks through the block table.  ``route_map`` carries a calibrated
    adaptive-routing profile's per-head top_k budgets (None = static).
    Returns (sampled next token (B,) — meaningful only for rows whose
    prompt is now fully cached, new caches)."""
    rmap = _as_route_map(route_map)

    def prefill_step(params, tokens, caches, block_table, kv_len, q_len,
                     slots, active):
        page_state = {"block_table": block_table, "kv_len": kv_len,
                      "q_len": q_len, "slots": slots, "active": active,
                      "chunked": chunked}
        positions = (kv_len[:, None] + jnp.arange(tokens.shape[1])
                     if chunked else None)
        logits, new_caches = T.prefill(params, tokens, cfg, caches,
                                       backend=backend,
                                       page_state=page_state,
                                       positions=positions,
                                       route_map=rmap)
        last = jnp.maximum(q_len - 1, 0)[:, None, None]      # (B,1,1)
        lg = jnp.take_along_axis(logits, last, axis=1)[:, 0]  # (B,V)
        return jnp.argmax(lg, axis=-1).astype(jnp.int32), new_caches

    return prefill_step


def make_paged_decode_step(cfg: ModelConfig, backend: str = "reference",
                           route_map=None):
    """One continuous-batching decode step over all sequence slots:
    token (B,), per-slot pre-step lengths kv_len (B,), active mask (B,).
    ``route_map`` as in :func:`make_paged_prefill_step`.
    Returns (next token (B,), new caches)."""
    rmap = _as_route_map(route_map)

    def decode_step(params, token, caches, block_table, kv_len, active):
        page_state = {"block_table": block_table, "kv_len": kv_len,
                      "q_len": active.astype(jnp.int32), "active": active}
        logits, new_caches = T.decode_step(params, token[:, None], cfg,
                                           caches, backend=backend,
                                           page_state=page_state,
                                           route_map=rmap)
        return (jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32),
                new_caches)

    return decode_step


# ------------------------------------------------------- sharded serving
def _shard_over_data(fn, mesh, n_host_args: int):
    """Wrap a per-shard step so the whole fleet runs as ONE jitted
    shard_map over the mesh ``data`` axis (DESIGN.md §7).

    Every argument after ``params`` carries a leading shard dim equal to
    the data-axis size; the body strips its local slice (leading dim 1),
    runs the unmodified single-host step, and re-stacks.  Params are
    replicated (spec ``P()``); the attention math inside is mesh-free
    (``Capabilities.sharded``), so no collective crosses shards — decode
    for S shards costs one dispatch instead of S."""
    from jax.sharding import PartitionSpec as P

    def local(params, *args):
        tok, new_caches = fn(params,
                             *jax.tree.map(lambda x: x[0], list(args)))
        return tok[None], jax.tree.map(lambda x: x[None], new_caches)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(),) + (P("data"),) * n_host_args,
                         out_specs=(P("data"), P("data")), check_vma=False)


def make_sharded_paged_prefill_step(cfg: ModelConfig, mesh,
                                    backend: str = "reference",
                                    chunked: bool = False,
                                    route_map=None):
    """Sharded :func:`make_paged_prefill_step`: every array argument
    gains a leading shard dim (S, ...) laid out over ``data``.  The
    adaptive ``route_map`` is a closure constant of the inner step, so
    it is replicated across shards — every shard routes from the same
    profile (shard-count invariance, pinned by test)."""
    return _shard_over_data(
        make_paged_prefill_step(cfg, backend=backend, chunked=chunked,
                                route_map=route_map),
        mesh, n_host_args=7)


def make_sharded_paged_decode_step(cfg: ModelConfig, mesh,
                                   backend: str = "reference",
                                   route_map=None):
    """Sharded :func:`make_paged_decode_step`: one jitted shard_map
    advances every shard's decode batch in a single dispatch."""
    return _shard_over_data(
        make_paged_decode_step(cfg, backend=backend, route_map=route_map),
        mesh, n_host_args=5)


# -------------------------------------------------------------- shardings
def _dp(mesh: Mesh):
    return shmod.data_axes(mesh)


def _div(n: int, mesh: Mesh, axes) -> bool:
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return n % size == 0


def batch_shardings(cfg: ModelConfig, mesh: Mesh, batch: int) -> Dict:
    dp = _dp(mesh)
    bspec = dp if _div(batch, mesh, dp) else None
    tok = NamedSharding(mesh, P(bspec, None))
    out = {"tokens": tok, "token": tok}
    if cfg.family == "vlm":
        out["cross_kv"] = NamedSharding(mesh, P(bspec, None, None))
    if cfg.family == "encdec":
        out["src_embeds"] = NamedSharding(mesh, P(bspec, None, None))
    return out


def cache_shardings(caches_shape, cfg: ModelConfig, mesh: Mesh,
                    batch: int, long_context: bool = False):
    """Leaf-name-driven cache shardings. Long-context (batch 1) shards the
    sequence dim over every axis (context parallelism)."""
    dp = _dp(mesh)
    bspec = dp if _div(batch, mesh, dp) else None
    seq_axes = (dp + ("model",)) if long_context and bspec is None \
        else "model"

    def spec_of(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("k", "v"):
            return P(None, bspec, None, seq_axes, None)  # leading scan dim
        if name == "ssm":
            return P(None, bspec, "model", None, None)
        if name in ("conv",):
            return P(None, bspec, None, "model")
        if name == "key_conv_state":
            return P(None, bspec, None, None, None)
        if name == "centroids":
            return P(None, bspec, None, seq_axes, None)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_shape)
    specs = [NamedSharding(mesh, spec_of(path, leaf))
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def model_flops(cfg: ModelConfig, shape: str) -> float:
    """MODEL_FLOPS: 6·N·D (train) / 2·N·D (fwd) with N = active non-dup
    params (MoE: only routed top-k + shared active)."""
    info = ASSIGNED_SHAPES[shape]
    n = api.active_param_count(cfg)
    if not cfg.tie_embeddings:
        n -= cfg.vocab_size * cfg.d_model  # lookup table has ~no matmul
    if info["kind"] == "train":
        return 6.0 * n * info["seq_len"] * info["global_batch"]
    if info["kind"] == "prefill":
        return 2.0 * n * info["seq_len"] * info["global_batch"]
    return 2.0 * n * info["global_batch"]  # decode: one token per seq


def eval_shapes_with_sharding(fn, mesh, *specs_args):
    """eval_shape + attach NamedShardings (helper for dryrun)."""
    shapes = jax.eval_shape(fn, *specs_args)
    return shapes
