"""Decoder-only LM, encoder-decoder and VLM backbones.

Layer layout is a repeating ``cfg.layer_pattern`` of slot kinds:
  dense | swa | moba          — attention block (+ MLP or MoE per family)
  ssm                         — Mamba-2 block
  shared_attn                 — zamba2-style shared-weight attention block
  cross                       — VLM cross-attention block (image memory)
  decoder                     — enc-dec layer (self + cross + MLP)

``num_layers == len(pattern) * n_groups`` and the model scans over groups
with stacked per-slot params — HLO size is O(len(pattern)), not O(layers),
which keeps 100-layer dry-run compiles fast.  `shared_attn` params are a
single (non-scanned) copy applied every group: weight sharing is exact.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE


def _block_kinds(cfg: ModelConfig):
    pattern = cfg.layer_pattern
    assert cfg.num_layers % len(pattern) == 0, (cfg.num_layers, pattern)
    return pattern, cfg.num_layers // len(pattern)


def _is_attn(kind: str) -> bool:
    return kind in ("dense", "swa", "moba", "cross", "decoder")


# ------------------------------------------------------------------ blocks
def init_block(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": jnp.ones((d,), jnp.float32)}
    if kind == "ssm":
        p["mamba"] = M.init_mamba2(ks[0], cfg)
        return p
    p["attn"] = L.init_attention(ks[0], cfg, kind)
    p["norm2"] = jnp.ones((d,), jnp.float32)
    if kind == "decoder":
        p["cross"] = L.init_attention(ks[1], cfg, "cross")
        p["norm_cross"] = jnp.ones((d,), jnp.float32)
    if cfg.family == "moe" and kind != "cross":
        p["moe"] = MOE.init_moe(ks[2], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[2], d, cfg.d_ff)
    return p


def apply_block(p: dict, x: jax.Array, cfg: ModelConfig, kind: str, *,
                positions=None, cache=None, backend="reference",
                cross_kv=None, causal=True, page_state=None,
                head_top_k=None):
    """Pre-LN block. Returns (x, aux_loss, new_cache, expert_load), the
    last the (held,) pairs an expert layer routed to each held expert
    (None for other blocks).

    ``head_top_k``: optional (H,) int32 per-head routing budgets for this
    layer's MoBA attention (adaptive routing profile, DESIGN.md §8)."""
    aux = jnp.zeros((), jnp.float32)
    load = None
    if kind == "ssm":
        h, new_cache = M.apply_mamba2(p["mamba"], L.rms_norm(
            x, p["norm1"], cfg.rms_norm_eps), cfg, cache)
        return x + h, aux, new_cache, load

    attn_kind = {"shared_attn": "dense", "decoder": "moba"
                 if cfg.attention.kind == "moba" else "dense"}.get(kind, kind)
    if kind == "cross":
        with jax.named_scope("attn_cross"):
            h, new_cache = L.apply_attention(
                p["attn"], L.rms_norm(x, p["norm1"], cfg.rms_norm_eps), cfg,
                "cross", positions=positions, cross_kv=cross_kv)
    else:
        self_cache = cache.get("self") if (kind == "decoder"
                                           and cache is not None) else cache
        with jax.named_scope(f"attn_{attn_kind}"):
            h, new_cache = L.apply_attention(
                p["attn"], L.rms_norm(x, p["norm1"], cfg.rms_norm_eps), cfg,
                attn_kind, positions=positions, cache=self_cache,
                backend=backend, causal=causal, page_state=page_state,
                head_top_k=head_top_k)
    x = x + h
    if kind == "decoder":
        with jax.named_scope("attn_cross"):
            h, _ = L.apply_attention(
                p["cross"], L.rms_norm(x, p["norm_cross"], cfg.rms_norm_eps),
                cfg, "cross", positions=positions, cross_kv=cross_kv)
        x = x + h
        new_cache = {"self": new_cache} if new_cache is not None else None
    if "moe" in p:
        with jax.named_scope("moe"):
            h, aux, load = MOE.apply_moe(
                p["moe"], L.rms_norm(x, p["norm2"], cfg.rms_norm_eps), cfg)
    elif "mlp" in p:
        with jax.named_scope("mlp"):
            h = L.apply_mlp(p["mlp"], L.rms_norm(x, p["norm2"],
                                                 cfg.rms_norm_eps))
    else:
        return x, aux, new_cache, load
    return x + h, aux, new_cache, load


# ------------------------------------------------------------------- model
def init_lm(key, cfg: ModelConfig) -> dict:
    pattern, n_groups = _block_kinds(cfg)
    keys = jax.random.split(key, len(pattern) + 4)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(
            keys[-1], (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "blocks": {},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[-2], (cfg.d_model, cfg.vocab_size),
            jnp.float32) * cfg.d_model ** -0.5
    for i, kind in enumerate(pattern):
        if kind == "shared_attn":
            params.setdefault("shared", init_block(keys[i], cfg, "dense"))
            continue
        gkeys = jax.random.split(keys[i], n_groups)
        params["blocks"][f"slot_{i}"] = jax.vmap(
            lambda kk: init_block(kk, cfg, kind))(gkeys)
    if cfg.num_encoder_layers:
        ekeys = jax.random.split(keys[-3], cfg.num_encoder_layers)
        enc_kind = ("moba" if (cfg.attention.kind == "moba"
                               and cfg.encoder_bidirectional_moba)
                    else "dense")
        params["encoder"] = {
            "blocks": jax.vmap(
                lambda kk: init_block(kk, cfg, enc_kind))(ekeys),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
    return params


def apply_encoder(params, src_embeds: jax.Array, cfg: ModelConfig,
                  backend="reference", unroll: bool = False) -> jax.Array:
    """Bidirectional encoder over stub frontend embeddings (B, T, d)."""
    enc_kind = ("moba" if (cfg.attention.kind == "moba"
                           and cfg.encoder_bidirectional_moba) else "dense")
    x = src_embeds.astype(cfg.dtype)

    def body(x, p):
        x, _, _, _ = apply_block(p, x, cfg, enc_kind, causal=False,
                                 backend=backend)
        return x, None

    if unroll:
        for li in range(cfg.num_encoder_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[li],
                                        params["encoder"]["blocks"]))
    else:
        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    return L.rms_norm(x, params["encoder"]["final_norm"], cfg.rms_norm_eps)


def lm_apply(params, tokens: jax.Array, cfg: ModelConfig, *,
             caches: Optional[dict] = None, backend: str = "reference",
             cross_kv: Optional[jax.Array] = None,
             positions: Optional[jax.Array] = None,
             remat: bool = False, unroll: bool = False,
             page_state: Optional[dict] = None,
             route_map: Optional[dict] = None):
    """tokens (B, S) -> (logits (B, S, V), stats, new_caches).

    ``stats``: ``aux_loss``, the expert layers' auxiliary loss summed
    (0 without experts), and for expert models ``expert_load``, the
    (layers, held) pairs each expert layer routed to each expert it holds.

    ``unroll=True`` replaces the layer-group scan with a python loop —
    needed by the dry-run because XLA cost_analysis counts while-loop
    bodies only once (HLO grows O(layers), compile stays tractable via the
    grouped pattern).

    ``route_map``: optional ``{"slot_i": (n_groups, H) int32}`` per-head
    MoBA routing budgets from a calibrated profile (DESIGN.md §8) —
    scanned alongside params/caches so each group's layers see their own
    (H,) rows.  Slots absent from the map (non-MoBA kinds, or all slots
    under static routing) run the static ``top_k``."""
    pattern, n_groups = _block_kinds(cfg)
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    # Megatron-SP residual stream: batch over dp, sequence over model —
    # remat-saved layer inputs shard 16x; SPMD all-gathers around the TP
    # matmuls (sequence length is always a model-axis multiple here).
    x = constrain(x, ("dp", "sp", None) if tokens.shape[1] > 1
                  else ("dp", None, None))

    def group_body(carry, xs):
        x, aux = carry
        gparams, gcaches, groute = xs
        new_gcaches, loads = {}, {}
        for i, kind in enumerate(pattern):
            p_i = (params["shared"] if kind == "shared_attn"
                   else gparams[f"slot_{i}"])
            cache_i = None if gcaches is None else gcaches.get(f"slot_{i}")
            rt_i = None if groute is None else groute.get(f"slot_{i}")
            x, a, nc, ld = apply_block(p_i, x, cfg, kind,
                                       positions=positions, cache=cache_i,
                                       backend=backend,
                                       page_state=page_state,
                                       head_top_k=rt_i,
                                       cross_kv=cross_kv
                                       if kind in ("cross", "decoder")
                                       else None)
            if nc is not None:
                new_gcaches[f"slot_{i}"] = nc
            if ld is not None:
                loads[f"slot_{i}"] = ld
            aux = aux + a
        return (x, aux), (new_gcaches or None, loads or None)

    body = jax.checkpoint(group_body) if remat else group_body
    if unroll:
        carry = (x, jnp.zeros((), jnp.float32))
        ys = []
        for gi in range(n_groups):
            gp = jax.tree.map(lambda a: a[gi], params["blocks"])
            gc = (None if caches is None
                  else jax.tree.map(lambda a: a[gi], caches))
            gr = (None if route_map is None
                  else jax.tree.map(lambda a: a[gi], route_map))
            carry, y = body(carry, (gp, gc, gr))
            ys.append(y)
        (x, aux) = carry
        new_caches, loads = (
            None if ys[0][j] is None else
            jax.tree.map(lambda *a: jnp.stack(a), *[y[j] for y in ys])
            for j in (0, 1))
    else:
        (x, aux), (new_caches, loads) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["blocks"], caches, route_map))
    stats = {"aux_loss": aux}
    if loads is not None:
        # (groups, held) per slot -> (layers, held) in layer order
        stats["expert_load"] = jnp.stack(
            [loads[k] for k in sorted(loads, key=lambda k: int(k[5:]))],
            axis=1).reshape(-1, cfg.moe.held)
    with jax.named_scope("lm_head"):
        x = L.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(dt)
        # force (d replicated, vocab tp-sharded) before the matmul: SPMD then
        # all-gathers the small head slice instead of all-reducing the huge
        # (B,S,V) partial logits (measured 109 GB/device of AR without this).
        head = constrain(head, (None, "tp"))
        logits = x @ head
        logits = constrain(logits, ("dp", None, "tp"))
    return logits, stats, new_caches


def lm_loss(params, batch: dict, cfg: ModelConfig,
            backend: str = "reference", remat: bool = False,
            unroll: bool = False):
    """batch: {'tokens': (B, S+1) int32} → (mean next-token CE + MoE aux,
    metrics: ``ce``, ``aux`` and for expert models ``expert_load``)."""
    tokens = batch["tokens"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    cross_kv = batch.get("cross_kv")
    if cfg.num_encoder_layers and "src_embeds" in batch:
        cross_kv = apply_encoder(params, batch["src_embeds"], cfg,
                                 backend=backend, unroll=unroll)
    logits, stats, _ = lm_apply(params, inp, cfg, backend=backend,
                                cross_kv=cross_kv, remat=remat,
                                unroll=unroll)
    aux = stats["aux_loss"]
    # memory-frugal CE: logsumexp + target gather — never materializes an
    # fp32 (B,S,V) tensor (the convert fuses into the reduction; measured
    # 263GB -> single-digit GB per device on qwen3-0.6b train_4k).
    with jax.named_scope("loss"):
        lse = jax.nn.logsumexp(logits, axis=-1)              # (B,S)
        tgt_logit = jnp.take_along_axis(
            logits, tgt[..., None], axis=-1)[..., 0].astype(jnp.float32)
        ll = tgt_logit - lse.astype(jnp.float32)
        mask = batch.get("mask", jnp.ones_like(tgt, jnp.float32))
        loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    metrics = {"ce": loss, "aux": aux}
    if "expert_load" in stats:
        metrics["expert_load"] = stats["expert_load"]
    return loss + aux, metrics


# -------------------------------------------------------------------- cache
def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> dict:
    """Stacked (n_groups-leading) caches matching the scan layout."""
    pattern, n_groups = _block_kinds(cfg)

    def one_group(_):
        g = {}
        for i, kind in enumerate(pattern):
            if kind == "ssm":
                g[f"slot_{i}"] = M.init_mamba2_cache(cfg, batch, dtype)
            elif kind == "shared_attn":
                g[f"slot_{i}"] = L.init_cache(cfg, "dense", batch, max_len,
                                              dtype)
            elif kind == "cross":
                continue  # cross kv recomputed from image embeddings
            elif kind == "decoder":
                g[f"slot_{i}"] = {"self": L.init_cache(
                    cfg, cfg.attention.kind, batch, max_len, dtype)}
            else:
                g[f"slot_{i}"] = L.init_cache(cfg, kind, batch, max_len,
                                              dtype)
        return g

    return jax.vmap(one_group)(jnp.arange(n_groups))


def init_paged_caches(cfg: ModelConfig, num_pages: int, page_size: int,
                      dtype=jnp.bfloat16, max_seqs: int = 0,
                      prefix_tails: bool = False,
                      kv_dtype: str = "fp32") -> dict:
    """Stacked paged caches (page pools) in the same group/slot layout as
    :func:`init_caches`, so either cache kind flows through the same scan.

    Only attention slots are pageable; recurrent (ssm) and cross/decoder
    slots have no paging granularity — the engine rejects those archs.
    ``max_seqs`` sizes the per-slot key-conv ring buffers on MoBA slots
    of key-conv models (zero skips them — dryrun/inspection use);
    ``prefix_tails`` additionally materializes the per-page raw-key
    tails prefix-cache engines restore ring state from.
    """
    from repro.serving import paged_cache as PC

    pattern, n_groups = _block_kinds(cfg)
    for kind in pattern:
        if kind not in ("dense", "swa", "moba", "shared_attn"):
            raise ValueError(
                f"paged caches support attention-only layer patterns; "
                f"got {kind!r} in {pattern}")

    def one_group(_):
        return {f"slot_{i}": PC.init_page_pool(
                    cfg, num_pages, page_size,
                    with_centroids=(kind == "moba"), dtype=dtype,
                    max_seqs=max_seqs, prefix_tails=prefix_tails,
                    kv_dtype=kv_dtype)
                for i, kind in enumerate(pattern)}

    return jax.vmap(one_group)(jnp.arange(n_groups))


def prefill(params, tokens: jax.Array, cfg: ModelConfig, caches,
            backend="reference", cross_kv=None, unroll: bool = False,
            page_state=None, positions=None, route_map=None):
    """``positions`` defaults to [0, S) (fresh prompts); chunked paged
    prefill passes per-row (B, S) offsets instead."""
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    logits, _, new_caches = lm_apply(
        params, tokens, cfg, caches=caches, backend=backend,
        cross_kv=cross_kv, unroll=unroll, page_state=page_state,
        positions=positions, route_map=route_map)
    return logits, new_caches


def decode_step(params, token: jax.Array, cfg: ModelConfig, caches,
                backend="reference", cross_kv=None, unroll: bool = False,
                page_state=None, route_map=None):
    """token (B, 1) against caches; returns (logits (B,1,V), new_caches).

    With a paged cache the per-sequence position is the scheduler's
    pre-step length; with a dense cache it is the shared cache length."""
    if page_state is not None:
        pos = page_state["kv_len"][:, None]                  # (B,1) ragged
    else:
        pos = _cache_len(caches, cfg) + jnp.arange(1)
    logits, _, new_caches = lm_apply(
        params, token, cfg, caches=caches, backend=backend,
        cross_kv=cross_kv, positions=pos, unroll=unroll,
        page_state=page_state, route_map=route_map)
    return logits, new_caches


def _cache_len(caches, cfg: ModelConfig):
    leaves = [v for k, v in jax.tree_util.tree_flatten_with_path(caches)[0]
              if str(k[-1]) == "DictKey(key='len')" or
              (hasattr(k[-1], "key") and k[-1].key == "len")]
    return leaves[0][0] if leaves else jnp.zeros((), jnp.int32)
