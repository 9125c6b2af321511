"""Attention-backend registry: one seam for every attention implementation.

Implementation choice used to be string-plumbed (``moba_impl`` / ``kind``
branches) through ``core/attention.py``, ``core/moba.py``,
``models/layers.py``, ``models/transformer.py``, ``launch/steps.py`` and
``serving/engine.py``.  This module replaces those branches with a
first-class registry (DESIGN.md §5): an :class:`AttentionBackend` declares
its :class:`Capabilities` (attention kinds × prefill/decode phases ×
dense/paged cache protocols × key-conv), and call sites select by *name +
capability query* via :func:`resolve`.

Registered backends:

  reference     O(N²) masked-softmax oracle (``core/moba.py``)
  xla           pure-XLA gather-and-densify (alias: ``sparse``)
  xla_unrolled  same, unrolled tiles for dry-run FLOP accounting
                (alias: ``sparse_unrolled``)
  flash         Pallas kernels: FlashMoBA prefill + the fused
                scalar-prefetched paged-decode kernel
                (aliases: ``kernel``, ``pallas``)
  sp            context/sequence-parallel MoBA (dense caches only)
  sp_unrolled   same, unrolled (dry-run)
  sharded       multi-host serving seam: per-shard math delegates to an
                inner single-host backend (default ``xla``); the sharded
                engine runs it inside one shard_map over the mesh
                ``data`` axis (``serving/sharded.py``, DESIGN.md §7)

Dense and sliding-window kinds share one implementation across backends
(base-class methods); MoBA is where backends differ.  Paged *prefill* is
deliberately shared too: the ragged reference path is the only
implementation with per-sequence ``kv_len`` masking (DESIGN.md §4).

Run ``python -m repro.core.backends`` to print the capability matrix —
CI uses this as a registry-drift check (every backend must import and
self-validate).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.core.moba import (moba_attention_reference, moba_decode_attention,
                             moba_paged_decode_attention,
                             moba_paged_prefill_attention)
from repro.core.quantization import KV_DTYPES

KINDS = ("dense", "swa", "moba")
PHASES = ("prefill", "decode")
CACHES = ("dense", "paged")


class BackendCapabilityError(ValueError):
    """Requested (backend, kind, phase, cache) combination is unsupported.

    The message names the backends that *do* support the combination, so
    callers (and users reading a traceback) can re-select."""


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend can run.  ``caches`` uses 'dense' for both the
    cache-free (training) and dense-KV-cache paths — they share math —
    and 'paged' for the serving engine's block-table pools.

    ``key_conv`` lists the cache protocols under which the backend can
    consume key-conv'd keys.  The conv itself happens in
    ``models/layers.py`` before keys reach any backend — paged caches
    additionally need the engine's per-slot raw-key ring buffer
    (DESIGN.md §4), so a backend declares the protocols whose conv state
    plumbing it is validated against rather than a single bool.

    ``sharded`` declares the backend safe inside the sharded serving
    engine's per-shard ``shard_map`` body (DESIGN.md §7): its math must
    be mesh-free — no collectives, no axis names — because each shard
    runs it on a local pool slice.  ``sp``/``sp_unrolled`` issue their
    own collectives over a mesh axis and so cannot nest.

    ``kv_dtypes`` lists the paged-pool storage dtypes the backend's
    paged paths are validated against (``core/quantization.py``):
    ``int8``/``fp8`` pools carry per-page scale leaves the backend must
    dequantize with.  Default is fp32-only — quantized support is an
    explicit opt-in so an unvalidated backend fails at admission, not
    with silently-garbage attention output.

    ``adaptive_topk`` declares that the backend's paged MoBA paths honor
    per-(layer, head) ``head_top_k`` budgets (SNR-guided adaptive
    routing, DESIGN.md §8).  ``sp``/``sp_unrolled`` run the dense-cache
    context-parallel fallback whose distributed selection has no
    per-head budget plumbing — they stay static."""

    kinds: Tuple[str, ...] = KINDS
    phases: Tuple[str, ...] = PHASES
    caches: Tuple[str, ...] = CACHES
    key_conv: Tuple[str, ...] = CACHES
    sharded: bool = True
    kv_dtypes: Tuple[str, ...] = ("fp32",)
    adaptive_topk: bool = True

    def supports(self, kind: str, phase: str, cache: str = "dense",
                 key_conv: bool = False, sharded: bool = False,
                 kv_dtype: str = "fp32", adaptive: bool = False) -> bool:
        return (kind in self.kinds and phase in self.phases
                and cache in self.caches
                and (not key_conv or cache in self.key_conv)
                and (not sharded or self.sharded)
                and kv_dtype in self.kv_dtypes
                and (not adaptive or self.adaptive_topk))


class AttentionBackend:
    """Protocol + shared implementations.

    Subclasses override the ``moba_*`` hooks; dense/swa attention and the
    paged-prefill path are shared (see module docstring).  ``**opts``
    carries backend-specific hints (e.g. ``interpret`` for Pallas) that
    other backends ignore.
    """

    name: str = ""
    aliases: Tuple[str, ...] = ()
    capabilities: Capabilities = Capabilities()

    # ------------------------------------------------------------ helpers
    @staticmethod
    def _window(cfg: AttentionConfig, kind: str) -> int:
        return cfg.window if kind == "swa" else 0

    # ------------------------------------------- full-sequence / dense KV
    def prefill(self, cfg: AttentionConfig, kind: str, q, k, v, *,
                q_positions=None, kv_len=None, causal: bool = True,
                **opts) -> jax.Array:
        """Multi-token attention: training, prefill, or cached prefill
        (``kv_len`` marks the valid prefix of a dense cache).

        Causal SWA over whole sequences at positions ``0..n-1`` (no
        cache, no explicit positions: training) scores only the band
        around the diagonal; every other call takes the dense path."""
        if kind == "moba":
            return self.moba_prefill(cfg, q, k, v, q_positions=q_positions,
                                     **opts)
        from repro.core.attention import (banded_window_attention,
                                          dense_attention)
        window = self._window(cfg, kind)
        if (window and causal and kv_len is None and q_positions is None
                and q.shape[2] == k.shape[2]):
            return banded_window_attention(q, k, v, window, scale=cfg.scale)
        return dense_attention(q, k, v, causal=causal,
                               q_positions=q_positions, kv_len=kv_len,
                               window=window, scale=cfg.scale)

    def decode(self, cfg: AttentionConfig, kind: str, q, k, v, kv_len, *,
               centroids=None, q_positions=None, **opts) -> jax.Array:
        """Single-token attention against a dense cache of which the first
        ``kv_len`` positions are valid."""
        if kind == "moba":
            return self.moba_decode(cfg, q, k, v, kv_len,
                                    centroids=centroids, **opts)
        from repro.core.attention import dense_attention
        return dense_attention(q, k, v, causal=True,
                               q_positions=q_positions, kv_len=kv_len,
                               window=self._window(cfg, kind),
                               scale=cfg.scale)

    # --------------------------------------------------------- paged KV
    def paged_prefill(self, cfg: AttentionConfig, kind: str, q, k, v, *,
                      post_len, positions, **opts) -> jax.Array:
        """Ragged fresh prefill (right-padded rows; ``post_len`` is the
        per-sequence valid length after this step).  Shared across
        backends: the reference path is the only implementation with
        per-sequence kv_len masking, and routing a padded row is harmless
        (DESIGN.md §4)."""
        if kind == "moba":
            return moba_attention_reference(
                q, k, v, cfg.moba, q_positions=positions,
                kv_len=post_len[:, None, None, None], scale=cfg.scale,
                head_top_k=opts.get("head_top_k"))
        from repro.core.attention import dense_attention
        return dense_attention(q, k, v, causal=True, q_positions=positions,
                               kv_len=post_len,
                               window=self._window(cfg, kind),
                               scale=cfg.scale)

    def paged_chunk_prefill(self, cfg: AttentionConfig, kind: str, q, cache,
                            block_table, kv_len, q_len,
                            **opts) -> jax.Array:
        """Chunked prefill: multi-token attention for a ragged chunk whose
        K/V (and every earlier chunk's) are already appended to ``cache``.
        ``kv_len`` is the per-sequence pre-chunk length, ``q_len`` the
        chunk's valid tokens, so query i,j sits at position
        ``kv_len[i] + j``.  Shared across backends like
        :meth:`paged_prefill`: MoBA routes the chunk's queries on the
        per-page centroid cache (bitwise the same page selection as
        one-shot prefill — complete pages have identical centroids and
        partial pages are only ever force-included, DESIGN.md §6) and the
        dense/swa kinds densify through the block table."""
        from repro.serving import paged_cache as PC
        if kind == "moba":
            return moba_paged_prefill_attention(
                q, cache["pages_k"], cache["pages_v"], cache["centroids"],
                block_table, kv_len, q_len, cfg.moba, scale=cfg.scale,
                scales_k=cache.get("scales_k"),
                scales_v=cache.get("scales_v"),
                head_top_k=opts.get("head_top_k"))
        kf, vf = PC.paged_gather_kv(cache, block_table)
        from repro.core.attention import dense_attention
        return dense_attention(q, kf, vf, causal=True,
                               q_positions=kv_len[:, None]
                               + jnp.arange(q.shape[2]),
                               kv_len=kv_len + q_len,
                               window=self._window(cfg, kind),
                               scale=cfg.scale)

    def paged_decode(self, cfg: AttentionConfig, kind: str, q, cache,
                     block_table, kv_len, *, positions=None,
                     **opts) -> jax.Array:
        """Single-token attention against a paged pool through the block
        table.  ``kv_len`` is the post-append per-sequence length.  SWA
        gathers only the ~ceil(window/page_size)+1 pages inside the
        window; dense necessarily densifies the table."""
        from repro.serving import paged_cache as PC
        if kind == "moba":
            return self.moba_paged_decode(cfg, q, cache, block_table,
                                          kv_len, **opts)
        if kind == "swa":
            return PC.swa_windowed_decode_attention(
                q, cache, block_table, kv_len, cfg.window, scale=cfg.scale)
        kf, vf = PC.paged_gather_kv(cache, block_table)
        from repro.core.attention import dense_attention
        return dense_attention(q, kf, vf, causal=True,
                               q_positions=positions, kv_len=kv_len,
                               scale=cfg.scale)

    # ------------------------------------------------ MoBA-specific hooks
    def moba_prefill(self, cfg: AttentionConfig, q, k, v, *,
                     q_positions=None, **opts) -> jax.Array:
        raise NotImplementedError(f"{self.name}: moba prefill")

    def moba_decode(self, cfg: AttentionConfig, q, k, v, kv_len, *,
                    centroids=None, **opts) -> jax.Array:
        # block routing is implementation-independent at decode; the XLA
        # gather path is the shared dense-cache implementation
        return moba_decode_attention(q, k, v, kv_len, cfg.moba,
                                     scale=cfg.scale, centroids=centroids)

    def moba_paged_decode(self, cfg: AttentionConfig, q, cache, block_table,
                          kv_len, **opts) -> jax.Array:
        return moba_paged_decode_attention(
            q, cache["pages_k"], cache["pages_v"], cache["centroids"],
            block_table, kv_len, cfg.moba, scale=cfg.scale,
            scales_k=cache.get("scales_k"), scales_v=cache.get("scales_v"),
            head_top_k=opts.get("head_top_k"))


# ---------------------------------------------------------------- backends
class ReferenceBackend(AttentionBackend):
    """O(N²) masked-softmax oracle — the correctness anchor."""

    name = "reference"

    def moba_prefill(self, cfg, q, k, v, *, q_positions=None, **opts):
        return moba_attention_reference(q, k, v, cfg.moba,
                                        q_positions=q_positions,
                                        scale=cfg.scale)


class XLABackend(AttentionBackend):
    """Pure-XLA gather-and-densify (production fallback, differentiable)."""

    name = "xla"
    aliases = ("sparse",)
    capabilities = Capabilities(kv_dtypes=KV_DTYPES)
    use_scan = True

    def moba_prefill(self, cfg, q, k, v, *, q_positions=None, **opts):
        from repro.kernels import ref
        return ref.moba_sparse_xla(q, k, v, cfg.moba,
                                   q_positions=q_positions, scale=cfg.scale,
                                   use_scan=self.use_scan)


class XLAUnrolledBackend(XLABackend):
    """Unrolled tiles: XLA cost_analysis counts scan bodies once — the
    dry-run needs this form for faithful FLOP accounting."""

    name = "xla_unrolled"
    aliases = ("sparse_unrolled",)
    use_scan = False


class FlashBackend(AttentionBackend):
    """Pallas kernel path: FlashMoBA prefill (DESIGN.md §2) + the fused
    scalar-prefetched paged-decode kernel (DESIGN.md §5).  Dense-cache
    decode shares the XLA gather (routing math is identical; the kernel
    pays off where the block table gives page-granular indirection)."""

    name = "flash"
    aliases = ("kernel", "pallas")
    capabilities = Capabilities(kv_dtypes=KV_DTYPES)
    # interpret vs compiled Pallas lowering.  None defers to
    # `kernels.runtime.resolve_interpret`: the REPRO_PALLAS_INTERPRET
    # env var if set, else compiled on TPU hosts / interpret everywhere
    # else — CPU CI and a TPU pod run the same code with no edits.
    # Override per call via opts, per process via
    # `backends.get("flash").interpret = False`, or per CLI via
    # `--attn-backend flash:compiled` (see :func:`parse_backend_spec`).
    interpret: Optional[bool] = None
    # paged-decode grid: "grouped" = (B·Hkv, U) MXU tiles (default),
    # "flat" = legacy (B·H, top_k) per-query-head VPU products
    decode_grid: str = "grouped"
    # training/prefill grid: "grouped" = grouped-GQA topk + kb-tiled
    # fwd/bwd MXU grids (default), "flat" = legacy seed-era grids kept
    # selectable for bisection (DESIGN.md §2)
    train_grid: str = "grouped"
    # K/V streaming granularity of the kb-tiled fwd/bwd grids;
    # 0 = auto (min(block_size, 128)).  Set via `flash:kb_tile=N`.
    kb_tile: int = 0

    def _interpret(self, opts) -> bool:
        from repro.kernels.runtime import resolve_interpret
        return resolve_interpret(opts.get("interpret", self.interpret))

    def moba_prefill(self, cfg, q, k, v, *, q_positions=None, **opts):
        from repro.kernels import ops
        return ops.flash_moba(q, k, v, cfg.moba, q_positions=q_positions,
                              scale=cfg.scale,
                              kb_tile=opts.get("kb_tile", self.kb_tile),
                              grid=opts.get("grid", self.train_grid),
                              interpret=self._interpret(opts))

    def moba_paged_decode(self, cfg, q, cache, block_table, kv_len, **opts):
        from repro.kernels import moba_decode
        return moba_decode.moba_paged_decode_pallas(
            q, cache["pages_k"], cache["pages_v"], cache["centroids"],
            block_table, kv_len, cfg.moba, scale=cfg.scale,
            interpret=self._interpret(opts),
            grid=opts.get("grid", self.decode_grid),
            scales_k=cache.get("scales_k"), scales_v=cache.get("scales_v"),
            head_top_k=opts.get("head_top_k"))


class SPBackend(AttentionBackend):
    """Sequence/context-parallel MoBA (distributed/moba_sp.py).  Dense
    caches only, and never inside the sharded engine's shard_map (it
    issues its own collectives over a mesh axis); the sharded engine
    instead uses it *around* the paged path as the context-parallel
    fallback for requests longer than one shard's pool (DESIGN.md §7)."""

    name = "sp"
    capabilities = Capabilities(caches=("dense",), key_conv=("dense",),
                                sharded=False, adaptive_topk=False)
    use_scan = True

    def moba_prefill(self, cfg, q, k, v, *, q_positions=None, **opts):
        from repro.distributed.moba_sp import moba_attention_sp
        return moba_attention_sp(q, k, v, cfg.moba, scale=cfg.scale,
                                 q_positions=q_positions,
                                 use_scan=self.use_scan)

    def moba_decode(self, cfg, q, k, v, kv_len, *, centroids=None, **opts):
        from repro.distributed.moba_sp import moba_decode_cp
        return moba_decode_cp(q, k, v, kv_len, cfg.moba, scale=cfg.scale,
                              centroids=centroids)


class SPUnrolledBackend(SPBackend):
    name = "sp_unrolled"
    use_scan = False


class ShardedBackend(AttentionBackend):
    """Multi-host serving backend (DESIGN.md §7): the name the sharded
    engine's admission query resolves.  Per-shard attention math is
    delegated to a mesh-free ``inner`` backend (default ``xla``) — the
    sharding itself lives in the engine's ``shard_map``-wrapped step
    functions (``launch/steps.py``), not in the attention math, which is
    exactly why a shard's tokens are bit-identical to a single-host
    engine's.  Usable on a single host too (it is just ``inner`` then).
    """

    name = "sharded"
    capabilities = Capabilities(kv_dtypes=KV_DTYPES)
    inner = "xla"

    def _delegate(self, opts) -> AttentionBackend:
        return get(opts.pop("inner", None) or self.inner)

    def moba_prefill(self, cfg, q, k, v, *, q_positions=None, **opts):
        return self._delegate(opts).moba_prefill(
            cfg, q, k, v, q_positions=q_positions, **opts)

    def moba_decode(self, cfg, q, k, v, kv_len, *, centroids=None, **opts):
        return self._delegate(opts).moba_decode(
            cfg, q, k, v, kv_len, centroids=centroids, **opts)

    def moba_paged_decode(self, cfg, q, cache, block_table, kv_len, **opts):
        return self._delegate(opts).moba_paged_decode(
            cfg, q, cache, block_table, kv_len, **opts)


# ---------------------------------------------------------------- registry
_REGISTRY: Dict[str, AttentionBackend] = {}
_ALIASES: Dict[str, str] = {}


def register(backend: AttentionBackend) -> AttentionBackend:
    assert backend.name, "backend must set a name"
    for key in (backend.name,) + backend.aliases:
        taken = _ALIASES.get(key)
        assert taken is None or taken == backend.name, (
            f"backend name/alias {key!r} already registered for {taken!r}")
    _REGISTRY[backend.name] = backend
    for key in (backend.name,) + backend.aliases:
        _ALIASES[key] = backend.name
    return backend


def names() -> Tuple[str, ...]:
    """Canonical backend names (aliases excluded), registration order."""
    return tuple(_REGISTRY)


def get(name: str) -> AttentionBackend:
    canonical = _ALIASES.get(name)
    if canonical is None:
        raise BackendCapabilityError(
            f"unknown attention backend {name!r}; registered: "
            f"{sorted(_ALIASES)}")
    return _REGISTRY[canonical]


def parse_backend_spec(spec: str) -> str:
    """``name[:option,...]`` → registered backend name, applying each
    option to the backend instance — the one string every
    CLI/EngineConfig surface accepts (``--attn-backend flash:compiled``,
    ``--attn-backend flash:flat,kb_tile=64``).

    Options: ``interpret`` / ``compiled`` toggle the Pallas lowering on
    backends that expose an ``interpret`` attribute (process-wide, like
    setting ``backends.get(name).interpret`` directly); ``grouped`` /
    ``flat`` select the kernel grids — both the paged-decode grid
    (``decode_grid``) and the training/prefill grid (``train_grid``) on
    backends carrying those attributes; ``kb_tile=N`` sets the K/V
    streaming granularity of the kb-tiled training grids (0 = auto).
    Unknown names or options raise :class:`BackendCapabilityError`.
    """
    name, _, optstr = spec.partition(":")
    if not optstr:
        return name
    be = get(name)
    for opt in optstr.split(","):
        opt = opt.strip()
        if opt in ("interpret", "compiled"):
            if not hasattr(be, "interpret"):
                raise BackendCapabilityError(
                    f"backend {be.name!r} has no interpret/compiled toggle "
                    f"(only Pallas backends do); got {spec!r}")
            be.interpret = opt == "interpret"
        elif opt in ("grouped", "flat"):
            if not hasattr(be, "decode_grid") \
                    and not hasattr(be, "train_grid"):
                raise BackendCapabilityError(
                    f"backend {be.name!r} has no decode-grid option; "
                    f"got {spec!r}")
            if hasattr(be, "decode_grid"):
                be.decode_grid = opt
            if hasattr(be, "train_grid"):
                be.train_grid = opt
        elif opt.startswith("kb_tile="):
            if not hasattr(be, "kb_tile"):
                raise BackendCapabilityError(
                    f"backend {be.name!r} has no kb_tile option (only the "
                    f"kb-tiled Pallas training grids do); got {spec!r}")
            try:
                be.kb_tile = int(opt.split("=", 1)[1])
            except ValueError:
                raise BackendCapabilityError(
                    f"unknown backend option {opt!r} in {spec!r}: "
                    f"kb_tile takes an integer (0 = auto)") from None
        else:
            raise BackendCapabilityError(
                f"unknown backend option {opt!r} in {spec!r}; expected "
                f"interpret | compiled | grouped | flat | kb_tile=N")
    return name


def resolve_backend_spec(spec: Optional[str], *,
                         default: str = "reference") -> str:
    """THE backend-spec resolver every surface shares — ``Engine``,
    ``ShardedEngine``, and the train/serve CLIs all funnel through this
    one function so their spec handling cannot drift.

    An empty/None ``spec`` falls back to ``default`` (each surface's
    documented default backend); otherwise the ``name[:option,...]``
    string is parsed by :func:`parse_backend_spec` (applying options to
    the registry instance) and the name is validated eagerly against
    the registry, so an unknown backend fails at config time with a
    :class:`BackendCapabilityError` instead of inside the first jitted
    step.  Returns the backend name as given (aliases preserved —
    ``get`` canonicalizes at use)."""
    spec = (spec or "").strip() or default
    name = parse_backend_spec(spec)
    get(name)
    return name


def resolve(name: str, *, kind: str, phase: str, cache: str = "dense",
            key_conv: bool = False, sharded: bool = False,
            kv_dtype: str = "fp32", adaptive: bool = False
            ) -> AttentionBackend:
    """Name + capability query: the single entry point call sites use.
    ``sharded=True`` additionally demands mesh-free per-shard math (the
    sharded serving engine's admission query, DESIGN.md §7);
    ``kv_dtype`` of ``int8``/``fp8`` demands quantized-pool support
    (per-page scale dequantization in every paged path);
    ``adaptive=True`` demands per-head ``head_top_k`` routing support
    (SNR-guided adaptive routing, DESIGN.md §8)."""
    be = get(name)
    if not be.capabilities.supports(kind, phase, cache, key_conv, sharded,
                                    kv_dtype, adaptive):
        able = [b.name for b in _REGISTRY.values()
                if b.capabilities.supports(kind, phase, cache, key_conv,
                                           sharded, kv_dtype, adaptive)]
        raise BackendCapabilityError(
            f"backend {be.name!r} does not support kind={kind!r} "
            f"phase={phase!r} cache={cache!r} key_conv={key_conv} "
            f"sharded={sharded} kv_dtype={kv_dtype!r} adaptive={adaptive}; "
            f"backends that do: {able}")
    return be


for _be in (ReferenceBackend(), XLABackend(), XLAUnrolledBackend(),
            FlashBackend(), SPBackend(), SPUnrolledBackend(),
            ShardedBackend()):
    register(_be)


def capability_matrix() -> str:
    """Human-readable support table (also the CI registry-drift check)."""
    lines = [f"{'backend':<14}{'aliases':<22}{'kinds':<18}"
             f"{'phases':<18}{'caches':<14}{'key_conv':<14}"
             f"{'sharded':<10}{'adaptive':<10}kv_dtypes"]
    for be in _REGISTRY.values():
        c = be.capabilities
        lines.append(f"{be.name:<14}{','.join(be.aliases) or '-':<22}"
                     f"{','.join(c.kinds):<18}{','.join(c.phases):<18}"
                     f"{','.join(c.caches):<14}{','.join(c.key_conv):<14}"
                     f"{'yes' if c.sharded else '-':<10}"
                     f"{'yes' if c.adaptive_topk else '-':<10}"
                     f"{','.join(c.kv_dtypes)}")
    return "\n".join(lines)


_DOCS_BEGIN = "<!-- capability-matrix:begin (generated) -->"
_DOCS_END = "<!-- capability-matrix:end -->"


def sync_docs(path: str) -> bool:
    """Rewrite the generated capability-matrix block of ``path`` (between
    the begin/end markers).  Returns True when the file changed — CI runs
    this and fails on a dirty diff, so docs/backends.md can never drift
    from the registry."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    b, e = text.index(_DOCS_BEGIN), text.index(_DOCS_END)
    block = (f"{_DOCS_BEGIN}\n```\n{capability_matrix()}\n```\n")
    new = text[:b] + block + text[e:]
    if new == text:
        return False
    with open(path, "w", encoding="utf-8") as f:
        f.write(new)
    return True


def _main(argv=None) -> int:
    import sys
    argv = sys.argv[1:] if argv is None else argv
    # drift check: every backend constructs, every alias resolves to a
    # registered backend, and at least one backend covers each
    # (kind, phase, cache) cell that the serving engine needs.
    assert names(), "registry is empty"
    for alias, canonical in _ALIASES.items():
        assert get(alias) is _REGISTRY[canonical], alias
    for kind in KINDS:
        for phase in PHASES:
            for cache in CACHES:
                able = [b for b in _REGISTRY.values()
                        if b.capabilities.supports(kind, phase, cache)]
                assert able, f"no backend covers {kind}/{phase}/{cache}"
    if argv and argv[0] == "--sync-docs":
        path = argv[1] if len(argv) > 1 else "docs/backends.md"
        changed = sync_docs(path)
        print(f"{path}: {'updated' if changed else 'up to date'}")
        return 0
    print(capability_matrix())
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
