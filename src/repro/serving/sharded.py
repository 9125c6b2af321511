"""Sharded multi-host serving engine (DESIGN.md §7).

The single-host :class:`~repro.serving.engine.Engine` owns ONE page pool
and ONE scheduler; this module scales it over the mesh ``data`` axis
without changing any attention math:

  * every shard owns its own slice of the paged state — page pool,
    centroid cache, key-conv ring buffers — stacked along a leading
    shard dim and laid out over ``data`` (`paged_cache.shard_pools`);
  * a host-side :class:`Router` assigns each incoming request to the
    least-loaded shard, after which its whole lifetime (admission,
    growth, preemption, replay) is handled by that shard's own
    :class:`~repro.serving.scheduler.Scheduler`;
  * each engine step runs at most one jitted ``shard_map`` prefill and
    one jitted ``shard_map`` decode across ALL shards
    (`launch/steps.make_sharded_paged_*`): inside the body each device
    strips its local pool slice and runs the unmodified single-host
    step, so zero collectives cross shards and a request's greedy
    tokens are bit-identical to the single-host engine's
    (`tests/test_sharded_serving.py`);
  * a single request longer than one shard's pool cannot be paged — it
    falls back to context-parallel decode over the same devices
    (`distributed/moba_sp.moba_decode_cp`), routing on shard-local
    centroids from the dense cache's incremental centroid cache.

Prefill rows are padded to ONE bucket computed from the global longest
take via the pure function :func:`~repro.serving.engine.prefill_bucket`
— bucket sizes are shard-invariant by construction (asserted), so the
jit cache holds one prefill variant per bucket engine-wide instead of
fragmenting per shard.
"""
from __future__ import annotations

import collections
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShardingConfig
from repro.core import quantization as Q
from repro.distributed import sharding as shmod
from repro.launch import steps as S
from repro.models import transformer as T
from repro.serving import paged_cache as PC
from repro.serving.engine import (EngineConfig, HostSwapStore, Prefix,
                                  admission_capability_check,
                                  build_decode_batch, build_prefill_batch,
                                  build_route_profile, drain_cache_ops,
                                  needs_key_conv, prefill_bucket,
                                  prefill_takes, record_prefill,
                                  resolve_engine_backend,
                                  resolve_pool_sizes, unsupported_reason)
from repro.serving.scheduler import (Request, Scheduler, ServingError,
                                     UnsupportedFeatureError)


class Router:
    """Host-side router over per-shard schedulers.

    ``pick`` prefers the shard whose prefix tree holds the longest
    cached prefix of the request (an LRU-neutral ``peek_prefix`` — each
    shard's tree is private, so affinity is what turns shared system
    prompts into cross-request page sharing), then the smallest
    page-demand ``load`` (committed + queued pages), ties broken by
    lowest shard id — fully deterministic for a given submission order,
    which the equivalence suite relies on.  Without the prefix cache
    every peek is 0 and this reduces to pure least-loaded routing.
    Returns −1 when no shard can serve it (context-parallel fallback or
    rejection is the engine's call)."""

    def __init__(self, scheds: Sequence[Scheduler]):
        self.scheds = scheds

    def pick(self, req: Request) -> int:
        fitting = [s for s, sch in enumerate(self.scheds) if sch.fits(req)]
        if not fitting:
            return -1
        return min(fitting, key=lambda s: (-self.scheds[s].peek_prefix(req),
                                           self.scheds[s].load, s))


class ShardedEngine:
    """Continuous-batching engine whose page pools are sharded over the
    mesh ``data`` axis.  ``ecfg`` sizes are PER SHARD (``max_seqs``
    slots and ``num_pages`` pages on every shard); total capacity is
    ``n_shards`` times that.  API mirrors :class:`Engine`:
    ``submit`` / ``step`` / ``run`` / ``stats`` (+ ``shard_stats``)."""

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: Optional[EngineConfig] = None, n_shards: int = 2,
                 mesh=None):
        reason = unsupported_reason(cfg)
        if reason is not None:
            raise UnsupportedFeatureError(*reason)
        self.cfg = cfg
        self.ecfg = ecfg = ecfg or EngineConfig()
        self.attn_backend = resolve_engine_backend(
            ecfg.attn_backend, "sharded")
        if ecfg.dispatch_ahead < 0:
            raise ServingError(
                f"dispatch_ahead must be >= 0, got {ecfg.dispatch_ahead}")
        if mesh is None:
            if n_shards > len(jax.devices()):
                raise ServingError(
                    f"n_shards={n_shards} exceeds the {len(jax.devices())}"
                    f" visible devices; simulate with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N")
            mesh = shmod.auto_mesh((n_shards,), ("data",))
        if "data" not in mesh.axis_names:
            raise ServingError(
                f"sharded engine needs a 'data' mesh axis, got "
                f"{mesh.axis_names}")
        self.mesh = mesh
        self.n_shards = ns = mesh.shape["data"]
        # same admission query as Engine, additionally demanding the
        # backend's per-shard math is mesh-free (Capabilities.sharded)
        if ecfg.kv_dtype not in Q.KV_DTYPES:
            raise ServingError(
                f"unknown kv_dtype {ecfg.kv_dtype!r}; expected one of "
                f"{Q.KV_DTYPES}")
        from repro.core.adaptive import parse_route_policy
        try:
            route_mode, _ = parse_route_policy(ecfg.route_policy)
        except ValueError as e:
            raise UnsupportedFeatureError("route_policy", str(e)) from e
        admission_capability_check(cfg, self.attn_backend, sharded=True,
                                   kv_dtype=ecfg.kv_dtype,
                                   adaptive=route_mode != "static")
        self.page_size, self.pages_per_seq, self.num_pages = \
            resolve_pool_sizes(cfg, ecfg)
        # ONE routing profile, calibrated (or loaded) once and embedded
        # as a replicated closure constant of the shard_map steps —
        # every shard routes identically, so a request's tokens cannot
        # depend on which shard the router picked (shard invariance).
        # The context-parallel fallback (`_run_cp`, dense caches on the
        # ``sp`` backend) has no per-head budget plumbing and stays on
        # static routing — a documented limitation (docs/serving.md).
        self.route_profile, self._route_map = build_route_profile(
            cfg, params, ecfg.route_policy, self.pages_per_seq)
        self.params = jax.device_put(params, NamedSharding(mesh, P()))
        conv = needs_key_conv(cfg)
        if ecfg.prefix_cache and conv \
                and cfg.attention.moba.key_conv_width - 1 > self.page_size:
            raise ServingError(
                f"prefix cache needs key_conv_width - 1 "
                f"({cfg.attention.moba.key_conv_width - 1}) <= page_size "
                f"({self.page_size}): ring state restores from one "
                f"page's raw-key tail")
        base = T.init_paged_caches(cfg, self.num_pages, self.page_size,
                                   dtype=jnp.dtype(cfg.dtype),
                                   max_seqs=ecfg.max_seqs,
                                   prefix_tails=ecfg.prefix_cache and conv,
                                   kv_dtype=ecfg.kv_dtype)
        self.caches = PC.shard_pools(base, mesh, ns)
        # one swap store per shard: its byte cap and ``used`` accounting
        # pair with that shard's scheduler, and saves/restores slice the
        # stacked pools at the shard index
        self.swap_stores = [
            HostSwapStore(self, ecfg.swap_bytes, shard=s)
            if ecfg.swap_bytes > 0 else None for s in range(ns)]
        self.scheds = [Scheduler(
            num_pages=self.num_pages, page_size=self.page_size,
            max_seqs=ecfg.max_seqs, max_pages_per_seq=self.pages_per_seq,
            max_prefill_batch=ecfg.max_prefill_batch,
            chunk_tokens=ecfg.prefill_chunk,
            prefix_cache=ecfg.prefix_cache, key_conv=conv,
            full_page_match=ecfg.kv_dtype != "fp32",
            swap=self.swap_stores[s]) for s in range(ns)]
        self.router = Router(self.scheds)
        self._chunk_aware = bool(ecfg.prefill_chunk or ecfg.prefix_cache
                                 or ecfg.swap_bytes > 0)
        self._prefill = jax.jit(
            S.make_sharded_paged_prefill_step(
                cfg, mesh, backend=self.attn_backend,
                chunked=self._chunk_aware, route_map=self._route_map),
            donate_argnums=(2,))
        self._decode = jax.jit(
            S.make_sharded_paged_decode_step(cfg, mesh,
                                             backend=self.attn_backend,
                                             route_map=self._route_map),
            donate_argnums=(2,))
        self._cur_tok = np.zeros((ns, ecfg.max_seqs), np.int32)
        self._next_rid = 0
        self._t0 = None
        self.finished: List[Request] = []
        # dispatch-ahead pipeline (mirrors Engine's): entries are
        # (per_shard request lists, the step's (ns, max_seqs) token
        # array still on device)
        self._inflight: Deque[Tuple[List[List[Request]], jax.Array]] = \
            collections.deque()
        self._tok_dev = None
        self._emitted: List[Tuple[Request, int]] = []
        for sch in self.scheds:
            sch.before_preempt = self._sync_for_preempt
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0,
                      "prefill_tokens": 0, "decode_steps": 0,
                      "decode_tokens": 0, "preemptions": 0,
                      "cp_requests": 0, "cp_tokens": 0, "cp_s": 0.0,
                      "tree_evictions": 0, "pages_in_use_peak": 0,
                      "dispatch_depth_peak": 0, "pipeline_drains": 0}
        for k in self.scheds[0].stats:
            self.stats[k] = 0
        self.shard_stats = [{"prefill_tokens": 0, "decode_tokens": 0,
                             "requests": 0} for _ in range(ns)]
        # jit-cache hygiene: every prefill width ever compiled (the
        # shard-invariance regression test asserts this stays one entry
        # per distinct global bucket, never one per shard)
        self.prefill_widths: set = set()
        # context-parallel fallback state (built lazily on first use)
        self._cp_queue: Deque[Request] = collections.deque()
        self._cp_mesh = None
        self._cp_prefill = None
        self._cp_decode = None

    # ------------------------------------------------------------- intake
    def make_request(self, prompt: Sequence[int], max_new_tokens: int,
                     arrival: float = 0.0, eos_id: Optional[int] = None
                     ) -> Request:
        """Build a request WITHOUT queueing it — the staged intake.
        Routing happens at :meth:`prefill`; over-long requests that only
        the context-parallel fallback can serve must go through
        :meth:`submit` + the legacy loop instead."""
        req = Request(rid=self._next_rid,
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, arrival=arrival,
                      eos_id=eos_id)
        self._next_rid += 1
        return req

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               arrival: float = 0.0, eos_id: Optional[int] = None
               ) -> Request:
        req = self.make_request(prompt, max_new_tokens, arrival, eos_id)
        shard = self.router.pick(req)
        if shard < 0:
            need = len(req.prompt) + max_new_tokens
            if need > self.cp_capacity:
                raise ServingError(
                    f"request {req.rid}: prompt+gen {need} tokens exceed "
                    f"even the context-parallel capacity "
                    f"{self.cp_capacity} ({self.n_shards} shards)")
            self._cp_queue.append(req)       # longer than one shard's pool
            return req
        req.shard = shard
        self.scheds[shard].submit(req)
        self.shard_stats[shard]["requests"] += 1
        return req

    # --------------------------------------------------------------- sizes
    @property
    def shard_capacity(self) -> int:
        """Tokens one shard's pool can hold."""
        return self.num_pages * self.page_size

    @property
    def cp_capacity(self) -> int:
        """Max context the context-parallel fallback can decode: the
        fleet-wide pool equivalent, dense-cached over all shards."""
        return self.n_shards * self.shard_capacity

    # -------------------------------------------------------------- steps
    def _run_prefill(self, per_shard: List[List[Request]]) -> None:
        """One shard_map prefill over every shard's batch.  All shards
        pad to ONE bucket derived from the global longest take via the
        pure :func:`prefill_bucket`, so the jit cache holds one prefill
        variant per bucket engine-wide instead of one per shard."""
        ns, bp = self.n_shards, self.ecfg.max_prefill_batch
        takes = [prefill_takes(reqs, self.ecfg.prefill_chunk)
                 for reqs in per_shard]
        gmax = max(max(t) for t in takes if t)
        lmax = prefill_bucket(gmax, self.page_size)
        self.prefill_widths.add(lmax)
        rows = [build_prefill_batch(self.scheds[s], per_shard[s], takes[s],
                                    bp, self.pages_per_seq, lmax)
                for s in range(ns)]
        # shard-invariant bucketing: every shard's rows must be padded to
        # the one global bucket — fires if a refactor reintroduces
        # per-shard local buckets (the jit-cache fragmentation bug)
        assert all(r[0].shape == (bp, lmax) for r in rows), \
            [r[0].shape for r in rows]
        tokens, kv_len, q_len, slots, active, table = (
            np.stack([r[i] for r in rows]) for i in range(6))
        t0 = time.perf_counter()
        tok, self.caches = self._prefill(
            self.params, jnp.asarray(tokens), self.caches,
            jnp.asarray(table), jnp.asarray(kv_len), jnp.asarray(q_len),
            jnp.asarray(slots), jnp.asarray(active))
        tok = np.asarray(tok)
        self.stats["prefill_s"] += time.perf_counter() - t0
        wall = self._wall()
        for s in range(ns):
            n_tok = int(sum(takes[s]))
            self.stats["prefill_tokens"] += n_tok
            self.shard_stats[s]["prefill_tokens"] += n_tok
            record_prefill(per_shard[s], takes[s], tok[s],
                           self._cur_tok[s], wall)

    def _wall(self) -> float:
        return (0.0 if self._t0 is None
                else time.perf_counter() - self._t0)

    # ------------------------------------------- dispatch-ahead pipeline
    def _dispatch_decode(self, per_shard: List[List[Request]]) -> None:
        """Enqueue one shard_map decode step across ALL shards without
        blocking on its tokens (see ``Engine._dispatch_decode``)."""
        ns, ms = self.n_shards, self.ecfg.max_seqs
        rows = [build_decode_batch(reqs, ms) for reqs in per_shard]
        kv_len = np.stack([r[0] for r in rows])
        active = np.stack([r[1] for r in rows])
        table = np.stack([sch.block_table for sch in self.scheds])
        if self._tok_dev is None:
            self._tok_dev = jnp.asarray(self._cur_tok)
        t0 = time.perf_counter()
        tok, self.caches = self._decode(
            self.params, self._tok_dev, self.caches,
            jnp.asarray(table), jnp.asarray(kv_len), jnp.asarray(active))
        self._tok_dev = jnp.where(jnp.asarray(active), tok, self._tok_dev)
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["decode_steps"] += 1
        for reqs in per_shard:
            for r in reqs:
                r.dispatched += 1
        self._inflight.append(([list(reqs) for reqs in per_shard], tok))
        self.stats["dispatch_depth_peak"] = max(
            self.stats["dispatch_depth_peak"], len(self._inflight))

    def _observe_one(self) -> None:
        per_shard, tok_dev = self._inflight.popleft()
        t0 = time.perf_counter()
        tok = np.asarray(tok_dev)
        self.stats["decode_s"] += time.perf_counter() - t0
        for s, reqs in enumerate(per_shard):
            for r in reqs:
                r.dispatched -= 1
                if r.state != "running" or r.done:
                    continue        # EOS overrun under dispatch-ahead
                r.cache_len += 1
                t = int(tok[s][r.slot])
                r.out.append(t)
                self._cur_tok[s, r.slot] = t
                self.stats["decode_tokens"] += 1
                self.shard_stats[s]["decode_tokens"] += 1
                if r.t_first is None:
                    r.t_first = self._wall()
                if self.ecfg.prefix_cache \
                        and r.cache_len % self.page_size == 0:
                    self.scheds[s].note_cached(r)
                self._emitted.append((r, t))
        if not self._inflight:
            self._tok_dev = None    # host vector authoritative again

    def drain(self) -> None:
        if self._inflight:
            self.stats["pipeline_drains"] += 1
        while self._inflight:
            self._observe_one()

    def _sync_for_preempt(self) -> None:
        self.drain()
        self._finish_done()

    def _finish_done(self) -> None:
        for sch in self.scheds:
            for r in [r for r in sch.running
                      if r.state == "running" and r.done
                      and r.dispatched == 0]:
                sch.finish(r)
                r.t_done = self._wall()
                self.finished.append(r)

    def _update_stats(self) -> None:
        for key in self.scheds[0].stats:
            self.stats[key] = sum(sch.stats[key] for sch in self.scheds)
        self.stats["tree_evictions"] = sum(
            sch.tree.evictions for sch in self.scheds
            if sch.tree is not None)
        self.stats["pages_in_use_peak"] = max(
            self.stats["pages_in_use_peak"],
            sum(self.num_pages - sch.alloc.available
                for sch in self.scheds))

    # ------------------------------------------------------------- stages
    def prefill(self, req: Request, now: float = float("inf")
                ) -> Optional[Prefix]:
        """Stage 1 over shard boundaries: route ``req`` (preemption
        replays keep their original shard — its swap store and prefix
        tree hold their state), admit it on that shard's scheduler, and
        cache + sample exactly as the single-shard engine.  Returns None
        when the shard cannot host it right now.  Raises for requests
        only the context-parallel fallback could serve: CP decode is
        synchronous and solo, so it is not staged — use :meth:`submit` +
        :meth:`run` for those."""
        if req.state not in ("waiting",) or req.slot >= 0:
            raise ServingError(
                f"request {req.rid}: prefill() on state {req.state!r} "
                f"(slot {req.slot}); only waiting requests stage")
        if self._t0 is None:
            self._t0 = time.perf_counter()
        fresh = req.shard < 0
        shard = self.router.pick(req) if fresh else req.shard
        if shard < 0:
            raise ServingError(
                f"request {req.rid}: no shard can page "
                f"{len(req.prompt)} + {req.max_new_tokens} tokens; the "
                f"context-parallel fallback is not staged — submit() + "
                f"run() serve it synchronously")
        sch = self.scheds[shard]
        queued = req in sch.waiting         # preemption replay
        if queued:
            sch.waiting.remove(req)
        ok = sch.admit(req)
        if not ok:
            self._sync_for_preempt()
            ok = sch.admit(req)
        if not ok:
            if queued:
                sch.waiting.appendleft(req)
            return None
        req.shard = shard
        if fresh:
            self.shard_stats[shard]["requests"] += 1
        # snapshot: the final chunk's record_prefill grows ``context``
        # by the sampled token (see Engine.prefill)
        target = len(req.context)
        first = True
        while req.cache_len < target:
            if not first:
                ok = sch._cow_tail(req)
                assert ok, "chunk continuation pages reserved at admission"
            self.caches = drain_cache_ops(self.caches, sch,
                                          self.swap_stores[shard],
                                          self.page_size, shard=shard)
            per = [[] for _ in range(self.n_shards)]
            per[shard] = [req]
            self._run_prefill(per)
            sch.note_cached(req)
            first = False
        req.state = "prefilled"
        self._update_stats()
        return Prefix(req=req, token=int(req.out[-1]), slot=req.slot,
                      shard=shard)

    def insert(self, prefix: Prefix, slot: Optional[int] = None) -> bool:
        """Stage 2: bind a prefilled request into its shard's decode
        batch.  False when the handle went stale (preempted since
        prefill) — re-prefill it."""
        req = prefix.req
        if slot is not None and slot != req.slot:
            raise ServingError(
                f"request {req.rid}: insert at slot {slot} but its pages "
                f"live at slot {req.slot} on shard {req.shard}; slots "
                f"bind at prefill")
        if req.state != "prefilled":
            return False
        req.state = "running"
        tok = int(req.out[-1])
        self._cur_tok[req.shard, req.slot] = tok
        if self._tok_dev is not None:
            self._tok_dev = self._tok_dev.at[req.shard, req.slot].set(tok)
        return True

    def generate_step(self, now: float = float("inf")
                      ) -> List[Tuple[Request, int]]:
        """Stage 3: per-shard growth/preemption plans, ONE shard_map
        decode dispatch across all shards, and the ``(request, token)``
        pairs observed this call (one pipeline-depth behind dispatch
        when ``dispatch_ahead > 0``)."""
        preempted = 0
        for sch in self.scheds:
            preempted += len(sch.plan_decode(now))
        self.stats["preemptions"] += preempted
        for s, sch in enumerate(self.scheds):
            self.caches = drain_cache_ops(self.caches, sch,
                                          self.swap_stores[s],
                                          self.page_size, shard=s)
        decodes = [[r for r in sch.running
                    if r.state == "running" and not r.budget_spent]
                   for sch in self.scheds]
        if any(decodes):
            self._dispatch_decode(decodes)
        depth = self.ecfg.dispatch_ahead if any(decodes) else 0
        while len(self._inflight) > depth:
            self._observe_one()
        self._finish_done()
        self._update_stats()
        out, self._emitted = self._emitted, []
        return out

    @property
    def preempted_waiting(self) -> List[Request]:
        """Preemption victims awaiting re-prefill, across all shards."""
        return [r for sch in self.scheds for r in sch.waiting
                if r.n_preempt > 0]

    # ------------------------------------------------- legacy closed loop
    def step(self, now: float = float("inf")) -> Dict:
        """One fleet iteration of the legacy driver, now layered on the
        stages: at most one arrived context-parallel request (served
        solo and synchronously), then per-shard admission plans and at
        most one shard_map prefill + one shard_map decode across shards,
        observed synchronously."""
        self.drain()
        n_cp = 0
        if self._cp_queue and self._cp_queue[0].arrival <= now:
            self._run_cp(self._cp_queue.popleft())
            n_cp = 1
        n_pre = 0
        for sch in self.scheds:
            n_pre += len(sch.plan_decode(now))
        self.stats["preemptions"] += n_pre
        prefills = [sch.plan_prefills(now) for sch in self.scheds]
        for s, sch in enumerate(self.scheds):
            self.caches = drain_cache_ops(self.caches, sch,
                                          self.swap_stores[s],
                                          self.page_size, shard=s)
        if any(prefills):
            self._run_prefill(prefills)
            for s, sch in enumerate(self.scheds):
                for r in prefills[s]:
                    sch.note_cached(r)
        decodes = [[r for r in sch.running
                    if r.state == "running" and not r.budget_spent]
                   for sch in self.scheds]
        if any(decodes):
            self._dispatch_decode(decodes)
            self.drain()
        n0 = len(self.finished)
        self._finish_done()
        n_done = len(self.finished) - n0
        self._emitted.clear()
        self._update_stats()
        return {"prefilled": sum(len(p) for p in prefills),
                "decoded": sum(len(d) for d in decodes),
                "finished": n_done + n_cp, "cp_served": n_cp,
                "preempted": n_pre}

    # ------------------------------------------- context-parallel fallback
    def _cp_setup(self):
        """Lazily build the CP mesh (same devices, ``model`` axis for
        `moba_decode_cp`'s collectives) and the dense-cache step pair on
        the ``sp`` backend.  ShardingConfig turns every other constraint
        off: only the MoBA KV cache is sequence-sharded."""
        if self._cp_mesh is None:
            self._cp_mesh = shmod.auto_mesh(
                (1, self.n_shards), ("data", "model"))
            self._cp_prefill = jax.jit(
                S.make_prefill_step(self.cfg, backend="sp"),
                donate_argnums=(2,))
            self._cp_decode = jax.jit(
                S.make_decode_step(self.cfg, backend="sp"),
                donate_argnums=(2,))
        return self._cp_mesh

    def _run_cp(self, req: Request) -> None:
        """Serve one over-long request with context-parallel decode: the
        dense KV cache (and its incremental centroid cache) is sharded
        over the mesh on the sequence dim inside `moba_decode_cp`'s
        shard_map; routing happens on shard-local centroids and only
        centroid scores cross chips (DESIGN.md §7)."""
        cfg = self.cfg
        mesh = self._cp_setup()
        # cache length: a multiple of shards × block size so every shard
        # holds whole blocks (moba_decode_cp's layout requirement)
        unit = self.n_shards * self.page_size
        need = len(req.prompt) + req.max_new_tokens
        max_len = -(-need // unit) * unit
        caches = T.init_caches(cfg, 1, max_len, dtype=jnp.dtype(cfg.dtype))
        scfg = ShardingConfig(fsdp=False, tensor_parallel=False,
                              sequence_parallel=False)
        t0 = time.perf_counter()
        with shmod.use_mesh(mesh, scfg):
            logits, caches = self._cp_prefill(
                self.params, jnp.asarray(req.prompt[None]), caches)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(
                jnp.int32)
            req.cache_len = len(req.prompt)
            req.out.append(int(np.asarray(tok)[0, 0]))
            req.t_first = self._wall()
            while not req.done:
                tok, caches = self._cp_decode(self.params, tok, caches)
                req.out.append(int(np.asarray(tok)[0, 0]))
                req.cache_len += 1
        # CP wall time is tracked apart from the paged counters so
        # per-shard tokens/s (decode_tokens / decode_s) stays honest
        self.stats["cp_s"] += time.perf_counter() - t0
        self.stats["cp_requests"] += 1
        self.stats["cp_tokens"] += len(req.out)
        req.state = "done"
        req.t_done = self._wall()
        self.finished.append(req)

    # ---------------------------------------------------------------- run
    def has_work(self) -> bool:
        return (any(sch.has_work() for sch in self.scheds)
                or bool(self._cp_queue) or bool(self._inflight))

    def run(self, realtime: bool = False) -> List[Request]:
        """Drain all submitted requests (paged shards + CP fallback, in
        arrival order within each path) and return the ones finished by
        this call."""
        n0 = len(self.finished)
        if self._t0 is None:
            self._t0 = time.perf_counter()
        while self.has_work():
            now = self._wall() if realtime else float("inf")
            self.step(now=now)
            if realtime and not any(sch.running for sch in self.scheds):
                pending = [sch.waiting[0].arrival for sch in self.scheds
                           if sch.waiting]
                pending += [r.arrival for r in list(self._cp_queue)[:1]]
                if pending:
                    wait = min(pending) - self._wall()
                    if wait > 0:
                        time.sleep(min(wait, 0.05))
        return self.finished[n0:]
