"""Paged-decode microbenchmark: XLA gather-and-densify vs fused Pallas.

Runs one decode-attention step (routing + page gather + attend) against a
populated page pool across context lengths × block sizes × K/V storage
dtypes, for three paths: the XLA gather path
(`core.moba.moba_paged_decode_attention`), the grouped MXU-tiled Pallas
kernel and the legacy flat Pallas grid (`kernels.moba_decode`,
DESIGN.md §5).  As with ``kernels_micro``, interpret-mode wall time is
not TPU-meaningful; the recorded signal is (a) the paths agree at
benchmark shapes and (b) the analytic per-step HBM bytes each path
moves — the §Roofline memory-side input for decode.

The ``--kv-dtype`` axis stores the page pool quantized (int8 / fp8 with
per-(page, kv head) fp32 scales, ``core/quantization.py``) and measures
every path against the *fp32* XLA oracle on the same underlying K/V —
so ``max_abs_diff_vs_xla`` for a quantized case is the end-to-end
quantization error, gated per dtype.  Centroids (and hence routing)
stay fp32, so the selected pages are identical across dtypes and the
HBM savings are pure payload-byte savings.

Analytic HBM accounting (K and V both counted; ``esize`` = payload
bytes/elt: 4 fp32, 1 int8/fp8; quantized paths add the per-page fp32
scale reads, and the XLA densify copy is always written/re-read at
fp32):

  route            every path reads the B·npg·Hkv·d fp32 centroid gather
  xla              gathers per *query* head with no dedup — source
                   reads at esize + the densified fp32 (B,H,k,ps,d)
                   copy written then re-read
  pallas_flat      per-(query head, slot) page streamed once from the
                   pool at esize
  pallas_grouped   per-kv-head deduplicated union of the group's pages
                   (Σ n_uniq, measured from the actual routing):
                   Σ n_uniq·ps·d·esize·2

``--json out.json`` writes the stable machine-readable schema consumed
by the CI ``bench-smoke`` job (see ``_report``): shapes, per-path
``hbm_bytes`` / ``wall_us`` / ``max_abs_diff_vs_xla``, per-case
``kv_dtype``, and a top-level ``agree`` verdict.  The process exits
non-zero when any path disagrees with the fp32 XLA oracle beyond its
dtype's ``AGREE_TOL``, so the CI leg fails on numerical drift, not just
on crashes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoBAConfig
from repro.core import moba as M
from repro.core import quantization as Q
from repro.kernels import moba_decode as MD
from repro.kernels.runtime import resolve_interpret

SCHEMA_VERSION = 2
# per-dtype path-vs-fp32-oracle ceilings; fp32 is pure kernel drift,
# int8/fp8 budgets the quantization error at the benchmark shapes
AGREE_TOL = {"fp32": 1e-3, "int8": 5e-2, "fp8": 2e-1}
ITERS = 3
SHAPES = [(512, 64, 4), (1024, 64, 4), (1024, 128, 4)]   # (ctx, ps, top_k)
SMOKE_SHAPES = [(256, 32, 2)]


def _build_pool(rng, b, n_ctx, hkv, d, ps, kv_dtype="fp32"):
    npg = -(-n_ctx // ps)
    num_pages = b * npg
    kv_lens = np.full((b,), n_ctx, np.int32)
    kv_lens[1:] = rng.integers(max(1, n_ctx // 4), n_ctx, size=b - 1)
    perm = rng.permutation(num_pages)
    table = np.full((b, npg), -1, np.int32)
    pos = 0
    for i in range(b):
        need = -(-int(kv_lens[i]) // ps)
        table[i, :need] = perm[pos:pos + need]
        pos += need
    from repro.serving import paged_cache as PC
    pg_dtype = (jnp.float32 if kv_dtype == "fp32"
                else Q.payload_dtype(kv_dtype))
    cache = {"pages_k": jnp.zeros((num_pages, hkv, ps, d), pg_dtype),
             "pages_v": jnp.zeros((num_pages, hkv, ps, d), pg_dtype),
             "centroids": jnp.zeros((num_pages, hkv, d), jnp.float32)}
    if kv_dtype != "fp32":
        cache["scales_k"] = jnp.ones((num_pages, hkv), jnp.float32)
        cache["scales_v"] = jnp.ones((num_pages, hkv), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, hkv, npg * ps, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, hkv, npg * ps, d)), jnp.float32)
    cache = PC.paged_append_prefill(cache, jnp.asarray(table),
                                    jnp.asarray(kv_lens), kc, vc)
    return cache, jnp.asarray(table), jnp.asarray(kv_lens)


def _hbm_bytes(path, *, b, h, hkv, d, ps, tk, npg, union_pages, esize):
    route = b * npg * hkv * d * 4
    scales = 0 if esize == 4 else union_pages * hkv * 4 * 2
    per_head_src = b * h * tk * ps * d * esize * 2    # K and V, no dedup
    per_head_f32 = b * h * tk * ps * d * 4 * 2
    if path == "xla":
        return route + scales + per_head_src + 2 * per_head_f32
    if path == "pallas_flat":
        return route + scales + per_head_src
    if path == "pallas_grouped":
        return route + scales + union_pages * ps * d * esize * 2
    raise ValueError(path)


def run_cases(shapes, kv_dtypes=("fp32",)):
    cases = []
    b, h, hkv, d = 4, 4, 2, 64
    for (n_ctx, ps, tk) in shapes:
        cfg = MoBAConfig(block_size=ps, top_k=tk)
        # same seed per shape across dtypes: identical underlying K/V,
        # so the fp32 XLA output is the oracle for every dtype's paths
        cache0, table, kv_lens = _build_pool(
            np.random.default_rng(n_ctx + ps), b, n_ctx, hkv, d, ps)
        rng = np.random.default_rng(n_ctx + ps + 1)
        q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
        npg = table.shape[1]
        oracle_fn = jax.jit(
            lambda *a, c=cfg: M.moba_paged_decode_attention(*a, c))
        oracle = np.asarray(oracle_fn(
            q, cache0["pages_k"], cache0["pages_v"], cache0["centroids"],
            table, kv_lens).block_until_ready())
        active = np.asarray(kv_lens) > 0  # kv_len==0 rows: kernels emit
        #                                   zeros, XLA emits garbage

        # measured union size: the grouped grid's realized page count
        # (routing is fp32 in every mode, so one measurement serves all)
        idx, sel_valid = M.moba_paged_route(q, cache0["centroids"], table,
                                            kv_lens, cfg, page_size=ps)
        _, n_uniq = MD.union_pages(idx, sel_valid, npg)
        union_pages = int(jnp.sum(n_uniq))

        for kv_dtype in kv_dtypes:
            cache = cache0 if kv_dtype == "fp32" else _build_pool(
                np.random.default_rng(n_ctx + ps), b, n_ctx, hkv, d, ps,
                kv_dtype)[0]
            sk, sv = cache.get("scales_k"), cache.get("scales_v")
            esize = jnp.dtype(cache["pages_k"].dtype).itemsize
            kw = {"scales_k": sk, "scales_v": sv}
            fns = {
                "xla": jax.jit(lambda *a, c=cfg:
                               M.moba_paged_decode_attention(*a, c, **kw)),
                "pallas_grouped": jax.jit(
                    lambda *a, c=cfg: MD.moba_paged_decode_pallas(
                        *a, c, grid="grouped", **kw)),
                "pallas_flat": jax.jit(
                    lambda *a, c=cfg: MD.moba_paged_decode_pallas(
                        *a, c, grid="flat", **kw)),
            }
            args = (q, cache["pages_k"], cache["pages_v"],
                    cache["centroids"], table, kv_lens)
            outs = {name: np.asarray(fn(*args).block_until_ready())
                    for name, fn in fns.items()}

            paths = {}
            for name, fn in fns.items():
                t0 = time.perf_counter()
                for _ in range(ITERS):
                    fn(*args).block_until_ready()
                wall_us = (time.perf_counter() - t0) / ITERS * 1e6
                err = float(np.abs(outs[name][active]
                                   - oracle[active]).max())
                paths[name] = {
                    "wall_us": wall_us,
                    "hbm_bytes": _hbm_bytes(name, b=b, h=h, hkv=hkv, d=d,
                                            ps=ps, tk=tk, npg=npg,
                                            union_pages=union_pages,
                                            esize=esize),
                    "max_abs_diff_vs_xla": err,
                }
            tol = AGREE_TOL[kv_dtype]
            suffix = "" if kv_dtype == "fp32" else f"_{kv_dtype}"
            cases.append({
                "name": f"paged_decode_N{n_ctx}_B{ps}{suffix}",
                "kv_dtype": kv_dtype,
                "shape": {"batch": b, "heads": h, "kv_heads": hkv,
                          "head_dim": d, "ctx": n_ctx, "page_size": ps,
                          "top_k": tk, "pages_per_seq": npg},
                "union_pages": union_pages,
                "agree_tol": tol,
                "agree": all(p["max_abs_diff_vs_xla"] <= tol
                             for p in paths.values()),
                "paths": paths,
            })
    return cases


def _report(cases):
    return {
        "benchmark": "decode_micro",
        "schema_version": SCHEMA_VERSION,
        "dtype": "float32",
        "kv_dtypes": sorted({c["kv_dtype"] for c in cases}),
        "jax_version": jax.__version__,
        "device": jax.default_backend(),
        "interpret": resolve_interpret(None),
        "agree_tol": AGREE_TOL,
        "agree": all(c["agree"] for c in cases),
        "cases": cases,
    }


def bench():
    """run.py hook: flatten the JSON cases into its CSV row format."""
    rows = []
    for case in run_cases(SHAPES):
        for pname, p in case["paths"].items():
            rows.append((f"{case['name']}_{pname}", p["wall_us"],
                         f"maxerr={p['max_abs_diff_vs_xla']:.1e};"
                         f"hbm_bytes={p['hbm_bytes']:.2e}"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--json", metavar="OUT",
                    help="write the machine-readable report here "
                         "(the BENCH_decode.json schema)")
    ap.add_argument("--smoke", action="store_true",
                    help="one small shape only (the CI bench-smoke leg)")
    ap.add_argument("--shapes", choices=["full", "smoke", "all"],
                    default=None,
                    help="shape set (default full; --smoke implies "
                         "smoke; 'all' = full + smoke, used to "
                         "regenerate the committed snapshot so smoke "
                         "runs always find their cases in it)")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=sorted(AGREE_TOL) + ["all"],
                    help="K/V page-pool storage dtype axis ('all' runs "
                         "every dtype; quantized pools are measured "
                         "against the fp32 XLA oracle)")
    args = ap.parse_args(argv)
    shape_set = args.shapes or ("smoke" if args.smoke else "full")
    shapes = {"full": SHAPES, "smoke": SMOKE_SHAPES,
              "all": SHAPES + SMOKE_SHAPES}[shape_set]
    kv_dtypes = (tuple(sorted(AGREE_TOL)) if args.kv_dtype == "all"
                 else (args.kv_dtype,))
    cases = run_cases(shapes, kv_dtypes)
    report = _report(cases)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json}", file=sys.stderr)
    for case in cases:
        for pname, p in case["paths"].items():
            print(f"{case['name']}_{pname},{p['wall_us']:.1f},"
                  f"maxerr={p['max_abs_diff_vs_xla']:.1e};"
                  f"hbm_bytes={p['hbm_bytes']:.2e}")
    if not report["agree"]:
        bad = [c["name"] for c in cases if not c["agree"]]
        print(f"PATH DISAGREEMENT beyond per-dtype tolerance: {bad}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
