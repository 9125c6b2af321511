"""Run one cell of the benchmark once, and print its result.

  python bench/run.py --workload qwen3-0.6b.train_4k --seed 7 \\
      --seconds 30 --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
is found by name: the configuration in ``bench/configs/<config>.json``,
its architecture in ``bench/arch/<arch>.py`` (the configuration's
``arch`` key), the traffic mix in ``bench/traffic/<traffic>.json`` (its
``kind`` names the driver, ``bench/<kind>.py``), the limits of the
correctness check in ``bench/limits/<workload>.json``, each per-layer
metric's reader in ``bench/metrics/<metric>.py`` and each kernel's work
in ``bench/work/<kernel>.py``.

The run refuses to start without a TPU, or with fewer chips than the cell
asks for.  Set-up (weights from ``--seed``, compilation, warm-up, the
state the traffic needs) is ``setup_s``; then the window runs for
``--seconds``.  ``--trace 1`` traces the window and reports the cell's
per-layer metrics instead of its end-to-end ones.  After the window the
run compares what the timed path produced with the plain reference
(``bench/reference.py``), prints each number compared beside its limit
as the last lines of standard error, and prints one JSON line last on
standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# run as a script, the harness's directory heads sys.path and its module
# names (trace, model) would shadow the standard library's
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import model  # noqa: E402
from bench.model import load_module  # noqa: E402

TRACE_DIR = ROOT / ".bench_traces"


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def resolve(workload: str) -> dict:
    """Everything one cell needs, found by the names in ``BENCHMARK.json``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if workload not in entries:
        raise SystemExit(f"unknown workload {workload!r}; the benchmark has "
                         f"{sorted(entries)}")
    w = entries[workload]
    config = model.load_config(w["config"])
    try:
        model.arch(config)
    except FileNotFoundError as e:
        raise SystemExit(str(e)) from None
    e2e = [m for m in spec["end_to_end"] if applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if applies(m, workload, reported)]
    return {
        "workload": w,
        "config": config,
        "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{workload}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
        "readers": {m["name"]: BENCH / "metrics" / f"{m['name']}.py"
                    for m in per_layer},
    }


def kernel_patterns() -> dict:
    out = {}
    for path in sorted((BENCH / "work").glob("*.py")):
        mod = load_module(path)
        if hasattr(mod, "MATCH"):
            out[path.stem] = mod.MATCH
    return out


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(f"this cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return devs[:n]


def peaks_of(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


class Run:
    """What one run leaves for the per-layer readers: the configuration,
    the traffic, the device's peaks, the driver's record of the window
    and the reduced trace."""

    def __init__(self, cell, record, trace, peaks):
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.record = record
        self.trace = trace
        self.peaks = peaks


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """Drive the cell once; returns the result line as a dict."""
    driver = importlib.import_module(f"bench.{cell['traffic']['kind']}")
    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / f"{cell['workload']['name']}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = driver.run(cell, seed, seconds, trace_dir, T_START)
    reduced = None
    if trace:
        from bench import trace as tr
        reduced = tr.reduce(trace_dir, kernel_patterns())
        shutil.rmtree(trace_dir, ignore_errors=True)
    dev = devices[0]
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices),
                     "memory_peak_bytes": out["memory_peak_bytes"]}
    checks = out["checks"]
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    if trace:
        run = Run(cell, out["record"], reduced, peaks_of(dev.device_kind))
        metrics = {}
        for name, path in cell["readers"].items():
            value = load_module(path).read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        result_device["busy_s"] = reduced["busy_s"]
        result_device["window_s"] = reduced["window_s"]
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": result_device}
    if trace:
        line["breakdown"] = reduced["breakdown"]
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = resolve(args.workload)
    devices = require_chips(cell["workload"]["chips"])
    peaks_of(devices[0].device_kind)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, chk in line["checks"].items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
