"""Readings of the correctness check's control: the plain reference in
float8, put in the program's place.

  python bench/control.py --workload qwen3-0.6b.train_4k --seeds 1 2 3
  python bench/control.py --workload qwen3-0.6b.longdoc --seeds 1 2 3 \\
      --seconds 20

A training cell's control makes the check's three steps with the float8
reference and is compared with the float32 reference as the program is.
A serving cell's control needs the program's served tokens: each seed
runs the cell once (its own check included), then reads, at every served
position of the same sample, the reference's gap of the token the
float8 reference puts first.  Prints one JSON line per seed with the
program's and the control's numbers and the cell's limits.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def train_control(cell: dict, seed: int) -> dict:
    from bench import train
    ref = train.reference_readings(cell["config"], cell["traffic"], seed)
    low = train.reference_readings(cell["config"], cell["traffic"], seed,
                                   low=True)
    return train.readings(low, ref)


def quantiles(g) -> dict:
    import numpy as np
    return {"tokens": int(len(g)), "exact": float(np.mean(g == 0)),
            "p50": float(np.median(g)), "p90": float(np.percentile(g, 90)),
            "p99": float(np.percentile(g, 99)), "max": float(np.max(g))}


def serve_control(cell: dict, seed: int, seconds: float) -> dict:
    from bench import serve
    out = serve.run(cell, seed, seconds, None, time.perf_counter())
    args = (cell["config"], seed, out["check_inputs"])
    prog, low = serve.reference_gaps(*args), serve.reference_gaps(*args,
                                                                   low=True)
    return {"program_logit_gap": out["checks"][0][1],
            "logit_gap": float(low.max()), "program": quantiles(prog),
            "control": quantiles(low), "attempted": out["attempted"],
            "failed": out["failed"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from bench import run
    cell = run.resolve(args.workload)
    run.require_chips(cell["workload"]["chips"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        if cell["traffic"]["kind"] == "train":
            got = train_control(cell, seed)
        else:
            got = serve_control(cell, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": got, "limits": cell["limits"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
