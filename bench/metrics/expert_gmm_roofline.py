"""Share of its roofline that the grouped expert matmuls reach in the
traced window: the least time their work could take on the chip (the
larger of operations over peak and bytes over bandwidth,
``bench/work/expert_gmm.py``) over their summed device time, in
percent.  Silent where the trace shows no kernel of that name."""
from bench.work.expert_gmm import work


def read(run):
    t = run.trace["kernel_s"].get("expert_gmm", 0.0)
    if t <= 0:
        return None
    r = run.record
    flops, nbytes = work(run.config, r["batch"], r["seq"])
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"]) * r["steps"]
    return 100.0 * least / t
