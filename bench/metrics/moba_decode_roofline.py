"""Share of its roofline that the paged MoBA decode kernel reaches in the
traced window: the least time its work could take (the larger of
operations over peak and bytes over bandwidth, ``bench/work/
moba_decode.py``, over every token decoded in the window) over its summed
device time, in percent.  Silent where the trace shows no such kernel."""
from bench.work.moba_decode import work


def read(run):
    t = run.trace["kernel_s"].get("moba_decode", 0.0)
    if t <= 0 or not run.record["decode_kv_lens"]:
        return None
    flops, nbytes = work(run.config, run.record["decode_kv_lens"])
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
