"""Model FLOP utilisation of the train step: the operations the
algorithm needs per step (``bench/work/model.py``) times steps per second
of the window, over the chip's bf16 peak, in percent."""
from bench.work.model import train_flops


def read(run):
    r = run.record
    flops = train_flops(run.config, r["batch"], r["seq"]) * r["steps"]
    return 100.0 * flops / r["window_s"] / run.peaks["bf16_flops_per_s"]
