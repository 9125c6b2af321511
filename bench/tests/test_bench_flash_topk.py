"""The flash_topk routing kernel in the benchmark: found in a trace by
the name its Pallas call gives it, its work against a count made by
hand, and its roofline reader, which is silent on a trace of a program
whose kernel has no name."""
from types import SimpleNamespace

import pytest

from bench import run
from bench import trace as T
from bench.tests.test_bench_work import C
from bench.work import flash_topk

MS = 1_000_000
KERNEL = ('custom-call(f32[16,8192,64]{2,1,0} %q, f32[16,64,64]{2,1,0} '
          '%c), custom_call_target="tpu_custom_call"')
# the kernel as a TPU trace names it, before and after its Pallas call
# was given a name; and an op whose text merely mentions the name
NAMED = f"%flash_topk.3 = s32[16,8192,8]{{2,1,0}} {KERNEL}"
UNNAMED = f"%closed_call.41 = s32[16,8192,8]{{2,1,0}} {KERNEL}"
MENTION = ("%fusion.7 = s32[16,8192,8]{2,1,0} fusion(s32[16,8192,8]{2,1,0} "
           "%flash_topk.3), kind=kLoop")


def reduced(ops):
    return T.reduce_events([ops], [(0, 100 * MS, T.WINDOW)],
                           run.kernel_patterns())


def reading(trace):
    r = SimpleNamespace(config=run.resolve("moba-340m.train_8k")["config"],
                        record={"steps": 2, "batch": 1, "seq": 8192,
                                "window_s": 0.1},
                        trace=trace, peaks=run.peaks_of("TPU v5 lite"))
    path = run.BENCH / "metrics" / "flash_topk_roofline.py"
    return run.load_module(path).read(r)


def test_found_by_its_name():
    got = reduced([(0, 10 * MS, NAMED), (10 * MS, 13 * MS, MENTION),
                   (20 * MS, 24 * MS, NAMED)])
    assert got["kernel_s"]["flash_topk"] == pytest.approx(0.014)
    share = reading(got)
    flops, nbytes = flash_topk.work(run.resolve(
        "moba-340m.train_8k")["config"], 1, 8192)
    # bound by bytes at this shape: 2 steps of bytes over bandwidth
    assert share == pytest.approx(100 * 2 * nbytes / 819e9 / 0.014)


def test_silent_without_the_name():
    assert reading(reduced([(0, 10 * MS, UNNAMED)])) is None


def test_flash_topk_work_by_hand():
    seq, d, h, hkv, bs, k = 10, 8, 4, 2, 4, 3
    earlier = sum(t // bs for t in range(seq))       # blocks before t's
    flops, nbytes = flash_topk.work(C, 2, seq)
    assert flops == 2 * 2 * h * 2 * d * earlier      # 2 moba layers
    nb = -(-seq // bs)
    assert nbytes == 2 * 2 * (seq * h * d * 2 + hkv * nb * d * 2
                              + seq * h * k * 4)
