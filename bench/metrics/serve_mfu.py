"""Model FLOP utilisation of serving: the forward operations of every
prompt token prefilled and every token decoded in the window
(``bench/work/model.py``; logits counted only where a token is served),
over the window and the chip's bf16 peak, in percent."""
import numpy as np

from bench.work.model import serve_flops


def read(run):
    r = run.record
    pos = [np.arange(n) for n in r["prompt_lens"]]
    pos.append(np.asarray(r["decode_kv_lens"], np.int64) - 1)
    flops = serve_flops(run.config, np.concatenate(pos), r["tokens_out"])
    return 100.0 * flops / r["window_s"] / run.peaks["bf16_flops_per_s"]
