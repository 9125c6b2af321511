"""Engine prefill time per thousand prompt tokens in the window (the
engine's ``prefill_s`` over ``prefill_tokens``; its prefill blocks on the
sampled token, so this is device time plus dispatch)."""


def read(run):
    st = run.record["stats"]
    if st["prefill_tokens"] <= 0:
        return None
    return 1e6 * st["prefill_s"] / st["prefill_tokens"]
