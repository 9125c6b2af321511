"""Mean share of the engine's slots that a decode step of the window
filled: decoded tokens over decode steps and slots (engine counters), in
percent."""


def read(run):
    st = run.record["stats"]
    if st["decode_steps"] <= 0:
        return None
    return 100.0 * st["decode_tokens"] / st["decode_steps"] \
        / run.record["max_seqs"]
