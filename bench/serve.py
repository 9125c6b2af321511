"""Serving driver: an open loop of requests through the paged engine's
staged API (``make_request`` / ``prefill`` / ``insert`` /
``generate_step``), timed for a window.

The traffic file gives the mix: prompt and output lengths, the arrival
rate in requests per second (Poisson), how many slots are filled before
the window, and the engine's settings.  The lengths and the gaps between
arrivals are drawn once from the file's own ``sizes_seed``, so that
every run serves the same set of sizes; ``--seed`` orders them and draws
the prompt tokens.

Arrivals are due on the host clock.  Each loop turn takes in what is
due, admits at most one waiting request (its prefill blocks the loop),
and calls ``generate_step`` while any slot decodes.  After the window no
request is sent; the loop runs on until every request that fell due in
the window has its first token, a minute at the most.

End to end: ``serve_tokens_per_s`` (tokens delivered in the window over
the window), ``itl_p95_ms`` (every gap between consecutive tokens of a
request, both ends in the window) and ``ttft_p90_ms`` (due time to first
token, over every request due in the window; one with no token a minute
after the window counts as failed, at its wait).

Correct: after the window, a sample of the served requests drawn from
the seed, the one with the most tokens among them, goes through the
plain reference over prompt and served tokens; ``logit_gap`` is the
widest gap by which a served token's reference logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import collections
import gc
import contextlib
import math
import time

import numpy as np

DRAIN_S = 60.0


def lengths(dist: dict, n: int, rng) -> np.ndarray:
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if dist["dist"] == "loguniform":
        return np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n)).astype(int)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def schedule(tr: dict, seed: int, seconds: float):
    """(prompt lengths, output lengths, due times) of the requests: the
    set is fixed by the traffic file, the order by ``seed``.  The first
    ``fill`` requests are due before the window (due time -inf)."""
    rate = tr["rate_per_s"]
    n = tr["fill"] + int(math.ceil(rate * seconds * 2)) + (8 if rate else 0)
    base = np.random.default_rng(tr["sizes_seed"])
    prompts = lengths(tr["prompt"], n, base)
    outputs = lengths(tr["output"], n, base)
    gaps = base.exponential(1.0 / rate, n) if rate else np.zeros(n)
    rng = np.random.default_rng([seed, 1])
    order = rng.permutation(n)
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    due[: tr["fill"]] = -np.inf
    return prompts[order], outputs[order], due


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def warm(engine, vocab: int, ps: int, chunk: int, rng) -> None:
    """Compile every prefill bucket the traffic can use (powers of two
    from one page to the chunk) and the decode step."""
    b = max(16, ps)
    sizes = []
    while b <= chunk:
        sizes.append(b)
        b *= 2
    for n in sizes:
        req = engine.make_request(rng.integers(0, vocab, n), 2)
        engine.insert(engine.prefill(req))
        while engine.has_work():
            engine.generate_step()


def run(cell: dict, seed: int, seconds: float, trace_dir, t_start: float
        ) -> dict:
    import jax
    from jax.profiler import TraceAnnotation
    from bench import model
    from repro.serving.engine import Engine, EngineConfig

    c, tr = cell["config"], cell["traffic"]
    e = tr["engine"]
    cfg = model.arch(c).program_config(c)
    params = model.make_weights(c, seed)
    model.check_layout(c, params)
    engine = Engine(cfg, params, EngineConfig(
        max_seqs=e["max_seqs"], max_seq_len=e["max_seq_len"],
        prefill_chunk=e["prefill_chunk"],
        max_prefill_batch=e["max_prefill_batch"],
        attn_backend=e["attn_backend"]))
    vocab = c["vocab_size"]
    rng = np.random.default_rng([seed, 2])
    warm(engine, vocab, engine.page_size, e["prefill_chunk"], rng)

    plen, olen, due = schedule(tr, seed, seconds)
    reqs = []

    def request(i):
        with TraceAnnotation("bench.data"):
            req = engine.make_request(rng.integers(0, vocab, plen[i]),
                                      int(olen[i]))
        reqs.append(req)
        return req

    times = collections.defaultdict(list)     # rid -> token host times
    first_tok = {}

    def admit(req, clock):
        with TraceAnnotation("bench.prefill"):
            pre = engine.prefill(req)
        if pre is None:
            return False
        with TraceAnnotation("bench.admit"):
            engine.insert(pre)
        first_tok[req.rid] = clock()
        times[req.rid].append(first_tok[req.rid])
        return True

    for i in range(tr["fill"]):
        if not admit(request(i), lambda: -1.0):
            raise RuntimeError("the pool cannot hold the filled slots")
    setup_s = time.perf_counter() - t_start

    trace = (jax.profiler.trace(str(trace_dir)) if trace_dir
             else contextlib.nullcontext())
    nxt = tr["fill"]
    queue = collections.deque()
    sent, dues = {}, {}
    decoded = []                        # (host time, kv length) per token
    free = lambda: len(engine.sched.running) < e["max_seqs"]  # noqa: E731
    with trace:
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            clock = lambda: time.perf_counter() - t0  # noqa: E731
            s0 = dict(engine.stats)
            end = None
            while True:
                now = clock()
                if end is None and now >= seconds:
                    end = now
                    s1 = dict(engine.stats)
                    backlog = len(queue)
                if end is not None and (now - end > DRAIN_S or all(
                        r in first_tok for r in dues)):
                    break
                while end is None and nxt < len(due) and due[nxt] <= now:
                    req = request(nxt)
                    dues[req.rid], sent[req.rid] = due[nxt], clock()
                    queue.append(req)
                    nxt += 1
                if queue and free() and admit(queue[0], clock):
                    queue.popleft()
                if engine.sched.running:
                    with TraceAnnotation("bench.decode_dispatch"):
                        emitted = engine.generate_step()
                    t = clock()
                    for r, _ in emitted:
                        times[r.rid].append(t)
                        decoded.append((t, len(r.prompt) + len(r.out) - 1))
                elif end is None and nxt < len(due):
                    time.sleep(max(0.0, min(due[nxt] - clock(), 1e-3)))
                elif end is not None and not queue:
                    break
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use",
                                                       0)
    window = end

    in_win = lambda t: 0.0 <= t <= window  # noqa: E731
    delivered = sum(1 for ts in times.values() for t in ts if in_win(t))
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
            if in_win(a) and in_win(b)]
    ttft = [first_tok.get(rid, end + DRAIN_S) - d for rid, d in dues.items()]
    failed = sum(1 for rid in dues if rid not in first_tok)
    late = [sent[rid] - d for rid, d in dues.items()]
    end_to_end = {"serve_tokens_per_s": delivered / window,
                  "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps
                  else float("nan"),
                  "ttft_p90_ms": 1e3 * percentile(ttft, 90) if ttft
                  else float("nan"),
                  "setup_s": setup_s}
    record = {
        "window_s": window,
        "max_seqs": e["max_seqs"],
        "backlog_at_end": backlog,
        "stats": {k: s1[k] - s0[k] for k in ("prefill_s", "prefill_tokens",
                                             "decode_steps",
                                             "decode_tokens")},
        "lateness_s": late,
        "ttft_s": ttft,
        "decode_kv_lens": [n for t, n in decoded if in_win(t)],
        "prompt_lens": [len(r.prompt) for r in reqs
                        if in_win(first_tok.get(r.rid, -1.0))],
        "tokens_out": delivered,
    }
    served = [r for r in reqs if len(r.out) > 0]
    sample = pick_sample(served, seed, tr["check_tokens"])
    check = [(np.asarray(r.prompt), np.asarray(r.out)) for r in sample]
    engine.params = engine.caches = None
    del engine, params
    gc.collect()
    gap = reference_gap(c, seed, check)
    return {"end_to_end": end_to_end, "attempted": tr["fill"] + len(dues),
            "failed": failed, "memory_peak_bytes": int(peak),
            "checks": [("logit_gap", gap, cell["limits"]["logit_gap"])],
            "record": record, "check_inputs": check}


def pick_sample(served, seed: int, tokens: int):
    """The request with the most served tokens, then others in an order
    drawn from the seed, until ``tokens`` served tokens are in."""
    if not served:
        return []
    longest = max(served, key=lambda r: len(r.out))
    rest = [r for r in served if r is not longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    sample, n = [longest], len(longest.out)
    for i in order:
        if n >= tokens:
            break
        sample.append(rest[i])
        n += len(rest[i].out)
    return sample


def bucket(n: int, step: int) -> int:
    return -(-n // step) * step


def reference_inputs(prompt, out):
    """The sequence the reference reads, the rows that predicted each
    served token, and the served tokens; padded at the end to whole
    buckets so that few shapes compile (causal rows never see the
    padding, and padded rows are dropped)."""
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    toks = out.astype(np.int32)
    n, m = bucket(len(seq), 2048), bucket(len(rows), 512)
    return (np.pad(seq, (0, n - len(seq))), np.pad(rows, (0, m - len(rows))),
            np.pad(toks, (0, m - len(toks))), len(rows))


def reference_gaps(c: dict, seed: int, check, low: bool = False):
    """Per served token, the gap of its logit below the reference's best
    at its position, or (``low``) the gap of the token that the float8
    control puts first there."""
    import jax
    import jax.numpy as jnp
    from bench import model, reference as R
    params = model.make_weights(c, seed)
    gaps = jax.jit(lambda p, s, r, t: R.served_gaps(c, p, s, r, t))
    firsts = jax.jit(lambda p, s, r: R.low_argmax(c, p, s, r))
    out = []
    for prompt, served in check:
        seq, rows, toks, n = reference_inputs(prompt, served)
        seq, rows, toks = jnp.asarray(seq), jnp.asarray(rows), \
            jnp.asarray(toks)
        if low:
            toks = firsts(params, seq, rows)
        out.append(np.asarray(gaps(params, seq, rows, toks))[:n])
    return np.concatenate(out) if out else np.zeros(0)


def reference_gap(c: dict, seed: int, check, low: bool = False) -> float:
    """The widest of :func:`reference_gaps` (inf with nothing served)."""
    g = reference_gaps(c, seed, check, low)
    return float(np.max(g)) if len(g) else float("inf")
