"""The trace reduction (``bench/trace.py``) on small traces."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace as T

MS = 1_000_000


def test_reduction_of_hand_made_events():
    # window 0..100 ms, one device: a loop op encloses a fusion and a
    # kernel that overlap each other; the last op runs past the window
    devices = [[(5 * MS, 45 * MS, "while.1"),
                (10 * MS, 30 * MS, "fusion.1"),
                (25 * MS, 40 * MS, "_fwd_kernel_tiled"),
                (60 * MS, 70 * MS, "_bwd_kernel_tiled"),
                (95 * MS, 120 * MS, "fusion.1")]]
    spans = [(0, 100 * MS, T.WINDOW),
             (0, 50 * MS, "bench.train_step"),
             (1 * MS, 4 * MS, "bench.data"),
             (50 * MS, 100 * MS, "bench.train_step")]
    got = T.reduce_events(devices, spans, {"moba_fwd": r"_fwd_kernel",
                                           "moba_bwd": r"_bwd_kernel",
                                           "moba_decode": r"_decode"})
    # busy: 5..45 (union) + 60..70 + 95..100 (clipped) = 55 ms
    assert got["busy_s"] == pytest.approx(0.055)
    assert got["window_s"] == pytest.approx(0.1)
    assert got["kernel_s"] == pytest.approx(
        {"moba_fwd": 0.015, "moba_bwd": 0.010, "moba_decode": 0.0})
    # the loop op only encloses others and is left out of the ranking
    assert dict(got["breakdown"]["device_ops"]) == pytest.approx({
        "fusion.1": 0.025, "moba_fwd: _fwd_kernel_tiled": 0.015,
        "moba_bwd: _bwd_kernel_tiled": 0.010})
    # idle 0..5 (its middle, 2.5 ms, lies in bench.data), 45..60 and
    # 70..95 (both in the second step)
    assert dict(got["breakdown"]["idle_gaps"]) == pytest.approx(
        {"bench.data": 0.005, "bench.train_step": 0.040})


def test_segments_name_the_innermost_span():
    segs = T._segments([(0, 10, "a"), (2, 4, "b"), (3, 4, "c"),
                        (6, 8, "b")])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"),
                    (6, 8, "b"), (8, 10, "a")]


def test_reads_the_bench_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    f(x).block_until_ready()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    _, spans = T.read_events(files[0])
    names = [n for _, _, n in spans]
    assert names.count("bench.train_step") == 3
    assert names.count(T.WINDOW) == 1
    (s, e), = [(s, e) for s, e, n in spans if n == T.WINDOW]
    assert all(s <= a and b <= e for a, b, n in spans
               if n == "bench.train_step")
