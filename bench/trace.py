"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* busy time: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
* the window: the benchmark's own ``bench.window`` host span;
* each kernel's summed device time, found by a pattern on the op name;
* a breakdown: the device ops that took the most time (leaving out ops
  that only enclose others, such as a layer loop's ``while``), and the
  idle time of the window by the innermost ``bench.*`` host span that
  covered it.

On a TPU the ops of the "XLA Ops" line are named by their HLO text; a
Pallas kernel is a ``tpu_custom_call`` with no kernel name in it, so a
kernel is found by the shape of its call (``MATCH`` in
``bench/work/<kernel>.py``).
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10
NAME_CHARS = 160


def _union(intervals):
    """Sorted, merged (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _gaps(merged, lo, hi):
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _segments(spans):
    """Flatten nested spans into sorted, disjoint ``(start, end, name)``
    pieces, each named by the innermost span covering it."""
    segs, stack, cursor = [], [], None
    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            if top[1] > cursor:
                segs.append((cursor, top[1], top[2]))
                cursor = top[1]
        if stack and s > cursor:
            segs.append((cursor, s, stack[-1][2]))
        cursor = s
        stack.append((s, e, name))
    while stack:
        top = stack.pop()
        if top[1] > cursor:
            segs.append((cursor, top[1], top[2]))
            cursor = top[1]
    return segs


def _label(segs, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and segs[i][0] <= t < segs[i][1]:
        return segs[i][2]
    return None


def read_events(path):
    """(device ops per device, host spans) of one trace file: ops as
    ``(start_ns, end_ns, name)``, spans the ``bench.*`` host events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") \
                and "Core" not in plane.name.split(":")[-1]:
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.end_ns, e.name)
                            for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


def reduce_events(devices, spans, kernels: dict[str, str]) -> dict:
    """Busy and window seconds, kernel seconds (``kernels``: name ->
    regular expression on the op name) and the breakdown."""
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows or not any(devices):
        raise ValueError("trace holds no bench.window span or no device op")
    lo, hi = windows[0]
    busy, kernel_ns = [], collections.Counter()
    by_op, idle = collections.Counter(), collections.Counter()
    names = {n for ops in devices for _, _, n in ops}
    kernel_of = {n: [k for k, pat in kernels.items() if re.search(pat, n)]
                 for n in names}
    segs = _segments([sp for sp in spans if sp[2] != WINDOW])
    starts = [sg[0] for sg in segs]
    for ops in devices:
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in ops
               if e > lo and s < hi]
        ops.sort()
        merged = _union([(s, e) for s, e, _ in ops])
        busy.append(sum(e - s for s, e in merged))
        for i, (s, e, name) in enumerate(ops):
            for kname in kernel_of[name]:
                kernel_ns[kname] += e - s
            if i + 1 < len(ops) and ops[i + 1][1] <= e:
                continue              # encloses the next op: a loop or call
            label = name[:NAME_CHARS]
            if kernel_of[name]:
                label = f"{'+'.join(kernel_of[name])}: {label}"
            by_op[label] += e - s
        for s, e in _gaps(merged, lo, hi):
            label = _label(segs, starts, (s + e) / 2)
            idle[label or "no bench span"] += e - s
    n = len(devices)
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": {k: kernel_ns[k] / n / 1e9 for k in kernels},
        "breakdown": {
            "device_ops": [[name, ns / n / 1e9]
                           for name, ns in by_op.most_common(TOP)],
            "idle_gaps": [[name, ns / n / 1e9]
                          for name, ns in idle.most_common(TOP)],
        },
    }


def reduce(trace_dir: Path, kernels: dict[str, str]) -> dict:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, spans = read_events(files[-1])
    return reduce_events(devices, spans, kernels)
