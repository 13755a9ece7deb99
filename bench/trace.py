"""Reduce a JAX profiler trace of the measured window to device busy and
idle time, the device operations that took most time, the time of each
compiled program, and the longest idle gaps attributed to the host spans
the benchmark recorded around its calls into the program.

The window is the host span ``bench_window``. A device's busy time is the
union of the intervals of its operations (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) clipped to the window, averaged over the chips
used. An idle gap is an interval of the window in which no operation runs
on the first chip; it is attributed to the host span that covers most of
it, the innermost one on a tie.
"""
from __future__ import annotations

import glob
import os
from typing import Iterable, Optional

WINDOW = "bench_window"
SPANS = ("field_step", "stage_array", "flush", "query", "prefill",
         "decode_step", "sample")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no span)"


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping [start, end) intervals; sorted, disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The complement of disjoint sorted ``busy`` within [lo, hi)."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def attribute(gap: tuple[float, float],
              spans: list[tuple[str, float, float]]) -> str:
    """The span that overlaps most of the gap; the shorter one on a tie."""
    a, b = gap
    best, best_key = NO_SPAN, (0.0, 0.0)
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_events(device_ops: list[list[tuple[str, float, float]]],
                  modules: list[tuple[str, float, float]],
                  host_spans: list[tuple[str, float, float]],
                  window: tuple[float, float]) -> dict:
    """The reduction on plain event lists, times in seconds.

    ``device_ops`` holds one list of (name, start, end) per chip used;
    ``modules`` the compiled programs run on the first chip; ``host_spans``
    the benchmark's spans.
    """
    lo, hi = window
    window_s = hi - lo
    busy_per_chip = []
    for ops in device_ops:
        u = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_per_chip.append(sum(b - a for a, b in u))
    busy_s = (sum(busy_per_chip) / len(busy_per_chip)) if busy_per_chip \
        else 0.0

    first = device_ops[0] if device_ops else []
    by_op: dict[str, float] = {}
    for name, s, e in first:
        c = clip([(s, e)], lo, hi)
        if c:
            key = name.split(" = ")[0]
            by_op[key] = by_op.get(key, 0.0) + (c[0][1] - c[0][0])
    by_module: dict[str, list[float]] = {}
    for name, s, e in modules:
        c = clip([(s, e)], lo, hi)
        if c:
            key = name.split("(")[0]
            by_module.setdefault(key, []).append(c[0][1] - c[0][0])

    idle = gaps(union(clip([(s, e) for _, s, e in first], lo, hi)), lo, hi)
    spans = [(n, s, e) for n, s, e in host_spans if n in SPANS]
    by_gap: dict[str, float] = {}
    longest = 0.0
    for g in idle:
        name = attribute(g, spans)
        by_gap[name] = by_gap.get(name, 0.0) + (g[1] - g[0])
        longest = max(longest, g[1] - g[0])

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": top(by_op), "idle_gaps": top(by_gap),
            "longest_gap_s": longest, "n_gaps": len(idle),
            "modules": {k: {"count": len(v), "seconds": sum(v)}
                        for k, v in by_module.items()}}


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def reduce_trace(trace_dir, chips: int = 1) -> Optional[dict]:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` and reduce it.
    Returns None when there is no trace or it holds no window span."""
    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        return None
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(files[-1])
    device_ops, modules, host_spans = {}, [], []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[idx] = list(_events(line))
                elif line.name == MODULES_LINE and idx == 0:
                    modules = list(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW:
                        window = (s, e)
                    elif name in SPANS:
                        host_spans.append((name, s, e))
    if window is None:
        return None
    ops = [device_ops[i] for i in sorted(device_ops)[:chips]]
    out = reduce_events(ops, modules, host_spans, window)
    out["device_planes"] = len(device_ops)
    out["trace_file_bytes"] = os.path.getsize(files[-1])
    return out
