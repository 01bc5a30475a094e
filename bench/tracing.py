"""Profiler capture of the window, and its reduction to device numbers.

The reduction works on plain interval lists, so a test can feed it a
small recorded trace: device operations ``(name, start_ns, duration_ns)``
per chip, and the benchmark's own host annotations (``bench.window``
around the window, ``req.page`` (range and join pages) / ``req.probe`` /
``req.count`` / ``req.write`` around each request, ``gen.idle`` while the
generator waits for the next due time).  :func:`load` pulls those lists
out of an ``.xplane.pb``.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "bench.window"
HOST_LABELS = ("req.page", "req.probe", "req.count", "req.write",
               "gen.idle")
# device-plane lines, most specific first: operations, then whole programs
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
KERNEL = "dot_seen"

Event = Tuple[str, int, int]   # (name, start_ns, duration_ns)


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace device activity and the benchmark's annotations, without the
    Python tracer (every Python call would land in the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclass
class Recorded:
    """What the reduction reads from one trace."""

    ops: Dict[str, List[Event]] = field(default_factory=dict)      # per chip
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per chip
    host: List[Event] = field(default_factory=list)


def load(log_dir: str) -> Recorded:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    rec = Recorded()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                # an op's name is its whole HLO line: keep "%name.N"
                evs = [(e.name.split(" = ", 1)[0], int(e.start_ns),
                        int(e.duration_ns)) for e in line.events]
                if line.name in OP_LINES:
                    rec.ops.setdefault(plane.name, []).extend(evs)
                elif line.name in MODULE_LINES:
                    rec.modules.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rec.host.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events
                    if e.name == WINDOW or e.name in HOST_LABELS)
    return rec


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


@dataclass
class Summary:
    window_s: float
    chips: int                     # chips with device events in the trace
    busy_s: float                  # mean over chips of the busy union
    kernel_s: float                # summed device time of KERNEL programs
    kernel_events: int
    device_ops: List[List]         # [[name, seconds]] top 10
    idle_gaps: List[List]          # [[host label, seconds]] top 10

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def summarize(rec: Recorded, kernel: str = KERNEL) -> Summary:
    """Busy share, kernel time and breakdown inside the window annotation.

    Busy is the union of operation intervals on each chip, clipped to the
    window, averaged over the chips that ran anything.  Kernel time sums
    the device programs whose name holds ``kernel``.  Each idle gap of a
    chip is charged to the host annotation that overlaps it most
    (``host.other`` where none does); gaps sum per label.
    """
    windows = [(s, s + d) for n, s, d in rec.host if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    chips = sorted(set(rec.ops) | set(rec.modules))
    busy_ns = 0
    per_op: Dict[str, int] = {}
    gaps: Dict[str, int] = {}
    kernel_ns = 0
    kernel_events = 0
    spans = sorted((s, s + d, n) for n, s, d in rec.host if n in HOST_LABELS)
    for chip in chips:
        ops = rec.ops.get(chip) or rec.modules.get(chip, [])
        busy = union(clip(ops, lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, s, d in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                per_op[name] = per_op.get(name, 0) + (b - a)
        for name, s, d in rec.modules.get(chip, []):
            a, b = max(s, lo), min(s + d, hi)
            if kernel in name and b > a:
                kernel_ns += b - a
                kernel_events += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = _label(spans, g0, g1)
                gaps[label] = gaps.get(label, 0) + (g1 - g0)
    n_chips = max(len(chips), 1)
    top = lambda d: [[k, v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return Summary(window_s=(hi - lo) / 1e9, chips=len(chips), busy_s=busy_ns / n_chips / 1e9,
                   kernel_s=kernel_ns / 1e9, kernel_events=kernel_events,
                   device_ops=top(per_op), idle_gaps=top(gaps))


def _label(spans: Sequence[Tuple[int, int, str]], g0: int, g1: int) -> str:
    # requests run one after another, so spans sorted by start are sorted
    # by end too: start at the first that ends after the gap opens
    best, best_ns = "host.other", 0
    i = bisect.bisect_right(spans, (g0, g0, ""))
    for k in range(max(i - 1, 0), len(spans)):
        s, e, name = spans[k]
        if s >= g1:
            break
        ov = min(e, g1) - max(s, g0)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def breakdown(summary: Summary) -> dict:
    return {"device_ops": summary.device_ops,
            "idle_gaps": summary.idle_gaps}
