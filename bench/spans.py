"""The program's own spans in a traced run of one cell, per Range page.

Usage, from the root of a checkout::

    python3 bench/spans.py --workload ring64.ycsb_e --seed 7 --seconds 51

Runs the cell once as ``bench/run.py --trace 1`` does, with one
difference: for the window alone the deployment's cluster and service use
the program's tracer in its profiler mode (``Tracer(profiler=True)`` of
``repro.obs.trace``), so every span the served path opens
(``serve.request``, ``cluster.query``, ``query.stream_open``,
``query.visibility_build``, ``kernel.h2d``, ``storage.seek``, ...) lands on
the host plane of the same trace as the device's operations, on one clock.
``bench/run.py`` itself keeps the program's ``NULL_TRACER``.

It logs each span's count, total and self time a Range page, and the
device's idle gaps charged to the innermost span over them.  The last line
of standard output is the run's result line with one more key,
``program_spans``: the readings of :data:`READINGS` and
``tombstone_runs_per_page``, each per Range page of the window.  Without a
TPU it exits 1 and prints no result.

The spans nest on one thread.  A span's self time is its time less the
union of its children: each instant a span covers is charged to the
innermost span open there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, Iterable, List, Optional, Sequence, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

# the name prefixes of the program's own spans (repro.obs.trace)
PROGRAM = ("serve.", "cluster.", "query.", "storage.", "kernel.", "net.")
# reading -> (span names summed, whether their self time is taken), in ms a
# Range page
READINGS = {
    "visibility_build_ms_per_page": (("query.visibility_build",), False),
    "storage_ms_per_page": (
        ("storage.read_clock", "storage.seek", "storage.scan"), False),
    "dispatch_ms_per_page": (
        ("query.pack", "query.host_seen", "kernel.dot_seen"), False),
    "mask_sync_ms_per_page": (("kernel.mask_sync",), False),
    "merge_ms_per_page": (("cluster.query",), True),
}

Event = Tuple[str, int, int]   # (name, start_ns, duration_ns)
Segment = Tuple[int, int, str]  # (start_ns, end_ns, innermost span)


def load(log_dir: str) -> List[Event]:
    """The program's spans on the host plane of the newest ``.xplane.pb``
    under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events
                           if e.name.startswith(PROGRAM))
    return out


def nest(events: Iterable[Event], lo: int, hi: int):
    """The spans clipped to ``[lo, hi)``: each name's summed nanoseconds
    and count, and the innermost-span timeline, a sorted list of disjoint
    ``(start, end, name)`` segments.

    A child lies inside its parent; one that outlives the span it started
    in is cut at that span's end.  A name's segments sum to its self time.
    """
    total: Dict[str, int] = {}
    count: Dict[str, int] = {}
    clipped = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            clipped.append((a, -b, name))
            total[name] = total.get(name, 0) + (b - a)
            count[name] = count.get(name, 0) + 1
    clipped.sort()   # by start, the longer (the parent) first
    segments: List[Segment] = []
    stack: List[Tuple[int, str]] = []    # (end, name), ends non-increasing
    cursor = lo
    for a, neg_b, name in clipped:
        while stack and stack[-1][0] <= a:
            end, top = stack.pop()
            if end > cursor:
                segments.append((cursor, end, top))
                cursor = end
        if stack and a > cursor:
            segments.append((cursor, a, stack[-1][1]))
        cursor = a
        end = -neg_b if not stack else min(-neg_b, stack[-1][0])
        stack.append((end, name))
    while stack:
        end, top = stack.pop()
        if end > cursor:
            segments.append((cursor, end, top))
            cursor = end
    return total, count, segments


def innermost(segments: Sequence[Segment], starts: Sequence[int],
              g0: int, g1: int) -> Optional[str]:
    """The span whose innermost segments overlap ``[g0, g1)`` most, or
    None where the time no span covers is at least as long."""
    by_name: Dict[str, int] = {}
    i = max(bisect.bisect_right(starts, g0) - 1, 0)
    for k in range(i, len(segments)):
        s, e, name = segments[k]
        if s >= g1:
            break
        ov = min(e, g1) - max(s, g0)
        if ov > 0:
            by_name[name] = by_name.get(name, 0) + ov
    if not by_name:
        return None
    best = max(by_name, key=by_name.get)
    uncovered = (g1 - g0) - sum(by_name.values())
    return best if by_name[best] > uncovered else None


@dataclass
class Spans:
    """The program's spans inside the window, by name."""

    total_s: Dict[str, float] = field(default_factory=dict)
    self_s: Dict[str, float] = field(default_factory=dict)
    count: Dict[str, int] = field(default_factory=dict)
    # [[innermost span, seconds]] top 10; a gap mostly outside every span
    # is charged to the benchmark's host label, as in its ``idle_gaps``
    gaps: List[List] = field(default_factory=list)


def summarize(rec, program: List[Event]) -> Spans:
    """Reduce the program's spans over the window of ``rec`` (a
    ``bench.tracing.Recorded``), with the device's idle gaps."""
    from bench import tracing

    windows = [(s, s + d) for n, s, d in rec.host if n == tracing.WINDOW]
    if not windows:
        raise ValueError(f"no {tracing.WINDOW!r} annotation in the trace")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    total, count, segments = nest(program, lo, hi)
    own: Dict[str, int] = {}
    for s, e, name in segments:
        own[name] = own.get(name, 0) + (e - s)
    starts = [s for s, _e, _n in segments]
    labels = sorted((s, s + d, n) for n, s, d in rec.host
                    if n in tracing.HOST_LABELS)
    gaps: Dict[str, int] = {}
    if segments:
        for chip in sorted(set(rec.ops) | set(rec.modules)):
            ops = rec.ops.get(chip) or rec.modules.get(chip, [])
            busy = tracing.union(tracing.clip(ops, lo, hi))
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 > g0:
                    name = (innermost(segments, starts, g0, g1)
                            or tracing._label(labels, g0, g1))
                    gaps[name] = gaps.get(name, 0) + (g1 - g0)
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return Spans(total_s={k: v / 1e9 for k, v in total.items()},
                 self_s={k: v / 1e9 for k, v in own.items()},
                 count=count, gaps=[[k, v / 1e9] for k, v in top])


def per_page(spans: Spans, run) -> Dict[str, Optional[float]]:
    """The readings a Range page of ``run`` (a ``bench.harness.RunData``);
    None where the window served no page or holds none of the spans."""
    pages = run.count("range")
    out: Dict[str, Optional[float]] = {}
    for reading, (names, own) in READINGS.items():
        table = spans.self_s if own else spans.total_s
        out[reading] = (sum(table.get(n, 0.0) for n in names) * 1e3 / pages
                        if pages and any(n in table for n in names) else None)
    stats = run.stats.get("range")
    out["tombstone_runs_per_page"] = (
        sum(s["tombstone_runs"] for s in stats) / len(stats)
        if stats and "tombstone_runs" in stats[0] else None)
    return out


@contextlib.contextmanager
def profiler_tracer(dep):
    """The deployment on ``Tracer(profiler=True)`` while open, then back on
    the tracers it had."""
    from repro.obs.trace import Tracer

    cluster, service = dep.cluster, dep.service
    saved = cluster.tracer, service.tracer
    cluster.tracer = service.tracer = Tracer(profiler=True)
    try:
        yield
    finally:
        cluster.tracer, service.tracer = saved


@contextlib.contextmanager
def hooked(captured: dict):
    """While open, the harness's traced window runs on the profiler-mode
    tracer and its trace loader also keeps the program's spans; the
    window's ``RunData``, the ``Recorded`` trace and the spans land in
    ``captured``."""
    from bench import harness, tracing

    run_window, load_trace = harness.run_window, tracing.load

    def window(dep, ops, seconds, traced):
        with profiler_tracer(dep) if traced else contextlib.nullcontext():
            captured["run"] = run_window(dep, ops, seconds, traced)
        return captured["run"]

    def load_both(log_dir):
        captured["rec"] = load_trace(log_dir)
        captured["program"] = load(log_dir)
        return captured["rec"]

    harness.run_window, tracing.load = window, load_both
    try:
        yield
    finally:
        harness.run_window, tracing.load = run_window, load_trace


def run_traced(workload: str, seed: int, seconds: float, *,
               t_start: float, log=None, **kw) -> dict:
    """One traced run of the cell with the program's spans on; the result
    line's object with ``program_spans``.  ``kw`` goes to
    ``harness.run_cell``."""
    from bench import harness

    log = log or (lambda msg: print(msg, flush=True))
    captured: dict = {}
    with hooked(captured):
        result = harness.run_cell(workload, seed, seconds, True,
                                  t_start=t_start, log=log, **kw)
    run = captured["run"]
    spans = summarize(captured["rec"], captured["program"])
    pages = max(run.count("range"), 1)
    log(f"program spans: {sum(spans.count.values()) / pages:.1f} a page")
    for name in sorted(spans.total_s, key=lambda k: -spans.self_s.get(k, 0)):
        log(f"span {name}: {spans.count[name] / pages:.2f} a page, "
            f"total {spans.total_s[name] * 1e3 / pages:.4f} ms a page, "
            f"self {spans.self_s.get(name, 0.0) * 1e3 / pages:.4f} ms "
            "a page")
    for name, sec in spans.gaps:
        log(f"program gap {name}: {sec:.4f}s")
    result["program_spans"] = per_page(spans, run)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    # run as a script, this directory heads sys.path: its modules are only
    # ever imported as the ``bench`` package
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    for path in (CHECKOUT, os.path.join(CHECKOUT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import harness

    try:
        result = run_traced(args.workload, args.seed, args.seconds,
                            t_start=T_START, root=CHECKOUT)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
