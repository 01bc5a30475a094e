"""One run of one cell: build, set up, warm, measure, check, report.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file the harness finds by the name in
``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment and its data scale
  (with an optional ``bench/configs/<config>.py`` builder, see
  :mod:`bench.deploy`);
* ``bench/traffic/<traffic>.json``: the mix, read by :mod:`bench.traffic`;
* ``bench/cells/<workload>.json``: the offered rate of the cell;
* ``bench/metrics/<metric>.py``: a ``read(run)`` function that returns
  the per-layer metric from a :class:`RunData`, or None where the run has
  nothing to read.

A configuration holds one set (``"set_name"``, ``"elements"``) or many:
``"sets": {"name_format": "links.%d", "universe": 100000, "groups":
[{"count": 2, "elements": 20000}, {"count": 500, "elements": 40}]}``
builds 502 sets, each of members drawn from the seed among the first
``universe`` YCSB keys; ``remove_share`` and ``write_batch`` apply to each
set.  A mix's ``ops`` may share among ``range``, ``probe``, ``count``
(a whole-set ``Count``), ``join`` (the first page of a ``Join``, its limit
drawn from ``range_length`` like a range's), ``insert`` and ``remove``;
with many sets it adds ``"set_theta"`` (the scrambled Zipfian by which a
request picks its set) and, with joins, ``"join": {"kind": "intersect",
"left": [0], "right": [0, 1]}`` (the groups each side is picked from).
A join page is a page: it counts in the ``page`` tail and carries the
``req.page`` label; a count has the ``count`` tail and ``req.count``.

The loop is open: requests are due at Poisson times and each is timed
from when it was due to its reply, through ``BigsetClient`` and
``BigsetService.handle``.  One process, one thread: a request that is due
while another is served waits, and that wait is in its latency.

Set-up ends by moving everything it built into Python's permanent
generation (``gc.freeze``), as a server does after a bulk load: the
collector then scans only what the window allocates.  Without it, each
full collection walked the whole 100,000-element heap: about 0.1 s with
the host stopped, five times in a 51-s window on a TPU v5e's host, at
times that moved with the seed.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import importlib.util
import json
import os
import re
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import deploy, reference, roofline, tracing, traffic

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LABELS = {"range": "req.page", "join": "req.page", "probe": "req.probe",
          "count": "req.count", "insert": "req.write", "remove": "req.write"}
# the request kinds an end-to-end tail ``<group>_p<q>_ms`` is taken over
TAILS = {"page": ("range", "join"), "probe": ("probe",),
         "count": ("count",), "write": ("insert", "remove")}
# the read kinds: each request's QueryStats is kept, and warm-up sends some
READS = ("range", "probe", "count", "join")
# requests due in the window but not started this long after it closed
# are counted as failed, so an overloaded run still ends in time
DRAIN_S = 30.0
# read-only requests of each read kind the mix has, sent before the window
WARM_REQUESTS = 4


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: str
    cfg: dict
    traffic: str
    mix: dict
    rate: float
    end_to_end: List[dict]
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = CHECKOUT,
              overrides: Optional[dict] = None) -> Cell:
    """Resolve a cell by name into its configuration, mix and rate."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = dict(_load_json(os.path.join(root, config["file"])))
    cfg.update(overrides or {})
    mix = _load_json(os.path.join(root, "bench", "traffic",
                                  w["traffic"] + ".json"))
    rate = _load_json(os.path.join(root, "bench", "cells",
                                   workload + ".json"))["rate_ops_s"]
    return Cell(
        name=workload, chips=int(w["chips"]), config=w["config"], cfg=cfg,
        traffic=w["traffic"], mix=mix, rate=float(rate),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, workload)])


def metric_reader(name: str, root: str = CHECKOUT) -> Callable:
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def cpu_device() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1}


def enable_cache(root: str) -> str:
    """The persistent compile cache at ``<checkout>/.jax_cache``, a fixed
    path inside the checkout, whatever the environment names."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    return enable_compile_cache()


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits while open."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_compile(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GcPauses:
    """Counts Python's garbage collections and their seconds while open."""

    def __init__(self):
        self.count = 0
        self.full = 0
        self.seconds = 0.0
        self._t = 0.0

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.count += 1
        self.full += info["generation"] == 2
        self.seconds += time.perf_counter() - self._t


@dataclass
class RunData:
    """What one run measured; per-layer readers take their metric from it."""

    seconds: float
    answers: List[reference.Answer]
    latency: Dict[str, List[float]]    # seconds from due to reply, by kind
    service: Dict[str, List[float]]    # seconds from call to reply, by kind
    stats: Dict[str, List[dict]]       # per-request QueryStats, by read kind
    completed_in_window: int
    dispatch: object                   # DispatchStats delta over the window
    io: object                         # IoStats delta over the window
    net_bytes: int
    launches: List[tuple] = field(default_factory=list)  # (dots, runs)
    trace: Optional[tracing.Summary] = None
    peaks: Optional[dict] = None

    def count(self, *kinds: str) -> int:
        return sum(len(self.latency.get(k, ())) for k in kinds)


@contextlib.contextmanager
def launch_log(out: List[tuple]):
    """Record ``(real dots, real tombstone runs)`` of every ``dot_seen``
    launch the served path makes: the shapes the roofline counts."""
    from repro.kernels.dot_seen.ops import DISPATCHES
    from repro.query import batch

    orig = batch.BatchVisibility.seen_mask

    def seen_mask(self, dots):
        before = DISPATCHES.launches
        mask = orig(self, dots)
        if DISPATCHES.launches != before:
            out.append((len(dots), self.tombstone.n_runs()))
        return mask

    batch.BatchVisibility.seen_mask = seen_mask
    try:
        yield
    finally:
        batch.BatchVisibility.seen_mask = orig


def _annotate(on: bool):
    if on:
        import jax

        return jax.profiler.TraceAnnotation
    return lambda _name: contextlib.nullcontext()


def _answer(op: traffic.Op) -> reference.Answer:
    return reference.Answer(op.kind, op.key, op.limit, op.set_name,
                            op.right, op.join_kind)


def execute(dep: deploy.Deployment, op: traffic.Op):
    """Send one request; return its answer, its QueryStats (reads), and
    whether a cursor lease is left open."""
    from repro.query import Count, Join, Membership, Range
    from repro.serve.bigset_service import Backpressure

    c, s = dep.client, op.set_name
    a = _answer(op)
    try:
        if op.kind in ("range", "join"):
            plan = (Range(s, start=op.key, limit=op.limit)
                    if op.kind == "range" else
                    Join(op.join_kind, s, op.right, limit=op.limit))
            page = c.query(plan, r=dep.r)
            a.members = page.members
            a.ok = True
            return a, page.stats, page.cursor is not None
        if op.kind == "probe":
            page = c.query(Membership(s, op.key), r=dep.r)
            a.present = bool(page.present)
            a.ok = True
            return a, page.stats, False
        if op.kind == "count":
            page = c.query(Count(s), r=dep.r)
            a.count = page.count
            a.ok = True
            return a, page.stats, False
        if op.kind == "insert":
            c.insert(s, op.key)
        elif op.kind == "remove":
            a.removed = bool(c.remove(s, op.key))
        else:
            raise ValueError(f"unknown op kind {op.kind!r}")
    except Backpressure:
        return a, None, False
    a.ok = True
    return a, None, False


def warm(dep: deploy.Deployment, sets: List[traffic.SetData],
         ops: List[traffic.Op]) -> int:
    """Compile every kernel shape the window's reads reach, then send a few
    read-only requests of each read kind.  Returns the shapes warmed.

    A tombstone's shape is that of each set's storage sets on every vnode,
    and each it can take as it gains at most one run for each remove the
    window sends to the set.  A batch
    is a chunk of a stream padded to :data:`PAD_BUCKET` dots.  Chunks grow
    32, 128, 512 dots, then the executor's batch size: counts, joins and
    range pages of more than 32 + 128 members may read that far (a page,
    past dead elements), so their batches are warmed at every width.
    """
    import jax.numpy as jnp

    from repro.core.vclock import DenseClock
    from repro.kernels.dot_seen import dot_seen
    from repro.query.batch import PAD_BUCKET, bucket_shape, dense_shape
    from repro.query.executor import DEFAULT_BATCH_SIZE

    cluster = dep.cluster
    removes = collections.Counter(o.set_name for o in ops
                                  if o.kind == "remove")
    shapes = set()
    for s in sets:
        grow = removes[s.name]
        for pset in cluster.ring.storage_sets(s.name):
            for vn in cluster.vnodes.values():
                ts = vn.read_tombstone(pset)
                a, r = dense_shape(ts)
                if not ts.is_zero():
                    shapes.add(bucket_shape(a, r))
                # every bucket the tombstone can pass through on the way
                shapes.update(bucket_shape(max(a, 1), r + k)
                              for k in range(1, grow + 1))
    deep = any(o.kind in ("count", "join") or (o.limit or 0) > 32 + 128
               for o in ops)
    widest = (-(-DEFAULT_BATCH_SIZE // PAD_BUCKET) * PAD_BUCKET if deep
              else PAD_BUCKET)
    for n in range(PAD_BUCKET, widest + 1, PAD_BUCKET):
        zeros = np.zeros((n,), np.int32)
        for a, r in sorted(shapes):
            clock = DenseClock(jnp.ones((a, r), jnp.int32),
                               jnp.zeros((a, r), jnp.int32))
            np.asarray(dot_seen(clock, zeros, zeros))
    for kind in READS:
        for op in [o for o in ops if o.kind == kind][:WARM_REQUESTS]:
            _a, _stats, leased = execute(dep, op)
            if leased:
                dep.client.close()
    # the warm reads spent part of the service's byte budget: let its
    # window roll so the measured window starts with a fresh one
    time.sleep(dep.service.config.budget_window)
    return len(shapes)


def run_window(dep: deploy.Deployment, ops: List[traffic.Op],
               seconds: float, traced: bool) -> RunData:
    from repro.kernels.dot_seen.ops import DISPATCHES

    ann = _annotate(traced)
    kinds = sorted({o.kind for o in ops})
    latency = {k: [] for k in kinds}
    service = {k: [] for k in kinds}
    stats: Dict[str, List[dict]] = {k: [] for k in READS}
    answers: List[reference.Answer] = []
    d0 = DISPATCHES.snapshot()
    io0 = dep.cluster.io_stats()
    net0 = dep.cluster.net.bytes_sent
    completed = 0
    with ann(tracing.WINDOW):
        t0 = time.perf_counter()
        close_at = t0 + seconds
        give_up = close_at + DRAIN_S
        for op in ops:
            due = t0 + op.due
            now = time.perf_counter()
            if now < due:
                with ann("gen.idle"):
                    if due - now > 0.002:
                        time.sleep(due - now - 0.001)
                    while time.perf_counter() < due:
                        pass
            start = time.perf_counter()
            if start > give_up:
                answers.append(_answer(op))
                continue
            with ann(LABELS[op.kind]):
                a, st, leased = execute(dep, op)
            end = time.perf_counter()
            if leased:  # a one-page scan: release its cursor lease
                dep.client.close()
            answers.append(a)
            if a.ok:
                latency[op.kind].append(end - due)
                service[op.kind].append(end - start)
                if st is not None:
                    stats[op.kind].append(st)
                if end <= close_at:
                    completed += 1
    return RunData(
        seconds=seconds, answers=answers, latency=latency, service=service,
        stats=stats, completed_in_window=completed,
        dispatch=DISPATCHES.delta(d0),
        io=dep.cluster.io_stats().delta(io0),
        net_bytes=dep.cluster.net.bytes_sent - net0)


def read_back(dep: deploy.Deployment,
              sets: List[traffic.SetData]) -> Dict[bytes, List[bytes]]:
    """Each set whole, paginated at the configuration's quorum."""
    from repro.query import Scan

    out: Dict[bytes, List[bytes]] = {}
    page_size = dep.service.config.max_page_size
    for s in sets:
        out[s.name] = [el for page in dep.client.pages(
            Scan(s.name, page_size=page_size), r=dep.r)
            for el in page.members]
    return out


def on_replicas(dep: deploy.Deployment,
                keys: List[reference.Key]) -> Dict[reference.Key, List[bool]]:
    """Each (set, element)'s presence on every replica its partition is
    written to, read from that replica alone (no quorum merge, no read
    repair)."""
    ring = dep.cluster.ring
    out: Dict[reference.Key, List[bool]] = {}
    for s, key in keys:
        pid = ring.partition(s, key)
        pset = ring.storage_set(s, pid)
        out[s, key] = [dep.cluster.vnodes[a].is_member(pset, key)[0]
                       for a in ring.owners(pid)]
    return out


def after_power_loss(dep: deploy.Deployment, keys: List[reference.Key]
                     ) -> Dict[reference.Key, List[bool]]:
    """Each (set, element)'s presence on every replica its partition is
    written to, after every vnode lost power at once and came back from
    its own media.

    First ``sync_all``, the program's acknowledgement barrier: it fsyncs
    what each store's group commit still holds.  Then every vnode crashes
    (memory and unsynced log tail gone) before any restarts, so no replica
    can be healed from another: what each holds comes from its media alone.
    """
    cluster, ring = dep.cluster, dep.cluster.ring
    cluster.sync_all()
    actors = list(cluster.vnodes)
    for a in actors:
        cluster.crash(a)
    for a in actors:
        cluster.restart(a)
    held: Dict[tuple, frozenset] = {}
    out: Dict[reference.Key, List[bool]] = {}
    for s, key in keys:
        pid = ring.partition(s, key)
        pset = ring.storage_set(s, pid)
        row = []
        for a in ring.owners(pid):
            if (a, pset) not in held:
                held[a, pset] = cluster.vnodes[a].value(pset)
            row.append(key in held[a, pset])
        out[s, key] = row
    return out


def p95_ms(xs: List[float]) -> Optional[float]:
    return float(np.percentile(xs, 95)) * 1e3 if xs else None


def p50_ms(xs: List[float]) -> Optional[float]:
    return float(np.percentile(xs, 50)) * 1e3 if xs else None


def end_to_end(run: RunData, setup_s: float,
               names: List[str]) -> Dict[str, Optional[float]]:
    """``setup_s``, ``ops_per_s``, and a tail ``<group>_p<q>_ms`` over all
    requests of one group of kinds (:data:`TAILS`) served in the run."""
    out = {"setup_s": setup_s,
           "ops_per_s": run.completed_in_window / run.seconds}
    for name in names:
        m = re.fullmatch(r"([a-z]+)_p(\d+)_ms", name)
        if m and m.group(1) in TAILS:
            xs = [x for k in TAILS[m.group(1)] for x in run.latency.get(k, ())]
            out[name] = (float(np.percentile(xs, int(m.group(2)))) * 1e3
                         if xs else None)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: str = CHECKOUT, require_chip: bool = True,
             overrides: Optional[dict] = None, rate: Optional[float] = None,
             fault=None, log=None) -> dict:
    """One run; returns the result line's object.

    ``fault`` makes a context manager that breaks the timed path
    underneath (the control and the fault tests): it is entered after
    set-up, before warm-up, and left after the read-back.
    """
    log = log or (lambda msg: print(msg, flush=True))
    cell = load_cell(workload, root, overrides)
    device = check_device(cell.chips) if require_chip else cpu_device()
    log(f"device: {device['kind']} x{device['count']}")
    log(f"compile cache: {enable_cache(root)}")
    with CompileCounter() as compiles:
        try:
            return _run(cell, seed, seconds, trace, t_start, root,
                        require_chip, device, rate or cell.rate, fault,
                        compiles, log)
        finally:
            gc.unfreeze()


def _run(cell, seed, seconds, trace, t_start, root, require_chip, device,
         rate, fault, compiles, log) -> dict:
    cfg = cell.cfg

    dep = deploy.deploy(cfg, os.path.join(root, "bench", "configs"),
                        cell.config)
    sets, first_new_keynum = traffic.data(cfg, seed)
    t0 = time.perf_counter()
    deploy.populate(dep, sets, int(cfg["write_batch"]))
    log(f"populate: {len(sets)} sets, "
        f"{sum(len(s.elements) for s in sets)} inserted, "
        f"{sum(len(s.removed) for s in sets)} removed in "
        f"{time.perf_counter() - t0:.3f}s")
    initial = {}
    for s in sets:
        gone = set(s.removed)
        initial[s.name] = [el for el in s.elements if el not in gone]
    gc.collect()
    gc.freeze()
    log(f"heap frozen: {gc.get_freeze_count()} objects")
    ops = traffic.schedule(cell.mix, rate, seconds, seed, sets,
                           first_new_keynum)
    fault_cm = fault() if fault is not None else contextlib.nullcontext()
    with fault_cm:
        shapes = warm(dep, sets, ops)
        log(f"warm: {shapes} kernel shapes, {compiles.count} compiles "
            f"({compiles.cache_hits} persistent-cache hits)")
        launches: List[tuple] = []
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            with contextlib.ExitStack() as stack:
                if trace:
                    stack.enter_context(launch_log(launches))
                    stack.enter_context(tracing.capture(trace_dir))
                before = compiles.count
                setup_s = time.perf_counter() - t_start
                with GcPauses() as pauses:
                    run = run_window(dep, ops, seconds, traced=trace)
                in_window = compiles.count - before
            if trace:
                run.trace = tracing.summarize(tracing.load(trace_dir))
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        run.launches = launches
        memory = _memory_peak()
        t0 = time.perf_counter()
        scan = read_back(dep, sets)
        written = [(a.set_name, a.key) for a in run.answers if a.ok and
                   a.kind in ("insert", "remove")]
        replicas = on_replicas(dep, written)
        t1 = time.perf_counter()
        restarted = (after_power_loss(dep, list(dict.fromkeys(
            [(s.name, el) for s in sets for el in s.elements] + written)))
            if cfg.get("durable") else None)
        log(f"read-back {t1 - t0:.3f}s, power loss and restart "
            f"{time.perf_counter() - t1:.3f}s")
    served = sum(1 for a in run.answers if a.ok)
    log(f"window: {len(ops)} due in {seconds}s at {rate} ops/s, "
        f"{served} served, {len(ops) - served} refused or unserved, "
        f"{run.completed_in_window} completed inside the window")
    log(f"compiles inside the window: {in_window}")
    log(f"garbage collections inside the window: {pauses.count}, "
        f"{pauses.full} full, {pauses.seconds:.3f}s")
    for kind in sorted(run.latency):
        lat = run.latency[kind]
        if lat:
            log(f"latency {kind}: n={len(lat)} p50={p50_ms(lat):.3f}ms "
                f"p95={p95_ms(lat):.3f}ms max={max(lat) * 1e3:.3f}ms "
                f"service p50={p50_ms(run.service[kind]):.3f}ms")
    verdict = reference.judge(initial, run.answers, scan, replicas,
                              restarted)
    for ex in verdict.examples:
        log(f"mismatch {ex}")

    result = {
        "correct": verdict.correct,
        "attempted": len(ops),
        "failed": len(ops) - served,
        "metrics": {},
        "device": dict(device, memory_peak_bytes=memory),
    }
    if trace:
        run.peaks = (roofline.peaks(device["kind"]) if require_chip
                     else None)
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        for m in cell.per_layer:
            value = metric_reader(m["name"], root)(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = tracing.breakdown(run.trace)
    else:
        e2e = end_to_end(run, setup_s, [m["name"] for m in cell.end_to_end])
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    result["checks"] = {k: {"value": n, "limit": reference.LIMITS[k]}
                        for k, n in verdict.numbers.items()}
    return result


def _memory_peak() -> int:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)
