#!/usr/bin/env python3
"""Chip smoke test: the served bigset path, end to end, on one TPU.

Builds the deployment ``repro.launch.serve_bigset`` builds — a
three-replica, fully replicated, synchronous :class:`BigsetCluster` behind
:class:`BigsetService` and :class:`BigsetClient` — and drives it through
the wire protocol only:

1. insert 500,000 elements (``b"%08d"``) in batches of 1,000;
2. remove a seeded random 10% of them, which fragments the set tombstone
   into tens of thousands of interval runs (nothing is compacted, so every
   read tests its keys against that tombstone on the device);
3. read the set back: a paginated ``Scan``, a ``Count``, ``Range`` pages
   and ``Membership`` probes, each checked against a plain Python-set
   reference built from the same seed.

Then it checks the device: every ``dot_seen`` launch of those reads must
have gone to the compiled Pallas kernel, and that kernel, called directly
on the real tombstone (and on the tombstone shifted up to counters of
``2**24 - 1``), must agree with the pure-jnp reference.

Usage::

    python3 chip_smoke.py [--seed N]

It refuses to run without a TPU.  Seconds printed per phase are smoke
timings of one run, not benchmark numbers.  The last line of stdout is
one JSON object, printed only when every phase passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SET = b"smoke"
N_ELEMENTS = 500_000
REMOVE_SHARE = 10        # percent of the inserted elements removed
BATCH = 1_000            # ops per client.batch request, elements per page


class SmokeFailure(Exception):
    """A phase gave a wrong answer."""


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_compile(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def ride_out(fn, *args, **kw):
    """Retry a request the service refused for its byte budget."""
    from repro.serve.bigset_service import Backpressure

    while True:
        try:
            return fn(*args, **kw)
        except Backpressure as bp:
            time.sleep(bp.retry_after)


def served_path(n_elements: int, seed: int, log=print) -> dict:
    """Write, remove and read back ``n_elements`` through the client.

    Returns what the device checks need: the cluster, the reference set of
    survivors and the ``DISPATCHES`` delta of the read phase.
    """
    import numpy as np

    from repro.cluster.clusters import BigsetCluster
    from repro.kernels.dot_seen.ops import DISPATCHES
    from repro.query import Count, Range, Scan
    from repro.serve.bigset_service import BigsetClient, BigsetService

    rng = np.random.default_rng(seed)
    cluster = BigsetCluster(3)  # full replication, sync=True
    client = BigsetClient(BigsetService(cluster))
    elements = [b"%08d" % i for i in range(n_elements)]

    t0 = time.perf_counter()
    for base in range(0, n_elements, BATCH):
        client.batch(SET, [["add", el] for el in elements[base:base + BATCH]])
    log(f"phase insert: {n_elements} elements in "
        f"{time.perf_counter() - t0:.3f}s (smoke timing)")

    n_removed = n_elements * REMOVE_SHARE // 100
    order = rng.permutation(n_elements)[:n_removed]
    removed = {elements[i] for i in order}
    t0 = time.perf_counter()
    for base in range(0, n_removed, BATCH):
        chunk = [elements[i] for i in order[base:base + BATCH]]
        results = client.batch(SET, [["remove", el] for el in chunk])
        expect(all(r.get("removed") for r in results),
               f"a remove in batch {base // BATCH} removed nothing")
    log(f"phase remove: {n_removed} elements in "
        f"{time.perf_counter() - t0:.3f}s (smoke timing)")
    survivors = [el for el in elements if el not in removed]
    log(f"elements inserted {n_elements}, removed {n_removed}, "
        f"surviving {len(survivors)}")

    before = DISPATCHES.snapshot()
    t0 = time.perf_counter()
    scanned = []
    n_pages = 0
    for page in client.pages(Scan(SET, page_size=BATCH)):
        scanned.extend(page.members)
        n_pages += 1
    expect(scanned == survivors,
           f"scan returned {len(scanned)} elements, want {len(survivors)} "
           "in order with none skipped or repeated")
    log(f"phase scan: {len(scanned)} elements in {n_pages} pages, "
        f"{time.perf_counter() - t0:.3f}s (smoke timing)")

    t0 = time.perf_counter()
    count = ride_out(client.query, Count(SET)).count
    expect(count == len(survivors), f"count {count} != {len(survivors)}")
    log(f"phase count: {count} in {time.perf_counter() - t0:.3f}s "
        "(smoke timing)")

    t0 = time.perf_counter()
    for lo in sorted(rng.integers(0, n_elements, 3).tolist()):
        start, end = elements[lo], b"%08d" % (lo + 3 * BATCH)
        want = [el for el in survivors if start <= el < end]
        plan = Range(SET, start=start, end=end, limit=BATCH)
        got = []
        cursor = None
        while True:
            page = ride_out(client.query, plan, cursor=cursor)
            expect(len(page.members) <= BATCH, "range page over its limit")
            got.extend(page.members)
            cursor = page.cursor
            if cursor is None:
                break
        expect(got == want, f"range [{start!r}, {end!r}) returned "
               f"{len(got)} elements, want {len(want)}")
    log(f"phase range: 3 ranges in {time.perf_counter() - t0:.3f}s "
        "(smoke timing)")

    t0 = time.perf_counter()
    gone = elements[int(order[0])]
    kept = survivors[len(survivors) // 2]
    present, _ = ride_out(client.membership, SET, gone)
    expect(not present, f"removed element {gone!r} is still a member")
    present, ctx = ride_out(client.membership, SET, kept)
    expect(present and bool(ctx), f"surviving element {kept!r} is missing")
    log(f"phase membership: 2 probes in {time.perf_counter() - t0:.3f}s "
        "(smoke timing)")

    client.close()
    return {"cluster": cluster, "survivors": survivors,
            "dispatches": DISPATCHES.delta(before)}


def check_kernel(tombstone, seed: int, log=print) -> None:
    """Compiled kernel vs reference on the real tombstone, and on the same
    tombstone shifted so its last run ends at the largest exact counter."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dots import Dot
    from repro.kernels.dot_seen import dot_seen_pallas, dot_seen_ref
    from repro.query.batch import MAX_COUNTER, BatchVisibility

    rng = np.random.default_rng(seed + 1)
    vis = BatchVisibility(tombstone)
    starts = np.asarray(vis.dense.starts)
    ends = np.asarray(vis.dense.ends)
    valid = starts <= ends
    rows, cols = np.nonzero(valid)
    # a sample of the runs, always with the one that ends highest
    pick = rng.choice(len(rows), size=min(len(rows), 2048), replace=False)
    pick = np.append(pick, np.argmax(ends[rows, cols]))
    r, c = rows[pick], cols[pick]
    n_actors = starts.shape[0]

    def probes(s, e):
        # both edges of the sampled runs and their outer neighbours, plus
        # counters drawn over the whole exact range
        actors = np.concatenate([r, r, r, r,
                                 rng.integers(0, n_actors, 4096)])
        counters = np.concatenate([s[r, c] - 1, s[r, c], e[r, c],
                                   e[r, c] + 1,
                                   rng.integers(0, MAX_COUNTER + 1, 4096)])
        return (actors.astype(np.int32),
                np.clip(counters, 0, MAX_COUNTER).astype(np.int32))

    def kernel(s, e, actors, counters):
        return np.asarray(dot_seen_pallas(
            jnp.asarray(s), jnp.asarray(e), jnp.asarray(actors),
            jnp.asarray(counters)))

    shift = MAX_COUNTER - int(ends[valid].max())
    for name, s, e in [
            ("real", starts, ends),
            ("shifted", np.where(valid, starts + shift, 1),
             np.where(valid, ends + shift, 0))]:
        actors, counters = probes(s, e)
        got = kernel(s, e, actors, counters)
        want = np.concatenate([
            np.asarray(dot_seen_ref(jnp.asarray(s), jnp.asarray(e),
                                    jnp.asarray(actors[i:i + 1024]),
                                    jnp.asarray(counters[i:i + 1024])))
            for i in range(0, len(actors), 1024)])
        bad = int((got != want).sum())
        expect(bad == 0, f"kernel disagrees with reference on {bad} of "
               f"{len(got)} {name} probes")
        log(f"kernel vs reference ({name}, counters up to "
            f"{int(counters.max())}): {len(got)} probes agree, "
            f"{int(got.sum())} seen")

    # the real tombstone's answers also match the sparse clock itself
    index = {i: a for a, i in vis._actor_index.items()}
    actors, counters = probes(starts, ends)
    got = kernel(starts, ends, actors, counters)
    oracle = np.array([
        a in index and tombstone.seen(Dot(index[a], int(k)))
        for a, k in zip(actors.tolist(), counters.tolist())])
    bad = int((got != oracle).sum())
    expect(bad == 0, f"kernel disagrees with the clock on {bad} probes")
    log(f"kernel vs the sparse clock: {len(got)} probes agree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {device['kind']} x{device['count']}")

    from repro.launch.compile_cache import enable_compile_cache
    from repro.query.batch import bucket_shape, dense_shape

    print(f"compile cache: {enable_compile_cache()}")
    compiles = CompileCounter()
    t_all = time.perf_counter()
    out = served_path(N_ELEMENTS, args.seed)
    d = out["dispatches"]
    print(f"dispatches (read phases): launches={d.launches} rows={d.rows} "
          f"pallas_launches={d.pallas_launches} interpreted={d.interpreted}")
    expect(d.pallas_launches > 0, "no dot_seen launch reached Pallas")
    expect(d.pallas_launches == d.launches and d.interpreted == 0,
           "a served dot_seen launch ran the reference or the interpreter")

    cluster = out["cluster"]
    for actor in cluster.actors:
        ts = cluster.vnodes[actor].read_tombstone(SET)
        shape = dense_shape(ts)
        print(f"tombstone {actor}: (A, R) = {shape} -> bucketed "
              f"{bucket_shape(*shape)}")
    expect(dense_shape(ts)[1] > 4096,
           f"tombstone has only {dense_shape(ts)[1]} runs in its widest row")
    t0 = time.perf_counter()
    check_kernel(ts, args.seed)
    print(f"phase kernel check: {time.perf_counter() - t0:.3f}s "
          "(smoke timing)")
    print(f"compiles: {compiles.count} in {compiles.seconds:.3f}s, "
          f"{compiles.cache_hits} persistent-cache hits")
    print(f"total: {time.perf_counter() - t_all:.3f}s (smoke timing)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
