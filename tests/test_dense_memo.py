"""The densified tombstone kept on the device while it is unchanged.

Each replica stream reads its vnode's tombstone and densifies it only when
it is not the very ``Clock`` object the memo's entry for that (vnode, set)
was built from (``query/executor.py``).  The contracts:

* two pages over an unchanged tombstone build once per replica stream and
  then reuse, and ``QueryStats.tombstone_builds``/``tombstone_reuses`` and
  the ``query.stream_open`` span's ``dense`` attribute say so;
* a remove, or a compaction that trims the tombstone, retires the entry,
  so no page ever tests against a stale tombstone;
* every answer still equals the scalar oracle and the Python-set
  reference, and after every query the entry a stream used is the vnode's
  current tombstone object.
"""
import contextlib

from hypothesis import given, settings, strategies as st

from repro.cluster.clusters import BigsetCluster
from repro.cluster.placement import Ring
from repro.obs.trace import Tracer
from repro.query import executor
from repro.query.batch import BatchVisibility
from repro.query.plan import Count, Membership, Range, Scan

S = b"memo_set"
ELEMS = [b"m%03d" % i for i in range(48)]


def seeded(n=300, step=3, tracer=None, ring=None):
    """A synchronous cluster holding ``n`` elements, every ``step``-th
    removed: a non-empty tombstone on every replica."""
    if ring is not None:
        c = BigsetCluster(ring=ring, sync=True, tracer=tracer)
    else:
        c = BigsetCluster(3, sync=True, tracer=tracer)
    for i in range(n):
        c.add(S, b"e%04d" % i)
    for i in range(0, n, step):
        c.remove(S, b"e%04d" % i)
    return c


def scalar_oracle(vnode, set_name=S):
    """The visible ``(element, dots)`` of one replica by the scalar path
    (``BatchVisibility.seen_scalar``), in element order."""
    raw = list(vnode.fold_raw(set_name))
    vis = BatchVisibility(vnode.read_tombstone(set_name))
    dead = vis.seen_scalar([d for _el, d, _v in raw])
    out = {}
    for (el, d, _v), is_dead in zip(raw, dead):
        if not is_dead:
            out.setdefault(el, set()).add(d)
    return [(el, frozenset(ds)) for el, ds in sorted(out.items())]


def entries(res):
    return [(el, frozenset(ds)) for el, ds in res.entries]


def counts(res):
    return res.stats.tombstone_builds, res.stats.tombstone_reuses


def stream_attrs(tr):
    spans = tr.drain()
    return ([s.attrs.get("dense") for s in spans
             if s.name == "query.stream_open"],
            sum(s.name == "query.visibility_build" for s in spans),
            sum(s.name == "kernel.h2d" for s in spans))


def memo_entry(vnode, set_name=S):
    return executor._DENSE.get(vnode, {}).get(set_name)


class TestMemo:
    def test_two_pages_build_once_per_replica_stream_then_reuse(self):
        tr = Tracer()
        c = seeded(tracer=tr)
        tr.clear()
        oracle = scalar_oracle(c.vnodes["vnode0"])
        assert len(oracle) == 200

        p1 = c.query(Range(S, limit=120), r=2)
        assert counts(p1) == (2, 0)
        assert stream_attrs(tr) == (["build", "build"], 2, 2)
        p2 = c.query(Range(S, limit=120, cursor=p1.cursor), r=2)
        assert counts(p2) == (0, 2)
        # the memo lookup is the stream's visibility set-up: no copy
        assert stream_attrs(tr) == (["reuse", "reuse"], 2, 0)

        assert entries(p1) == oracle[:120]
        assert entries(p2) == oracle[120:]
        # the reused entry still drives the kernel, and the runs each
        # stream tests against are counted as before
        assert p2.stats.kernel_launches > 0
        assert p2.stats.tombstone_runs == p1.stats.tombstone_runs > 0

    def test_remove_between_pages_forces_a_rebuild(self):
        c = seeded()
        p1 = c.query(Range(S, limit=100), r=2)
        gone = scalar_oracle(c.vnodes["vnode0"])[150][0]
        old = memo_entry(c.vnodes["vnode0"])
        c.remove(S, gone)
        p2 = c.query(Range(S, limit=100, cursor=p1.cursor), r=2)
        assert counts(p2) == (2, 0)
        assert gone not in p2.members
        assert entries(p2) == scalar_oracle(c.vnodes["vnode0"])[100:200]
        vn = c.vnodes["vnode0"]
        assert memo_entry(vn) is not old
        assert memo_entry(vn).tombstone is vn.read_tombstone(S)

    def test_compaction_that_trims_the_tombstone_retires_the_entry(self):
        c = seeded()
        p1 = c.query(Range(S, limit=100), r=2)
        before = {a: vn.read_tombstone(S) for a, vn in c.vnodes.items()}
        for vn in c.vnodes.values():
            vn.compact()
        for a, vn in c.vnodes.items():
            after = vn.read_tombstone(S)
            assert after is not before[a]
            assert after.n_runs() < before[a].n_runs()
        # every covered key was discarded, so the trimmed tombstone is
        # empty: nothing to build, and the stale entry is neither reused
        # nor kept
        p2 = c.query(Range(S, limit=100, cursor=p1.cursor), r=2)
        assert counts(p2) == (0, 0)
        assert entries(p2) == scalar_oracle(c.vnodes["vnode0"])[100:200]
        assert all(memo_entry(vn) is None for vn in c.vnodes.values())
        # the next remove's tombstone is built afresh on every stream
        gone = p2.members[-1]
        c.remove(S, gone)
        p3 = c.query(Range(S, limit=100, cursor=p1.cursor), r=2)
        assert counts(p3) == (2, 0)
        assert gone not in p3.members
        for vn in c.vnodes.values():
            entry = memo_entry(vn)
            assert entry is None or entry.tombstone is vn.read_tombstone(S)

    def test_membership_after_a_range_reuses_the_entry(self):
        c = seeded()
        c.query(Range(S, limit=50), r=2)
        hit = c.query(Membership(S, b"e0001"), r=2)
        miss = c.query(Membership(S, b"e0003"), r=2)
        assert hit.present and not miss.present
        assert counts(hit) == (0, 2) and counts(miss) == (0, 2)

    def test_ring_streams_build_once_per_partition_replica(self):
        ring = Ring.build(["vnode0", "vnode1", "vnode2"], factor=3,
                          n_partitions=2)
        c = seeded(ring=ring)
        p1 = c.query(Range(S, limit=60), r=2)
        p2 = c.query(Range(S, limit=60, cursor=p1.cursor), r=2)
        assert counts(p1) == (4, 0)          # partitions x r
        assert counts(p2) == (0, 4)
        assert p2.stats.tombstone_runs == p1.stats.tombstone_runs > 0


# ------------------------------------------------------------------ property
op_st = st.one_of(
    st.tuples(st.sampled_from(["add", "remove", "member"]),
              st.integers(0, len(ELEMS) - 1)),
    st.tuples(st.just("compact"), st.integers(0, 2)),
    st.tuples(st.just("range"), st.integers(0, len(ELEMS) - 1),
              st.integers(1, 40)),
    st.tuples(st.just("scan"), st.integers(5, 30)),
    st.tuples(st.just("count")),
)


@contextlib.contextmanager
def streams_read(log):
    """Record ``(vnode, set name, tombstone)`` of every stream opened."""
    orig = executor.QueryExecutor._visibility

    def recording(self, set_name, stats, stream):
        vis = orig(self, set_name, stats, stream)
        log.append((self.vnode, set_name, vis.tombstone))
        return vis

    executor.QueryExecutor._visibility = recording
    try:
        yield
    finally:
        executor.QueryExecutor._visibility = orig


def check_run(cluster, ops):
    # every element added and a few removed first: a chunk reaches
    # MIN_BATCH dots, so the kernel tests them against a dense tombstone
    # from the first query on
    for el in ELEMS:
        cluster.add(S, el)
    for el in ELEMS[::7]:
        cluster.remove(S, el)
    ref = set(ELEMS) - set(ELEMS[::7])
    log = []
    with streams_read(log):
        for op in ops:
            kind = op[0]
            del log[:]
            if kind == "add":
                cluster.add(S, ELEMS[op[1]])
                ref.add(ELEMS[op[1]])
                continue
            if kind == "remove":
                cluster.remove(S, ELEMS[op[1]])
                ref.discard(ELEMS[op[1]])
                continue
            if kind == "compact":
                list(cluster.vnodes.values())[op[1]].compact()
                continue
            want = sorted(ref)
            if kind == "member":
                res = cluster.query(Membership(S, ELEMS[op[1]]))
                assert res.present == (ELEMS[op[1]] in ref)
            elif kind == "range":
                res = cluster.query(Range(S, start=ELEMS[op[1]],
                                          limit=op[2]))
                assert res.members == [
                    e for e in want if e >= ELEMS[op[1]]][:op[2]]
            elif kind == "scan":
                got, cursor = [], None
                while True:
                    res = cluster.query(Scan(S, page_size=op[1],
                                             cursor=cursor))
                    got += res.members
                    cursor = res.cursor
                    if cursor is None:
                        break
                assert got == want
            else:
                res = cluster.query(Count(S))
                assert res.count == len(want)
            # every stream tested against its vnode's current tombstone:
            # a memo entry, where there is one, is that very object
            for vnode, set_name, ts in log:
                assert ts is vnode.read_tombstone(set_name)
                entry = memo_entry(vnode, set_name)
                if ts.is_zero():
                    assert entry is None
                else:
                    assert entry.tombstone is ts
            if kind != "scan":   # a scan's log spans all its pages
                opened = sum(not ts.is_zero() for _v, _s, ts in log)
                assert counts(res)[0] + counts(res)[1] == opened


class TestMemoProperty:
    @given(st.lists(op_st, min_size=8, max_size=30))
    @settings(max_examples=25, deadline=None)
    def test_full_replication_matches_the_reference(self, ops):
        check_run(BigsetCluster(3, sync=True), ops)

    @given(st.lists(op_st, min_size=8, max_size=30))
    @settings(max_examples=25, deadline=None)
    def test_two_partition_ring_matches_the_reference(self, ops):
        ring = Ring.build(["vnode0", "vnode1", "vnode2"], factor=3,
                          n_partitions=2)
        check_run(BigsetCluster(ring=ring, sync=True), ops)
