"""Observability stack: tracing, metrics, exporters, and the stats op.

The load-bearing invariants, in test order:

* **One tree per request.**  A traced quorum query yields a single span
  tree rooted at the serve layer covering every downstream layer —
  coordinator, replica streams, storage, the visibility rebuild and
  kernel launches, network deliveries, read repair — and stays a tree
  (zero orphans) under drop/duplicate/reorder delivery.  Every child lies
  inside its parent, with one span per stream and per launch.
* **Determinism under injected clocks.**  Fake clocks make span
  durations and histogram contents exact, and two identical runs
  produce identical traces.
* **Disabled ⇒ zero behavior change.**  The default NULL_TRACER wraps
  no payloads: wire traffic is byte-identical with tracing off
  (ARCHITECTURE invariant 10), builds no profiler annotation, and
  ``Network.send`` refuses un-billed non-empty payloads so wire
  accounting cannot silently read zero.
* **Profiler mode.**  ``Tracer(profiler=True)`` opens one profiler
  annotation per span for exactly its lifetime and keeps no spans.
"""
import json

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro.cluster.clusters import BigsetCluster, TracedPayload
from repro.cluster.sim import Network
from repro.cluster.placement import Ring
from repro.obs.export import span_trees, spans_to_chrome, tree_names
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               lift_dispatch_stats, lift_network)
from repro.obs.trace import NULL_TRACER, TraceContext, Tracer
from repro.query import executor
from repro.query.plan import Membership, Range, Scan
from repro.serve.bigset_service import (BigsetClient, BigsetService,
                                        ServiceConfig)

SET = b"obs_set"


def ticking_clock(step=1.0, start=0.0):
    """Deterministic monotonic clock: advances ``step`` per call."""
    state = [start]

    def clk():
        state[0] += step
        return state[0]

    return clk


def build_traced(net=None, tracer=None, n=3):
    tr = tracer or Tracer(clock=ticking_clock())
    cluster = BigsetCluster(n, net=net, sync=True, tracer=tr)
    service = BigsetService(cluster, clock=ticking_clock(step=0.001))
    client = BigsetClient(service)
    return tr, cluster, service, client


# =============================================================== trace layer
class TestTracer:
    def test_injected_clock_exact_durations(self):
        tr = Tracer(clock=ticking_clock())
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        inner, outer = tr.spans  # finish order: inner first
        assert (inner.start, inner.end, inner.duration) == (2.0, 3.0, 1.0)
        assert (outer.start, outer.end, outer.duration) == (1.0, 4.0, 3.0)

    def test_implicit_and_explicit_parenting(self):
        tr = Tracer(clock=ticking_clock())
        with tr.span("root") as root:
            with tr.span("child") as child:
                pass
            # explicit context parenting — the network-crossing idiom
            remote = tr.finish(tr.start("remote", parent=root.context()))
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert remote.parent_id == root.span_id
        assert root.parent_id is None

    def test_error_attr_on_exception(self):
        tr = Tracer(clock=ticking_clock())
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        (sp,) = tr.spans
        assert sp.attrs["error"] == "ValueError"
        assert sp.end is not None  # finished even on the raise path

    def test_identical_runs_identical_trees(self):
        def run():
            tr = Tracer(clock=ticking_clock())
            with tr.span("a"):
                with tr.span("b"):
                    tr.finish(tr.start("c"))
            return [(s.name, s.trace_id, s.span_id, s.parent_id, s.start,
                     s.end) for s in tr.spans]

        assert run() == run()

    def test_null_tracer_records_nothing(self):
        with NULL_TRACER.span("anything") as sp:
            sp.set(huge=list(range(100)))
        assert NULL_TRACER.spans == []
        assert not NULL_TRACER.enabled
        assert sp.attrs == {}  # set() was a no-op

    def test_profiler_mode_annotates_each_span_and_keeps_none(
            self, monkeypatch):
        events = []

        class Annotation:
            def __init__(self, name, **attrs):
                self.name, self.attrs = name, attrs

            def __enter__(self):
                events.append(("enter", self.name, self.attrs))

            def __exit__(self, *exc):
                events.append(("exit", self.name, {}))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        tr = Tracer(clock=ticking_clock(), profiler=True)
        with tr.span("outer", n=1) as outer:
            with tr.span("inner"):
                pass
            late = tr.start("remote", parent=outer.context())
            tr.finish(late)
            outer.set(ignored=True)  # the block gets the inert span
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        assert events == [
            ("enter", "outer", {"n": 1}), ("enter", "inner", {}),
            ("exit", "inner", {}), ("enter", "remote", {}),
            ("exit", "remote", {}), ("exit", "outer", {}),
            ("enter", "boom", {}), ("exit", "boom", {})]
        assert tr.spans == []      # the profiler's buffer holds them
        assert late.end is not None and outer.attrs == {}


# ==================================================================== metrics
class TestMetrics:
    def test_counter_monotonic(self):
        c = Counter()
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_histogram_deterministic_buckets(self):
        h = Histogram(buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 3.0, 100.0):
            h.observe(v)
        # bisect_left: upper bounds inclusive; last slot is overflow
        assert h.counts == [2, 1, 1, 1]
        assert h.count == 5
        h2 = Histogram(buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 3.0, 100.0):
            h2.observe(v)
        assert h2.snapshot() == h.snapshot()

    def test_histogram_rejects_unsorted(self):
        with pytest.raises(ValueError):
            Histogram(buckets=(2.0, 1.0))

    def test_registry_kind_and_bucket_conflicts(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")
        reg.histogram("h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(1.0, 3.0))
        # get-or-create is idempotent
        assert reg.counter("x") is reg.counter("x")

    def test_lift_network_and_dispatch(self):
        reg = MetricsRegistry()
        net = Network()
        net.send("a", "b", b"payload", 7)
        lift_network(reg, net)
        lift_dispatch_stats(reg)
        snap = reg.snapshot()
        assert snap["net.bytes_sent"]["value"] == 7
        assert snap["net.msgs_sent"]["value"] == 1
        assert "kernels.dot_seen.launches" in snap
        assert "kernels.dot_seen.rows" in snap


# ============================================================== wire billing
class TestWireBilling:
    def test_send_requires_billing_nonempty(self):
        net = Network()
        with pytest.raises(ValueError):
            net.send("a", "b", b"not empty", 0)

    def test_send_allows_empty_control_payloads(self):
        net = Network()
        net.send("a", "b", None, 0)
        net.send("a", "b", b"", 0)
        assert net.msgs_sent == 2 and net.bytes_sent == 0


# ===================================================== end-to-end span trees
def diverge(cluster, elements):
    """Insert ``elements`` on vnode0 only — quorum queries must read-repair."""
    for el in elements:
        cluster.vnodes["vnode0"].coordinate_insert(SET, el, ())


class TestTracedQuery:
    def test_single_tree_covers_every_layer(self):
        """The acceptance check: one traced quorum query exports ONE span
        tree covering serve -> executor -> storage -> kernel -> network ->
        read-repair."""
        tr, cluster, service, client = build_traced()
        client.batch(SET, [["add", b"r%02d" % i] for i in range(5)])
        client.batch(SET, [["remove", b"r00"]])  # a tombstone to rebuild
        diverge(cluster, [b"x%02d" % i for i in range(3)])
        tr.clear()  # keep only the query's spans

        page = client.query(Scan(SET, page_size=100))
        assert len(page.entries) == 7

        spans = tr.drain()
        trees = span_trees(spans)
        assert len(trees) == 1, "one request, one trace"
        (tree,) = trees.values()
        assert tree["orphans"] == []
        assert [r.name for r in tree["roots"]] == ["serve.request"]

        names = tree_names(spans)
        assert names["serve.request"] == 1
        assert names["cluster.query"] == 1          # executor scatter
        assert names["query.stream_open"] == 2      # majority quorum of 3
        # each stream's tombstone, then the two replicas' set clocks
        assert names["storage.read_clock"] == 3
        assert names["query.visibility_build"] == 2
        assert names["kernel.h2d"] == 2
        assert names["storage.seek"] == 2
        # each stream pulls its chunk, then finds the range's end
        assert names["storage.scan"] == 4
        # 7 or 4 dots a chunk, under MIN_BATCH: tested on the host
        assert names["query.host_seen"] == 2
        assert "kernel.dot_seen" not in names
        assert names["query.read_repair"] == 3      # one per replayed element
        assert names["net.deliver"] == 3            # each replay delivered

    def test_read_repair_spans_carry_replay_counts(self):
        tr, cluster, service, client = build_traced()
        client.batch(SET, [["add", b"a"]])
        diverge(cluster, [b"solo"])
        tr.clear()
        client.query(Scan(SET, page_size=100))
        repairs = [s for s in tr.spans if s.name == "query.read_repair"]
        assert len(repairs) == 1
        assert repairs[0].attrs["replayed"] == 1
        assert repairs[0].attrs["element"] == b"solo"
        # its net.deliver child parents on it, not on the query span
        delivers = [s for s in tr.spans if s.name == "net.deliver"]
        assert {d.parent_id for d in delivers} == {repairs[0].span_id}

    def test_membership_query_tree(self):
        tr, cluster, service, client = build_traced()
        client.batch(SET, [["add", b"present"]])
        tr.clear()
        page = client.query(Membership(SET, b"present"))
        assert page.present
        names = tree_names(tr.spans)
        assert names["serve.request"] == 1
        assert names["cluster.query"] == 1
        assert names["query.stream_open"] == 2      # one probe per replica
        # each replica's set clock and tombstone
        assert names["storage.read_clock"] == 4
        assert names["storage.seek"] == 2
        # nothing removed: no tombstone to rebuild, nothing to launch
        assert "query.visibility_build" not in names
        assert "kernel.dot_seen" not in names

    def test_traced_range_over_a_ring_nests_per_stream_and_launch(
            self, monkeypatch):
        """A Range over a 2-partition ring: every child lies inside its
        parent; one ``query.stream_open`` per replica stream; one
        ``kernel.dot_seen`` and one ``kernel.mask_sync`` per launch; and
        ``tombstone_runs`` sums the runs of every tombstone rebuilt."""
        rebuilt = []

        class Recording(executor.BatchVisibility):
            def __init__(self, tombstone, **kw):
                rebuilt.append(tombstone.n_runs())
                super().__init__(tombstone, **kw)

        monkeypatch.setattr(executor, "BatchVisibility", Recording)
        tr = Tracer(clock=ticking_clock())
        ring = Ring.build(["vnode0", "vnode1", "vnode2"], factor=3,
                          n_partitions=2)
        cluster = BigsetCluster(ring=ring, sync=True, tracer=tr)
        elements = [b"e%03d" % i for i in range(120)]
        for el in elements:
            cluster.add(SET, el)
        for el in elements[::3]:
            cluster.remove(SET, el)
        tr.clear()

        res = cluster.query(Range(SET, limit=60), r=2)
        assert len(res.entries) == 60
        spans = tr.drain()
        by_id = {s.span_id: s for s in spans}
        for s in spans:
            if s.parent_id is not None:
                parent = by_id[s.parent_id]
                assert parent.start < s.start and s.end < parent.end, s
        count = lambda name: sum(s.name == name for s in spans)
        assert count("query.stream_open") == 2 * 2   # partitions x r
        assert len(rebuilt) == 4
        launches = res.stats.kernel_launches
        assert launches >= 2                         # a chunk per partition
        assert count("kernel.dot_seen") == launches
        assert count("kernel.mask_sync") == launches
        assert count("query.pack") == launches
        for s in spans:
            if s.name == "kernel.dot_seen":
                assert s.attrs["N"] % 512 == 0 and s.attrs["A"] == 8
                assert s.attrs["R"] == 512
        assert res.stats.tombstone_runs == sum(rebuilt) > 0

    @given(st.sampled_from([0.0, 0.15, 0.3]), st.sampled_from([0.0, 0.2]),
           st.booleans(), st.integers(0, 2 ** 16))
    @settings(max_examples=25, deadline=None)
    def test_tree_integrity_under_lossy_delivery(self, drop, dup, reorder,
                                                 seed):
        """Property: every replica sub-span of a traced quorum query parents
        under the coordinator root across drop/duplicate/reorder schedules —
        lossy delivery loses leaves, never tree integrity."""
        net = Network(seed=seed, drop_prob=drop, dup_prob=dup,
                      reorder=reorder)
        tr, cluster, service, client = build_traced(net=net)
        client.batch(SET, [["add", b"e%02d" % i] for i in range(6)])
        tr.clear()
        client.query(Scan(SET, page_size=100))

        trees = span_trees(tr.spans)
        assert len(trees) == 1
        (tree,) = trees.values()
        assert tree["orphans"] == []
        assert [r.name for r in tree["roots"]] == ["serve.request"]
        names = tree_names(tr.spans)
        # the synchronous skeleton is delivery-independent ...
        assert names["cluster.query"] == 1
        assert names["query.stream_open"] == 2
        assert names["storage.seek"] == 2
        assert names["storage.scan"] >= 2
        # ... and whatever repair traffic was delivered landed in-tree
        assert names.get("net.deliver", 0) + len(tree["orphans"]) == \
            sum(1 for s in tr.spans if s.name == "net.deliver")

    def test_antientropy_round_spans(self):
        tr, cluster, service, client = build_traced()
        client.batch(SET, [["add", b"a"], ["add", b"b"]])
        tr.clear()
        assert cluster.tick(budget=1) == 1
        names = {s.name for s in tr.spans}
        assert {"ae.round", "ae.pull", "net.deliver"} <= names
        trees = span_trees(tr.spans)
        for tree in trees.values():
            assert tree["orphans"] == []

    def test_converged_pair_zero_fold_with_tracing(self):
        """Tracing on must not disturb the PR-5 zero-fold property: a
        converged pair syncs from digests alone (no keys folded)."""
        tr, cluster, service, client = build_traced()
        client.batch(SET, [["add", b"e%02d" % i] for i in range(8)])
        cluster.settle()
        cluster.tick(budget=4)
        stats = cluster.ae_stats()
        assert stats.keys_scanned == 0
        assert stats.rounds_skipped > 0
        assert any(s.name == "ae.round" for s in tr.spans)


# ============================================================ disabled = noop
class TestDisabledNoop:
    def workload(self, tracer):
        net = Network(seed=42)
        cluster = BigsetCluster(3, net=net, sync=True, tracer=tracer)
        service = BigsetService(cluster, clock=ticking_clock(step=0.001))
        client = BigsetClient(service)
        client.batch(SET, [["add", b"w%02d" % i] for i in range(10)])
        client.batch(SET, [["remove", b"w03"]])
        page = client.query(Scan(SET, page_size=100))
        return net, [e for e, _ in page.entries]

    def test_wire_traffic_byte_identical(self):
        """Invariant 10: tracing disabled is a strict no-op — the disabled
        run ships byte-identical traffic because payloads are never
        wrapped, and the traced run bills identical sizes because the
        TracedPayload context rides outside ``size_bytes``."""
        net_off, entries_off = self.workload(None)  # NULL_TRACER default
        net_on, entries_on = self.workload(Tracer(clock=ticking_clock()))
        assert entries_off == entries_on
        assert net_off.bytes_sent == net_on.bytes_sent
        assert net_off.msgs_sent == net_on.msgs_sent

    def test_null_tracer_builds_no_profiler_annotation(self, monkeypatch):
        """Tracing off, a read with kernel launches never touches the
        profiler; the same read in profiler mode does (the control)."""
        def refuse(*a, **kw):
            raise AssertionError("TraceAnnotation built")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        cluster = BigsetCluster(3, sync=True)
        for i in range(80):
            cluster.add(SET, b"n%03d" % i)
        cluster.remove(SET, b"n000")
        res = cluster.query(Scan(SET, page_size=100))
        assert len(res.entries) == 79 and res.stats.kernel_launches > 0
        cluster.tracer = Tracer(profiler=True)
        with pytest.raises(AssertionError):
            cluster.query(Scan(SET, page_size=100))

    def test_disabled_cluster_never_wraps_payloads(self):
        captured = []
        net = Network()
        orig = net.send

        def spy(src, dst, payload, size_bytes):
            captured.append(payload)
            orig(src, dst, payload, size_bytes)

        net.send = spy
        cluster = BigsetCluster(3, net=net, sync=True)  # tracing off
        cluster.add(SET, b"el")
        cluster.query(Scan(SET, page_size=10))
        assert captured and not any(
            isinstance(p, TracedPayload) for p in captured)


# ================================================================= exporters
class TestExporters:
    def make_spans(self):
        tr, cluster, service, client = build_traced()
        client.batch(SET, [["add", b"a"], ["add", b"b"]])
        diverge(cluster, [b"c"])
        tr.clear()
        client.query(Scan(SET, page_size=100))
        return tr.drain()

    def test_chrome_trace_round_trip(self):
        """The CI smoke check in library form: a Chrome trace-event export
        re-parses into >= 1 complete span tree."""
        spans = self.make_spans()
        doc = json.loads(json.dumps(spans_to_chrome(spans)))
        events = doc["traceEvents"]
        assert len(events) == len(spans)
        ids = {e["args"]["span_id"] for e in events}
        roots = 0
        for e in events:
            assert e["ph"] == "X"
            assert e["dur"] >= 0
            parent = e["args"]["parent_id"]
            assert parent is None or parent in ids
            roots += parent is None
        assert roots >= 1

    def test_bytes_attrs_are_json_safe(self):
        tr = Tracer(clock=ticking_clock())
        tr.finish(tr.start("s", set_name=b"\xff\xfe", pair=[b"a", b"b"]))
        (event,) = json.loads(json.dumps(spans_to_chrome(tr.spans)))[
            "traceEvents"]
        assert event["args"]["pair"] == ["a", "b"]
        assert isinstance(event["args"]["set_name"], str)


# ================================================================== stats op
class TestStatsOp:
    def test_stats_snapshot_node_and_session(self):
        tr, cluster, service, client = build_traced()
        client.batch(SET, [["add", b"s%02d" % i] for i in range(4)])
        client.query(Scan(SET, page_size=100))
        out = client.stats()
        node, session = out["node"], out["session"]
        for name in ("storage.bytes_read", "net.bytes_sent",
                     "kernels.dot_seen.launches", "antientropy.rounds",
                     "serve.sessions", "query.bytes_read"):
            assert name in node, name
        assert node["serve.requests"]["type"] == "counter"
        assert node["serve.requests"]["value"] >= 3  # batch, query, stats
        assert node["serve.request_seconds"]["type"] == "histogram"
        assert node["serve.request_seconds"]["count"] >= 3
        assert session["mutations"] == 4
        assert session["pages"] == 1
        assert session["bytes_read"] > 0

    def test_session_stats_isolated_per_session(self):
        tr, cluster, service, client_a = build_traced()
        client_b = BigsetClient(service)
        client_a.batch(SET, [["add", b"a"]])
        client_b.batch(SET, [["add", b"b"], ["add", b"c"]])
        assert client_a.stats()["session"]["mutations"] == 1
        assert client_b.stats()["session"]["mutations"] == 2

    def test_metrics_deterministic_under_injected_clocks(self):
        def run():
            tr, cluster, service, client = build_traced()
            client.batch(SET, [["add", b"d%02d" % i] for i in range(3)])
            client.query(Scan(SET, page_size=100))
            snap = service.metrics.snapshot()
            # dispatch gauges track a process-global ledger — not a
            # per-run quantity, so exclude them from the equality check
            return {k: v for k, v in snap.items()
                    if not k.startswith("kernels.")}

        assert run() == run()
