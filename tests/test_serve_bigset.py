"""Serve-layer tests: wire protocol, cursor leases, backpressure, IO cost.

The four contracts of :mod:`repro.serve.bigset_service`:

* the wire codec round-trips every plan shape and rejects malformed
  envelopes with typed errors;
* pagination through the service is exact — pages concatenate to the
  one-shot result with no re-emitted and no skipped elements, even when
  backpressure rejections interleave with resumes (property-tested, runs
  under the hypothesis fallback shim);
* admission control is observable (``retry`` + retry-after hint) and a
  rejected page never invalidates its cursor lease, while idle leases
  expire and foreign sessions are refused;
* the paper's cost claim at the serve layer: each page of a 100k-element
  Scan reads O(page + causal metadata) bytes (per-page IoStats).
"""
import msgpack
import pytest
from hypothesis import given, settings, strategies as st

from repro.cluster.clusters import BigsetCluster
from repro.core.bigset import BigsetVnode
from repro.index import by_element_suffix
from repro.obs.trace import Tracer
from repro.query import (Count, IndexLookup, IndexRange, Join, LeaseError,
                         Membership, PlanError, Range, Scan, plan_from_wire,
                         plan_to_wire, unwrap_lease, wrap_lease)
from repro.serve.bigset_service import (ANON_SESSION, STATUS_ERROR, STATUS_OK,
                                        STATUS_RETRY, WIRE_VERSION,
                                        Backpressure, BigsetClient,
                                        BigsetService, ServiceConfig,
                                        ServiceError)
from repro.storage.lsm import LsmStore

S = b"srvset"
T = b"srvset2"
ELEMS = [b"a", b"b", b"c", b"d", b"e", b"f", b"g", b"h", b"i", b"j"]

ops_st = st.lists(
    st.tuples(
        st.sampled_from(["add", "rem"]),
        st.integers(0, 2),
        st.sampled_from(ELEMS),
    ),
    max_size=24,
)


def make_service(n=3, config=None):
    """Service over a fresh cluster with a test-controlled clock."""
    cluster = BigsetCluster(n)
    clk = [0.0]
    service = BigsetService(cluster, config, clock=lambda: clk[0])
    return cluster, service, BigsetClient(service), clk


def apply_ops(cluster, ops, set_name=S):
    for op, coord, el in ops:
        if op == "add":
            cluster.add(set_name, el, coordinator=coord)
        else:
            cluster.remove(set_name, el, coordinator=coord)


# ---------------------------------------------------------------- wire codec
class TestPlanWire:
    PLANS = [
        Membership(S, b"x"),
        Range(S, start=b"a", end=b"z", limit=10),
        Range(S, cursor=b"tok"),
        Count(S, start=b"b"),
        Scan(S, page_size=7),
        Join("intersect", S, T, limit=3),
        Join("union", S, T),
        Join("difference", S, T, cursor=b"tok"),
        IndexLookup(S, b"idx", b"key", limit=2),
        IndexRange(S, b"idx", start=b"a", end=b"m", limit=5, cursor=b"tok"),
    ]

    def test_roundtrip_every_shape(self):
        for plan in self.PLANS:
            assert plan_from_wire(plan_to_wire(plan)) == plan

    @given(st.binary(max_size=12), st.binary(max_size=12),
           st.integers(1, 1000))
    @settings(max_examples=40)
    def test_roundtrip_property(self, set_name, start, limit):
        plan = Range(set_name or b"s", start=start or None, limit=limit)
        assert plan_from_wire(plan_to_wire(plan)) == plan

    def test_malformed_envelopes(self):
        with pytest.raises(PlanError):
            plan_from_wire(b"\xffnot-msgpack")
        with pytest.raises(PlanError):
            plan_from_wire(msgpack.packb(["nope"]))
        with pytest.raises(PlanError):  # wrong version
            plan_from_wire(msgpack.packb([99, "scan", {"set_name": S}]))
        with pytest.raises(PlanError):  # unknown shape
            plan_from_wire(msgpack.packb([1, "explode", {}]))
        with pytest.raises(PlanError):  # unknown field
            plan_from_wire(msgpack.packb(
                [1, "scan", {"set_name": S, "hacker": 1}]))
        with pytest.raises(PlanError):  # fails plan validation
            plan_from_wire(msgpack.packb(
                [1, "scan", {"set_name": S, "page_size": -4}]))

    def test_invalid_plan_never_encodes(self):
        with pytest.raises(PlanError):
            plan_to_wire(Scan(S, page_size=0))


# -------------------------------------------------------------------- leases
class TestLeases:
    def test_wrap_roundtrip_and_binding(self):
        tok = wrap_lease(b"sess1", b"cursor-bytes")
        assert unwrap_lease(tok, b"sess1") == b"cursor-bytes"
        with pytest.raises(LeaseError):
            unwrap_lease(tok, b"sess2")
        corrupt = bytearray(tok)
        corrupt[5] = (corrupt[5] + 1) % 128
        with pytest.raises(LeaseError):
            unwrap_lease(bytes(corrupt), b"sess1")

    def test_lease_expiry(self):
        _, service, client, clk = make_service(
            config=ServiceConfig(lease_ttl=10.0))
        client.batch(S, [["add", el] for el in ELEMS])
        page = client.query(Scan(S, page_size=3))
        clk[0] += 11.0  # idle past the ttl
        with pytest.raises(LeaseError):
            client.query(Scan(S, page_size=3), cursor=page.cursor)
        # the lease table was swept, not just refused
        assert not service._leases

    def test_foreign_session_refused(self):
        _, service, client, _ = make_service()
        client.batch(S, [["add", el] for el in ELEMS])
        page = client.query(Scan(S, page_size=3))
        other = BigsetClient(service)
        assert other.session != client.session
        with pytest.raises(LeaseError):
            other.query(Scan(S, page_size=3), cursor=page.cursor)
        # the owner can still resume
        rest = client.query(Scan(S, page_size=100), cursor=page.cursor)
        assert page.members + rest.members == sorted(ELEMS)

    def test_close_session_releases_leases(self):
        _, service, client, _ = make_service(
            config=ServiceConfig(max_open_cursors=1))
        client.batch(S, [["add", el] for el in ELEMS])
        client.query(Scan(S, page_size=2))
        fresh = BigsetClient(service)
        with pytest.raises(Backpressure) as bp:
            fresh.query(Scan(S, page_size=2))
        assert bp.value.reason == "open_cursors"
        client.close()  # releases the outstanding page
        assert fresh.query(Scan(S, page_size=2)).members == ELEMS[:2]

    def test_plan_embedded_cursor_is_refused(self):
        """A raw executor cursor inside the wire plan would bypass lease
        binding, expiry, and admission accounting — the service must force
        all pagination through the lease token."""
        _, service, client, _ = make_service()
        client.batch(S, [["add", el] for el in ELEMS])
        page = client.query(Scan(S, page_size=3))
        raw_cursor = unwrap_lease(page.cursor, client.session)
        with pytest.raises(ServiceError) as err:
            client.query(Scan(S, page_size=3, cursor=raw_cursor))
        assert err.value.kind == "request"
        with pytest.raises(ServiceError):
            client.query(Range(S, cursor=raw_cursor))
        # the legitimate token path still works
        rest = client.query(Scan(S, page_size=100), cursor=page.cursor)
        assert page.members + rest.members == sorted(ELEMS)

    def test_identical_scans_hold_independent_leases(self):
        """Two byte-identical scans in one session must not share a lease:
        resuming (and thereby releasing) one must not strand the other."""
        _, service, client, _ = make_service()
        client.batch(S, [["add", el] for el in ELEMS])
        a = client.query(Scan(S, page_size=2))
        b = client.query(Scan(S, page_size=2))
        assert a.members == b.members and a.cursor != b.cursor
        a2 = client.query(Scan(S, page_size=2), cursor=a.cursor)
        b2 = client.query(Scan(S, page_size=2), cursor=b.cursor)
        assert a2.members == b2.members == sorted(ELEMS)[2:4]

    def test_session_ids_are_not_guessable(self):
        _, service, client, _ = make_service()
        other = BigsetClient(service)
        assert client.session != other.session
        assert len(client.session) >= 16  # a credential, not a counter

    def test_rejected_touch_renews_lease(self):
        """Backpressure must not starve a lease into expiry: every valid
        touch — including a rejected one — renews the deadline."""
        _, service, client, clk = make_service(
            config=ServiceConfig(byte_budget=1, budget_window=20.0,
                                 lease_ttl=10.0))
        client.batch(S, [["add", el] for el in ELEMS])
        page = client.query(Scan(S, page_size=2))      # t=0, spends budget
        clk[0] = 6.0
        with pytest.raises(Backpressure):              # renews to t=16
            client.query(Scan(S, page_size=2), cursor=page.cursor)
        clk[0] = 12.0  # past the original t=10 deadline, inside the renewal
        with pytest.raises(Backpressure):              # still leased; t=22 now
            client.query(Scan(S, page_size=2), cursor=page.cursor)
        clk[0] = 21.0  # window rolled at t=20; lease renewed at t=12 is alive
        rest = client.query(Scan(S, page_size=100), cursor=page.cursor)
        assert page.members + rest.members == sorted(ELEMS)


# -------------------------------------------------------------- backpressure
class TestBackpressure:
    def test_rejection_is_observable_on_the_wire(self):
        _, service, client, clk = make_service(
            config=ServiceConfig(byte_budget=1, budget_window=5.0))
        client.batch(S, [["add", el] for el in ELEMS])
        client.query(Scan(S, page_size=2))  # spends the window's budget
        raw = service.handle(msgpack.packb([WIRE_VERSION, "query", {
            "plan": plan_to_wire(Scan(S, page_size=2)),
            "session": client.session}]))
        version, status, body = msgpack.unpackb(raw)
        assert (version, status) == (WIRE_VERSION, STATUS_RETRY)
        assert body["reason"] == "byte_budget"
        assert 0 < body["retry_after"] <= 5.0
        assert service.rejections == 1

    def test_rejection_preserves_cursor_and_resume_is_exact(self):
        _, service, client, clk = make_service(
            config=ServiceConfig(byte_budget=1, budget_window=5.0,
                                 lease_ttl=1e9))
        client.batch(S, [["add", el] for el in ELEMS])
        one_shot = client.query(Scan(S, page_size=100)).members
        clk[0] += 5.0

        page = client.query(Scan(S, page_size=3))
        got = list(page.members)
        cursor = page.cursor
        rejections = 0
        while cursor is not None:
            try:
                page = client.query(Scan(S, page_size=3), cursor=cursor)
            except Backpressure as bp:
                rejections += 1
                clk[0] += bp.retry_after  # back off, then retry same token
                continue
            got.extend(page.members)
            cursor = page.cursor
        assert rejections > 0, "budget never engaged; test is vacuous"
        assert got == one_shot  # no re-emit, no skip across rejections

    def test_budget_window_refills(self):
        _, service, client, clk = make_service(
            config=ServiceConfig(byte_budget=1, budget_window=2.0))
        client.batch(S, [["add", el] for el in ELEMS])
        client.query(Count(S))
        with pytest.raises(Backpressure):
            client.query(Count(S))
        clk[0] += 2.0
        assert client.query(Count(S)).count == len(ELEMS)

    def test_mutations_bypass_read_budget(self):
        _, service, client, clk = make_service(
            config=ServiceConfig(byte_budget=1, budget_window=1e9))
        client.query(Count(S))
        with pytest.raises(Backpressure):
            client.query(Count(S))
        assert client.insert(S, b"still-writable")  # writes stay admitted


# --------------------------------------------------- pagination exactness
class TestServePagination:
    @given(ops_st, st.integers(1, 7))
    @settings(max_examples=20, deadline=None)
    def test_paged_scan_equals_one_shot_under_backpressure(self, ops, page):
        cluster, service, client, clk = make_service(
            config=ServiceConfig(byte_budget=600, budget_window=1.0,
                                 lease_ttl=1e9))
        apply_ops(cluster, ops)
        one_shot = cluster.query(Scan(S, page_size=10_000), r=3)

        def advance(seconds):
            clk[0] += seconds + 1e-3

        entries = []
        for pg in client.pages(Scan(S, page_size=page), r=3, sleep=advance):
            entries.extend(pg.entries)
        assert [e for e, _ in entries] == one_shot.members
        assert {e: frozenset(d) for e, d in entries} == {
            e: frozenset(d) for e, d in one_shot.entries}

    @given(ops_st, st.integers(1, 5))
    @settings(max_examples=10, deadline=None)
    def test_index_pagination_through_service(self, ops, page):
        cluster, service, client, clk = make_service()
        cluster.register_index(S, by_element_suffix(1))
        apply_ops(cluster, ops)
        one_shot = cluster.query(
            IndexRange(S, b"element_suffix:1"), r=2)
        got = []
        for pg in client.pages(IndexRange(S, b"element_suffix:1", limit=page),
                               r=2):
            assert pg.index_entries is not None
            got.extend(pg.index_entries)
        assert [(ik, el) for ik, el, _ in got] == [
            (ik, el) for ik, el, _ in one_shot.index_entries]


# ------------------------------------------------------------ write path
class TestWritePath:
    def test_insert_returns_minted_dot(self):
        cluster, _, client, _ = make_service()
        dot = client.insert(S, b"x")
        assert dot == ["vnode0", 1, 1]  # single dot rides as [actor, c, c]
        dot2 = client.insert(S, b"x")
        assert dot2 == ["vnode0", 2, 2]

    def test_membership_ctx_round_trips_into_remove(self):
        cluster, _, client, _ = make_service()
        client.batch(S, [["add", b"x"], ["add", b"y"]])
        present, ctx = client.membership(S, b"x", r=3)
        assert present and ctx
        assert client.remove(S, b"x", ctx=ctx)
        for actor in cluster.actors:  # gone on every replica
            assert cluster.vnodes[actor].value(S) == {b"y"}

    def test_stale_ctx_remove_loses_to_concurrent_readd(self):
        cluster, _, client, _ = make_service()
        client.insert(S, b"x")
        _, stale_ctx = client.membership(S, b"x")
        client.insert(S, b"x")  # concurrent re-add mints a fresh dot
        client.remove(S, b"x", ctx=stale_ctx)
        present, ctx = client.membership(S, b"x")
        assert present  # add-wins: only the observed dot was removed
        assert ctx == [["vnode0", 2, 2]]

    def test_legacy_per_dot_ctx_still_decodes(self):
        # pre-interval clients sent [[actor, counter], ...] — the service
        # must keep honouring that alongside the run-triple form
        cluster, _, client, _ = make_service()
        client.insert(S, b"x")
        assert client.remove(S, b"x", ctx=[["vnode0", 1]])
        for actor in cluster.actors:
            assert cluster.vnodes[actor].value(S) == set()

    def test_contiguous_ctx_coalesces_on_the_wire(self):
        # ten dots of one actor ship as a single run triple
        cluster, _, client, _ = make_service()
        for _ in range(10):
            client.insert(S, b"x")
        _, ctx = client.membership(S, b"x", r=3)
        assert ctx == [["vnode0", 1, 10]]
        assert client.remove(S, b"x", ctx=ctx)

    def test_batch_remove_observes_earlier_add(self):
        cluster, _, client, _ = make_service()
        results = client.batch(S, [
            ["add", b"keep"],
            ["add", b"tmp"],
            ["remove", b"tmp"],
            ["remove", b"never-there"],
        ])
        assert "dot" in results[0] and "dot" in results[1]
        assert results[2]["removed"] is True
        assert results[3]["removed"] is False
        assert cluster.value(S, r=3) == {b"keep"}

    def test_batch_removes_share_one_clock_write(self):
        tracer = Tracer()
        cluster = BigsetCluster(3, tracer=tracer)
        client = BigsetClient(BigsetService(cluster))
        dots = client.batch(S, [["add", el] for el in ELEMS])
        tracer.drain()
        results = client.batch(
            S, [["remove", el] for el in ELEMS[:6]]
            + [["remove", ELEMS[0]], ["remove", b"never-there"]])
        # one remove per op on the wire, each with its own context ...
        assert [r["removed"] for r in results] == [True] * 6 + [False, False]
        assert [r["ctx"] for r in results[:6]] == [[d["dot"]] for d in dots[:6]]
        # ... but the six probed removes reached the replicas as one write
        assert sum(s.name == "cluster.remove" for s in tracer.spans) == 1
        for actor in cluster.actors:
            assert cluster.vnodes[actor].value(S) == set(ELEMS[6:])

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_batch_matches_ops_one_at_a_time(self, seed):
        # The same mixed batch, sent whole and op by op, answers and ends
        # in the same state on every replica.
        import random
        rnd = random.Random(seed)
        ops = [[rnd.choice(["add", "remove", "remove"]), rnd.choice(ELEMS)]
               for _ in range(60)]
        prefix = [["add", el] for el in ELEMS]
        whole, _, whole_client, _ = make_service()
        single, _, single_client, _ = make_service()
        got = whole_client.batch(S, prefix + ops)
        want = [r for op in prefix + ops for r in single_client.batch(S, [op])]
        assert got == want
        for actor in whole.actors:
            a, b = whole.vnodes[actor], single.vnodes[actor]
            assert a.value(S) == b.value(S)
            assert a.read_clock(S) == b.read_clock(S)
            assert a.read_tombstone(S) == b.read_tombstone(S)

    def test_values_ride_inserts(self):
        cluster, _, client, _ = make_service()
        client.insert(S, b"doc", value=b"payload")
        vn = cluster.vnodes[cluster.actors[0]]
        assert [v for _, _, v in vn.fold_values(S)] == [b"payload"]


# ------------------------------------------------------------ wire errors
class TestWireErrors:
    def call(self, service, op, body):
        raw = service.handle(msgpack.packb([WIRE_VERSION, op, body]))
        return msgpack.unpackb(raw)

    def test_error_taxonomy(self):
        _, service, client, _ = make_service()
        v, status, body = self.call(service, "explode", {})
        assert status == STATUS_ERROR and body["error"] == "request"
        v, status, body = self.call(service, "query", {"plan": b"garbage"})
        assert status == STATUS_ERROR and body["error"] == "plan"
        v, status, body = self.call(service, "query", {
            "plan": plan_to_wire(Scan(S)), "session": b"who?"})
        assert status == STATUS_ERROR and body["error"] == "session"
        v, status, body = self.call(service, "query", {
            "plan": plan_to_wire(Scan(S)), "cursor": b"not-a-lease"})
        assert status == STATUS_ERROR and body["error"] == "lease"

    def test_bad_envelopes(self):
        _, service, _, _ = make_service()
        for raw in (b"\xff\xff", msgpack.packb("hi"),
                    msgpack.packb([2, "query", {}]),
                    msgpack.packb([1, 42, {}])):
            _, status, body = msgpack.unpackb(service.handle(raw))
            assert status == STATUS_ERROR and body["error"] == "request"

    def test_malformed_scalars_become_error_responses(self):
        """Out-of-range coordinators, bad quorums, non-bytes values: typed
        ``error`` responses, never exceptions escaping handle()."""
        _, service, _, _ = make_service(n=3)
        bad = [
            ("insert", {"set": S, "element": b"x", "coordinator": 7}),
            ("insert", {"set": S, "element": b"x", "coordinator": "zzz"}),
            ("insert", {"set": S, "element": b"x", "value": "not-bytes"}),
            ("insert", {"set": S, "element": b"x", "ctx": [["a"]]}),
            ("remove", {"set": S, "element": b"x", "coordinator": -1}),
            ("batch", {"set": S, "ops": [["add", "not-bytes"]]}),
            ("batch", {"set": S, "ops": [["add", b"x", 123]]}),
            ("query", {"plan": plan_to_wire(Scan(S)), "r": 99}),
            ("query", {"plan": plan_to_wire(Scan(S)), "r": "two"}),
        ]
        for op, body in bad:
            _, status, out = self.call(service, op, body)
            assert status == STATUS_ERROR and out["error"] == "request", (
                op, body, out)

    def test_cursor_on_non_paginating_plan(self):
        _, service, client, _ = make_service()
        client.batch(S, [["add", b"x"], ["add", b"y"]])
        page = client.query(Scan(S, page_size=1))
        assert page.cursor is not None
        with pytest.raises(PlanError):
            client.query(Membership(S, b"x"), cursor=page.cursor)

    def test_page_size_is_capped(self):
        _, service, client, _ = make_service(
            config=ServiceConfig(max_page_size=3))
        client.batch(S, [["add", el] for el in ELEMS])
        page = client.query(Scan(S, page_size=10_000))
        assert len(page.entries) == 3 and page.cursor is not None


# ---------------------------------------------------------- IO acceptance
class TestServeIo:
    def test_scan_page_io_is_o_page_not_o_n(self):
        """Acceptance: each page of a 100k-element Scan through the service
        reads O(page + causal metadata) bytes — per-page IoStats attached
        to every wire response, never O(n)."""
        n = 100_000
        page_size = 256
        cluster = BigsetCluster(1)
        vn = BigsetVnode(cluster.actors[0], LsmStore(memtable_limit=1 << 20))
        cluster.vnodes[cluster.actors[0]] = vn
        for i in range(n):
            vn.coordinate_insert(S, b"%08d" % i)
        vn.store.flush()

        meter = vn.store.meter()
        assert sum(1 for _ in vn.fold(S)) == n
        fold_bytes = meter.delta().bytes_read

        service = BigsetService(cluster)
        client = BigsetClient(service)
        seen = 0
        worst_page = 0
        for page in client.pages(Scan(S, page_size=page_size), r=1):
            assert len(page.entries) <= page_size
            seen += len(page.entries)
            worst_page = max(worst_page, page.stats["bytes_read"])
        assert seen == n
        # o(n): every page far under the full fold, and absolutely page-sized
        assert worst_page * 20 < fold_bytes, (worst_page, fold_bytes)
        assert worst_page < 64 * 1024, worst_page


class TestJoinStrategyOnTheWire:
    def test_per_page_stats_surface_planner_choice(self):
        """The planner's strategy rides the serve layer's per-page stats:
        a skewed intersect reports gallop, a forced zipper reports zipper,
        both return identical pages."""
        cluster = BigsetCluster(3)
        for i in range(400):
            cluster.add(T, b"%05d" % i, coordinator=i % 3)
        for i in range(0, 400, 40):
            cluster.add(S, b"%05d" % i, coordinator=i % 3)
        client = BigsetClient(BigsetService(cluster))
        expected = [b"%05d" % i for i in range(0, 400, 40)]

        auto = client.query(Join("intersect", S, T))
        assert auto.stats["strategy"] == "gallop"
        assert auto.members == expected
        forced = client.query(Join("intersect", S, T, strategy="zipper"))
        assert forced.stats["strategy"] == "zipper"
        assert forced.entries == auto.entries
        assert auto.stats["keys_scanned"] < forced.stats["keys_scanned"]
        # non-join shapes report no strategy
        assert client.query(Count(S)).stats["strategy"] == ""

    def test_lease_cursor_resumes_across_strategies(self):
        """A lease minted under one strategy resumes under another — the
        cursor names a position, not an algorithm."""
        cluster = BigsetCluster(3)
        for el in ELEMS:
            cluster.add(S, el, coordinator=0)
            cluster.add(T, el, coordinator=0)
        client = BigsetClient(BigsetService(cluster))
        first = client.query(Join("union", S, T, limit=4, strategy="zipper"))
        rest = client.query(Join("union", S, T, strategy="gallop"),
                            cursor=first.cursor)
        assert first.members + rest.members == sorted(ELEMS)
