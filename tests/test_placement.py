"""Partitioned placement: ring properties, routed clusters, handoff.

The acceptance surface of the placement refactor:

* the ring is deterministic, minimally-moving rendezvous placement;
* a partitioned cluster's ``query()`` results are identical to the
  unpartitioned cluster's under drop/dup/reorder (same elements, same
  values, same page boundaries — dots differ only in which owner minted
  them);
* a ring-epoch bump converges via digest handoff shipping only the moved
  partitions' data + causal metadata, with zero element folds for
  unmoved partitions;
* crash/restart during handoff loses no acknowledged writes;
* storage actually partitions: each vnode stores ~factor/n of the set.
"""
from hypothesis import given, settings, strategies as st

from repro.cluster.clusters import BigsetCluster, Ring, VnodeDown
from repro.cluster.placement import (DEFAULT_PARTITIONS, partition_set,
                                     plan_coverage, split_partition_set)
from repro.cluster.sim import Network
from repro.query.plan import Count, IndexLookup, Membership, Range, Scan
from repro.query.planner import side_stats

S = b"users"
ACTORS8 = [f"v{i}" for i in range(8)]


def elems(n, prefix=b"el"):
    return [prefix + b"%05d" % i for i in range(n)]


# --------------------------------------------------------------- ring units
class TestRing:
    def test_placement_is_deterministic(self):
        r1 = Ring.build(ACTORS8, factor=3, seed=7)
        r2 = Ring.build(list(ACTORS8), factor=3, seed=7)
        assert r1 == r2
        assert all(r1.owners(p) == r2.owners(p) for p in r1.partitions())
        assert r1.partition(S, b"x") == r2.partition(S, b"x")

    def test_seed_changes_placement(self):
        a = Ring.build(ACTORS8, factor=3, seed=0)
        b = Ring.build(ACTORS8, factor=3, seed=1)
        assert any(a.owners(p) != b.owners(p) for p in a.partitions())

    def test_owners_and_fallbacks_partition_the_actors(self):
        ring = Ring.build(ACTORS8, factor=3)
        for pid in ring.partitions():
            owners, rest = ring.owners(pid), ring.fallbacks(pid)
            assert len(owners) == 3
            assert not set(owners) & set(rest)
            assert set(owners) | set(rest) == set(ACTORS8)

    def test_minimal_movement_on_join(self):
        """Rendezvous: adding a vnode moves only the partitions where the
        newcomer out-scores an incumbent — about factor/(n+1) of them —
        and every move gains exactly the newcomer."""
        old = Ring.build(ACTORS8, factor=3)
        new = old.with_actors(ACTORS8 + ["v8"])
        delta = old.delta_to(new)
        assert delta.old_epoch == 0 and delta.new_epoch == 1
        assert 0 < len(delta.moves) < DEFAULT_PARTITIONS
        for move in delta.moves:
            assert move.joined == ("v8",)
            assert len(move.left) == 1
            assert set(move.survivors()) == set(move.old_owners) - set(
                move.left)
        # expected ~ 64 * 3/9 ≈ 21 moved partitions; allow generous slack
        assert len(delta.moves) <= DEFAULT_PARTITIONS // 2

    def test_unmoved_partitions_keep_owner_order(self):
        old = Ring.build(ACTORS8, factor=3)
        new = old.with_actors(ACTORS8 + ["v8"])
        moved = set(old.delta_to(new).moved_pids())
        for pid in old.partitions():
            if pid not in moved:
                assert old.owners(pid) == new.owners(pid)

    def test_full_ring_is_degenerate(self):
        ring = Ring.full(["a", "b", "c"])
        assert ring.full_replication and ring.n_partitions == 1
        assert ring.partition(S, b"anything") == 0
        assert ring.owners(0) == ("a", "b", "c")  # ORDER preserved
        assert ring.storage_set(S, 0) == S        # passthrough
        assert ring.write_quorum() == 2

    def test_pset_codec_round_trips(self):
        pset = partition_set(S, 37)
        assert split_partition_set(pset) == (S, 37)
        assert split_partition_set(S) == (S, None)
        # partition sets sort outside the application's own namespace
        assert pset.startswith(S + b"\x00")

    def test_coverage_minimises_vnode_footprint(self):
        ring = Ring.build(ACTORS8, factor=3)
        cover = plan_coverage(ring, S, ACTORS8, r=2)
        assert len(cover.assignments) == DEFAULT_PARTITIONS
        assert all(len(actors) == 2 for _p, _s, actors in cover.assignments)
        # every assignment draws from the partition's owners
        for pid, pset, actors in cover.assignments:
            assert set(actors) <= set(ring.owners(pid))
            assert pset == ring.storage_set(S, pid)

    def test_coverage_raises_vnode_down_with_payload(self):
        ring = Ring.build(ACTORS8, factor=3)
        # find a partition and kill enough of its owners to break quorum
        victims = ring.owners(0)[:2]
        live = [a for a in ACTORS8 if a not in victims]
        try:
            plan_coverage(ring, S, live, r=2, pids=[0])
        except VnodeDown as e:
            assert e.vnode in victims
            assert e.set_name == S
        else:
            raise AssertionError("expected VnodeDown")

    def test_coverage_rejects_r_above_factor(self):
        ring = Ring.build(ACTORS8, factor=3)
        try:
            plan_coverage(ring, S, ACTORS8, r=4, pids=[0])
        except ValueError as e:
            assert "replication factor" in str(e)
        else:
            raise AssertionError("expected ValueError")


# ------------------------------------------- partitioned == unpartitioned
def apply_ops(cluster, ops):
    for kind, i, coord in ops:
        el = b"el%02d" % i
        if kind == "add":
            cluster.add(S, el, coordinator=coord % cluster.n,
                        value=b"v" + el)
        else:
            cluster.remove(S, el, coordinator=coord % cluster.n)


ops_st = st.lists(
    st.tuples(st.sampled_from(["add", "remove"]), st.integers(0, 24),
              st.integers(0, 7)),
    min_size=1, max_size=40)


class TestPartitionedEquivalence:
    @given(ops_st, st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_results_match_unpartitioned_under_faults(self, ops, seed):
        """Same ops through a faulty network on both topologies; after
        convergence every query shape answers identically."""
        full = BigsetCluster(
            3, net=Network(seed=seed, dup_prob=0.2, reorder=True))
        part = BigsetCluster(
            ring=Ring.build(ACTORS8, factor=3),
            net=Network(seed=seed, dup_prob=0.2, reorder=True))
        apply_ops(full, ops)
        apply_ops(part, ops)
        full.settle()
        part.settle()
        fr = full.query(Scan(S, page_size=100), repair=False)
        pr = part.query(Scan(S, page_size=100), repair=False)
        assert pr.members == fr.members
        assert pr.count == fr.count
        assert (part.query(Count(S), repair=False).count
                == full.query(Count(S), repair=False).count)
        for i in (0, 7, 19):
            el = b"el%02d" % i
            assert (part.query(Membership(S, el), repair=False).present
                    == full.query(Membership(S, el), repair=False).present)

    @staticmethod
    def apply_ops_ctx(cluster, ops):
        """Ops with *client-provided* remove contexts (§4.3.2): the ctx is
        the dots of the element's own prior adds, so the outcome is pure
        set algebra — identical on any topology under any delivery."""
        ctxs = {}
        for kind, i, coord in ops:
            el = b"el%02d" % i
            if kind == "add":
                d = cluster.add(S, el, coordinator=coord % cluster.n,
                                value=b"v" + el)
                ctxs.setdefault(el, []).append(d.dot)
            else:
                ctx = ctxs.pop(el, None)
                if ctx:
                    cluster.remove(S, el, coordinator=coord % cluster.n,
                                   ctx=ctx)

    @given(ops_st, st.integers(0, 2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_dropped_deltas_heal_via_quorum_and_ticks(self, ops, seed):
        """Drops leave replicas divergent; quorum reads stay correct and
        anti-entropy ticks converge the partitioned cluster to the same
        answer as a fault-free unpartitioned one."""
        oracle = BigsetCluster(3)
        part = BigsetCluster(
            ring=Ring.build(ACTORS8, factor=3),
            net=Network(seed=seed, drop_prob=0.3, reorder=True), sync=False)
        self.apply_ops_ctx(oracle, ops)
        self.apply_ops_ctx(part, ops)
        part.settle()
        for _ in range(40):
            part.tick()
            part.settle()  # an async cluster only queues the tick's pulls
        truth = oracle.query(Range(S), repair=False)
        got = part.query(Range(S), repair=False)
        assert got.members == truth.members

    def test_pagination_boundaries_identical(self):
        full = BigsetCluster(3)
        part = BigsetCluster(ring=Ring.build(ACTORS8, factor=3))
        for el in elems(30):
            full.add(S, el)
            part.add(S, el)
        cur_f = cur_p = None
        for _ in range(10):
            pf = full.query(Scan(S, page_size=7, cursor=cur_f))
            pp = part.query(Scan(S, page_size=7, cursor=cur_p))
            assert pp.members == pf.members
            assert (pp.cursor is None) == (pf.cursor is None)
            cur_f, cur_p = pf.cursor, pp.cursor
            if cur_f is None:
                break
        assert cur_f is None

    def test_coverage_surfaced_in_stats(self):
        part = BigsetCluster(ring=Ring.build(ACTORS8, factor=3))
        part.add(S, b"x")
        res = part.query(Membership(S, b"x"))
        assert res.stats.coverage == "epoch=0;partitions=1;vnodes=2;r=2"
        res = part.query(Range(S))
        assert res.stats.coverage == (
            f"epoch=0;partitions={DEFAULT_PARTITIONS};vnodes=7;r=2")

    def test_index_queries_fan_in_across_partitions(self):
        from repro.index.spec import by_value_prefix

        full = BigsetCluster(3)
        part = BigsetCluster(ring=Ring.build(ACTORS8, factor=3))
        spec = by_value_prefix(2, name=b"pfx")
        for c in (full, part):
            c.register_index(S, spec)
            for i, el in enumerate(elems(20)):
                c.add(S, el, value=b"%02d-payload" % (i % 4))
        res_f = full.query(IndexLookup(S, b"pfx", b"01"))
        res_p = part.query(IndexLookup(S, b"pfx", b"01"))
        assert ([(ik, el) for ik, el, _ in res_p.index_entries]
                == [(ik, el) for ik, el, _ in res_f.index_entries])


# ------------------------------------------------------------ ring change
class TestHandoff:
    def _loaded_cluster(self, n_elems=120, **kw):
        c = BigsetCluster(ring=Ring.build(ACTORS8, factor=3), **kw)
        for el in elems(n_elems):
            c.add(S, el, value=b"v:" + el)
        return c

    def drain(self, c, ticks=30):
        for _ in range(ticks):
            c.tick(budget=0)
            if not (c.ring_state()["handoffs_pending"]
                    or c.ring_state()["retires_pending"]):
                break

    def test_epoch_bump_ships_only_moved_partitions(self):
        c = self._loaded_cluster()
        before = c.query(Scan(S, page_size=500)).members
        shipped0 = c.ae_stats().keys_shipped
        scanned0 = c.ae_stats().keys_scanned
        delta = c.add_vnode("v8")
        moved = set(delta.moved_pids())
        # every scheduled task concerns a moved partition — nothing else
        assert {t.pid for t in c._handoffs} <= moved
        assert {t.pid for t in c._retires} <= moved
        self.drain(c)
        assert c.ring_state()["handoffs_pending"] == 0
        assert c.ring_state()["retires_pending"] == 0
        # wire cost: exactly the surviving keys of moved partitions were
        # shipped (each to the one gaining owner), zero for unmoved ones
        old = Ring.build(ACTORS8, factor=3)
        moved_keys = sum(
            1 for el in elems(120) if old.partition(S, el) in moved)
        assert c.ae_stats().keys_shipped - shipped0 == moved_keys
        # donor folds touched only moved partitions: the scan ledger grew
        # by O(moved keys), not O(total keys)
        assert c.ae_stats().keys_scanned - scanned0 <= 2 * moved_keys + len(
            moved)
        # results identical across the epoch bump
        assert c.query(Scan(S, page_size=500)).members == before

    def test_leaver_copy_retired_only_after_domination(self):
        c = self._loaded_cluster()
        delta = c.add_vnode("v8")
        move = next(m for m in delta.moves
                    if any(c.ring.partition(S, el) == m.pid
                           for el in elems(120)))
        pset = c.ring.storage_set(S, move.pid)
        leaver = move.left[0]
        assert side_stats(c.vnodes[leaver].store, pset).keys > 0
        self.drain(c)
        # handoff done: the new owner dominates, the leaver's copy is gone
        assert side_stats(c.vnodes[leaver].store, pset).keys == 0
        assert side_stats(c.vnodes["v8"].store, pset).keys > 0
        assert c.ae_stats().handoff_retired == len(c._retires)

    def test_epoch_retires_and_cursors_fall_forward(self):
        c = self._loaded_cluster(n_elems=40)
        page1 = c.query(Scan(S, page_size=15), ring_epoch=0)
        c.add_vnode("v8")
        self.drain(c)
        assert c.ring_state()["serveable_epochs"] == [1]
        # the pinned epoch 0 is retired: the cursor re-plans under epoch 1
        # and resumes from the same element boundary
        page2 = c.query(Scan(S, page_size=100, cursor=page1.cursor),
                        ring_epoch=0)
        assert "epoch=1" in page2.stats.coverage
        assert page1.members + page2.members == elems(40)

    def test_crash_restart_during_handoff_loses_nothing(self):
        c = self._loaded_cluster(durable=True)
        c.sync_all()  # acknowledgement barrier: all 120 writes durable
        c.add_vnode("v8")
        c.tick(budget=0)   # partial handoff under way
        c.crash("v8")      # the joiner dies mid-pull
        for _ in range(3):
            c.tick(budget=0)   # tasks skip the crashed joiner
        c.restart("v8")
        self.drain(c)
        assert c.ring_state()["handoffs_pending"] == 0
        assert c.query(Scan(S, page_size=500)).members == elems(120)

    def test_donor_crash_during_handoff_loses_nothing(self):
        c = self._loaded_cluster(durable=True)
        c.sync_all()
        delta = c.add_vnode("v8")
        donors = {t.src for t in c._handoffs}
        victim = sorted(donors)[0]
        c.crash(victim)
        for _ in range(5):
            c.tick(budget=0)   # pulls from the crashed donor are skipped
        c.restart(victim)
        self.drain(c, ticks=40)
        assert c.ring_state()["handoffs_pending"] == 0
        assert c.ring_state()["retires_pending"] == 0
        assert c.query(Scan(S, page_size=500)).members == elems(120)
        assert delta.new_epoch == c.ring.epoch

    def test_writes_during_handoff_survive(self):
        """Writes landing while partitions move are never lost: they go to
        the NEW ring's owners, and handoff completion is clock descent —
        the donor's whole history, not a snapshot."""
        c = self._loaded_cluster()
        c.add_vnode("v8")
        c.tick(budget=0)
        late = [b"late%02d" % i for i in range(20)]
        for el in late:
            c.add(S, el)
        self.drain(c)
        got = c.query(Scan(S, page_size=500)).members
        assert got == sorted(elems(120) + late)


# ------------------------------------------------------- sloppy placement
class TestHintedHandoff:
    def test_write_routes_around_crashed_owner(self):
        c = BigsetCluster(ring=Ring.build(ACTORS8, factor=3), durable=True)
        c.add(S, b"seed")
        pref = c.ring.preference_list(S, b"target")
        victim = pref.owners[0]
        c.crash(victim)
        # coordinate from a live vnode: hinted handoff routes *replicas*
        # around the crashed owner, a dead coordinator still refuses
        alive = next(i for i, a in enumerate(c.actors) if a != victim)
        c.add(S, b"target", value=b"val", coordinator=alive)
        assert c.ae_stats().hints_recorded == 1
        # quorum reads stay available around the crash
        assert c.query(Membership(S, b"target")).present
        c.restart(victim)
        for _ in range(6):
            c.tick(budget=0)
        assert c.ae_stats().hints_resolved == 1
        assert c.ring_state()["hints_pending"] == 0
        # the returned owner holds the element locally now
        pset = c.ring.storage_set(S, pref.pid)
        assert c.vnodes[victim].is_member(pset, b"target")[0]
        # and the fallback's parked copy was retired after domination
        fallback = next(a for a in pref.fallbacks
                        if side_stats(c.vnodes[a].store, pset).keys == 0)
        assert fallback is not None

    def test_vnode_down_when_no_owner_or_fallback(self):
        actors = ["a", "b", "c"]
        c = BigsetCluster(ring=Ring.build(actors, factor=3), durable=True)
        c.add(S, b"x", coordinator=1)
        for v in actors[1:]:
            c.crash(v)
        # entry vnode "a" is alive but partitions whose owners are all
        # crashed (factor==n: no fallbacks) must refuse the write loudly
        try:
            for i in range(50):
                c.add(S, b"probe%02d" % i, coordinator=0)
        except VnodeDown as e:
            assert e.vnode in actors
            assert e.set_name == S
        else:
            raise AssertionError("expected VnodeDown")

    def test_crashed_coordinator_raises_with_payload(self):
        c = BigsetCluster(ring=Ring.build(ACTORS8, factor=3), durable=True)
        c.add(S, b"x")
        c.crash(0)
        try:
            c.add(S, b"y", coordinator=0)
        except VnodeDown as e:
            assert e.vnode == "v0"
            assert e.set_name == S
        else:
            raise AssertionError("expected VnodeDown")


# ----------------------------------------------------------- storage bound
class TestStoragePartitioning:
    def test_per_vnode_storage_is_fractional(self):
        """8 vnodes / factor 3: each vnode stores ~3/8 of the elements
        (the full-replication baseline stores all of them everywhere)."""
        n = 400
        c = BigsetCluster(ring=Ring.build(ACTORS8, factor=3))
        for el in elems(n):
            c.add(S, el, value=b"payload:" + el)
        per_vnode = []
        for a in c.actors:
            keys = sum(
                side_stats(c.vnodes[a].store, c.ring.storage_set(S, pid)).keys
                for pid in c.ring.partitions())
            per_vnode.append(keys)
        assert sum(per_vnode) == 3 * n  # factor copies in total, no more
        # balanced-ish: nobody stores more than ~60% above the 3/8 mean
        assert max(per_vnode) <= 1.6 * (3 * n / 8)
