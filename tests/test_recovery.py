"""Durability and crash recovery: WAL framing, group commit, crash injection.

Three layers of the prefix-durability invariant (invariant 11,
acknowledged ⇒ durable):

* **Codec** — CRC framing makes any truncation of the log decode to an
  exact record prefix; a torn tail is discarded, never replayed.
* **Store** — a crash at an arbitrary seeded kill point (WAL byte offset,
  mid-flush, mid-compaction) loses exactly the unacknowledged tail:
  ``recover()`` on fresh state restores every batch with
  ``seq <= commit_seq`` from durable media alone.
* **Cluster** — ``BigsetCluster.crash()/restart()``: WAL replay brings the
  acknowledged prefix back *before any network traffic*, and scheduled
  anti-entropy (``tick()``) heals the unacknowledged tail from peers,
  dot-bounded (post-heal ticks are skipped without folding a single key).

All strategies stay inside the ``repro.testing.hypothesis_fallback``
surface (integers / lists / tuples / binary / sampled_from / randoms), so
the suite runs identically on the CI leg without hypothesis installed.
"""
from __future__ import annotations

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cluster.clusters import BigsetCluster, VnodeDown
from repro.cluster.sim import Network
from repro.obs.trace import Tracer
from repro.query import plan as qp
from repro.storage import (CrashError, CrashPoint, DurableMedia, LsmStore,
                           WalError)
from repro.storage.wal import decode_wal, encode_wal_record

S = b"people"


def key(i: int) -> bytes:
    return b"k%04d" % i


def batches_to_wal(batches) -> bytes:
    return b"".join(
        encode_wal_record(seq, items)
        for seq, items in enumerate(batches, start=1))


# --------------------------------------------------------------------- codec
class TestWalCodec:
    @given(st.lists(
        st.lists(st.tuples(st.binary(max_size=12), st.binary(max_size=24)),
                 max_size=4),
        max_size=6))
    @settings(max_examples=40, deadline=None)
    def test_roundtrip(self, batches):
        records, torn = decode_wal(batches_to_wal(batches))
        assert torn == 0
        assert [list(r.items) for r in records] == batches
        assert [r.seq for r in records] == list(range(1, len(batches) + 1))
        assert sum(r.nbytes for r in records) == len(batches_to_wal(batches))

    @given(st.integers(min_value=0, max_value=600), st.randoms())
    @settings(max_examples=50, deadline=None)
    def test_any_truncation_decodes_to_a_record_prefix(self, cut, rng):
        batches = [
            [(bytes([rng.randrange(256)]) * rng.randrange(1, 8),
              bytes([rng.randrange(256)]) * rng.randrange(0, 12))
             for _ in range(rng.randrange(3))]
            for _ in range(rng.randrange(1, 8))
        ]
        wal = batches_to_wal(batches)
        full, _ = decode_wal(wal)
        cut = min(cut, len(wal))
        records, torn = decode_wal(wal[:cut])
        # exact prefix property: whole records below the cut, nothing else
        assert records == full[:len(records)]
        consumed = sum(r.nbytes for r in records)
        assert consumed <= cut and torn == cut - consumed
        if torn == 0 and cut == len(wal):
            assert len(records) == len(full)

    def test_corrupt_byte_stops_replay_at_the_frame(self):
        wal = batches_to_wal([[(b"a", b"1")], [(b"b", b"2")], [(b"c", b"3")]])
        first, _ = decode_wal(wal)
        # flip one byte inside the second record's body
        pos = first[0].nbytes + first[1].nbytes - 1
        bad = wal[:pos] + bytes([wal[pos] ^ 0xFF]) + wal[pos + 1:]
        records, torn = decode_wal(bad)
        assert [r.seq for r in records] == [1]
        assert torn == len(wal) - first[0].nbytes


# --------------------------------------------------------------------- store
def fresh_recover(media: DurableMedia, **kw) -> "tuple[LsmStore, object]":
    store = LsmStore(media=media, **kw)
    return store, store.recover()


class TestDurableStore:
    def test_group_commit_issues_fewer_fsyncs_than_batches(self):
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=8)
        for i in range(20):
            store.put(key(i), b"v")
        assert store.stats.num_fsyncs == 2        # 20 batches, depth 8
        assert store.commit_seq == 16             # acked = fsynced prefix
        store.sync()
        assert store.stats.num_fsyncs == 3 and store.commit_seq == 20
        assert media.wal_fsyncs == 3

    def test_volatile_store_has_no_wal_accounting(self):
        store = LsmStore()
        for i in range(50):
            store.put(key(i), b"v")
        assert store.commit_seq == 50             # trivially acked
        assert store.stats.bytes_wal == 0
        assert store.stats.num_fsyncs == 0

    @given(st.integers(min_value=1, max_value=10),
           st.integers(min_value=1, max_value=40))
    @settings(max_examples=30, deadline=None)
    def test_acked_prefix_survives_an_unsynced_crash(self, depth, n):
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=depth)
        for i in range(n):
            store.put(key(i), b"v%d" % i)
        acked = store.commit_seq
        assert n - acked < depth                  # tail bounded by the group
        media.crash()                             # drops the unsynced buffer
        recovered, res = fresh_recover(media, group_depth=depth)
        assert res.batches_replayed + res.batches_skipped == acked
        assert res.torn_bytes == 0
        for i in range(n):
            expected = b"v%d" % i if i < acked else None
            assert recovered.get(key(i)) == expected
        assert recovered.commit_seq == acked == recovered._seq

    @given(st.integers(min_value=0, max_value=4000))
    @settings(max_examples=30, deadline=None)
    def test_crash_at_arbitrary_wal_offset(self, offset):
        """Seeded kill point at any byte of the log: replay restores exactly
        the acknowledged batches, the torn record is discarded."""
        media = DurableMedia()
        media.schedule_crash(CrashPoint(wal_bytes=offset))
        store = LsmStore(media=media, group_depth=1)
        acked = 0
        crashed = False
        for i in range(40):
            try:
                store.put(key(i), b"v%d" % i)
                acked = store.commit_seq
            except CrashError:
                crashed = True
                break
        media.crash()
        recovered, res = fresh_recover(media)
        assert res.batches_replayed == acked
        if crashed:
            assert len(media.wal) <= offset       # truncated at the kill point
        for i in range(40):
            expected = b"v%d" % i if i < acked else None
            assert recovered.get(key(i)) == expected

    @pytest.mark.parametrize("k", [1, 2, 13])
    def test_crash_at_a_record_boundary_keeps_the_whole_record(self, k):
        """A kill point exactly at the end of record k lets that fsync
        finish: record k is acknowledged, the next fsync dies with nothing
        past the boundary, and replay restores exactly k batches."""
        probe = DurableMedia()
        store = LsmStore(media=probe, group_depth=1)
        ends = []
        for i in range(k + 1):
            store.put(key(i), b"v%d" % i)
            ends.append(len(probe.wal))
        media = DurableMedia()
        media.schedule_crash(CrashPoint(wal_bytes=ends[k - 1]))
        store = LsmStore(media=media, group_depth=1)
        for i in range(k):
            store.put(key(i), b"v%d" % i)
        assert store.commit_seq == k
        with pytest.raises(CrashError):
            store.put(key(k), b"v%d" % k)
        assert len(media.wal) == ends[k - 1]
        media.crash()
        recovered, res = fresh_recover(media)
        assert res.batches_replayed == k and res.torn_bytes == 0
        assert recovered.get(key(k - 1)) == b"v%d" % (k - 1)
        assert recovered.get(key(k)) is None

    def test_empty_wal_recovers_to_an_empty_store(self):
        store, res = fresh_recover(DurableMedia())
        assert res.batches_replayed == res.batches_skipped == 0
        assert res.segments == 0 and res.torn_bytes == 0
        assert len(store) == 0 and store.commit_seq == 0
        # the recovered store is fully writable
        store.put(b"a", b"1")
        assert store.get(b"a") == b"1"

    def test_torn_final_record_is_discarded(self):
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=100)
        for i in range(10):
            store.put(key(i), b"v%d" % i)
        # tear the fsync 5 bytes short of the full buffer
        media.schedule_crash(
            CrashPoint(wal_bytes=len(media.wal) + media.wal_pending() - 5))
        with pytest.raises(CrashError):
            store.sync()
        media.crash()
        recovered, res = fresh_recover(media)
        assert res.torn_bytes > 0
        assert res.batches_replayed == 9          # record 10 was torn
        assert recovered.get(key(8)) == b"v8"
        assert recovered.get(key(9)) is None

    def test_wal_records_below_horizon_replay_idempotently(self):
        """A durable flush captures WAL'd batches in a segment; the stale
        records still in the log are skipped on replay — and billed zero
        recovery bytes (byte-billed once, at the original append)."""
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=1, memtable_limit=6)
        for i in range(10):                       # flush fires at batch 6
            store.put(key(i), b"v%d" % i)
        media.crash()
        recovered, res = fresh_recover(media)
        assert res.segments == 1 and res.horizon == 6
        # records 1-5 still sit in the log below the horizon and are
        # skipped; record 6 was dropped from the unsynced buffer by the
        # flush that captured it; 7-10 replay
        assert res.batches_skipped == 5
        assert res.batches_replayed == 4
        replayed_bytes = res.bytes_replayed
        assert recovered.stats.bytes_recovered == replayed_bytes
        for i in range(10):
            assert recovered.get(key(i)) == b"v%d" % i
        # recovery is deterministic: a second fresh store sees the same
        again, res2 = fresh_recover(media)
        assert res2 == res
        assert dict(again.scan()) == dict(recovered.scan())

    def test_crash_before_flush_segment_publishes(self):
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=100)
        for i in range(4):
            store.put(key(i), b"v%d" % i)
        store.sync()                              # acked: 4
        for i in range(4, 8):
            store.put(key(i), b"v%d" % i)         # unsynced tail
        media.schedule_crash(CrashPoint(file_writes=1))
        with pytest.raises(CrashError):
            store.flush()                         # dies writing the segment
        media.crash()
        recovered, res = fresh_recover(media)
        assert res.segments == 0                  # old (empty) manifest wins
        assert res.batches_replayed == 4          # exactly the acked prefix
        assert recovered.get(key(3)) == b"v3"
        assert recovered.get(key(4)) is None

    def test_crash_between_segment_and_manifest(self):
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=100)
        for i in range(4):
            store.put(key(i), b"v%d" % i)
        store.sync()
        media.schedule_crash(CrashPoint(file_writes=2))
        with pytest.raises(CrashError):
            store.flush()                         # segment lands, manifest dies
        media.crash()
        recovered, res = fresh_recover(media)
        # the orphan segment is invisible without its manifest: durable
        # state is still old-manifest + WAL, i.e. the acknowledged prefix
        assert res.segments == 0
        assert res.batches_replayed == 4
        assert dict(recovered.scan()) == {key(i): b"v%d" % i for i in range(4)}

    def test_mid_compaction_crash_preserves_precompaction_state(self):
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=1)
        for i in range(10):
            store.put(key(i), b"v%d" % i)
        store.flush()                             # seg + manifest: 2 publishes
        for i in range(10, 15):
            store.put(key(i), b"v%d" % i)
        before = dict(store.scan())
        # compact() = inner flush (2 publishes) then the merged segment (3rd)
        media.schedule_crash(CrashPoint(file_writes=3))
        with pytest.raises(CrashError):
            store.compact()
        media.crash()
        recovered, res = fresh_recover(media)
        assert dict(recovered.scan()) == before
        assert res.segments == 2                  # pre-merge manifest rules

    def test_crash_on_wal_reset_after_compaction_manifest(self):
        """The compaction manifest landed but the WAL reset did not: every
        surviving WAL record sits at or below the new horizon and must be
        skipped (replaying would resurrect filter-discarded keys)."""
        media = DurableMedia()
        store = LsmStore(media=media, group_depth=1)
        for i in range(8):
            store.put(key(i), b"v%d" % i)
        before = dict(store.scan())
        # inner flush (2 publishes) + merged segment (3) + manifest (4),
        # then the WAL reset is the 5th
        media.schedule_crash(CrashPoint(file_writes=5))
        with pytest.raises(CrashError):
            store.compact()
        media.crash()
        recovered, res = fresh_recover(media)
        assert res.segments == 1                  # the merged run
        assert res.batches_replayed == 0
        assert res.batches_skipped == 8 and res.bytes_replayed == 0
        assert dict(recovered.scan()) == before

    def test_recover_guards(self):
        with pytest.raises(WalError):
            LsmStore().recover()                  # no durable media
        media = DurableMedia()
        store = LsmStore(media=media)
        store.put(b"a", b"1")
        with pytest.raises(WalError):
            store.recover()                       # not a fresh store

    def test_legacy_clock_payloads_roundtrip_through_recovery(self):
        """``KIND_CLOCK`` records written by the pre-interval per-dot codec
        replay through the WAL, decode, serve reads, and re-encode in the
        run-length form on the next write."""
        import msgpack

        from repro.core.bigset import (BigsetVnode, clock_key, element_key,
                                       tombstone_key)
        from repro.core.clock import Clock
        from repro.core.dots import Dot

        # Pre-refactor replica state: set-clock base {a: 2} + cloud {4, 5}
        # (gap at 3), tombstone cloud {4} — element y@(a,4) was removed.
        legacy_clock = msgpack.packb({"b": [["a", 2]], "c": [["a", [4, 5]]]})
        legacy_ts = msgpack.packb({"b": [], "c": [["a", [4]]]})
        media = DurableMedia()
        old = LsmStore(media=media)
        old.put(clock_key(S), legacy_clock)
        old.put(tombstone_key(S), legacy_ts)
        old.put(element_key(S, b"x", Dot("a", 2)), b"")
        old.put(element_key(S, b"z", Dot("a", 5)), b"")
        old.sync()
        media.crash()

        store, res = fresh_recover(media)
        assert res.batches_replayed == 4 and res.torn_bytes == 0
        vn = BigsetVnode("b", store)
        assert vn.value(S) == {b"x", b"z"}
        clk = Clock.from_obj(msgpack.unpackb(store.get(clock_key(S)),
                                             strict_map_key=False))
        assert clk.seen(Dot("a", 5)) and not clk.seen(Dot("a", 3))

        # a write through the recovered vnode upgrades the record in place
        vn.coordinate_insert(S, b"w")
        upgraded = msgpack.unpackb(store.get(clock_key(S)),
                                   strict_map_key=False)
        assert "r" in upgraded and "c" not in upgraded
        store.sync()
        media.crash()
        store2, _ = fresh_recover(media)
        assert BigsetVnode("b", store2).value(S) == {b"w", b"x", b"z"}


# ------------------------------------------------------------------- cluster
def run_writes(clusters, lo, hi, coordinators=(0, 1, 2)):
    for i in range(lo, hi):
        c = coordinators[i % len(coordinators)]
        for cluster in clusters:
            cluster.add(S, key(i), coordinator=c, value=b"v%d" % i)


def heal(big: BigsetCluster, ctrl: BigsetCluster, ticks: int = 80) -> int:
    """Tick until every replica matches the control cluster; returns ticks."""
    for t in range(ticks):
        if all(big.vnodes[a].value(S) == ctrl.vnodes[a].value(S)
               for a in big.actors):
            return t
        big.tick()
        big.settle()
    raise AssertionError("anti-entropy did not heal within budget")


class TestClusterCrashRecovery:
    def test_kill_mid_batch_restart_heal_matches_no_crash_run(self):
        """The acceptance path: a seeded kill point tears vnode0's WAL
        mid-batch; restart replays the acknowledged prefix from durable
        media alone, one tick heals the tail, and the healed stores are
        byte-identical to a control cluster that never crashed."""
        big = BigsetCluster(3, durable=True, group_depth=4)
        ctrl = BigsetCluster(3, durable=True, group_depth=4)
        run_writes([big, ctrl], 0, 30)
        media = big.media["vnode0"]
        # arm the kill point 3 bytes short of the next fsync's end: the
        # fsync that crosses it tears the durable log mid-record
        media.schedule_crash(
            CrashPoint(wal_bytes=len(media.wal) + media.wal_pending() + 40))
        crashed_at = None
        for i in range(30, 40):
            try:
                big.add(S, key(i), coordinator=0, value=b"v%d" % i)
            except CrashError:
                crashed_at = i
                break
        assert crashed_at is not None
        big.crash(0)
        # the op that died mid-commit was never replicated: drop it from
        # the control run too, then keep writing through live coordinators
        run_writes([ctrl], 30, crashed_at)
        run_writes([big, ctrl], crashed_at + 1, 40, coordinators=(1, 2))
        ctrl.add(S, key(crashed_at), coordinator=1,
                 value=b"v%d" % crashed_at)
        big.add(S, key(crashed_at), coordinator=1, value=b"v%d" % crashed_at)

        rec = big.restart(0)
        assert rec.batches_replayed > 0           # WAL replay did the bulk
        before = big.ae_stats().keys_scanned
        ticks = heal(big, ctrl)
        # dot-bounded heal: the sync shipped the missing tail, and once
        # converged further ticks skip at O(causal metadata) — zero folds
        stats = big.ae_stats()
        assert stats.keys_shipped >= 1
        scanned_after_heal = stats.keys_scanned
        skipped_before = stats.rounds_skipped
        big.tick()
        assert big.ae_stats().keys_scanned == scanned_after_heal
        assert big.ae_stats().rounds_skipped > skipped_before
        # byte-identical stores: same live keys, same values, every replica
        for a in big.actors:
            assert (dict(big.vnodes[a].store.scan())
                    == dict(ctrl.vnodes[a].store.scan()))

    @given(st.integers(min_value=50, max_value=8000))
    @settings(max_examples=12, deadline=None)
    def test_every_acked_write_survives_restart_before_any_sync(self, offset):
        """WAL replay alone (no anti-entropy) restores every add() that
        returned: group_depth=1 acknowledges each batch at its own fsync,
        so only the op killed mid-commit may be missing."""
        big = BigsetCluster(3, durable=True, group_depth=1)
        media = big.media["vnode0"]
        media.schedule_crash(CrashPoint(wal_bytes=offset))
        acked = []
        for i in range(60):
            try:
                big.add(S, key(i), coordinator=i % 3, value=b"v%d" % i)
                acked.append(i)
            except CrashError:
                break
        big.crash(0)
        big.restart(0)
        vn = big.vnodes["vnode0"]
        present = vn.value(S)
        for i in acked:
            assert key(i) in present, f"acknowledged write {i} lost"

    @given(st.integers(min_value=0, max_value=2 ** 32 - 1))
    @settings(max_examples=10, deadline=None)
    def test_recovery_plus_digest_sync_converges_on_lossy_networks(self, seed):
        net = Network(seed=seed, drop_prob=0.25, dup_prob=0.25, reorder=True)
        big = BigsetCluster(3, net=net, sync=False, durable=True,
                            group_depth=4)
        run_writes([big], 0, 24)
        big.settle()
        big.crash(0)
        run_writes([big], 24, 32, coordinators=(1, 2))
        big.settle()
        big.restart(0)
        for _ in range(20):
            big.tick(budget=3)
            big.settle()
        vns = [big.vnodes[a] for a in big.actors]
        assert vns[0].value(S) == vns[1].value(S) == vns[2].value(S)
        # every write acknowledged by a *live* coordinator survived
        assert vns[0].value(S) == {key(i) for i in range(32)}

    def test_restart_under_traffic_with_nonquorum_crash(self):
        """A non-quorum replica crash leaves the write and query paths
        fully available; tick()-driven sync catches the replica up after
        restart (the ROADMAP's 'node restarts under traffic' scenario)."""
        big = BigsetCluster(3, durable=True, group_depth=4)
        ctrl = BigsetCluster(3, durable=True, group_depth=4)
        run_writes([big, ctrl], 0, 12)
        big.crash(2)                              # vnode2: outside the quorum
        crashed_rounds_before = big.ae_stats().rounds_crashed
        for i in range(12, 24):
            for cluster in (big, ctrl):
                cluster.add(S, key(i), coordinator=i % 2, value=b"v%d" % i)
            if i % 4 == 0:
                big.tick()                        # AE keeps running mid-crash
                res = big.query(qp.Scan(S, page_size=50))
                assert len(res.entries) == i + 1
        # rounds touching the dead member were counted, not attempted
        assert big.ae_stats().rounds_crashed > crashed_rounds_before
        with pytest.raises(VnodeDown):
            big.add(S, b"down", coordinator=2)
        rec = big.restart(2)
        assert rec.batches_replayed > 0
        heal(big, ctrl)
        for a in big.actors:
            assert big.vnodes[a].value(S) == ctrl.vnodes[a].value(S)

    def test_crashed_replica_drops_queued_traffic(self):
        big = BigsetCluster(3, sync=False, durable=True, group_depth=1)
        big.add(S, b"x")                          # replication still queued
        dropped_before = big.net.msgs_dropped
        big.crash(1)
        big.settle()                              # vnode1's copy evaporates
        assert big.net.msgs_dropped > dropped_before
        big.restart(1)
        assert big.vnodes["vnode1"].value(S) == set()
        big.tick()
        big.settle()
        assert big.vnodes["vnode1"].value(S) == {b"x"}

    def test_recovery_span_reports_replay(self):
        tracer = Tracer()
        big = BigsetCluster(3, durable=True, group_depth=2, tracer=tracer)
        run_writes([big], 0, 10)
        big.crash(0)
        rec = big.restart(0)
        spans = [s for s in tracer.spans if s.name == "storage.recover"]
        assert len(spans) == 1
        attrs = spans[0].attrs
        assert attrs["actor"] == "vnode0"
        assert attrs["batches_replayed"] == rec.batches_replayed
        assert attrs["torn_bytes"] == rec.torn_bytes

    def test_fault_api_guards(self):
        volatile = BigsetCluster(3)
        with pytest.raises(RuntimeError):
            volatile.crash(0)
        big = BigsetCluster(3, durable=True)
        with pytest.raises(RuntimeError):
            big.restart(0)                        # not crashed
        big.crash(0)
        big.crash(0)                              # idempotent
        with pytest.raises(VnodeDown):
            big.query(qp.Scan(S, page_size=10), r=3)  # quorum unreachable
        big.restart(0)
        assert "vnode0" in big.vnodes

    def test_restarted_vnode_reregisters_indexes(self):
        from repro.index.spec import by_value_prefix

        big = BigsetCluster(3, durable=True, group_depth=1)
        spec = by_value_prefix(1)
        big.register_index(S, spec)
        run_writes([big], 0, 8)
        big.crash(0)
        big.restart(0)
        # the recovered replica serves index queries: postings were durable
        # with their element-keys, and the spec re-registered on restart
        res = big.query(qp.IndexLookup(S, spec.name, b"v"), r=3)
        assert len(res.entries) == 8
        big.add(S, b"zz", coordinator=0, value=b"v99")
        res = big.query(qp.IndexLookup(S, spec.name, b"v"), r=3)
        assert len(res.entries) == 9
