"""Unit tests of the benchmark's yardstick: lookup by name, the traffic
generator, the reference comparison, the roofline bytes and the trace
reduction.  They run on the CPU and never touch a chip."""
import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

from bench import harness, reference, roofline, traffic, tracing

ROOT = harness.CHECKOUT


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.fixture
def grown_root(tmp_path):
    """A checkout copy to which a new mix, cell and metric are *added*."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((open(os.path.join(ROOT, "BENCHMARK.json"))).read())
    spec["workloads"].append({
        "name": "full3.probe_only", "config": "follower_100k_full3",
        "traffic": "probe_only", "chips": 1, "why": "test"})
    spec["end_to_end"].append({
        "name": "probe_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock",
        "workloads": ["full3.probe_only"]})
    spec["per_layer"].append({
        "name": "probes_seen", "unit": "probes", "better": "higher",
        "source": "program_counter", "layer": "serve",
        "moves": "probe_p95_ms", "workloads": ["full3.probe_only"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "traffic" / "probe_only.json").write_text(
        json.dumps({"ops": {"probe": 1.0}, "zipf_theta": 0.5}))
    (tmp_path / "bench" / "cells" / "full3.probe_only.json").write_text(
        json.dumps({"rate_ops_s": 40}))
    (tmp_path / "bench" / "metrics" / "probes_seen.py").write_text(
        "def read(run):\n    return float(run.count('probe')) or None\n")
    return tmp_path


class TestFoundByName:
    def test_new_mix_cell_and_metric_need_only_new_files(
            self, grown_root, sets_root, monkeypatch):
        cell = harness.load_cell("full3.probe_only", root=str(grown_root))
        assert cell.mix == {"ops": {"probe": 1.0}, "zipf_theta": 0.5}
        assert cell.rate == 40.0
        assert cell.cfg["elements"] == 100_000
        assert [m["name"] for m in cell.per_layer] == ["probes_seen"]
        elements = traffic.ycsb_keys(500)
        ops = traffic.schedule(cell.mix, cell.rate, 2.0, 3,
                               [traffic.SetData(b"s", 0, elements, [])], 500)
        assert len(ops) == 80 and {o.kind for o in ops} == {"probe"}
        read = harness.metric_reader("probes_seen", root=str(grown_root))
        run = harness.RunData(2.0, [], {"probe": [0.1] * 3}, {}, {}, 3,
                              None, None, 0)
        assert read(run) == 3.0
        names = [m["name"] for m in cell.end_to_end]
        assert names == ["ops_per_s", "setup_s", "probe_p95_ms"]
        assert harness.end_to_end(run, 5.0, names) == {
            "setup_s": 5.0, "ops_per_s": 1.5,
            "probe_p95_ms": pytest.approx(100.0)}

        # many sets of two groups, and every kind of request, run correct
        runs = []
        window = harness.run_window
        monkeypatch.setattr(harness, "run_window",
                            lambda *a, **k: runs.append(window(*a, **k))
                            or runs[-1])
        res = harness.run_cell(
            "links.mix", 13, 1.0, False, t_start=time.perf_counter(),
            root=sets_root, require_chip=False, log=lambda _m: None)
        assert res["correct"], res["checks"]
        assert res["attempted"] == 40 and res["failed"] == 0
        assert all(c["value"] == 0 for c in res["checks"].values())
        assert "wrong_counts" in res["checks"]
        assert set(res["metrics"]) == {"ops_per_s", "setup_s",
                                       "count_p95_ms"}
        (run,) = runs
        assert {k for k, xs in run.latency.items() if xs} == set(
            traffic.KINDS)
        assert len({a.set_name for a in run.answers}) >= 3
        # every file the benchmark already had is byte-identical
        for root in (grown_root, sets_root):
            for dirpath, _dirs, files in os.walk(os.path.join(ROOT,
                                                              "bench")):
                if "__pycache__" in dirpath:
                    continue
                for f in files:
                    orig = os.path.join(dirpath, f)
                    copy = os.path.join(root, os.path.relpath(orig, ROOT))
                    assert _digest(orig) == _digest(copy)

    def test_a_config_may_bring_its_own_builder(self, tmp_path):
        from bench import deploy

        (tmp_path / "five.py").write_text(
            "from repro.cluster.clusters import BigsetCluster\n"
            "def build(cfg):\n"
            "    return BigsetCluster(cfg['n_val'] + 2)\n")
        cfg = {"n_val": 3, "r": 2, "set_name": "s", "placement": "none"}
        dep = deploy.deploy(cfg, str(tmp_path), "five")
        assert dep.cluster.n == 5 and dep.r == 2
        with pytest.raises(ValueError):
            deploy.deploy(cfg, str(tmp_path), "six")

    @pytest.mark.parametrize("workload", ["full3.ycsb_e", "ring64.ycsb_e"])
    def test_every_cell_resolves_with_its_metrics(self, workload,
                                                  cells_root):
        cell = harness.load_cell(workload, root=cells_root)
        assert cell.rate > 0 and cell.chips == 1
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "ops_per_s"} <= names and len(names) >= 3
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"], cells_root))


class TestEndToEnd:
    def _run(self):
        ms = [x / 1e3 for x in range(1, 101)]   # 1 .. 100 ms
        return harness.RunData(
            10.0, [], {"range": ms, "insert": [0.5], "remove": [0.7]},
            {}, {}, 42, None, None, 0)

    def test_tails_are_found_by_name(self):
        e2e = harness.end_to_end(
            self._run(), 12.5,
            ["page_p95_ms", "page_p50_ms", "write_p95_ms", "probe_p95_ms",
             "ops_per_s", "setup_s", "other_p95_ms"])
        assert e2e["setup_s"] == 12.5 and e2e["ops_per_s"] == 4.2
        assert e2e["page_p95_ms"] == pytest.approx(95.05)
        assert e2e["page_p50_ms"] == pytest.approx(50.5)
        assert e2e["write_p95_ms"] == pytest.approx(690.0)
        # no probe was served: the metric is left out of the line
        assert e2e["probe_p95_ms"] is None
        assert "other_p95_ms" not in e2e


class TestTraffic:
    MIX_E = json.load(open(os.path.join(
        ROOT, "bench", "traffic", "ycsb_e_ranges.json")))
    # YCSB A, as a probe-and-write cell would add it
    MIX_A = {"ops": {"probe": 0.5, "insert": 0.25, "remove": 0.25},
             "zipf_theta": 0.99}

    def _ops(self, mix, seed, n=2000):
        elements = traffic.ycsb_keys(n)
        sets = [traffic.SetData(b"s", 0, elements, elements[1::2])]
        return traffic.schedule(mix, 50.0, 10.0, seed, sets, n)

    @pytest.mark.parametrize("seed", [0, 2**31 + 17, 98765432109])
    def test_same_seed_same_schedule(self, seed):
        assert self._ops(self.MIX_E, seed) == self._ops(self.MIX_E, seed)
        assert self._ops(self.MIX_A, seed) == self._ops(self.MIX_A, seed)

    def test_seeds_share_the_schedule_and_draw_the_keys(self):
        a, b = self._ops(self.MIX_E, 1), self._ops(self.MIX_E, 2)
        shape = lambda ops: [(o.due, o.kind, o.limit) for o in ops]
        assert shape(a) == shape(b)
        assert [o.key for o in a] != [o.key for o in b]
        assert len(a) == 500 and a[0].due == 0.0 and a[-1].due <= 10.0
        gaps = np.diff([o.due for o in a])
        assert (gaps >= 0).all() and gaps.mean() == pytest.approx(
            10.0 / 500, rel=0.01)

    def test_mix_shares_and_lengths(self):
        ops = self._ops(self.MIX_E, 5)
        kinds = [o.kind for o in ops]
        assert kinds.count("range") == 475 and kinds.count("insert") == 25
        assert all(1 <= o.limit <= 100 for o in ops if o.kind == "range")
        inserted = [o.key for o in ops if o.kind == "insert"]
        assert inserted == traffic.ycsb_keys(25, start=2000)

    def test_removes_take_live_elements_once(self):
        ops = self._ops(self.MIX_A, 9)
        elements = traffic.ycsb_keys(2000)
        live = set(elements[::2])
        for o in ops:
            if o.kind == "remove":
                assert o.key in live
                live.discard(o.key)

    # sha256 of [[due, kind, key, limit], ...] as JSON, and of the set-up
    # removes joined by newlines, made by the harness before it held many
    # sets: a cell with one set draws the same data and requests as then
    GOLDEN = {
        ("full3.ycsb_e", 7): "074e36c92cff2233896fcf1adb2bca7d"
                             "71b19d580589efd843321a86d3d0195e",
        ("full3.ycsb_e", 2718281828): "a71de114dc0f9ccd59403dffe3f37492"
                                      "88fdf28433a68e0a1e76e277246df21c",
        ("full3.ycsb_e", 98765432109): "8ddb5d89fe98d4d28d3e5c46705222c1"
                                       "d3b43cfcb8ffabf5bc5b9b9f60d6536c",
        ("ring64.ycsb_e", 7): "84ae25db29a4f15422b1ac655e147645"
                              "3418de13e5ee9e8d8482be35caca07d6",
        ("ring64.ycsb_e", 2718281828): "21d52bad3ec81bc3b93102703bf29db5"
                                       "fe0dbde97b33d975b52eadac83886108",
        ("ring64.ycsb_e", 98765432109): "bde5bbac763803e11f82b0207107afae"
                                        "4ba619d148f046e90c4eb6b3fca99731",
    }
    GOLDEN_REMOVED = {
        7: "dfd704f615cc13e0e283b65d433ead6c"
           "8e7b989e75fbdc9420a2bc9f1a308444",
        2718281828: "fe547e9acd06acfd6a371e690df65f19"
                    "85348a5c5d3aaf9bc216ac95af38c87a",
        98765432109: "98b8b5d599d1e2f512506bc94a496cf2"
                     "8158155cf8decd95733169a09a4061ff",
    }

    @pytest.mark.parametrize("workload,seed", sorted(GOLDEN))
    def test_one_set_cells_keep_their_schedules(self, workload, seed):
        cell = harness.load_cell(workload)
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
        sets, first_new = traffic.data(cell.cfg, seed)
        (s,) = sets
        assert s.name == b"followers" and first_new == 100_000
        ops = traffic.schedule(cell.mix, cell.rate, seconds, seed, sets,
                               first_new)
        blob = json.dumps([[o.due, o.kind, o.key.decode(), o.limit]
                           for o in ops])
        assert hashlib.sha256(blob.encode()).hexdigest() == (
            self.GOLDEN[workload, seed])
        assert {(o.set_name, o.right, o.join_kind) for o in ops} == {
            (b"followers", None, None)}
        if workload == "full3.ycsb_e":
            assert hashlib.sha256(b"\n".join(s.removed)).hexdigest() == (
                self.GOLDEN_REMOVED[seed])

    MIX_SETS = {"ops": {"range": 0.3, "probe": 0.1, "count": 0.1,
                        "join": 0.2, "insert": 0.1, "remove": 0.2},
                "range_length": [1, 50], "set_theta": 0.9,
                "join": {"kind": "union", "left": [0], "right": [1]}}
    CFG_SETS = {"remove_share": 0.2,
                "sets": {"name_format": "t%d", "universe": 400,
                         "groups": [{"count": 3, "elements": 100},
                                    {"count": 20, "elements": 2}]}}

    def test_many_sets_are_drawn_from_the_universe_by_the_seed(self):
        sets, first_new = traffic.data(self.CFG_SETS, 4)
        assert first_new == 400 and len(sets) == 23
        assert [s.name for s in sets[:2]] == [b"t0", b"t1"]
        assert [s.group for s in sets] == [0] * 3 + [1] * 20
        universe = set(traffic.ycsb_keys(400))
        for s in sets:
            n = 100 if s.group == 0 else 2
            assert len(set(s.elements)) == n <= len(universe)
            assert set(s.elements) <= universe
            assert len(s.removed) == round(n * 0.2)
            assert set(s.removed) <= set(s.elements)
        # drawn without replacement within a set, so sets overlap
        assert set(sets[0].elements) & set(sets[1].elements)
        again, _ = traffic.data(self.CFG_SETS, 4)
        assert again == sets and traffic.data(self.CFG_SETS, 5)[0] != sets

    def test_many_sets_picks_joins_counts_and_writes(self):
        sets, first_new = traffic.data(self.CFG_SETS, 4)
        ops = traffic.schedule(self.MIX_SETS, 50.0, 10.0, 8, sets, first_new)
        assert ops == traffic.schedule(self.MIX_SETS, 50.0, 10.0, 8, sets,
                                       first_new)
        by_name = {s.name: s for s in sets}
        kinds = [o.kind for o in ops]
        assert kinds.count("join") == 100 and kinds.count("count") == 50
        assert len({o.set_name for o in ops}) > 10
        live = {s.name: set(s.elements) - set(s.removed) for s in sets}
        inserted = []
        for o in ops:
            s = by_name[o.set_name]
            if o.kind == "join":
                assert o.key == b"" and o.join_kind == "union"
                assert s.group == 0 and by_name[o.right].group == 1
                assert 1 <= o.limit <= 50
            elif o.kind == "count":
                assert o.key == b"" and o.limit is None and o.right is None
            elif o.kind in ("range", "probe"):
                assert o.key in s.elements and o.right is None
            elif o.kind == "insert":
                inserted.append(o.key)
                live[o.set_name].add(o.key)
            else:
                assert o.key in live[o.set_name]
                live[o.set_name].discard(o.key)
        assert inserted == traffic.ycsb_keys(len(inserted), start=400)
        # the join lengths are drawn apart: the ranges' are the same as in
        # a mix without joins
        no_join = dict(self.MIX_SETS, ops={"range": 0.3, "probe": 0.7})
        ranges = lambda ops: [o.limit for o in ops if o.kind == "range"]
        assert ranges(ops) == ranges(traffic.schedule(
            no_join, 50.0, 10.0, 8, sets, first_new))

    def test_zipfian_is_skewed_and_in_range(self):
        z = traffic.Zipfian(10_000, 0.99)
        r = z.ranks(traffic.rng_for(1, 1), 100_000)
        assert r.min() >= 0 and r.max() < 10_000
        # rank 0 alone draws about 1 / zeta(n) of the picks
        assert np.mean(r == 0) == pytest.approx(1 / z.zetan, rel=0.1)

    def test_ycsb_key_format(self):
        keys = traffic.ycsb_keys(1000)
        assert all(k.startswith(b"user") and k[4:].isdigit() for k in keys)
        assert traffic.fnvhash64(0) == traffic.fnvhash64(0)
        assert len(set(keys)) == 1000


S = b"s"


def _judge(initial, answers, scan, replicas, restarted=None, **more):
    """``judge`` over one set ``S``, and any ``more`` sets' members."""
    keyed = lambda d: None if d is None else {(S, k): v for k, v in d.items()}
    return reference.judge(dict({S: initial}, **more), answers,
                           dict({S: scan}, **more), keyed(replicas),
                           keyed(restarted))


class TestReference:
    def _answers(self, members):
        a = reference.Answer("range", b"b", 2, S, ok=True, members=members)
        return [a]

    def test_exact_page_is_correct(self):
        v = _judge([b"a", b"b", b"c"], self._answers([b"b", b"c"]),
                   [b"a", b"b", b"c"], {})
        assert v.correct and v.numbers["wrong_pages"] == 0

    @pytest.mark.parametrize("members", [
        [b"b"], [b"c", b"b"], [b"b", b"c", b"d"], [b"a", b"b"]])
    def test_injected_wrong_page_is_flagged(self, members):
        v = _judge([b"a", b"b", b"c"], self._answers(members),
                   [b"a", b"b", b"c"], {})
        assert not v.correct and v.numbers["wrong_pages"] == 1

    def test_writes_probes_and_replicas(self):
        answers = [
            reference.Answer("insert", b"d", set_name=S, ok=True),
            reference.Answer("probe", b"d", set_name=S, ok=True,
                             present=True),
            reference.Answer("remove", b"a", set_name=S, ok=True,
                             removed=True),
            reference.Answer("probe", b"a", set_name=S, ok=True,
                             present=False),
            # refused: skipped
            reference.Answer("remove", b"a", set_name=S, ok=False),
        ]
        good = {b"d": [True, True, True], b"a": [False, False, False]}
        v = _judge([b"a", b"b"], answers, [b"b", b"d"], good)
        assert v.correct, v.examples
        assert "wrong_counts" not in v.numbers   # no count was sent
        lost = {b"d": [True, False, True], b"a": [False, False, False]}
        v = _judge([b"a", b"b"], answers, [b"b", b"d"], lost)
        assert v.numbers["lost_writes"] == 1 and not v.correct
        v = _judge([b"a", b"b"], answers, [b"a", b"b", b"d"], good)
        assert v.numbers["lost_writes"] == 1
        assert v.numbers["scan_mismatch"] == 1

    def test_writes_are_judged_per_set(self):
        # the same element written to one set and read back from another
        answers = [reference.Answer("insert", b"d", set_name=S, ok=True)]
        v = reference.judge({S: [b"a"], b"t": [b"d"]}, answers,
                            {S: [b"a", b"d"], b"t": [b"d"]},
                            {(S, b"d"): [True] * 3})
        assert v.correct, v.examples
        v = reference.judge({S: [b"a"], b"t": []}, answers,
                            {S: [b"a"], b"t": [b"d"]},
                            {(b"t", b"d"): [True] * 3})
        # not in its set's read-back, nor on any replica of its set
        assert v.numbers["lost_writes"] == 2
        assert v.numbers["scan_mismatch"] == 2    # one element in each set

    def test_power_loss_readback(self):
        answers = [reference.Answer("insert", b"d", set_name=S, ok=True)]
        whole = {b"a": [True, True, True], b"b": [False, False, False],
                 b"d": [True, True, True]}
        v = _judge([b"a"], answers, [b"a", b"d"], {b"d": [True] * 3}, whole)
        assert v.correct and v.numbers["lost_on_restart"] == 0
        lost = dict(whole)
        lost[b"d"] = [True, False, False]
        lost[b"b"] = [False, True, False]   # a removed element came back
        v = _judge([b"a"], answers, [b"a", b"d"], {b"d": [True] * 3}, lost)
        assert not v.correct and v.numbers["lost_on_restart"] == 3
        # a configuration that is not durable is not read back so
        v = _judge([b"a"], answers, [b"a", b"d"], {b"d": [True] * 3})
        assert "lost_on_restart" not in v.numbers

    LEFT = [b"a", b"c", b"d", b"f"]
    RIGHT = [b"b", b"c", b"f", b"g"]

    @pytest.mark.parametrize("kind,left,right,limit,want", [
        ("intersect", LEFT, RIGHT, 10, [b"c", b"f"]),
        ("intersect", LEFT, RIGHT, 1, [b"c"]),
        ("intersect", LEFT, [], 10, []),
        ("intersect", [], RIGHT, 10, []),
        ("union", LEFT, RIGHT, 10, [b"a", b"b", b"c", b"d", b"f", b"g"]),
        ("union", LEFT, RIGHT, 3, [b"a", b"b", b"c"]),
        ("union", LEFT, [], 10, LEFT),
        ("union", [], RIGHT, 2, [b"b", b"c"]),
        ("difference", LEFT, RIGHT, 10, [b"a", b"d"]),
        ("difference", RIGHT, LEFT, 10, [b"b", b"g"]),
        ("difference", LEFT, [], 3, [b"a", b"c", b"d"]),
        ("difference", [], RIGHT, 10, []),
    ])
    def test_join_page_is_the_exact_first_members(self, kind, left, right,
                                                  limit, want):
        got = reference.join_page(kind, reference.ReferenceSet(left),
                                  reference.ReferenceSet(right), limit)
        assert got == want
        answer = reference.Answer("join", b"", limit, S, b"t", kind,
                                  ok=True, members=want)
        v = reference.judge({S: left, b"t": right}, [answer],
                            {S: left, b"t": right}, {})
        assert v.correct and v.numbers["wrong_pages"] == 0
        answer.members = want[:-1] if want else [b"z"]
        v = reference.judge({S: left, b"t": right}, [answer],
                            {S: left, b"t": right}, {})
        assert v.numbers["wrong_pages"] == 1

    @pytest.mark.parametrize("got,wrong", [(2, 0), (1, 1), (3, 1),
                                           (None, 1)])
    def test_count_is_the_size_after_the_writes_before_it(self, got, wrong):
        answers = [
            reference.Answer("insert", b"z", set_name=S, ok=True),
            reference.Answer("remove", b"a", set_name=S, ok=True,
                             removed=True),
            reference.Answer("remove", b"b", set_name=S, ok=True,
                             removed=True),
            reference.Answer("count", b"", set_name=S, ok=True, count=got),
            reference.Answer("count", b"", set_name=S, ok=False),  # refused
        ]
        v = _judge([b"a", b"b", b"c"], answers, [b"c", b"z"],
                   {b"z": [True] * 3, b"a": [False] * 3, b"b": [False] * 3})
        assert v.numbers["wrong_counts"] == wrong
        assert v.correct == (wrong == 0)
        assert list(v.numbers)[:3] == ["wrong_pages", "wrong_probes",
                                       "wrong_counts"]


class TestRoofline:
    @pytest.mark.parametrize("n,runs,want", [
        # 512 dots x 9 B + min(9000 x 8, 512 x 14 x 8): the search wins
        (512, 9000, 512 * 9 + 512 * 14 * 8),
        # 32 dots x 9 B + min(150 x 8, 32 x 8 x 8): the whole tombstone wins
        (32, 150, 32 * 9 + 150 * 8),
        (1, 1, 9 + 8),
        (100, 0, 900),
    ])
    def test_bytes_match_hand_counts(self, n, runs, want):
        assert roofline.dot_seen_bytes(n, runs) == want

    @pytest.mark.parametrize("n", [1, 32, 512, 1024, 4096])
    @pytest.mark.parametrize("runs", [1, 2, 150, 512, 9000, 65536])
    def test_binary_search_reading_never_over_100(self, n, runs):
        peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
        # a per-dot binary search at full bandwidth: the fastest such kernel
        search = n * 9 + n * runs.bit_length() * 8
        share = roofline.least_time_s([(n, runs)], peak) / (search / peak)
        assert 0 < share <= 1.0

    def test_unknown_device_is_an_error(self):
        with pytest.raises(KeyError):
            roofline.peaks("TPU v99")


class TestTraceReduction:
    def _recorded(self):
        ms = 1_000_000
        return tracing.Recorded(
            ops={"/device:TPU:0": [
                ("fusion.1", 10 * ms, 5 * ms),
                ("dot_seen_kernel", 30 * ms, 10 * ms),
                ("fusion.1", 35 * ms, 10 * ms),      # overlaps the kernel
                ("copy", 95 * ms, 20 * ms),          # runs past the window
            ]},
            modules={"/device:TPU:0": [
                ("jit_dot_seen_pallas(1)", 30 * ms, 15 * ms),
                ("jit_other(2)", 10 * ms, 5 * ms),
            ]},
            host=[
                ("bench.window", 0, 100 * ms),
                ("gen.idle", 0, 10 * ms),
                ("req.page", 10 * ms, 40 * ms),
                ("gen.idle", 50 * ms, 40 * ms),
                ("req.write", 90 * ms, 10 * ms),
            ])

    def test_busy_idle_and_kernel_time(self):
        s = tracing.summarize(self._recorded())
        assert s.window_s == pytest.approx(0.1)
        # busy: [10, 15) + [30, 45) + [95, 100) = 25 ms of 100
        assert s.busy_s == pytest.approx(0.025)
        assert s.idle_pct == pytest.approx(75.0)
        assert s.kernel_s == pytest.approx(0.015) and s.kernel_events == 1
        assert s.device_ops[0] == ["fusion.1", pytest.approx(0.015)]
        gaps = dict(s.idle_gaps)
        # idle [0,10) gen.idle, [15,30) req.page, [45,95): 40 of 50 ms idle
        assert gaps == {"gen.idle": pytest.approx(0.06),
                        "req.page": pytest.approx(0.015)}

    def test_no_window_annotation_is_an_error(self):
        rec = self._recorded()
        rec.host = [h for h in rec.host if h[0] != "bench.window"]
        with pytest.raises(ValueError):
            tracing.summarize(rec)

    def test_union_merges_overlaps(self):
        assert tracing.union([(5, 9), (1, 3), (2, 4), (9, 10)]) == [
            (1, 4), (5, 10)]
