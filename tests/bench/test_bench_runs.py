"""Whole benchmark runs at a small size on the CPU: the harness's look for
a chip is skipped, everything else runs as on the chip.  A sound run is
correct; each fault a cell can have, planted under the timed path, makes
``correct`` come out false; the result line has exactly the contract's
keys; without a TPU the command exits nonzero and prints no result."""
import contextlib
import gc
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, run

SMALL = {"elements": 1500}
CELLS = ["full3.ycsb_e", "ring64.ycsb_e", "full3.probe_write", "links.mix"]
DURABLE = {"ring64.ycsb_e"}


def small_run(workload, seed=11, trace=False, fault=None, **kw):
    kw.setdefault("overrides", SMALL)
    return harness.run_cell(workload, seed, 1.0, trace,
                            t_start=time.perf_counter(), require_chip=False,
                            fault=fault, log=lambda _m: None, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, cells_root):
    res = small_run(workload, rate=20, root=cells_root)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 20
    assert all(c["value"] == 0 for c in res["checks"].values())
    # only a durable configuration is read back after a power loss
    assert ("lost_on_restart" in res["checks"]) == (workload in DURABLE)


@pytest.mark.parametrize("kind", ["union", "difference"])
def test_every_join_kind_is_correct(kind, cells_root):
    # the many-set cell's own mix joins by intersect
    path = os.path.join(cells_root, "bench", "traffic", "links_mix.json")
    with open(path) as f:
        mix = json.load(f)
    mix["join"]["kind"] = kind
    with open(path, "w") as f:
        json.dump(mix, f)
    res = small_run("links.mix", rate=20, root=cells_root)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_has_the_contract_keys(trace):
    res = small_run("full3.ycsb_e", trace=trace, rate=20)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(res) == keys + ["checks"]  # the checks come last
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    dev |= {"busy_s", "window_s"} if trace else set()
    assert set(res["device"]) == dev
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    cell = harness.load_cell("full3.ycsb_e")
    if trace:
        # no device plane on the CPU: the device readers stay silent
        assert set(res["metrics"]) == {
            "launches_per_page", "useful_row_pct", "bytes_read_per_page",
            "page_service_ms_p50"}
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    json.dumps(res)


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    assert run.main(["--workload", "full3.ycsb_e", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 1
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(os.path.join(harness.CHECKOUT, "bench"),
                    tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.CHECKOUT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "full3.ycsb_e",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


# --------------------------------------------------------------- faults
@contextlib.contextmanager
def patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _both_collect(make):
    from repro.cluster import clusters
    from repro.query import executor

    stack = contextlib.ExitStack()
    stack.enter_context(patched(executor, "collect_page", make))
    stack.enter_context(patched(clusters, "collect_page", make))
    return stack


def drop_last_member():
    """An answer altered where it is produced: every page loses its last
    member."""
    def make(orig):
        def collect_page(entries, limit, scope, res):
            orig(entries, limit, scope, res)
            if res.entries:
                res.entries.pop()
        return collect_page
    return _both_collect(make)


def half_the_page():
    """Half of the batch left out: a page keeps its first half."""
    def make(orig):
        def collect_page(entries, limit, scope, res):
            orig(entries, limit, scope, res)
            del res.entries[(len(res.entries) + 1) // 2:]
        return collect_page
    return _both_collect(make)


def flip_probe():
    """An answer altered where it is produced: Membership inverted."""
    from repro.cluster import clusters

    def make(orig):
        def quorum_is_member(streams):
            present, dots = orig(streams)
            return (not present), dots
        return quorum_is_member
    return patched(clusters, "quorum_is_member", make)


def writes_change_nothing():
    """A step that returns its state unchanged: storage drops every write
    batch the window acknowledges."""
    from repro.storage import lsm

    return patched(lsm.LsmStore, "put_batch",
                   lambda orig: lambda self, *a, **k: None)


def remove_says_nothing():
    """An answer altered where it is produced: every remove reports that
    it removed nothing, though it did."""
    from repro.cluster import clusters

    def make(orig):
        def remove(self, *a, **k):
            orig(self, *a, **k)
            return None
        return remove
    return patched(clusters.BigsetCluster, "remove", make)


def no_replication():
    """The exchange left out: the coordinator never ships the delta."""
    from repro.cluster import clusters

    return patched(clusters.BigsetCluster, "_replicate_to",
                   lambda orig: lambda self, *a, **k: None)


def join_drops_a_member():
    """An answer altered where it is produced: every join, zipper or
    gallop, loses its first member."""
    from repro.cluster import clusters

    def make(orig):
        def join(*a, **k):
            entries = iter(orig(*a, **k))
            next(entries, None)
            return entries
        return join

    stack = contextlib.ExitStack()
    stack.enter_context(patched(clusters, "zipper_join", make))
    stack.enter_context(patched(clusters, "gallop_join", make))
    return stack


def count_off_by_one():
    """An answer altered where it is produced: every count one too many."""
    from repro.cluster import clusters

    def make(orig):
        def q_count(self, *a, **k):
            res = orig(self, *a, **k)
            res.count += 1
            return res
        return q_count
    return patched(clusters.BigsetCluster, "_q_count", make)


def insert_into_the_wrong_set():
    """A step applied to the wrong state: every insert lands in another
    set of the deployment."""
    from repro.cluster import clusters

    def make(orig):
        def add(self, set_name, element, *a, **k):
            other = b"links.1" if set_name == b"links.0" else b"links.0"
            return orig(self, other, element, *a, **k)
        return add
    return patched(clusters.BigsetCluster, "add", make)


def fsync_does_nothing():
    """A later or rarer flush: the write-ahead log's fsync keeps nothing,
    so what the window wrote lives in memory alone."""
    from repro.storage import wal

    return patched(wal.DurableMedia, "wal_sync",
                   lambda orig: lambda self: None)


@pytest.mark.parametrize("workload,fault,number", [
    ("full3.ycsb_e", drop_last_member, "wrong_pages"),
    ("ring64.ycsb_e", drop_last_member, "wrong_pages"),
    ("full3.ycsb_e", half_the_page, "wrong_pages"),
    ("full3.probe_write", flip_probe, "wrong_probes"),
    ("full3.probe_write", remove_says_nothing, "wrong_removes"),
    ("full3.probe_write", writes_change_nothing, "lost_writes"),
    ("full3.ycsb_e", writes_change_nothing, "lost_writes"),
    ("ring64.ycsb_e", writes_change_nothing, "lost_writes"),
    ("full3.probe_write", no_replication, "lost_writes"),
    ("full3.ycsb_e", no_replication, "lost_writes"),
    ("ring64.ycsb_e", no_replication, "lost_writes"),
    ("ring64.ycsb_e", fsync_does_nothing, "lost_on_restart"),
    ("links.mix", join_drops_a_member, "wrong_pages"),
    ("links.mix", count_off_by_one, "wrong_counts"),
    ("links.mix", insert_into_the_wrong_set, "lost_writes"),
])
def test_fault_makes_the_run_incorrect(workload, fault, number, cells_root):
    res = small_run(workload, fault=fault, rate=40, root=cells_root)
    assert not res["correct"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_setup_heap_is_frozen_for_the_window_and_released_after(
        monkeypatch):
    frozen = []
    orig = harness.run_window

    def window(*a, **kw):
        frozen.append(gc.get_freeze_count())
        return orig(*a, **kw)

    monkeypatch.setattr(harness, "run_window", window)
    res = small_run("full3.ycsb_e", rate=20)
    assert res["correct"]
    assert frozen[0] > 0 and gc.get_freeze_count() == 0


def test_gc_pauses_counts_full_collections():
    with harness.GcPauses() as pauses:
        gc.collect()
    assert pauses.full >= 1 and pauses.count >= pauses.full
    assert pauses.seconds > 0
    assert pauses._on_gc not in gc.callbacks
