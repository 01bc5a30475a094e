"""Shared fixtures of the benchmark's CPU tests."""
import json
import os
import shutil

import pytest

from bench import harness

# A probe-and-write cell that no chip run measures: YCSB A over the
# fully replicated set.  Its mix, rate and tails are made here, as a later
# PR would add them, so the fault tests can drive probes and removes.
PROBE_CELL = {"name": "full3.probe_write", "config": "follower_100k_full3",
              "traffic": "probe_write", "chips": 1, "why": "test"}
PROBE_MIX = {"ops": {"probe": 0.5, "insert": 0.25, "remove": 0.25},
             "zipf_theta": 0.99}
PROBE_END_TO_END = [
    dict(name=n, unit="ms", better="lower", bound=0.25, source="host_clock",
         workloads=[PROBE_CELL["name"]])
    for n in ("probe_p95_ms", "write_p95_ms")]

# A many-set cell that no chip run measures: association lists in two
# groups (2 large sets, 30 small ones, drawn from one key universe so they
# overlap) under every kind of request, joins of a large set with any
# other.  Its configuration, mix and rate are new files, as a later PR
# would add them.
SETS_CONFIG = {
    "deployment": "test", "placement": "full", "n_val": 3, "r": 2,
    "durable": False, "remove_share": 0.1, "write_batch": 250,
    "sets": {"name_format": "links.%d", "universe": 3000,
             "groups": [{"count": 2, "elements": 600},
                        {"count": 30, "elements": 20}]}}
SETS_MIX = {"ops": {"range": 0.25, "probe": 0.15, "count": 0.15,
                    "join": 0.15, "insert": 0.15, "remove": 0.15},
            "range_length": [1, 100], "zipf_theta": 0.99, "set_theta": 0.99,
            "join": {"kind": "intersect", "left": [0], "right": [0, 1]}}
SETS_CELL = {"name": "links.mix", "config": "links_small_full3",
             "traffic": "links_mix", "chips": 1, "why": "test"}
SETS_END_TO_END = [
    dict(name="count_p95_ms", unit="ms", better="lower", bound=0.25,
         source="host_clock", workloads=[SETS_CELL["name"]])]


def add_sets_cell(root, spec: dict) -> None:
    """Add the many-set cell to a checkout copy at ``root`` (a path) whose
    ``BENCHMARK.json`` is ``spec``: new files and new entries alone."""
    spec["configs"].append({
        "name": SETS_CELL["config"], "source": "test",
        "file": f"bench/configs/{SETS_CELL['config']}.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append(SETS_CELL)
    spec["end_to_end"] += SETS_END_TO_END
    bench = root / "bench"
    (bench / "configs" / f"{SETS_CELL['config']}.json").write_text(
        json.dumps(SETS_CONFIG))
    (bench / "traffic" / f"{SETS_CELL['traffic']}.json").write_text(
        json.dumps(SETS_MIX))
    (bench / "cells" / f"{SETS_CELL['name']}.json").write_text(
        json.dumps({"rate_ops_s": 40}))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the compile cache is the chip's; a CPU test process keeps none
    monkeypatch.setattr(harness, "enable_cache", lambda root: "off")


@pytest.fixture
def cells_root(tmp_path):
    """A checkout copy whose BENCHMARK.json lists every cell the tests
    drive: the repository's own, plus the probe-and-write cell and the
    many-set cell."""
    shutil.copytree(os.path.join(harness.CHECKOUT, "bench"),
                    tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append(PROBE_CELL)
    spec["end_to_end"] += PROBE_END_TO_END
    add_sets_cell(tmp_path, spec)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "traffic" / "probe_write.json").write_text(
        json.dumps(PROBE_MIX))
    (tmp_path / "bench" / "cells" / "full3.probe_write.json").write_text(
        json.dumps({"rate_ops_s": 40}))
    return str(tmp_path)


@pytest.fixture
def sets_root(tmp_path):
    """A checkout copy to which the many-set configuration, its mix and
    its cell are *added*, and nothing else."""
    root = tmp_path / "sets"
    shutil.copytree(os.path.join(harness.CHECKOUT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    add_sets_cell(root, spec)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)
