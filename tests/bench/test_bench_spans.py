"""``bench/spans.py``: the program's spans in a traced run.  The reduction
(self time, gaps charged to the innermost span, readings a page) on a
hand-built trace, and whole traced runs at a small size on the CPU."""
import time

import pytest

from bench import harness, spans, tracing

SMALL = {"elements": 1500}
MS = 1_000_000


def _recorded():
    """A 100-ms window: device operations, the benchmark's host labels,
    and one more operation at [47, 49)."""
    return tracing.Recorded(
        ops={"/device:TPU:0": [
            ("fusion.1", 10 * MS, 5 * MS),
            ("dot_seen_kernel", 30 * MS, 10 * MS),
            ("fusion.1", 35 * MS, 10 * MS),
            ("fusion.2", 47 * MS, 2 * MS),
            ("copy", 95 * MS, 20 * MS),
        ]},
        host=[
            ("bench.window", 0, 100 * MS),
            ("gen.idle", 0, 10 * MS),
            ("req.page", 10 * MS, 40 * MS),
            ("gen.idle", 50 * MS, 40 * MS),
            ("req.write", 90 * MS, 10 * MS),
        ])


# a page as a serve.request over a cluster.query over stream and kernel
# spans, an insert cut at the window's end, and a span after the window
PROGRAM = [
    ("serve.request", 10 * MS, 40 * MS),
    ("cluster.query", 11 * MS, 38 * MS),
    ("query.stream_open", 12 * MS, 20 * MS),
    ("query.visibility_build", 13 * MS, 8 * MS),
    ("kernel.h2d", 19 * MS, 2 * MS),
    ("storage.scan", 22 * MS, 3 * MS),
    ("kernel.dot_seen", 27 * MS, 1 * MS),
    ("kernel.mask_sync", 28 * MS, 17 * MS),  # outlives its parent
    ("storage.scan", 47 * MS, 1 * MS),
    ("serve.request", 90 * MS, 10 * MS),
    ("cluster.insert", 91 * MS, 30 * MS),
    ("serve.request", 120 * MS, 5 * MS),
]


def _run_data(stats):
    return harness.RunData(0.1, [], {"range": [0.01] * len(stats)}, {},
                           {"range": stats}, len(stats), None, None, 0)


class TestReduction:
    def test_self_time_is_duration_less_children(self):
        s = spans.summarize(_recorded(), PROGRAM)
        assert s.count == {
            "serve.request": 2, "cluster.query": 1, "query.stream_open": 1,
            "query.visibility_build": 1, "kernel.h2d": 1, "storage.scan": 2,
            "kernel.dot_seen": 1, "kernel.mask_sync": 1, "cluster.insert": 1}
        total = s.total_s
        assert total["serve.request"] == pytest.approx(0.050)
        assert total["cluster.insert"] == pytest.approx(0.009)
        assert total["storage.scan"] == pytest.approx(0.004)
        assert total["kernel.mask_sync"] == pytest.approx(0.017)
        own = s.self_s
        # 40 less cluster.query's 38; 10 less cluster.insert's 9
        assert own["serve.request"] == pytest.approx(0.003)
        # 38 less stream_open [12, 32) and the scan [47, 48)
        assert own["cluster.query"] == pytest.approx(0.017)
        # 20 less visibility_build 8, scan 3, dot_seen 1, mask_sync [28, 32)
        assert own["query.stream_open"] == pytest.approx(0.004)
        assert own["query.visibility_build"] == pytest.approx(0.006)
        assert own["kernel.mask_sync"] == pytest.approx(0.004)
        # self times tile the covered time: no instant counted twice
        assert sum(own.values()) == pytest.approx(0.050)

    def test_gaps_go_to_the_innermost_span(self):
        s = spans.summarize(_recorded(), PROGRAM)
        # idle: [0,10) under no span, so its host label; [15,30) most under
        # visibility_build's own [15,19); [45,47) cluster.query's own;
        # [49,95) 6 ms under spans, 40 under none: the host label
        assert dict(s.gaps) == {
            "gen.idle": pytest.approx(0.010 + 0.046),
            "query.visibility_build": pytest.approx(0.015),
            "cluster.query": pytest.approx(0.002)}

    def test_no_program_spans_read_empty(self):
        assert spans.summarize(_recorded(), []) == spans.Spans()

    def test_no_window_annotation_is_an_error(self):
        rec = _recorded()
        rec.host = [h for h in rec.host if h[0] != tracing.WINDOW]
        with pytest.raises(ValueError):
            spans.summarize(rec, PROGRAM)

    @pytest.mark.parametrize("reading,want", [
        ("visibility_build_ms_per_page", 8 / 2),
        ("storage_ms_per_page", 4 / 2),
        ("dispatch_ms_per_page", 1 / 2),
        ("mask_sync_ms_per_page", 17 / 2),
        ("merge_ms_per_page", 17 / 2),
        ("tombstone_runs_per_page", 9000.0),
    ])
    def test_readings_per_page(self, reading, want):
        run = _run_data([{"tombstone_runs": 8000},
                         {"tombstone_runs": 10000}])
        got = spans.per_page(spans.summarize(_recorded(), PROGRAM), run)
        assert got[reading] == pytest.approx(want)

    @pytest.mark.parametrize("reading", [*spans.READINGS,
                                         "tombstone_runs_per_page"])
    def test_readings_silent_without_spans_or_counter(self, reading):
        run = _run_data([{"bytes_read": 10}])
        got = spans.per_page(spans.summarize(_recorded(), []), run)
        assert got[reading] is None


@pytest.mark.parametrize("workload", ["full3.ycsb_e", "ring64.ycsb_e"])
def test_traced_run_reads_the_program_spans(workload, monkeypatch):
    """The deployment runs the window alone on the profiler-mode tracer;
    every reading is there, the benchmark's own result is unchanged in
    form, and the harness is as it was afterwards."""
    from repro.obs.trace import NULL_TRACER

    deps = []
    orig = harness.deploy.deploy
    monkeypatch.setattr(harness.deploy, "deploy",
                        lambda *a: deps.append(orig(*a)) or deps[-1])
    run_window, load = harness.run_window, tracing.load
    res = spans.run_traced(workload, 11, 1.0, t_start=time.perf_counter(),
                           log=lambda _m: None, require_chip=False,
                           overrides=SMALL, rate=20)
    assert res["correct"], res["checks"]
    assert set(res["program_spans"]) == {*spans.READINGS,
                                         "tombstone_runs_per_page"}
    for name, value in res["program_spans"].items():
        assert value > 0, name
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    (dep,) = deps
    assert dep.cluster.tracer is NULL_TRACER is dep.service.tracer
    assert (harness.run_window, tracing.load) == (run_window, load)


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    assert spans.main(["--workload", "full3.ycsb_e", "--seed", "1",
                       "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""
