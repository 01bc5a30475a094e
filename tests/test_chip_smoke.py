"""``chip_smoke.py`` at a small size on the CPU.

The smoke runs on a TPU, where nobody watches it between chip runs.  These
tests drive its phases here so that a change to the served path or the
kernel breaks them before it breaks the chip run: the served path against
its reference, on the CPU's branch of the backend choice and on the TPU's
(with the kernel interpreted), the kernel check with the kernel
interpreted, and both refusals — no TPU, and no repo around the script.
"""
import functools
import importlib.util
import json
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
N_ELEMENTS = 3000


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def served(smoke):
    lines = []
    out = smoke.served_path(N_ELEMENTS, seed=0, log=lines.append)
    return out, lines


def test_served_path_matches_reference(served):
    out, lines = served
    n_removed = N_ELEMENTS * 10 // 100
    assert len(out["survivors"]) == N_ELEMENTS - n_removed
    assert any(f"removed {n_removed}" in line for line in lines)
    d = out["dispatches"]
    # the reads tested their keys on the device path; off the TPU that is
    # the reference, chosen by backend with no flag from the caller
    assert d.launches > 0 and d.pallas_launches == 0 and d.interpreted == 0


def test_served_path_takes_the_tpu_branch(smoke, monkeypatch):
    # Rehearse the chip's branch: the backend check says TPU, and the
    # kernel it picks runs in the interpreter, as only a test may ask.
    from repro.kernels.dot_seen import dot_seen_pallas

    ops = importlib.import_module("repro.kernels.dot_seen.ops")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(
        ops, "dot_seen_pallas",
        lambda *args, interpret, **kw: dot_seen_pallas(
            *args, interpret=True, **kw))
    out = smoke.served_path(1500, seed=1, log=lambda line: None)
    d = out["dispatches"]
    assert d.launches > 0
    assert d.pallas_launches == d.launches and d.interpreted == 0


def test_kernel_check_agrees_interpreted(smoke, served, monkeypatch):
    from repro.kernels.dot_seen import dot_seen_pallas
    from repro.query.batch import bucket_shape, dense_shape

    out, _ = served
    cluster = out["cluster"]
    ts = cluster.vnodes[cluster.actors[0]].read_tombstone(smoke.SET)
    n_actors, n_runs = dense_shape(ts)
    assert n_runs > 0 and bucket_shape(n_actors, n_runs)[0] % 8 == 0
    pkg = importlib.import_module("repro.kernels.dot_seen")
    monkeypatch.setattr(pkg, "dot_seen_pallas",
                        functools.partial(dot_seen_pallas, interpret=True))
    lines = []
    smoke.check_kernel(ts, seed=0, log=lines.append)
    assert any("shifted" in line and f"{2**24 - 1}" in line for line in lines)


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    for line in run.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
