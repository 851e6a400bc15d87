"""Smoke tests for bench.py: one parseable JSON line when it runs, a
non-zero exit when it has no chip (and is not told to use the CPU) or when
a phase fails."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


@pytest.mark.slow
def test_bench_emits_json_on_cpu(tmp_path):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", BENCH_FORCE_CPU="1", BENCH_ITERS="1",
               # the run exercises the ledger append path, into a scratch file
               MXNET_PERF_LEDGER=str(tmp_path / "ledger.jsonl"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    lines = [l for l in out.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, f"expected exactly one JSON line, got: {out.stdout!r}"
    rec = json.loads(lines[0])
    # the exit code says whether every phase ran: non-zero iff some phase
    # recorded an error
    failed = [k for k in rec if k == "error" or k.endswith("_error")]
    assert (out.returncode != 0) == bool(failed), (failed, out.stderr[-2000:])
    assert rec["metric"] == "resnet50_train_img_per_sec"
    assert rec["unit"] == "img/s"
    assert "vs_baseline" in rec
    assert rec["value"] > 0, rec
    assert rec.get("backend") == "cpu"


def test_bench_without_chip_exits_nonzero():
    """No accelerator and no BENCH_FORCE_CPU=1: the bench refuses to run —
    non-zero exit, no result line — instead of measuring the CPU under the
    chip's name."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_FORCE_CPU", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]


def test_failed_phase_makes_exit_nonzero(tmp_path, monkeypatch, capsys):
    """A phase that raises is recorded in its `*_error` field AND makes the
    exit code non-zero (it used to be swallowed into the field, exit 0)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_for_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setenv("BENCH_TELEMETRY_OUT", "0")
    monkeypatch.setenv("MXNET_OBSERVATORY", "0")
    monkeypatch.setenv("MXNET_PERF_LEDGER", "0")
    spec.loader.exec_module(bench)

    def boom(*a, **k):
        raise RuntimeError("stub phase failure")

    for name in dir(bench):
        if name.startswith("_measure_") and name != "_measure_all":
            monkeypatch.setattr(bench, name, boom)
    assert bench.main() == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["backend"] == "cpu"
    assert "stub phase failure" in rec["error"]
