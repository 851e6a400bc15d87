"""Rehearsals 1 and 2 of the on-chip-measurement guide, as tests: every cell
of BENCHMARK.json runs end to end on the CPU at tiny sizes — the four-chip
cell on four virtual CPU devices — with the platform check and the sizes
steered from the test, and prints the contract's result line with the cell's
metrics. And the benchmark refuses to run where there is no chip.

What these cannot show (times, memory, that the programs compile for the
chip) is rehearse_compile.py's and the chip run's.
"""
import json
import os
import subprocess
import sys

import pytest

import common

BENCH = json.load(open(os.path.join(common.REPO, "BENCHMARK.json")))
CELLS = [(w["name"], w["chips"]) for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def copy_root(tmp_path_factory):
    return common.tiny_copy(tmp_path_factory.mktemp("bench_copy"))


def expected(kind, workload):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or workload in m["workloads"]}


@pytest.mark.parametrize("workload,chips", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_cpu_at_tiny_size(copy_root, workload, chips, trace):
    rc, result, out, err = common.steered_run(copy_root, workload, trace,
                                              devices=chips)
    assert rc == 0, (out[-3000:], err[-3000:])
    assert result is not None, out[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == chips
    kind = "per_layer" if trace else "end_to_end"
    names = set(result["metrics"])
    assert names <= expected(kind, workload)
    units = {m["name"]: m["unit"] for m in BENCH[kind]}
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and m["value"] == m["value"]
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] >= result["device"]["busy_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert names
    else:
        assert names == expected(kind, workload)
        assert "setup_s" in names
        if "itl_p90_ms" in names:       # a serving cell: the client's numbers
            assert result["also"]["ttft_p90_ms"] >= \
                result["also"]["ttft_p50_ms"] > 0
            assert result["also"]["serve_tokens_per_s"] > 0


def test_refuses_to_run_without_the_chip():
    """Non-zero exit and no result line where jax finds no accelerator."""
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--workload", CELLS[0][0], "--seed", "0", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=common.REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "nothing was run" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]


def test_refuses_to_run_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and benchmark/ the exit
    code is non-zero and no result is printed."""
    root = common.tiny_copy(tmp_path)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELLS[0][0], "--seed", "0", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert out.returncode != 0
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
