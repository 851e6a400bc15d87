"""Test harness configuration.

Runs the suite on a virtual 8-device CPU mesh so multi-chip sharding code
paths execute without TPU hardware (SURVEY.md §4: "one test corpus, N
backends"; XLA host-platform device-count replaces the reference's
multi-process `tools/launch.py --launcher local` harness for unit scope).

The suite is pinned to the CPU platform here (`jax_platforms`), and the
persistent compilation cache is off for it and for every child process it
starts: tier-1 must not depend on, or write, what earlier runs compiled
(the program's own default is a cache at `.jax_cache/` in the checkout —
`mxnet_tpu/compile_cache.py`).
"""
import os

prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    # MXNET_TEST_SEED overrides the default for reproduction / flakiness
    # hunting (tools/flakiness_checker.py varies it per trial; reference
    # tests/python/unittest/common.py with_seed contract)
    s = int(os.environ.get("MXNET_TEST_SEED", "0"))
    np.random.seed(s)
    import mxnet_tpu as mx

    mx.random.seed(s)
    yield


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


def pytest_sessionfinish(session, exitstatus):
    """Under MXNET_DEBUG_SYNC=1 (the ci/run.sh lock-order rerun of the
    concurrency suites) the whole session doubles as a race hunt: any
    lock-order inversion or blocking hazard the suites drove fails the
    run here with both stacks, even when every assertion passed."""
    if os.environ.get("MXNET_DEBUG_SYNC") != "1":
        return
    from mxnet_tpu import analysis

    rep = analysis.report()
    if rep["inversions"] or rep["hazards"]:
        print("\n" + analysis.format_report(rep))
        session.exitstatus = max(int(exitstatus) or 0, 1)
    else:
        print(f"\nlock-order analysis clean: {len(rep['locks'])} locks, "
              f"{len(rep['edges'])} order edges, 0 inversions, 0 hazards")
