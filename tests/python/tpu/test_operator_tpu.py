"""TPU re-run of the operator corpus — the reference's "one test corpus,
N backends" pattern (`tests/python/gpu/test_operator_gpu.py` imports the
CPU test modules and re-runs them under the GPU context; SURVEY.md §4).

The CPU suite pins jax to the CPU platform process-wide
(`tests/conftest.py`), so the TPU leg runs in a SUBPROCESS on the default
accelerator backend: it executes every forward Spec of the op-coverage
sweep there and ships the outputs back for comparison against the
CPU-computed oracle — `check_consistency` across backends.

Gated by MXNET_TEST_TPU=1: a chip belongs to one process at a time, and
CPU CI has none.
"""
import json
import os
import pickle
import subprocess
import sys
import tempfile

import numpy as np
import pytest

if os.environ.get("MXNET_TEST_TPU", "0") != "1":
    pytest.skip("TPU backend re-run disabled (set MXNET_TEST_TPU=1 on a "
                "machine with exclusive accelerator access)",
                allow_module_level=True)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
sys.path.insert(0, os.path.join(REPO, "tests", "python", "unittest"))


def _driver_env():
    """Env for the on-chip driver subprocess: default accelerator backend
    and no virtual-device XLA flags. The pytest parent is pinned to the CPU
    by tests/conftest.py and never touches the chip, so the child is the
    one process that owns it. MXNET_TEST_TPU_PLATFORM steers the child to
    another platform for a chip-free dry-run of the harness mechanics."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    if os.environ.get("MXNET_TEST_TPU_PLATFORM"):
        env["JAX_PLATFORMS"] = os.environ["MXNET_TEST_TPU_PLATFORM"]
    return env


_DRIVER = r"""
import os, pickle, sys
import numpy as np
sys.path.insert(0, {repo!r})
sys.path.insert(0, {unittest_dir!r})
import mxnet_tpu as mx
import test_op_coverage as C

with open({inp!r}, "rb") as f:
    cases = pickle.load(f)
out = {{}}
# the corpus builds arrays on the DEFAULT context, which is the host cpu
# unless the accelerator is named
with mx.context.default_accelerator():
    for name, (inputs, attrs) in cases.items():
        try:
            res, _ = C._run_op(name, inputs, attrs)
            res_np = C._to_np(res)
            out[name] = res_np if not isinstance(res_np, list) else list(res_np)
        except Exception as e:  # noqa: BLE001
            out[name] = f"ERROR: {{e}}"
with open({outp!r}, "wb") as f:
    pickle.dump(out, f)
print("DONE", len(out))
"""

# gradient leg: compute d sum(op(x)) / dx0 on the accelerator via the
# autograd tape (the reference GPU corpus reruns backward too)
_GRAD_DRIVER = r"""
import os, pickle, sys
import numpy as np
sys.path.insert(0, {repo!r})
sys.path.insert(0, {unittest_dir!r})
import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.ndarray.register import invoke_nd

with open({inp!r}, "rb") as f:
    cases = pickle.load(f)
out = {{}}
with mx.context.default_accelerator():
    for name, (inputs, attrs) in cases.items():
        try:
            x0 = mx.nd.array(inputs[0])
            rest = [mx.nd.array(a) if isinstance(a, np.ndarray) else a
                    for a in inputs[1:]]
            x0.attach_grad()
            with autograd.record():
                res = invoke_nd(name, x0, *rest, **attrs)
                if isinstance(res, (list, tuple)):
                    res = res[0]
                loss = res.sum()
            loss.backward()
            out[name] = x0.grad.asnumpy()
        except Exception as e:  # noqa: BLE001
            out[name] = f"ERROR: {{e}}"
with open({outp!r}, "wb") as f:
    pickle.dump(out, f)
print("DONE", len(out))
"""


def test_op_forward_consistency_cpu_vs_tpu():
    import test_op_coverage as C

    specs = C._get_specs()
    # deterministic forward cases only (samplers excluded by construction);
    # reuse the corpus's own alias-dedup so the TPU leg mirrors it exactly
    cases = {name: (spec.inputs, spec.attrs)
             for name, spec in C._spec_cases() if spec.oracle is not None}

    with tempfile.TemporaryDirectory() as td:
        inp = os.path.join(td, "cases.pkl")
        outp = os.path.join(td, "out.pkl")
        with open(inp, "wb") as f:
            pickle.dump(cases, f)
        driver = _DRIVER.format(
            repo=REPO,
            unittest_dir=os.path.join(REPO, "tests", "python", "unittest"),
            inp=inp, outp=outp)
        env = _driver_env()
        proc = subprocess.run([sys.executable, "-c", driver],
                              capture_output=True, text=True, env=env,
                              cwd=REPO, timeout=3600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        with open(outp, "rb") as f:
            tpu_out = pickle.load(f)

    failures = []
    for name, spec in sorted(specs.items()):
        if name not in cases:
            continue
        got = tpu_out.get(name)
        if isinstance(got, str):
            failures.append(f"{name}: {got}")
            continue
        expect = spec.oracle(*spec.inputs)
        # at least the spec's own CPU tolerance, widened for accelerator
        # accumulation order
        rtol = max(spec.rtol, 1e-2)
        atol = max(spec.atol, 1e-3)
        try:
            if isinstance(expect, tuple):
                for g, e in zip(got, expect):
                    np.testing.assert_allclose(g, e, rtol=rtol, atol=atol)
            else:
                g = got[0] if isinstance(got, list) and \
                    not isinstance(expect, list) else got
                np.testing.assert_allclose(np.asarray(g), expect,
                                           rtol=rtol, atol=atol)
        except AssertionError as e:
            failures.append(f"{name}: {str(e).splitlines()[0]}")
    assert not failures, \
        f"{len(failures)} ops diverge on the accelerator:\n" + \
        "\n".join(failures[:20])


def test_op_gradient_consistency_cpu_vs_tpu():
    """Gradient leg of the cross-backend sweep (round-5; the reference's
    GPU corpus reruns backward as well): for every grad-enabled Spec,
    d sum(op(x))/dx computed on the accelerator must match the same
    quantity computed on CPU."""
    import test_op_coverage as C
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray.register import invoke_nd
    import mxnet_tpu as mx

    cases = {name: (spec.inputs, spec.attrs)
             for name, spec in C._spec_cases() if spec.grad}

    # CPU oracle via the same tape
    cpu_grads = {}
    for name, (inputs, attrs) in cases.items():
        x0 = mx.nd.array(inputs[0])
        rest = [mx.nd.array(a) if isinstance(a, np.ndarray) else a
                for a in inputs[1:]]
        x0.attach_grad()
        with autograd.record():
            res = invoke_nd(name, x0, *rest, **attrs)
            if isinstance(res, (list, tuple)):
                res = res[0]
            loss = res.sum()
        loss.backward()
        cpu_grads[name] = x0.grad.asnumpy()

    with tempfile.TemporaryDirectory() as td:
        inp = os.path.join(td, "cases.pkl")
        outp = os.path.join(td, "out.pkl")
        with open(inp, "wb") as f:
            pickle.dump(cases, f)
        driver = _GRAD_DRIVER.format(
            repo=REPO,
            unittest_dir=os.path.join(REPO, "tests", "python", "unittest"),
            inp=inp, outp=outp)
        env = _driver_env()
        proc = subprocess.run([sys.executable, "-c", driver],
                              capture_output=True, text=True, env=env,
                              cwd=REPO, timeout=3600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        with open(outp, "rb") as f:
            tpu_grads = pickle.load(f)

    failures = []
    for name, cg in sorted(cpu_grads.items()):
        tg = tpu_grads.get(name)
        if isinstance(tg, str):
            failures.append(f"{name}: {tg}")
            continue
        try:
            np.testing.assert_allclose(tg, cg, rtol=1e-2, atol=1e-3)
        except AssertionError as e:
            failures.append(f"{name}: {str(e).splitlines()[0]}")
    assert not failures, \
        f"{len(failures)} op GRADIENTS diverge on the accelerator:\n" + \
        "\n".join(failures[:20])
