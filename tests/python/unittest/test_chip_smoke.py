"""Rehearsals 1 and 2 of the on-chip-measurement guide, kept as tests:
`chip_smoke.py` refuses a process with no chip, and — with a test-only tiny
size and the platform check steered FROM THE TEST (the program has no option
for it) — every phase's control flow runs on the CPU, the `--multichip` phase
on four virtual devices. Plus where the persistent compile cache lives.

What these cannot show (that the kernels compile, that the chip agrees with
the host) is `test_chip_compile.py`'s and the chip run's.
"""
import contextlib
import importlib.util
import io
import json
import os
import subprocess
import sys

import pytest

import jax

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def test_refuses_to_run_without_the_chip():
    """Non-zero exit, no result line, and nothing of the program imported —
    let alone a model built."""
    code = ("import runpy, sys\n"
            "try:\n"
            f"    runpy.run_path({SCRIPT!r}, run_name='__main__')\n"
            "finally:\n"
            "    print('PROGRAM_IMPORTED', 'mxnet_tpu' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "nothing was run" in out.stderr
    assert "PROGRAM_IMPORTED False" in out.stdout
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]


# ---------------------------------------------------------------------------
# the phases at a tiny size on the CPU
# ---------------------------------------------------------------------------

def _tiny_symbol(classes):
    data = mx.sym.Variable("data")
    body = mx.sym.Convolution(data=data, num_filter=8, kernel=(3, 3),
                              pad=(1, 1), no_bias=True, name="conv0")
    body = mx.sym.BatchNorm(data=body, fix_gamma=False, name="bn0")
    body = mx.sym.Activation(data=body, act_type="relu", name="relu0")
    body = mx.sym.Pooling(data=body, global_pool=True, kernel=(2, 2),
                          pool_type="avg", name="pool0")
    fc1 = mx.sym.FullyConnected(data=mx.sym.Flatten(body), num_hidden=classes,
                                name="fc1")
    return mx.sym.SoftmaxOutput(data=fc1, name="softmax")


def _tiny_gluon(classes):
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, use_bias=False), nn.BatchNorm(),
            nn.Activation("relu"), nn.GlobalAvgPool2D(), nn.Dense(classes))
    return net


def _run_steered(argv):
    """chip_smoke.main(argv) with the platform check, the device context, the
    kernel-marker assertion, the sizes and the networks steered from here.
    Returns (stdout lines, programs the kernel assertion was asked about)."""
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test",
                                                  SCRIPT)
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    kernel_checks = []
    with pytest.MonkeyPatch.context() as mp:
        # the Pallas kernels run where the chip would run them, interpreted
        mp.setenv("MXNET_PALLAS_ATTENTION", "1")
        mp.setenv("MXNET_PALLAS_INTERPRET", "1")
        mp.delenv("MXNET_SPMD", raising=False)
        mp.setattr(cs, "REQUIRED_PLATFORM", "cpu")
        mp.setattr(cs, "device_context", lambda i=0: mx.cpu(i))
        # interpret mode leaves no tpu_custom_call to find
        mp.setattr(cs, "assert_kernel_in",
                   lambda text, what: kernel_checks.append(what))
        mp.setattr(cs, "resnet_symbol", _tiny_symbol)
        mp.setattr(cs, "resnet_gluon", _tiny_gluon)
        mp.setattr(cs, "describe_environment", lambda devs: None)
        cs.RESNET.update(batch=8, size=8, classes=4, steps=4,
                         multichip_batch=16, multichip_steps=2)
        cs.LM.update(vocab_size=96, d_model=32, n_heads=4, d_ff=64,
                     n_layers=2, max_len=64, dtype="float32")
        cs.SERVE.update(buckets=(8, 16, 64), min_prompt=4, max_prompt=40,
                        shared_prefix=16, max_new_tokens=4)
        cs.HYBRID.update(vocab_size=96, hidden_size=32,
                         shared_intermediate_size=64,
                         layer_types=("mamba", "mamba", "attention", "mamba"),
                         num_attention_heads=4, num_key_value_heads=2,
                         mamba_n_heads=4, mamba_d_head=16, mamba_d_state=128,
                         mamba_chunk_size=8, max_len=128, dtype="float32")
        cs.HYBRID_SERVE.update(buckets=(8, 32), prompts=(5, 8, 20, 31, 12, 3),
                               max_new_tokens=4)
        cs.LM_TRAIN.update(batch=4, seq=64)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cs.main(argv)
    assert rc == 0
    return buf.getvalue().strip().splitlines(), kernel_checks


@pytest.fixture(scope="module")
def default_run():
    return _run_steered([])


@pytest.fixture(scope="module")
def multichip_run():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices (tests/conftest.py)")
    return _run_steered(["--multichip"])


@pytest.mark.parametrize("phase,evidence", [
    ("train/module-fp32", "Module.fused_step x4: loss"),
    ("train/module-fp32", "vs host-CPU reference"),
    ("train/module-fp32", "timing sanity"),
    ("train/gluon-bf16", "record/backward/Trainer.step x4: loss"),
    ("serve/gpt2-small", "12 streamed requests"),
    ("serve/gpt2-small", "compiles after warm-up: engine 0"),
    ("serve/gpt2-small", "greedy parity"),
    ("serve/hybrid", "6 streamed requests over 4 slots"),
    ("serve/hybrid", "compiles after warm-up: engine 0"),
    ("serve/hybrid", "decode K/V access: kernel, block 128; state update: "
                     "kernel"),
    ("serve/hybrid", "greedy parity: 24/24"),
], ids=["module-steps", "module-logits-vs-cpu", "module-timing-sanity",
        "gluon-steps", "serve-streams", "serve-zero-compiles",
        "serve-greedy-parity", "hybrid-streams", "hybrid-zero-compiles",
        "hybrid-decode-paths", "hybrid-greedy-parity"])
def test_default_phases_run_on_cpu(default_run, phase, evidence):
    lines, _ = default_run
    assert any(l.startswith(f"[{phase}]") and evidence in l for l in lines), \
        "\n".join(lines)


def test_default_run_checks_the_kernel_and_the_prefix_cache(default_run):
    lines, kernel_checks = default_run
    assert any("lm.forward" in what for what in kernel_checks)
    assert any("prefix-cache hit: 16 of" in l for l in lines)
    # no multichip phase in a default run
    assert not any(l.startswith("[multichip") for l in lines)


@pytest.mark.parametrize("fixture", ["default_run", "multichip_run"])
def test_last_line_is_the_result_object(fixture, request):
    lines, _ = request.getfixturevalue(fixture)
    rec = json.loads(lines[-1])
    assert set(rec) == {"ok", "device"} and rec["ok"] is True
    assert set(rec["device"]) == {"platform", "kind", "count"}
    dev = jax.devices()[0]
    assert rec["device"] == {"platform": dev.platform,
                             "kind": dev.device_kind,
                             "count": len(jax.devices())}


@pytest.mark.parametrize("mesh", ["lm-train/sp2tp2", "lm-train/dp4",
                                  "module-resnet50/dp=4",
                                  "module-resnet50/fsdp=2,tp=2"])
def test_multichip_phase_spans_four_devices(multichip_run, mesh):
    lines, _ = multichip_run
    (line,) = [l for l in lines if l.startswith(f"[multichip/{mesh}]")]
    assert "parameter shards on 4 device(s)" in line


def test_multichip_phase_runs_only_itself(multichip_run):
    lines, kernel_checks = multichip_run
    assert not any(l.startswith(("[train/", "[serve/")) for l in lines)
    # the list-of-devices idiom is refused, not bound on device 0
    assert any("is refused with the MXNET_SPMD advice" in l for l in lines)
    # the ring hop and the per-shard flash forward were both checked for
    assert any("ring hop" in what for what in kernel_checks)
    assert any("dp=4" in what for what in kernel_checks)
    # and each sharded run was compared with one device
    assert sum("/1dev]" in l for l in lines) == 2


# ---------------------------------------------------------------------------
# where the persistent compile cache lives
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_dir_updates(monkeypatch):
    """Record (and swallow) every jax.config.update of the cache directory."""
    calls = []
    real = jax.config.update

    def update(name, value):
        if name == "jax_compilation_cache_dir":
            calls.append(value)
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return calls


def test_cache_dir_from_outside_is_left_to_jax(monkeypatch, tmp_path,
                                               cache_dir_updates):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory."""
    from mxnet_tpu import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.persistent_cache_dir() == str(tmp_path)
    assert cache_dir_updates == []


def test_cache_dir_default_is_fixed_inside_the_checkout(monkeypatch,
                                                        cache_dir_updates):
    from mxnet_tpu import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.persistent_cache_dir() == want
    assert cache_dir_updates == [want]
    # no other knob of the repo moves it
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "/nonexistent/a")
    monkeypatch.setenv("BENCH_COMPILE_CACHE", "/nonexistent/b")
    assert compile_cache.persistent_cache_dir() == want
