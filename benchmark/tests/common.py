"""Shared by the tests: a temp copy of the benchmark (BENCHMARK.json and
benchmark/, which is all the harness may need besides the program), shrunk to
sizes the CPU runs in seconds, and one steered run of a cell of it."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)

TINY_RESNET = dict(units=[1, 1], stage_filters=[16, 32], stem_filters=8,
                   image_size=40, num_classes=10)
TINY_GPT2 = dict(n_embd=64, n_head=4, n_layer=2, n_inner=128, vocab_size=211,
                 n_positions=128, dtype="float32")
TINY_TRAIN = dict(batch_per_chip=4, pool_batches=3, warmup_steps=3,
                  trace={"after_s": 0.3, "seconds": 0.4})
TINY_CHAT = dict(
    arrivals={"rate_per_s": 6.0, "lead_in_s": 1.0, "tail_s": 5.0},
    prompt_len={"median": 20, "min": 4, "max": 60},
    output_len={"median": 8, "min": 4, "max": 16}, max_total=128,
    engine={"max_slots": 4, "max_len": 128, "buckets": [16, 64]},
    parity_requests=2, trace={"after_s": 0.3, "seconds": 0.8})


def edit_json(path, **changes):
    with open(path) as f:
        d = json.load(f)
    for k, v in changes.items():
        if isinstance(v, dict) and isinstance(d.get(k), dict):
            d[k].update(v)
        else:
            d[k] = v
    with open(path, "w") as f:
        json.dump(d, f, indent=1)


def tiny_copy(dst):
    """BENCHMARK.json + benchmark/ copied under `dst`, every configuration
    and traffic file shrunk. Returns `dst`."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    bench = os.path.join(dst, "benchmark")
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for cfg in ("resnet50_v1", "resnet50_preact"):
        edit_json(os.path.join(bench, "configs", cfg + ".json"),
                  **TINY_RESNET)
    edit_json(os.path.join(bench, "configs", "gpt2_xl.json"), **TINY_GPT2)
    for job in ("module_fp32", "module_dp4", "gluon_bf16"):
        edit_json(os.path.join(bench, "traffic", job + ".json"), **TINY_TRAIN)
    edit_json(os.path.join(bench, "traffic", "chat.json"), **TINY_CHAT)
    return str(dst)


def steered_run(copy_root, workload, trace, seconds=1.5, devices=1):
    """One run of `workload` from the copy, on the CPU. Returns
    (returncode, result object or None, stdout, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "steer.py"), str(copy_root),
         workload, str(int(trace)), str(seconds)],
        capture_output=True, text=True, timeout=900, env=env, cwd=copy_root)
    lines = out.stdout.strip().splitlines()
    result = None
    if out.returncode == 0 and lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    return out.returncode, result, out.stdout, out.stderr
