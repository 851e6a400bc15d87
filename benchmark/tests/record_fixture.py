#!/usr/bin/env python3
"""Records the small TPU trace that test_trace_reduce.py checks the reduction
against (`recorded_v5e.xplane.pb`). Run once on the chip:

    python3 benchmark/tests/record_fixture.py chiprun_out/fixture

Two jitted programs of known names (`jit_fixture_matmul`, `jit_fixture_add`)
run in a fixed pattern with host sleeps between them, inside benchmark
annotations, so the test knows what the reduction has to find: 6 + 3
executions, device idle while the host sleeps, the gaps labelled by the
annotation that covers them.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture.py: needs the chip")

    @jax.jit
    def fixture_matmul(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.01
        return x

    @jax.jit
    def fixture_add(x):
        return x + 1.0

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    fixture_matmul(x).block_until_ready()
    fixture_add(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for i in range(6):
        with jax.profiler.TraceAnnotation("bench:work"):
            y = fixture_matmul(x)
            if i % 2 == 0:
                y = fixture_add(y)
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench:sleep"):
            time.sleep(0.004)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    shutil.copy(path, os.path.join(out_dir, "recorded_v5e.xplane.pb"))
    print(path, os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
