"""Bootstrap of the CPU rehearsals (run in a subprocess by the tests): steers
the platform check, the device context and the peaks table to the CPU *from
here* — run.py has no option for it — and, for a traced run, swaps the
reduction of the CPU's own trace (which has no device plane) for the recorded
TPU trace beside this file. Then runs one cell of the benchmark copy it is
given.

    python steer.py <copy root> <workload> <trace 0|1> <seconds>
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")


def main(copy_root, workload, trace, seconds):
    sys.path[:0] = [os.path.join(copy_root, "benchmark"), REPO]
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import harness
    import mxnet_tpu as mx
    import trace_reduce

    harness.REQUIRED_PLATFORM = "cpu"
    harness.device_context = lambda i=0: mx.cpu(i)
    harness.peaks_for = lambda kind: harness.load_json(
        harness.BENCH_DIR, "peaks.json")["TPU v5 lite"]
    real_load = trace_reduce.load
    trace_reduce.load = lambda path, n_devices=None, host_label=None: \
        real_load(RECORDED, n_devices=1, host_label=host_label)
    import run

    return run.main(["--workload", workload, "--seed", "3", "--seconds",
                     seconds, "--trace", trace])


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
