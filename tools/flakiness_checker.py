#!/usr/bin/env python
"""Re-run one test many times hunting flakiness (parity:
`tools/flakiness_checker.py`): takes `test_file.py:test_name` (or
module.test_name), runs it N times under different seeds, reports failures.

  python tools/flakiness_checker.py tests/python/unittest/test_ndarray.py:test_random -n 20
"""
import argparse
import os
import subprocess
import sys

DEFAULT_NUM_TRIALS = 10


def find_test_path(spec):
    if ":" in spec:
        path, name = spec.rsplit(":", 1)
    elif "." in spec and not spec.endswith(".py"):
        mod, name = spec.rsplit(".", 1)
        path = os.path.join(*mod.split(".")) + ".py"
    else:
        raise SystemExit("specify test as path/to/file.py:test_name")
    if not os.path.exists(path):
        raise SystemExit(f"no such test file: {path}")
    return path, name


def run_test_trials(path, name, num_trials, seed, verbose):
    failures = 0
    for i in range(num_trials):
        env = dict(os.environ)
        env["MXNET_TEST_SEED"] = str(seed if seed is not None else i)
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", f"{path}::{name}", "-q",
             "-x", "--no-header"],
            capture_output=True, text=True, env=env)
        ok = proc.returncode == 0
        if not ok:
            failures += 1
        if verbose or not ok:
            tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
            print(f"trial {i}: {'PASS' if ok else 'FAIL'}  {tail}")
    return failures


def main():
    p = argparse.ArgumentParser(description="check a test for flakiness")
    p.add_argument("test", help="path/to/test_file.py:test_name")
    p.add_argument("-n", "--num-trials", type=int,
                   default=DEFAULT_NUM_TRIALS)
    p.add_argument("-s", "--seed", type=int, default=None,
                   help="fixed seed (default: varies per trial)")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args()

    path, name = find_test_path(args.test)
    failures = run_test_trials(path, name, args.num_trials, args.seed,
                               args.verbose)
    print(f"{failures}/{args.num_trials} trials failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
