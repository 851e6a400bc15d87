#!/usr/bin/env python
"""Launch a distributed job as N local worker processes.

Parity: `tools/launch.py` + the dmlc_tracker `local` submitter the reference
delegates to (`tools/launch.py:71-73`, `dmlc_tracker/local.py`) — the thing
CI drives with `--launcher local` (`ci/docker/runtime_functions.sh:1099`).

The reference spawns a scheduler + S servers + N workers and wires them with
`DMLC_*` env rendezvous. The TPU build has no servers or scheduler: every
worker joins one jax.distributed process group (coordinator = worker 0), so
this launcher spawns exactly N workers and sets both the native names
(`MXNET_COORDINATOR` / `MXNET_NUM_PROCESSES` / `MXNET_PROCESS_ID`) and the
reference's (`DMLC_PS_ROOT_URI` / `DMLC_NUM_WORKER` / `DMLC_WORKER_ID`) so
either convention works in worker code. `-s/--num-servers` is accepted and
ignored (documented divergence: collectives have no server role).

Usage:
    python tools/launch.py -n 4 python tests/dist/test_dist_kvstore.py
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading


def _free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _stream(proc, rank, out):
    for line in iter(proc.stdout.readline, b""):
        out.write(f"[worker {rank}] ".encode() + line)
        out.flush()


def launch(num_workers, command, extra_env=None, platform="cpu", timeout=None,
           restart_policy="none"):
    """Spawn ``num_workers`` copies of ``command``; returns max exit code.

    Workers rendezvous on a fresh local port. ``restart_policy`` decides
    what a dying worker means:

    * ``none`` (default, the original contract): on the first non-zero
      exit the rest are killed (the reference's local tracker waits for
      all and hangs on partial failure; failing fast is strictly better
      for CI).
    * ``shrink``: the elastic contract (`mxnet_tpu/parallel/elastic.py`).
      Every worker gets `MXNET_ELASTIC=1` plus a shared
      `MXNET_ELASTIC_DIR` lease directory; a worker killed by a SIGNAL
      (negative exit — the preemption/kill case) does NOT bring the fleet
      down: survivors detect the lost lease, run the shrink rendezvous,
      re-exec into the smaller group (same pids, so they stay tracked
      here) and finish the job. A POSITIVE non-zero exit is still a bug
      and still fails fast. Overall rc is 0 only if at least one worker
      finished cleanly and none failed with a positive code.
    """
    if platform != "cpu" and num_workers > 1:
        # a chip belongs to one process: N local workers on an accelerator
        # platform would all open the same devices and fail or hang
        raise ValueError(
            f"--platform {platform} with {num_workers} workers on one host "
            f"is not a supported layout: nothing gives each worker its own "
            f"chip. On one host run ONE process over all its devices (a "
            f"mesh, e.g. MXNET_SPMD=dp=4); use -n > 1 only with "
            f"--platform cpu (docs/faq/distributed_training.md)")
    port = _free_port()
    procs = []
    threads = []
    elastic_env = {}
    if restart_policy == "shrink":
        import tempfile

        elastic_env = {
            "MXNET_ELASTIC": "1",
            "MXNET_ELASTIC_DIR": tempfile.mkdtemp(prefix="mxnet_elastic_"),
        }
    for rank in range(num_workers):
        env = dict(os.environ)
        env.update(elastic_env)
        env.update(extra_env or {})
        env.update({
            "MXNET_COORDINATOR": f"127.0.0.1:{port}",
            "MXNET_NUM_PROCESSES": str(num_workers),
            "MXNET_PROCESS_ID": str(rank),
            "MXNET_DIST_PLATFORM": platform,
            # reference ps-lite names (minus scheduler/server roles)
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(num_workers),
            "DMLC_WORKER_ID": str(rank),
        })
        p = subprocess.Popen(command, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        t = threading.Thread(target=_stream, args=(p, rank, sys.stdout.buffer),
                             daemon=True)
        t.start()
        procs.append(p)
        threads.append(t)

    rc = 0
    try:
        import time
        deadline = (time.monotonic() + timeout) if timeout else None
        live = list(procs)
        codes = []
        while live:
            # poll ALL workers: a failure in any rank must kill the rest even
            # while earlier ranks sit blocked inside a collective
            for p in list(live):
                code = p.poll()
                if code is None:
                    continue
                live.remove(p)
                codes.append(code)
                if code != 0:
                    if restart_policy == "shrink" and code < 0:
                        # signal death under the elastic policy: survivors
                        # shrink and carry the job — keep waiting for them
                        continue
                    rc = code
                    live = []
                    break
            if live and deadline and time.monotonic() > deadline:
                rc = 124
                break
            if live:
                time.sleep(0.2)
        if restart_policy == "shrink" and rc == 0 and codes and \
                not any(c == 0 for c in codes):
            rc = 1  # every worker died by signal; nobody finished the job
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for t in threads:
            t.join(timeout=5)
        if elastic_env:
            # every worker (including re-exec'd survivors) is gone now;
            # the lease/rendezvous dir must not accumulate across runs
            import shutil

            shutil.rmtree(elastic_env["MXNET_ELASTIC_DIR"],
                          ignore_errors=True)
    return rc


def main(argv=None):
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", required=True, type=int,
                        help="number of worker processes to launch")
    parser.add_argument("-s", "--num-servers", type=int, default=None,
                        help="accepted for reference CLI parity; ignored "
                             "(no server role in the collective design)")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local"],
                        help="only 'local' is meaningful: multi-host TPU jobs "
                             "rendezvous through the TPU runtime, not ssh/yarn")
    parser.add_argument("--env", action="append", default=[],
                        help="KEY=VALUE passed to every worker")
    parser.add_argument("--platform", type=str, default="cpu",
                        help="jax platform forced in workers (cpu for "
                             "multi-process correctness runs; an "
                             "accelerator platform only with -n 1 — one "
                             "process drives all of a host's chips)")
    parser.add_argument("--timeout", type=float, default=None,
                        help="per-worker wall-clock limit in seconds")
    parser.add_argument("--restart-policy", type=str, default="none",
                        choices=["none", "shrink"],
                        help="what a dying worker means: 'none' kills the "
                             "fleet (CI fail-fast); 'shrink' arms the "
                             "elastic runtime (MXNET_ELASTIC + shared "
                             "lease dir) so survivors shrink the "
                             "rendezvous and resume from the latest "
                             "checkpoint instead of hanging")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command to launch")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    extra = dict(kv.split("=", 1) for kv in args.env)
    try:
        return launch(args.num_workers, args.command, extra_env=extra,
                      platform=args.platform, timeout=args.timeout,
                      restart_policy=args.restart_policy)
    except ValueError as e:  # an unsupported layout, refused before any spawn
        parser.error(str(e))


if __name__ == "__main__":
    signal.signal(signal.SIGINT, signal.SIG_DFL)
    sys.exit(main())
