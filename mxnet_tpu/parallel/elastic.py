"""Elastic multi-worker runtime: heartbeat leases, worker-death detection,
and shrink-rendezvous resume.

PR 1's resilience layer detects stragglers and corrupt epochs but never
closes the loop: a dead worker today is everyone else parked forever in a
collective (the `dist.barrier` straggler warning logs and keeps waiting).
This module closes it:

* **Heartbeat leases** — every rank's :class:`Heartbeater` thread renews
  a per-rank lease file under ``MXNET_ELASTIC_DIR`` every
  ``MXNET_ELASTIC_HEARTBEAT_S``; a peer whose lease is older than
  ``MXNET_ELASTIC_GRACE_S`` is declared lost.
* **Guarded collectives** — `dist._allreduce_sum` / `_allgather` /
  `barrier` route through :meth:`ElasticRuntime.guard`: the collective
  runs on a worker thread while the caller polls the leases, so a worker
  death (or wedge) raises :class:`resilience.WorkerLostError` inside the
  training loop instead of blocking forever. A collective that merely
  runs slow with every lease fresh is never interrupted — the grace
  window bounds *stall with a dead peer*, not honest slowness.
* **Shrink rendezvous** — survivors agree on the new membership through
  generation-scoped join files (:meth:`ElasticRuntime.shrink`): new
  contiguous ranks, new world size, and a fresh coordinator chosen by the
  new rank 0. :meth:`ElasticRuntime.exec_resume` then re-execs the
  process image into the new process group (the torchelastic restart
  trampoline, minus the extra agent process) — the mesh, the grad-sync
  bucket plan, and the ZeRO-1 shard group all re-derive from the new
  world size on the way back up, and the training script resumes from
  the latest good checkpoint via `model.load_checkpoint`'s corrupt-epoch
  fallback (``begin_epoch = loaded + 1``). In-process jax re-init after
  losing a peer is NOT attempted: the runtime's device topology is baked
  at backend init, and a half-dead process group is unrecoverable state
  — re-exec is the honest, testable path (tests/dist/elastic_smoke.py).

Telemetry: ``elastic.generation`` / ``elastic.world_size`` gauges,
``elastic.lost_workers`` / ``elastic.shrinks`` counters,
``elastic.shrink_us`` latency histogram, plus an ``elastic.shrink``
tracing span so a shrink shows up on the merged timeline.

Gate: ``MXNET_ELASTIC=1`` + a shared ``MXNET_ELASTIC_DIR`` (tools/launch.py
``--restart-policy shrink`` sets both for every worker).
"""
from __future__ import annotations

import os
import socket
import sys
import threading
import time

from .. import analysis
from .. import health
from .. import telemetry
from .. import tracing
from ..base import getenv, register_env
from ..log import get_logger
from ..resilience import WorkerLostError

__all__ = ["ElasticRuntime", "WorkerLostError", "elastic_enabled",
           "active", "guard", "ensure_started", "generation",
           "shrink_and_exec", "runtime"]

register_env("MXNET_ELASTIC", False,
             "elastic dist runtime: heartbeat leases over the rendezvous, "
             "WorkerLostError from collectives instead of a hung barrier, "
             "shrink rendezvous + checkpoint resume on worker death")
register_env("MXNET_ELASTIC_DIR", "",
             "shared directory for heartbeat leases and the shrink "
             "rendezvous (must be visible to every worker; the launcher's "
             "--restart-policy shrink provisions it)")
register_env("MXNET_ELASTIC_HEARTBEAT_S", 0.5,
             "heartbeat lease renewal interval in seconds")
register_env("MXNET_ELASTIC_GRACE_S", 10.0,
             "a peer whose lease is older than this is declared lost; "
             "bounds how long a dead worker can stall the fleet")
register_env("MXNET_ELASTIC_GENERATION", 0,
             "current elastic generation (set by exec_resume across "
             "shrinks; generation 0 is the original launch)")


def elastic_enabled():
    return bool(getenv("MXNET_ELASTIC"))


def generation():
    """The process's elastic generation: 0 at first launch, +1 per shrink
    (resumed processes read it to decide to reload the checkpoint)."""
    return int(getenv("MXNET_ELASTIC_GENERATION") or 0)


def _logger():
    return get_logger("mxnet_tpu.elastic")


class Heartbeater(threading.Thread):
    """Daemon thread renewing this rank's lease file: an atomic replace of
    ``hb-<rank>`` containing ``<wall-time> <pid>`` every interval. Peers
    read the embedded timestamp (not mtime — clock-readable in tests and
    robust to filesystems with coarse mtimes)."""

    def __init__(self, path, interval_s):
        super().__init__(daemon=True, name="elastic-heartbeat")
        self.path = path
        self.interval_s = float(interval_s)
        self._stop = threading.Event()

    def beat_once(self):
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(f"{time.time()} {os.getpid()}")
        os.replace(tmp, self.path)
        if telemetry._enabled:
            telemetry.counter("elastic.heartbeats").inc()

    def run(self):
        while not self._stop.is_set():
            try:
                self.beat_once()
            except OSError as e:  # lease dir vanished — peers will notice
                _logger().warning("heartbeat write failed: %s", e)
            self._stop.wait(self.interval_s)

    def stop(self):
        self._stop.set()


def _read_lease(path):
    """Lease timestamp in ``path``, or None when missing/torn."""
    try:
        with open(path) as f:
            return float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return None


class ElasticRuntime:
    """One worker's view of the elastic fleet (rank/world of the CURRENT
    generation, lease dir, detector state). Normally a process singleton
    built from env (:func:`runtime`); tests construct instances directly.
    """

    def __init__(self, root, rank, world, gen=None, heartbeat_s=None,
                 grace_s=None):
        self.root = str(root)
        self.rank = int(rank)
        self.world = int(world)
        self.generation = generation() if gen is None else int(gen)
        self.heartbeat_s = float(getenv("MXNET_ELASTIC_HEARTBEAT_S")
                                 if heartbeat_s is None else heartbeat_s)
        self.grace_s = float(getenv("MXNET_ELASTIC_GRACE_S")
                             if grace_s is None else grace_s)
        self._heartbeater = None
        self._started_at = None
        self._lost = set()
        if telemetry._enabled:
            telemetry.gauge("elastic.generation").set(self.generation)
            telemetry.gauge("elastic.world_size").set(self.world)

    # -- lease plumbing ------------------------------------------------------

    def _gen_dir(self, gen=None):
        return os.path.join(self.root,
                            f"gen-{self.generation if gen is None else gen}")

    def _hb_path(self, rank, gen=None):
        return os.path.join(self._gen_dir(gen), f"hb-{rank}")

    def start(self):
        """Begin renewing this rank's lease (idempotent)."""
        if self._heartbeater is not None:
            return self
        os.makedirs(self._gen_dir(), exist_ok=True)
        self._started_at = time.time()
        self._heartbeater = Heartbeater(self._hb_path(self.rank),
                                        self.heartbeat_s)
        self._heartbeater.beat_once()
        self._heartbeater.start()
        return self

    def stop(self):
        if self._heartbeater is not None:
            self._heartbeater.stop()
            self._heartbeater = None

    def peer_ranks(self):
        return [r for r in range(self.world) if r != self.rank]

    def lost_peers(self):
        """Ranks whose lease expired (age > grace). A peer that never
        wrote a lease counts from this runtime's own start time — a
        worker that died before its first beat must still be detected."""
        now = time.time()
        base = self._started_at or now
        lost = []
        for r in self.peer_ranks():
            ts = _read_lease(self._hb_path(r))
            age = now - (ts if ts is not None else base)
            if age > self.grace_s:
                lost.append(r)
        for r in lost:
            if r not in self._lost:
                self._lost.add(r)
                if telemetry._enabled:
                    telemetry.counter("elastic.lost_workers").inc()
                if health._enabled:
                    health.event("worker_lost", rank=r, world=self.world,
                                 generation=self.generation)
                _logger().error(
                    "worker %d lost (lease expired > %.1fs) — fleet was "
                    "%d ranks, generation %d", r, self.grace_s, self.world,
                    self.generation)
        return lost

    def check(self, desc="collective"):
        """Raise :class:`WorkerLostError` if any peer's lease expired."""
        lost = self.lost_peers()
        if lost:
            raise WorkerLostError(desc, lost)

    # -- guarded collectives -------------------------------------------------

    def guard(self, fn, desc="collective"):
        """Run the (blocking) ``fn`` on a worker thread while polling the
        leases. Outcomes:

        * ``fn`` returns with every lease fresh → its result.
        * a peer's lease expires (before, during, or after a failure of
          ``fn``) → :class:`WorkerLostError`, chaining ``fn``'s own error
          when it raced the detection. The stuck daemon thread is
          abandoned — the caller is about to shrink+re-exec anyway.
        * ``fn`` raises with every lease fresh for a full grace window →
          the original error (a genuine collective failure, not a death).

        No fixed timeout: slow-but-alive fleets are never interrupted;
        the lease is the only unblock signal.
        """
        if self.world <= 1:
            return fn()
        box = {}
        done = threading.Event()

        def run():
            try:
                box["v"] = fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                box["e"] = e
            finally:
                done.set()

        th = threading.Thread(target=run, daemon=True,
                              name=f"elastic-guard-{desc}")
        th.start()
        poll = min(self.heartbeat_s, 0.2)
        raised_at = None
        while True:
            finished = done.wait(poll)
            if finished and "e" not in box:
                return box["v"]
            lost = self.lost_peers()
            if lost:
                raise WorkerLostError(desc, lost, cause=box.get("e"))
            if finished:
                # the collective failed but everyone still looks alive:
                # give the leases one grace window to expose a death that
                # raced the error (a gloo connection reset lands before
                # the lease goes stale), then let the real error through.
                # done is already set, so done.wait returns immediately —
                # sleep the poll interval explicitly or this lap of the
                # window becomes a busy spin over the lease files
                if raised_at is None:
                    raised_at = time.monotonic()
                elif time.monotonic() - raised_at > self.grace_s:
                    raise box["e"]
                time.sleep(poll)

    # -- shrink rendezvous ---------------------------------------------------

    def shrink(self):
        """Agree on the surviving membership and the next generation's
        process-group spec. Every survivor calls this after
        :class:`WorkerLostError`; returns ``{"generation", "world",
        "rank", "coordinator"}`` (coordinator None when world == 1)."""
        t0 = time.perf_counter()
        with tracing.span("elastic.shrink", cat="dist",
                          generation=self.generation, rank=self.rank):
            spec = self._shrink()
        dt_us = (time.perf_counter() - t0) * 1e6
        if telemetry._enabled:
            telemetry.counter("elastic.shrinks").inc()
            telemetry.histogram("elastic.shrink_us").record(dt_us)
            telemetry.gauge("elastic.generation").set(spec["generation"])
            telemetry.gauge("elastic.world_size").set(spec["world"])
        if health._enabled:
            health.event("elastic_shrink", generation=spec["generation"],
                         world=spec["world"], rank=spec["rank"])
        _logger().warning(
            "shrink rendezvous complete in %.0f ms: generation %d -> %d, "
            "world %d -> %d, new rank %d, coordinator %s",
            dt_us / 1e3, self.generation, spec["generation"], self.world,
            spec["world"], spec["rank"], spec["coordinator"])
        return spec

    def _shrink(self):
        new_gen = self.generation + 1
        gendir = self._gen_dir(new_gen)
        os.makedirs(gendir, exist_ok=True)
        my_join = os.path.join(gendir, f"join-{self.rank}")
        with open(my_join, "w") as f:
            f.write(str(os.getpid()))
        poll = min(self.heartbeat_s, 0.2)
        deadline = time.monotonic() + self.grace_s + 2 * self.heartbeat_s
        while True:
            joined = {int(n.split("-", 1)[1])
                      for n in os.listdir(gendir) if n.startswith("join-")}
            lost = set(self.lost_peers())
            expected = ({self.rank} |
                        set(self.peer_ranks())) - lost
            if expected <= joined or time.monotonic() > deadline:
                break
            time.sleep(poll)
        # membership is ONE published decision, not a per-rank snapshot:
        # survivors detect the loss at different times, so private
        # `joined - lost` views can disagree (rank A re-execs as world 1
        # while rank B waits for a 2-worker coordinator that never
        # comes). The lowest-ranked joiner publishes the member list with
        # an O_EXCL create (first writer wins; the next candidate takes
        # over if the decider dies mid-shrink) and everyone adopts it.
        members_path = os.path.join(gendir, "members")
        read_deadline = time.monotonic() + self.grace_s
        members = None
        while True:
            try:
                with open(members_path) as f:
                    members = sorted(int(x) for x in f.read().split(",")
                                     if x.strip())
                if members or time.monotonic() > read_deadline:
                    break
                # empty: the decider is between its O_EXCL create and its
                # write (a decision always names the decider itself);
                # past the deadline it died there, and the check below
                # says so
                time.sleep(poll)
                continue
            except OSError:
                pass
            joined = {int(n.split("-", 1)[1])
                      for n in os.listdir(gendir) if n.startswith("join-")}
            alive = sorted((joined | {self.rank}) - set(self.lost_peers()))
            if alive[0] == self.rank:
                try:
                    fd = os.open(members_path,
                                 os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                    with os.fdopen(fd, "w") as f:
                        f.write(",".join(str(r) for r in alive))
                except FileExistsError:
                    pass  # someone else decided first — adopt theirs
                continue
            if time.monotonic() > read_deadline:
                # the decider never published (joined then died with its
                # lease not yet expired, or it has not noticed the death):
                # claim the decision OURSELVES through the same O_EXCL
                # gate and loop to adopt whatever actually landed — two
                # late survivors then read ONE file instead of silently
                # forking into independent fleets
                try:
                    fd = os.open(members_path,
                                 os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                    with os.fdopen(fd, "w") as f:
                        f.write(",".join(str(r) for r in alive))
                except FileExistsError:
                    pass
                continue  # the file exists now; the next lap reads it
            time.sleep(poll)
        if self.rank not in members:
            # our join landed after the decision closed: we cannot be in
            # this generation. Fail loudly (the launcher's shrink policy
            # reports it) rather than split-brain into a private world.
            raise WorkerLostError(
                "shrink rendezvous", [],
                cause=RuntimeError(
                    f"generation {new_gen} membership {members} was "
                    f"published without rank {self.rank}"))
        new_world = len(members)
        new_rank = members.index(self.rank)
        coordinator = None
        if new_world > 1:
            coord_path = os.path.join(gendir, "coordinator")
            if new_rank == 0:
                with socket.socket(socket.AF_INET,
                                   socket.SOCK_STREAM) as s:
                    s.bind(("127.0.0.1", 0))
                    port = s.getsockname()[1]
                coordinator = f"127.0.0.1:{port}"
                tmp = coord_path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(coordinator)
                os.replace(tmp, coord_path)
            else:
                wait_until = time.monotonic() + self.grace_s
                while time.monotonic() < wait_until:
                    try:
                        with open(coord_path) as f:
                            coordinator = f.read().strip()
                        break
                    except OSError:
                        time.sleep(min(self.heartbeat_s, 0.2))
                if coordinator is None:
                    raise WorkerLostError(
                        "shrink rendezvous", [members[0]],
                        cause=RuntimeError("new rank 0 never published a "
                                           "coordinator"))
        return {"generation": new_gen, "world": new_world,
                "rank": new_rank, "coordinator": coordinator}

    def exec_resume(self, spec):
        """Re-exec this process into the shrunk process group: update the
        rendezvous env (native + DMLC names) and replace the image with
        the same argv. The resumed process reads ``generation() > 0`` and
        continues from the latest good checkpoint. Does not return."""
        env = os.environ
        env["MXNET_ELASTIC_GENERATION"] = str(spec["generation"])
        env["MXNET_NUM_PROCESSES"] = str(spec["world"])
        env["MXNET_PROCESS_ID"] = str(spec["rank"])
        env["DMLC_NUM_WORKER"] = str(spec["world"])
        env["DMLC_WORKER_ID"] = str(spec["rank"])
        if spec["coordinator"]:
            env["MXNET_COORDINATOR"] = spec["coordinator"]
            host, _, port = spec["coordinator"].rpartition(":")
            env["DMLC_PS_ROOT_URI"] = host
            env["DMLC_PS_ROOT_PORT"] = port
        else:
            for k in ("MXNET_COORDINATOR", "DMLC_PS_ROOT_URI",
                      "DMLC_PS_ROOT_PORT"):
                env.pop(k, None)
        self.stop()
        _logger().warning(
            "re-exec into generation %d as rank %d/%d: %s",
            spec["generation"], spec["rank"], spec["world"],
            " ".join([sys.executable] + sys.argv))
        sys.stdout.flush()
        sys.stderr.flush()
        # NOTE: execv runs no atexit handlers — telemetry dumps and engine
        # flushes of this incarnation are intentionally abandoned; the
        # resumed image re-creates them
        os.execv(sys.executable, [sys.executable] + sys.argv)


# ---------------------------------------------------------------------------
# process singleton
# ---------------------------------------------------------------------------

_runtime = None
_runtime_lock = analysis.make_lock("elastic.runtime")


def runtime():
    """The env-configured runtime singleton (None when the gate is off or
    the fleet is degenerate: no shared dir, or world <= 1)."""
    global _runtime
    if _runtime is not None:
        return _runtime
    if not elastic_enabled():
        return None
    root = str(getenv("MXNET_ELASTIC_DIR") or "")
    world = int(os.environ.get("MXNET_NUM_PROCESSES",
                               os.environ.get("DMLC_NUM_WORKER", "1")))
    if not root or world <= 1:
        return None
    rank = int(os.environ.get("MXNET_PROCESS_ID",
                              os.environ.get("DMLC_WORKER_ID", "0")))
    with _runtime_lock:
        if _runtime is None:
            _runtime = ElasticRuntime(root, rank, world)
    return _runtime


def ensure_started():
    """Start the heartbeat lease if the elastic gate is on (idempotent;
    called from `dist.init_process_group` / `launcher.initialize_from_env`
    so every rendezvous path arms the detector)."""
    rt = runtime()
    if rt is not None:
        rt.start()
    return rt


def active():
    """Whether collectives should route through the guard: a started
    runtime with real peers."""
    rt = _runtime
    return rt is not None and rt._heartbeater is not None and rt.world > 1


def guard(fn, desc="collective"):
    """Route one blocking collective through the runtime's lease guard
    (identity when the runtime is inactive)."""
    rt = _runtime
    if rt is None or rt._heartbeater is None:
        return fn()
    return rt.guard(fn, desc=desc)


def shrink_and_exec():
    """Survivor path after :class:`WorkerLostError`: run the shrink
    rendezvous, then re-exec into the new process group. Does not return
    (raises only if the rendezvous itself collapses)."""
    rt = runtime()
    if rt is None:
        raise WorkerLostError("shrink", [], cause=RuntimeError(
            "elastic runtime not configured (MXNET_ELASTIC/_DIR)"))
    rt.start()
    spec = rt.shrink()
    rt.exec_resume(spec)
