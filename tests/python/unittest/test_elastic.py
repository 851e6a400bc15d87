"""Elastic runtime (`parallel/elastic.py`, `MXNET_ELASTIC=1`): heartbeat
leases, worker-death detection inside collectives, shrink rendezvous, and
checkpoint resume.

Pins the PR's acceptance contract:

* **Detection** — a peer whose lease goes stale raises `WorkerLostError`
  from the guard within the grace window, whether the guarded collective
  is BLOCKED (a hung barrier — the failure mode PR 1 could only log) or
  FAILED (a gloo connection reset racing the lease expiry).
* **No false positives** — a slow-but-alive collective is never
  interrupted (the lease is the only unblock signal), and a collective
  failure with every lease fresh re-raises the original error after one
  grace window.
* **Shrink rendezvous** — concurrent survivors agree on membership, new
  contiguous ranks, and a coordinator published by the new rank 0.
* **Kill -> shrink -> resume** (slow, 2 REAL processes via tools/launch.py
  --restart-policy shrink): SIGKILL-ing worker 1 mid-epoch yields
  detection within MXNET_ELASTIC_GRACE_S, a 2 -> 1 shrink, re-exec, and a
  checkpoint resume whose final loss reaches the single-worker
  convergence bar (tests/dist/elastic_smoke.py).
"""
import os
import threading
import time

import pytest

from mxnet_tpu.parallel.elastic import ElasticRuntime, Heartbeater
from mxnet_tpu.resilience import WorkerLostError


def _rt(tmp_path, rank, world, hb=0.05, grace=0.4):
    return ElasticRuntime(str(tmp_path), rank, world, gen=0,
                          heartbeat_s=hb, grace_s=grace)


def _beat(tmp_path, rank, gen=0):
    """Write one fresh lease for ``rank`` (a fake peer)."""
    d = os.path.join(str(tmp_path), f"gen-{gen}")
    os.makedirs(d, exist_ok=True)
    Heartbeater(os.path.join(d, f"hb-{rank}"), 1.0).beat_once()


# ---------------------------------------------------------------------------
# leases + detection
# ---------------------------------------------------------------------------


def test_heartbeat_renews_and_peers_read_it(tmp_path):
    rt = _rt(tmp_path, 0, 2).start()
    try:
        _beat(tmp_path, 1)
        assert rt.lost_peers() == []
        rt.check()  # no raise
        # the lease file renews on its own
        p = rt._hb_path(0)
        t1 = open(p).read()
        time.sleep(0.15)
        assert open(p).read() != t1
    finally:
        rt.stop()


def test_stale_peer_detected(tmp_path):
    rt = _rt(tmp_path, 0, 2).start()
    try:
        _beat(tmp_path, 1)
        time.sleep(0.5)  # > grace without renewal
        assert rt.lost_peers() == [1]
        with pytest.raises(WorkerLostError) as ei:
            rt.check("barrier")
        assert ei.value.lost_ranks == (1,)
    finally:
        rt.stop()


def test_never_started_peer_detected(tmp_path):
    """A worker that died before its first beat must still be declared
    lost (age counts from this runtime's own start)."""
    rt = _rt(tmp_path, 0, 2).start()
    try:
        time.sleep(0.5)
        assert rt.lost_peers() == [1]
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# the collective guard
# ---------------------------------------------------------------------------


def test_guard_passthrough_result(tmp_path):
    rt = _rt(tmp_path, 0, 2).start()
    try:
        _beat(tmp_path, 1)
        assert rt.guard(lambda: 41 + 1) == 42
    finally:
        rt.stop()


def test_guard_unblocks_hung_collective(tmp_path):
    """The hung-barrier failure mode: the collective never returns, the
    peer's lease expires -> WorkerLostError within ~grace, caller thread
    free (the stuck daemon thread is abandoned)."""
    rt = _rt(tmp_path, 0, 2).start()
    try:
        _beat(tmp_path, 1)
        hang = threading.Event()
        t0 = time.monotonic()
        with pytest.raises(WorkerLostError):
            rt.guard(hang.wait, desc="barrier")  # blocks forever
        dt = time.monotonic() - t0
        assert dt < rt.grace_s + 2.0, f"detection took {dt:.1f}s"
        hang.set()
    finally:
        rt.stop()


def test_guard_failed_collective_with_dead_peer_chains(tmp_path):
    """A gloo 'connection reset' that races the lease expiry must come
    out as WorkerLostError with the original error chained."""
    rt = _rt(tmp_path, 0, 2).start()
    try:
        _beat(tmp_path, 1)
        time.sleep(0.2)  # lease ages but is still fresh (< 0.4 grace)...

        def boom():
            raise ValueError("connection reset by peer")

        with pytest.raises(WorkerLostError) as ei:
            rt.guard(boom)  # ...and goes stale inside the error's window
        assert isinstance(ei.value.cause, ValueError)
    finally:
        rt.stop()


def test_guard_failed_collective_all_alive_reraises(tmp_path):
    """A genuine collective failure with every lease fresh is NOT a
    worker death: after one grace window the original error re-raises."""
    rt = _rt(tmp_path, 0, 2, grace=0.3).start()
    stop = threading.Event()

    def keep_peer_alive():
        while not stop.is_set():
            _beat(tmp_path, 1)
            time.sleep(0.05)

    th = threading.Thread(target=keep_peer_alive, daemon=True)
    th.start()
    try:
        with pytest.raises(ValueError, match="not a death"):
            rt.guard(lambda: (_ for _ in ()).throw(ValueError("not a death")))
    finally:
        stop.set()
        th.join(timeout=2)
        rt.stop()


def test_guard_slow_but_alive_never_interrupted(tmp_path):
    """Slowness is not death: a collective taking several grace windows
    completes normally while the peer keeps beating."""
    rt = _rt(tmp_path, 0, 2, grace=0.2).start()
    stop = threading.Event()

    def keep_peer_alive():
        while not stop.is_set():
            _beat(tmp_path, 1)
            time.sleep(0.05)

    th = threading.Thread(target=keep_peer_alive, daemon=True)
    th.start()
    try:
        assert rt.guard(lambda: (time.sleep(0.7), "done")[1]) == "done"
    finally:
        stop.set()
        th.join(timeout=2)
        rt.stop()


def test_guard_world_one_is_identity(tmp_path):
    rt = _rt(tmp_path, 0, 1)
    assert rt.guard(lambda: "solo") == "solo"


# ---------------------------------------------------------------------------
# shrink rendezvous
# ---------------------------------------------------------------------------


def test_shrink_membership_and_coordinator(tmp_path):
    """3 workers, rank 1 dies: ranks 0 and 2 rendezvous concurrently into
    world 2 with new contiguous ranks and one agreed coordinator."""
    rts = {r: _rt(tmp_path, r, 3).start() for r in (0, 2)}
    try:
        time.sleep(0.5)  # rank 1 never beats -> lost
        for rt in rts.values():
            assert rt.lost_peers() == [1]
        specs = {}
        errs = []

        def run(r):
            try:
                specs[r] = rts[r].shrink()
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append((r, e))

        ths = [threading.Thread(target=run, args=(r,)) for r in rts]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=10)
        assert not errs, errs
        assert specs[0]["world"] == specs[2]["world"] == 2
        assert specs[0]["generation"] == specs[2]["generation"] == 1
        assert specs[0]["rank"] == 0 and specs[2]["rank"] == 1
        assert specs[0]["coordinator"] == specs[2]["coordinator"]
        assert specs[0]["coordinator"].startswith("127.0.0.1:")
    finally:
        for rt in rts.values():
            rt.stop()


@pytest.mark.parametrize("decider_writes", [True, False],
                         ids=["written_late", "never_written"])
def test_shrink_reader_meets_an_empty_members_file(tmp_path, decider_writes):
    """A survivor that opens `members` between the decider's O_EXCL create
    and its write reads nothing. That is no decision yet: it waits for the
    list (and adopts it) instead of failing as "published without rank 2";
    a decider that died there is reported after the grace, not waited for
    for ever."""
    rt = _rt(tmp_path, 2, 3).start()
    gen1 = os.path.join(str(tmp_path), "gen-1")
    os.makedirs(gen1)
    stop = threading.Event()
    shrink_began = []

    def fake_rank0():
        """Alive, joined, has created `members`; writes it 0.15 s into the
        survivor's rendezvous, or never."""
        open(os.path.join(gen1, "join-0"), "w").close()
        open(os.path.join(gen1, "members"), "w").close()
        while not stop.is_set():
            _beat(tmp_path, 0)
            if decider_writes and shrink_began \
                    and time.monotonic() > shrink_began[0] + 0.15:
                with open(os.path.join(gen1, "members"), "w") as f:
                    f.write("0,2")
                with open(os.path.join(gen1, "coordinator"), "w") as f:
                    f.write("127.0.0.1:1")
                return
            time.sleep(0.02)

    th = threading.Thread(target=fake_rank0, daemon=True)
    th.start()
    try:
        time.sleep(0.5)  # rank 1 never beats -> lost
        assert rt.lost_peers() == [1]
        shrink_began.append(time.monotonic())
        if decider_writes:
            assert rt.shrink() == {"generation": 1, "world": 2, "rank": 1,
                                   "coordinator": "127.0.0.1:1"}
        else:
            t0 = time.monotonic()
            with pytest.raises(WorkerLostError, match="without rank 2"):
                rt.shrink()
            assert time.monotonic() - t0 < 5
    finally:
        stop.set()
        th.join(timeout=2)
        rt.stop()


def test_shrink_to_one_has_no_coordinator(tmp_path):
    rt = _rt(tmp_path, 0, 2).start()
    try:
        time.sleep(0.5)
        spec = rt.shrink()
        assert spec == {"generation": 1, "world": 1, "rank": 0,
                        "coordinator": None}
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# the real 2-process kill -> shrink -> resume smoke
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_kill_shrink_resume_smoke(tmp_path):
    """SIGKILL one of two REAL dist workers mid-epoch: the survivor must
    detect within grace (no hung barrier), shrink 2 -> 1, re-exec, resume
    from the latest good checkpoint, and converge (loss bar asserted in
    the smoke script)."""
    import subprocess
    import sys

    repo = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..", ".."))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # workers choose their own platform
    env["ELASTIC_SMOKE_DIR"] = str(tmp_path / "ckpt")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--restart-policy", "shrink", "--timeout", "600",
         "--env", "MXNET_ELASTIC_GRACE_S=6",
         "--env", "MXNET_ELASTIC_HEARTBEAT_S=0.25",
         sys.executable,
         os.path.join(repo, "tests", "dist", "elastic_smoke.py")],
        env=env, cwd=repo, capture_output=True, timeout=660)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, f"launcher failed rc={proc.returncode}\n{out[-8000:]}"
    assert "SIGKILL self" in out, out[-8000:]
    assert "lost during" in out, out[-8000:]
    assert "shrink rendezvous complete" in out, out[-8000:]
    assert "resumed generation 1" in out, out[-8000:]
    assert "ELASTIC SMOKE PASSED: shrink + checkpoint resume converged" \
        in out, out[-8000:]
