"""The analysis gate, both halves.

Static: every tpulint rule against synthetic fixtures (positive trip,
negative clean, disable-comment suppression — and a reasonless disable
being itself a finding), the CLI contract (`--strict` exits nonzero on
each rule's fixture, 0 on the real repo), and the env-var registry
cross-check in both drift directions.

Runtime: the MXNET_DEBUG_SYNC lock-order recorder — ABBA inversion with
both stacks, consistent order staying clean, reentrancy, blocking
hazards (direct and through the real `engine.wait_all` site), condition
wait bookkeeping, and the zero-overhead-when-off pin in a fresh
subprocess (locks must be PLAIN threading primitives, not wrappers).
"""
import os
import subprocess
import sys
import threading

import pytest

from mxnet_tpu import analysis
from mxnet_tpu.base import MXNetError

from tools.tpulint import SourceFile, lint_sources

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))


def lint_text(text, select=None, env_doc=None, path="fixture.py"):
    return lint_sources([SourceFile(path, text=text)], select=select,
                        env_doc=env_doc)


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# executable-cache
# ---------------------------------------------------------------------------

_EXEC_BAD = """
import functools, jax

@functools.lru_cache(maxsize=None)
def make_step(sig):
    return jax.jit(lambda x: x + 1)
"""

_EXEC_BAD_DICT = """
import jax
_memo = {}

def get(sig):
    if sig not in _memo:
        _memo[sig] = jax.jit(lambda x: x * 2)
    return _memo[sig]
"""

_EXEC_GOOD = """
from mxnet_tpu.compile_cache import CompileCache
import jax

_cache = CompileCache("step")

def make_step(sig):
    return _cache.get_or_build(sig, lambda: jax.jit(lambda x: x + 1))
"""

_EXEC_LRU_NO_JIT = """
import functools

@functools.lru_cache(maxsize=None)
def parse_spec(s):
    return tuple(s.split(","))
"""


def test_executable_cache_positive():
    assert rules_of(lint_text(_EXEC_BAD, {"executable-cache"})) \
        == ["executable-cache"]
    assert rules_of(lint_text(_EXEC_BAD_DICT, {"executable-cache"})) \
        == ["executable-cache"]


def test_executable_cache_negative():
    assert lint_text(_EXEC_GOOD, {"executable-cache"}) == []
    # lru_cache over plain data is fine — only executables must be named
    assert lint_text(_EXEC_LRU_NO_JIT, {"executable-cache"}) == []


def test_executable_cache_catches_custom_vjp_factory():
    # the pallas_attention shape this PR migrated: lru_cache around a
    # custom_vjp-decorated closure (a reference, not a call)
    src = """
import functools, jax

@functools.lru_cache(maxsize=None)
def make(scale):
    @jax.custom_vjp
    def f(x):
        return x * scale
    return f
"""
    assert rules_of(lint_text(src, {"executable-cache"})) \
        == ["executable-cache"]


def test_disable_comment_requires_reason():
    ok = _EXEC_BAD.replace(
        "@functools.lru_cache(maxsize=None)",
        "@functools.lru_cache(maxsize=None)  "
        "# tpulint: disable=executable-cache (perf experiment, PR pending)")
    assert lint_text(ok, {"executable-cache"}) == []
    bare = _EXEC_BAD.replace(
        "@functools.lru_cache(maxsize=None)",
        "@functools.lru_cache(maxsize=None)  "
        "# tpulint: disable=executable-cache")
    got = rules_of(lint_text(bare, {"executable-cache"}))
    # the finding survives AND the reasonless disable is its own finding
    assert sorted(got) == ["bad-disable", "executable-cache"]


# ---------------------------------------------------------------------------
# cache-memory-tracking
# ---------------------------------------------------------------------------

_TRACK_BAD = """
from mxnet_tpu.compile_cache import CompileCache
_c = CompileCache("ops", maxsize=1024)
"""

_TRACK_BAD_ENV_SIZED = """
from mxnet_tpu.base import getenv
from mxnet_tpu.compile_cache import CompileCache
_c = CompileCache("ops", maxsize=int(getenv("MXNET_OP_CACHE_SIZE")))
"""

_TRACK_GOOD = """
from mxnet_tpu.compile_cache import CompileCache
_small = CompileCache("steps", maxsize=64)
_unbounded = CompileCache("executor")
_big = CompileCache("ops", maxsize=1024, track_memory=False)
"""


def test_cache_memory_tracking_positive():
    assert rules_of(lint_text(_TRACK_BAD, {"cache-memory-tracking"})) \
        == ["cache-memory-tracking"]
    # a size the linter cannot read (env-sized) counts as big
    assert rules_of(lint_text(_TRACK_BAD_ENV_SIZED,
                              {"cache-memory-tracking"})) \
        == ["cache-memory-tracking"]


def test_cache_memory_tracking_negative():
    # small bounded and unbounded caches keep per-entry memory tracking
    assert lint_text(_TRACK_GOOD, {"cache-memory-tracking"}) == []
    # a donating builder needs no cache flag: donated executables persist
    # in jax's on-disk cache like any other program
    donating = """
import jax

def step_fn(cache, sig):
    def build():
        return jax.jit(lambda w, g: w - g, donate_argnums=(0,))
    return cache.get_or_build(sig, build)
"""
    assert lint_text(donating, {"cache-memory-tracking"}) == []


# ---------------------------------------------------------------------------
# donation-aliasing: donate sites resolve to an hlolint contract row
# ---------------------------------------------------------------------------

_ALIAS_STRAY = """
import jax

step = jax.jit(lambda w, g: w - g, donate_argnums=(0,))
"""

_ALIAS_NO_ROW = """
import jax
from mxnet_tpu.compile_cache import CompileCache

_cache = CompileCache("no-such-contract-row")

def run(sig):
    def build():
        return jax.jit(lambda w: w * 2, donate_argnums=(0,))
    return _cache.get_or_build(sig, build)
"""

_ALIAS_BAD_TAG = """
import jax

def run(cache, sig):
    def build():
        return jax.jit(lambda w: w * 2, donate_argnums=(0,))
    return cache.get_or_build(sig, build,
                              audit="no-such-contract-row")
"""

_ALIAS_UNRESOLVABLE = """
import jax

def run(cache, sig):
    def build():
        return jax.jit(lambda w: w * 2, donate_argnums=(0,))
    return cache.get_or_build(sig, build)
"""

_ALIAS_GOOD_TAG = """
import jax

def run(cache, sig):
    def build():
        return jax.jit(lambda w: w * 2, donate_argnums=(0,))
    return cache.get_or_build(sig, build,
                              audit="zero1")
"""

_ALIAS_GOOD_NAME = """
import jax
from mxnet_tpu.compile_cache import CompileCache

_cache = CompileCache("generation")

def run(sig):
    def build():
        return jax.jit(lambda w: w * 2, donate_argnums=(0,))
    return _cache.get_or_build(sig, build)
"""


def test_donation_aliasing_stray_donate_outside_builder():
    got = lint_text(_ALIAS_STRAY, {"donation-aliasing"})
    assert rules_of(got) == ["donation-aliasing"]
    assert "outside" in got[0].message


def test_donation_aliasing_missing_contract_row():
    got = lint_text(_ALIAS_NO_ROW, {"donation-aliasing"})
    assert rules_of(got) == ["donation-aliasing"]
    assert "no contract row" in got[0].message


def test_donation_aliasing_bad_audit_literal():
    got = lint_text(_ALIAS_BAD_TAG, {"donation-aliasing"})
    assert rules_of(got) == ["donation-aliasing"]
    assert "names no contract row" in got[0].message


def test_donation_aliasing_unresolvable_cache_requires_tag():
    got = lint_text(_ALIAS_UNRESOLVABLE, {"donation-aliasing"})
    assert rules_of(got) == ["donation-aliasing"]
    assert 'audit="<row>"' in got[0].message


def test_donation_aliasing_negative():
    assert lint_text(_ALIAS_GOOD_TAG, {"donation-aliasing"}) == []
    assert lint_text(_ALIAS_GOOD_NAME, {"donation-aliasing"}) == []
    # a dynamic audit expression (the executor's composition dispatch)
    # is sanctioned — the runtime gate audits the real tag
    dynamic = _ALIAS_GOOD_TAG.replace('audit="zero1"', "audit=tag")
    assert lint_text(dynamic, {"donation-aliasing"}) == []
    # non-donating builders never trip the rule, wherever they compile
    clean = _ALIAS_UNRESOLVABLE.replace(", donate_argnums=(0,)", "")
    assert lint_text(clean, {"donation-aliasing"}) == []


def test_donation_aliasing_disable_escape_hatch():
    suppressed = _ALIAS_STRAY.replace(
        "donate_argnums=(0,))",
        "donate_argnums=(0,))  "
        "# tpulint: disable=donation-aliasing (bench-local scratch)")
    assert lint_text(suppressed, {"donation-aliasing"}) == []


# ---------------------------------------------------------------------------
# gate-discipline
# ---------------------------------------------------------------------------

_GATE_BAD_THREAD = """
import threading

def _loop():
    pass

_t = threading.Thread(target=_loop, daemon=True)
_t.start()
"""

_GATE_BAD_ENV = """
import os
DEBUG = os.environ.get("MYPKG_DEBUG", "0") == "1"
"""

_GATE_BAD_DEVICE = """
import jax
NDEV = len(jax.devices())
"""

_GATE_GOOD = """
import os, threading
from mxnet_tpu.base import getenv, register_env

register_env("MXNET_SOMETHING", False, "doc")
_enabled = bool(getenv("MXNET_SOMETHING"))   # the sanctioned gate read

def enable():
    t = threading.Thread(target=lambda: None, daemon=True)
    t.start()
    return os.environ.get("MYPKG_DEBUG")     # lazy, inside a function

if __name__ == "__main__":
    print(os.environ.get("MYPKG_DEBUG"))     # script entry is exempt
"""


def test_gate_discipline_positive():
    got = rules_of(lint_text(_GATE_BAD_THREAD, {"gate-discipline"}))
    assert got == ["gate-discipline", "gate-discipline"]  # ctor + start
    assert rules_of(lint_text(_GATE_BAD_ENV, {"gate-discipline"})) \
        == ["gate-discipline"]
    assert rules_of(lint_text(_GATE_BAD_DEVICE, {"gate-discipline"})) \
        == ["gate-discipline"]


def test_gate_discipline_negative():
    assert lint_text(_GATE_GOOD, {"gate-discipline"}) == []


def test_gate_discipline_statement_span_disable():
    # one reasoned disable anywhere in a multi-line statement covers it
    src = """
import os
FLAG = (os.environ.get("A", "")  # tpulint: disable=gate-discipline (script-entry env probe)
        or os.environ.get("B", ""))
"""
    assert lint_text(src, {"gate-discipline"}) == []


# ---------------------------------------------------------------------------
# tracer-hygiene
# ---------------------------------------------------------------------------

_TRACER_BAD_DECORATED = """
import time, jax

@jax.jit
def step(x):
    t0 = time.time()
    return x + t0
"""

_TRACER_BAD_PASSED = """
import os, jax

def body(x):
    if os.environ.get("MXNET_FAST"):
        return x * 2
    return x

fn = jax.jit(body)
"""

_TRACER_GOOD = """
import time, jax

def host_step(x):
    t0 = time.time()          # not traced — fine
    return fn(x), time.time() - t0

@jax.jit
def fn(x):
    return x * 2
"""


def test_tracer_hygiene_positive():
    assert rules_of(lint_text(_TRACER_BAD_DECORATED, {"tracer-hygiene"})) \
        == ["tracer-hygiene"]
    assert rules_of(lint_text(_TRACER_BAD_PASSED, {"tracer-hygiene"})) \
        == ["tracer-hygiene"]


def test_tracer_hygiene_negative():
    assert lint_text(_TRACER_GOOD, {"tracer-hygiene"}) == []


def test_tracer_hygiene_np_random():
    src = """
import numpy as np
import jax

def init(shape):
    return np.random.randn(*shape)   # host init — fine, not traced

def body(x):
    return x + np.random.randn()     # traced — baked-in constant

fn = jax.jit(body)
"""
    got = lint_text(src, {"tracer-hygiene"})
    assert rules_of(got) == ["tracer-hygiene"]
    assert "body" in got[0].message


# ---------------------------------------------------------------------------
# env-var-registry
# ---------------------------------------------------------------------------


def test_env_registry_both_directions(tmp_path):
    doc = tmp_path / "env_var.md"
    doc.write_text("| `MXNET_DOCUMENTED` | 0 | fine |\n"
                   "| `MXNET_STALE_ROW` | 0 | never read |\n")
    src = """
from mxnet_tpu.base import getenv
A = getenv("MXNET_DOCUMENTED")

def f():
    return getenv("MXNET_UNDOCUMENTED")
"""
    got = lint_sources([SourceFile("m.py", text=src)],
                       env_doc=str(doc), select={"env-var-registry"})
    msgs = sorted(f.message for f in got)
    assert len(got) == 2
    assert "MXNET_UNDOCUMENTED" in msgs[0] or "MXNET_UNDOCUMENTED" in msgs[1]
    assert any("MXNET_STALE_ROW" in m for m in msgs)


def test_env_registry_repo_is_clean():
    """The acceptance bar: the real tree + real doc table agree (this PR
    closed the MXNET_PALLAS_*/MXNET_UPDATE_AGGREGATION_SIZE drift)."""
    from tools.tpulint import lint_paths

    # same scan set as the ci/run.sh gate — the doc-coverage direction
    # needs tools/ (it reads the probe/test-seed knobs)
    findings = lint_paths(
        [os.path.join(REPO, "mxnet_tpu"), os.path.join(REPO, "tools")],
        env_doc=os.path.join(REPO, "docs", "faq", "env_var.md"),
        select={"env-var-registry"})
    assert findings == [], "\n".join(map(str, findings))


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


def _run_cli(args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.tpulint", *args],
                          capture_output=True, text=True, cwd=cwd)


def test_cli_nonzero_on_each_rule_fixture(tmp_path):
    fixtures = {
        "executable-cache": _EXEC_BAD,
        "cache-memory-tracking": _TRACK_BAD,
        "gate-discipline": _GATE_BAD_THREAD,
        "tracer-hygiene": _TRACER_BAD_DECORATED,
    }
    for rule, src in fixtures.items():
        p = tmp_path / f"{rule.replace('-', '_')}.py"
        p.write_text(src)
        r = _run_cli([str(p), "--strict", "--env-doc", "none",
                      "--select", rule])
        assert r.returncode == 1, (rule, r.stdout, r.stderr)
        assert rule in r.stdout
    # env-var-registry through the CLI too: undocumented read -> exit 1
    doc = tmp_path / "env_var.md"
    doc.write_text("| `MXNET_KNOWN` | 0 | fine |\n")
    p = tmp_path / "env_registry.py"
    p.write_text("from mxnet_tpu.base import getenv\n"
                 "A = getenv('MXNET_KNOWN')\n\n"
                 "def f():\n    return getenv('MXNET_MYSTERY_KNOB')\n")
    r = _run_cli([str(p), "--strict", "--env-doc", str(doc),
                  "--select", "env-var-registry"])
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "MXNET_MYSTERY_KNOB" in r.stdout


@pytest.mark.slow
def test_cli_repo_gate_is_clean():
    """`python -m tools.tpulint mxnet_tpu tools --strict` exits
    0 — every pre-existing violation is fixed or carries a reasoned
    disable (the ci/run.sh blocking gate)."""
    r = _run_cli(["mxnet_tpu", "tools", "--strict"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


# ---------------------------------------------------------------------------
# runtime lock-order recorder
# ---------------------------------------------------------------------------


@pytest.fixture
def sync_debug():
    was = analysis._enabled
    analysis.enable()
    analysis.reset()
    yield analysis
    analysis.enable(was)
    analysis.reset()


def _in_thread(fn):
    t = threading.Thread(target=fn, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()


def test_lock_order_abba_inversion_reports_both_stacks(sync_debug):
    a = analysis.make_lock("test.A")
    b = analysis.make_lock("test.B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    _in_thread(ab)
    assert analysis.clean()          # one order alone is fine
    _in_thread(ba)
    rep = analysis.report()
    assert len(rep["inversions"]) == 1
    inv = rep["inversions"][0]
    assert {inv["held"], inv["acquiring"]} == {"test.A", "test.B"}
    # both stacks: the inverting acquisition's AND the first-seen
    # opposite ordering's — the postmortem needs both sides
    assert inv["held_stack"] and inv["acquire_stack"] \
        and inv["opposite_stack"]
    assert any("test_tpulint" in s for s in inv["acquire_stack"])
    with pytest.raises(MXNetError, match="INVERSION"):
        analysis.assert_clean()


def test_lock_order_consistent_order_stays_clean(sync_debug):
    a = analysis.make_lock("test.A")
    b = analysis.make_lock("test.B")

    def a_then_b():
        with a:
            with b:
                pass

    for _ in range(3):
        _in_thread(a_then_b)
    rep = analysis.report()
    assert rep["inversions"] == [] and rep["hazards"] == []
    assert ("test.A", "test.B", 3) in rep["edges"]


def test_lock_order_transitive_cycle(sync_debug):
    # A->B and B->C established, then C->A closes the 3-cycle
    a, b, c = (analysis.make_lock(f"test.{n}") for n in "ABC")

    def chain(x, y):
        with x:
            with y:
                pass

    _in_thread(lambda: chain(a, b))
    _in_thread(lambda: chain(b, c))
    assert analysis.clean()
    _in_thread(lambda: chain(c, a))
    assert not analysis.clean()


def test_rlock_reentrant_acquire_is_not_an_edge(sync_debug):
    r = analysis.make_rlock("test.R")
    with r:
        with r:
            pass
    rep = analysis.report()
    assert rep["edges"] == [] and rep["inversions"] == []


def test_blocking_hazard_held_across_flush(sync_debug):
    lk = analysis.make_lock("test.holder")
    own = analysis.make_rlock("test.own")
    with lk:
        with own:
            # the lazy-flush shape: the graph's own lock is exempt, any
            # OTHER held lock is the hazard
            analysis.check_blocking("lazy.flush", exempt=(own,))
    rep = analysis.report()
    assert len(rep["hazards"]) == 1
    haz = rep["hazards"][0]
    assert haz["kind"] == "lazy.flush" and haz["held"] == ["test.holder"]
    assert haz["blocking_stack"] and haz["held_stacks"][0]
    with pytest.raises(MXNetError, match="BLOCKING HAZARD"):
        analysis.assert_clean()


def test_blocking_hazard_through_real_wait_all(sync_debug):
    """engine.wait_all is a real instrumented blocking site: holding a
    tracked lock across it is recorded; calling it lock-free is not."""
    from mxnet_tpu import engine

    engine.wait_all()
    assert analysis.clean()
    lk = analysis.make_lock("test.held_over_drain")
    with lk:
        engine.wait_all()
    rep = analysis.report()
    assert [h["kind"] for h in rep["hazards"]] == ["engine.wait_all"]


def test_no_hazard_when_nothing_held(sync_debug):
    analysis.check_blocking("collective.barrier")
    assert analysis.clean()


def test_condition_wait_releases_bookkeeping(sync_debug):
    cond = analysis.make_condition("test.cond")
    hit = []

    def waiter():
        with cond:
            cond.wait(timeout=5)
            # while re-armed inside the condition, a blocking check must
            # see the condition lock held
            assert analysis.check_blocking("lazy.flush") is not None
            hit.append(1)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    import time

    time.sleep(0.2)
    # waiter is parked in wait(): it released the condition lock, so this
    # acquire succeeds — and holding it IS a blocking hazard, correctly
    with cond:
        assert analysis.check_blocking("collective.barrier") is not None
        cond.notify()
    t.join(timeout=10)
    assert not t.is_alive() and hit
    rep = analysis.report()
    # both deliberate hazards, nothing else: wait() left no stale held
    # entries behind (a desync would surface as extra held locks here)
    assert sorted(h["kind"] for h in rep["hazards"]) \
        == ["collective.barrier", "lazy.flush"]
    assert rep["inversions"] == []
    assert all(h["held"] == ["test.cond"] for h in rep["hazards"])
    # with everything released, a fresh check records nothing
    assert analysis.check_blocking("lazy.flush") is None


def test_telemetry_counters_increment(sync_debug):
    from mxnet_tpu import telemetry

    before = telemetry.counter("analysis.lock_inversions").value
    a = analysis.make_lock("test.TA")
    b = analysis.make_lock("test.TB")
    _in_thread(lambda: (a.acquire(), b.acquire(),
                        b.release(), a.release()))
    _in_thread(lambda: (b.acquire(), a.acquire(),
                        a.release(), b.release()))
    assert telemetry.counter("analysis.lock_inversions").value \
        == before + 1


def test_zero_overhead_when_off_fresh_subprocess():
    """The PR 7/11 discipline, pinned: with MXNET_DEBUG_SYNC unset the
    factories return PLAIN threading primitives (not wrappers — zero
    per-acquire cost, not even a flag check) and the instrumented
    modules' locks are plain too."""
    env = {k: v for k, v in os.environ.items() if k != "MXNET_DEBUG_SYNC"}
    env["JAX_PLATFORMS"] = "cpu"
    code = """
import threading
from mxnet_tpu import analysis, engine
from mxnet_tpu.serving.generation.prefix_cache import RadixPrefixCache

assert not analysis.enabled()
plain_lock = type(threading.Lock())
plain_rlock = type(threading.RLock())
assert type(analysis.make_lock("x")) is plain_lock
assert type(analysis.make_rlock("x")) is plain_rlock
assert type(analysis.make_condition("x")._lock) is plain_rlock
assert type(engine._path_lock) is plain_lock
assert type(RadixPrefixCache()._lock) is plain_rlock
assert analysis.report()["locks"] == []
analysis.check_blocking("lazy.flush")        # no-op, records nothing
assert analysis.clean()
print("ZERO_OVERHEAD_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ZERO_OVERHEAD_OK" in r.stdout


def test_tracked_from_import_fresh_subprocess():
    """MXNET_DEBUG_SYNC=1 at process start tracks even the module-level
    locks created at import, and a driven serving path records real
    acquisition-order edges."""
    env = dict(os.environ, MXNET_DEBUG_SYNC="1", JAX_PLATFORMS="cpu")
    code = """
from mxnet_tpu import analysis, engine

assert analysis.enabled()
assert type(engine._path_lock).__name__ == "_TrackedLock"
with engine._path_lock:
    pass
rep = analysis.report()
assert "engine.path_vars" in rep["locks"], rep["locks"]
assert rep["inversions"] == [] and rep["hazards"] == []
print("TRACKED_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TRACKED_OK" in r.stdout


def test_same_name_instance_locks_no_false_inversion(sync_debug):
    """Distinct instances sharing a name (every Beacon is
    'health.beacon') must not self-invert when nested: order within a
    name class is unverifiable by name — the lockdep same-class trade."""
    a = analysis.make_lock("test.same")
    b = analysis.make_lock("test.same")
    with a:
        with b:
            pass
    rep = analysis.report()
    assert rep["inversions"] == [] and rep["edges"] == []
    # distinct names still detect through a same-named middle hop
    outer = analysis.make_lock("test.outer")
    inner = analysis.make_lock("test.inner")

    def oi():
        with outer:
            with a:
                with inner:
                    pass

    def io():
        with inner:
            with outer:
                pass

    _in_thread(oi)
    assert analysis.clean()
    _in_thread(io)
    assert not analysis.clean()


def test_tracked_locked_probe_works_on_rlock(sync_debug):
    """RLock has no .locked() before Python 3.13 — the tracked wrapper
    must stay drop-in on both lock kinds under the gate."""
    for mk in (analysis.make_lock, analysis.make_rlock):
        lk = mk("test.lockedprobe")
        assert lk.locked() is False
        got_it = threading.Event()
        let_go = threading.Event()

        def hold():
            with lk:
                got_it.set()
                let_go.wait(10)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        assert got_it.wait(10)
        # observed from ANOTHER thread a held lock reads True (the
        # owned-by-us RLock probe blind spot is documented; no caller
        # queries its own hold)
        assert lk.locked() is True
        let_go.set()
        t.join(timeout=10)
        assert not t.is_alive()
        assert lk.locked() is False


def test_gate_discipline_lambda_on_violation_line_not_suppressed():
    # a lambda sharing the line must not swallow the import-scope read
    src = """
import os
_CB = (lambda: 1, os.environ["MXNET_X"])
"""
    got = rules_of(lint_text(src, {"gate-discipline"}))
    assert got == ["gate-discipline"]


def test_gate_discipline_module_level_with_statement():
    # ast.withitem has no lineno — the checker must not crash, and the
    # header expression still counts as import-scope
    clean = """
import contextlib

with contextlib.suppress(Exception):
    VALUE = 1
"""
    assert lint_text(clean, {"gate-discipline"}) == []
    bad = """
import os, contextlib

with contextlib.suppress(Exception):
    FLAG = os.environ["MXNET_X"]
"""
    assert rules_of(lint_text(bad, {"gate-discipline"})) \
        == ["gate-discipline"]


def test_executable_cache_from_functools_import_cache():
    # `from functools import cache` (and aliases) must not evade the rule
    src = """
from functools import cache
import jax

@cache
def make_step(sig):
    return jax.jit(lambda x: x + 1)
"""
    assert rules_of(lint_text(src, {"executable-cache"})) \
        == ["executable-cache"]
    aliased = src.replace("import cache", "import cache as memo") \
                 .replace("@cache", "@memo")
    assert rules_of(lint_text(aliased, {"executable-cache"})) \
        == ["executable-cache"]
    # a user-defined decorator named cache is NOT flagged without import
    local = """
import jax

def cache(f):
    return f

@cache
def make_step(sig):
    return jax.jit(lambda x: x + 1)
"""
    assert lint_text(local, {"executable-cache"}) == []


def test_gate_discipline_class_body_and_decorators():
    """Class bodies and def decorators/defaults execute at import — the
    gate must see them (a config-class env read is the classic evasion)."""
    class_body = """
import os, threading

class Cfg:
    DEBUG = os.environ.get("MXNET_DEBUG_X")
"""
    assert rules_of(lint_text(class_body, {"gate-discipline"})) \
        == ["gate-discipline"]
    decorator = """
import os

def reg(v):
    def deco(f):
        return f
    return deco

@reg(os.environ["MXNET_Y"])
def handler():
    pass
"""
    assert rules_of(lint_text(decorator, {"gate-discipline"})) \
        == ["gate-discipline"]
    default_arg = """
import os

def f(flag=os.environ.get("MXNET_Z")):
    return flag
"""
    assert rules_of(lint_text(default_arg, {"gate-discipline"})) \
        == ["gate-discipline"]
    # method BODIES still run later — only the class-level statements count
    method_ok = """
import os

class Svc:
    def read(self):
        return os.environ.get("MXNET_OK")
"""
    assert lint_text(method_ok, {"gate-discipline"}) == []


def test_cli_rejects_unknown_select_rule(tmp_path):
    # a typo'd --select must error (exit 2), never pass vacuously clean
    p = tmp_path / "x.py"
    p.write_text("A = 1\n")
    r = _run_cli([str(p), "--strict", "--env-doc", "none",
                  "--select", "executble-cache"])
    assert r.returncode == 2, (r.returncode, r.stdout, r.stderr)
    assert "unknown rule" in r.stderr
