"""hlolint — compiled-program contract auditor + steady-state recompile
blamer (PR 15).

Covers the whole pass end to end:

* the StableHLO/HLO parsers (collective inventory with byte volumes,
  ``input_output_alias`` ground truth, declared-donation markers with
  per-arg byte sizing, ``mhlo.num_partitions``);
* :func:`mxnet_tpu.analysis.program_summary` on real compiled programs —
  a donated elementwise update whose donation ALIASES, and a sharded
  multi-device program whose collective inventory and input residency
  are visible;
* the contract audit (``tools/hlolint``): clean entries pass, and a
  deliberately broken fixture fails the gate naming the executable AND
  the offending collective (the acceptance criterion), donation floors,
  the full-bucket all-reduce ban, replicated-fraction residency;
* the ``MXNET_HLOLINT_DUMP`` ledger/dump hook (per-tag caps, atexit dump
  in a fresh subprocess, CLI ``check`` over the produced dump);
* the steady-state recompile blamer: a miss on a warmed cache produces
  exactly ONE ``compile_blame`` journal event naming the changed key
  axis (shape(batch) on the serving bucket ladder, dtype, hyperparam,
  sharding), and ZERO events over warmed steady-state loops;
* the jax mixed-sharded-concat miscompile CANARY: the minimal repro of
  the jax-0.4.x SPMD partitioner bug that zero1's replicate-first pack
  works around — pinned so a jax upgrade can neither silently re-break
  the workaround nor fossilize it after the fix lands upstream.
"""
import contextlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis, compile_cache, health, telemetry
from mxnet_tpu.compile_cache import CompileCache

TOOLS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "..", "..")
sys.path.insert(0, TOOLS_DIR)

from tools import hlolint  # noqa: E402
from tools.hlolint import Contract, audit, contracts  # noqa: E402


@contextlib.contextmanager
def _health_journal():
    """Flip the health journal on WITHOUT health.enable() — enable()
    starts the process-wide watchdog daemon, which races other suites'
    deterministic beacon sweeps (the test_generation_scale precedent)."""
    prev = health._enabled
    health._enabled = True
    try:
        yield
    finally:
        health._enabled = prev


@contextlib.contextmanager
def _env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _blame_events():
    return health.events(kind="compile_blame")


# ---------------------------------------------------------------------------
# parsers (pure text — no jax)
# ---------------------------------------------------------------------------

_HLO_FIXTURE = textwrap.dedent("""\
    HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {2}: (3, {}, must-alias) }, entry_computation_layout={(f32[64,8]{1,0})->f32[64,8]{1,0}}
    ENTRY %main.14_spmd (param: f32[64,8]) -> f32[64,8] {
      %ag = f32[64,8]{1,0} all-gather(f32[16,8]{1,0} %x), channel_id=2, replica_groups=[1,4]<=[4], dimensions={0}
      %ar = f32[] all-reduce(f32[] %y), channel_id=1, replica_groups=[1,4]<=[4]
      %ars = (f32[32]{0}, f32[32]{0}) all-reduce-start(f32[32]{0} %z), channel_id=3
      %ard = f32[32]{0} all-reduce-done((f32[32]{0}, f32[32]{0}) %ars)
      %rs = f32[16,8]{1,0} reduce-scatter(f32[64,8]{1,0} %w), channel_id=4, dimensions={0}
    }
""")


def test_parse_collectives_counts_and_bytes():
    kinds, lines = analysis.parse_collectives(_HLO_FIXTURE)
    assert kinds["all-gather"] == {"count": 1, "bytes": 64 * 8 * 4}
    # scalar all-reduce (4B) + the async -start form counted ONCE via its
    # tuple result (2 x 32 floats); -done contributes nothing
    assert kinds["all-reduce"]["count"] == 2
    assert kinds["all-reduce"]["bytes"] == 4 + 2 * 32 * 4
    assert kinds["reduce-scatter"] == {"count": 1, "bytes": 16 * 8 * 4}
    assert len(lines) == 4


def test_parse_io_aliases_header():
    aliases = analysis.parse_io_aliases(_HLO_FIXTURE)
    assert {a["param"] for a in aliases} == {0, 3}
    kinds = {a["param"]: a["kind"] for a in aliases}
    assert kinds[0] == "may-alias" and kinds[3] == "must-alias"


_STABLEHLO_FIXTURE = textwrap.dedent("""\
    module @jit_step attributes {mhlo.num_partitions = 4 : i32, mhlo.num_replicas = 1 : i32} {
      func.func public @main(%arg0: tensor<8x4xf32> {tf.aliasing_output = 0 : i32}, %arg1: tensor<32xf32> {jax.buffer_donor = true}, %arg2: tensor<8x4xf32>, %arg3: tensor<f32>) -> (tensor<8x4xf32> {jax.result_info = ""}) {
        %0 = stablehlo.add %arg0, %arg2 : tensor<8x4xf32>
        return %0 : tensor<8x4xf32>
      }
    }
""")


def test_parse_donated_args_markers_and_bytes():
    donated = analysis.parse_donated_args(_STABLEHLO_FIXTURE)
    assert set(donated) == {0, 1}                      # arg2/arg3 unmarked
    assert donated[0] == {"output": 0, "bytes": 8 * 4 * 4}
    assert donated[1] == {"output": None, "bytes": 32 * 4}


def test_parse_donated_args_survives_sharding_attr():
    """A donated arg with an explicit layout carries `mhlo.sharding =
    "{devices=[4,1]<=[4]}"` in the SAME attr dict — nested braces inside
    the quoted value must not defeat the donation marker (they did:
    caught in review; the sharded programs are exactly the ones the
    audit protects)."""
    sig = (
        'func.func public @main(%arg0: tensor<8x4xf32> '
        '{mhlo.sharding = "{devices=[4,1]<=[4]}", '
        'tf.aliasing_output = 0 : i32}, '
        '%arg1: tensor<8x4xf32> '
        '{jax.buffer_donor = true, '
        'mhlo.sharding = "{devices=[4,1]<=[4]}"}, '
        '%arg2: tensor<8x4xf32> '
        '{mhlo.sharding = "{replicated}"}) -> (tensor<8x4xf32>) {\n'
        '  return %arg0 : tensor<8x4xf32>\n')
    donated = analysis.parse_donated_args("module @m {\n" + sig + "}\n")
    assert donated == {0: {"output": 0, "bytes": 128},
                       1: {"output": None, "bytes": 128}}


def test_program_summary_sharded_donation_is_visible():
    """End-to-end form of the same regression: an explicitly-sharded
    donated jit must still show its donation in the summary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    shard = NamedSharding(mesh, P("dp"))
    fn = jax.jit(lambda w, g: w - 0.1 * g, donate_argnums=(0,),
                 in_shardings=(shard, shard), out_shardings=shard)
    avals = ((jax.ShapeDtypeStruct((4096,), jnp.float32),
              jax.ShapeDtypeStruct((4096,), jnp.float32)), {})
    s = analysis.program_summary(fn, avals)
    assert s["num_devices"] == 4
    assert s["donation"]["declared"] == [0]
    assert s["donation"]["unaliased"] == []
    assert {a["param"] for a in s["donation"]["aliased"]} == {0}


def test_parse_num_partitions():
    assert analysis.parse_num_partitions(_STABLEHLO_FIXTURE) == 4
    assert analysis.parse_num_partitions("module @m { }") == 1


def test_summarize_hlo_text_cross_references_declared_and_aliased():
    s = analysis.summarize_hlo_text(_STABLEHLO_FIXTURE, _HLO_FIXTURE)
    assert s["donation"]["declared"] == [0, 1]
    # param 0 aliased (alias header), param 1 did not -> unaliased
    assert s["donation"]["unaliased"] == [1]
    assert s["donation"]["declared_bytes"]["1"] == 128
    assert s["collective_bytes"] > 0


# ---------------------------------------------------------------------------
# program_summary on real compiled programs
# ---------------------------------------------------------------------------


def test_program_summary_donated_elementwise_aliases():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda w, g: w - 0.1 * g, donate_argnums=(0,))
    avals = ((jax.ShapeDtypeStruct((64, 64), jnp.float32),
              jax.ShapeDtypeStruct((64, 64), jnp.float32)), {})
    s = analysis.program_summary(fn, avals)
    assert s["num_devices"] == 1
    assert s["collectives"] == {}
    assert s["donation"]["declared"] == [0]
    assert s["donation"]["unaliased"] == []
    assert {a["param"] for a in s["donation"]["aliased"]} == {0}
    assert [r["bytes"] for r in s["inputs"]] == [64 * 64 * 4] * 2


def test_program_summary_sharded_collectives_and_residency():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    shard, repl = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())

    def f(x):
        y = jax.lax.with_sharding_constraint(x * 2.0, shard)
        return jax.lax.with_sharding_constraint(y, repl)

    fn = jax.jit(f, in_shardings=(shard,), out_shardings=repl)
    avals = ((jax.ShapeDtypeStruct((4096,), jnp.float32),), {})
    s = analysis.program_summary(fn, avals)
    assert s["num_devices"] == 4
    assert s["collectives"].get("all-gather", {}).get("count", 0) >= 1
    row = s["inputs"][0]
    assert row["replicated"] is False
    assert row["local_bytes"] == row["bytes"] // 4


# ---------------------------------------------------------------------------
# the contract audit
# ---------------------------------------------------------------------------


def _entry(tag, key="('fwd', (8, 8))", cache=None, **summary):
    base = {"collectives": {}, "collective_bytes": 0,
            "collective_lines": [],
            "donation": {"declared": [], "declared_bytes": {},
                         "aliased": [], "unaliased": []},
            "inputs": [], "num_devices": 1}
    base.update(summary)
    return {"cache": cache or tag, "tag": tag, "key": key, "summary": base}


def test_audit_clean_serving_entry_passes():
    findings = audit([_entry("serving")], contracts.CONTRACTS,
                     require=["serving"])
    assert findings == []


def test_audit_required_row_with_no_entries_fails():
    findings = audit([], contracts.CONTRACTS, require=["serving"])
    assert len(findings) == 1
    assert "nothing to audit" in findings[0].message


def test_audit_flags_single_device_collective_named():
    """The deliberately-broken-contract fixture of the acceptance
    criteria: a generation (tp=1) program that grew an all-gather must
    fail the gate with the executable's key AND the collective named."""
    bad = _entry("generation", key="('decode', 3, 48)",
                 collectives={"all-gather": {"count": 1, "bytes": 4096}},
                 donation={"declared": [0], "declared_bytes": {"0": 1 << 20},
                           "aliased": [{"output": "0", "param": 0,
                                        "kind": "may-alias"}],
                           "unaliased": []})
    findings = audit([bad], contracts.CONTRACTS, require=["generation"])
    assert len(findings) == 1
    f = findings[0]
    assert "all-gather" in f.message
    assert f.key == "('decode', 3, 48)"


def test_audit_flags_large_unaliased_donation_but_floors_small():
    don = {"declared": [0, 1],
           "declared_bytes": {"0": 1 << 20, "1": 128},
           "aliased": [], "unaliased": [0, 1]}
    reg = {"t": Contract(donation="required")}
    findings = audit([_entry("t", donation=dict(don), num_devices=2)], reg)
    # the 1MiB failed donation fires; the 128B one is floored away; plus
    # the row-level "nothing aliased" finding
    msgs = " | ".join(f.message for f in findings)
    assert "[0]" in msgs and "[0, 1]" not in msgs
    assert "none of the" in msgs
    don_small = {"declared": [1], "declared_bytes": {"1": 128},
                 "aliased": [{"output": "", "param": 9, "kind": "may-alias"}],
                 "unaliased": [1]}
    assert audit([_entry("t", donation=don_small, num_devices=2)], reg) == []


def test_audit_flags_full_bucket_allreduce():
    e = _entry("zero1",
               collectives={"all-reduce": {"count": 1, "bytes": 1 << 20},
                            "all-gather": {"count": 1, "bytes": 1 << 20}},
               num_devices=4,
               inputs=[{"shape": (262144,), "dtype": "float32",
                        "bytes": 1 << 20, "replicated": False,
                        "local_bytes": 1 << 18}],
               donation={"declared": [0], "declared_bytes": {"0": 1 << 20},
                         "aliased": [{"output": "", "param": 0,
                                      "kind": "may-alias"}],
                         "unaliased": []})
    findings = audit([e], contracts.CONTRACTS, require=["zero1"])
    assert any("full-bucket" in f.message for f in findings)
    # halving the all-reduce payload (a per-shard sum) passes
    e2 = json.loads(json.dumps(e))
    e2["summary"]["collectives"]["all-reduce"]["bytes"] = 1 << 18
    assert audit([e2], contracts.CONTRACTS, require=["zero1"]) == []


def test_audit_replicated_fraction_cap_and_dp_only_exemption():
    reg = {"t": Contract(max_replicated_fraction=0.5)}
    repl_row = {"shape": (4096,), "dtype": "float32", "bytes": 16384,
                "replicated": True, "local_bytes": 16384}
    shard_row = {"shape": (1024,), "dtype": "float32", "bytes": 4096,
                 "replicated": False, "local_bytes": 1024}
    bad = _entry("t", num_devices=4, inputs=[repl_row, shard_row])
    assert any("replicated" in f.message for f in audit([bad], reg))
    # dp-only: nothing large is sharded -> the cap does not bind
    dp_only = _entry("t", num_devices=4, inputs=[repl_row])
    assert audit([dp_only], reg) == []


def test_registry_has_every_core_row():
    for tag in ("spmd", "zero1", "pipeline", "serving", "generation",
                "lazy"):
        assert tag in contracts.CONTRACTS, tag
    # serving/lazy never donate; the sharded planes must
    assert contracts.CONTRACTS["serving"].donation == "forbidden"
    assert contracts.CONTRACTS["lazy"].donation == "forbidden"
    for tag in ("spmd", "zero1", "pipeline", "generation"):
        assert contracts.CONTRACTS[tag].donation == "required", tag


def test_cli_check_fails_broken_fixture_and_explains(tmp_path, capsys):
    dump = {"pid": 1, "entries": [
        _entry("generation", key="('decode', 3, 48)",
               collectives={"all-gather": {"count": 2, "bytes": 8192}},
               collective_lines=["%ag = f32[64,8]{1,0} all-gather(...)"],
               donation={"declared": [0],
                         "declared_bytes": {"0": 1 << 20},
                         "aliased": [{"output": "0", "param": 0,
                                      "kind": "may-alias"}],
                         "unaliased": []})]}
    path = tmp_path / "hlolint-1.json"
    path.write_text(json.dumps(dump))
    from tools.hlolint.__main__ import main

    rc = main(["check", str(path), "--require", "generation", "--strict",
               "--explain"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "('decode', 3, 48)" in out
    assert "all-gather" in out
    assert "FAIL" in out and "all-gather: 2 op(s)" in out  # the inventory


def test_cli_show_prints_inventories(tmp_path, capsys):
    path = tmp_path / "hlolint-2.json"
    path.write_text(json.dumps({"pid": 1, "entries": [_entry("serving")]}))
    from tools.hlolint.__main__ import main

    assert main(["show", str(path)]) == 0
    assert "executable [serving]" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# the MXNET_HLOLINT_DUMP ledger + exit hook
# ---------------------------------------------------------------------------


def test_audit_ledger_records_caps_and_dumps(tmp_path):
    tag = "hlolint-test-tag"
    with _env(MXNET_HLOLINT_DUMP=str(tmp_path), MXNET_HLOLINT_CACHES=tag,
              MXNET_HLOLINT_MAX_ENTRIES="2"):
        import jax

        cache = CompileCache(tag)
        for i in range(3):
            fn = cache.get_or_build(
                ("e", i), lambda: jax.jit(lambda x: x + 1.0))
            fn(np.zeros((4,), np.float32))
        ledger = [k for k in compile_cache.audit_ledger() if k[0] == tag]
        assert len(ledger) == 2            # per-tag cap enforced
        out = compile_cache.dump_audit(str(tmp_path))
        assert out is not None
        entries = hlolint.load_dumps([str(tmp_path)])
        mine = [e for e in entries if e["tag"] == tag]
        assert len(mine) == 2
        for e in mine:
            assert e["summary"]["num_devices"] == 1
            assert e["summary"]["collectives"] == {}


def test_dump_hook_fires_at_exit_in_subprocess(tmp_path):
    """The CI gate's substrate: a process that warms a named cache under
    MXNET_HLOLINT_DUMP writes its program summaries at exit, with no
    explicit dump call — and the CLI audits them green."""
    code = textwrap.dedent("""\
        import numpy as np
        from mxnet_tpu.compile_cache import CompileCache
        import jax
        c = CompileCache("serving")
        fn = c.get_or_build(("fwd", False, ((8, 4), "float32")),
                            lambda: jax.jit(lambda x: x * 2.0))
        fn(np.zeros((8, 4), np.float32))
    """)
    env = dict(os.environ, MXNET_HLOLINT_DUMP=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(TOOLS_DIR))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=300)
    entries = hlolint.load_dumps([str(tmp_path)])
    assert any(e["tag"] == "serving" for e in entries)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hlolint", "check", str(tmp_path),
         "--require", "serving", "--strict"],
        cwd=os.path.abspath(TOOLS_DIR), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cache_inventory_aggregates_live_entries():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    shard, repl = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    cache = CompileCache("hlolint-inv-test")

    def build():
        def f(x):
            y = jax.lax.with_sharding_constraint(x + 1.0, shard)
            return jax.lax.with_sharding_constraint(y, repl)

        return jax.jit(f, in_shardings=(shard,), out_shardings=repl)

    fn = cache.get_or_build(("inv", 0), build)
    arr = jax.device_put(np.zeros((512,), np.float32), shard)
    fn(arr)
    inv = analysis.cache_inventory("hlolint-inv-test")
    assert inv["entries"] == 1 and inv["errors"] == 0
    assert inv["collectives"].get("all-gather", {}).get("count", 0) >= 1
    assert inv["collective_bytes"] > 0


# ---------------------------------------------------------------------------
# the steady-state recompile blamer
# ---------------------------------------------------------------------------


def _noop_builder(v):
    return lambda: (lambda *a, **k: v)


def test_blamer_one_event_naming_shape_batch():
    cache = CompileCache("blame-shape")
    f32 = np.dtype("float32")
    cache.get_or_build(("fwd", False, ((4, 8), f32)), _noop_builder(1))
    cache.get_or_build(("fwd", False, ((8, 8), f32)), _noop_builder(2))
    cache.get_or_build(("fwd", False, ((8, 8), f32)), _noop_builder(2))
    with _health_journal():
        before = len(_blame_events())
        c0 = telemetry.counter("compile.blamed_misses").value
        cache.get_or_build(("fwd", False, ((9, 8), f32)), _noop_builder(3))
        events = _blame_events()[before:]
    assert len(events) == 1                      # exact accounting
    ev = events[0]
    assert ev["axis"] == "shape(batch)"
    assert ev["axes"][0]["old"] == "8" and ev["axes"][0]["new"] == "9"
    assert "((8, 8)" in ev["nearest"]            # nearest names bucket 8
    assert telemetry.counter("compile.blamed_misses").value == c0 + 1


def test_blamer_warmup_misses_never_blame():
    """Misses BEFORE the first hit are warmup, not steady state."""
    cache = CompileCache("blame-warm")
    with _health_journal():
        before = len(_blame_events())
        for i in range(4):
            cache.get_or_build(("w", i), _noop_builder(i))
        assert len(_blame_events()) == before


def test_blamer_axis_classification():
    f32, f16 = np.dtype("float32"), np.dtype("float16")
    cases = [
        # (warmed key, missing key, expected axis)
        (("k", ((8, 4), f32), 0.1), ("k", ((8, 4), f16), 0.1), "dtype"),
        (("k", ((8, 4), f32), 0.1), ("k", ((8, 4), f32), 0.2),
         "hyperparam"),
        (("k", ((8, 4), f32), ("spmd", "tp=2")),
         ("k", ((8, 4), f32), ("spmd", "tp=4")), "sharding"),
        (("k", ((8, 4), f32), "adam"), ("k", ((8, 4), f32), "sgd"),
         "attr"),
        (("k", ((8, 4), f32)), ("k", ((8, 2), f32)), "shape(dim1)"),
    ]
    for i, (warm, miss, expect) in enumerate(cases):
        cache = CompileCache(f"blame-axis-{i}")
        cache.get_or_build(warm, _noop_builder(1))
        cache.get_or_build(warm, _noop_builder(1))        # hit -> warmed
        with _health_journal():
            before = len(_blame_events())
            cache.get_or_build(miss, _noop_builder(2))
            events = _blame_events()[before:]
        assert len(events) == 1 and events[0]["axis"] == expect, \
            (warm, miss, expect, events)


def test_blamer_serving_bucket_ladder(tmp_path):
    """The satellite acceptance: a request one row past the largest
    bucket must blame shape(batch) and name the nearest bucket."""
    from mxnet_tpu.io.io import DataDesc
    from mxnet_tpu.serving import warmup

    DIM, CLASSES = 8, 4
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=CLASSES, name="fc2")
    sym = mx.sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(sym)
    mod.bind([DataDesc("data", (4, DIM))], [DataDesc("softmax_label", (4,))],
             for_training=False)
    mx.random.seed(11)
    mod.init_params(mx.init.Xavier())
    p = mod.as_predictor(buckets=(2, 4, 8))
    with _health_journal():
        before = len(_blame_events())
        warmup(p)                       # 3 compiles, zero hits: quiet
        x = np.random.RandomState(0).uniform(
            -1, 1, (4, DIM)).astype(np.float32)
        for _ in range(5):              # steady state: hits, quiet
            p.predict(x)
        assert len(_blame_events()) == before, \
            "zero blame events over the warmed steady-state loop"
        # one row past the largest bucket -> a NEW executable
        x9 = np.random.RandomState(1).uniform(
            -1, 1, (9, DIM)).astype(np.float32)
        from mxnet_tpu import ndarray as nd

        p._run(9, [nd.array(x9)])
        events = _blame_events()[before:]
    assert len(events) == 1
    ev = events[0]
    assert ev["cache"] == "serving"
    assert ev["axis"] == "shape(batch)"
    assert ev["axes"][0]["old"] == "8" and ev["axes"][0]["new"] == "9"
    assert "(8," in ev["nearest"]       # the nearest bucket, named


def test_blame_report_line(tmp_path, capsys):
    snap = {"counters": {"compile.blamed_misses": 3,
                         "compile.blame_axis.shape_batch": 2,
                         "compile.blame_axis.dtype": 1},
            "gauges": {}, "histograms": {}, "derived": {}}
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(snap))
    sys.path.insert(0, os.path.join(TOOLS_DIR, "tools"))
    import telemetry_report

    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "hlolint: 3 steady-state recompile(s) blamed" in out
    assert "shape_batch 2" in out and "dtype 1" in out


# ---------------------------------------------------------------------------
# the jax mixed-sharded-concat miscompile canary
# ---------------------------------------------------------------------------

# jax 0.4.37 MISCOMPILED a concat of mixed-sharded operands partitioned
# straight to a 1-D dp layout (values interleaved by shard stride), which
# is one of the two reasons for zero1's replicate-first pack. The installed
# jax (0.9.0) partitions it correctly, so the pin is False; replicate-first
# stays for its other reason (no full-bucket all-reduce per pack —
# parallel/zero1.py Zero1Context.traced_update pack()). If this test goes
# red the partitioner regressed: keep the workaround.
JAX_MIXED_SHARDED_CONCAT_MISCOMPILES = False


@pytest.mark.skipif(
    len(__import__("jax").devices()) < 4,
    reason="needs the 8-virtual-device CPU mesh (tests/conftest.py)")
def test_jax_mixed_sharded_concat_canary():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    dp_flat = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())
    w1 = np.arange(32, dtype=np.float32).reshape(8, 4)       # tp col
    w2 = np.arange(100, 132, dtype=np.float32).reshape(4, 8)  # tp row
    w3 = np.arange(200, 208, dtype=np.float32)                # replicated
    a1 = jax.device_put(w1, NamedSharding(mesh, P("tp", None)))
    a2 = jax.device_put(w2, NamedSharding(mesh, P(None, "tp")))
    a3 = jax.device_put(w3, repl)
    expected = np.concatenate([w.reshape(-1) for w in (w1, w2, w3)])

    def pack_direct(x, y, z):
        flat = jnp.concatenate([x.reshape(-1), y.reshape(-1),
                                z.reshape(-1)])
        return jax.lax.with_sharding_constraint(flat, dp_flat)

    def pack_replicate_first(x, y, z):
        flat = jnp.concatenate([x.reshape(-1), y.reshape(-1),
                                z.reshape(-1)])
        flat = jax.lax.with_sharding_constraint(flat, repl)
        return jax.lax.with_sharding_constraint(flat, dp_flat)

    direct = np.asarray(jax.jit(pack_direct)(a1, a2, a3))
    workaround = np.asarray(jax.jit(pack_replicate_first)(a1, a2, a3))

    # the workaround lowering must be correct on EVERY jax
    np.testing.assert_array_equal(workaround, expected)

    miscompiles = bool((direct != expected).any())
    assert miscompiles == JAX_MIXED_SHARDED_CONCAT_MISCOMPILES, (
        "the installed jax {} the mixed-sharded concat repro. If a jax "
        "upgrade FIXED it: flip JAX_MIXED_SHARDED_CONCAT_MISCOMPILES to "
        "False and consider retiring the replicate-first pack in "
        "parallel/zero1.py. If it REGRESSED after being fixed: restore "
        "the workaround before anything else.".format(
            "no longer miscompiles" if not miscompiles
            else "again miscompiles"))
