"""program_scopes.py: an `op_name` cut to its scope path and direction; the
charging rules on hand-made intervals and a hand-written HLO text (a fusion
charged to the convolution it carries, a `while` and its body once, the
asynchronous copies, two programs with an instruction of one name told apart
by their executions, the engine's decode and prefill); the compiled modules
the file itself holds, on the traces recorded on the v5e
(`recorded_v5e.xplane.pb`, which names no scope: every new reader reports
nothing; `recorded_v5e_scoped.xplane.pb`, whose programs name their work) and
against this file's own reading of a module's text; and each new reader on
a hand-made table."""
import os
import re
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import harness  # noqa: E402
import program_scopes as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")
# record_scoped_fixture.py on the v5e (recorded for PR 36, whose tree was
# refused; PR 37 names the same scopes): six fused steps of a tiny symbol net, a tiny TransformerLM behind an engine
SCOPED = os.path.join(HERE, "recorded_v5e_scoped.xplane.pb")
READERS = ("step_scope_coverage_pct", "tick_scope_coverage_pct",
           "conv_ms_per_step", "conv_mxu_pct", "elementwise_ms_per_step",
           "decode_dense_ms_per_tick", "mamba_state_scope_ms_per_tick",
           "mamba_ssd_scope_ms_per_ktoken", "prefill_ms_per_bucket_ktoken")
STEP, OTHER = 9485870588864213460, 77


# A module's TEXT read to the rows `module_rows` reads of its bytes: the test's
# own reading, independent of the reader's (the program keeps no such map).
# An instruction line: `  ROOT %fusion.7 = bf16[8,8]{1,0:T(8,128)(2,1)}
# fusion(...), kind=kOutput, calls=%fused_computation.15,
# metadata={op_name="jit(f)/mlp/dot_general"}`; the opcode is the first
# lower-case word followed by `(` after the ` = ` (dtypes are followed by `[`,
# layout tiles are upper-case).
_INSTR_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLED_RE = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations)="
    r"(\{[^}]*\}|%?[\w.\-]+)")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")


def rows_of_text(hlo_text):
    """`(module name, {instruction name: (opcode, op_name, inner)})` over
    every computation of one post-optimization HLO module's text."""
    module = ""
    computations = {}       # computation name -> [(opcode, op_name)]
    rows = {}               # instruction name -> (opcode, op_name, called)
    current = None
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTR_RE.match(line)
        if m is not None and current is not None:
            name, opcode = m.group(1), m.group(2)
            found = _OP_NAME_RE.search(line)
            op_name = found.group(1) if found else ""
            called = [c.strip().lstrip("%")
                      for grp in _CALLED_RE.findall(line)
                      for c in grp.strip("{}").split(",") if c.strip()]
            rows[name] = (opcode, op_name, called)
            if opcode != "parameter":
                current.append((opcode, op_name))
            continue
        m = _COMPUTATION_RE.match(line)
        if m is not None:
            current = computations.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
    return module, {
        name: (opcode, op_name,
               tuple(pair for c in called for pair in computations.get(c, ())))
        for name, (opcode, op_name, called) in rows.items()}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jit(main)/jvp(Convolution:conv0)/conv_general_dilated",
     ("Convolution:conv0", "fwd")),
    ("jit(step)/jit(main)/transpose(jvp(Convolution:conv0))/"
     "conv_general_dilated:", ("Convolution:conv0", "bwd")),
    ("jit(program)/transpose(jvp(net_stage1))/net_conv0/Convolution/"
     "jit(_conv)/conv_general_dilated", ("net_stage1/net_conv0/Convolution",
                                         "bwd")),
    ("jit(step)/optimizer.update/mul", ("optimizer.update", "fwd")),
    ("jit(fn)/jit(main)/mamba.ssd/while/body/closed_call/dot_general",
     ("mamba.ssd", "fwd")),
    ("jit(fn)/jit(main)/attn.decode/shd,shld->shl/dot_general",
     ("attn.decode", "fwd")),
    ("jit(fn)/jit(main)/moe.route/cond/branch_1_fun/add",
     ("moe.route", "fwd")),
    ("jit(fn)/attn.decode/broadcast_in_dim;jit(fn)/attn.decode/mul:",
     ("attn.decode", "fwd")),
    ("jit(program)/jvp(net)/net_stage1/net_stage1/net_stage1_conv0/"
     "Convolution/conv_general_dilated",
     ("net/net_stage1/net_stage1_conv0/Convolution", "fwd")),
    ("jit(f)/jvp()/gt", (ps.UNSCOPED, "fwd")),
    ("jit(f)/transpose(jvp(jit(_where)))/select_n", (ps.UNSCOPED, "bwd")),
    ("jit(fixture_matmul)/dot_general:", (ps.UNSCOPED, "fwd")),
    ("", (ps.UNSCOPED, "fwd")),
])
def test_scope_of_an_op_name(op_name, want):
    assert ps.scope_of(op_name) == want


def test_kinds_of_a_path():
    assert ps.innermost("Convolution:stage1_unit1_conv1") == "Convolution"
    assert ps.innermost("net_stage1/net_conv0/Convolution") == "Convolution"
    assert ps.outermost("mla.project/norm") == "mla.project"
    assert ps.is_named("mlp") and not ps.is_named(ps.UNSCOPED) \
        and not ps.is_named(ps.ASYNC_COPY)


# ---------------------------------------------------------------------------
# charging, on a hand-written program
# ---------------------------------------------------------------------------

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %convolution.3 = f32[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/jit(main)/jvp(Convolution:conv0)/conv_general_dilated"}
  ROOT %multiply.4 = f32[8,8]{1,0} multiply(%convolution.3, %p1), metadata={op_name="jit(step)/jit(main)/jvp(BatchNorm:bn0)/mul"}
}

%fused_computation.2 (p0.1: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  %constant.9 = f32[] constant(0)
  ROOT %maximum.5 = f32[8,8]{1,0} maximum(%p0.1, %p0.1), metadata={op_name="jit(step)/jit(main)/jvp(Activation:relu0)/max"}
}

%body.7 (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]{1,0}) parameter(0)
  %dot.8 = f32[8,8]{1,0} dot(%arg, %arg), metadata={op_name="jit(step)/jit(main)/mamba.ssd/while/body/dot_general"}
  ROOT %tuple.9 = (s32[], f32[8,8]{1,0}) tuple(%arg, %dot.8)
}

%cond.10 (arg.1: (s32[], f32[8,8])) -> pred[] {
  %arg.1 = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %lt.11 = pred[] compare(%arg.1, %arg.1), direction=LT, metadata={op_name="jit(step)/jit(main)/mamba.ssd/while/cond/lt"}
}

ENTRY %main.20 (x: f32[8,8], w: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %w = f32[8,8]{1,0} parameter(1), metadata={op_name="w"}
  %copy-start = (f32[8,8]{1,0:S(1)}, f32[8,8]{1,0}, u32[]{:S(2)}) copy-start(f32[8,8]{1,0} %w), cross_program_prefetch_index=0
  %copy-done = f32[8,8]{1,0:S(1)} copy-done(%copy-start)
  %fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(%x, %copy-done), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(main)/jvp(BatchNorm:bn0)/mul"}
  %fusion.2 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jit(main)/jvp(Activation:relu0)/max"}
  %while.12 = (s32[], f32[8,8]{1,0}) while(%fusion.2), condition=%cond.10, body=%body.7, metadata={op_name="jit(step)/jit(main)/mamba.ssd/while"}
  %transpose.13 = f32[8,8]{1,0} transpose(%fusion.2), dimensions={1,0}, metadata={op_name="jit(step)/jit(main)/transpose(jvp(Convolution:conv0))/transpose"}
  ROOT %bitcast.14 = f32[8,8]{1,0} bitcast(%transpose.13)
}
"""


def event(name, opcode, s, e):
    return (f"%{name} = f32[8,8]{{1,0:T(8,128)(2,1)}} {opcode}(f32[8,8] %x)",
            s, e)


def hand_made():
    """Two executions of `jit_step` (10 ms each) and one of another program
    that also has a `fusion.1`; `programs` is what the file would hold: the
    rows of each program's module, by its fingerprint."""
    ops, mods = [], []
    for k in range(2):
        t = 0.020 * k
        mods.append((f"jit_step({STEP})", t, t + 0.010))
        ops += [event("copy-start", "copy-start", t, t + 0.0001),
                event("copy-done", "copy-done", t + 0.0001, t + 0.0005),
                event("fusion.1", "fusion", t + 0.001, t + 0.004),
                event("fusion.2", "fusion", t + 0.004, t + 0.005),
                # a while of 3 ms whose body's dots take 2 ms of it
                event("while.12", "while", t + 0.005, t + 0.008),
                event("dot.8", "dot", t + 0.0055, t + 0.0065),
                event("dot.8", "dot", t + 0.0065, t + 0.0075),
                event("transpose.13", "transpose", t + 0.008, t + 0.009),
                event("bitcast.14", "bitcast", t + 0.009, t + 0.0095)]
    mods.append((f"jit_other({OTHER})", 0.040, 0.042))
    ops.append(event("fusion.1", "fusion", 0.040, 0.042))
    ops.append(event("stray.1", "fusion", 0.050, 0.051))    # in no execution
    module, rows = rows_of_text(HLO)
    assert module == "jit_step"
    programs = {STEP: rows, OTHER: {"fusion.1": (
        "fusion", "jit(other)/jit(main)/mlp/dot_general", ())}}
    return ops, mods, programs


def test_charging_rules():
    ops, mods, programs = hand_made()
    times = ps.charge(ops, mods, programs)
    assert set(times) == {"jit_step", "jit_other"}
    step = times["jit_step"]
    approx = pytest.approx
    assert len(step.executions) == 2 and step.known
    # the fusion whose own op_name is BatchNorm's carries a convolution
    assert step.seconds["Convolution:conv0", "fwd"] == approx(0.006)
    assert ("BatchNorm:bn0", "fwd") not in step.seconds
    assert step.seconds["Activation:relu0", "fwd"] == approx(0.002)
    # a while and its body count once: 3 ms an execution in all
    assert step.seconds["mamba.ssd", "fwd"] == approx(0.006)
    assert step.opcodes["mamba.ssd", "fwd"] == {"while", "dot"}
    # transpose(jvp(X)) -> X, bwd
    assert step.seconds["Convolution:conv0", "bwd"] == approx(0.002)
    assert step.seconds[ps.ASYNC_COPY, "fwd"] == approx(0.001)
    assert step.seconds[ps.UNSCOPED, "fwd"] == approx(0.001)     # no op_name
    assert step.total_s == approx(0.018)
    assert step.named_s == approx(0.016)
    assert step.unscoped_s == approx(0.001)
    assert ps.issued_pct([step]) == approx(100 * 17 / 18)
    # each execution keeps its own charges
    assert [sum(one.values()) for one in step.per_execution] == [
        approx(0.009), approx(0.009)]
    # the conv+BatchNorm fusion spans two scopes; the ReLU one does not
    assert step.fusion_s == approx(0.008) and step.mixed_s == approx(0.006)
    assert step.ms_per_execution(
        lambda p: ps.innermost(p) == "Convolution") == approx(4.0)
    # the other program's fusion.1 is its own: told apart by the
    # fingerprint of the execution it lies in
    other = times["jit_other"]
    assert other.seconds == {("mlp", "fwd"): approx(0.002)}
    scoped = ps.ScopeTimes(times)
    assert scoped.step is step and scoped.engine() == []
    text = "\n".join(scoped.table())
    assert "program jit_step" in text
    assert "88.9% under a scope the program named, 5.6% XLA's own " \
           "asynchronous copies, 5.6% unscoped" in text
    assert "Convolution:conv0 fwd: 3.000 ms an execution" in text
    assert "mixed (several scopes in one fusion) 33.3%" in text


def test_an_execution_the_end_of_the_trace_cut_is_left_out():
    """Four whole steps of 10 ms and a fifth of which the trace holds 4 ms:
    neither its operations nor the execution count."""
    programs = {STEP: {"fusion.2": (
        "fusion", "jit(step)/jit(main)/jvp(Activation:relu0)/max", ())}}
    mods = [(f"jit_step({STEP})", 0.010 * k, 0.010 * k + 0.010)
            for k in range(4)] + [(f"jit_step({STEP})", 0.040, 0.044)]
    ops = [event("fusion.2", "fusion", s, e) for _, s, e in mods]
    step = ps.charge(ops, mods, programs)["jit_step"]
    assert len(step.executions) == 4
    assert step.ms_per_execution(ps.is_named) == pytest.approx(10.0)
    # too few others to know a median: kept
    assert len(ps.charge(ops[2:], mods[2:],
                         programs)["jit_step"].executions) == 3


def test_a_program_whose_module_the_file_does_not_hold(tmp_path):
    """Nothing names its operations: all `unscoped`, and the table says
    why."""
    ops, mods, programs = hand_made()
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    assert ps.hlo_programs(str(empty)) == {}
    step = ps.charge(ops, mods, {OTHER: programs[OTHER]})["jit_step"]
    assert not step.known and step.named_s == 0
    assert step.seconds[ps.UNSCOPED, "fwd"] == pytest.approx(
        step.total_s - step.seconds[ps.ASYNC_COPY, "fwd"])
    assert "the file does not hold the program's module" in "\n".join(
        ps.ScopeTimes({"jit_step": step}).table())


def _compiled_here():
    """A jitted function compiled here, as the profiler would store it: the
    serialized HloModuleProto of its executable, and the module's text."""
    import jax
    import jax.numpy as jnp

    def layer(w, x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ w).sum()

    compiled = jax.jit(jax.grad(layer)).lower(
        jnp.ones((8, 8)), jnp.ones((4, 8))).compile()
    (module,) = compiled.runtime_executable().hlo_modules()
    return module.as_serialized_hlo_module_proto(), compiled.as_text()


def test_the_files_rows_are_the_programs_own():
    """`module_rows` reads of a module's bytes what `rows_of_text` reads
    of its text."""
    proto, text = _compiled_here()
    rows = ps.module_rows(memoryview(proto), (0, len(proto)))
    _, want = rows_of_text(text)
    assert set(rows) == set(want) and len(rows) > 10
    assert {n: r[:2] for n, r in rows.items()} == {
        n: r[:2] for n, r in want.items()}
    called = {n for n, r in rows.items() if r[2]}
    assert called and called == {n for n, r in want.items() if r[2]}
    assert any(op_name.endswith("/jvp(mlp)/dot_general")
               for _, op_name, _ in rows.values())


def engine_trace():
    """An engine: three decode executions of 2 ms and one prefill of 8 ms
    (other fingerprints: other buckets)."""
    ops, mods = [], []
    main = "jit(fn)/jit(main)/"
    for k in range(3):
        t = 0.010 * k
        mods.append(("jit_fn(1)", t, t + 0.002))
        ops += [event("fusion.1", "fusion", t, t + 0.0005),
                event("custom-call.2", "custom-call", t + 0.0005, t + 0.0015),
                event("fusion.3", "fusion", t + 0.0015, t + 0.002)]
    programs = {1: {
        "fusion.1": ("fusion", main + "mlp/dot_general", ()),
        "custom-call.2": ("custom-call",
                          main + "mamba.state_update/pallas_call", ()),
        "fusion.3": ("fusion", main + "mul", ())}}
    mods.append(("jit_fn(2)", 0.003, 0.009))
    ops += [event("while.1", "while", 0.003, 0.007),
            event("fusion.2", "fusion", 0.007, 0.009)]
    mods.append(("jit_fn(3)", 0.023, 0.025))
    ops.append(event("fusion.2", "fusion", 0.023, 0.025))
    mods.append(("jit_tick_counters(4)", 0.0095, 0.0096))
    ops.append(event("fusion.9", "fusion", 0.0095, 0.0096))
    for fp in (2, 3):
        programs[fp] = {
            "while.1": ("while", main + "mamba.ssd/while", ()),
            "fusion.2": ("fusion", main + "attn.prefill/dot_general", ())}
    return ops, mods, programs


def test_the_engines_programs_are_decode_and_prefill():
    programs = ps.charge(*engine_trace())
    assert set(programs) == {ps.DECODE, ps.PREFILL, "jit_tick_counters"}
    decode, prefill = programs[ps.DECODE], programs[ps.PREFILL]
    assert len(decode.executions) == 3 and len(prefill.executions) == 2
    assert prefill.modules == {"jit_fn(2)", "jit_fn(3)"}
    assert decode.ms_per_execution(lambda p: p == "mlp") == pytest.approx(0.5)
    assert prefill.seconds["mamba.ssd", "fwd"] == pytest.approx(0.004)
    times = ps.ScopeTimes(programs, spans=[
        (0.0025, 0.0095, 512, 300),     # holds the 6 ms prefill
        (0.0225, 0.0255, 256, 200),     # holds the 2 ms one
        (0.0500, 0.0600, 256, 100)])    # its execution is not in the trace
    assert times.engine() == [decode, prefill]
    assert times.prefill_per_bucket() == (pytest.approx(0.008), 768, 2)
    # the scan's 4 ms are the first admission's; the tokens are those of
    # the two admissions whose executions the trace holds
    assert times.prefill_per_token(lambda p: p == "mamba.ssd") \
        == (pytest.approx(0.004), 500)
    assert times.prefill_per_token(lambda p: p == "no such scope") \
        == (0, 500)
    # where the engine idled, the device's first event is the prefill
    # itself and its span starts before it: the admission still counts
    early = ps.ScopeTimes(programs, spans=[(0.0005, 0.0095, 512, 300)])
    assert early.prefill_per_bucket() == (pytest.approx(0.006), 512, 1)
    nothing = ps.ScopeTimes(programs, spans=[(0.2, 0.3, 512, 300)])
    assert nothing.prefill_per_bucket() is None
    assert nothing.prefill_per_token(ps.is_named) is None
    # a program from before PR 37: its spans do not say their tokens
    silent = ps.ScopeTimes(programs, spans=[(0.0025, 0.0095, 512, None)])
    assert silent.prefill_per_token(ps.is_named) is None
    assert silent.prefill_per_bucket()[1:] == (512, 1)
    assert "3 mx:generation.prefill spans: bucket tokens 1024, prompt " \
           "tokens 600" in times.table()[-1]
    assert "prefill 2, 8.00; decode 3, 6.00" in times.table()[-2]


# ---------------------------------------------------------------------------
# the file, the readers
# ---------------------------------------------------------------------------

def fake_run(path, **kw):
    return types.SimpleNamespace(tracer=types.SimpleNamespace(
        xplane_path=lambda: path), **kw)


def test_the_recorded_file_holds_its_programs():
    programs = ps.hlo_programs(RECORDED)
    matmul = 11730107425037342888       # jit_fixture_matmul's fingerprint
    assert set(programs) == {matmul, 5871758261428352688}
    opcode, op_name, inner = programs[matmul]["fusion.7"]
    assert (opcode, op_name) == ("fusion", "jit(fixture_matmul)/dot_general")
    assert ("convolution", "jit(fixture_matmul)/dot_general") in inner
    assert ("tanh", "jit(fixture_matmul)/tanh") in inner
    assert programs[matmul]["copy-start"][:2] == ("copy-start", "")
    ops, spans = ps.ops_and_spans(RECORDED)
    assert ops and spans == []
    assert ps.ops_and_spans(RECORDED, device=3) == ([], [])
    # every operation the timeline shows is an instruction of its program
    trace = tr.load(RECORDED, n_devices=1)
    fingerprint = {name: int(ps.FINGERPRINT.search(name).group(1))
                   for name, _, _ in trace.devices[0].modules}
    for name, s, e in trace.devices[0].modules:
        inside = {tr._op_name(text) for text, a, _ in ops if s <= a < e}
        assert inside and inside <= set(programs[fingerprint[name]])


def test_the_trace_of_programs_that_name_their_work():
    """The fixture recorded on the v5e: the modules the file holds name
    every operation of the fused step and of the engine's programs (three of
    them called `jit_fn`, each found by its fingerprint)."""
    trace = tr.load(SCOPED, n_devices=1)
    times = ps.load(SCOPED, trace)
    assert {"jit_step", ps.DECODE, ps.PREFILL} <= set(times.programs)
    step = times.programs["jit_step"]
    assert times.step is step and len(step.executions) == 6 and step.known
    paths = {path for path, _ in step.seconds}
    assert {"Convolution:conv1", "Convolution:conv2", "BatchNorm:bn1",
            "FullyConnected:fc1", "SoftmaxOutput:softmax",
            "optimizer.update", ps.ASYNC_COPY} <= paths
    for node in ("Convolution:conv1", "Convolution:conv2", "BatchNorm:bn1"):
        assert (node, "fwd") in step.seconds and (node, "bwd") in step.seconds
    assert 0.8 < step.named_s / step.total_s < 0.95
    assert 0 < step.mixed_s <= step.fusion_s <= step.total_s
    decode, prefill = times.programs[ps.DECODE], times.programs[ps.PREFILL]
    assert decode.known and prefill.known
    assert len(decode.executions) == 9 and len(prefill.executions) == 3
    assert len(prefill.modules) == 2            # two buckets, two programs
    outer = lambda prog: {ps.outermost(p) for p, _ in prog.seconds}  # noqa: E731
    shared = {"embed", "norm", "attn.project", "attn.out", "mlp", "head"}
    assert shared | {"attn.decode"} <= outer(decode)
    assert shared | {"attn.prefill"} <= outer(prefill)
    # the three admissions say their own sizes, and find their executions
    assert [(b, t) for _, _, b, t in times.spans] == [(16, 9), (32, 20),
                                                      (32, 30)]
    seconds, buckets, n = times.prefill_per_bucket()
    assert (buckets, n) == (80, 3)
    assert seconds == pytest.approx(
        sum(e - s for s, e in prefill.executions))
    normed, tokens = times.prefill_per_token(
        lambda p: ps.outermost(p) == "norm")
    assert tokens == 59
    assert normed == pytest.approx(prefill.select(
        lambda p: ps.outermost(p) == "norm"))


def test_the_table_of_a_file_on_disk(capsys):
    assert ps.main([SCOPED]) == 0
    out = capsys.readouterr().out
    assert "program jit_step (jit_step(10161305340423127865)): 6 " \
           "executions" in out
    assert "program decode (jit_fn(17616849545881187708)): 9 " in out
    assert "3 mx:generation.prefill spans: bucket tokens 80, prompt " \
           "tokens 59" in out


def test_a_trace_without_scopes_reads_as_nothing(monkeypatch, capsys):
    """The recorded trace names no scope: the table says so, `for_run` gives
    None, every new reader reports nothing (and does not raise), and the
    file is read once a process."""
    trace = tr.load(RECORDED, n_devices=1)
    times = ps.load(RECORDED, trace)
    assert set(times.programs) == {"jit_fixture_matmul", "jit_fixture_add"}
    assert not times.named_anything()
    matmul = times.programs["jit_fixture_matmul"]
    assert len(matmul.executions) == 6
    assert matmul.total_s == pytest.approx(
        tr.total(tr.merge((s, e) for n, s, e in trace.devices[0].ops
                          if 0.05 < s)) - times.programs[
                              "jit_fixture_add"].total_s, rel=0.02)
    obs = {"trace": trace, "traced_step_s": 0.01, "items_per_step": 4,
           "trace_telemetry": {"prefills": 2, "prefill_tokens": 100}}
    run = fake_run(RECORDED, config={}, chips=1, peaks={})
    monkeypatch.setattr(ps, "_loaded", {})
    loads = []
    real = ps.load
    monkeypatch.setattr(ps, "load",
                        lambda *a, **k: loads.append(a[0]) or real(*a, **k))
    for name in READERS:
        assert harness.load_plugin("layer_metrics", name).read(obs, run) \
            is None
    assert loads == [RECORDED]
    out = capsys.readouterr().out
    assert "NO instruction carries a scope" in out
    assert "every program, executions and device ms in all: " \
           "jit_fixture_matmul 6, " in out
    assert "no operation of the trace carries a scope" in out
    assert ps.for_run({}, fake_run(None)) is None
    assert ps.main([RECORDED]) == 0


def test_a_file_the_reader_cannot_take_apart_costs_no_result(
        monkeypatch, capsys):
    """Whatever `load` raises (a profiler that lays the programs' modules
    out otherwise), `for_run` logs it and gives None once: every new reader
    reports nothing and the run's result line is printed without them."""
    trace = tr.load(RECORDED, n_devices=1)
    monkeypatch.setattr(ps, "_loaded", {})
    calls = []

    def broken(path):
        calls.append(path)
        raise ValueError("wire type 3 at byte 7")

    monkeypatch.setattr(ps, "hlo_programs", broken)
    obs = {"trace": trace, "traced_step_s": 0.01, "items_per_step": 4}
    run = fake_run(RECORDED, config={}, chips=1, peaks={})
    for name in READERS:
        assert harness.load_plugin("layer_metrics", name).read(obs, run) \
            is None
    assert calls == [RECORDED]
    assert "the file was not read: ValueError('wire type 3 at byte 7')" \
        in capsys.readouterr().out


def test_a_reader_that_raises_costs_its_own_metric_alone(monkeypatch, capsys):
    """Beyond the file's reading: a table the readers' arithmetic does not
    foresee (here, one that raises where it is asked for its engine, and a
    step whose time is not a number) is said on a `[scopes]` line, and each
    reader gives None where `run.py` would otherwise lose the result."""
    class Odd:
        step = None

        def engine(self):
            raise KeyError("decode")

        def prefill_per_bucket(self):
            return float("nan"), 1, 1

    monkeypatch.setattr(ps, "_loaded", {"a-path": Odd()})
    run = fake_run("a-path", config={}, chips=1, peaks={})
    obs = {"trace": object()}
    for name in READERS:
        assert harness.load_plugin("layer_metrics", name).read(obs, run) \
            is None
    out = capsys.readouterr().out
    assert "[scopes] tick_scope_coverage_pct raised KeyError('decode'): " \
           "not reported" in out
    assert "[scopes] prefill_ms_per_bucket_ktoken read nan: not reported" \
        in out


def test_readers_on_a_training_step(monkeypatch):
    ops, mods, programs = hand_made()
    times = ps.ScopeTimes(ps.charge(ops, mods, programs))
    monkeypatch.setattr(ps, "_loaded", {"a-path": times})
    trace = tr.ReducedTrace([tr.DeviceTrace(
        0, [(tr._op_name(t), s, e) for t, s, e in ops], mods)], [])
    obs = {"trace": trace, "items_per_step": 8}
    run = fake_run("a-path", chips=2, peaks={"bf16_flops_per_s": 1e9},
                   config={"train_flops": "flops:resnet_train_flops_per_image",
                           "arch": "v1_gluon", "image_size": 32,
                           "image_channels": 3, "stem_filters": 8,
                           "units": [1], "stage_filters": [16],
                           "bottleneck_ratio": 4, "num_classes": 10})
    read = lambda name: harness.load_plugin(  # noqa: E731
        "layer_metrics", name).read(obs, run)
    approx = pytest.approx
    # 1 ms of 18 is unscoped; the asynchronous copies are XLA's own
    assert read("step_scope_coverage_pct") == approx(100 * 17 / 18)
    assert read("conv_ms_per_step") == approx(4.0)          # 3 fwd + 1 bwd
    # everything else the program named: the ReLU 1 ms, the scan 3 ms
    assert read("elementwise_ms_per_step") == approx(4.0)
    import flops

    per_chip = flops.resnet_train_flops_per_image(run.config) * 8 / 2
    assert read("conv_mxu_pct") == approx(100 * per_chip / 4e-3 / 1e9)
    assert read("tick_scope_coverage_pct") is None          # no engine here
    assert read("decode_dense_ms_per_tick") is None
    assert read("prefill_ms_per_bucket_ktoken") is None


def test_readers_on_an_engine(monkeypatch):
    ops, mods, programs = engine_trace()
    times = ps.ScopeTimes(ps.charge(ops, mods, programs), spans=[
        (0.0025, 0.0095, 512, 300), (0.0225, 0.0255, 256, 200)])
    monkeypatch.setattr(ps, "_loaded", {"a-path": times})
    trace = tr.ReducedTrace([tr.DeviceTrace(
        0, [(tr._op_name(t), s, e) for t, s, e in ops], mods)], [])
    obs = {"trace": trace}
    run = fake_run("a-path", config={}, chips=1, peaks={})
    read = lambda name: harness.load_plugin(  # noqa: E731
        "layer_metrics", name).read(obs, run)
    approx = pytest.approx
    # decode 6 ms of which 1.5 unscoped, prefill 8 ms all named
    assert read("tick_scope_coverage_pct") == approx(100 * 12.5 / 14)
    assert read("decode_dense_ms_per_tick") == approx(0.5)
    assert read("mamba_state_scope_ms_per_tick") == approx(1.0)
    # 4 ms of scan in the two admissions' executions, whose spans say 300
    # and 200 tokens
    assert read("mamba_ssd_scope_ms_per_ktoken") == approx(8.0)
    assert read("prefill_ms_per_bucket_ktoken") == approx(8.0 / 0.768)
    assert read("step_scope_coverage_pct") == approx(0.0)   # tick_counters
    assert read("conv_ms_per_step") is None
    times.spans = [(s, e, b, None) for s, e, b, _ in times.spans]
    assert read("mamba_ssd_scope_ms_per_ktoken") is None
    assert read("prefill_ms_per_bucket_ktoken") == approx(8.0 / 0.768)
