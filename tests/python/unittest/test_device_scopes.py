"""Device-side scopes (ISSUE 37): the program names the work it issues with
``jax.named_scope`` where it is traced into a program, so every instruction
of a step or a tick carries the layer that issued it in its ``op_name``. The
program keeps no map for a reader: a profiler trace holds each program's
compiled module, and ``benchmark/program_scopes.py`` reads the names there.

Covers:
* the symbol executor — ``<Op>:<node>`` on the forward, under
  ``transpose(jvp(`` on the backward, ``optimizer.update`` on the update of
  the fused step;
* gluon — ``<block>/<Op>`` in a hybridized block's capture and in the
  one-program step, nothing in the eager call path;
* the registry — an eager op enters its scope when its one-op program is
  first traced and never again;
* the four serving models — the vocabulary of their decode and prefill
  programs;
* no op is moved — the optimized HLO of a Module step, a gluon step and each
  serving model's decode and prefill program, less metadata, is that of the
  same program traced with ``jax.named_scope`` taken out (the parent's);
* nothing of a program outlives its owner (a dropped block's parameters are
  collectable);
* the admission span — ``bucket`` and ``tokens`` with tracing on (record)
  and off (the annotation's arguments).
"""
import contextlib
import gc
import re
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, compile_cache, gluon, nd, tracing
from mxnet_tpu import parallel as par
from mxnet_tpu.io.io import DataDesc


def _program_text(cache, key, optimized=False):
    """The module of one entry of a CompileCache, lowered again from the
    shapes the cache recorded of its first call: the StableHLO text with its
    locations, or (``optimized``) the compiled module's HLO text."""
    fn = cache._entries[key]
    args, kwargs = cache._entry_stats[key]["avals"]
    with compile_cache.donation_warnings_suppressed():
        lowered = getattr(fn, "_fn", fn).lower(*args, **kwargs)
        if optimized:
            return lowered.compile().as_text()
    return lowered.as_text(debug_info=True)


def _op_names(text):
    """Every name a module's text gives an operation: the ``op_name`` of an
    HLO instruction, the location of a StableHLO one."""
    return set(re.findall(r'op_name="([^"]*)"', text)) | set(
        re.findall(r'"(p?jit\([^"]*)"', text))


def _less_metadata(hlo_text):
    """An optimized module's text with what only names it taken out: each
    instruction's ``metadata={...}`` and the header's tables of the source
    lines they point into."""
    text = re.sub(r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:\d+ .*\n)*", "\n", hlo_text)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", text)


@contextlib.contextmanager
def _scopes_taken_out(monkeypatch):
    """``jax.named_scope`` opening nothing: the program as it was traced
    before it named its work."""
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope",
                  lambda name: contextlib.nullcontext())
        yield


def _paths(op_names):
    """The scope path of each op_name: the primitive (the last component)
    and the ``jit(...)`` wrappers dropped."""
    out = set()
    for name in op_names:
        out.add("/".join(p for p in name.split("/")[:-1]
                         if not re.match(r"^p?jit\(", p)))
    return out


# ---------------------------------------------------------------------------
# symbol executor: the Module fused step
# ---------------------------------------------------------------------------


def _conv_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1), name="pool1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=3, name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fused_step_program(optimized=False):
    """The text of a tiny symbol net's fused step program."""
    batch = 2
    mod = mx.mod.Module(_conv_symbol())
    mod.bind([DataDesc("data", (batch, 3, 8, 8))],
             [DataDesc("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params=(
        ("learning_rate", 0.1), ("momentum", 0.9)))
    rng = np.random.RandomState(0)
    data_batch = mx.io.DataBatch(
        [nd.array(rng.uniform(-1, 1, (batch, 3, 8, 8)).astype(np.float32))],
        [nd.array(rng.randint(0, 3, batch).astype(np.float32))])
    assert mod.fused_step(data_batch) is True
    cache = mod._exec._cache
    (key,) = [k for k in cache.keys() if k[0] == "fused_step"]
    return _program_text(cache, key, optimized)


@pytest.fixture(scope="module")
def fused_step_names():
    return _op_names(_fused_step_program())


@pytest.mark.parametrize("want", [
    "jvp(Convolution:conv1)/conv_general_dilated",
    "transpose(jvp(Convolution:conv1))/conv_general_dilated",
    "jvp(BatchNorm:bn1)/",
    "transpose(jvp(BatchNorm:bn1))/",
    "jvp(Activation:relu1)/",
    "jvp(Pooling:pool1)/",
    "jvp(FullyConnected:fc1)/dot_general",
    "transpose(jvp(FullyConnected:fc1))/",
    "jvp(SoftmaxOutput:softmax)/",
    "/optimizer.update/",
])
def test_fused_step_names_every_node(fused_step_names, want):
    assert any(n.startswith("jit(step)/") and want in n
               for n in fused_step_names), sorted(fused_step_names)[:40]


# ---------------------------------------------------------------------------
# gluon: the capture, the one-program step, the eager path
# ---------------------------------------------------------------------------


def _net():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"), gluon.nn.GlobalAvgPool2D(),
                gluon.nn.Dense(3))
    net.initialize()
    return net


def _gluon_programs(optimized=False):
    """`(net, {program kind: text})` of a hybridized net: the forward-only
    capture and the one-program training step."""
    net = _net()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).uniform(
        -1, 1, (2, 3, 8, 8)).astype(np.float32))
    y = nd.array([0, 1])
    net(x).asnumpy()                                # the forward capture
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    loss.hybridize()
    with autograd.record():
        out = loss(net(x), y)
    out.backward()
    trainer.step(2)                                 # one donated program
    # the step program is an entry of the LAST recorded call's op (the loss)
    found = {}
    for cache in (net._cached_op._cache, loss._cached_op._cache):
        for key in cache.keys():
            if key[0] in ("fwd", "bwd") and key[0] not in found:
                found[key[0]] = _program_text(cache, key, optimized)
    return net, found


@pytest.fixture(scope="module")
def gluon_names():
    net, found = _gluon_programs()
    return net, {kind: _op_names(text) for kind, text in found.items()}


def test_gluon_capture_nests_block_and_op(gluon_names):
    net, found = gluon_names
    prefix = net.name
    paths = _paths(found["fwd"])
    for block, op in (("conv0", "Convolution"), ("batchnorm0", "BatchNorm"),
                      ("relu0", "Activation"), ("dense0", "FullyConnected")):
        assert any(p.startswith(f"{prefix}_{block}/{op}") for p in paths), \
            sorted(paths)


def test_gluon_step_program_names_backward_and_update(gluon_names):
    net, found = gluon_names
    names = found["bwd"]
    conv = f"{net.name}_conv0"
    assert any(f"/jvp({conv})/Convolution/" in n for n in names)
    assert any(f"/transpose(jvp({conv}))/Convolution/" in n for n in names)
    assert any("/optimizer.update/" in n for n in names), sorted(names)[-20:]


class _CountedScopes:
    """`jax.named_scope` patched to list the names entered."""

    def __init__(self, monkeypatch):
        self.names = []
        real = jax.named_scope

        def counted(name):
            self.names.append(name)
            return real(name)

        monkeypatch.setattr(jax, "named_scope", counted)


def test_eager_op_enters_its_scope_once_a_program(monkeypatch):
    """An eager `nd` op on concrete arrays enters its scope when its one-op
    program is first traced, and none on the second call of the same
    shapes: the scope is inside the jitted function."""
    counted = _CountedScopes(monkeypatch)
    x = nd.array(np.arange(35, dtype=np.float32).reshape(5, 7))
    nd.sum(x * 1.0, axis=1).asnumpy()               # warms `_mul_scalar`
    counted.names.clear()
    nd.tanh(x).asnumpy()
    first = list(counted.names)
    nd.tanh(x).asnumpy()
    assert first and set(first) == {"tanh"}
    assert counted.names == first
    # under autograd the forward-with-residuals program is traced once too
    x.attach_grad()
    counted.names.clear()
    for _ in range(2):
        with autograd.record():
            y = nd.sigmoid(x)
        y.backward()
        if not counted.names:
            pytest.fail("the recorded op's program entered no scope")
        seen = list(counted.names)
    assert set(seen) == {"sigmoid"} and counted.names == seen


def test_eager_block_call_enters_no_scope(monkeypatch):
    net = _net()
    x = nd.array(np.ones((2, 3, 8, 8), np.float32))
    net(x).asnumpy()                    # every op's program traced here
    counted = _CountedScopes(monkeypatch)
    net(x).asnumpy()
    assert counted.names == []


# ---------------------------------------------------------------------------
# the serving models' vocabulary
# ---------------------------------------------------------------------------

_FULL, _WINDOW = "full_attention", "sliding_attention"


def _transformer():
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig

    return TransformerLM(TransformerLMConfig(
        vocab_size=64, d_model=32, n_heads=2, d_ff=64, n_layers=2,
        max_len=48, dtype="float32"), _mesh())


def _hybrid():
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    return HybridLM(HybridLMConfig.from_config(dict(
        hidden_size=64, shared_intermediate_size=128, num_attention_heads=8,
        num_key_value_heads=2, attention_multiplier=0.125,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
        rms_norm_eps=1e-5, layer_types=["mamba", "attention"],
        mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
        mamba_chunk_size=8, mamba_expand=2, mamba_n_groups=1, vocab_size=211,
        max_position_embeddings=128, dtype="float32")), _mesh())


def _latent():
    from mxnet_tpu.models import LatentMoELM, LatentMoELMConfig

    return LatentMoELM(LatentMoELMConfig.from_config(dict(
        vocab_size=211, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, kv_lora_rank=128, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, first_k_dense_replace=1, num_experts=16,
        num_experts_per_tok=4, num_shared_experts=1,
        routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 64,
                      "type": "deepseek_yarn"},
        max_position_embeddings=256, dtype="float32", hidden_act="silu",
        tie_word_embeddings=False, use_qk_norm=True,
        moe_router_enable_expert_bias=True)), _mesh())


def _window():
    from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig

    return WindowMoELM(WindowMoELMConfig.from_config(dict(
        vocab_size=211, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
        sliding_window=8, use_sliding_window=True, max_window_layers=0,
        layer_types=[_WINDOW, _FULL], mlp_layer_types=["sparse"] * 2,
        rope_parameters={
            _FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 16,
                    "original_max_position_embeddings": 16, "beta_fast": 32,
                    "beta_slow": 1,
                    "attention_factor": 1.2772588722239782},
            _WINDOW: {"rope_type": "default", "rope_theta": 10000}},
        max_position_embeddings=256, dtype="float32", hidden_act="silu",
        attention_bias=False, tie_word_embeddings=False,
        model_type="mellum")), _mesh())


def _mesh():
    return par.create_mesh(devices=jax.devices()[:1], dp=1)


SHARED = {"embed", "norm", "head", "attn.out"}
VOCABULARY = {
    ("transformer", "decode"): SHARED | {"attn.project", "attn.decode",
                                         "mlp"},
    ("transformer", "prefill"): SHARED | {"attn.project", "attn.prefill",
                                          "mlp"},
    ("hybrid", "decode"): SHARED | {
        "attn.project", "attn.decode", "mlp", "mamba.project", "mamba.conv",
        "mamba.gates", "mamba.state_update", "mamba.out"},
    ("hybrid", "prefill"): SHARED | {
        "attn.project", "attn.prefill", "mlp", "mamba.project", "mamba.conv",
        "mamba.gates", "mamba.ssd", "mamba.out", "cache.write"},
    ("latent", "decode"): SHARED | {
        "mla.project", "mla.absorb", "mla.attend", "mlp", "moe.route",
        "moe.group", "moe.experts", "moe.shared", "cache.write"},
    ("latent", "prefill"): SHARED | {
        "mla.project", "mla.attend", "mlp", "moe.route", "moe.group",
        "moe.experts", "moe.shared", "cache.write"},
    ("window", "decode"): SHARED | {
        "attn.project", "attn.rotary", "attn.decode", "attn.window",
        "moe.route", "moe.group", "moe.experts", "cache.write"},
    ("window", "prefill"): SHARED | {
        "attn.project", "attn.rotary", "attn.prefill", "moe.route",
        "moe.group", "moe.experts", "cache.write"},
}
MODELS = {"transformer": _transformer, "hybrid": _hybrid, "latent": _latent,
          "window": _window}


def _serving_program(model, program):
    """`jax.jit(...).lower(...)` of one serving model's decode or prefill
    program at a tiny size, traced here and now."""
    lm = MODELS[model]()
    params = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(2, 32))
    aval = jax.ShapeDtypeStruct
    if program == "decode":
        fn, tail = lm.decode_step, (aval((2,), jnp.int32),
                                    aval((2,), jnp.int32))
    else:
        fn, tail = lm.prefill, (aval((16,), jnp.int32), aval((), jnp.int32),
                                aval((), jnp.int32))
    # a function of its own a call: jax finds no earlier trace of it
    return jax.jit(lambda *args: fn(*args)).lower(params, *cache, *tail)


@pytest.mark.parametrize("model,program", sorted(VOCABULARY))
def test_serving_program_vocabulary(model, program):
    """Each serving model's decode and prefill program names its work: the
    outermost scope of every op_name of the lowered program."""
    text = _serving_program(model, program).as_text(debug_info=True)
    outer = {p.split("/")[0] for p in _paths(_op_names(text))}
    assert VOCABULARY[model, program] <= outer, \
        sorted(VOCABULARY[model, program] - outer)


# ---------------------------------------------------------------------------
# no op is moved: the optimized HLO is the unscoped program's, less metadata
# ---------------------------------------------------------------------------


def _assert_same_program(named, bare):
    assert 'op_name="' in named
    named, bare = _less_metadata(named), _less_metadata(bare)
    assert named == bare, next(
        (a, b) for a, b in zip(named.splitlines(), bare.splitlines())
        if a != b)


@pytest.mark.parametrize("model,program", sorted(VOCABULARY))
def test_serving_program_is_the_unscoped_one_less_metadata(
        monkeypatch, model, program):
    named = _serving_program(model, program).compile().as_text()
    assert any(scope in named for scope in ("/head/", "/embed/"))
    with _scopes_taken_out(monkeypatch):
        bare = _serving_program(model, program).compile().as_text()
    assert "/head/" not in bare and "/embed/" not in bare
    _assert_same_program(named, bare)


def test_fused_step_is_the_unscoped_one_less_metadata(monkeypatch):
    named = _fused_step_program(optimized=True)
    assert "Convolution:conv1" in named and "optimizer.update" in named
    with _scopes_taken_out(monkeypatch):
        bare = _fused_step_program(optimized=True)
    assert "Convolution:conv1" not in bare and "optimizer.update" not in bare
    _assert_same_program(named, bare)


@pytest.mark.parametrize("kind", ["fwd", "bwd"])
def test_gluon_program_is_the_unscoped_one_less_metadata(monkeypatch, kind):
    # the blocks' names are a process-wide count: each net under its own
    # prefix, so the two captures differ in nothing but the scopes
    with _scopes_taken_out(monkeypatch):
        _, bare = _gluon_programs(optimized=True)
    _, named = _gluon_programs(optimized=True)
    assert "/Convolution/" in named[kind]
    assert "/Convolution/" not in bare[kind]
    _assert_same_program(named[kind], bare[kind])


# ---------------------------------------------------------------------------
# nothing is kept for a reader
# ---------------------------------------------------------------------------


def test_a_dropped_block_leaves_its_parameters_collectable():
    """The scopes are metadata of the compiled module and nothing else: no
    map of them, no reference to an executable is kept anywhere a cache's
    owner does not reach. A hybridized block that was run and dropped is
    collected, and its parameters' buffers with it."""
    net = _net()
    net.hybridize()
    net(nd.array(np.ones((2, 3, 8, 8), np.float32))).asnumpy()
    cache = net._cached_op._cache
    (key,) = [k for k in cache.keys() if k[0] == "fwd"]
    assert "/Convolution/" in _program_text(cache, key)
    gone = [weakref.ref(net), weakref.ref(cache)] + [
        weakref.ref(p.data()._data)
        for p in net.collect_params().values()]
    assert len(gone) > 4
    del net, cache
    gc.collect()
    assert [ref() for ref in gone] == [None] * len(gone)


def test_the_program_keeps_no_map_of_its_scopes():
    """ISSUE 37, not built: `compile_cache.program_scopes`,
    `CompileCache.entry_scopes`, `analysis.parse_scopes` — the profiler's
    file carries every program's module, so a reader asks the program
    nothing."""
    from mxnet_tpu import analysis

    assert not hasattr(compile_cache, "program_scopes")
    assert not hasattr(compile_cache.CompileCache, "entry_scopes")
    assert not hasattr(analysis, "parse_scopes")


def test_prefill_norms_every_row_and_then_cuts_the_last():
    """`TransformerLM`'s prefill programs norm all `Lb` rows and cut the
    last real one afterwards — the op order they had before the forwards
    shared `_logits` — so the scopes change names, not instructions."""
    lm = _transformer()
    params = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(2, 32))
    aval = jax.ShapeDtypeStruct
    tail = (aval((16,), jnp.int32), aval((), jnp.int32), aval((), jnp.int32))
    for fn, more in ((lm.prefill, ()), (lm.prefill_at,
                                        (aval((), jnp.int32),))):
        eqns = jax.make_jaxpr(fn)(params, *cache, *tail, *more).eqns
        normed = [i for i, e in enumerate(eqns)
                  if "norm" in str(e.source_info.name_stack)]
        cuts = [i for i, e in enumerate(eqns)
                if e.primitive.name == "dynamic_slice"
                and e.outvars[0].aval.shape == (1, lm.cfg.d_model)]
        assert len(cuts) == 1 and cuts[0] > normed[-1]
        assert eqns[normed[-1]].outvars[0].aval.shape == (16, lm.cfg.d_model)


# ---------------------------------------------------------------------------
# the admission span says its own size
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("on", [False, True], ids=["tracing_off",
                                                   "tracing_on"])
def test_prefill_span_carries_bucket_and_tokens(monkeypatch, on):
    from mxnet_tpu.serving.generation import GenerationEngine

    lm = _transformer()
    params = lm.init_params(jax.random.PRNGKey(0))
    prev = tracing.enabled()
    tracing.enable(on)
    tracing.reset()
    annotated = []
    real = tracing.span

    def spying(name, *args, **kwargs):
        annotated.append((name, kwargs))
        return real(name, *args, **kwargs)

    monkeypatch.setattr(tracing, "span", spying)
    try:
        eng = GenerationEngine(lm, params, max_slots=2, max_len=48,
                               buckets=(8, 16))
        prompt = np.arange(1, 12, dtype=np.int32)       # 11 tokens
        eng.submit(prompt, max_new_tokens=2).result(timeout=120)
        eng.close()
        events, _ = tracing.take_events()
    finally:
        tracing.reset()
        tracing.enable(prev)
    (args,) = [kw for name, kw in annotated if name == "generation.prefill"]
    assert args["bucket"] == 16 and args["tokens"] == 11
    records = [e for e in events if e.get("name") == "generation.prefill"]
    if on:
        assert records and all(r["args"]["bucket"] == 16
                               and r["args"]["tokens"] == 11
                               for r in records)
    else:
        assert events == []
