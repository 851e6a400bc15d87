"""The Pallas kernels of the main path, compiled at real widths for a
DESCRIBED TPU v5e (no chip attached): what interpret mode cannot show — a
slice the Mosaic tiling refuses, more fast memory than a kernel may use — is
refused here, at no chip time (on-chip-measurement guide, section 2,
rehearsal 3). A compile that passes is not a chip run; `chip_smoke.py` is.

Also pins the shape tests that decide, before the call, whether a kernel
applies: lengths no accepted block divides and K/V beyond the fast-memory
budget go to the XLA path instead of to a compiler refusal.
"""
import contextlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp
# no chip is opened here, only the compiler: several test processes (xdist
# workers) may load libtpu at once instead of queueing on its lockfile
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.gradient_compression import quantize_2bit_pallas
from mxnet_tpu.ops import pallas_attention as pa


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2. The persistent compile cache is
    off around these compiles: an entry for a described chip cannot be read
    back without one."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"the v5e topology cannot be described here: {e!r}")
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


@contextlib.contextmanager
def _counted(prefix, kinds):
    """Telemetry on around a trace: yields a dict that holds, once the
    block has ended, how far each counter `prefix + kind` moved in it."""
    from mxnet_tpu import telemetry

    counters = {k: telemetry.counter(prefix + k) for k in kinds}
    before = {k: c.value for k, c in counters.items()}
    was, moved = telemetry.enabled(), {}
    telemetry.enable()
    try:
        yield moved
    finally:
        telemetry.enable(was)
        moved.update({k: c.value - before[k] for k, c in counters.items()})


KV128_BODIES = ("attn.decode.kv128.", ("one_query", "grouped"))
SLAB_BODIES = ("attn.decode.slab.", ("one_query", "grouped"))


@pytest.mark.parametrize("shape,dtype", [
    ((8, 1024, 12, 64), jnp.bfloat16),     # GPT-2 small, the serve width
    ((8, 1024, 12, 64), jnp.float32),
    ((4, 2048, 16, 128), jnp.bfloat16),
], ids=["gpt2s-bf16", "gpt2s-f32", "L2048-d128-bf16"])
def test_flash_forward_compiles_for_v5e(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    blocks = pa.flash_blocks(shape, shape, dtype, causal=True)
    assert blocks == (128, 128)
    text = _compiled_text(
        lambda q, k, v: pa.flash_attention(q, k, v, causal=True,
                                           block_q=blocks[0],
                                           block_k=blocks[1]), x, x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
def test_block_partials_compile_for_v5e(one_chip, with_bias):
    """The ring hop at the sp=2 shard of the serve width."""
    shape = (2, 512, 12, 64)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    scale = 1.0 / np.sqrt(shape[-1])
    assert pa.partial_blocks(shape, shape, jnp.bfloat16) == (128, 128)
    if with_bias:
        bias = jax.ShapeDtypeStruct((1, 1, 512, 512), jnp.float32,
                                    sharding=one_chip)
        text = _compiled_text(
            lambda q, k, v, b: pa.block_partials_pallas(q, k, v, b, scale),
            x, x, x, bias)
    else:
        text = _compiled_text(
            lambda q, k, v: pa.block_partials_pallas(q, k, v, None, scale),
            x, x, x)
    assert "tpu_custom_call" in text


def test_quantize_2bit_compiles_for_v5e(one_chip):
    """2^20 values, compiled (not interpreted): Mosaic accepts the 2-D
    lane-aligned blocks and the 32-bit stores. Bit-equality with
    `quantize_2bit` is test_gradient_compression.py's."""
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda g, r: quantize_2bit_pallas(g, r, 0.5), x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("length,why", [
    (1000, "no multiple-of-8 block <= 128 divides it for the ring's q block"),
    (16384, "whole K/V would exceed the kernel's fast-memory budget"),
], ids=["L1000", "L16384"])
def test_shape_test_routes_to_xla(length, why):
    """Decided by shape, before any call: these lengths never reach the
    compiler (it refused them: block 125 is not a multiple of 8; 16.16M of
    scoped vmem against a 16.00M limit)."""
    shape = (1, length, 16, 128)
    if length == 1000:
        # 1000 = 8 x 125: the flash forward still tiles it with block 40,
        # the ring hop (q block is a LANE dim: multiple of 128) cannot
        assert pa.flash_blocks(shape, shape, jnp.bfloat16, True) == (40, 40)
        assert pa.partial_blocks(shape, shape, jnp.bfloat16) is None, why
        with pytest.raises(ValueError):
            pa.block_partials_pallas(
                jnp.zeros((1, length, 1, 8)), jnp.zeros((1, length, 1, 8)),
                jnp.zeros((1, length, 1, 8)), None, 1.0, interpret=True)
    else:
        assert pa.flash_blocks(shape, shape, jnp.bfloat16, True) is None, why
        assert pa.partial_blocks(shape, shape, jnp.bfloat16) is None, why


def test_shape_test_accepts_only_blocks_the_tiling_accepts():
    assert pa._divisor_block(1024) == 128
    assert pa._divisor_block(100) == 100            # the full dimension
    assert pa._divisor_block(320) == 80             # multiple of 8
    assert pa._divisor_block(1000) == 40            # not 125
    assert pa._divisor_block(1009) is None          # prime: no block at all
    assert pa._divisor_block(320, multiple=128) is None
    assert pa._divisor_block(512, multiple=128) == 128
    # cross-length causal attention is the XLA path's (sequence ENDS align)
    assert pa.flash_blocks((1, 32, 4, 64), (1, 64, 4, 64), jnp.float32,
                           True) is None


def test_flash_forward_odd_block_compiles_for_v5e(one_chip):
    """The largest accepted block that is not 128: L=1000 tiles with 40."""
    shape = (2, 1000, 4, 64)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    bq, bk = pa.flash_blocks(shape, shape, jnp.bfloat16, True)
    text = _compiled_text(
        lambda q, k, v: pa.flash_attention(q, k, v, causal=True, block_q=bq,
                                           block_k=bk), x, x, x)
    assert "tpu_custom_call" in text


def test_resident_budget_boundary_compiles_for_v5e(one_chip):
    """The longest K/V the shape test lets through (12 MiB resident of the
    16 MiB the compiler allows) does compile."""
    shape = (1, 12288, 8, 128)
    assert pa.flash_blocks(shape, shape, jnp.bfloat16, True) == (128, 128)
    assert pa.flash_blocks((1, 12416, 8, 128), (1, 12416, 8, 128),
                           jnp.bfloat16, True) is None
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: pa.flash_attention(q, k, v, causal=True), x, x, x)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the decode program at the serving cells' real widths (ISSUE 24)
# ---------------------------------------------------------------------------

_HLO_RESULT = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                         r"([\w\-]+)\(")


def _page_sized_ops(text, page):
    """HLO instructions that move a slab page or more: a copy, slice,
    dynamic-(update-)slice, scatter or transpose whose result is a K/V-shaped
    array (rank >= 4) of at least `page` elements."""
    moved = []
    for line in text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m or m.group(2) not in (
                "copy", "slice", "dynamic-slice", "dynamic-update-slice",
                "scatter", "transpose", "copy-start"):
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if len(dims) >= 4 and int(np.prod(dims)) >= page:
            moved.append(line.strip()[:160])
    return moved


_SLAB_KERNEL = re.compile(r"%decode_update_attend[.\d]* = .*? "
                          r"custom-call\(([^)]*)\)")
_KV128_KERNEL = re.compile(r"%kv128_attend[.\d]* = .*? "
                           r"custom-call\(([^)]*)\)")
_HLO_OPERAND = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*? ([\w\-]+)\("
                          r"(%[\w.\-]+)?", re.M)


def _slab_kernel_calls(text, kernel=_SLAB_KERNEL):
    """The operands of every `decode_update_attend` custom call of a compiled
    program (or of the kernel whose calls `kernel` matches), by the
    instruction that made them: the grid's bound, the work
    list's two members and the positions first, then the layer, the tick's
    rows and the slabs. XLA's own copies and bitcasts are looked through (it
    stages a list in fast memory for a later call: the same list)."""
    made = {m.group(1): m.group(2, 3) for m in _HLO_OPERAND.finditer(text)}

    def source(name):
        while made.get(name, ("",))[0] in ("copy", "copy-start", "copy-done",
                                           "bitcast"):
            name = made[name][1]
        return name

    return [[source(re.sub(r"/\*.*?\*/", "", name).strip())
             for name in m.group(1).split(",")]
            for m in kernel.finditer(text)]


@pytest.mark.parametrize("slots,cfg", [
    (32, dict(vocab_size=50257, d_model=1600, n_heads=25, d_ff=6400)),
    (8, dict(vocab_size=50257, d_model=768, n_heads=12, d_ff=3072)),
], ids=["gpt2xl-32x25x64", "gpt2s-8x12x64"])
def test_decode_program_touches_no_slab_page_on_v5e(one_chip, monkeypatch,
                                                    slots, cfg):
    """The engine's decode program at GPT-2 XL's and GPT-2 small's widths
    (1,024 rows, bf16) but 2 layers, so that it compiles in seconds: the
    slab kernel is in it, both slabs are aliased input to output, and no
    XLA op copies, slices, scatters into or re-lays a slab page. (The page
    is the full size; the full depth's memory figure is
    `benchmark/rehearse_compile.py`'s.)"""
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig

    # code that asks jax.default_backend() sees "cpu" here
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    config = TransformerLMConfig(n_layers=2, max_len=1024, dtype="bfloat16",
                                 **cfg)
    dev = next(iter(one_chip.device_set))
    lm = TransformerLM(config, par.create_mesh(devices=[dev], dp=1))
    hd = config.d_model // config.n_heads
    slab_shape = (slots, 2, config.n_heads, 1024, hd)
    assert lm.decode_block(slab_shape, jnp.bfloat16) == 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    host_lm = TransformerLM(config, par.create_mesh(
        devices=jax.devices()[:1], dp=1))
    params = {k: sds(v.shape, v.dtype) for k, v in jax.eval_shape(
        host_lm.init_params, jax.random.PRNGKey(0)).items()}
    slab = sds(slab_shape, jnp.bfloat16)

    def fn(params, ck, cv, tokens, positions):      # the engine's wrapper
        logits, ck, cv = lm.decode_step(params, ck, cv, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), ck, cv

    with _counted(*SLAB_BODIES) as went:
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
            params, slab, slab, sds((slots,), jnp.int32),
            sds((slots,), jnp.int32)).compile()
    # one query a head: the per-lane body, once a layer's trace (ISSUE 48)
    assert went == {"one_query": 2, "grouped": 0}
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2        # one kernel a layer
    # ... exactly one, whose grid (a step a live block: ISSUE 41) and work
    # list are built once a tick, not once a layer: every call takes the
    # same bound, the same two lists and the same positions
    calls = _slab_kernel_calls(text)
    assert len(calls) == 2
    assert len({tuple(operands[:4]) for operands in calls}) == 1
    page = slots * config.n_heads * 1024 * hd
    # a dynamic-update-slice on a slab would be one of these
    assert _page_sized_ops(text, page) == []
    ma = compiled.memory_analysis()
    slab_bytes = 2 * 2 * page * 2                    # K and V, 2 layers, bf16
    assert ma.alias_size_in_bytes >= slab_bytes      # donated and aliased
    # no temporary of a page's size (2 bytes an element), nor of the
    # embedding table's: its rows are sliced where the table lies
    assert ma.temp_size_in_bytes < page


# ---------------------------------------------------------------------------
# the hybrid model's decode program at granite-4.0-h-micro's widths (ISSUE 26)
# ---------------------------------------------------------------------------

def test_hybrid_decode_program_updates_state_in_place_on_v5e(one_chip,
                                                             monkeypatch):
    """The engine's decode program for one period (10 layers: 9 Mamba-2, 1
    grouped-query attention) of granite-4.0-h-micro and the next period's
    attention layer, at its published widths, 32 slots x 4,096 rows: it
    compiles for the v5e; both kernels are in it (the K/V slab's with 4
    queries a head, the recurrent state's); the two slab kernels share one
    grid, built once a tick; every member of the cache is aliased input to
    output; and no XLA op copies, slices, updates or re-lays a K/V page or
    the recurrent-state slab — each live slot's state is read once and
    written once, by the kernel, where it lies. The trace counts which way
    each Mamba layer's state update went."""
    import json
    import os

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite_4_0_h_micro.json")) as f:
        published = json.load(f)
    published["layer_types"] = (published["layer_types"][:10]    # a period
                                + ["attention"])
    config = HybridLMConfig.from_config(published)
    slots, rows = 32, 4096
    dev = next(iter(one_chip.device_set))
    lm = HybridLM(config, par.create_mesh(devices=[dev], dp=1))
    host_lm = HybridLM(config, par.create_mesh(devices=jax.devices()[:1],
                                               dp=1))

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: sds(v) for k, v in jax.eval_shape(
        host_lm.init_params, jax.random.PRNGKey(0)).items()}
    cache = tuple(sds(v) for v in jax.eval_shape(
        lambda: host_lm.init_cache(slots, rows)))
    assert [c.shape for c in cache] == [
        (32, 2, 8, 4096, 64), (32, 2, 8, 4096, 64), (32, 9, 64, 64, 128),
        (32, 9, 3, 4352)]
    assert lm.decode_block(cache[0].shape, cache[0].dtype) == 512
    assert lm.state_kernel(cache[2].shape, cache[2].dtype)

    def fn(params, cache, tokens, positions):       # the engine's wrapper
        logits, *cache = lm.decode_step(params, *cache, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), tuple(cache)

    ints = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    with _counted("mamba.state_update.", ("kernel", "xla")) as went, \
            _counted(*SLAB_BODIES) as body:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, ints, ints).compile()
    assert went == {"kernel": 9, "xla": 0}
    # 32 queries over 8 K/V heads: the grouped body, on the MXU (ISSUE 48)
    assert body == {"one_query": 0, "grouped": 2}
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 11      # 9 state + 2 K/V kernels
    assert len(re.findall(r"%mamba_state_update[.\d]* = ", text)) == 9
    # the attention layers' kernels take the tick's rows themselves (no
    # dynamic-update-slice, copy or re-laying of a K/V page) and one grid
    calls = _slab_kernel_calls(text)
    assert len(calls) == 2
    assert len({tuple(operands[:4]) for operands in calls}) == 1
    assert [line for line in _page_sized_ops(text, slots * 8 * rows * 64)
            if "bf16[" in line] == []
    state = slots * 64 * 64 * 128                   # one layer's page
    moved = [line for line in _page_sized_ops(text, state)
             if "f32[" in line]
    assert moved == []
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes    # all four, whole
    assert ma.temp_size_in_bytes < state * 4        # no temporary of a page


# ---------------------------------------------------------------------------
# the gluon step's one backward program at ResNet-50's widths (ISSUE 27)
# ---------------------------------------------------------------------------

def test_recorded_call_backward_returns_no_residual_on_v5e(one_chip):
    """Stage 2 of the model zoo's ResNet-50 v1 (four BottleneckV1 units of
    width 512 on a [64, 256, 56, 56] input, bfloat16): forward and pullback
    of the recorded call as the one program `backward()` runs. It compiles
    for the v5e, and what it returns is the gradients and the stage's
    output — the activations the pullback needs (0.45 GB) are temporaries
    of the program, where the forward+vjp program returned them."""
    from mxnet_tpu import nd
    from mxnet_tpu._cached_op import backward_program
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1

    batch = 64
    stage = ResNetV1(BottleneckV1, [3, 4, 6, 3],
                     [64, 256, 512, 1024, 2048]).features[5]
    stage.initialize()
    stage.hybridize()
    stage.cast("bfloat16")
    stage(nd.zeros((1, 256, 56, 56), dtype="bfloat16"))  # shapes, the op
    op = stage._cached_op
    params = [p.data() for p in stage._cached_graph_params]
    assert len(stage) == 4 and len(params) == 73

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaves = tuple(sds(p.shape, p.dtype) for p in params) \
        + (sds((batch, 256, 56, 56), jnp.bfloat16),)
    sig = tuple((a.shape, a.dtype) for a in leaves)
    learned = tuple(i for i, p in enumerate(stage._cached_graph_params)
                    if p.grad_req != "null")
    key = sds((2,), jnp.uint32)
    program = backward_program(
        jaxprs=[op._trace(True, sig)(key, *leaves).jaxpr],
        wiring=(tuple(("l", i) for i in range(len(leaves))),),
        wanted=learned, heads=((0, 0, False),), emit=((0, 0),))
    compiled = program.lower((key,), leaves, ()).compile()
    (emitted,), grads = compiled.out_info
    assert emitted.shape == (batch, 512, 28, 28) and len(grads) == 47

    def nbytes(avals):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in avals)

    ma = compiled.memory_analysis()
    exact = nbytes(grads) + nbytes([emitted])
    # tiling pads the small per-channel vectors; an activation is 51 MB
    assert exact <= ma.output_size_in_bytes <= exact + 2e6
    assert ma.temp_size_in_bytes > 4 * nbytes([emitted])
    live = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        + ma.temp_size_in_bytes
    assert live < 4e9


# ---------------------------------------------------------------------------
# the latent-attention expert model's decode program at sarvam-105b's widths
# (ISSUE 31)
# ---------------------------------------------------------------------------

def test_latent_moe_decode_program_compiles_for_v5e(one_chip, monkeypatch):
    """The engine's decode program for the dense layer and one expert layer
    of `sarvam_105b_ep4` at its published widths, 32 slots x 16,384 rows: it
    compiles for the v5e; the latent kernel is in it once a layer (the trace
    counts which way each layer went) and the grouped matmul `gmm` twice an
    expert layer; every member of the cache is aliased input to output; and
    no XLA op touches a latent slab at all — no `dynamic-update-slice` and
    no `dynamic-slice` on one (the kernel takes the tick's row and sends a
    tile back through its aliased outputs, ISSUE 38), no copy, and the only
    instructions whose result is a slab are the kernel's."""
    import json
    import os

    from mxnet_tpu import parallel as par
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import LatentMoELM, LatentMoELMConfig

    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmark", "configs",
                           "sarvam_105b_ep4.json")) as f:
        published = json.load(f)
    published["num_hidden_layers"] = 2          # the dense + one expert layer
    slots, rows = 32, 16384
    config = LatentMoELMConfig.from_config(published, max_len=rows)
    assert (config.num_experts, config.experts_held, config.expert_first) \
        == (128, 32, 0)
    dev = next(iter(one_chip.device_set))
    lm = LatentMoELM(config, par.create_mesh(devices=[dev], dp=1))
    host_lm = LatentMoELM(config, par.create_mesh(devices=jax.devices()[:1],
                                                  dp=1))

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: sds(v) for k, v in jax.eval_shape(
        host_lm.init_params, jax.random.PRNGKey(0)).items()}
    cache = tuple(sds(v) for v in jax.eval_shape(
        lambda: host_lm.init_cache(slots, rows)))
    assert [c.shape for c in cache] == [
        (32, 2, 16384, 512), (32, 2, 64, 16384), (32, 1, 8)]
    assert lm.decode_block(cache[0].shape, cache[0].dtype) == 2048

    def fn(params, cache, tokens, positions):       # the engine's wrapper
        logits, *cache = lm.decode_step(params, *cache, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), tuple(cache)

    ints = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    went = {k: telemetry.counter("mla.attend." + k)
            for k in ("kernel", "xla")}
    before = {k: c.value for k, c in went.items()}
    was = telemetry.enabled()
    telemetry.enable()
    try:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, ints, ints).compile()
    finally:
        telemetry.enable(was)
    assert {k: c.value - before[k] for k, c in went.items()} == {
        "kernel": 2, "xla": 0}
    text = compiled.as_text()
    assert len(re.findall(r"%latent_attend[.\d]* = ", text)) == 2
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 2
    page = slots * rows * 64                        # the smaller slab's page
    assert _page_sized_ops(text, page) == []
    # nothing slices or updates a slab (whatever the size of its result),
    # and a slab is the result of a parameter or of the kernel alone
    slabs = ("bf16[32,2,16384,512]", "bf16[32,2,64,16384]")
    touching = [line.strip()[:200] for line in text.splitlines()
                if any(s in line for s in slabs)]
    assert [line for line in touching
            if re.search(r" dynamic-(update-)?slice\(", line)] == []
    made = set()                        # opcodes whose result is a slab
    for line in touching:
        _, eq, rest = line.partition(" = ")
        m = re.search(r"\s([a-z][a-z-]*)\(", " " + rest)
        if eq and m and any(s in rest[:m.start()] for s in slabs):
            made.add(m.group(1))
    assert "custom-call" in made
    assert made <= {"parameter", "custom-call", "get-tuple-element",
                    "tuple"}, made
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes    # all three, whole
    assert ma.temp_size_in_bytes < page * 2         # no temporary of a page


def test_latent_prefill_attention_compiles_for_v5e(one_chip):
    """The prefill attention kernel at sarvam-105b's widths — 64 heads,
    keys of 128 + a shared 64, values of 128 — over the 8,192 bucket."""
    from mxnet_tpu.ops import pallas_latent

    heads, length = 64, 8192
    block = pallas_latent.prefill_block(length)
    assert block == 1024

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    text = _compiled_text(
        lambda qn, qr, kn, kr, v: pallas_latent.prefill_attend(
            qn, qr, kn, kr, v, block=block, scale=0.1),
        sds(heads, length, 128), sds(heads, length, 64),
        sds(heads, length, 128), sds(length, 64), sds(heads, length, 128))
    assert len(re.findall(r"%latent_prefill_attend[.\d]* = ", text)) == 1


# ---------------------------------------------------------------------------
# the window/full-attention expert model's programs at Mellum2-12B's widths
# (ISSUE 33): one period — three window layers and a full one
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mellum_period(one_chip):
    """`(lm, params, cache)` of one period of `mellum2_12b_l8` at its
    published widths, 32 slots x 16,384 positions, as shapes on the
    described chip."""
    import json

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2_12b_l8.json")) as f:
        published = json.load(f)
    assert len(published["layer_types"]) == 28      # carried whole
    published["num_hidden_layers"] = 4
    config = WindowMoELMConfig.from_config(published, max_len=16384)
    assert config.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert (config.num_experts, config.experts_held, config.expert_first) \
        == (64, 64, 0)
    dev = next(iter(one_chip.device_set))
    lm = WindowMoELM(config, par.create_mesh(devices=[dev], dp=1))
    host_lm = WindowMoELM(config, par.create_mesh(devices=jax.devices()[:1],
                                                  dp=1))

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: sds(v) for k, v in jax.eval_shape(
        host_lm.init_params, jax.random.PRNGKey(0)).items()}
    cache = tuple(sds(v) for v in jax.eval_shape(
        lambda: host_lm.init_cache(32, 16384)))
    assert [c.shape for c in cache] == [
        (32, 1, 4, 16384, 128), (32, 1, 4, 16384, 128),
        (32, 3, 4, 1024, 128), (32, 3, 4, 1024, 128), (32, 4, 8)]
    return lm, params, cache


def test_window_moe_decode_program_compiles_for_v5e(one_chip, monkeypatch,
                                                    mellum_period):
    """The engine's decode program: it compiles for the v5e; the `hd`-minor
    slab kernel is in it once a layer (ring members and the full one alike)
    and the grouped matmul `gmm` twice a layer — K = 2304 and 896 are not
    multiples of 512 and must not fall to `lax.ragged_dot`; every member of
    the cache is aliased input to output; no XLA op copies, slices,
    scatters into or re-lays a page of either kind (the new row is merged
    inside the kernel)."""
    lm, params, cache = mellum_period
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.decode_block(cache[0].shape, cache[0].dtype) == 1024
    assert lm.decode_block(cache[2].shape, cache[2].dtype) == 1024

    def fn(params, cache, tokens, positions):       # the engine's wrapper
        logits, *cache = lm.decode_step(params, *cache, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), tuple(cache)

    ints = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    with _counted(*KV128_BODIES) as bodies:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, ints, ints).compile()
    # 8 queries a K/V head: every layer's trace took the grouped body
    assert bodies == {"one_query": 0, "grouped": 4}
    text = compiled.as_text()
    assert len(re.findall(r"%kv128_attend[.\d]* = ", text)) == 4
    # its grid (a step a live block: ISSUE 45) and work list are built once
    # a MEMBER a tick, not once a layer: the three rings' calls take the
    # same bound, lists and positions, the full layer's its own
    calls = _slab_kernel_calls(text, _KV128_KERNEL)
    assert len(calls) == 4
    assert len({tuple(operands[:4]) for operands in calls}) == 2
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 8
    assert "ragged-dot" not in text
    ring_page = 32 * 4 * 1024 * 128                 # the smaller page
    assert _page_sized_ops(text, ring_page) == []
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes    # all five, whole
    assert ma.temp_size_in_bytes < ring_page * 2


def test_window_moe_prefill_program_compiles_for_v5e(one_chip, monkeypatch,
                                                     mellum_period):
    """The engine's 16,384-token prefill program: the band kernel once a
    layer — a window layer's grid holds 3 key blocks of 512 a query block,
    the full layer's 16 of 1,024 — and the grouped matmul over chunks of
    4,096 tokens; it fits the chip beside the weights and the cache."""
    from mxnet_tpu.ops import pallas_window

    lm, params, cache = mellum_period
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.prefill_block(16384, 1024) == 512
    assert pallas_window.band_steps(16384, 512, 1024) == 3
    assert lm.prefill_block(16384) == 1024

    def fn(params, cache, toks, length, slot):      # the engine's wrapper
        logits, *cache = lm.prefill(params, *cache, toks, length, slot)
        return jnp.argmax(logits).astype(jnp.int32), tuple(cache)

    toks = jax.ShapeDtypeStruct((16384,), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, toks, scalar, scalar).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%swa_prefill_attend[.\d]* = ", text)) == 4
    assert "%gmm" in text and "ragged-dot" not in text
    ma = compiled.memory_analysis()
    live = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert live < 9e9, live         # one period of two: 5.5 GB resident


# ---------------------------------------------------------------------------
# the same model as the afmoe block at Trinity-Large-Preview's widths (ISSUE
# 39): 8 K/V heads with 6 queries each, rings of 4,096, experts of 3072 x 3072
# ---------------------------------------------------------------------------

def test_kv_block_halves_for_eight_heads():
    """`kv_block`'s budget (the K and V blocks of every head, double
    buffered, in 4 MiB) gives 8 K/V heads a block of 512 rows where 4 heads
    take 1,024: a full member of 16,384 rows is 32 grid steps a slot, a ring
    of 4,096 is 8."""
    from mxnet_tpu.ops import pallas_window

    assert pallas_window.kv_block((32, 1, 8, 16384, 128), jnp.bfloat16) == 512
    assert pallas_window.kv_block((32, 4, 8, 4096, 128), jnp.bfloat16) == 512
    assert pallas_window.kv_block((32, 1, 4, 16384, 128),
                                  jnp.bfloat16) == 1024
    assert pallas_window.band_block(16384, 4096) == 1024
    assert pallas_window.band_steps(16384, 1024, 4096) == 5


@pytest.mark.parametrize("slab", [(32, 1, 8, 16384, 128),
                                  (32, 4, 8, 4096, 128)],
                         ids=["full-16384", "rings-4096"])
def test_kv128_attend_compiles_at_six_queries_a_head_for_v5e(one_chip, slab):
    """The decode kernel at 8 K/V heads x 6 query heads (a `[6, 128]` query
    tile: neither a power of two nor a packed bfloat16 tile) over both of
    Trinity's members."""
    from mxnet_tpu.ops import pallas_window

    block = pallas_window.kv_block(slab, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, k, v, slab_k, slab_v, positions):
        return pallas_window.kv_update_attend(
            q, k, v, slab_k, slab_v, jnp.int32(0), positions, block=block,
            scale=128 ** -0.5)

    text = jax.jit(fn, donate_argnums=(3, 4)).lower(
        sds((32, 48, 128)), sds((32, 8, 128)), sds((32, 8, 128)), sds(slab),
        sds(slab), sds((32,), jnp.int32)).compile().as_text()
    assert len(re.findall(r"%kv128_attend[.\d]* = ", text)) == 1


@pytest.mark.parametrize("window", [4096, None])
@pytest.mark.parametrize("length", [2048, 4096, 8192, 16384])
def test_swa_prefill_attend_compiles_at_48_heads_for_v5e(one_chip, length,
                                                         window):
    """The prefill attention kernel at 48 query / 8 K/V heads for every
    bucket of the cell, over the band of 4,096 and causal."""
    from mxnet_tpu.ops import pallas_window

    block = pallas_window.band_block(length, window)
    assert block == 1024

    def sds(heads):
        return jax.ShapeDtypeStruct((heads, length, 128), jnp.bfloat16,
                                    sharding=one_chip)

    text = _compiled_text(
        lambda q, k, v: pallas_window.band_prefill_attend(
            q, k, v, block=block, scale=128 ** -0.5, window=window),
        sds(48), sds(8), sds(8))
    assert "swa_prefill_attend" in text


@pytest.mark.parametrize("rows", [128, 16384])
@pytest.mark.parametrize("n", [6144, 3072])
def test_grouped_matmul_compiles_at_k_3072_for_v5e(one_chip, monkeypatch,
                                                   rows, n):
    """The grouped product over 32 held experts of 3072 x 6144 (gate | up)
    and 3072 x 3072 (down) for a tick's 128 rows and a prefill chunk's
    16,384: the Pallas `gmm`, tiled from the shapes, not `lax.ragged_dot`."""
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import experts

    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert experts.gmm_tiling(rows, 3072, n, 2) == (
        (128, 512, 3072) if rows == 128 else (256, 1024, 1024))
    mesh = par.create_mesh(devices=[next(iter(one_chip.device_set))], dp=1)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(
        lambda x, w, sizes: experts.grouped_product(x, w, sizes, mesh),
        sds((rows, 3072)), sds((32, 3072, n)), sds((32,), jnp.int32))
    assert "%gmm" in text and "ragged-dot" not in text


@pytest.fixture(scope="module")
def trinity_share(one_chip):
    """`(lm, params, cache)` of `trinity_large_ep8` as the benchmark runs it
    — 5 layers, 32 of 256 experts, 25,024 vocabulary rows, 32 slots x 16,384
    positions — as shapes on the described chip."""
    import json

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity_large_ep8.json")) as f:
        published = json.load(f)
    assert len(published["layer_types"]) == 60      # carried whole
    config = WindowMoELMConfig.from_config(published, max_len=16384)
    assert config.layer_types == ("sliding_attention",) * 3 \
        + ("full_attention", "sliding_attention")
    assert (config.num_experts, config.experts_held, config.expert_first,
            config.num_dense_layers) == (256, 32, 0, 1)
    dev = next(iter(one_chip.device_set))
    lm = WindowMoELM(config, par.create_mesh(devices=[dev], dp=1))
    host_lm = WindowMoELM(config, par.create_mesh(devices=jax.devices()[:1],
                                                  dp=1))

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: sds(v) for k, v in jax.eval_shape(
        host_lm.init_params, jax.random.PRNGKey(0)).items()}
    assert params["l0.wqkv"].shape == (3072, 14336)     # q | k | v | gate
    cache = tuple(sds(v) for v in jax.eval_shape(
        lambda: host_lm.init_cache(32, 16384)))
    assert [c.shape for c in cache] == [
        (32, 1, 8, 16384, 128), (32, 1, 8, 16384, 128),
        (32, 4, 8, 4096, 128), (32, 4, 8, 4096, 128), (32, 4, 4)]
    return lm, params, cache


def test_afmoe_decode_program_compiles_for_v5e(one_chip, monkeypatch,
                                               trinity_share):
    """The engine's decode program of the afmoe block: the slab kernel once
    a layer (four rings and the full member) at 6 queries a head, the
    grouped matmul twice an EXPERT layer (none in the dense one), every
    member of the cache aliased input to output, no XLA op on a page."""
    lm, params, cache = trinity_share
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.decode_block(cache[0].shape, cache[0].dtype) == 512
    assert lm.decode_block(cache[2].shape, cache[2].dtype) == 512

    def fn(params, cache, tokens, positions):       # the engine's wrapper
        logits, *cache = lm.decode_step(params, *cache, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), tuple(cache)

    ints = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, ints, ints).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%kv128_attend[.\d]* = ", text)) == 5
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 8
    assert "ragged-dot" not in text
    for scope in ("attn.gate", "attn.qknorm", "moe.shared", "/mlp/"):
        assert scope in text, scope
    page = 32 * 8 * 4096 * 128                      # a ring's page
    assert _page_sized_ops(text, page) == []
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes    # all five, whole
    assert ma.temp_size_in_bytes < page * 2
    # what stands on the chip: 8.65 GB of weights + 4.29 GB of cache
    assert 12.9e9 < ma.argument_size_in_bytes < 13.0e9


def test_afmoe_prefill_program_compiles_for_v5e(one_chip, monkeypatch,
                                                trinity_share):
    """The engine's 16,384-token prefill program of the afmoe block: the
    band kernel once a layer (a window layer's grid holds 5 key blocks of
    1,024 a query block), the grouped matmul over chunks of 4,096 tokens,
    the dense MLP and the shared expert a chunk at a time; its temporaries
    fit beside the 12.95 GB that stand."""
    lm, params, cache = trinity_share
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.prefill_block(16384, 4096) == 1024
    assert lm.prefill_block(16384) == 1024

    def fn(params, cache, toks, length, slot):      # the engine's wrapper
        logits, *cache = lm.prefill(params, *cache, toks, length, slot)
        return jnp.argmax(logits).astype(jnp.int32), tuple(cache)

    toks = jax.ShapeDtypeStruct((16384,), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, toks, scalar, scalar).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%swa_prefill_attend[.\d]* = ", text)) == 5
    assert "%gmm" in text and "ragged-dot" not in text
    ma = compiled.memory_analysis()
    live = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert live < 15.0e9, live      # 12.95 GB stand; temporaries 1.7 GB


# ---------------------------------------------------------------------------
# the hybrid model as the Olmo-Hybrid block at Olmo-Hybrid-7B's widths (ISSUE
# 42): 30 linear heads of a 96 x 192 float32 state, 30 K/V heads of 128 with
# one query each, 32 slots x 2,048 positions
# ---------------------------------------------------------------------------

def test_gdn_state_update_compiles_at_its_bytes_for_v5e(one_chip):
    """The delta rule's state kernel over the cell's whole state slab: it
    compiles for the v5e, the slab is aliased input to output, and its bytes
    on the chip are the count's — `[.., 96, 30 x 192]` float32 is 45 whole
    lane rows a sublane row, so nothing is padded: within 2% of 849 MB."""
    from mxnet_tpu.ops import pallas_ssm

    slots, layers, heads, dk, dv = 32, 12, 30, 96, 192
    slab = (slots, layers, dk, heads * dv)
    assert pallas_ssm.gdn_update_applies(slab, jnp.float32, heads)

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(slab, alpha, beta, q, k, v, alive):
        return pallas_ssm.gdn_state_update(slab, jnp.int32(5), alpha, beta,
                                           q, k, v, alive)

    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        sds(*slab), sds(slots, heads), sds(slots, heads),
        sds(slots, heads, dk), sds(slots, heads, dk), sds(slots, heads, dv),
        sds(slots, dtype=jnp.bool_)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gdn_state_update[.\d]* = ", text)) == 1
    assert "f32[32,12,96,5760]{3,2,1,0:T(8,128)}" in text
    count = int(np.prod(slab)) * 4
    assert abs(count / 1e6 - 849.3) < 0.1
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= count
    # the slab and the tick's few small operands, nothing else: a padded
    # layout (256 lanes a head) would be 1.13 GB
    assert count <= ma.argument_size_in_bytes <= 1.02 * count
    assert ma.temp_size_in_bytes < count // (slots * layers)    # no page


def test_kv128_attend_compiles_at_one_query_a_head_for_v5e(one_chip):
    """The decode kernel at 30 K/V heads with ONE query each (a `[1, 128]`
    query tile a head, the step's 30 score rows one `(30, 128)` scratch:
    ISSUE 44's body, which hands back `[S, 1, 30, 128]` where the grouped
    body hands back `[S, H, G, 128]`) over the cell's full member, whose
    block `kv_block`'s budget puts at 128 rows."""
    from mxnet_tpu.ops import pallas_window

    slab = (32, 4, 30, 2048, 128)
    block = pallas_window.kv_block(slab, jnp.bfloat16)
    assert block == 128

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, k, v, slab_k, slab_v, positions):
        return pallas_window.kv_update_attend(
            q, k, v, slab_k, slab_v, jnp.int32(2), positions, block=block,
            scale=128 ** -0.5)

    text = jax.jit(fn, donate_argnums=(3, 4)).lower(
        sds((32, 30, 128)), sds((32, 30, 128)), sds((32, 30, 128)),
        sds(slab), sds(slab), sds((32,), jnp.int32)).compile().as_text()
    assert len(re.findall(r"%kv128_attend[.\d]* = ", text)) == 1
    call = re.search(r"%kv128_attend[.\d]* = \((\S+)", text).group(1)
    assert call.startswith("f32[32,1,30,128]"), call


@pytest.mark.parametrize("length", [256, 512, 1024])
def test_swa_prefill_attend_compiles_at_30_heads_for_v5e(one_chip, length):
    """The prefill attention kernel at 30 heads, no window, group 1, for
    every bucket of the cell."""
    from mxnet_tpu.ops import pallas_window

    block = pallas_window.band_block(length)
    assert block == length

    def sds():
        return jax.ShapeDtypeStruct((30, length, 128), jnp.bfloat16,
                                    sharding=one_chip)

    text = _compiled_text(
        lambda q, k, v: pallas_window.band_prefill_attend(
            q, k, v, block=block, scale=128 ** -0.5),
        sds(), sds(), sds())
    assert "swa_prefill_attend" in text


@pytest.fixture(scope="module")
def olmo_period(one_chip):
    """`(lm, params, cache)` of one period of `olmo_hybrid_7b_l16` (three
    linear layers and a full one) at its published widths, 32 slots x 2,048
    positions, as shapes on the described chip."""
    import json

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo_hybrid_7b_l16.json")) as f:
        published = json.load(f)
    assert len(published["layer_types"]) == 32      # carried whole
    published["num_hidden_layers"] = 4
    config = HybridLMConfig.from_config(published, max_len=2048)
    assert config.layer_types == ("linear_attention",) * 3 \
        + ("full_attention",)
    dev = next(iter(one_chip.device_set))
    lm = HybridLM(config, par.create_mesh(devices=[dev], dp=1))
    host_lm = HybridLM(config, par.create_mesh(devices=jax.devices()[:1],
                                               dp=1))

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: sds(v) for k, v in jax.eval_shape(
        host_lm.init_params, jax.random.PRNGKey(0)).items()}
    cache = tuple(sds(v) for v in jax.eval_shape(
        lambda: host_lm.init_cache(32, 2048)))
    assert [c.shape for c in cache] == [
        (32, 1, 30, 2048, 128), (32, 1, 30, 2048, 128), (32, 3, 96, 5760),
        (32, 3, 3, 11520)]
    return lm, params, cache


def test_olmo_decode_program_compiles_for_v5e(one_chip, monkeypatch,
                                              olmo_period):
    """The engine's decode program of one period: it compiles for the v5e;
    the state kernel is in it once a linear layer and the `hd`-minor slab
    kernel once a full layer (not `decode_update_attend`, whose slabs lie
    `L`-minor, and not the XLA formulation); every member of the cache is
    aliased input to output; no XLA op copies, slices, updates or re-lays a
    K/V page or a layer's page of the state slab. The trace counts which way
    each linear layer's state update went, and which body of the slab
    kernel the full layer took."""
    lm, params, cache = olmo_period
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.decode_block(cache[0].shape, cache[0].dtype) == 128
    assert lm.state_kernel(cache[2].shape, cache[2].dtype)

    def fn(params, cache, tokens, positions):       # the engine's wrapper
        logits, *cache = lm.decode_step(params, *cache, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), tuple(cache)

    ints = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    with _counted("gdn.state_update.", ("kernel", "xla")) as went, \
            _counted(*KV128_BODIES) as bodies:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, ints, ints).compile()
    assert went == {"kernel": 3, "xla": 0}
    # one query a K/V head: the full layer's trace took the one-chain body
    assert bodies == {"one_query": 1, "grouped": 0}
    text = compiled.as_text()
    assert len(re.findall(r"%gdn_state_update[.\d]* = ", text)) == 3
    assert len(re.findall(r"%kv128_attend[.\d]* = ", text)) == 1
    # the kernel's call, not the name: the work list is `pallas_decode`'s
    # (PR 45), and a trace jax has cached of what it calls carries the
    # frames of whoever traced it first, `decode_update_attend` in this file
    assert not _SLAB_KERNEL.search(text)
    assert [line for line in _page_sized_ops(text, 32 * 30 * 2048 * 128)
            if "bf16[" in line] == []
    state = 32 * 96 * 5760                          # one layer's page
    assert [line for line in _page_sized_ops(text, state)
            if "f32[" in line] == []
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes    # all four, whole
    assert ma.temp_size_in_bytes < state * 4        # no temporary of a page


@pytest.mark.parametrize("bucket", [256, 1024])
def test_olmo_prefill_program_compiles_for_v5e(one_chip, monkeypatch,
                                               olmo_period, bucket):
    """The engine's prefill program of one period at the cell's smallest and
    largest bucket: it compiles for the v5e (the chunked delta rule's
    triangular solve and scan included), the full layer's attention is the
    prefill kernel, and every member of the cache is aliased."""
    lm, params, cache = olmo_period
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.prefill_block(bucket) == bucket

    def fn(params, cache, tokens, length, slot):    # the engine's wrapper
        logits, *cache = lm.prefill(params, *cache, tokens, length, slot)
        return jnp.argmax(logits).astype(jnp.int32), tuple(cache)

    one = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, jax.ShapeDtypeStruct((bucket,), jnp.int32,
                                           sharding=one_chip),
        one, one).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%swa_prefill_attend[.\d]* = ", text)) == 1
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes
    assert ma.temp_size_in_bytes < 2 ** 30


# ---------------------------------------------------------------------------
# the LFM2 expert block at its published widths and the cell's depth (ISSUE 47)
# ---------------------------------------------------------------------------

HBM_BUDGET = 14.5e9     # of a v5e's 16 GB: what a program and its cache may take


@pytest.fixture(scope="module")
def lfm2_stage(one_chip):
    """`(lm, params, cache)` of `lfm2_8b_a1b_l12` whole — the 12 layers of
    the stage at their published widths, 64 slots x 8,192 positions — as
    shapes on the described chip."""
    import json

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2_8b_a1b_l12.json")) as f:
        published = json.load(f)
    assert len(published["layer_types"]) == 24      # carried whole
    config = HybridLMConfig.from_config(published, max_len=8192)
    assert config.layer_types.count("conv") == 9 \
        and config.layer_types.count("full_attention") == 3
    dev = next(iter(one_chip.device_set))
    lm = HybridLM(config, par.create_mesh(devices=[dev], dp=1))
    host_lm = HybridLM(config, par.create_mesh(devices=jax.devices()[:1],
                                               dp=1))

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = {k: sds(v) for k, v in jax.eval_shape(
        host_lm.init_params, jax.random.PRNGKey(0)).items()}
    cache = tuple(sds(v) for v in jax.eval_shape(
        lambda: host_lm.init_cache(64, 8192)))
    assert [c.shape for c in cache] == [
        (64, 3, 8, 8192, 64), (64, 3, 8, 8192, 64), (64, 9, 2, 2048),
        (64, 10, 4)]
    return lm, params, cache


def _resident(ma):
    """Bytes a compiled program needs on the chip while it runs: its
    arguments (weights and cache), its temporaries, and what of its result
    is not an argument's own buffer."""
    return ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes


def test_lfm2_decode_program_compiles_for_v5e(one_chip, monkeypatch,
                                              lfm2_stage):
    """The engine's 64-slot decode program of the whole stage: it compiles
    for the v5e; the experts' two products a layer are jax's `gmm` (256
    sorted rows: two row tiles of 128) and not `lax.ragged_dot`; the slab
    kernel `decode_update_attend` is in it once an attention layer and the
    three calls share one grid; every member of the cache is aliased input
    to output; no XLA op copies, slices, updates or re-lays a K/V page; and
    weights, cache and temporaries fit 14.5 GB."""
    lm, params, cache = lfm2_stage
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.decode_block(cache[0].shape, cache[0].dtype) == 1024
    assert not lm.state_kernel(cache[2].shape, cache[2].dtype)

    def fn(params, cache, tokens, positions):       # the engine's wrapper
        logits, *cache = lm.decode_step(params, *cache, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), tuple(cache)

    ints = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    with _counted("moe.grouped_product.", ("gmm", "ragged_dot")) as went, \
            _counted(*SLAB_BODIES) as body:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, ints, ints).compile()
    assert went == {"gmm": 20, "ragged_dot": 0}
    assert body == {"one_query": 0, "grouped": 3}
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 20
    assert "ragged-dot" not in text
    calls = _slab_kernel_calls(text)
    assert len(calls) == 3
    assert len({tuple(operands[:4]) for operands in calls}) == 1
    assert [line for line in _page_sized_ops(text, 64 * 8 * 8192 * 64)
            if "bf16[" in line] == []
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes    # all four, whole
    assert ma.temp_size_in_bytes < 2 ** 26
    assert 11.0e9 < _resident(ma) < HBM_BUDGET


@pytest.mark.parametrize("bucket,blockwise", [(2048, False), (8192, True)])
def test_lfm2_prefill_program_compiles_for_v5e(one_chip, monkeypatch,
                                               lfm2_stage, bucket,
                                               blockwise):
    """The engine's prefill program of the whole stage at the cell's
    smallest and largest bucket: it compiles for the v5e with the cache
    resident and fits 14.5 GB — the attention of 32 heads of 64 as one score
    matrix at 2,048 (0.5 GB) and blockwise at 8,192, where the matrix would
    be 8.6 GB —, the experts' products are `gmm`, and every member of the
    cache is aliased."""
    lm, params, cache = lfm2_stage
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    assert lm.prefill_block(bucket) is None
    assert lm.prefill_blockwise(bucket) == blockwise

    def fn(params, cache, tokens, length, slot):    # the engine's wrapper
        logits, *cache = lm.prefill(params, *cache, tokens, length, slot)
        return jnp.argmax(logits).astype(jnp.int32), tuple(cache)

    one = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    with _counted("moe.grouped_product.", ("gmm", "ragged_dot")) as went:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((bucket,), jnp.int32,
                                               sharding=one_chip),
            one, one).compile()
    assert went == {"gmm": 20, "ragged_dot": 0}
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 20
    ma = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache)
    assert ma.alias_size_in_bytes >= cache_bytes
    assert ma.temp_size_in_bytes < 2 ** 30
    assert _resident(ma) < HBM_BUDGET


# ---------------------------------------------------------------------------
# the slab kernel's two bodies (ISSUE 48): a group of queries on the MXU
# ---------------------------------------------------------------------------

def _gpt2xl():
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig

    return TransformerLM, TransformerLMConfig(
        vocab_size=50257, d_model=1600, n_heads=25, d_ff=6400, n_layers=48,
        max_len=1024, dtype="bfloat16"), (32, 1024)


def _published(name, **more):
    import json

    from mxnet_tpu.models import HybridLM, HybridLMConfig

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmark", "configs", name)) as f:
        return HybridLM, HybridLMConfig.from_config(json.load(f), **more)


@pytest.mark.parametrize("cell,build,want", [
    ("lfm2moe_workers64", lambda: _published(
        "lfm2_8b_a1b_l12.json", max_len=8192) + ((64, 8192),), (3, 0)),
    ("granite4h_workers32", lambda: _published(
        "granite_4_0_h_micro.json") + ((32, 4096),), (4, 0)),
    ("gpt2xl_chat", _gpt2xl, (0, 48)),
], ids=["lfm2", "granite", "gpt2xl"])
def test_slab_kernel_body_follows_the_heads(monkeypatch, cell, build, want):
    """Which body of `decode_update_attend` a cell's whole decode program
    takes, from the counter a layer's trace moves (telemetry on only):
    `attn.decode.slab.grouped | one_query` reads 3 | 0 in LFM2's stage and
    4 | 0 in granite (32 queries over 8 K/V heads: two products a pair of
    heads), 0 | 48 in GPT-2 XL (25 over 25: the per-lane body it had). The
    body follows the operands' shapes and nothing else. A trace is enough
    to count; no chip is described."""
    from mxnet_tpu import parallel as par

    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    cls, config, (slots, rows) = build()
    lm = cls(config, par.create_mesh(devices=jax.devices()[:1], dp=1))
    params = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(slots, rows))
    assert lm.decode_block(cache[0].shape, cache[0].dtype) is not None
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    with _counted(*SLAB_BODIES) as went:
        jax.eval_shape(lm.decode_step, params, *cache, ints, ints)
    assert (went["grouped"], went["one_query"]) == want


@pytest.mark.parametrize("slab,q_heads", [
    ((64, 3, 8, 8192, 64), 32),         # lfm2moe_workers64
    ((32, 4, 8, 4096, 64), 32),         # granite4h_workers32
    ((32, 2, 6, 2048, 64), 12),         # 3 pairs of heads, 2 queries each
    ((32, 2, 5, 2048, 64), 20),         # heads that do not pair off
    ((32, 2, 4, 2048, 32), 16),         # four heads of 32 a product
], ids=["lfm2", "granite", "6x2", "5x4", "hd32"])
def test_slab_kernel_grouped_compiles_for_v5e(one_chip, slab, q_heads):
    """The grouped body alone at the two cells' slabs and at shapes that
    stack another number of heads a product: Mosaic takes it at the block
    `decode_block` chooses (the K and V blocks of every head, double
    buffered, stay inside the budget that keeps the kernel's scoped VMEM
    under Mosaic's 16 MiB), under the name the benchmark's readers look
    for, with both slabs aliased and no copy of a page around it."""
    from mxnet_tpu.ops import pallas_decode as pd

    n, _, heads, rows, hd = slab
    block = pd.decode_block(slab, jnp.bfloat16)
    assert block is not None and rows % block == 0
    assert 4 * heads * hd * block * 2 <= pd._BLOCK_BUDGET_BYTES

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, k, v, ck, cv, pos):
        return pd.decode_update_attend(q, k, v, ck, cv, jnp.int32(1), pos,
                                       block=block)

    compiled = jax.jit(fn, donate_argnums=(3, 4)).lower(
        sds((n, q_heads, hd)), sds((n, heads, hd)), sds((n, heads, hd)),
        sds(slab), sds(slab), sds((n,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(_slab_kernel_calls(text)) == 1
    assert _page_sized_ops(text, n * heads * rows * hd) == []
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * int(np.prod(slab)) * 2
    assert ma.temp_size_in_bytes < n * heads * rows * hd
