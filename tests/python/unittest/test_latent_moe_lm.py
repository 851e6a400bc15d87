"""`LatentMoELM` (latent attention, a dropless expert layer told which experts
it holds; the `sarvam_mla` block) against the plain reference
`benchmark/reference/sarvam_mla_moe.py`, at a tiny size with the published
structure: one leading dense layer, expert layers with a sigmoid router, a
selection bias and a shared expert, YaRN-corrected rotary positions. The
model is float32 here, so it agrees with the float32 reference to rounding:
every tolerance is 1e-4 of the compared quantity's scale. The bfloat16 model
at the published widths is compared on the chip
(benchmark/runners/serve_latent_moe.py).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import parallel as par
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import LatentMoELM, LatentMoELMConfig
from mxnet_tpu.models import experts, latent_moe
from mxnet_tpu.ops import pallas_latent
from mxnet_tpu.serving import GenerationEngine, qos

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [os.path.join(REPO, "benchmark")]
from reference import sarvam_mla_moe as ref  # noqa: E402
from runners.serve_latent_moe import published  # noqa: E402

from lm_jit import jitted  # noqa: E402

ref.PAD_TO = ref.BLOCK = 16     # the chip's sizes would spend these tiny tests on padding

TOL = 1e-4
VOCAB = 211
CONFIG = dict(
    vocab_size=VOCAB, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, kv_lora_rank=128, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, first_k_dense_replace=1, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 64,
                  "type": "deepseek_yarn"},
    max_position_embeddings=256, dtype="float32", hidden_act="silu",
    tie_word_embeddings=False, use_qk_norm=True,
    moe_router_enable_expert_bias=True)


def _model(config):
    return LatentMoELM(LatentMoELMConfig.from_config(config),
                       par.create_mesh(devices=jax.devices()[:1], dp=1))


@pytest.fixture(scope="module")
def tiny():
    lm = _model(CONFIG)
    params = lm.init_params(jax.random.PRNGKey(0))
    return lm, params, published(params)


def _share(config, params, first, count):
    """The configuration and weights of the chip that holds the experts
    `[first, first + count)` of the uncut model."""
    cut = dict(config, num_experts=count,
               published={"num_experts": config["num_experts"]},
               share={"expert_first": first})
    held = {k: (v[first:first + count] if ".experts_" in k else v)
            for k, v in params.items()}
    return cut, held


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _close(got, want, what, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert np.isfinite(got).all() and err <= tol, (what, err)


def _poisoned(lm, slots, max_len):
    """A cache whose latent members are NaN: what a careless previous
    occupant may leave in a slot."""
    return tuple(c if c.dtype == jnp.int32 else
                 jnp.full(c.shape, jnp.nan, c.dtype)
                 for c in lm.init_cache(slots, max_len))


def _prefill(lm, params, cache, prompt, bucket, slot):
    padded = np.full(bucket, 7, np.int32)       # padded "with anything"
    padded[:len(prompt)] = prompt
    out = jitted(lm, "prefill")(params, *cache, jnp.asarray(padded),
                                 jnp.asarray(len(prompt), jnp.int32),
                                 jnp.asarray(slot, jnp.int32))
    return out[0], tuple(out[1:])


def _decode(lm, params, cache, slot, token, position):
    slots = cache[0].shape[0]
    tokens = np.zeros(slots, np.int32)
    positions = np.full(slots, -1, np.int32)
    tokens[slot], positions[slot] = token, position
    out = jitted(lm, "decode_step")(params, *cache, jnp.asarray(tokens),
                                     jnp.asarray(positions))
    return out[0][slot], tuple(out[1:])


def _latent_rows(cache, slot, n):
    """`[layers, n, R + rope]` of what a slot's first `n` positions hold."""
    c, kr = np.asarray(cache[0])[slot], np.asarray(cache[1])[slot]
    return np.concatenate([c[:, :n], kr[:, :, :n].transpose(0, 2, 1)], -1)


@pytest.mark.parametrize("length", [7, 16, 29, 40])
def test_forward_matches_reference(tiny, length):
    lm, params, weights = tiny
    seq = _tokens(length)
    want = ref.logits(CONFIG, weights, seq, np.arange(length))
    _close(lm.forward(params, seq[None])[0], want, "logits")


def test_blockwise_prefill_attention_is_the_plain_one(monkeypatch):
    """A sequence longer than one attention block is attended blockwise
    with a running softmax; the result is the one-block formulation's."""
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(48, 3, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(48, 3, 16)), jnp.float32)
    want = latent_moe._causal_attention(q, k, v, 0.3)
    monkeypatch.setattr(latent_moe, "_ATTN_BLOCK", 16)
    _close(latent_moe._causal_attention(q, k, v, 0.3), want, "blockwise")


def test_chunked_expert_layer_is_the_whole_one(tiny, monkeypatch):
    """A prefill longer than the expert chunk groups its tokens a chunk at
    a time; the layer's result does not change."""
    lm, params, _ = tiny
    h = jnp.asarray(np.random.default_rng(1).normal(size=(32, 64)),
                    jnp.float32)
    want, routing = lm._mlp(params, 1, h)
    monkeypatch.setattr(experts, "EXPERT_CHUNK", 8)
    got, chunked = lm._mlp(params, 1, h)
    _close(got, want, "chunked experts")
    assert np.array_equal(routing, chunked)


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("prompt_len,bucket", [(7, 8), (16, 16), (9, 32),
                                               (29, 32)])
def test_prefill_then_decode_matches_full_forward(tiny, monkeypatch, path,
                                                  prompt_len, bucket):
    """Prefill (the unabsorbed form) into a slot whose previous occupant
    left NaN everywhere, then decode through the cache (the absorbed form):
    every logit row and every latent row the slot holds are the reference's
    full forward's; the other slots stay NaN."""
    lm, params, weights = tiny
    if path == "kernel":
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        assert lm.decode_block((3, 3, 256, 128), jnp.float32) == 256
    steps = 6
    seq = _tokens(prompt_len + steps, seed=prompt_len)
    want, latents, _ = ref.forward(CONFIG, weights, seq,
                                   np.arange(prompt_len - 1, len(seq)))
    logits, cache = _prefill(lm, params, _poisoned(lm, 3, 256),
                             seq[:prompt_len], bucket, slot=1)
    _close(logits, want[0], "prefill logits")
    for t in range(prompt_len, len(seq)):
        logits, cache = _decode(lm, params, cache, 1, seq[t], t)
        _close(logits, want[t - prompt_len + 1], f"decode logits at {t}")
    for got, layer in zip(_latent_rows(cache, 1, len(seq)), latents):
        _close(got, layer, "latent rows")
    assert np.isnan(np.asarray(cache[0])[[0, 2]]).all(), \
        "a dead slot's rows were touched"
    assert np.isnan(np.asarray(cache[1])[[0, 2]]).all()
    assert (np.asarray(cache[2])[[0, 2]] == 0).all()


def test_absorbed_decode_equals_unabsorbed(tiny):
    """One layer, one step: the absorbed form over the latent rows (what
    decode runs) against up-projecting every cached row to keys and values
    and attending those (what prefill runs)."""
    lm, params, _ = tiny
    c = lm.cfg
    rng = np.random.default_rng(3)
    n, slots = 21, 2
    u = jnp.asarray(rng.normal(size=(n, c.hidden_size)), jnp.float32)
    want, lat, k_r = lm._attention_seq(params, 1, u)
    cache_c = jnp.zeros((slots, 3, 32, c.kv_lora_rank)).at[1, 1, :n - 1] \
        .set(lat[:n - 1])
    cache_kr = jnp.zeros((slots, 3, c.qk_rope_head_dim, 32)) \
        .at[1, 1, :, :n - 1].set(k_r[:n - 1].T)
    positions = jnp.asarray([-1, n - 1], jnp.int32)
    got, cache_c, cache_kr = lm._attention_step(
        params, 1, jnp.stack([u[0], u[n - 1]]), cache_c, cache_kr, 1,
        positions, None)
    _close(got[1], want[n - 1], "absorbed vs unabsorbed")
    _close(cache_c[1, 1, n - 1], lat[n - 1], "the written latent")
    _close(cache_kr[1, 1, :, n - 1], k_r[n - 1], "the written key")
    assert not np.asarray(cache_c[0]).any()


def _bits(x):
    """An array's bit patterns, so that equality also holds NaN to NaN."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


# a slot's position, by slot (-1: dead); block 128 of L = 256 unless a
# length is given
KERNEL_CASES = {
    "first_row": ([0, 0, 3, 17], 1, "float32"),
    "block_minus_1": ([127, 127, 126, 127], 1, "float32"),
    "block": ([128, 128, 129, 128], 1, "float32"),
    "last_row": ([255, 255, 254, 255], 1, "float32"),
    "edges_together": ([127, 128, 5, 255], 1, "float32"),
    "five_rows_into_a_poisoned_block": ([133, 5, 133, 5], 1, "float32"),
    "all_dead": ([-1, -1, -1, -1], 1, "float32"),
    "one_dead_among_live": ([127, -1, 133, 255], 1, "float32"),
    "two_dead_among_live": ([-1, 128, -1, 255], 1, "float32"),
    "layer_0": ([127, 128, -1, 255], 0, "float32"),
    "layer_2_of_3": ([0, 200, 16, -1], 2, "float32"),
    "bf16_tiles": ([127, 128, 5, 255], 1, "bfloat16"),
    "bf16_dead_and_edges": ([-1, 15, 16, 143], 0, "bfloat16"),
    # blocks of 512 fetched a quarter (128 rows) a copy, L = 1,024: the last
    # unit is fetched and scored as far as the position
    "pieces_of_the_first_unit": ([0, 127, 128, 511], 1, "float32", 1024),
    "pieces_of_the_second_unit": ([512, 639, 640, 1023], 1, "float32",
                                  1024),
    "pieces_dead_and_poisoned": ([-1, 700, 383, -1], 2, "float32", 1024),
    "pieces_bf16": ([511, 512, 900, 5], 0, "bfloat16", 1024),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_latent_kernel_matches_restatement(case):
    """The Pallas kernel (interpreted) against the XLA formulation
    (`_write_rows`, then `_attend_latent`) on slabs whose rows AT and past
    each position are what a previous occupant left — NaN in `c`, inf in
    `k_r` — at positions on, before and after a block's edge, under a traced
    layer index: (i) the attention, (ii) both slabs bit for bit, everywhere
    — the other rows and lanes of the written tile, every other layer and
    slot — and (iii) a dead slot's page as it was."""
    positions, layer, dtype, *length = KERNEL_CASES[case]
    rng = np.random.default_rng(5)
    slots, layers, rank, rope, heads = 4, 3, 128, 8, 4
    length, = length or [256]
    block = pallas_latent.latent_block((slots, layers, length, rank),
                                       dtype, target=length // 2)
    assert block == length // 2
    positions = np.asarray(positions, np.int32)
    slab_c = rng.normal(size=(slots, layers, length, rank)).astype("f4")
    slab_kr = rng.normal(size=(slots, layers, rope, length)).astype("f4")
    for s, p in enumerate(positions):
        slab_c[s, :, max(p, 0):] = np.nan
        slab_kr[s, :, :, max(p, 0):] = np.inf
    slab_c, slab_kr = jnp.asarray(slab_c, dtype), jnp.asarray(slab_kr, dtype)
    qc = jnp.asarray(rng.normal(size=(slots, heads, rank)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(slots, heads, rope)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(slots, rank)), jnp.float32)
    k_r = jnp.asarray(rng.normal(size=(slots, rope)), jnp.float32)
    at = jnp.asarray(positions)
    want_c = latent_moe._write_rows(slab_c, layer, at, lat[:, None, :], 2)
    want_kr = latent_moe._write_rows(slab_kr, layer, at, k_r[:, :, None], 3)
    want = latent_moe._attend_latent(qc, qr, want_c[:, layer],
                                     want_kr[:, layer], at, 0.2)

    @jax.jit
    def kernel(layer):                      # the layer is traced
        return pallas_latent.latent_attend(
            qc, qr, lat, k_r, slab_c, slab_kr, layer, at, block=block,
            scale=0.2, interpret=True)

    got, got_c, got_kr = kernel(jnp.int32(layer))
    assert np.isfinite(np.asarray(got)).all()
    if (positions >= 0).any():
        _close(got, want, "latent kernel",
               TOL if dtype == "float32" else 1e-2)
    assert not np.asarray(got)[positions < 0].any()
    assert np.array_equal(_bits(got_c), _bits(want_c))
    assert np.array_equal(_bits(got_kr), _bits(want_kr))
    dead = positions < 0
    assert np.array_equal(_bits(got_c)[dead], _bits(slab_c)[dead])
    assert np.array_equal(_bits(got_kr)[dead], _bits(slab_kr)[dead])
    for s in np.flatnonzero(~dead):         # ... and the row did go in
        assert np.array_equal(_bits(got_c[s, layer, positions[s]]),
                              _bits(lat[s].astype(dtype)))
        assert np.array_equal(_bits(got_kr[s, layer, :, positions[s]]),
                              _bits(k_r[s].astype(dtype)))


def test_decode_step_kernel_and_xla_leave_the_same_cache(tiny, monkeypatch):
    """`decode_step` through the kernel (interpreted) and through the XLA
    formulation over four ticks that cross a block's edge (4,094 -> 4,097
    with blocks of 2,048), one slot deep in its first block, one dead: the
    same argmax every tick, and the same cache — the first layer's rows,
    whose inputs are the token's alone, bit for bit; the later layers' to
    rounding (their inputs carry the attention before them, which the two
    sum in different orders); the dead slot's page untouched."""
    _, params, _ = tiny
    slots, max_len = 3, 8192
    lm = _model(dict(CONFIG, max_position_embeddings=max_len))
    rng = np.random.default_rng(11)
    start = np.asarray([4094, -1, 4], np.int32)
    cache = lm.init_cache(slots, max_len)
    filled = []
    for member in cache[:2]:
        rows = rng.normal(size=member.shape).astype("f4") * 0.5
        filled.append(rows)
    for s, p in enumerate(start):
        filled[0][s, :, max(p, 0):] = np.nan
        filled[1][s, :, :, max(p, 0):] = np.nan
    before = (jnp.asarray(filled[0]), jnp.asarray(filled[1]), cache[2])
    tokens = rng.integers(0, VOCAB, (4, slots)).astype(np.int32)

    def run(kernel):
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1" if kernel else "0")
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        assert lm.decode_block(before[0].shape, before[0].dtype) \
            == (2048 if kernel else None)
        step = jax.jit(lm.decode_step)
        cache, picked = before, []
        for t, toks in enumerate(tokens):
            positions = np.where(start >= 0, start + t, -1).astype(np.int32)
            logits, *cache = step(params, *cache, jnp.asarray(toks),
                                  jnp.asarray(positions))
            picked.append(np.asarray(jnp.argmax(logits, -1)))
        return np.stack(picked), tuple(cache)

    went = {k: telemetry.counter("mla.attend." + k)
            for k in ("kernel", "xla")}
    was = telemetry.enabled()
    telemetry.enable()
    try:
        # once a layer a trace (jit may trace a step more than once)
        count = {k: c.value for k, c in went.items()}
        picked_k, cache_k = run(True)
        assert went["kernel"].value - count["kernel"] \
            >= lm.cfg.num_hidden_layers
        assert went["xla"].value == count["xla"]
        count = {k: c.value for k, c in went.items()}
        picked_x, cache_x = run(False)
        assert went["xla"].value - count["xla"] >= lm.cfg.num_hidden_layers
        assert went["kernel"].value == count["kernel"]
    finally:
        telemetry.enable(was)
    live = start >= 0
    assert np.array_equal(picked_k[:, live], picked_x[:, live])
    for got, want, was_ in zip(cache_k[:2], cache_x[:2], before[:2]):
        assert np.array_equal(_bits(got[:, 0]), _bits(want[:, 0]))
        assert np.array_equal(np.isnan(got), np.isnan(want))
        _close(np.nan_to_num(got), np.nan_to_num(want), "cache rows", 1e-5)
        assert np.array_equal(_bits(got[1]), _bits(was_[1]))
    assert np.array_equal(cache_k[2], cache_x[2])
    for s in np.flatnonzero(live):          # the four rows went in
        assert np.isfinite(np.asarray(
            cache_k[0][s, :, start[s]:start[s] + 4])).all()
        assert np.isfinite(np.asarray(
            cache_k[1][s, :, :, start[s]:start[s] + 4])).all()


def test_prefill_kernel_matches_restatement():
    """The prefill attention kernel (interpreted) — keys in two parts, key
    blocks streamed through the grid, blocks past the diagonal skipped —
    against the XLA formulation over concatenated keys."""
    rng = np.random.default_rng(6)
    heads, length, dn, dr, dv = 3, 384, 16, 8, 16
    qn, kn = (jnp.asarray(rng.normal(size=(heads, length, dn)), jnp.float32)
              for _ in range(2))
    qr = jnp.asarray(rng.normal(size=(heads, length, dr)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(length, dr)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(heads, length, dv)), jnp.float32)
    assert pallas_latent.prefill_block(length, target=128) == 128
    assert pallas_latent.prefill_block(200) is None
    got = pallas_latent.prefill_attend(qn, qr, kn, kr, v, block=128,
                                       scale=0.3, interpret=True)
    q = jnp.concatenate([qn, qr], -1).transpose(1, 0, 2)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[None], qr.shape)], -1)
    want = latent_moe._causal_attention(q, k.transpose(1, 0, 2),
                                        v.transpose(1, 0, 2), 0.3)
    _close(got.transpose(1, 0, 2), want, "prefill kernel")


def test_prefill_through_the_kernel_writes_the_same_rows(tiny, monkeypatch):
    """A bucket the prefill kernel takes (whole lane rows): logits and
    latent rows are those of the XLA formulation."""
    lm, params, _ = tiny
    prompt = _tokens(200, seed=21)
    want, cache = _prefill(lm, params, lm.init_cache(2, 256), prompt, 256, 1)
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    assert lm.prefill_block(256) == 256
    got, kernel = _prefill(lm, params, lm.init_cache(2, 256), prompt, 256, 1)
    _close(got, want, "prefill logits")
    _close(_latent_rows(kernel, 1, 200), _latent_rows(cache, 1, 200),
           "latent rows")


def test_grouped_kernel_is_the_plain_grouped_product(tiny, monkeypatch):
    """The expert layer through the Pallas grouped matmul (interpreted) is
    the one through `lax.ragged_dot`, empty groups and the rows past the
    groups included."""
    lm, params, _ = tiny
    cut, held = _share(CONFIG, params, 4, 8)
    chip = _model(cut)
    # 64 tokens x 4 choices: whole 128-row tiles, what the kernel takes
    h = jnp.asarray(np.random.default_rng(17).normal(size=(64, 64)),
                    jnp.float32)
    want, routing = chip._mlp(held, 1, h)
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    got, kernel = chip._mlp(held, 1, h)
    _close(got, want, "grouped kernel")
    assert np.array_equal(routing, kernel)


def test_selection_uses_the_bias_and_weights_do_not(tiny):
    """The 4 experts with the largest `s + b` are chosen; the weights are
    `s` over the selection's sum times the routed scale — `b` moves which
    experts, never how much."""
    lm, params, _ = tiny
    x = jnp.asarray(np.random.default_rng(7).normal(size=(40, 64)),
                    jnp.float32)
    s = np.asarray(jax.nn.sigmoid(x @ params["l1.router"]), np.float64)
    b = np.asarray(params["l1.router_bias"], np.float64)
    chosen, weights = lm._route(params, 1, x)
    chosen = np.asarray(chosen)
    want = np.argsort(-(s + b), axis=-1)[:, :4]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    unbiased = np.argsort(-s, axis=-1)[:, :4]
    assert not np.array_equal(np.sort(unbiased, -1), np.sort(want, -1)), \
        "the bias decides nothing at this size: the test sees nothing"
    picked = np.take_along_axis(s, chosen, -1)
    _close(weights, picked / picked.sum(-1, keepdims=True) * 2.5, "weights")


def _softmax_family():
    """The other model that calls `experts.expert_layer`: a softmax router
    with normalised weights, no bias, no shared expert (`WindowMoELM`), at
    16 experts of which 4 are chosen."""
    from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig
    from reference import mellum_swa_moe
    from runners import serve_swa_moe

    kinds = ["sliding_attention", "full_attention"]
    config = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
        norm_topk_prob=True, rms_norm_eps=1e-6, sliding_window=8,
        layer_types=kinds, mlp_layer_types=["sparse"] * 2,
        rope_parameters={k: {"rope_type": "default", "rope_theta": 10000}
                         for k in kinds},
        max_position_embeddings=64, dtype="float32")

    def model(cfg):
        return WindowMoELM(WindowMoELMConfig.from_config(cfg),
                           par.create_mesh(devices=jax.devices()[:1], dp=1))

    def reference(cfg, h, weights):
        return mellum_swa_moe.expert_mlp(
            h, weights, mellum_swa_moe._static(cfg, kinds[1]))[0]

    return config, model, reference, \
        lambda params, cfg: serve_swa_moe.published(params, cfg), False


def _sigmoid_family():
    return CONFIG, _model, \
        lambda cfg, h, weights: ref.expert_mlp(h, weights,
                                               ref._static(cfg))[0], \
        lambda params, cfg: published(params), True


@pytest.mark.parametrize("family", [_sigmoid_family, _softmax_family],
                         ids=["sigmoid+bias", "softmax"])
def test_the_shares_add_up(family):
    """Four chips hold 4 of the 16 experts each. Each computes its own
    experts' part (and the shared expert, where the model has one); the
    routed parts of the four and the shared expert ONCE are the uncut layer
    of the reference — through the one expert function both models call,
    under either router."""
    config, model, reference, names, has_shared = family()
    lm = model(config)
    params = lm.init_params(jax.random.PRNGKey(0))
    h = jnp.asarray(np.random.default_rng(11).normal(size=(24, 64)),
                    jnp.float32)

    def layer(weights):
        return {k[len("layers.1."):]: v for k, v in weights.items()
                if k.startswith("layers.1.")}

    want = reference(config, h, layer(names(params, config)))
    x = lm._rms(h, params["l1.norm2"])
    total = h
    if has_shared:
        total = total + lm._gated(x, params["l1.shared_in"],
                                  params["l1.shared_out"])
    for first in range(0, 16, 4):
        cut, held = _share(config, params, first, 4)
        chip = model(cut)
        assert (chip.cfg.num_experts, chip.cfg.experts_held,
                chip.cfg.expert_first) == (16, 4, first)
        routed, local = experts.expert_layer(
            x, jnp.ones(24, bool), lambda xs: chip._route(held, 1, xs),
            held["l1.experts_in"], held["l1.experts_out"],
            expert_first=first, mesh=chip.mesh)
        assert ((np.asarray(local) >= -1) & (np.asarray(local) < 4)).all()
        total = total + routed
        # the chip's whole layer is the reference's, given the same share
        part = reference(cut, h, layer(names(held, cut)))
        _close(chip._mlp(held, 1, h)[0], part, f"share from {first}")
    _close(total, want, "the four shares (and the shared expert once)")


def test_no_token_is_dropped_when_all_choose_one_expert(tiny):
    """A routing that sends every token to the same experts: nothing is
    dropped or padded to a capacity, every (token, expert) pair is
    computed."""
    lm, params, weights = tiny
    bias = np.zeros(16, np.float32)
    bias[[2, 5, 9, 14]] = 10.0              # every token chooses these four
    rigged = dict(params, **{"l1.router_bias": jnp.asarray(bias)})
    h = jnp.asarray(np.random.default_rng(13).normal(size=(50, 64)),
                    jnp.float32)
    x = lm._rms(h, rigged["l1.norm2"])
    _, local = lm._experts(rigged, 1, x, jnp.ones(50, bool))
    assert (np.sort(np.asarray(local), -1) == [2, 5, 9, 14]).all()
    layer = {k[len("layers.1."):]: v
             for k, v in published(rigged).items()
             if k.startswith("layers.1.")}
    want, _ = ref.expert_mlp(h, layer, ref._static(CONFIG))
    _close(lm._mlp(rigged, 1, h)[0], want, "50 tokens on 4 experts")


def test_from_config_refuses_what_the_block_cannot_express():
    for key, value in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("use_qk_norm", False)):
        with pytest.raises(ValueError, match=key):
            LatentMoELMConfig.from_config(dict(CONFIG, **{key: value}))
    with pytest.raises(ValueError, match="held experts"):
        _model(dict(CONFIG, num_experts=8, published={"num_experts": 16},
                    share={"expert_first": 12}))


# -- through GenerationEngine ------------------------------------------------

def _greedy_reference(weights, prompt, n):
    """The reference's own greedy continuation, one full forward a token."""
    seq = list(prompt)
    for _ in range(n):
        row = ref.logits(CONFIG, weights, np.asarray(seq), [len(seq) - 1])
        seq.append(int(np.asarray(row)[0].argmax()))
    return seq[len(prompt):]


def _engine(lm, params, **kw):
    kw.setdefault("max_slots", 3)
    return GenerationEngine(lm, params, max_len=64, prefix_cache=False,
                            spec_k=0, **kw)


def test_a_batch_of_mixed_lengths_equals_each_request_alone(tiny):
    """7 requests over 3 slots: every stream is the reference's greedy
    continuation of its own prompt (slots are reused, so a prefill really
    replaces what its slot held), and nothing compiles after the first
    pass."""
    lm, params, weights = tiny
    prompts = [_tokens(n, seed=n) for n in (3, 8, 9, 17, 5, 30, 12)]
    with _engine(lm, params, buckets=(8, 32)) as eng:
        assert len(eng._kv) == 3 and eng.kv_slab_bytes() == sum(
            int(leaf.nbytes) for leaf in eng._kv)
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.result(timeout=120) for s in streams]
        misses = eng.cache.misses
        again = eng.generate(prompts[3], max_new_tokens=6)
        assert eng.cache.misses == misses
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(weights, p, 6)
    assert again == got[3]


def test_fork_is_a_bitwise_copy_of_one_slot(tiny):
    lm, params, _ = tiny
    eng = _engine(lm, params, buckets=(16,), start=False)
    try:
        s = eng.submit(_tokens(11, seed=9), max_new_tokens=5)
        for _ in range(3):
            eng._tick_once()
        src = eng.slot_snapshot(s.slot)
        others = eng.slot_snapshot((s.slot + 1) % 3)
        eng._fork(s.slot, (s.slot + 2) % 3)
        for a, b in zip(src, eng.slot_snapshot((s.slot + 2) % 3)):
            assert np.array_equal(a, b) and np.abs(a).sum() > 0
        for a, b in zip(others, eng.slot_snapshot((s.slot + 1) % 3)):
            assert np.array_equal(a, b)
    finally:
        eng.close()


def test_park_copies_every_member_and_resume_is_bit_equal(tiny):
    """QoS park and resume go through the fork executable, which copies one
    slot of EVERY member of the cache — the latent rows, the shared keys
    and the routing — and the preempted stream resumes bit-equal to an
    uncontended run."""
    lm, params, _ = tiny
    qos.install(qos.TenantRegistry(qos.parse_spec(
        "lat:interactive;bulk:batch")))
    try:
        bp = [_tokens(9, seed=40), _tokens(14, seed=41)]
        ip = _tokens(6, seed=42)
        with _engine(lm, params, max_slots=2, buckets=(16,)) as base:
            want = [base.generate(p, max_new_tokens=20) for p in bp]
            iwant = base.generate(ip, max_new_tokens=4)
        eng = _engine(lm, params, max_slots=2, buckets=(16,), start=False)
        try:
            assert eng.total_slots == 3
            bs = [eng.submit(p, max_new_tokens=20, tenant="bulk")
                  for p in bp]
            for _ in range(50):
                if eng.live_slots == 2:
                    break
                eng._tick_once()
            eng._tick_once()
            istream = eng.submit(ip, max_new_tokens=4, tenant="lat")
            for _ in range(3):
                before = [eng.slot_snapshot(s) for s in range(2)]
                eng._tick_once()                # parks the youngest
                if eng.parked_count:
                    break
            assert eng.parked_count == 1
            (rec,) = eng._parked.values()
            victim = bs.index(rec["sess"].stream)
            parked = eng.slot_snapshot(2)
            n = rec["length"]
            rows = [parked[0][:, :n], parked[1][:, :, :n]]
            assert all(np.abs(r).sum() > 0 for r in rows)
            # the rows the host counts for it are those its slot held
            was = before[victim]
            assert np.array_equal(rows[0][:, :n - 1], was[0][:, :n - 1])
            assert np.array_equal(rows[1][:, :, :n - 1],
                                  was[1][:, :, :n - 1])
            for _ in range(400):
                if all(s._future.done() for s in bs + [istream]):
                    break
                eng._tick_once()
            assert [s.result(1) for s in bs] == want
            assert istream.result(1) == iwant
        finally:
            eng.close()
    finally:
        qos.clear()


@pytest.mark.parametrize("kwargs,what", [
    (dict(prefix_cache=True, spec_k=0), "prefix cache"),
    (dict(prefix_cache=False, spec_k=2), "speculative decoding"),
])
def test_engine_refuses_what_the_model_does_not_offer(tiny, kwargs, what):
    lm, params, _ = tiny
    traits = lm.cache_traits(lm.init_cache(2, 64))
    assert traits["state_bytes_per_slot"] == 0 and not traits["rewindable"]
    assert not hasattr(lm, "prefill_at") and not hasattr(lm, "verify_step")
    with pytest.raises(MXNetError, match=what + ".*prefill_at"):
        GenerationEngine(lm, params, max_slots=2, max_len=64, buckets=(16,),
                         start=False, **kwargs)


def test_routing_counters_read_the_decode_programs_own_routing(tiny):
    """With telemetry on, the engine's counters of the expert layer are
    what the decode program itself routed: re-derived here from the model's
    routing of the same tokens at the same positions."""
    from mxnet_tpu import telemetry

    lm, params, _ = tiny
    cut, held = _share(CONFIG, params, 4, 8)
    chip = _model(cut)
    prev = telemetry.enabled()
    telemetry.enable()
    names = ("expert_assignments", "experts_hit", "expert_tokens_max",
             "latent_rows_live")
    pre = "serving.generation."
    try:
        eng = _engine(chip, held, buckets=(16,), start=False)
        c0 = {k: telemetry.counter(pre + k).value for k in names}
        prompts = [_tokens(n, seed=n) for n in (5, 9)]
        streams = [eng.submit(p, max_new_tokens=3) for p in prompts]
        for _ in range(20):
            if all(s.done for s in streams):
                break
            eng._tick_once()
        assert eng._ahead is None
        got = {k: telemetry.counter(pre + k).value - c0[k] for k in names}
        routed = eng.slot_snapshot(streams[0].slot)[2]
        eng.close()
    finally:
        telemetry.enable(prev)
    assert routed.shape == (2, 4) and routed.max() < 8
    # 2 sessions x 2 decoded tokens; the rows a decode attends are the
    # prompt, the tokens before it and its own
    assert got["latent_rows_live"] == (5 + 1) + (5 + 2) + (9 + 1) + (9 + 2)
    want = 0
    for step in range(2):
        for p, s in zip(prompts, streams):
            seq = jnp.asarray(np.concatenate([p, s.tokens[:step + 1]]))
            h = jnp.take(held["embed"], seq, axis=0)
            for i in range(3):
                mixed, _, _ = chip._attention_seq(
                    held, i, chip._rms(h, held[f"l{i}.norm1"]))
                h, local = chip._mlp(held, i, h + mixed)
                if local is not None:
                    want += int((np.asarray(local)[-1] >= 0).sum())
    assert got["expert_assignments"] == want
    assert 0 < want < 2 * 2 * 2 * 4             # a share: not every pair
    # which sessions share a tick decides these two; their exact values
    # are test_tick_counters_of_a_hand_made_routing's
    assert 0 < got["expert_tokens_max"] <= got["experts_hit"] <= want


def test_tick_counters_of_a_hand_made_routing(tiny):
    lm, _, _ = tiny
    routed = np.full((4, 2, 4), -1, np.int32)
    routed[0, 0] = [3, 5, -1, -1]
    routed[1, 0] = [3, -1, 7, -1]
    routed[2, 0] = [3, 5, 7, 9]                 # a dead slot's: not counted
    routed[3, 1] = [0, 1, 2, 15]
    positions = jnp.asarray([4, 9, -1, 0], jnp.int32)
    got = np.asarray(lm.tick_counters(None, None, jnp.asarray(routed),
                                      positions))
    # pairs 4 + 4; layer 0 hits {3, 5, 7} and layer 1 {0, 1, 2, 15}; the
    # fullest expert has 2 tokens in layer 0 and 1 in layer 1; rows 5+10+1
    assert got.tolist() == [8, 7, 3, 16]


def test_counters_cost_nothing_with_telemetry_off(tiny):
    """Telemetry off: the counters' program is compiled by `warm()` (so a
    later traced window compiles nothing) and never dispatched."""
    lm, params, _ = tiny
    eng = _engine(lm, params, buckets=(16,), start=False)
    try:
        eng.warm()
        calls = []
        real = eng._tick_counters_fn
        eng._tick_counters_fn = lambda: calls.append(1) or real()
        s = eng.submit(_tokens(7), max_new_tokens=4)
        for _ in range(10):
            if s.done:
                break
            eng._tick_once()
        assert s.done and calls == []
    finally:
        eng.close()


def test_decode_runs_ahead_and_nothing_compiles_after_warm_up(tiny):
    from jax import monitoring

    lm, params, weights = tiny
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        eng = _engine(lm, params, buckets=(8, 32), start=False)
        eng.warm()
        del compiles[:]
        prompts = [_tokens(n, seed=n) for n in (4, 8, 20, 6, 13)]
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        ahead = 0
        for _ in range(100):
            if all(s.done for s in streams):
                break
            eng._tick_once()
            ahead += eng._ahead is not None
        eng.close()
        assert ahead > 5
        assert compiles == []
    finally:
        monitoring.unregister_event_duration_listener(listener)
    for p, s in zip(prompts, streams):
        assert list(s.tokens) == _greedy_reference(weights, p, 6)
