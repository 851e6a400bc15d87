"""`HybridLM` as the LFM2 expert block (ISSUE 47): gated short-convolution
layers whose only cache is a window, beside rotary grouped-query attention
with per-head norms, under a sigmoid-routed layer of experts, against the plain
reference `benchmark/reference/lfm2_moe.py`. Tiny, with the published
structure: the first 8 of the published `layer_types` ("ccFcccFc"), two leading
dense layers, 8 experts of which 2 a token, 2 queries a K/V head, 3 taps. A file
of its own beside `test_hybrid_lm.py` (whose helpers it uses) so that it runs
on a worker of its own. The model is float32 here, so every tolerance is 1e-4
of the compared quantity's scale — but where the router's near-ties decide:
the random weights here leave the reference's margins (printed by
`test_lfm2_margins_leave_room`) a hundred times the rounding, so no expert is
swapped and the tolerance holds for every token;
`test_the_comparison_sees_each_part_of_the_lfm2_block` pins what misses it.
The bfloat16 model at the published widths is compared on the chip
(benchmark/runners/serve_conv_moe.py).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import parallel as par
from mxnet_tpu.models import HybridLM, HybridLMConfig
from mxnet_tpu.models import experts, hybrid, recurrent, window_moe
from mxnet_tpu.serving import GenerationEngine

from test_hybrid_lm import (TOL, VOCAB, _close, _poisoned,  # noqa: F401
                            _tokens)
from test_hybrid_lm_olmo import _Programs, _err
from reference import lfm2_moe as lref  # noqa: E402
from runners.serve_conv_moe import Published  # noqa: E402

lref.PAD_TO = 32
lref.BLOCK = 16
# the catalog row's keys as given (model-configs/architectures.jsonl,
# LFM2-8B-A1B), at tiny sizes
LFM2 = dict(
    model_type="lfm2_moe", conv_L_cache=3, conv_bias=False, hidden_size=64,
    intermediate_size=96,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv", "conv", "conv", "full_attention",
                 "conv"],
    max_position_embeddings=128, moe_intermediate_size=32, norm_eps=1e-5,
    norm_topk_prob=True, num_attention_heads=4, num_dense_layers=2,
    num_experts=8, num_experts_per_tok=2, num_hidden_layers=8,
    num_key_value_heads=2, rope_theta=1000000, routed_scaling_factor=1,
    use_expert_bias=True, vocab_size=VOCAB, dtype="float32")
MEMBERS = ("k", "v", "conv", "routed")


def _lm(**changes):
    cfg = dataclasses.replace(HybridLMConfig.from_config(LFM2), **changes)
    return HybridLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))


@pytest.fixture(scope="module")
def lfm2():
    lm = _lm()
    params = lm.init_params(jax.random.PRNGKey(0))
    return lm, params, Published(params, LFM2)


def _kernels(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")


def test_lfm2_builds_what_the_block_names(lfm2):
    lm, params, weights = lfm2
    c = lm.cfg
    assert c.layer_types == ("conv", "conv", "full_attention", "conv", "conv",
                             "conv", "full_attention", "conv")
    assert (c.embedding_multiplier, c.residual_multiplier, c.logits_scaling,
            c.attention_multiplier) == (1.0, 1.0, 1.0, 16 ** -0.5)
    assert (c.rms_norm_eps, c.rope_theta, c.conv_L_cache) == (1e-5, 1e6, 3)
    assert (c.qk_norm, c.qk_norm_heads, c.tie_word_embeddings,
            c.post_norm_kinds) == (True, True, True, ())
    assert (lm.n_attention, lm.n_recurrent, lm.n_expert_layers) == (2, 6, 6)
    assert lm._page == [0, 1, 0, 2, 3, 4, 1, 5]
    assert isinstance(lm.mixer, recurrent.ShortConvMixer)
    assert lm.mixer.state_shape is None and lm.members == MEMBERS
    assert "head" not in params
    assert params["l0.c_in"].shape == (64, 192)
    assert params["l0.conv_w"].shape == (3, 64)
    assert params["l2.q_norm"].shape == params["l2.k_norm"].shape == (16,)
    assert params["l1.w_in"].shape == (64, 192) and "l2.w_in" not in params
    assert params["l2.experts_in"].shape == (8, 64, 64)
    assert params["l2.experts_out"].shape == (8, 32, 64)
    assert params["l2.router"].dtype == params["l2.router_bias"].dtype \
        == jnp.float32
    cache = lm.init_cache(3, 64)
    assert [(m.shape, m.dtype) for m in cache] == [
        ((3, 2, 2, 64, 16), jnp.float32), ((3, 2, 2, 64, 16), jnp.float32),
        ((3, 6, 2, 64), jnp.float32), ((3, 6, 2), jnp.int32)]
    traits = lm.cache_traits(cache)
    assert traits["state_bytes_per_slot"] == 6 * 2 * 64 * 4    # the windows
    assert not traits["rewindable"]
    assert traits["tick_counters"] == (
        "expert_assignments", "experts_hit", "expert_tokens_max",
        "kv_rows_live_full")
    # every leaf reaches the reference under a name it asks for
    asked = {"embed_tokens.weight", "embedding_norm.weight"}
    for i, kind in enumerate(c.layer_types):
        names = lref.NORMS + (lref.CONV if kind == "conv"
                              else lref.ATTENTION)
        names += lref.DENSE if i < 2 else (
            "feed_forward.gate.weight", "feed_forward.expert_bias") + tuple(
                f"feed_forward.experts.{e}.w{j}.weight"
                for e in range(8) for j in (1, 2, 3))
        asked |= {f"layers.{i}.{n}" for n in names}
    assert set(weights) == asked and len(weights) == len(asked)
    assert weights["layers.0.conv.conv.weight"].shape == (64, 1, 3)
    assert weights["layers.3.feed_forward.experts.5.w3.weight"].shape \
        == (64, 32)


def test_lfm2_margins_leave_room(lfm2):
    """The tolerance of this file holds for every token because no router
    margin of the sequences it uses is within a float32 rounding of zero."""
    _, _, weights = lfm2
    smallest = []
    for n, seed in ((29, 0), (40, 5), (19, 13)):
        _, _, margins = lref.forward(LFM2, weights, _tokens(n, seed=seed),
                                     [n - 1])
        smallest.append(float(np.min(margins)))
    print("smallest router margins:", smallest)
    assert min(smallest) > 1e-5


@pytest.mark.parametrize("length", [1, 2, 7, 29])
def test_lfm2_forward_matches_reference(lfm2, length):
    lm, params, weights = lfm2
    seq = _tokens(length)
    want = lref.logits(LFM2, weights, seq, np.arange(length))
    _close(lm.forward(params, seq[None])[0], want, "logits")


def _slot_rows(cache, slot, page, n):
    return np.stack([np.asarray(m)[slot, page, :, :n].transpose(1, 0, 2)
                     for m in cache[:2]], axis=1)


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("prompt_len,bucket", [(1, 8), (2, 8), (8, 8),
                                               (9, 16), (13, 32)])
def test_lfm2_prefill_then_decode_matches_full_forward(lfm2, monkeypatch,
                                                       path, prompt_len,
                                                       bucket):
    """Prefill into a slot whose previous occupant left NaN everywhere (the
    prompt ending inside the bucket's padding, or shorter than the window),
    then decode through the cache; every logit row, the slot's windows and
    its K/V rows are the reference's; the other slots' windows stay NaN and
    their `routed` stays what it was."""
    lm, params, weights = lfm2
    if path == "kernels":
        _kernels(monkeypatch)
        assert lm.decode_block((3, 2, 2, 128, 16), jnp.float32) == 128
    steps = 6
    seq = _tokens(prompt_len + steps, seed=prompt_len)
    want, (windows, kv), _ = lref.forward(
        LFM2, weights, seq, np.arange(prompt_len - 1, len(seq)))
    run = _Programs(lm)
    poisoned = _poisoned(lm, 3, 128)
    poisoned = poisoned[:3] + (jnp.full(poisoned[3].shape, -7, jnp.int32),)
    logits, cache = run.prefill(params, poisoned, seq[:prompt_len], bucket,
                                slot=1)
    _close(logits, want[0], "prefill logits")
    for t in range(prompt_len, len(seq)):
        logits, cache = run.decode(params, cache, 1, seq[t], t)
        _close(logits, want[t - prompt_len + 1], f"decode logits at {t}")
    for got, window in zip(np.asarray(cache[2])[1], windows):
        _close(got, window, "conv window")
    for page, rows in enumerate(kv):
        _close(_slot_rows(cache, 1, page, len(seq)), rows, "K/V rows")
    assert np.isnan(np.asarray(cache[2])[[0, 2]]).all(), \
        "a dead slot's window was touched"
    routed = np.asarray(cache[3])
    assert (routed[[0, 2]] == -7).all()
    assert ((routed[1] >= 0) & (routed[1] < 8)).all()


def test_lfm2_padding_leaves_the_true_last_tokens_window(lfm2):
    lm, params, _ = lfm2
    prompt = _tokens(13, seed=3)
    run = _Programs(lm)
    _, exact = run.prefill(params, lm.init_cache(2, 64), prompt, 13, 0)
    _, padded = run.prefill(params, lm.init_cache(2, 64), prompt, 32, 0)
    assert np.abs(np.asarray(exact[2][0])).sum() > 0
    np.testing.assert_array_equal(np.asarray(exact[2][0]),
                                  np.asarray(padded[2][0]))
    np.testing.assert_array_equal(np.asarray(padded[2][1]), 0)


def test_lfm2_blockwise_prefill_is_the_matrix_one(lfm2, monkeypatch):
    """The prefill attention past the score budget runs blockwise
    (`window_moe._band_attention`) and gives what one score matrix gives;
    which of the two a bucket takes follows from shapes alone."""
    lm, params, _ = lfm2
    assert not lm.prefill_blockwise(64)
    seq = _tokens(64, seed=9)
    want = lm.forward(params, seq[None])[0]
    monkeypatch.setattr(window_moe, "_ATTN_BLOCK", 16)
    monkeypatch.setattr(hybrid, "_SCORES_BUDGET", 4 * 4 * 32 * 32)
    assert lm.prefill_blockwise(64) and not lm.prefill_blockwise(32) \
        and not lm.prefill_blockwise(72)
    _close(lm.forward(params, seq[None])[0], want, "blockwise logits")
    # the published heads at the cell's buckets
    big = dataclasses.replace(lm.cfg, hidden_size=2048,
                              num_attention_heads=32, num_key_value_heads=8)
    monkeypatch.undo()
    big = HybridLM(big, lm.mesh)
    assert [big.prefill_blockwise(b) for b in (1024, 2048, 4096, 8192)] \
        == [False, False, True, True]


def test_expert_bias_moves_the_selection_and_never_the_weights(lfm2):
    """`expert_bias` enters the choice of experts only: with a large bias
    toward two experts every token chooses them, and their weights are
    still the unbiased scores over their sum (+ 1e-6)."""
    lm, params, weights = lfm2
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 64), jnp.float32)
    chosen0, w0 = lm._route(params, 2, x)
    bias = jnp.zeros(8).at[jnp.array([3, 6])].set(10.0)
    chosen1, w1 = lm._route(dict(params, **{"l2.router_bias": bias}), 2, x)
    assert set(np.asarray(chosen1).ravel()) == {3, 6}
    assert not np.array_equal(np.sort(np.asarray(chosen0), -1),
                              np.sort(np.asarray(chosen1), -1))
    s = jax.nn.sigmoid(jnp.dot(x, params["l2.router"],
                               precision=jax.lax.Precision.HIGHEST))
    picked = np.take_along_axis(np.asarray(s), np.asarray(chosen1), -1)
    np.testing.assert_allclose(
        np.asarray(w1), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # the bias as drawn changes some selections and not all
    unbiased, _ = lm._route(dict(params, **{"l2.router_bias": jnp.zeros(8)}),
                            2, jax.random.normal(jax.random.PRNGKey(5),
                                                 (512, 64), jnp.float32))
    biased, _ = lm._route(params, 2, jax.random.normal(
        jax.random.PRNGKey(5), (512, 64), jnp.float32))
    moved = (np.sort(np.asarray(unbiased), -1)
             != np.sort(np.asarray(biased), -1)).any(-1).mean()
    assert 0.01 < moved < 0.5, moved
    # without the key the block has no bias parameter at all
    plain = HybridLM(HybridLMConfig.from_config(
        dict(LFM2, use_expert_bias=False)), lm.mesh)
    assert not [n for n in plain._shapes() if n.endswith("router_bias")]


def _no_gate(self, params, i, c, conv):
    return conv.astype(c.dtype) @ params[f"l{i}.c_out"]


def _activated(self, params, i, c, conv):
    y = c.astype(jnp.float32) * jax.nn.silu(conv)
    return y.astype(c.dtype) @ params[f"l{i}.c_out"]


def _wrong_order(self, params, i, u):           # C | B | x
    c, b, x = jnp.split(u @ params[f"l{i}.c_in"], 3, axis=-1)
    return c, b * x


def _qk_norm_whole(lm):
    """Olmo's whole-vector norm in place of the per-head one."""
    other = HybridLM(dataclasses.replace(lm.cfg, qk_norm_heads=False),
                     lm.mesh)

    def qkv(params, i, u):
        params = dict(params, **{
            f"l{i}.q_norm": jnp.tile(params[f"l{i}.q_norm"], 4),
            f"l{i}.k_norm": jnp.tile(params[f"l{i}.k_norm"], 2)})
        return other._qkv(params, i, u)
    return qkv


@pytest.mark.parametrize("sabotage", [
    "none", "no_gate", "an_activation", "c_b_x", "qk_norm_whole_vector",
    "norm_after_rotation", "no_rotary", "theta_1e4", "bias_in_the_weights",
    "router_eps_1e-2", "no_normalisation", "untied_head", "bias_ignored"])
def test_the_comparison_sees_each_part_of_the_lfm2_block(lfm2, monkeypatch,
                                                         sabotage):
    """Each fault the comparison guards misses the tolerance — by the full
    forward's logits or by the logits of a prefill and 30 decode steps — and
    the block as built meets it."""
    _, params, weights = lfm2
    lm = _lm()
    # norm weights that differ a channel, so that a norm on the wrong axis
    # or side shows
    params = {k: v * (1 + 0.5 * jnp.arange(v.shape[0]) / v.shape[0])
              if k.endswith(("q_norm", "k_norm")) else v
              for k, v in params.items()}
    weights = Published(params, LFM2)
    if sabotage == "no_gate":
        monkeypatch.setattr(lm.mixer, "_out", _no_gate.__get__(lm.mixer))
    elif sabotage == "an_activation":
        monkeypatch.setattr(lm.mixer, "_out", _activated.__get__(lm.mixer))
    elif sabotage == "c_b_x":
        monkeypatch.setattr(lm.mixer, "_project",
                            _wrong_order.__get__(lm.mixer))
    elif sabotage == "qk_norm_whole_vector":
        monkeypatch.setattr(lm, "_qkv", _qk_norm_whole(lm))
    elif sabotage == "norm_after_rotation":
        plain = HybridLM(dataclasses.replace(
            lm.cfg, qk_norm=False, qk_norm_heads=False), lm.mesh)
        rotate, weights_of = lm._rotate, {}

        def unnormed(params, i, u):
            weights_of["q"], weights_of["k"] = (
                params[f"l{i}.q_norm"], params[f"l{i}.k_norm"])
            return plain._qkv(params, i, u)

        def rotate_then_norm(q, k, positions):
            q, k = rotate(q, k, positions)
            return (lm._rms(q, weights_of["q"]), lm._rms(k, weights_of["k"]))
        monkeypatch.setattr(lm, "_qkv", unnormed)
        monkeypatch.setattr(lm, "_rotate", rotate_then_norm)
    elif sabotage == "no_rotary":
        lm = _lm(rope_theta=None)
    elif sabotage == "theta_1e4":
        lm = _lm(rope_theta=1e4)
    elif sabotage == "bias_in_the_weights":
        def biased(x, router, bias, top_k, scale, normalise=True, eps=0.0):
            s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router,
                                       precision=jax.lax.Precision.HIGHEST))
            w, chosen = jax.lax.top_k(s + bias, top_k)
            return chosen, w / (w.sum(-1, keepdims=True) + eps) * scale
        monkeypatch.setattr(experts, "sigmoid_route", biased)
    elif sabotage == "router_eps_1e-2":
        real = experts.sigmoid_route
        monkeypatch.setattr(
            experts, "sigmoid_route",
            lambda *a, eps=0.0, **k: real(*a, eps=1e-2, **k))
    elif sabotage == "no_normalisation":
        lm = _lm(norm_topk_prob=False)
    elif sabotage == "untied_head":
        lm = _lm(tie_word_embeddings=False)
        params = dict(params, head=jax.random.normal(
            jax.random.PRNGKey(9), (64, VOCAB)) / 8)
    elif sabotage == "bias_ignored":            # use_expert_bias read as off
        lm = _lm(use_expert_bias=False)
    seq = _tokens(40, seed=5)
    want = lref.logits(LFM2, weights, seq, np.arange(40))
    errs = [_err(lm.forward(params, seq[None])[0], want)]
    run = _Programs(lm)
    logits, cache = run.prefill(params, lm.init_cache(1, 64), seq[:9], 16, 0)
    rows = [np.asarray(logits)]
    for t in range(9, 40):
        logits, cache = run.decode(params, cache, 0, seq[t], t)
        rows.append(np.asarray(logits))
    errs.append(_err(np.stack(rows), np.asarray(want)[8:]))
    if sabotage == "none":
        assert max(errs) <= TOL, errs
    else:
        assert max(errs) > 10 * TOL, (sabotage, errs)


@pytest.mark.parametrize("change,said", [
    (dict(conv_bias=True), "conv_bias=True"),
    (dict(layer_types=["conv", "sliding_attention"]),
     r"unknown layer types \['sliding_attention'\]"),
    (dict(layer_types=["conv", "mamba"]), "mamba_expand|one kind"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(rope_scaling={"factor": 2.0}), "rope_scaling"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(num_dense_layers=9), "num_dense_layers=9 of 8"),
])
def test_lfm2_from_config_refuses_by_name(change, said):
    with pytest.raises(ValueError, match=said):
        lm_cfg = HybridLMConfig.from_config(dict(LFM2, **change))
        HybridLM(lm_cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))


def test_lfm2_from_config_reads_keys_only_where_they_are_named():
    """A missing key of a kind of layer that IS named is refused by its name
    and the kind's; a cut that leaves only dense layers needs no expert key;
    a second kind of recurrent layer beside `conv` is refused."""
    missing = {k: v for k, v in LFM2.items() if k != "conv_L_cache"}
    with pytest.raises(ValueError, match="'conv_L_cache'.*'conv' layers"):
        HybridLMConfig.from_config(missing)
    missing = {k: v for k, v in LFM2.items() if k != "moe_intermediate_size"}
    with pytest.raises(ValueError, match="'moe_intermediate_size'.*'expert'"):
        HybridLMConfig.from_config(missing)
    dense = {k: v for k, v in LFM2.items()
             if k not in ("num_experts", "moe_intermediate_size",
                          "num_experts_per_tok")}
    cfg = HybridLMConfig.from_config(dict(dense, num_hidden_layers=2))
    lm = HybridLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))
    assert lm.members == ("k", "v", "conv") and lm.n_expert_layers == 0
    assert "tick_counters" not in lm.cache_traits(lm.init_cache(2, 16))
    mixed = dataclasses.replace(HybridLMConfig.from_config(LFM2),
                                layer_types=("conv", "linear_attention"))
    with pytest.raises(ValueError, match="one kind of recurrent layer"):
        HybridLM(mixed, par.create_mesh(devices=jax.devices()[:1], dp=1))


def _greedy(weights, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        row = lref.logits(LFM2, weights, np.asarray(seq), [len(seq) - 1])
        seq.append(int(np.asarray(row)[0].argmax()))
    return seq[len(prompt):]


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_lfm2_engine_serves_more_requests_than_slots(lfm2, monkeypatch,
                                                     path):
    """Through `GenerationEngine`, 7 requests over 3 slots (slots die and
    are refilled): every stream is the reference's greedy continuation. On
    the kernel path the slab kernel, interpreted, does the decode attention
    (the grouped product needs whole row tiles of 128, which 3 slots x 2
    experts are not: `lax.ragged_dot`)."""
    lm, params, weights = lfm2
    if path == "kernels":
        _kernels(monkeypatch)
    prompts = [_tokens(n, seed=n) for n in (3, 8, 9, 17, 5, 30, 1)]
    with GenerationEngine(lm, params, max_slots=3, max_len=128,
                          buckets=(8, 32), prefix_cache=False,
                          spec_k=0) as eng:
        assert len(eng._kv) == 4
        assert (eng._slab_block == 128) == (path == "kernels")
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.result(timeout=300) for s in streams]
    for p, g in zip(prompts, got):
        assert g == _greedy(weights, p, 6)


def test_lfm2_dead_slots_stay_bit_for_bit(lfm2):
    """A decode step over slots of which some are dead: the dead slots'
    windows, rows and `routed` are bit-for-bit what they were."""
    lm, params, _ = lfm2
    run = _Programs(lm)
    cache = lm.init_cache(3, 32)
    for slot, n in ((0, 5), (1, 9), (2, 3)):
        _, cache = run.prefill(params, cache, _tokens(n, seed=slot), 16, slot)
    _, cache = run.decode(params, cache, 0, 4, 5)    # `routed` of slot 0
    before = [np.asarray(m) for m in cache]
    tokens = jnp.asarray([5, 6, 7], jnp.int32)
    positions = jnp.asarray([-1, 9, -1], jnp.int32)
    out = run.decode_fn(params, *cache, tokens, positions)
    for name, was, now in zip(lm.members, before, out[1:]):
        now = np.asarray(now)
        np.testing.assert_array_equal(now[[0, 2]], was[[0, 2]], err_msg=name)
        assert not np.array_equal(now[1], was[1]), name


def test_lfm2_counters_against_a_host_count_and_no_late_compile(lfm2):
    """The engine's state counters now count windows
    (`state_bytes_per_slot`), the model's own tick counters (computed on the
    device from what each decode left in `routed` and from its positions)
    against a host count, the trace-time counter of the grouped product's
    path, and jax's own count of compiles after warm-up: none."""
    from jax import monitoring

    from mxnet_tpu import telemetry

    lm, params, _ = lfm2
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    prev = telemetry.enabled()
    telemetry.enable()
    monitoring.register_event_duration_secs_listener(listener)
    try:
        path0 = telemetry.counter("moe.grouped_product.ragged_dot").value
        eng = GenerationEngine(lm, params, max_slots=3, max_len=64,
                               buckets=(16,), start=False,
                               prefix_cache=False, spec_k=0)
        per_slot = int(eng._kv[2].nbytes) // 3
        assert per_slot == 6 * 2 * 64 * 4
        eng.warm()
        # two grouped products an expert layer, in the decode program and in
        # the one prefill bucket
        assert telemetry.counter("moe.grouped_product.ragged_dot").value \
            - path0 == 2 * 2 * lm.n_expert_layers
        del compiles[:]
        pre = "serving.generation."
        names = ("state_slots_live", "state_bytes_touched",
                 "kv_rows_live_full", "expert_assignments", "experts_hit",
                 "expert_tokens_max")
        c0 = {k: telemetry.counter(pre + k).value for k in names}
        lens = (5, 9)
        streams = [eng.submit(_tokens(n, seed=n), max_new_tokens=3)
                   for n in lens]
        for _ in range(20):
            if all(s.done for s in streams):
                break
            eng._tick_once()
        eng.close()
        got = {k: telemetry.counter(pre + k).value - c0[k] for k in names}
        assert got["state_slots_live"] == 4     # 2 sessions x 2 decodes
        assert got["state_bytes_touched"] == 2 * 4 * per_slot
        assert got["kv_rows_live_full"] == sum(
            n + 1 + n + 2 for n in lens) * lm.n_attention
        # 4 decoded tokens x 2 experts x 6 expert layers
        assert got["expert_assignments"] == 4 * 2 * lm.n_expert_layers
        # two live slots a decode: an expert layer has 2 to 4 experts hit
        # and its fullest holds 1 or 2 tokens
        assert 2 * 2 * 6 <= got["experts_hit"] <= 2 * 4 * 6
        assert 2 * 1 * 6 <= got["expert_tokens_max"] <= 2 * 2 * 6
        assert telemetry.gauge(pre + "state_bytes_resident").value \
            == 3 * per_slot
        assert compiles == []
    finally:
        monitoring.unregister_event_duration_listener(listener)
        telemetry.enable(prev)


@pytest.mark.parametrize("slots", [64, 32])
def test_lfm2_decode_takes_gmm_where_rows_fill_a_tile(lfm2, monkeypatch,
                                                      slots):
    """With whole row tiles of 128 (64 slots x 2 experts a token) the decode
    program's grouped products are jax's `gmm`, interpreted here, and give
    what `lax.ragged_dot` gives; 32 slots x 2 are no whole tile and stay
    with `ragged_dot`. Counted once a trace."""
    from mxnet_tpu import telemetry

    lm, params, _ = lfm2
    cache = lm.init_cache(slots, 16)
    tokens = jnp.asarray(_tokens(slots, seed=2))
    positions = jnp.where(jnp.arange(slots) % 3 == 0, -1,
                          jnp.arange(slots) % 7)
    want = jax.jit(lm.decode_step)(params, *cache, tokens, positions)
    _kernels(monkeypatch)
    monkeypatch.setattr(lm, "decode_block", lambda *a: None)   # the slab: XLA
    prev = telemetry.enabled()
    telemetry.enable()
    try:
        n0 = {k: telemetry.counter("moe.grouped_product." + k).value
              for k in ("gmm", "ragged_dot")}
        got = jax.jit(lm.decode_step)(params, *cache, tokens, positions)
        moved = {k: telemetry.counter("moe.grouped_product." + k).value
                 - n0[k] for k in n0}
    finally:
        telemetry.enable(prev)
    calls = 2 * lm.n_expert_layers
    assert moved == ({"gmm": calls, "ragged_dot": 0} if slots == 64
                     else {"gmm": 0, "ragged_dot": calls})
    alive = np.asarray(positions) >= 0
    _close(np.asarray(got[0])[alive], np.asarray(want[0])[alive], "logits")
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]))
