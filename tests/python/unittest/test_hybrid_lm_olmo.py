"""`HybridLM` as the Olmo-Hybrid block (ISSUE 42): gated-delta-rule layers
beside unrotated full-attention layers, against the plain reference
`benchmark/reference/olmo_hybrid.py`, and what the second block must leave
alone: granite's parameters and lowered programs. Tiny, with the published
structure: period "lllF", dk != dv, head counts that are no power of two (6
linear heads of 8 x 64, 3 attention heads of 16), chunks of 8. A file of its
own beside `test_hybrid_lm.py` (whose helpers and `tiny` fixture it uses) so
that the two run on two workers. The model is float32 here, so every
tolerance is 1e-4 of the compared quantity's scale, and
`test_the_comparison_sees_each_part_of_the_olmo_block` pins what misses it.
The bfloat16 model at the published widths is compared on the chip
(benchmark/runners/serve_gdn_hybrid.py).
"""
import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import parallel as par
from mxnet_tpu.models import HybridLM, HybridLMConfig
from mxnet_tpu.models import recurrent
from mxnet_tpu.models import rotary
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import pallas_ssm
from mxnet_tpu.ops import pallas_window
from mxnet_tpu.serving import GenerationEngine

from test_hybrid_lm import (CONFIG, TOL, VOCAB, _close,  # noqa: F401
                            _poisoned, _tokens, tiny)
from reference import olmo_hybrid as oref  # noqa: E402
from runners.serve_gdn_hybrid import Published  # noqa: E402

oref.PAD_TO = 32
OLMO = dict(
    model_type="olmo_hybrid", vocab_size=VOCAB, hidden_size=48,
    intermediate_size=96, num_hidden_layers=5, num_attention_heads=3,
    num_key_value_heads=3, hidden_act="silu", max_position_embeddings=128,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=["linear_attention", "linear_attention", "linear_attention",
                 "full_attention", "linear_attention", "linear_attention",
                 "linear_attention", "full_attention"],
    linear_num_key_heads=6, linear_num_value_heads=6, linear_key_head_dim=8,
    linear_value_head_dim=64, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
    gdn_chunk_size=8, dtype="float32")


def _olmo_lm(**changes):
    cfg = dataclasses.replace(HybridLMConfig.from_config(OLMO), **changes)
    return HybridLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))


@pytest.fixture(scope="module")
def olmo():
    lm = _olmo_lm()
    params = lm.init_params(jax.random.PRNGKey(0))
    return lm, params, Published(params, OLMO)


class _Programs:
    """A model's prefill and decode, each jitted ONCE (`test_hybrid_lm`'s
    helpers jit anew a call, a second a step)."""

    def __init__(self, lm):
        self.prefill_fn = jax.jit(lm.prefill)
        self.decode_fn = jax.jit(lm.decode_step)

    def prefill(self, params, cache, prompt, bucket, slot):
        padded = np.full(bucket, 7, np.int32)       # padded "with anything"
        padded[:len(prompt)] = prompt
        out = self.prefill_fn(params, *cache, jnp.asarray(padded),
                              jnp.asarray(len(prompt), jnp.int32),
                              jnp.asarray(slot, jnp.int32))
        return out[0], tuple(out[1:])

    def decode(self, params, cache, slot, token, position):
        slots = cache[0].shape[0]
        tokens = np.zeros(slots, np.int32)
        positions = np.full(slots, -1, np.int32)
        tokens[slot], positions[slot] = token, position
        out = self.decode_fn(params, *cache, jnp.asarray(tokens),
                             jnp.asarray(positions))
        return out[0][slot], tuple(out[1:])


def _err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


def _slot_states(cache, slot, lm):
    """A slot's state pages `[dk, H dv]` as the reference's `[H, dk, dv]`."""
    m = lm.mixer
    return np.asarray(cache[2])[slot].reshape(-1, m.dk, m.heads, m.dv) \
        .transpose(0, 2, 1, 3)


def test_olmo_builds_what_the_block_names(olmo):
    lm, params, weights = olmo
    c = lm.cfg
    assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",) \
        + ("linear_attention",)         # the first num_hidden_layers
    assert (c.embedding_multiplier, c.residual_multiplier, c.logits_scaling,
            c.attention_multiplier) == (1.0, 1.0, 1.0, 16 ** -0.5)
    assert (lm.n_attention, lm.n_recurrent, lm._page) == (1, 4,
                                                         [0, 1, 2, 0, 3])
    assert isinstance(lm.mixer, recurrent.GatedDeltaMixer)
    assert params["head"].shape == (48, VOCAB) and "l3.q_norm" in params
    assert params["l0.g_in"].shape == (48, 2 * 48 + 2 * 384 + 12)
    assert params["l0.conv_w"].shape == (4, 2 * 48 + 384)
    assert not [n for n in params if n.startswith("l0.m_") or "conv_b" in n]
    assert [c.shape for c in lm.init_cache(2, 64)] == [
        (2, 1, 3, 64, 16), (2, 1, 3, 64, 16), (2, 4, 8, 384),
        (2, 4, 3, 480)]
    # every leaf reaches the reference under a name it asks for
    asked = {f"layers.{i}.{n}" for i, kind in enumerate(c.layer_types)
             for n in oref.LAYER_WEIGHTS[kind]} | {
                 "embed_tokens.weight", "lm_head.weight", "norm.weight"}
    assert set(weights) == asked
    assert weights["layers.0.linear_attn.v_conv1d.weight"].shape \
        == (384, 1, 4)


@pytest.mark.parametrize("length", [7, 8, 9, 29])
def test_olmo_forward_matches_reference(olmo, length):
    lm, params, weights = olmo
    seq = _tokens(length)
    want = oref.logits(OLMO, weights, seq, np.arange(length))
    _close(lm.forward(params, seq[None])[0], want, "logits")


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("prompt_len,bucket", [(7, 8), (8, 8), (9, 16),
                                               (29, 32), (13, 32)])
def test_olmo_prefill_then_decode_matches_full_forward(olmo, monkeypatch,
                                                       path, prompt_len,
                                                       bucket):
    """As the granite case: prefill into a slot whose previous occupant left
    NaN everywhere, then decode through the cache; every logit row, the
    slot's final states and its K/V rows are the reference's; the other
    slots stay NaN."""
    lm, params, weights = olmo
    if path == "kernels":
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        assert lm.state_kernel((3, 4, 8, 384), jnp.float32)
    steps = 6
    seq = _tokens(prompt_len + steps, seed=prompt_len)
    want, states, kv = oref.forward(OLMO, weights, seq,
                                    np.arange(prompt_len - 1, len(seq)))
    run = _Programs(lm)
    logits, cache = run.prefill(params, _poisoned(lm, 3, 128),
                                seq[:prompt_len], bucket, slot=1)
    _close(logits, want[0], "prefill logits")
    for t in range(prompt_len, len(seq)):
        logits, cache = run.decode(params, cache, 1, seq[t], t)
        _close(logits, want[t - prompt_len + 1], f"decode logits at {t}")
    for got, layer_state in zip(_slot_states(cache, 1, lm), states):
        _close(got, layer_state, "recurrent state")
    rows = np.stack([np.asarray(m)[1, 0, :, :len(seq)].transpose(1, 0, 2)
                     for m in cache[:2]], axis=1)
    _close(rows, kv[0], "K/V rows")
    assert np.isnan(np.asarray(cache[2])[[0, 2]]).all(), \
        "a dead slot's state was touched"
    assert np.isnan(np.asarray(cache[3])[[0, 2]]).all()


def test_olmo_padding_leaves_the_true_last_tokens_state(olmo):
    lm, params, _ = olmo
    prompt = _tokens(13, seed=3)
    run = _Programs(lm)
    _, exact = run.prefill(params, lm.init_cache(2, 64), prompt, 13, 0)
    _, padded = run.prefill(params, lm.init_cache(2, 64), prompt, 32, 0)
    for a, b, name in zip(exact[2:], padded[2:], ("state", "conv")):
        assert np.abs(np.asarray(a[0])).sum() > 0
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("length", [5, 16, 21])
def test_chunked_delta_rule_is_the_recurrence(length):
    """The chunked (WY) form against the token-by-token recurrence, states
    and outputs, with `beta > 1` present (negative eigenvalues) and decays
    down to 0.5 a step; lengths under a chunk, whole chunks and ragged."""
    rng = np.random.default_rng(length)
    h, dk, dv = 3, 8, 16
    lm = _olmo_lm(linear_num_heads=h, linear_key_head_dim=dk,
                  linear_value_head_dim=dv)
    f32 = jnp.float32
    q, k = (jnp.asarray(rng.standard_normal((length, h, dk)), f32)
            for _ in range(2))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(rng.standard_normal((length, h, dv)), f32)
    beta = jnp.asarray(rng.uniform(0.05, 1.95, (length, h)), f32)
    alpha = jnp.asarray(rng.uniform(0.5, 1.0, (length, h)), f32)
    assert (np.asarray(beta) > 1).any() and np.asarray(alpha).min() < 0.6
    o, state = lm.mixer.chunked(q, k, v, beta, jnp.log(alpha))
    s = np.zeros((h, dk, dv))
    want = []
    for t in range(length):
        s = np.asarray(alpha[t], np.float64)[:, None, None] * s
        u = np.asarray(v[t], np.float64) - np.einsum("hkv,hk->hv", s, k[t])
        s = s + np.einsum("hk,hv->hkv", np.asarray(beta[t])[:, None] * k[t],
                          u)
        want.append(np.einsum("hkv,hk->hv", s, q[t]))
    _close(o, np.stack(want), "chunked outputs")
    _close(state, s, "chunked state")


# what each sabotage changes, for the harness below: a model built with other
# fields, weights moved, or a piece of the program replaced
def _no_l2(self, params, i, q, k, b_raw, a_raw):
    q1, k1, beta, g = recurrent.GatedDeltaMixer._gates(self, params, i, q, k,
                                                       b_raw, a_raw)
    return q.astype(jnp.float32) * self.dk ** -0.5, k.astype(jnp.float32), \
        beta, g


def _no_q_scale(self, params, i, q, k, b_raw, a_raw):
    q, k, beta, g = recurrent.GatedDeltaMixer._gates(self, params, i, q, k,
                                                     b_raw, a_raw)
    return q * self.dk ** 0.5, k, beta, g


def _no_gate(self, params, i, o, gate):
    o = self.rms(o, params[f"l{i}.g_norm"]).reshape(o.shape[0], -1)
    return o.astype(gate.dtype) @ params[f"l{i}.g_out"]


def _decay_after(old, alpha, beta, q, k, v):
    s, dk, _ = old.shape
    h, dv = v.shape[1:]
    old = old.reshape(s, dk, h, dv)
    u = v - jnp.einsum("skhv,shk->shv", old, k)
    new = alpha[:, None, :, None] * (
        old + (beta[:, :, None] * k).transpose(0, 2, 1)[..., None]
        * u[:, None])
    return jnp.einsum("skhv,shk->shv", new, q), new.reshape(s, dk, h * dv)


def _qk_norm_a_head(lm):
    def qkv(params, i, u):
        c, t = lm.cfg, u.shape[0]
        q, k, v = ((u @ params[f"l{i}.w{s}"]).reshape(
            t, c.num_attention_heads, c.head_dim) for s in "qkv")
        q, k = (lm._rms(x, params[f"l{i}.{s}_norm"].reshape(x.shape[1:]))
                for s, x in (("q", q), ("k", k)))
        return q, k, v
    return qkv


def _rotated(lm):
    plain = lm._qkv

    def qkv(params, i, u):
        q, k, v = plain(params, i, u)
        at = jnp.arange(u.shape[0])
        inv = rotary.yarn_inv_freq(lm.cfg.head_dim, 10000.0, None)
        return (rotary.rotate_half(q, at, inv),
                rotary.rotate_half(k, at, inv), v)
    return qkv


@pytest.mark.parametrize("sabotage", [
    "none", "beta_not_doubled", "decay_after_correction", "no_l2_norm",
    "no_q_scale", "one_shared_convolution", "no_output_gate",
    "qk_norm_a_head", "norm_on_the_wrong_side", "rotary", "bf16_state"])
def test_the_comparison_sees_each_part_of_the_olmo_block(olmo, monkeypatch,
                                                         sabotage):
    """Each fault the comparison guards misses the tolerance — by the full
    forward's logits, by the logits of a prefill and 31 decode steps, or by
    the final recurrent states — and the block as built meets it."""
    _, params, weights = olmo
    lm = _olmo_lm()
    if sabotage == "beta_not_doubled":
        lm = _olmo_lm(linear_allow_neg_eigval=False)
    elif sabotage == "norm_on_the_wrong_side":
        lm = _olmo_lm(post_norm_kinds=())
    elif sabotage == "decay_after_correction":     # the decode step's order
        monkeypatch.setattr(recurrent, "gdn_step_xla", _decay_after)
    elif sabotage in ("no_l2_norm", "no_q_scale"):
        patch = {"no_l2_norm": _no_l2, "no_q_scale": _no_q_scale}[sabotage]
        monkeypatch.setattr(lm.mixer, "_gates",
                            patch.__get__(lm.mixer), raising=True)
    elif sabotage == "no_output_gate":
        monkeypatch.setattr(lm.mixer, "_out", _no_gate.__get__(lm.mixer))
    elif sabotage == "qk_norm_a_head":
        monkeypatch.setattr(lm, "_qkv", _qk_norm_a_head(lm))
        params = {k: v * (1 + 0.5 * jnp.arange(v.shape[0]) / v.shape[0])
                  if k.endswith(("q_norm", "k_norm")) else v
                  for k, v in params.items()}
        weights = Published(params, OLMO)
    elif sabotage == "rotary":
        monkeypatch.setattr(lm, "_qkv", _rotated(lm))
    elif sabotage == "one_shared_convolution":      # k's stream takes q's
        params = {k: v.at[:, 48:96].set(v[:, :48]) if k.endswith("conv_w")
                  else v for k, v in params.items()}
    seq = _tokens(40, seed=5)
    want, states, _ = oref.forward(OLMO, weights, seq, np.arange(40))
    errs = [_err(lm.forward(params, seq[None])[0], want)]
    run = _Programs(lm)
    logits, cache = run.prefill(params, lm.init_cache(1, 64), seq[:9], 16, 0)
    rows = [np.asarray(logits)]
    for t in range(9, 40):
        logits, cache = run.decode(params, cache, 0, seq[t], t)
        if sabotage == "bf16_state":
            cache = cache[:2] + (cache[2].astype(jnp.bfloat16)
                                 .astype(jnp.float32),) + cache[3:]
        rows.append(np.asarray(logits))
    errs.append(_err(np.stack(rows), np.asarray(want)[8:]))
    errs += [_err(got, s) for got, s in zip(_slot_states(cache, 0, lm),
                                            states)]
    if sabotage == "none":
        assert max(errs) <= TOL, errs
    else:
        assert max(errs) > 10 * TOL, (sabotage, errs)


# the tiny model's page (two heads of 64 lanes a group), the published page
# (30 heads of 96 x 192: fifteen groups of 384 lanes) and heads of whole lane
# rows (a group of one)
@pytest.mark.parametrize("dims", [(4, 6, 8, 64), (2, 30, 96, 192),
                                  (2, 3, 16, 128)],
                         ids=["tiny", "published", "lanes128"])
@pytest.mark.parametrize("alive", [[1, 1, 1, 1], [0, 1, 0, 1], [0, 0, 0, 0]])
def test_gdn_state_kernel_matches_the_xla_formulation(alive, dims):
    """`gdn_state_update` (interpreted) against `gdn_step_xla`, the
    formulation `step` takes elsewhere: the written state and `o` to float32
    rounding of the 2 dk terms of a contraction, dead slots and the other
    pages bit for bit (the slab goes back through the aliased output), a
    dead slot's `o` zero."""
    (NL, H, dk, dv), S, page = dims, 4, 1
    rng = np.random.default_rng(2)
    f32 = jnp.float32
    slab = jnp.asarray(rng.standard_normal((S, NL, dk, H * dv)), f32)
    alpha = jnp.asarray(rng.uniform(0.5, 1, (S, H)), f32)
    beta = jnp.asarray(rng.uniform(0, 2, (S, H)), f32)
    q, k = (jnp.asarray(rng.standard_normal((S, H, dk)), f32) / dk ** 0.5
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((S, H, dv)), f32)
    alive = jnp.asarray(alive, bool)
    assert pallas_ssm.gdn_update_applies(slab.shape, slab.dtype, H)
    o, out = pallas_ssm.gdn_state_update(slab, page, alpha, beta, q, k, v,
                                         alive, interpret=True)
    want_o, want = recurrent.gdn_step_xla(slab[:, page], alpha, beta, q, k, v)
    live = np.asarray(alive)
    if live.any():
        np.testing.assert_allclose(np.asarray(out[:, page])[live],
                                   np.asarray(want)[live], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(o)[live],
                                   np.asarray(want_o)[live], rtol=1e-5,
                                   atol=1e-5)
    assert not np.asarray(o)[~live].any()
    assert np.array_equal(np.asarray(out)[~live], np.asarray(slab)[~live])
    others = [i for i in range(NL) if i != page]
    assert np.array_equal(np.asarray(out[:, others]),
                          np.asarray(slab[:, others]))


def test_gdn_kernel_shape_test():
    f32 = jnp.float32
    assert pallas_ssm.gdn_update_applies((32, 12, 96, 5760), f32, 30)
    assert not pallas_ssm.gdn_update_applies((32, 12, 96, 5760),
                                             jnp.bfloat16, 30)
    # 3 heads of 64 lanes: no whole number of groups of two
    assert not pallas_ssm.gdn_update_applies((4, 2, 8, 192), f32, 3)
    assert not pallas_ssm.gdn_update_applies((4, 2, 12, 384), f32, 6)
    assert not pallas_ssm.gdn_update_applies((4, 2, 8, 100), f32, 2)
    # a page that, in and out and double-buffered, exceeds the budget
    assert not pallas_ssm.gdn_update_applies((4, 2, 512, 4096), f32, 32)


@pytest.mark.parametrize("alive", [[1, 1, 1, 1], [0, 1, 0, 1]])
def test_kv128_kernel_at_one_query_a_head(alive):
    """`kv_update_attend` (interpreted) at ONE query a K/V head and a head
    count that is no power of two — the full layers' tick of the Olmo block
    — against the XLA formulation; `kv_block` at its 30 heads of 128."""
    from mxnet_tpu.models import window_moe

    assert pallas_window.kv_block((32, 4, 30, 2048, 128),
                                  jnp.bfloat16) == 128
    rng = np.random.default_rng(7)
    slots, layers, heads, rows, hd = 4, 2, 3, 256, 128
    positions = np.where(alive, [127, 128, 5, 255], -1).astype(np.int32)
    slab_k, slab_v = (rng.normal(size=(slots, layers, heads, rows, hd))
                      .astype("f4") for _ in range(2))
    q, k_new, v_new = (jnp.asarray(rng.normal(size=(slots, heads, hd)),
                                   jnp.float32) for _ in range(3))
    want_k = tfm._write_rows(jnp.asarray(slab_k), 1, jnp.asarray(positions),
                             k_new)
    want_v = tfm._write_rows(jnp.asarray(slab_v), 1, jnp.asarray(positions),
                             v_new)
    want = window_moe._attend_member(q, want_k[:, 1], want_v[:, 1],
                                     jnp.asarray(positions), 0.2)
    got, got_k, got_v = pallas_window.kv_update_attend(
        q, k_new, v_new, jnp.asarray(slab_k), jnp.asarray(slab_v),
        jnp.int32(1), jnp.asarray(positions), block=128, scale=0.2,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    _close(got, want, "decode kernel")


ONE_QUERY_ROWS = 256                    # two blocks of 128
ONE_QUERY_POSITIONS = {
    # both sides of a block's edge, row 0, the last row
    "full": [127, 128, 0, ONE_QUERY_ROWS - 1],
    # a ring that has wrapped (p >= R): rows 127, 0, 128 and 255 again
    "ring": [ONE_QUERY_ROWS + 127, ONE_QUERY_ROWS, 3 * ONE_QUERY_ROWS + 128,
             4 * ONE_QUERY_ROWS - 1]}


@pytest.mark.parametrize("alive", [[1, 1, 1, 1], [0, 1, 1, 0], [0, 0, 0, 0]],
                         ids=["all", "some", "none"])
@pytest.mark.parametrize("heads", [3, 30])
@pytest.mark.parametrize("member", ["full", "ring"])
def test_kv128_one_query_body(member, heads, alive):
    """The body `kv_update_attend` takes at ONE query a K/V head — the
    step's heads as one softmax chain `[heads, block]` (ISSUE 44) —
    interpreted, against the XLA formulation: positions on both sides of a
    block's edge, row 0 and the last row of a full member, a ring that has
    wrapped; 3 heads and 30 (no multiple of the 8 sublanes of a vreg: what
    pads the chain's last vreg must reach no max and no sum); all, some and
    no slot alive (a dead slot's rows, `inf` and `nan`, go back as they
    came, and with no live slot the write-back block does); `inf` in the K
    rows and `nan` in the V rows past a live slot's position and in the row
    it writes; a scale that is not `1/sqrt(128)`. The slabs bit-equal, the
    attention at the file's tolerance."""
    from mxnet_tpu.models import window_moe

    rng = np.random.default_rng(11)
    slots, layers, rows, hd = 4, 2, ONE_QUERY_ROWS, 128
    positions = np.where(alive, ONE_QUERY_POSITIONS[member],
                         -1).astype(np.int32)
    slab_k, slab_v = (rng.normal(size=(slots, layers, heads, rows, hd))
                      .astype("f4") for _ in range(2))
    for s, p in enumerate(positions):
        for slab, left in ((slab_k, np.inf), (slab_v, np.nan)):
            if p < 0:
                slab[s] = left                  # a dead slot: all of it
            else:
                slab[s, :, :, min(p + 1, rows):] = left
                slab[s, :, :, p % rows] = left  # the row to write
    q, k_new, v_new = (jnp.asarray(rng.normal(size=(slots, heads, hd)),
                                   jnp.float32) for _ in range(3))
    at = jnp.asarray(np.where(positions >= 0, positions % rows, -1))
    want_k = tfm._write_rows(jnp.asarray(slab_k), 1, at, k_new)
    want_v = tfm._write_rows(jnp.asarray(slab_v), 1, at, v_new)
    want = window_moe._attend_member(q, want_k[:, 1], want_v[:, 1],
                                     jnp.asarray(positions), 0.37)
    got, got_k, got_v = pallas_window.kv_update_attend(
        q, k_new, v_new, jnp.asarray(slab_k), jnp.asarray(slab_v),
        jnp.int32(1), jnp.asarray(positions), block=128, scale=0.37,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    if any(alive):
        _close(got, want, "decode kernel, one query a head")
    assert not np.asarray(got)[positions < 0].any()


GROUPED_LOWERED = {     # sha256 of the lowered text on PR 45's tree (see below)
    # mellum2_12b_l8: 32 queries over 4 K/V heads, 2 full layers, 6 rings
    ((32, 2, 4, 16384, 128), 32):
        "e87e78d5f7f524f37be2c083244315ab813d993b514f58ec448ca6638e00b7e2",
    ((32, 6, 4, 1024, 128), 32):
        "d485ec629f8b761e6ac9a1915b9e90a3b20e8a73f54e9516dae89f7a8b8c680e",
    # trinity_large_ep8: 48 queries over 8 K/V heads, a full layer, 4 rings
    ((32, 1, 8, 16384, 128), 48):
        "e345e72c91197899442e6644869c4ce8e50fb0fc364c991e0d349a724b631096",
    ((32, 4, 8, 4096, 128), 48):
        "c2e169cabd47f8562f259c7b5b26a771a150c6c2fa2196a026fd852289ba2a4e"}


@pytest.mark.parametrize("slab,q_heads", list(GROUPED_LOWERED), ids=[
    "mellum-full", "mellum-rings", "trinity-full", "trinity-rings"])
def test_kv128_grouped_lowers_what_it_did(slab, q_heads):
    """The one-query body is a branch on a static fact of the trace: at
    mellum's 8 and Trinity's 6 queries a K/V head, over the members of
    their cells, `kv_update_attend` lowers to a pinned text (its sha256;
    the kernel interpreted, so the text is the kernel's own operations, the
    grid, the block specs and the aliasing — a Mosaic lowering carries the
    source's line numbers and moves with any edit above the kernel).
    The values are of the commit of PR 45 (on top of 231f04e, PR 44), which
    made the grid one axis over the tick's live blocks: the text carries
    the grid and the block specs, so it moved by design. The grouped
    body's arithmetic did not change — a step learns its slot and block
    from the work list, no more — which is held numerically, not by this
    text: `test_window_moe_lm.py::test_kv128_grid_is_the_live_blocks` and
    `test_decode_kernel_matches_restatement`."""
    block = pallas_window.kv_block(slab, jnp.bfloat16)
    assert block == {4: 1024, 8: 512}[slab[2]]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    def fn(q, k, v, slab_k, slab_v, positions):
        return pallas_window.kv_update_attend(
            q, k, v, slab_k, slab_v, jnp.int32(0), positions, block=block,
            scale=128 ** -0.5, interpret=True)

    text = jax.jit(fn, donate_argnums=(3, 4)).lower(
        sds((32, q_heads, 128)), sds((32, slab[2], 128)),
        sds((32, slab[2], 128)), sds(slab), sds(slab),
        sds((32,), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == GROUPED_LOWERED[slab, q_heads]


GRANITE_LOWERED = {     # sha256 of the lowered text at commit eef6210 (PR 41)
    ("xla", "decode"):
        "3d4c97a630722d1d0cf1191e443c763ca2eb783cc2399c21741a353ef06d79c7",
    ("xla", "prefill"):
        "dd7c68c467c0186d8f3c26059ff6ff0fa0077dab2a177a0a58e01aba4e3c23d2",
    # ... but this one, of the commit of PR 48 (on top of f6fb760, PR 47):
    # `decode_update_attend` scores a K/V head's group of queries on the
    # MXU, so granite's kernel text moved by design (e01cb83e... before)
    ("kernels", "decode"):
        "2dea706b2bf268c717ec678b61b8e465bc4c73b63573e2d4c451911185944a71",
    ("kernels", "prefill"):
        "dd7c68c467c0186d8f3c26059ff6ff0fa0077dab2a177a0a58e01aba4e3c23d2"}
GRANITE_LEAVES = {
    "attention": {"norm1", "norm2", "w_in", "w_out", "wq", "wk", "wv", "wo"},
    "mamba": {"norm1", "norm2", "w_in", "w_out", "m_in", "conv_w", "conv_b",
              "dt_bias", "A_log", "D", "m_norm", "m_out"}}


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_granite_builds_and_lowers_what_it_did(tiny, monkeypatch, path):
    """What the Olmo block added compiles to nothing for granite: the same
    parameter names and shapes, and the decode and prefill programs lower to
    the text they lowered to before the class knew a second block (its
    sha256, taken on the parent commit; the installation is pinned, so the
    text is a function of the program alone)."""
    lm, params, _ = tiny
    assert set(params) == {"embed", "norm_f"} | {
        f"l{i}.{leaf}" for i, kind in enumerate(CONFIG["layer_types"])
        for leaf in GRANITE_LEAVES[kind]}
    assert params["l0.m_in"].shape == (64, 2 * 128 + 2 * 16 + 4)
    assert params["l0.conv_w"].shape == (4, 160)
    assert [c.shape for c in lm.init_cache(3, 128)] == [
        (3, 1, 2, 128, 8), (3, 1, 2, 128, 8), (3, 3, 4, 32, 16),
        (3, 3, 3, 160)]
    assert "tick_counters" not in lm.cache_traits(lm.init_cache(3, 128))
    if path == "kernels":
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(3, 128))
    ints = jax.ShapeDtypeStruct((3,), jnp.int32)
    one = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = {
        "decode": jax.jit(lm.decode_step).lower(shapes, *cache, ints, ints),
        "prefill": jax.jit(lm.prefill).lower(
            shapes, *cache, jax.ShapeDtypeStruct((16,), jnp.int32), one,
            one)}
    for name, low in lowered.items():
        assert hashlib.sha256(low.as_text().encode()).hexdigest() \
            == GRANITE_LOWERED[path, name], (path, name)


@pytest.mark.parametrize("change,said", [
    (dict(layer_types=["mamba", "ring"]), r"unknown layer types \['ring'\]"),
    (dict(layer_types=["mamba", "linear_attention"]),
     "one kind of recurrent layer"),
])
def test_layer_kinds_are_refused_by_name(change, said):
    cfg = dataclasses.replace(HybridLMConfig.from_config(CONFIG),
                              layer_types=tuple(change["layer_types"]))
    with pytest.raises(ValueError, match=said):
        HybridLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))


def test_from_config_reads_a_mixers_keys_only_where_it_is_named():
    """A configuration without Mamba layers needs no Mamba key (it died on a
    KeyError for `mamba_expand`); a missing key of a kind that IS named is
    refused by its name and the kind's; what the Olmo block cannot express
    is refused by name."""
    no_mamba = {k: v for k, v in CONFIG.items() if not k.startswith("mamba")}
    no_mamba["layer_types"] = ["attention", "attention"]
    cfg = HybridLMConfig.from_config(no_mamba)
    assert cfg.layer_types == ("attention", "attention")
    lm = HybridLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))
    assert (lm.n_attention, lm.n_recurrent) == (2, 0)
    seq = _tokens(9)
    assert np.isfinite(np.asarray(lm.forward(
        lm.init_params(jax.random.PRNGKey(1)), seq[None]))).all()
    with pytest.raises(ValueError, match="'mamba_expand'.*'mamba' layers"):
        HybridLMConfig.from_config(dict(no_mamba, layer_types=["mamba"]))
    only_full = {k: v for k, v in OLMO.items() if not k.startswith("linear")}
    only_full["layer_types"] = ["full_attention"] * 2
    assert HybridLMConfig.from_config(only_full).qk_norm
    missing = {k: v for k, v in OLMO.items() if k != "linear_key_head_dim"}
    with pytest.raises(ValueError, match="'linear_key_head_dim'.*"
                                         "'linear_attention' layers"):
        HybridLMConfig.from_config(missing)
    for change, said in (
            (dict(rope_parameters={"rope_theta": 500000.0}), "rope_theta"),
            (dict(attention_bias=True), "attention_bias"),
            (dict(tie_word_embeddings=True), "tie_word_embeddings"),
            (dict(linear_num_key_heads=3), "linear_num_key_heads"),
            (dict(hidden_act="gelu"), "hidden_act")):
        with pytest.raises(ValueError, match=said):
            HybridLMConfig.from_config(dict(OLMO, **change))


def _olmo_greedy(weights, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        row = oref.logits(OLMO, weights, np.asarray(seq), [len(seq) - 1])
        seq.append(int(np.asarray(row)[0].argmax()))
    return seq[len(prompt):]


def test_olmo_engine_serves_more_requests_than_slots(olmo):
    lm, params, weights = olmo
    prompts = [_tokens(n, seed=n) for n in (3, 8, 9, 17, 5, 30, 12)]
    with GenerationEngine(lm, params, max_slots=3, max_len=64,
                          buckets=(8, 32), prefix_cache=False,
                          spec_k=0) as eng:
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.result(timeout=120) for s in streams]
    for p, g in zip(prompts, got):
        assert g == _olmo_greedy(weights, p, 6)


def test_olmo_counters_against_a_host_count_and_no_late_compile(olmo):
    """The engine's state counters from `state_bytes_per_slot`, the model's
    own `kv_rows_live_full` (computed on the device from the positions of
    each decode) against a host count, and jax's own count of compiles after
    warm-up: none."""
    from jax import monitoring

    from mxnet_tpu import telemetry

    lm, params, _ = olmo
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    prev = telemetry.enabled()
    telemetry.enable()
    monitoring.register_event_duration_secs_listener(listener)
    try:
        eng = GenerationEngine(lm, params, max_slots=3, max_len=64,
                               buckets=(16,), start=False,
                               prefix_cache=False, spec_k=0)
        assert lm.cache_traits(eng._kv)["tick_counters"] \
            == ("kv_rows_live_full",)
        per_slot = sum(int(leaf.nbytes) for leaf in eng._kv[2:]) // 3
        assert per_slot == 4 * (8 * 384 * 4 + 3 * 480 * 4)
        eng.warm()
        del compiles[:]
        pre = "serving.generation."
        names = ("state_slots_live", "state_bytes_touched",
                 "kv_rows_live_full")
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
        # a decode at position p attends p + 1 rows in the one full layer
        assert got["kv_rows_live_full"] == sum(
            n + 1 + n + 2 for n in lens) * lm.n_attention
        assert telemetry.gauge(pre + "state_bytes_resident").value \
            == 3 * per_slot
        assert compiles == []
    finally:
        monitoring.unregister_event_duration_listener(listener)
        telemetry.enable(prev)
