"""`WindowMoELM` (window and full attention layers mixed, a K/V cache whose
window layers are rings, a softmax-routed dropless expert layer; the `mellum`
block) against the plain reference `benchmark/reference/mellum_swa_moe.py`,
at a tiny size with the published structure: two periods of three window
layers and a full one, a window of 8 under sequences of 40, 8 experts of
which 2 are chosen, YaRN-corrected rotary positions on the full layers. The
model is float32 here, so it agrees with the float32 reference to rounding:
every tolerance is 1e-4 of the compared quantity's scale. The bfloat16 model
at the published widths is compared on the chip
(benchmark/runners/serve_swa_moe.py).

The second half holds the same model built as the `afmoe` block
(Trinity-Large-Preview: an output gate, per-head norms, four norms a layer,
unrotated full layers, a leading dense layer, a sigmoid router with a
selection bias beside a shared expert, 3 query heads a K/V head) against
`benchmark/reference/trinity_afmoe.py`, and shows of each part of that block
that the comparison fails when it is left out.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig
from mxnet_tpu.models import experts, window_moe
from mxnet_tpu.models.transformer import _write_rows
from mxnet_tpu.ops import pallas_window
from mxnet_tpu.serving import GenerationEngine, qos

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [os.path.join(REPO, "benchmark")]
from reference import mellum_swa_moe as ref  # noqa: E402
from reference import trinity_afmoe as aref  # noqa: E402
from runners import serve_afmoe, serve_swa_moe  # noqa: E402

from pallas_grid import grid_of_call  # noqa: E402
from lm_jit import jitted  # noqa: E402

ref.PAD_TO = ref.BLOCK = 16     # the chip's sizes would spend these tiny tests on padding
aref.PAD_TO = aref.BLOCK = 16

TOL = 1e-4
VOCAB = 211
FULL, WINDOW = "full_attention", "sliding_attention"
CONFIG = dict(
    vocab_size=VOCAB, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
    sliding_window=8, use_sliding_window=True, max_window_layers=0,
    layer_types=[WINDOW, WINDOW, WINDOW, FULL] * 2,
    mlp_layer_types=["sparse"] * 8,
    rope_parameters={
        FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 16,
               "original_max_position_embeddings": 16, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        WINDOW: {"rope_type": "default", "rope_theta": 10000}},
    max_position_embeddings=256, dtype="float32", hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False, model_type="mellum")
# what the decode kernel takes: heads of 128, a ring of whole lane rows
KERNEL = dict(CONFIG, num_hidden_layers=4, head_dim=128, sliding_window=128,
              layer_types=[WINDOW, WINDOW, WINDOW, FULL],
              mlp_layer_types=["sparse"] * 4, max_position_embeddings=512)


def _model(config):
    return WindowMoELM(WindowMoELMConfig.from_config(config),
                       par.create_mesh(devices=jax.devices()[:1], dp=1))


def _built(config):
    lm = _model(config)
    params = lm.init_params(jax.random.PRNGKey(0))
    return lm, params, serve_swa_moe.published(params, config)


@pytest.fixture(scope="module")
def tiny():
    return _built(CONFIG)


@pytest.fixture(scope="module")
def wide():
    return _built(KERNEL)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


def _close(got, want, what, tol=TOL):
    err = _err(got, want)
    assert np.isfinite(np.asarray(got)).all() and err <= tol, (what, err)


def _poisoned(lm, slots, max_len):
    """A cache whose K/V members are NaN: what a careless previous occupant
    may leave in a slot."""
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


def _check_rows(lm, cache, slot, n, want):
    """What the slot holds after `n` positions against the reference's
    rotated keys and values: a full member's rows `[0, n)`, a ring
    unrolled."""
    members = [np.asarray(m)[slot] for m in cache[:4]]
    for i, kv in enumerate(want):
        if i in lm.full_layers:
            (k, v), page = members[:2], lm.full_layers.index(i)
        else:
            (k, v), page = members[2:], lm.window_layers.index(i)
        (k, first), (v, _) = (serve_swa_moe.unrolled(m, page, n)
                              for m in (k, v))
        assert first == (0 if i in lm.full_layers
                         else max(0, n - lm.cfg.sliding_window))
        _close(np.stack([k, v], 1), kv[first:n], f"layer {i} rows")


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("length", [7, 16, 29, 40])
def test_forward_matches_reference(tiny, length):
    lm, params, weights = tiny
    seq = _tokens(length)
    want = ref.logits(CONFIG, weights, seq, np.arange(length))
    _close(lm.forward(params, seq[None])[0], want, "logits")


@pytest.mark.parametrize("change,what", [
    (dict(sliding_window=7), "a window of one fewer"),
    (dict(sliding_window=9), "a window of one more"),
    (dict(rope_parameters=dict(CONFIG["rope_parameters"], **{FULL: dict(
        CONFIG["rope_parameters"][FULL], attention_factor=1.0)})),
     "YaRN's attention factor left out"),
    (dict(rope_parameters=dict(CONFIG["rope_parameters"], **{
        FULL: CONFIG["rope_parameters"][WINDOW]})),
     "YaRN's blended frequencies left out"),
])
def test_the_comparison_sees_a_wrong_window_and_missing_yarn(tiny, change,
                                                             what):
    """The comparison this file and the benchmark make is tight enough to
    fail for a window off by one and for YaRN left out."""
    _, params, weights = tiny
    seq = _tokens(40)
    want = ref.logits(CONFIG, weights, seq, np.arange(40))
    wrong = _model(dict(CONFIG, **change))
    assert _err(wrong.forward(params, seq[None])[0], want) > 100 * TOL, what


def test_blockwise_band_attention_is_the_plain_one(monkeypatch):
    """A sequence longer than one attention block is attended blockwise,
    each query block over the key blocks of its band only; the result is
    the one-block formulation's."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(64, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 64, 16)), jnp.float32)
            for _ in range(2))
    for window in (None, 8, 16, 21):
        monkeypatch.setattr(window_moe, "_ATTN_BLOCK", 1024)
        want = window_moe._band_attention(q, k, v, 0.3, window)
        monkeypatch.setattr(window_moe, "_ATTN_BLOCK", 16)
        _close(window_moe._band_attention(q, k, v, 0.3, window), want,
               f"blockwise, window {window}")


CASES = [("xla", 5, 8), ("xla", 8, 8), ("xla", 13, 16), ("xla", 29, 32),
         ("kernel", 50, 64), ("kernel", 200, 256), ("kernel", 300, 512)]


@pytest.mark.parametrize("path,prompt_len,bucket", CASES)
def test_prefill_then_decode_matches_full_forward(tiny, wide, monkeypatch,
                                                  path, prompt_len, bucket):
    """Prefill into a slot whose previous occupant left NaN everywhere,
    then decode through the cache until the rings have wrapped again: every
    logit row, every row of the full members and the unrolled rings are the
    reference's full forward's; the other slots stay NaN. Prompts shorter
    than the window, equal to it, and bucket-padded past it (the padding's
    rows must not wrap over real ones)."""
    lm, params, weights = tiny if path == "xla" else wide
    config = CONFIG if path == "xla" else KERNEL
    steps, max_len = (20, 64) if path == "xla" else (6, 512)
    if path == "kernel":
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        assert lm.decode_block((3, 1, 2, 512, 128), jnp.float32) == 512
        assert lm.decode_block((3, 3, 2, 128, 128), jnp.float32) == 128
    seq = _tokens(prompt_len + steps, seed=prompt_len)
    want, kv, _ = ref.forward(config, weights, seq,
                              np.arange(prompt_len - 1, len(seq)))
    logits, cache = _prefill(lm, params, _poisoned(lm, 3, max_len),
                             seq[:prompt_len], bucket, slot=1)
    _close(logits, want[0], "prefill logits")
    _check_rows(lm, cache, 1, prompt_len, kv)
    for t in range(prompt_len, len(seq)):
        logits, cache = _decode(lm, params, cache, 1, seq[t], t)
        _close(logits, want[t - prompt_len + 1], f"decode logits at {t}")
    _check_rows(lm, cache, 1, len(seq), kv)
    for member in cache[:4]:
        assert member.shape[3] == (max_len if member is cache[0]
                                   or member is cache[1]
                                   else config["sliding_window"])
        assert np.isnan(np.asarray(member)[[0, 2]]).all(), \
            "a dead slot's rows were touched"
    assert (np.asarray(cache[4])[[0, 2]] == 0).all()


def test_a_short_prompt_after_a_long_occupant(tiny):
    """A prompt shorter than the window into a slot whose previous occupant
    was longer: the decode at `p < window - 1` attends rows `[0, p]` of the
    ring and nothing stale."""
    lm, params, weights = tiny
    old = _tokens(29, seed=1)
    _, cache = _prefill(lm, params, lm.init_cache(2, 64), old, 32, slot=1)
    for t in range(3):
        _, cache = _decode(lm, params, cache, 1, 5 + t, 29 + t)
    seq = _tokens(3 + 12, seed=2)
    want = ref.logits(CONFIG, weights, seq, np.arange(2, len(seq)))
    logits, cache = _prefill(lm, params, cache, seq[:3], 8, slot=1)
    _close(logits, want[0], "prefill logits")
    for t in range(3, len(seq)):
        logits, cache = _decode(lm, params, cache, 1, seq[t], t)
        _close(logits, want[t - 2], f"decode logits at {t}")


def test_a_ring_that_the_padding_wrapped_fails(tiny):
    """What the prefill must NOT do: write every row of the padded bucket
    at `p mod window`. The rows of the padding then lie over real ones and
    the next token's logits are not the reference's."""
    lm, params, weights = tiny
    seq = _tokens(14, seed=4)
    want = ref.logits(CONFIG, weights, seq, [13])
    _, cache = _prefill(lm, params, lm.init_cache(2, 64), seq[:13], 16, 1)
    logits, _ = _decode(lm, params, cache, 1, seq[13], 13)
    _close(logits, want[0], "the right ring")
    padded = np.full(16, 7, np.int32)
    padded[:13] = seq[:13]
    _, kept = lm._sequence(params, jnp.asarray(padded), 13)
    k_ring, v_ring = cache[2], cache[3]
    for page, i in enumerate(lm.window_layers):
        k, v = kept[i]                  # positions 8..15 at rows 0..7
        k_ring = k_ring.at[1, page].set(k[:, 8:16])
        v_ring = v_ring.at[1, page].set(v[:, 8:16])
    wrapped = cache[:2] + (k_ring, v_ring) + cache[4:]
    logits, _ = _decode(lm, params, wrapped, 1, seq[13], 13)
    assert _err(logits, want[0]) > 100 * TOL


# -- kernels against their restatements in XLA ---------------------------------

@pytest.mark.parametrize("alive", [[1, 1, 1, 1], [0, 1, 0, 1], [0, 0, 0, 0]])
@pytest.mark.parametrize("rows", [256, 128])
def test_decode_kernel_matches_restatement(alive, rows):
    """The Pallas kernel (interpreted) against the XLA formulation (write
    one row a slot, attend the member masked) on a member of `rows` rows
    under positions below its length (a full member, a ring not yet
    wrapped) and past it (a ring wrapped once and several times), the rows
    past the live ones NaN, with dead slots. 4 queries a K/V head."""
    rng = np.random.default_rng(5)
    slots, layers, heads, group, hd = 4, 2, 2, 4, 128
    block = pallas_window.kv_block((slots, layers, heads, rows, hd),
                                   jnp.float32, target=128)
    assert block == 128
    assert pallas_window.kv_block((4, 2, 2, 256, 64), jnp.float32) is None
    if rows == 256:
        positions = [127, 128, 5, 255]
    else:                               # a ring of 128 under 512 positions
        positions = [127, 128, 5, 389]
    positions = np.where(alive, positions, -1).astype(np.int32)
    slab_k, slab_v = (rng.normal(size=(slots, layers, heads, rows, hd))
                      .astype("f4") for _ in range(2))
    for s, p in enumerate(positions):
        for slab in (slab_k, slab_v):
            slab[s, :, :, min(max(p, 0) + 1, rows):] = np.nan
            slab[s, :, :, max(p, 0) % rows] = np.nan    # the row to write
    q = jnp.asarray(rng.normal(size=(slots, heads * group, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(slots, heads, hd)),
                                jnp.float32) for _ in range(2))
    at = jnp.asarray(np.where(positions >= 0, positions % rows, -1))
    want_k = _write_rows(jnp.asarray(slab_k), 1, at, k_new)
    want_v = _write_rows(jnp.asarray(slab_v), 1, at, v_new)
    want = window_moe._attend_member(q, want_k[:, 1], want_v[:, 1],
                                     jnp.asarray(positions), 0.2)
    got, got_k, got_v = pallas_window.kv_update_attend(
        q, k_new, v_new, jnp.asarray(slab_k), jnp.asarray(slab_v),
        jnp.int32(1), jnp.asarray(positions), block=block, scale=0.2,
        interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    if any(alive):
        _close(got, want, "decode kernel")
    else:
        assert not np.asarray(got).any()


GRID_ROWS, GRID_BLOCK, GRID_LAYER = 512, 128, 1
# (K/V heads, queries a head): Trinity's 6, mellum's 8, the Olmo block's one
GRID_BODIES = {"grouped6": (2, 6), "grouped8": (2, 8), "one_query": (3, 1)}
GRID_POSITIONS = {
    # a dead slot between live ones; both sides of a block's edge; the last
    # row; and an empty block after every live slot but the full one
    "full": [300, -1, 127, GRID_ROWS - 1, 128, -1],
    # a ring the positions have not filled: the rows past them are the
    # previous occupant's; one slot on the row that fills it
    "ring_filling": [5, -1, 255, GRID_ROWS - 1, 256, -1],
    # a ring wrapped once and several times, the new row in a MIDDLE block
    # (1, 2, 1, 2): the slot's last step is block 3, not the new row's
    "ring_wrapped": [GRID_ROWS + 200, -1, 3 * GRID_ROWS + 300,
                     GRID_ROWS + 130, -1, 2 * GRID_ROWS + 383]}


def _grid_operands(body, positions, seed=13):
    """Operands of one `kv_update_attend` call on a member of `GRID_ROWS`
    rows (4 blocks) with `inf` in K and `nan` in V wherever the tick may
    not look: past a live slot's rows, in the row it writes, and all of a
    dead slot."""
    heads, group = GRID_BODIES[body]
    rng = np.random.default_rng(seed)
    slots, layers, hd = len(positions), 2, 128
    slab_k, slab_v = (rng.normal(size=(slots, layers, heads, GRID_ROWS, hd))
                      .astype("f4") for _ in range(2))
    for s, p in enumerate(positions):
        for slab, left in ((slab_k, np.inf), (slab_v, np.nan)):
            if p < 0:
                slab[s] = left
            else:
                slab[s, :, :, min(p + 1, GRID_ROWS):] = left
                slab[s, :, :, p % GRID_ROWS] = left
    q = jnp.asarray(rng.normal(size=(slots, heads * group, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(slots, heads, hd)),
                                jnp.float32) for _ in range(2))
    return (q, k_new, v_new, jnp.asarray(slab_k), jnp.asarray(slab_v),
            jnp.asarray(positions, jnp.int32))


def _kv128_grid(*operands):
    """What `kv128_attend`'s call is handed for its grid: the bound and the
    work list, evaluated."""
    bound, slot_of, block_of, pos = grid_of_call(
        lambda q, k, v, sk, sv, pos: pallas_window.kv_update_attend
        .__wrapped__(q, k, v, sk, sv, GRID_LAYER, pos, block=GRID_BLOCK,
                     interpret=True), *operands)
    assert pos.tolist() == np.asarray(operands[-1]).tolist()
    return int(bound), slot_of.tolist(), block_of.tolist()


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("member", list(GRID_POSITIONS))
@pytest.mark.parametrize("body", list(GRID_BODIES))
def test_kv128_grid_is_the_live_blocks(body, member):
    """`kv128_attend`'s grid is ONE axis with a step a LIVE block (ISSUE
    45; `pallas_decode.live_steps`), interpreted, for both bodies: the
    bound handed to the call is the sum over the slots of `(min(p + 1, R)
    - 1) // block + 1` — a full member's `p // block + 1`, a wrapped ring's
    every block — and the work list walks the live slots in slot order,
    each from block 0; `inf` and `nan` in the blocks past a position and in
    every block of a dead slot reach neither the attention nor the slab;
    a live slot changes ONE row of the slab (so one 16-row group), a dead
    slot none; the attention is the plain `jax.numpy` formulation's and the
    slabs are its, bit for bit."""
    positions = GRID_POSITIONS[member]
    operands = _grid_operands(body, positions)
    q, k_new, v_new, slab_k, slab_v, pos = operands
    walk = [(s, b) for s, p in enumerate(positions) if p >= 0
            for b in range((min(p + 1, GRID_ROWS) - 1) // GRID_BLOCK + 1)]
    assert len(walk) == {"full": 10, "ring_filling": 10,
                         "ring_wrapped": 16}[member]
    bound, slot_of, block_of = _kv128_grid(*operands)
    assert bound == len(walk)
    assert list(zip(slot_of, block_of))[:bound] == walk
    assert len(slot_of) == len(positions) * (GRID_ROWS // GRID_BLOCK)
    assert 0 <= min(slot_of) and max(slot_of) < len(positions)
    assert 0 <= min(block_of) and max(block_of) < GRID_ROWS // GRID_BLOCK

    at = jnp.where(pos >= 0, pos % GRID_ROWS, -1)
    want_k = _write_rows(slab_k, GRID_LAYER, at, k_new)
    want_v = _write_rows(slab_v, GRID_LAYER, at, v_new)
    want = window_moe._attend_member(q, want_k[:, GRID_LAYER],
                                     want_v[:, GRID_LAYER], pos, 0.31)
    got, got_k, got_v = pallas_window.kv_update_attend(
        *operands[:5], jnp.int32(GRID_LAYER), pos, block=GRID_BLOCK,
        scale=0.31, interpret=True)
    _close(got, want, "the live-only grid")
    assert not np.asarray(got)[np.asarray(positions) < 0].any()
    for before, after, wanted in ((slab_k, got_k, want_k),
                                  (slab_v, got_v, want_v)):
        assert np.array_equal(_bits(after), _bits(wanted))
        moved = (_bits(after) != _bits(before)).any(axis=(2, 4))
        for s, p in enumerate(positions):   # [layers, rows] a slot
            rows = np.argwhere(moved[s]).tolist()
            assert rows == ([] if p < 0 else [[GRID_LAYER, p % GRID_ROWS]])


@pytest.mark.parametrize("body", list(GRID_BODIES))
def test_kv128_grid_of_a_tick_with_no_live_slot(body):
    """No live slot: the grid is one step, which sends its write-back block
    back as it came — the slabs (every row `inf` or `nan`) return bit for
    bit and the attention is zeros."""
    operands = _grid_operands(body, [-1, -1, -1])
    bound, slot_of, block_of = _kv128_grid(*operands)
    assert bound == 1 and block_of[0] == 0
    got, got_k, got_v = pallas_window.kv_update_attend(
        *operands[:5], jnp.int32(GRID_LAYER), operands[5], block=GRID_BLOCK,
        interpret=True)
    assert not np.asarray(got).any()
    assert np.array_equal(_bits(got_k), _bits(operands[3]))
    assert np.array_equal(_bits(got_v), _bits(operands[4]))


@pytest.mark.parametrize("window", [None, 128, 100, 300])
def test_prefill_kernel_matches_restatement(window):
    """The prefill attention kernel (interpreted) — grouped queries, key
    blocks streamed through the grid, blocks outside the band skipped —
    against the XLA formulation, for windows of one block, of less, and of
    more than two."""
    rng = np.random.default_rng(6)
    heads, group, length, hd = 2, 2, 512, 128
    q = jnp.asarray(rng.normal(size=(heads * group, length, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(heads, length, hd)), jnp.float32)
            for _ in range(2))
    assert pallas_window.band_block(length, None, target=128) == 128
    assert pallas_window.band_block(16384, 1024) == 512
    assert pallas_window.band_block(16384) == 1024
    assert pallas_window.band_block(200) is None
    steps = pallas_window.band_steps(length, 128, window)
    assert steps == {None: 4, 128: 2, 100: 2, 300: 4}[window]
    assert pallas_window.band_steps(16384, 512, 1024) == 3
    got = pallas_window.band_prefill_attend(q, k, v, block=128, scale=0.3,
                                            window=window, interpret=True)
    want = window_moe._band_attention(q.transpose(1, 0, 2), k, v, 0.3, window)
    _close(got.transpose(1, 0, 2), want, "prefill kernel")


# -- the expert layer ------------------------------------------------------------

def test_router_is_a_softmax_with_normalised_top_k(tiny):
    lm, params, _ = tiny
    x = jnp.asarray(np.random.default_rng(7).normal(size=(40, 64)),
                    jnp.float32)
    p = np.asarray(jax.nn.softmax(x @ params["l1.router"], -1), np.float64)
    chosen, weights = lm._route(params, 1, x)
    want = np.argsort(-p, axis=-1)[:, :2]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(want, -1))
    picked = np.take_along_axis(p, np.asarray(chosen), -1)
    _close(weights, picked / picked.sum(-1, keepdims=True), "weights")
    assert np.allclose(np.asarray(weights).sum(-1), 1, atol=1e-6)
    plain = _model(dict(CONFIG, norm_topk_prob=False))
    _close(plain._route(params, 1, x)[1], picked, "unnormalised weights")


def test_grouped_kernel_is_the_plain_grouped_product(tiny, monkeypatch):
    """The expert layer through the Pallas grouped matmul (interpreted),
    tiled from its shapes, is the one through `lax.ragged_dot`."""
    lm, params, _ = tiny
    h = jnp.asarray(np.random.default_rng(17).normal(size=(64, 64)),
                    jnp.float32)           # 64 tokens x 2: one 128-row tile
    want, routing = lm._mlp(params, 1, h)
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    got, kernel = lm._mlp(params, 1, h)
    _close(got, want, "grouped kernel")
    assert np.array_equal(routing, kernel)


@pytest.mark.parametrize("m,k,n,want", [
    (256, 4096, 4096, (128, 512, 4096)),        # PR 31's constants ...
    (256, 2048, 4096, (128, 512, 4096)),
    (32768, 4096, 4096, (256, 1024, 1024)),
    (32768, 2048, 4096, (256, 1024, 1024)),
    (256, 2304, 1792, (128, 1152, 1792)),       # ... and K = 2304 / 896
    (256, 896, 2304, (128, 896, 2304)),
    (32768, 2304, 1792, (256, 1152, 896)),
    (32768, 896, 2304, (256, 896, 768)),
    (128, 3072, 6144, (128, 512, 3072)),        # ... and K = 3072 (PR 39):
    (128, 3072, 3072, (128, 512, 3072)),        # a tick's 32 x 4 pairs,
    (16384, 3072, 6144, (256, 1024, 1024)),     # a prefill chunk's 4,096 x 4
    (16384, 3072, 3072, (256, 1024, 1024)),
    (100, 64, 64, None),                        # no whole row tile
])
def test_grouped_tiles_follow_the_shapes(m, k, n, want):
    got = experts.gmm_tiling(m, k, n, 2)
    assert got == want
    if want:
        tm, tk, tn = want
        assert m % tm == 0 and k % tk == 0 and n % tn == 0
        # the weight tile, double-buffered, beside the fp32 accumulator and
        # the row and output tiles: under Mosaic's 16 MiB
        assert 2 * tk * tn * 2 + tm * tn * 4 + 2 * tm * (tk + tn) * 2 \
            < 14 * 2 ** 20


def test_from_config_refuses_what_the_block_cannot_express():
    for key, value in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("attention_bias", True),
                       ("use_sliding_window", False)):
        with pytest.raises(ValueError, match=key):
            WindowMoELMConfig.from_config(dict(CONFIG, **{key: value}))
    with pytest.raises(ValueError, match="mlp_layer_types"):
        WindowMoELMConfig.from_config(dict(
            CONFIG, mlp_layer_types=["dense"] + ["sparse"] * 7))
    with pytest.raises(ValueError, match="layer_types"):
        WindowMoELMConfig.from_config(dict(
            CONFIG, layer_types=["chunked_attention"] * 8))
    with pytest.raises(ValueError, match="held experts"):
        _model(dict(CONFIG, num_experts=4, published={"num_experts": 8},
                    share={"expert_first": 6}))
    # the afmoe keys
    for key, value in (("n_group", 2), ("topk_group", 2),
                       ("num_expert_groups", 4), ("num_limited_groups", 2),
                       ("num_shared_experts", 2), ("score_func", "tanh"),
                       ("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("num_dense_layers", 6), ("hidden_act", "gelu"),
                       ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            WindowMoELMConfig.from_config(dict(AFMOE, **{key: value}))
    with pytest.raises(ValueError, match="mlp_layer_types"):
        WindowMoELMConfig.from_config(dict(
            AFMOE, mlp_layer_types=["sparse"] * 5))
    assert WindowMoELMConfig.from_config(dict(
        AFMOE, mlp_layer_types=["dense"] + ["sparse"] * 4)) \
        == WindowMoELMConfig.from_config(AFMOE)


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
    replaces what its slot held; the rings wrap), and nothing compiles
    after the first pass."""
    lm, params, weights = tiny
    prompts = [_tokens(n, seed=n) for n in (3, 8, 9, 17, 5, 30, 12)]
    with _engine(lm, params, buckets=(8, 32)) as eng:
        assert [m.shape[1:] for m in eng._kv] == [
            (2, 2, 64, 16), (2, 2, 64, 16), (6, 2, 8, 16), (6, 2, 8, 16),
            (8, 2)]
        assert eng.kv_slab_bytes() == sum(int(m.nbytes) for m in eng._kv)
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
        assert [m.shape for m in src] == [
            (2, 2, 64, 16), (2, 2, 64, 16), (6, 2, 8, 16), (6, 2, 8, 16),
            (8, 2)]
        for a, b in zip(src, eng.slot_snapshot((s.slot + 2) % 3)):
            assert np.array_equal(a, b) and np.abs(a).sum() > 0
        for a, b in zip(others, eng.slot_snapshot((s.slot + 1) % 3)):
            assert np.array_equal(a, b)
    finally:
        eng.close()


def test_park_copies_every_member_and_resume_is_bit_equal(tiny):
    """QoS park and resume go through the fork executable, which copies one
    slot of EVERY member of the cache — full rows, rings and the routing —
    and the preempted stream resumes bit-equal to an uncontended run."""
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
                eng._tick_once()                # parks the youngest
                if eng.parked_count:
                    break
            assert eng.parked_count == 1
            parked = eng.slot_snapshot(2)
            assert all(np.abs(m).sum() > 0 for m in parked[:4])
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
def test_engine_refuses_what_a_ring_cannot_offer(tiny, kwargs, what):
    lm, params, _ = tiny
    traits = lm.cache_traits(lm.init_cache(2, 64))
    assert traits["state_bytes_per_slot"] == 0 and not traits["rewindable"]
    assert not hasattr(lm, "prefill_at") and not hasattr(lm, "verify_step")
    with pytest.raises(MXNetError, match=what + ".*ring of sliding_window"):
        GenerationEngine(lm, params, max_slots=2, max_len=64, buckets=(16,),
                         start=False, **kwargs)


def test_tick_counters_against_a_host_count(tiny):
    """With telemetry on, the engine's counters are what the decode program
    itself routed and attended: re-derived here from the model's routing of
    the same tokens at the same positions, and from the positions."""
    from mxnet_tpu import telemetry

    lm, params, _ = tiny
    prev = telemetry.enabled()
    telemetry.enable()
    names = lm.TICK_COUNTERS
    pre = "serving.generation."
    try:
        eng = _engine(lm, params, buckets=(16,), start=False)
        assert eng._tick_counter_names == names
        c0 = {k: telemetry.counter(pre + k).value for k in names}
        prompts = [_tokens(n, seed=n) for n in (5, 12)]
        streams = [eng.submit(p, max_new_tokens=3) for p in prompts]
        for _ in range(20):
            if all(s.done for s in streams):
                break
            eng._tick_once()
        assert eng._ahead is None
        got = {k: telemetry.counter(pre + k).value - c0[k] for k in names}
        routed = eng.slot_snapshot(streams[0].slot)[4]
        eng.close()
    finally:
        telemetry.enable(prev)
    assert routed.shape == (8, 2) and 0 <= routed.min() and routed.max() < 8
    # 2 sessions x 2 decoded tokens at positions n and n + 1: a full layer
    # attends p + 1 rows, a window layer min(p + 1, 8); 2 full and 6 window
    # layers
    at = [5, 6, 12, 13]
    assert got["kv_rows_live_full"] == 2 * sum(p + 1 for p in at)
    assert got["kv_rows_live_window"] == 6 * sum(min(p + 1, 8) for p in at)
    # every expert is held: each decoded token computes 2 pairs in 8 layers
    assert got["expert_assignments"] == 4 * 8 * 2
    assert 0 < got["expert_tokens_max"] <= got["experts_hit"] <= 4 * 8 * 2


def test_tick_counters_of_a_hand_made_routing(tiny):
    lm, _, _ = tiny
    routed = np.full((4, 8, 2), -1, np.int32)
    routed[0, 0] = [3, 5]
    routed[1, 0] = [3, 7]
    routed[2, 0] = [1, 2]                       # a dead slot's: not counted
    routed[3, 1] = [0, 6]
    positions = jnp.asarray([4, 9, -1, 0], jnp.int32)
    cache = lm.init_cache(4, 64)
    got = np.asarray(lm.tick_counters(*cache[:4], jnp.asarray(routed),
                                      positions))
    # pairs 4 + 2; layer 0 hits {3, 5, 7} and layer 1 {0, 6}; the fullest
    # expert has 2 tokens in layer 0 and 1 in layer 1; rows (5 + 10 + 1) in
    # each of 2 full layers, (5 + 8 + 1) in each of 6 window layers
    assert got.tolist() == [6, 5, 3, 32, 84]


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


@pytest.mark.parametrize("family", ["tiny", "afmoe"])
def test_nothing_compiles_after_warm_up(request, family):
    from jax import monitoring

    lm, params, _ = request.getfixturevalue(family)
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
        for _ in range(100):
            if all(s.done for s in streams):
                break
            eng._tick_once()
        assert all(s.done for s in streams) and compiles == []
        eng.close()
    finally:
        monitoring.unregister_event_duration_listener(listener)


# ===========================================================================
# the afmoe block (Trinity-Large-Preview) in the same class
# ===========================================================================

# the published keys at a tiny size: one period and a layer more, 1 dense
# layer, 3 query heads a K/V head (not a power of two), a window of 8
AFMOE = dict(
    vocab_size=VOCAB, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, num_dense_layers=1,
    num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
    score_func="sigmoid", route_norm=True, route_scale=2.448, n_group=1,
    topk_group=1, num_expert_groups=1, num_limited_groups=1,
    mup_enabled=True, rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None,
    sliding_window=8, global_attn_every_n_layers=4,
    layer_types=[WINDOW, WINDOW, WINDOW, FULL] * 2,
    max_position_embeddings=256, dtype="float32", hidden_act="silu",
    tie_word_embeddings=False, model_type="afmoe")
AFMOE_KERNEL = dict(AFMOE, head_dim=128, sliding_window=128,
                    max_position_embeddings=512)


def _built_afmoe(config):
    lm = _model(config)
    params = lm.init_params(jax.random.PRNGKey(0))
    return lm, params, serve_afmoe.published(params, config)


@pytest.fixture(scope="module")
def afmoe():
    return _built_afmoe(AFMOE)


@pytest.fixture(scope="module")
def afmoe_wide():
    return _built_afmoe(AFMOE_KERNEL)


def test_afmoe_builds_what_the_block_names(afmoe):
    lm, params, weights = afmoe
    c = lm.cfg
    assert (c.attention_gate, c.qk_norm, c.post_norms, c.mup_enabled) \
        == (True,) * 4
    assert c.rope_full is None and dict(c.rope_window)["rope_theta"] == 1e4
    assert (c.num_dense_layers, c.n_expert_layers, c.score_func) \
        == (1, 4, "sigmoid")
    assert lm.full_layers == (3,) and lm.window_layers == (0, 1, 2, 4)
    assert params["l0.wqkv"].shape == (64, (6 + 2 * 2 + 6) * 16)
    assert params["l0.w_in"].shape == (64, 256) and "l0.router" not in params
    assert params["l1.router_bias"].dtype == jnp.float32
    assert {n.rpartition(".")[2] for n in params if n.startswith("l1.")} == {
        "norm1", "norm1_post", "norm2", "norm2_post", "wqkv", "wo", "q_norm",
        "k_norm", "router", "router_bias", "experts_in", "experts_out",
        "shared_in", "shared_out"}
    assert "layers.1.self_attn.gate_proj.weight" in weights
    assert [m.shape for m in lm.init_cache(3, 64)] == [
        (3, 1, 2, 64, 16), (3, 1, 2, 64, 16), (3, 4, 2, 8, 16),
        (3, 4, 2, 8, 16), (3, 4, 2)]


def test_a_config_without_the_new_keys_builds_what_it_built(tiny):
    """mellum's configuration: the parameter names and shapes of before this
    block existed, and none of its scopes in the lowered decode; the afmoe
    block's decode names them."""
    lm, params, _ = tiny
    assert {n.rpartition(".")[2] for n in params if n.startswith("l")} == {
        "norm1", "norm2", "wqkv", "wo", "router", "experts_in",
        "experts_out"}
    assert params["l0.wqkv"].shape == (64, (4 + 2 * 2) * 16)
    assert len(params) == 8 * 7 + 3

    def lowered(model, weights):
        cache = model.init_cache(2, 64)
        ints = jnp.zeros(2, jnp.int32)
        return jax.jit(model.decode_step).lower(
            weights, *cache, ints, ints).as_text(debug_info=True)

    new = ("attn.gate", "attn.qknorm", "moe.shared", "/mlp/")
    text = lowered(lm, params)
    assert "attn.rotary" in text and not [n for n in new if n in text]
    alm = _model(AFMOE)
    text = lowered(alm, alm.init_params(jax.random.PRNGKey(0)))
    assert not [n for n in new if n not in text]


@pytest.mark.parametrize("length", [7, 16, 29, 40])
def test_afmoe_forward_matches_reference(afmoe, length):
    lm, params, weights = afmoe
    seq = _tokens(length)
    want = aref.logits(AFMOE, weights, seq, np.arange(length))
    _close(lm.forward(params, seq[None])[0], want, "logits")


def _biased_weights(x, router, bias, top_k, scale, normalise=True, eps=0.0):
    """`experts.sigmoid_route` with the fault of weighing by `s + b`."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ router) + bias
    weights, chosen = jax.lax.top_k(s, top_k)
    return chosen, weights / weights.sum(-1, keepdims=True) * scale


@pytest.mark.parametrize("what,change", [
    ("the gate left out", dict(attention_gate=False)),
    ("rotary applied to a full layer",
     dict(rope_full=(("rope_theta", 10000.0), ("rope_type", "default")))),
    ("rotary left off a window layer", dict(rope_window=None)),
    ("a head norm left out", dict(qk_norm=False)),
    ("a post-norm left out", dict(post_norms=False)),
    ("route_scale left out", dict(route_scale=1.0)),
    ("mup left out", dict(mup_enabled=False)),
    ("the shared expert not at all", dict(num_shared_experts=0)),
    ("the window off by one", dict(sliding_window=7)),
    ("the shared expert counted twice", "shared"),
    ("the bias entering the weights", "bias"),
    ("a head norm's weight ignored", "q_norm"),
    ("a post-norm's weight ignored", "norm1_post"),
])
def test_the_comparison_sees_each_part_of_the_afmoe_block(afmoe, monkeypatch,
                                                          what, change):
    """The comparison this file and the benchmark make is tight enough to
    fail for each part of the block left out or misapplied. The norm
    weights, 1 as drawn, are scaled here so that a weight ignored shows."""
    lm, params, _ = afmoe
    params = {k: v * 1.5 if "norm" in k else v for k, v in params.items()}
    weights = serve_afmoe.published(params, AFMOE)
    seq = _tokens(40)
    want = aref.logits(AFMOE, weights, seq, np.arange(40))
    _close(lm.forward(params, seq[None])[0], want, "the block as it is")
    wrong, wrong_params = lm, dict(params)
    if isinstance(change, dict):
        wrong = WindowMoELM(dataclasses.replace(lm.cfg, **change), lm.mesh)
    elif change == "shared":
        wrong_params["l2.shared_out"] = params["l2.shared_out"] * 2
    elif change == "bias":
        monkeypatch.setattr(experts, "sigmoid_route", _biased_weights)
    else:
        wrong_params[f"l2.{change}"] = jnp.ones_like(params[f"l2.{change}"])
    got = wrong.forward(wrong_params, seq[None])[0]
    assert _err(got, want) > 100 * TOL, what


AFMOE_CASES = [("xla", 5, 8), ("xla", 8, 8), ("xla", 13, 16),
               ("xla", 29, 32), ("kernel", 200, 256)]


@pytest.mark.parametrize("path,prompt_len,bucket", AFMOE_CASES)
def test_afmoe_prefill_then_decode_matches_full_forward(
        afmoe, afmoe_wide, monkeypatch, path, prompt_len, bucket):
    """As `test_prefill_then_decode_matches_full_forward`, for the afmoe
    block: prompts shorter than, equal to and longer than the window, so
    that the rings do and do not wrap; 3 query heads a K/V head through
    both formulations (the kernel interpreted)."""
    lm, params, weights = afmoe if path == "xla" else afmoe_wide
    config = AFMOE if path == "xla" else AFMOE_KERNEL
    steps, max_len = (20, 64) if path == "xla" else (5, 512)
    if path == "kernel":
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        assert lm.decode_block((3, 1, 2, 512, 128), jnp.float32) == 512
    seq = _tokens(prompt_len + steps, seed=prompt_len)
    want, kv, _ = aref.forward(config, weights, seq,
                               np.arange(prompt_len - 1, len(seq)))
    logits, cache = _prefill(lm, params, _poisoned(lm, 3, max_len),
                             seq[:prompt_len], bucket, slot=1)
    _close(logits, want[0], "prefill logits")
    _check_rows(lm, cache, 1, prompt_len, kv)
    for t in range(prompt_len, len(seq)):
        logits, cache = _decode(lm, params, cache, 1, seq[t], t)
        _close(logits, want[t - prompt_len + 1], f"decode logits at {t}")
    _check_rows(lm, cache, 1, len(seq), kv)
    for member in cache[:4]:
        assert np.isnan(np.asarray(member)[[0, 2]]).all(), \
            "a dead slot's rows were touched"
    assert cache[4].shape == (3, 4, 2)      # the expert layers' routing


def test_afmoe_router_is_a_biased_sigmoid_top_k(afmoe):
    lm, params, _ = afmoe
    x = jnp.asarray(np.random.default_rng(7).normal(size=(40, 64)),
                    jnp.float32)
    s = 1 / (1 + np.exp(-np.asarray(x @ params["l1.router"], np.float64)))
    bias = np.asarray(params["l1.router_bias"], np.float64)
    chosen, weights = lm._route(params, 1, x)
    want = np.argsort(-(s + bias), axis=-1)[:, :2]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(want, -1))
    # the bias moves the selection of this draw, and never the weights
    assert not np.array_equal(np.sort(want, -1),
                              np.sort(np.argsort(-s, axis=-1)[:, :2], -1))
    picked = np.take_along_axis(s, np.asarray(chosen), -1)
    _close(weights, picked / picked.sum(-1, keepdims=True) * 2.448,
           "weights")


def test_the_shares_of_an_afmoe_layer_add_up_to_the_uncut_layer():
    """16 experts over 4 shares of 4: a share routes over all 16 and sums
    over its own; the four shares' routed parts plus the shared expert —
    which every share computes whole — counted ONCE are the uncut layer,
    and the reference given a share is the model given that share."""
    whole_cfg = dict(AFMOE, num_experts=16)
    whole = _model(whole_cfg)
    params = whole.init_params(jax.random.PRNGKey(3))
    x = jnp.asarray(np.random.default_rng(11).normal(size=(24, 64)),
                    jnp.float32)
    real = jnp.ones(24, bool)
    want, routing = whole._mlp_out(params, 2, x, real)
    shared = experts.gated_mlp(x, params["l2.shared_in"],
                               params["l2.shared_out"])
    total, seen = shared, np.zeros((24, 2), int)
    seq = _tokens(29, seed=5)
    for first in (0, 4, 8, 12):
        cfg = dict(whole_cfg, num_experts=4, published={"num_experts": 16},
                   share={"expert_first": first})
        chip = _model(cfg)
        assert (chip.cfg.num_experts, chip.cfg.experts_held,
                chip.cfg.expert_first) == (16, 4, first)
        held = {k: v[first:first + 4] if "experts_" in k else v
                for k, v in params.items()}
        part, local = chip._mlp_out(held, 2, x, real)
        total = total + (part - shared)
        seen += np.asarray(local) >= 0
        assert np.array_equal(
            np.asarray(local)[np.asarray(local) >= 0] + first,
            np.asarray(routing)[np.asarray(local) >= 0])
        want_logits = aref.logits(cfg, serve_afmoe.published(held, cfg), seq,
                                  np.arange(29))
        _close(chip.forward(held, seq[None])[0], want_logits,
               f"the share from {first} against the reference")
    assert (seen == 1).all()            # every choice on exactly one share
    _close(total, want, "the shares' sum")
    twice = total + shared
    assert _err(twice, want) > 100 * TOL


def test_afmoe_batch_through_the_engine_equals_each_request_alone(afmoe):
    """7 requests over 3 slots through `GenerationEngine`: every stream is
    the reference's greedy continuation of its own prompt, and nothing
    compiles after the first pass."""
    lm, params, weights = afmoe
    prompts = [_tokens(n, seed=n) for n in (3, 8, 9, 17, 5, 30, 12)]
    with _engine(lm, params, buckets=(8, 32)) as eng:
        assert [m.shape[1:] for m in eng._kv] == [
            (1, 2, 64, 16), (1, 2, 64, 16), (4, 2, 8, 16), (4, 2, 8, 16),
            (4, 2)]
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.result(timeout=120) for s in streams]
        misses = eng.cache.misses
        again = eng.generate(prompts[3], max_new_tokens=6)
        assert eng.cache.misses == misses
    for p, g in zip(prompts, got):
        seq = list(p)
        for _ in range(6):
            row = aref.logits(AFMOE, weights, np.asarray(seq),
                              [len(seq) - 1])
            seq.append(int(np.asarray(row)[0].argmax()))
        assert g == seq[len(p):]
    assert again == got[3]


def test_afmoe_tick_counters_against_a_host_count(afmoe):
    """With a dense layer present the routing member has a page an EXPERT
    layer, and the counters count over those: re-derived from the
    positions and from what the program left in the cache."""
    from mxnet_tpu import telemetry

    lm, params, _ = afmoe
    prev = telemetry.enabled()
    telemetry.enable()
    names = lm.TICK_COUNTERS
    pre = "serving.generation."
    try:
        eng = _engine(lm, params, buckets=(16,), start=False)
        c0 = {k: telemetry.counter(pre + k).value for k in names}
        prompts = [_tokens(n, seed=n) for n in (5, 12)]
        streams = [eng.submit(p, max_new_tokens=3) for p in prompts]
        for _ in range(20):
            if all(s.done for s in streams):
                break
            eng._tick_once()
        got = {k: telemetry.counter(pre + k).value - c0[k] for k in names}
        routed = eng.slot_snapshot(streams[0].slot)[4]
        eng.close()
    finally:
        telemetry.enable(prev)
    assert routed.shape == (4, 2) and 0 <= routed.min() and routed.max() < 8
    at = [5, 6, 12, 13]
    assert got["kv_rows_live_full"] == 1 * sum(p + 1 for p in at)
    assert got["kv_rows_live_window"] == 4 * sum(min(p + 1, 8) for p in at)
    # every expert is held: a decoded token computes 2 pairs in each of the
    # 4 expert layers, none in the dense one
    assert got["expert_assignments"] == 4 * 4 * 2
    assert 0 < got["expert_tokens_max"] <= got["experts_hit"] <= 4 * 4 * 2
    hand = np.full((3, 4, 2), -1, np.int32)
    hand[0, 0], hand[1, 0], hand[2, 3] = [3, 5], [3, 7], [0, 6]
    counted = np.asarray(lm.tick_counters(
        *lm.init_cache(3, 64)[:4], jnp.asarray(hand),
        jnp.asarray([4, 9, 0], jnp.int32)))
    # rows (5 + 10 + 1) in the 1 full layer, (5 + 8 + 1) in each of 4 rings
    assert counted.tolist() == [6, 5, 3, 16, 56]


def test_weights_can_be_drawn_wider_than_they_are_kept():
    """jax's bfloat16 normal takes 128 values and has a mean of -0.012 (a
    hundred standard errors of this many draws): `draw_dtype="float32"`
    draws a leaf in float32 and keeps it in the served dtype, unbiased;
    the default draw is what it was."""
    lm = _model(dict(AFMOE, dtype="bfloat16", hidden_size=512,
                     moe_intermediate_size=256))
    key = jax.random.PRNGKey(0)
    narrow, wide = lm.init_params(key), lm.init_params(key, "float32")
    assert {k: (v.shape, v.dtype) for k, v in narrow.items()} \
        == {k: (v.shape, v.dtype) for k, v in wide.items()}
    name = "l1.experts_in"                  # 8 x 512 x 512 draws
    assert narrow[name].dtype == jnp.bfloat16
    scale, n = 512 ** 0.5, narrow[name].size
    mean = {k: float(np.asarray(p[name], np.float64).mean()) * scale
            for k, p in (("narrow", narrow), ("wide", wide))}
    assert abs(mean["wide"]) < 4 * n ** -0.5 < 8 * n ** -0.5 \
        < abs(mean["narrow"])
    assert len(np.unique(np.asarray(narrow[name], np.float32))) <= 256
    again = lm.init_params(key)
    assert all(np.array_equal(np.asarray(narrow[k], np.float32),
                              np.asarray(again[k], np.float32))
               for k in narrow)
    for k in ("l1.router", "l1.router_bias", "l1.norm1_post"):
        assert np.array_equal(np.asarray(narrow[k]), np.asarray(wide[k]))
