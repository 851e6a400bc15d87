"""How `TransformerLM.decode_step` touches the KV slab (ISSUE 24): one row
per live slot written in place, attention over rows ``[0, positions[s]]``
only — through the Pallas kernel (`ops/pallas_decode.py`, interpret mode
here) and through the XLA formulation it falls back to. The reference is a
plain `jax.numpy` re-statement of the page-copying step both replaced.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import parallel as par
from mxnet_tpu import telemetry
from mxnet_tpu.models import TransformerLM, TransformerLMConfig
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import pallas_decode as pd
from mxnet_tpu.serving import GenerationEngine

import slab_kernel_2d
from pallas_grid import grid_of_call

S, NL, H, L, LAYER = 6, 2, 2, 512, 1
BLOCK = 256                      # what decode_block gives for L=512


def old_step(q, k_new, v_new, ck, cv, layer, pos):
    """The step before ISSUE 24, in plain jax.numpy at fp32: slice the
    layer's page of every slot, write the row into it, set it back, and
    attend the whole page under an additive length mask."""
    f32 = jnp.float32
    q, ck, cv = q.astype(f32), ck.astype(f32), cv.astype(f32)
    page_k, page_v = ck[:, layer], cv[:, layer]                # [S,H,L,hd]
    rows = jnp.arange(ck.shape[0])
    page_k = page_k.at[rows, :, pos].set(k_new.astype(f32))
    page_v = page_v.at[rows, :, pos].set(v_new.astype(f32))
    ck, cv = ck.at[:, layer].set(page_k), cv.at[:, layer].set(page_v)
    mask = jnp.where(jnp.arange(ck.shape[3])[None, None, :]
                     <= pos[:, None, None], 0.0, -1e9)
    s = jnp.einsum("shd,shld->shl", q, page_k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(s + mask, axis=-1)
    return jnp.einsum("shl,shld->shd", p, page_v), ck, cv


def new_step(path, q, k_new, v_new, ck, cv, layer, pos):
    if path == "kernel":
        block = pd.decode_block(ck.shape, ck.dtype)
        assert block == BLOCK
        return pd.decode_update_attend(q, k_new, v_new, ck, cv, layer, pos,
                                       block=block, interpret=True)
    ck = tfm._write_rows(ck, layer, pos, k_new)
    cv = tfm._write_rows(cv, layer, pos, v_new)
    return tfm._attend_rows(q, ck, cv, layer, pos), ck, cv


POSITIONS = {
    "all0": [0] * S,
    "group-1": [127] * S,
    "block-1": [BLOCK - 1] * S,
    "block": [BLOCK] * S,
    "block+1": [BLOCK + 1] * S,
    "last": [L - 1] * S,
    "mix": [0, 100, BLOCK - 1, BLOCK, 300, L - 1],
    "mix-dead": [-1, 129, -1, 0, L - 1, -1],
    "dead": [-1] * S,
}
# the kernel takes the L-minor slabs (hd not a multiple of 128); hd = 128
# lies hd-minor on the chip and keeps the XLA formulation
CASES = [(path, dt, hd, name)
         for path, hds in (("kernel", (64,)), ("xla", (64, 128)))
         for dt in ("float32", "bfloat16") for hd in hds for name in POSITIONS]


def _operands(dt, hd, seed=0):
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dt)
    slab = lambda: jnp.asarray(rng.standard_normal((S, NL, H, L, hd)), dt)
    row = lambda: jnp.asarray(rng.standard_normal((S, H, hd)), dt)
    return row(), row(), row(), slab(), slab()


@pytest.mark.parametrize("path,dt,hd,name", CASES,
                         ids=["-".join(map(str, c)) for c in CASES])
def test_slab_access_matches_the_old_step(path, dt, hd, name):
    """Attention and both slabs against the re-stated old step, on a slab
    full of random rows. Tolerance from the dtype: fp32 differs by the order
    of the sums; bf16 by the XLA path's bf16 softmax weights (2^-8)."""
    if path == "kernel":
        assert pd.decode_block((S, NL, H, L, 128), dt) is None
    q, k_new, v_new, ck, cv = _operands(dt, hd)
    pos = jnp.asarray(POSITIONS[name], jnp.int32)
    alive = np.asarray(pos) >= 0
    want, want_k, want_v = old_step(q, k_new, v_new, ck, cv, LAYER,
                                    jnp.maximum(pos, 0))
    got, got_k, got_v = new_step(path, q, k_new, v_new, ck, cv, LAYER, pos)
    tol = 2e-5 if dt == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[alive],
                               np.asarray(want)[alive], rtol=tol, atol=tol)
    # a dead slot attends nothing and writes nothing
    assert not np.asarray(got, np.float32)[~alive].any()
    for got_slab, want_slab, old in ((got_k, want_k, ck), (got_v, want_v, cv)):
        got_slab = np.asarray(got_slab, np.float32)
        assert np.array_equal(got_slab[alive], np.asarray(want_slab)[alive])
        assert np.array_equal(got_slab[~alive],
                              np.asarray(old, np.float32)[~alive])


@pytest.mark.parametrize("path", ["kernel", "xla"])
@pytest.mark.parametrize("junk", [np.inf, np.nan])
def test_rows_past_the_position_never_reach_the_output(path, junk):
    """What a previous occupant left beyond a slot's position — inf, nan —
    is skipped or selected away, never multiplied by a zero weight."""
    q, k_new, v_new, ck, cv = _operands("float32", 64, seed=1)
    pos_list = [0, 100, BLOCK - 1, BLOCK, 300, L - 2]
    pos = jnp.asarray(pos_list, jnp.int32)
    beyond = jnp.arange(L)[None, None, None, :, None] \
        > pos[:, None, None, None, None]
    want, _, _ = old_step(q, k_new, v_new, ck, cv, LAYER, pos)
    got, _, _ = new_step(path, q, k_new, v_new, jnp.where(beyond, junk, ck),
                         jnp.where(beyond, junk, cv), LAYER, pos)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the grid of the tick's live blocks and nothing else (ISSUE 41)
# ---------------------------------------------------------------------------

LIVE_ONLY = {
    "edges": [0, BLOCK - 1, BLOCK, L - 1, 127, 128],
    "dead-between": [5, -1, -1, 300, -1, L - 1],
    "dead-ends": [-1, BLOCK, -1, 0, BLOCK - 1, -1],
    "none-live": [-1] * S,
    "all-last": [L - 1] * S,
    # as many slots as the cells': every one live, the edges among them
    "all-32": [0, BLOCK - 1, BLOCK, L - 1] + list(range(3, 500, 18)),
}


def _restated(q, k_new, v_new, ck, cv, layer, pos, group):
    """`old_step` for ``group`` queries a slab head: a query head reads the
    slab head ``i // group``."""
    n, hq, hd = q.shape
    want = [old_step(q.reshape(n, hq // group, group, hd)[:, :, g],
                     k_new, v_new, ck, cv, layer, pos)[0]
            for g in range(group)]
    return jnp.stack(want, axis=2).reshape(n, hq, hd)


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _within(got, was):
    """The largest difference is at most 1e-5 of the output's scale."""
    got, was = np.asarray(got, np.float32), np.asarray(was, np.float32)
    return np.abs(got - was).max() <= 1e-5 * max(np.abs(was).max(), 1e-30)


@pytest.mark.parametrize("junk", [None, np.inf, np.nan],
                         ids=["clean", "inf", "nan"])
@pytest.mark.parametrize("group", [1, 2, 4, 8],
                         ids=["1q", "2q", "4q", "8q"])
@pytest.mark.parametrize("name", LIVE_ONLY)
def test_live_only_grid(name, group, junk):
    """A grid step a live block and no other: the kernel (interpreted)
    against the re-stated old step, the XLA formulation and the kernel as it
    was with its grid of every (slot, block) (`slab_kernel_2d`), with dead
    slots between and around live ones, none live, every slot at its last
    row, 32 live slots, and positions on both sides of a block's and a lane
    group's edge; one query a slab head (GPT-2's), four (granite's, LFM2's),
    two and eight; inf or nan in every dead page and past every position. At
    one query a head the attention is the old grid's bit for bit: the same
    body. A group of queries goes through the MXU (ISSUE 48) where
    `slab_kernel_2d` still streams each query per lane: the same products
    and float32 weights in another order of sums, held to 1e-5 of the
    output's scale. Every slab row but the written ones comes back bit for
    bit either way, the junk included."""
    positions = LIVE_ONLY[name]
    n = len(positions)
    rng = np.random.default_rng(3)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k_new, v_new = draw(n, H * group, 64), draw(n, H, 64), draw(n, H, 64)
    clean_k, clean_v = draw(n, NL, H, L, 64), draw(n, NL, H, L, 64)
    pos = jnp.asarray(positions, jnp.int32)
    alive = np.asarray(pos) >= 0
    ck, cv = clean_k, clean_v
    if junk is not None:
        # past a live slot's position and all of a dead slot's pages
        beyond = jnp.arange(L)[None, None, None, :, None] \
            > pos[:, None, None, None, None]
        ck, cv = jnp.where(beyond, junk, ck), jnp.where(beyond, junk, cv)
    block = pd.decode_block(ck.shape, ck.dtype)
    got, got_k, got_v = pd.decode_update_attend(
        q, k_new, v_new, ck, cv, LAYER, pos, block=block, interpret=True)
    was, was_k, was_v = slab_kernel_2d.decode_update_attend(
        q, k_new, v_new, ck, cv, LAYER, pos, block=block, interpret=True)
    if group == 1:
        assert np.array_equal(_bits(got), _bits(was))
    else:
        assert np.isfinite(np.asarray(got)).all() and _within(got, was)
    assert np.array_equal(_bits(got_k), _bits(was_k))
    assert np.array_equal(_bits(got_v), _bits(was_v))
    want = _restated(q, k_new, v_new, clean_k, clean_v, LAYER,
                     jnp.maximum(pos, 0), group)
    xla = tfm._attend_rows(q, tfm._write_rows(ck, LAYER, pos, k_new),
                           tfm._write_rows(cv, LAYER, pos, v_new), LAYER, pos)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[alive], np.asarray(want)[alive],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[alive], np.asarray(xla)[alive],
                               rtol=2e-5, atol=2e-5)
    assert not got[~alive].any()
    for slab, after, new in ((ck, got_k, k_new), (cv, got_v, v_new)):
        expect = np.array(slab)
        for s, p in enumerate(positions):
            if p >= 0:
                expect[s, LAYER, :, p, :] = np.asarray(new)[s]
        assert np.array_equal(_bits(after), _bits(expect))


GROUPED = [(group, dt, name) for group in (2, 4, 8)
           for dt in ("float32", "bfloat16") for name in POSITIONS]


@pytest.mark.parametrize("group,dt,name", GROUPED,
                         ids=["-".join(map(str, c)) for c in GROUPED])
def test_grouped_body_matches_the_per_lane_body(group, dt, name):
    """ISSUE 48: a slab head's group of queries as two MXU products and one
    running softmax (`pallas_decode._kernel`) against the body it replaced,
    which `slab_kernel_2d` still is — each query streamed per lane on the
    vector unit in float32 — and against the XLA formulation; 2, 4 and 8
    queries a head, float32 and bfloat16 slabs, every entry of `POSITIONS`,
    inf in K and nan in V past every position and all through a dead
    slot's pages. The products take the operands as they are stored and the
    weights stay float32 (a bfloat16 slab takes them as two bfloat16
    terms), so the two bodies differ by float32's rounding and the order of
    the sums: at most 1e-5 of the output's scale. Exactly one row a live
    slot goes back to each slab; every other row returns bit for bit."""
    positions = POSITIONS[name]
    rng = np.random.default_rng(group)
    dtype = jnp.dtype(dt)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    q, k_new, v_new = draw(S, H * group, 64), draw(S, H, 64), draw(S, H, 64)
    clean_k, clean_v = draw(S, NL, H, L, 64), draw(S, NL, H, L, 64)
    pos = jnp.asarray(positions, jnp.int32)
    alive = np.asarray(pos) >= 0
    beyond = jnp.arange(L)[None, None, None, :, None] \
        > pos[:, None, None, None, None]
    ck = jnp.where(beyond, jnp.inf, clean_k).astype(dtype)
    cv = jnp.where(beyond, jnp.nan, clean_v).astype(dtype)
    got, got_k, got_v = pd.decode_update_attend(
        q, k_new, v_new, ck, cv, LAYER, pos, block=BLOCK, interpret=True)
    was, _, _ = slab_kernel_2d.decode_update_attend(
        q, k_new, v_new, ck, cv, LAYER, pos, block=BLOCK, interpret=True)
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == (S, H * group, 64)
    assert np.isfinite(got).all() and not got[~alive].any()
    assert _within(got, was)
    xla = tfm._attend_rows(q, tfm._write_rows(ck, LAYER, pos, k_new),
                           tfm._write_rows(cv, LAYER, pos, v_new), LAYER, pos)
    tol = 2e-5 if dt == "float32" else 2e-2    # XLA's bf16 softmax weights
    np.testing.assert_allclose(got[alive], np.asarray(xla, np.float32)[alive],
                               rtol=tol, atol=tol)
    for slab, after, new in ((ck, got_k, k_new), (cv, got_v, v_new)):
        expect = np.array(slab.astype(jnp.float32))
        for s, p in enumerate(positions):
            if p >= 0:
                expect[s, LAYER, :, p, :] = np.asarray(
                    new.astype(jnp.float32))[s]
        assert after.dtype == dtype
        assert np.array_equal(_bits(after.astype(jnp.float32)),
                              _bits(expect))


def _grid_of(positions):
    """What the kernel's call is handed for its grid: the bound and the work
    list, the first operands of the `pallas_call` in the function's trace
    (before the positions and the layer), evaluated."""
    n = len(positions)
    row, slab = jnp.zeros((n, H, 64)), jnp.zeros((n, NL, H, L, 64))
    bound, slot_of, block_of, pos = grid_of_call(
        lambda q, k, v, ck, cv, pos: pd.decode_update_attend.__wrapped__(
            q, k, v, ck, cv, LAYER, pos, block=BLOCK),
        row, row, row, slab, slab, jnp.asarray(positions, jnp.int32))
    assert pos.tolist() == list(positions)
    return int(bound), slot_of, block_of


GRIDS = dict(LIVE_ONLY, **{"one": [-1, -1, 300, -1, -1, -1],
                           "steps": [300, -1, 0, 511, -1, 256]})


@pytest.mark.parametrize("name", GRIDS)
def test_grid_bound_is_the_live_blocks(name):
    """The grid's bound is what the engine's `slab_blocks_live` counts, the
    tick's live blocks (`live_blocks(positions, block).sum()`), and 1 for a
    tick with none; the work list walks the live slots in slot order, each
    from block 0 to the block of its position, and past the bound, where
    nothing steps, it stays inside the slab."""
    positions = GRIDS[name]
    bound, slot_of, block_of = _grid_of(positions)
    walk = [(s, b) for s, p in enumerate(positions) if p >= 0
            for b in range(p // BLOCK + 1)]
    assert len(walk) == pd.live_blocks(np.asarray(positions), BLOCK).sum()
    assert bound == max(len(walk), 1)
    assert list(zip(slot_of.tolist(), block_of.tolist()))[:len(walk)] == walk
    assert slot_of.shape == block_of.shape == (len(positions) * (L // BLOCK),)
    assert 0 <= slot_of.min() and slot_of.max() < len(positions)
    assert 0 <= block_of.min() and block_of.max() < L // BLOCK
    if not walk:
        assert block_of[0] == 0            # the one step: a block sent back
    same = pd.live_steps(jnp.asarray(positions), BLOCK, L // BLOCK)
    assert [np.asarray(x).tolist() for x in same] == [
        bound, slot_of.tolist(), block_of.tolist()]



@pytest.fixture
def tiny_lm():
    cfg = TransformerLMConfig(vocab_size=61, d_model=128, n_heads=2, d_ff=128,
                              n_layers=2, max_len=256, dtype="float32")
    # one device: on a mesh of several the model keeps the XLA formulation
    lm = TransformerLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))
    return lm, lm.init_params(jax.random.PRNGKey(0))


def _decode(lm, params, positions, seed=2):
    rng = np.random.default_rng(seed)
    n = len(positions)
    shape = (n, lm.cfg.n_layers, lm.cfg.n_heads, 256, 64)
    ck = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    cv = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 61, n), jnp.int32)
    logits, nk, nv = jax.jit(lm.decode_step)(
        params, ck, cv, tokens, jnp.asarray(positions, jnp.int32))
    return logits, (ck, cv), (nk, nv)


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_decode_step_writes_exactly_one_row_a_live_slot(tiny_lm, monkeypatch,
                                                        path):
    """After one decode_step each slab differs from its input in exactly
    the rows (s, i, :, positions[s], :) of the live slots, all layers."""
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION",
                       "1" if path == "kernel" else "0")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    lm, params = tiny_lm
    positions = [0, 127, 128, -1, 255]
    assert (lm.decode_block((5, 2, 2, 256, 64), jnp.float32)
            == (256 if path == "kernel" else None))
    _, olds, news = _decode(lm, params, positions)
    for old, new in zip(olds, news):
        changed = np.asarray(old != new).any(axis=(2, 4))        # [S,NL,L]
        want = np.zeros_like(changed)
        for s, p in enumerate(positions):
            if p >= 0:
                want[s, :, p] = True
        assert np.array_equal(changed, want)


def test_decode_step_kernel_and_xla_agree(tiny_lm, monkeypatch):
    lm, params = tiny_lm
    positions = [3, 127, 128, -1, 255]
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    out = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", flag)
        out[flag] = _decode(lm, params, positions)
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(np.asarray(out["1"][0])[live],
                               np.asarray(out["0"][0])[live],
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(out["1"][2], out["0"][2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


ONE_QUERY_LOWERED = {   # sha256 of the lowered text at commit f6fb760 (PR 47)
    "tiny": (dict(vocab_size=61, d_model=128, n_heads=2, d_ff=128, n_layers=2,
                  max_len=256, dtype="float32"), 5,
             "c68d8e0b7bfaa6f53aa4dbdac7f4b377e5b2bef1b562270e479f0a218edc4c8e"),
    # GPT-2 XL's widths, two of its 48 layers: the chat cell's 32 slots
    "xl": (dict(vocab_size=50257, d_model=1600, n_heads=25, d_ff=6400,
                n_layers=2, max_len=1024, dtype="bfloat16"), 32,
           "011997c9914a39acb01e9a1c8b0c80c644b53c44463c8f5cad41e335938e926d")}


@pytest.mark.parametrize("name", ONE_QUERY_LOWERED)
def test_one_query_decode_lowers_what_it_did(monkeypatch, name):
    """One query a slab head keeps its body (ISSUE 48: the grouped body is
    a branch on a static fact of the trace, `Hq // H`): with the kernels on
    `TransformerLM.decode_step` lowers to the text it lowered to on the
    parent commit — its sha256, taken there before the change; the kernel
    interpreted, so the text is the kernel's own operations, the grid, the
    block specs and the aliasing (a Mosaic lowering carries the source's
    line numbers); the installation is pinned, so the text is a function of
    the program alone."""
    import hashlib

    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    config, slots, pinned = ONE_QUERY_LOWERED[name]
    cfg = TransformerLMConfig(**config)
    lm = TransformerLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))
    slab = jax.ShapeDtypeStruct(
        (slots, cfg.n_layers, cfg.n_heads, cfg.max_len, 64), cfg.dtype)
    assert lm.decode_block(slab.shape, slab.dtype) == BLOCK
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    lowered = jax.jit(lm.decode_step).lower(
        jax.eval_shape(lm.init_params, jax.random.PRNGKey(0)), slab, slab,
        ints, ints)
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() == pinned


def test_live_blocks_counts_what_the_kernel_reads():
    pos = np.array([-1, 0, 255, 256, 511, -7])
    assert pd.live_blocks(pos, 256).tolist() == [0, 1, 1, 2, 2, 0]
    assert pd.live_blocks(pos, 128).tolist() == [0, 1, 2, 3, 4, 0]


@pytest.mark.parametrize("shape,dtype,want", [
    ((32, 48, 25, 1024, 64), "bfloat16", 256),    # GPT-2 XL, the chat cell
    ((8, 12, 12, 1024, 64), "bfloat16", 256),     # GPT-2 small, chip_smoke
    ((3, 2, 4, 48, 12), "float32", None),         # hd not a sublane multiple
    ((3, 2, 4, 64, 16), "float32", None),         # L below a lane row
    ((4, 2, 8, 1024, 128), "bfloat16", None),     # hd-minor on the chip
    ((4, 2, 100, 1024, 64), "bfloat16", 128),     # the block budget halves it
    ((4, 2, 200, 1024, 64), "float32", None),     # ... and gives up
    ((4, 2, 8, 1000, 64), "bfloat16", None),      # no lane-aligned block
    # the block follows the rows a slot: an eighth, from 256 to 1,024
    ((32, 4, 8, 4096, 64), "bfloat16", 512),      # granite4h_workers32
    ((64, 3, 8, 8192, 64), "bfloat16", 1024),     # lfm2moe_workers64
    ((4, 2, 8, 2048, 64), "bfloat16", 256),
    ((4, 2, 8, 32768, 64), "bfloat16", 1024),
    ((4, 2, 20, 8192, 64), "bfloat16", 512),      # ... under the budget
], ids=["xl", "small", "hd12", "L64", "hd128", "wide", "wider", "L1000",
        "granite", "lfm2", "L2048", "L32768", "L8192-wide"])
def test_decode_block_shape_test(shape, dtype, want):
    assert pd.decode_block(shape, dtype) == want


def test_engine_marks_dead_slots_and_counts_slab_blocks(tiny_lm, monkeypatch):
    """The engine hands every slot without a live session position -1, and
    with telemetry on counts the blocks each dispatch reads."""
    lm, params = tiny_lm
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    was = telemetry._enabled
    telemetry.enable()
    try:
        live0 = telemetry.counter("serving.generation.slab_blocks_live").value
        total0 = telemetry.counter(
            "serving.generation.slab_blocks_total").value
        with GenerationEngine(lm, params, max_slots=4, max_len=256,
                              buckets=(16,), prefix_cache=False) as eng:
            assert eng._slab_block == 256
            assert eng._tick_positions().tolist() == [-1] * 4
            toks = eng.generate(np.arange(1, 6, dtype=np.int32),
                                max_new_tokens=4)
            assert len(toks) == 4
        live = telemetry.counter(
            "serving.generation.slab_blocks_live").value - live0
        total = telemetry.counter(
            "serving.generation.slab_blocks_total").value - total0
    finally:
        if not was:
            telemetry.disable()
    # 3 decode ticks (the first token is the prefill's), one live slot of
    # four, one 256-row block a slot
    assert (live, total) == (3, 12)
