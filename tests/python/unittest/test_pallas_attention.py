"""Fused flash-attention Pallas kernel (`ops/pallas_attention.py`) vs the
plain-XLA reference, in interpret mode (the chip-free validation path the
pallas guide prescribes). On TPU the same kernel runs compiled; the
transformer's `_attention` dispatches to it there by default."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_attention import (flash_attention,
                                            reference_attention)


def _qkv(b=2, l=64, h=4, d=32, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, l, h, d).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_multiple_k_blocks_streaming():
    """More K blocks than Q blocks: the running max/sum-exp rescale is
    what's being exercised."""
    q, k, v = _qkv(l=128)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=16,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_bf16_inputs():
    q, k, v = _qkv()
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=False, block_q=32, block_k=32,
                          interpret=True)
    ref = reference_attention(qb, kb, vb, causal=False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_gradients_match_reference():
    """custom_vjp backward = vjp of the reference attention — gradients to
    q, k AND v must equal the pure-XLA path."""
    q, k, v = _qkv(l=32)

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=16,
                                block_k=16, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_rejects_indivisible_shapes():
    q, k, v = _qkv(l=60)  # 60 % 128-clamped-to-60 ok; force bad blocks
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)


def test_transformer_dispatches_to_pallas(monkeypatch):
    """With the policy forced on (+ interpret for CPU), the transformer's
    local attention runs the fused kernel and matches the XLA path."""
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    from mxnet_tpu.models.transformer import TransformerLM, TransformerLMConfig

    from mxnet_tpu import parallel as par

    mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
    cfg = TransformerLMConfig(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=1, d_ff=64, max_len=16, causal=True,
                              dtype="float32")
    model = TransformerLM(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    with mesh:
        out_pallas = np.asarray(model.forward(params, tokens))
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "0")
        out_xla = np.asarray(model.forward(params, tokens))
    np.testing.assert_allclose(out_pallas, out_xla, rtol=2e-2, atol=2e-2)


def test_ring_hop_partials_and_gradients():
    """The differentiable ring-hop wrapper (`block_partials_pallas`):
    forward partials match `_block_attn`, and gradients through the
    custom_vjp match differentiating `_block_attn` directly."""
    from mxnet_tpu.ops.pallas_attention import block_partials_pallas
    from mxnet_tpu.parallel.ring_attention import _block_attn, _bhql_to_bqhl

    rng = np.random.RandomState(1)
    B, L, H, D = 2, 256, 2, 16   # two 128-row q blocks x four k blocks
    q, k, v = (jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
               for _ in range(3))
    qpos = np.arange(L)[:, None]
    bias = jnp.asarray(np.where(qpos >= np.arange(L)[None, :], 0.0,
                                -1e30)[None, None].astype(np.float32))
    scale = 1.0 / np.sqrt(D)

    def loss_pallas(q, k, v):
        o, m, l = block_partials_pallas(q, k, v, bias, scale,
                                        block_q=128, block_k=64,
                                        interpret=True)
        return ((o / _bhql_to_bqhl(l)) ** 2).sum()

    def loss_xla(q, k, v):
        o, m, l = _block_attn(q, k, v, bias, scale)
        return ((o / _bhql_to_bqhl(l)) ** 2).sum()

    np.testing.assert_allclose(float(loss_pallas(q, k, v)),
                               float(loss_xla(q, k, v)), rtol=1e-5)
    g_p = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g_x = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_p, g_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ring_attention_with_pallas_hops(monkeypatch):
    """End to end: ring attention over a 4-device sp mesh with the fused
    kernel in every hop (interpret mode) equals the XLA-hop ring."""
    import jax as _jax

    if len(_jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from mxnet_tpu import parallel as par
    from mxnet_tpu.parallel.ring_attention import ring_self_attention

    rng = np.random.RandomState(2)
    B, L, H, D = 2, 32, 2, 8
    q, k, v = (rng.randn(B, L, H, D).astype(np.float32) for _ in range(3))
    mesh = par.create_mesh(devices=_jax.devices()[:4], dp=1, sp=4)
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "0")
    with mesh:
        out_xla = np.asarray(ring_self_attention(q, k, v, mesh=mesh,
                                                 causal=True))
    monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    with mesh:
        out_pl = np.asarray(ring_self_attention(q, k, v, mesh=mesh,
                                                causal=True))
    np.testing.assert_allclose(out_pl, out_xla, rtol=1e-4, atol=1e-5)


def test_flash_causal_cross_length_rejected():
    """Causal with lq != lk aligns sequence ENDS in the XLA reference; the
    kernel's aligned-position mask would differ, so it must refuse and
    let callers keep the XLA path."""
    q, _, _ = _qkv(l=32)
    k, v, _ = _qkv(l=64, seed=1)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True, interpret=True)


def test_partials_reject_per_head_bias():
    from mxnet_tpu.ops.pallas_attention import flash_block_partials

    q, k, v = _qkv(l=32)
    per_head = jnp.zeros((2, 4, 32, 32), jnp.float32)
    with pytest.raises(ValueError):
        flash_block_partials(q, k, v, bias=per_head, interpret=True)


def test_pallas_compile_cache_miss_pinning():
    """Kernel factories live in CompileCache("pallas") (were anonymous
    lru_caches): one miss per distinct (scale, causal, blocks, interpret)
    config, pure hits on replay — named_stats deltas, the repo rule."""
    from mxnet_tpu import compile_cache

    q, k, v = _qkv(l=32)
    cfg = dict(causal=True, block_q=16, block_k=16, interpret=True)
    before = compile_cache.named_stats("pallas")
    flash_attention(q, k, v, **cfg)
    mid = compile_cache.named_stats("pallas")
    assert mid["misses"] - before["misses"] in (0, 1)  # warm if reused cfg
    flash_attention(q, k, v, **cfg)
    after = compile_cache.named_stats("pallas")
    assert after["misses"] - mid["misses"] == 0        # steady state
    assert after["hits"] - mid["hits"] >= 1
    # distinct config -> distinct executable: exactly one more miss max
    flash_attention(q, k, v, causal=False, block_q=16, block_k=16,
                    interpret=True)
    end = compile_cache.named_stats("pallas")
    assert end["misses"] - after["misses"] <= 1
