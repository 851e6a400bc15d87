"""`HybridLM` (Mamba-2 + grouped-query attention, the Granite 4.0-H block)
against the plain reference `benchmark/reference/granite_hybrid.py`, at a
tiny size with the published structure: period "mmAm", 4 queries a K/V head,
chunks of 8. The model is float32 here, so it agrees with the float32
reference to rounding: every tolerance is 1e-4 of the compared quantity's
scale — a bfloat16 recurrent state (2^-9 a step) or a skipped term (the `D`
skip, the convolution's bias, the gate) misses it by orders of magnitude,
which `test_tolerance_catches` pins. The bfloat16 model at the published
widths is compared on the chip (benchmark/runners/serve_closed_loop.py).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import HybridLM, HybridLMConfig
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import pallas_decode as pd
from mxnet_tpu.ops import pallas_ssm
from mxnet_tpu.serving import GenerationEngine, qos

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [os.path.join(REPO, "benchmark")]
from reference import granite_hybrid as ref  # noqa: E402
from runners.serve_closed_loop import granite_published  # noqa: E402

from lm_jit import jitted  # noqa: E402

ref.PAD_TO = 32     # the chip's 512 would spend these tiny tests on padding

TOL = 1e-4
VOCAB = 211
CONFIG = dict(
    hidden_size=64, shared_intermediate_size=128, num_attention_heads=8,
    num_key_value_heads=2, attention_multiplier=0.125,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, layer_types=["mamba", "mamba", "attention", "mamba"],
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_expand=2, mamba_n_groups=1, vocab_size=VOCAB,
    max_position_embeddings=128, dtype="float32")


@pytest.fixture(scope="module")
def tiny():
    cfg = HybridLMConfig.from_config(CONFIG)
    lm = HybridLM(cfg, par.create_mesh(devices=jax.devices()[:1], dp=1))
    params = lm.init_params(jax.random.PRNGKey(0))
    return lm, params, granite_published(params)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _close(got, want, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert np.isfinite(got).all() and err <= TOL, (what, err)


def _poisoned(lm, slots, max_len):
    """A cache whose every member is NaN: what a careless previous
    occupant may leave in a slot."""
    return tuple(jnp.full(c.shape, jnp.nan, c.dtype)
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


@pytest.mark.parametrize("length", [7, 8, 9, 29])
def test_forward_matches_reference(tiny, length):
    lm, params, weights = tiny
    seq = _tokens(length)
    want = ref.logits(CONFIG, weights, seq, np.arange(length))
    _close(lm.forward(params, seq[None])[0], want, "logits")


# chunk - 1, chunk, chunk + 1, several chunks with a ragged last one, and a
# prompt well below its bucket
@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("prompt_len,bucket", [(7, 8), (8, 8), (9, 16),
                                               (29, 32), (13, 32)])
def test_prefill_then_decode_matches_full_forward(tiny, monkeypatch, path,
                                                  prompt_len, bucket):
    """Prefill into a slot whose previous occupant left NaN everywhere, then
    decode through the cache: every logit row and the slot's final recurrent
    state are the reference's full forward's; the other slots stay NaN."""
    lm, params, weights = tiny
    if path == "kernels":
        monkeypatch.setenv("MXNET_PALLAS_ATTENTION", "1")
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        assert lm.state_kernel((3, 3, 4, 32, 128), jnp.float32)
    steps = 6
    seq = _tokens(prompt_len + steps, seed=prompt_len)
    want, states = ref.forward(CONFIG, weights, seq,
                               np.arange(prompt_len - 1, len(seq)))
    # 128 rows: the decode kernel's smallest block
    logits, cache = _prefill(lm, params, _poisoned(lm, 3, 128),
                             seq[:prompt_len], bucket, slot=1)
    _close(logits, want[0], "prefill logits")
    for t in range(prompt_len, len(seq)):
        logits, cache = _decode(lm, params, cache, 1, seq[t], t)
        _close(logits, want[t - prompt_len + 1], f"decode logits at {t}")
    ssm = np.asarray(cache[2])
    for got, layer_state in zip(ssm[1], states):
        _close(got, layer_state, "recurrent state")
    assert np.isnan(ssm[[0, 2]]).all(), "a dead slot's state was touched"
    assert np.isnan(np.asarray(cache[3])[[0, 2]]).all()


def test_padding_leaves_the_true_last_tokens_state(tiny):
    """A prompt padded to its bucket leaves exactly the state and the
    convolution window of the unpadded run."""
    lm, params, _ = tiny
    prompt = _tokens(13, seed=3)
    _, exact = _prefill(lm, params, lm.init_cache(2, 64), prompt, 13, 0)
    _, padded = _prefill(lm, params, lm.init_cache(2, 64), prompt, 32, 0)
    for a, b, name in zip(exact[2:], padded[2:], ("ssm", "conv")):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
    # K/V rows of the real tokens too
    np.testing.assert_allclose(np.asarray(exact[0][0])[:, :, :13],
                               np.asarray(padded[0][0])[:, :, :13],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sabotage", ["bf16_state", "no_D", "no_conv_bias"])
def test_tolerance_catches(tiny, sabotage):
    """What the tolerance is there to catch does miss it, by the logits or
    by the final recurrent state: a state rounded to bfloat16 after every
    step (2^-9 of each entry a step), and two skipped terms."""
    lm, params, weights = tiny
    seq = _tokens(40, seed=5)
    want, states = ref.forward(CONFIG, weights, seq, np.arange(8, 40))
    want = np.asarray(want)
    if sabotage != "bf16_state":
        leaf = {"no_D": "D", "no_conv_bias": "conv_b"}[sabotage]
        params = {k: jnp.zeros_like(v) if k.endswith("." + leaf) else v
                  for k, v in params.items()}
    logits, cache = _prefill(lm, params, lm.init_cache(1, 64), seq[:9], 16, 0)
    rows = [np.asarray(logits)]
    for t in range(9, 40):
        logits, cache = _decode(lm, params, cache, 0, seq[t], t)
        if sabotage == "bf16_state":
            cache = cache[:2] + (cache[2].astype(jnp.bfloat16)
                                 .astype(jnp.float32),) + cache[3:]
        rows.append(np.asarray(logits))
    errs = [np.abs(np.stack(rows) - want).max() / np.abs(want).max()]
    errs += [np.abs(np.asarray(got) - np.asarray(s)).max()
             / np.abs(np.asarray(s)).max()
             for got, s in zip(cache[2][0], states)]
    assert max(errs) > 10 * TOL, (sabotage, errs)


@pytest.mark.parametrize("group", [4, 1])
def test_decode_kernel_grouped_queries(group):
    """`decode_update_attend` in interpret mode with 4 queries a slab head
    and with one, and a score multiplier of its own, against a plain
    restatement."""
    S, NL, H, L, hd, layer, scale = 4, 2, 2, 256, 64, 1, 0.05
    rng = np.random.default_rng(group)
    f32 = jnp.float32
    ck, cv = (jnp.asarray(rng.standard_normal((S, NL, H, L, hd)), f32)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((S, H * group, hd)), f32)
    k, v = (jnp.asarray(rng.standard_normal((S, H, hd)), f32)
            for _ in range(2))
    pos = jnp.asarray([0, 130, -1, 255], jnp.int32)
    got, gk, gv = pd.decode_update_attend(
        q, k, v, ck, cv, layer, pos, block=pd.decode_block(ck.shape, f32),
        scale=scale, interpret=True)
    wk = tfm._write_rows(ck, layer, pos, k)
    wv = tfm._write_rows(cv, layer, pos, v)
    assert np.array_equal(np.asarray(gk), np.asarray(wk))
    assert np.array_equal(np.asarray(gv), np.asarray(wv))
    rows = np.arange(L)[None, :] <= np.asarray(pos)[:, None]
    kk = np.repeat(np.asarray(wk[:, layer]), group, axis=1)     # [S,Hq,L,hd]
    vv = np.repeat(np.asarray(wv[:, layer]), group, axis=1)
    s = np.einsum("shd,shld->shl", np.asarray(q), kk) * scale
    s = np.where(rows[:, None, :], s, -np.inf)
    with np.errstate(invalid="ignore"):
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
    want = np.einsum("shl,shld->shd", p, vv)
    live = np.asarray(pos) >= 0
    np.testing.assert_allclose(np.asarray(got)[live], want[live], rtol=1e-4,
                               atol=1e-5)
    assert not np.asarray(got)[~live].any()
    # the XLA formulation the kernel stands in for
    xla = tfm._attend_rows(q, wk, wv, layer, pos, scale=scale)
    np.testing.assert_allclose(np.asarray(xla)[live], want[live], rtol=1e-4,
                               atol=1e-5)


# the tiny model's page, the published page (H = P = 64: 32 whole 128-row
# tiles) and a page whose H * P = 120 rows fill no whole tile
@pytest.mark.parametrize("dims", [(3, 4, 16, 128), (2, 64, 64, 128),
                                  (2, 3, 40, 128)],
                         ids=["tiny", "published", "rows120"])
@pytest.mark.parametrize("alive", [[1, 1, 1, 1], [0, 1, 0, 1], [0, 0, 0, 0]])
def test_state_kernel_matches_restatement(alive, dims):
    """The kernel against a float64 restatement: the written state is the
    float32 formula's to an ulp of its terms, `y` is no further from float64
    than twice what the XLA formulation of `Mamba2Mixer.step` (float32, summed
    over lanes) is, dead slots and the other pages stay bit for bit."""
    (NL, H, P, N), S, page = dims, 4, 1
    rng = np.random.default_rng(1)
    f32 = jnp.float32
    slab = jnp.asarray(rng.standard_normal((S, NL, H, P, N)), f32)
    decay = jnp.asarray(rng.uniform(0.5, 1, (S, H)), f32)
    dtx = jnp.asarray(rng.standard_normal((S, H, P)), f32)
    b, c = (jnp.asarray(rng.standard_normal((S, N)), f32) for _ in range(2))
    alive = jnp.asarray(alive, bool)
    assert pallas_ssm.state_update_applies(slab.shape, slab.dtype)
    y, out = pallas_ssm.state_update(slab, page, decay, dtx, b, c, alive,
                                     interpret=True)
    # the XLA formulation, term by term
    kept = decay[:, :, None, None] * slab[:, page]
    fed = dtx[..., None] * b[:, None, None, :]
    new = kept + fed
    xla = np.asarray(jnp.sum(new * c[:, None, None, :], -1))
    live = np.asarray(alive)
    got = np.asarray(out[:, page])[live]
    ulp = np.spacing(np.abs(np.asarray(kept)) + np.abs(np.asarray(fed)))
    assert (np.abs(got - np.asarray(new)[live]) <= ulp[live]).all()
    # y of the state AS WRITTEN, in float64
    want = np.einsum("shpn,sn->shp", got.astype(np.float64),
                     np.asarray(c, np.float64)[live])
    if live.any():
        err = np.abs(np.asarray(y)[live] - want)
        parent = np.abs(xla[live] - np.einsum(
            "shpn,sn->shp", np.asarray(new, np.float64)[live],
            np.asarray(c, np.float64)[live]))
        assert err.max() <= 2 * parent.max(), (err.max(), parent.max())
        assert np.median(err) <= 2 * np.median(parent)
        np.testing.assert_allclose(np.asarray(y)[live], want, rtol=1e-5,
                                   atol=1e-5)
    assert not np.asarray(y)[~live].any()
    # dead slots and the other layers' pages: bit for bit
    assert np.array_equal(np.asarray(out)[~live], np.asarray(slab)[~live])
    others = [i for i in range(NL) if i != page]
    assert np.array_equal(np.asarray(out[:, others]),
                          np.asarray(slab[:, others]))


def _greedy_reference(weights, prompt, n):
    """The reference's own greedy continuation, one full forward a token."""
    seq = list(prompt)
    for _ in range(n):
        row = ref.logits(CONFIG, weights, np.asarray(seq), [len(seq) - 1])
        seq.append(int(np.asarray(row)[0].argmax()))
    return seq[len(prompt):]


def test_engine_serves_more_requests_than_slots(tiny):
    """Through `GenerationEngine`, 7 requests over 3 slots: every stream is
    the reference's greedy continuation (slots are reused, so a prefill
    really replaces what its slot held)."""
    lm, params, weights = tiny
    prompts = [_tokens(n, seed=n) for n in (3, 8, 9, 17, 5, 30, 12)]
    with GenerationEngine(lm, params, max_slots=3, max_len=64,
                          buckets=(8, 32), prefix_cache=False,
                          spec_k=0) as eng:
        assert len(eng._kv) == 4 and eng.kv_slab_bytes() == sum(
            int(leaf.nbytes) for leaf in eng._kv)
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [s.result(timeout=120) for s in streams]
        misses = eng.cache.misses
        again = eng.generate(prompts[3], max_new_tokens=6)
        assert eng.cache.misses == misses           # nothing recompiles
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(weights, p, 6)
    assert again == got[3]


def _state_at(lm, params, prompt, length):
    """The state members of a session's slot once the host counts `length`
    tokens in it, alone in an engine of the same shape."""
    eng = GenerationEngine(lm, params, max_slots=2, max_len=64,
                           buckets=(16,), start=False, prefix_cache=False,
                           spec_k=0)
    try:
        s = eng.submit(prompt, max_new_tokens=40, tenant="bulk")
        for _ in range(60):
            if s.slot is not None and eng._lengths[s.slot] == length:
                return eng.slot_snapshot(s.slot)[2:]
            eng._tick_once()
        raise AssertionError("the session never reached that length")
    finally:
        eng.close()


def test_park_copies_every_member_and_resume_is_bit_equal(tiny):
    """QoS park and resume go through the fork executable, which copies one
    slot of EVERY member of the cache. The parked copy holds the state of
    exactly the tokens the host counts for it — a slot is parked between
    decodes, never under one in flight, which would advance a recurrent
    state once more than the host knows — and the preempted stream resumes
    bit-equal to an uncontended run."""
    lm, params, _ = tiny
    qos.install(qos.TenantRegistry(qos.parse_spec(
        "lat:interactive;bulk:batch")))
    try:
        bp = [_tokens(9, seed=40), _tokens(14, seed=41)]
        ip = _tokens(6, seed=42)
        with GenerationEngine(lm, params, max_slots=2, max_len=64,
                              buckets=(16,), prefix_cache=False,
                              spec_k=0) as base:
            want = [base.generate(p, max_new_tokens=20) for p in bp]
            iwant = base.generate(ip, max_new_tokens=4)
        eng = GenerationEngine(lm, params, max_slots=2, max_len=64,
                               buckets=(16,), start=False,
                               prefix_cache=False, spec_k=0)
        try:
            assert eng.total_slots == 3
            bs = [eng.submit(p, max_new_tokens=20, tenant="bulk")
                  for p in bp]
            for _ in range(50):
                if eng.live_slots == 2:
                    break
                eng._tick_once()
            eng._tick_once()                    # some state has built up
            istream = eng.submit(ip, max_new_tokens=4, tenant="lat")
            for _ in range(3):
                if eng.parked_count:
                    break
                eng._tick_once()                # parks the youngest
            assert eng.parked_count == 1
            (rec,) = eng._parked.values()
            assert rec["sess"].stream is bs[1]
            parked = eng.slot_snapshot(2)
            for got, alone in zip(parked[2:], _state_at(
                    lm, params, bp[1], rec["length"])):
                assert np.abs(alone).sum() > 0
                np.testing.assert_allclose(got, alone, rtol=1e-5, atol=1e-6)
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


def test_fork_is_a_bitwise_copy_of_one_slot(tiny):
    lm, params, _ = tiny
    eng = GenerationEngine(lm, params, max_slots=3, max_len=64,
                           buckets=(16,), start=False, prefix_cache=False,
                           spec_k=0)
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


@pytest.mark.parametrize("kwargs,what", [
    (dict(prefix_cache=True, spec_k=0), "prefix cache"),
    (dict(prefix_cache=False, spec_k=2), "speculative decoding"),
])
def test_engine_refuses_what_a_recurrent_state_cannot_do(tiny, kwargs, what):
    lm, params, _ = tiny
    with pytest.raises(MXNetError, match=what + ".*recurrent"):
        GenerationEngine(lm, params, max_slots=2, max_len=64, buckets=(16,),
                         start=False, **kwargs)


def test_engine_refuses_by_environment_default(tiny, monkeypatch):
    lm, params, _ = tiny
    monkeypatch.setenv("MXNET_GENERATION_PREFIX_CACHE", "1")
    with pytest.raises(MXNetError, match="prefix cache"):
        GenerationEngine(lm, params, max_slots=2, max_len=64, buckets=(16,),
                         start=False)


def test_state_counters(tiny):
    """Telemetry of the recurrent state: live state slots a dispatch, twice
    their state's bytes touched, the slab's bytes resident."""
    from mxnet_tpu import telemetry

    lm, params, _ = tiny
    prev = telemetry.enabled()
    telemetry.enable()
    try:
        eng = GenerationEngine(lm, params, max_slots=3, max_len=64,
                               buckets=(16,), start=False,
                               prefix_cache=False, spec_k=0)
        per_slot = sum(int(leaf.nbytes) for leaf in eng._kv[2:]) // 3
        pre = "serving.generation."
        c0 = {k: telemetry.counter(pre + k).value
              for k in ("state_slots_live", "state_bytes_touched",
                        "prefill_tokens")}
        streams = [eng.submit(_tokens(n, seed=n), max_new_tokens=3)
                   for n in (5, 9)]
        for _ in range(20):
            if all(s.done for s in streams):
                break
            eng._tick_once()
        eng.close()
        live = telemetry.counter(pre + "state_slots_live").value \
            - c0["state_slots_live"]
        assert live == 4                # 2 sessions x 2 decoded tokens
        assert telemetry.counter(pre + "state_bytes_touched").value \
            - c0["state_bytes_touched"] == 2 * live * per_slot
        assert telemetry.gauge(pre + "state_bytes_resident").value \
            == 3 * per_slot
        assert telemetry.counter(pre + "prefill_tokens").value \
            - c0["prefill_tokens"] == 14
    finally:
        telemetry.enable(prev)


def test_decode_runs_ahead_of_its_commit(tiny):
    """Without sessions that end on a token's value the engine dispatches
    the next decode before it commits the last one (`_dispatch_ahead`): the
    streams are those of the tick that does not (an `eos_id` nobody emits
    turns it off), a slot freed by a deadline under a decode in flight is
    refilled and its new session joins the next dispatch, and the last tick
    leaves nothing out."""
    lm, params, weights = tiny
    prompts = [_tokens(n, seed=50 + n) for n in (5, 12, 9, 20, 7)]

    def serve(**kw):
        eng = GenerationEngine(lm, params, max_slots=2, max_len=64,
                               buckets=(8, 32), start=False,
                               prefix_cache=False, spec_k=0)
        try:
            streams = [eng.submit(p, max_new_tokens=7, **kw)
                       for p in prompts]
            ahead = 0
            for _ in range(200):
                if all(s.done for s in streams):
                    break
                eng._tick_once()
                ahead += eng._ahead is not None
            assert eng._ahead is None and not eng._has_work()
            return [s.result(1) for s in streams], ahead
        finally:
            eng.close()

    got, ahead = serve()
    plain, none_ahead = serve(eos_id=VOCAB + 5)
    assert ahead > 10 and none_ahead == 0
    assert got == plain
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(weights, p, 7)
    # a deadline frees a slot while a decode that advances it is out
    eng = GenerationEngine(lm, params, max_slots=1, max_len=64, buckets=(8,),
                           start=False, prefix_cache=False, spec_k=0)
    try:
        doomed = eng.submit(prompts[0], max_new_tokens=30, timeout=3600)
        for _ in range(4):
            eng._tick_once()
        assert eng._ahead is not None
        doomed._engine._sessions[doomed.slot].deadline = 0.0   # now past
        nxt = eng.submit(prompts[0], max_new_tokens=7)
        for _ in range(40):
            if nxt.done:
                break
            eng._tick_once()
        assert doomed.done and nxt.result(1) == got[0]
    finally:
        eng.close()


def test_nothing_compiles_after_warm_up(tiny):
    """jax's own count, not only the engine's: the decode program must not
    compile a second time for tokens fed back from the device (its token
    argument is placed like its token output, `_tokens_on_device`)."""
    from jax import monitoring

    lm, params, _ = tiny
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        eng = GenerationEngine(lm, params, max_slots=3, max_len=64,
                               buckets=(8, 32), start=False,
                               prefix_cache=False, spec_k=0)
        eng.warm()
        del compiles[:]
        streams = [eng.submit(_tokens(n, seed=n), max_new_tokens=6)
                   for n in (4, 8, 20, 6, 13)]
        for _ in range(100):
            if all(s.done for s in streams):
                break
            eng._tick_once()
        eng.close()
        assert all(len(s.tokens) == 6 for s in streams)
        assert compiles == []
    finally:
        monitoring.unregister_event_duration_listener(listener)
