"""Continuous-batching autoregressive generation: slot KV-cache sessions,
token-level scheduling, streaming front-end.

Covers the generation PR end to end:
* model-level O(1) decode parity — ``prefill`` + ``decode_step`` logits
  match the full-sequence re-forward (documented-ulp tolerance: the cache
  path and the blockwise-softmax forward are different program structures,
  the PR 6 FMA precedent);
* continuous-vs-sequential parity — ragged sessions forced through
  queueing + mid-stream admit/evict produce BIT-EXACT token streams vs
  each session run alone (per-slot computation is row-independent, so the
  co-residents of the slab must not matter);
* slot reuse isolation — a session admitted into a slot a previous
  session dirtied sees none of its KV rows;
* warmup compile pinning — exactly one prefill program per bucket plus
  ONE decode program, zero steady-state misses over concurrent traffic
  (and structurally O(1): the decode cache key never changes);
* scheduling — mid-stream overlap (fewer fused decode ticks than the
  sequential sum), per-tick deadline sweeps for queued AND live sessions
  (DeadlineExceededError on the stream, slot freed — never a wedged
  iterator), queue-full backpressure, close() drain, zero ticks when
  idle;
* router — occupancy-balanced placement across engine replicas;
* observability — serving.generation.* telemetry, the kv_cache memory
  census category, and the tools/telemetry_report.py summary line;
* tracing — one tick's live span tree under both overlap orders (no
  per-session-per-tick span), the session root's token count, and the
  queue-wait histogram recorded once per prefill;
* acceptance — 1k concurrent ragged streaming sessions complete with
  zero steady-state compiles and sampled bit-exact parity vs sequential.
"""
import json
import threading
import time

import numpy as np
import pytest

import jax

from mxnet_tpu import memory, serving, telemetry, tracing
from mxnet_tpu import parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import TransformerLM, TransformerLMConfig
from mxnet_tpu.serving import DeadlineExceededError, QueueFullError, \
    ServerClosedError
from mxnet_tpu.serving.generation import (GenerationEngine, GenerationRouter,
                                          prefill_ladder)

VOCAB = 64


def _model(max_len=48, n_layers=2, d_model=32, vocab=VOCAB, seed=0):
    mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
    cfg = TransformerLMConfig(vocab_size=vocab, d_model=d_model, n_heads=2,
                              d_ff=2 * d_model, n_layers=n_layers,
                              max_len=max_len, dtype="float32")
    lm = TransformerLM(cfg, mesh)
    return lm, lm.init_params(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def lm48():
    """One small model shared across the suite (compiles are per-engine,
    params are read-only)."""
    return _model(max_len=48)


def _prompts(n, lo=2, hi=12, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture
def tele():
    prev = telemetry.enabled()
    telemetry.enable()
    yield telemetry
    telemetry.enable(prev)


def _counter(name):
    m = telemetry.get(name)
    return m.value if m is not None else 0


# ---------------------------------------------------------------------------
# model-level O(1) decode parity
# ---------------------------------------------------------------------------


def test_prefill_decode_match_full_forward(lm48):
    """The cache path (prefill + per-token decode) reproduces the full
    re-forward logits at every step — rtol 1e-3 headroom over the
    observed ~2e-4 (different softmax program structure; PR 6 FMA
    precedent), and greedy argmax agrees exactly."""
    lm, params = lm48
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, VOCAB, 6).astype(np.int32)
    ck, cv = lm.init_cache(3, 32)
    pf = jax.jit(lm.prefill)
    dec = jax.jit(lm.decode_step)
    toks = np.zeros(8, np.int32)
    toks[:6] = prompt
    logits, ck, cv = pf(params, ck, cv, jax.numpy.asarray(toks),
                        jax.numpy.asarray(6), jax.numpy.asarray(1))
    seq = list(prompt)
    cur, pos = int(np.argmax(np.asarray(logits))), 6
    ref = np.asarray(lm.forward(params, jax.numpy.asarray(
        np.array(seq, np.int32))[None]))[0, -1]
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=1e-3, atol=1e-4)
    assert cur == int(np.argmax(ref))
    tokens = np.zeros(3, np.int32)
    positions = np.zeros(3, np.int32)
    for _ in range(4):
        seq.append(cur)
        tokens[1], positions[1] = cur, pos
        lg, ck, cv = dec(params, ck, cv, jax.numpy.asarray(tokens),
                         jax.numpy.asarray(positions))
        got = np.asarray(lg)[1]
        full = np.asarray(lm.forward(params, jax.numpy.asarray(
            np.array(seq, np.int32))[None]))[0, -1]
        np.testing.assert_allclose(got, full, rtol=1e-3, atol=1e-4)
        assert int(np.argmax(got)) == int(np.argmax(full))
        cur, pos = int(np.argmax(got)), pos + 1


def test_cache_rejects_overlong():
    lm, _ = _model(max_len=16, n_layers=1, d_model=16)
    with pytest.raises(ValueError):
        lm.init_cache(2, 64)


# ---------------------------------------------------------------------------
# engine: parity, isolation, scheduling
# ---------------------------------------------------------------------------


def test_continuous_matches_sequential(lm48):
    """24 ragged sessions through a 3-slot engine (forced queueing and
    mid-stream admit/evict) produce BIT-EXACT token streams vs each
    session run alone through a fresh engine of the same slab shape."""
    lm, params = lm48
    prompts = _prompts(24, seed=1)
    with GenerationEngine(lm, params, max_slots=3, max_len=48,
                          buckets=(8, 16)) as eng:
        streams = [eng.submit(p, max_new_tokens=3 + (i % 5))
                   for i, p in enumerate(prompts)]
        got = [s.result(timeout=60) for s in streams]
    with GenerationEngine(lm, params, max_slots=3, max_len=48,
                          buckets=(8, 16)) as ref:
        for i, p in enumerate(prompts):
            alone = ref.generate(p, max_new_tokens=3 + (i % 5))
            assert alone == got[i], f"session {i} diverged under batching"


def test_slot_reuse_isolation(lm48):
    """No KV bleed: with ONE slot, session B decoded after session A
    dirtied the slot equals B run in a fresh engine."""
    lm, params = lm48
    a, b = _prompts(2, seed=2)
    with GenerationEngine(lm, params, max_slots=1, max_len=48,
                          buckets=(16,)) as eng:
        eng.generate(a, max_new_tokens=10)       # dirty the slot
        b_after = eng.generate(b, max_new_tokens=8)
    with GenerationEngine(lm, params, max_slots=1, max_len=48,
                          buckets=(16,)) as fresh:
        assert fresh.generate(b, max_new_tokens=8) == b_after


def test_midstream_overlap(lm48, tele):
    """Continuous batching actually shares decode ticks: 3 sessions of 10
    tokens through 2 slots take FEWER fused ticks than the 27 a
    session-at-a-time engine would need (the third admits into a freed
    slot while the survivors keep decoding)."""
    lm, params = lm48
    prompts = _prompts(3, seed=4)
    slots0 = _counter("serving.generation.tick_slots")
    with GenerationEngine(lm, params, max_slots=2, max_len=48,
                          buckets=(16,)) as eng:
        streams = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for s in streams:
            assert len(s.result(timeout=60)) == 10
        decode_ticks = (_counter("serving.generation.tick_slots")
                        - slots0) // 2
    assert decode_ticks < 27, \
        f"{decode_ticks} fused ticks — no mid-stream sharing happened"


def test_eos_eviction(lm48, tele):
    """A session whose greedy stream hits eos_id stops there (the EOS
    token is delivered), freeing the slot early."""
    lm, params = lm48
    (p,) = _prompts(1, seed=5)
    with GenerationEngine(lm, params, max_slots=2, max_len=48,
                          buckets=(16,)) as eng:
        full = eng.generate(p, max_new_tokens=10)
        # eos must be a token at its FIRST occurrence in the stream, or
        # the earlier duplicate stops the generation sooner
        k = max(i for i, t in enumerate(full) if t not in full[:i])
        evict0 = _counter("serving.generation.evict_eos")
        short = eng.generate(p, max_new_tokens=10, eos_id=full[k])
    assert short == full[:k + 1]
    assert _counter("serving.generation.evict_eos") - evict0 == 1


def test_submit_validation(lm48):
    lm, params = lm48
    with GenerationEngine(lm, params, max_slots=1, max_len=48,
                          buckets=(8,)) as eng:
        with pytest.raises(MXNetError):
            eng.submit(np.zeros(0, np.int32))           # empty
        with pytest.raises(MXNetError):
            eng.submit(np.ones(9, np.int32))            # > largest bucket
        with pytest.raises(MXNetError):
            eng.submit([1, 2], max_new_tokens=47)       # 2+47 > 48
    assert prefill_ladder(None, 48) == (8, 16, 32, 48)
    assert prefill_ladder((64, 4), 48) == (4, 48)


# ---------------------------------------------------------------------------
# warmup / compile discipline
# ---------------------------------------------------------------------------


def test_warmup_compile_pinning(lm48, tele):
    """Exactly len(buckets) prefill compiles + ONE decode compile; a
    second warmup compiles nothing; concurrent ragged traffic afterwards
    causes ZERO new 'generation' cache misses; and the O(1) structure is
    pinned: one decode executable serves every admission pattern and
    every generated length."""
    from mxnet_tpu import compile_cache

    lm, params = lm48
    eng = GenerationEngine(lm, params, max_slots=4, max_len=48,
                           buckets=(8, 16, 32))
    w = serving.warmup(eng)
    assert w["compiles"] == 4                      # 3 prefill + 1 decode
    assert serving.warmup(eng)["compiles"] == 0
    before = compile_cache.named_stats("generation")
    streams = [eng.submit(p, max_new_tokens=4 + (i % 6))
               for i, p in enumerate(_prompts(16, lo=2, hi=30, seed=6))]
    for s in streams:
        s.result(timeout=60)
    after = compile_cache.named_stats("generation")
    assert after["misses"] - before["misses"] == 0, \
        "steady-state generation traffic compiled something"
    assert after["hits"] > before["hits"]
    decode_keys = [k for k in eng.cache.keys() if k[0] == "decode"]
    assert len(decode_keys) == 1
    eng.close()


# ---------------------------------------------------------------------------
# deadlines / backpressure / drain
# ---------------------------------------------------------------------------


def test_deadline_while_queued(lm48, tele):
    """A session expiring in queue fails with DeadlineExceededError at
    the next tick sweep — it never wedges behind the long session holding
    the only slot."""
    lm, params = lm48
    with GenerationEngine(lm, params, max_slots=1, max_len=48,
                          buckets=(16,)) as eng:
        a = eng.submit(_prompts(1, seed=7)[0], max_new_tokens=40)
        b = eng.submit(_prompts(1, seed=8)[0], max_new_tokens=5,
                       timeout=0.001)
        with pytest.raises(DeadlineExceededError):
            b.result(timeout=60)
        with pytest.raises(DeadlineExceededError):
            list(b)
        assert len(a.result(timeout=60)) == 40     # survivor unaffected
    assert _counter("serving.generation.evict_deadline") >= 1


def test_deadline_mid_generation(tele):
    """A LIVE session past its deadline is evicted at the tick sweep: the
    stream raises DeadlineExceededError after the tokens already
    delivered, and the slot frees."""
    lm, params = _model(max_len=256, n_layers=1, d_model=16)
    with GenerationEngine(lm, params, max_slots=1, max_len=256,
                          buckets=(8,)) as eng:
        s = eng.submit([1, 2, 3], max_new_tokens=250, timeout=0.05)
        with pytest.raises(DeadlineExceededError):
            for _ in s:
                pass
        assert 1 <= len(s.tokens) < 250            # partial stream
        deadline = time.monotonic() + 5
        while eng.live_slots and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.live_slots == 0


def test_queue_full_and_manual_drain(lm48):
    """QueueFullError the moment the bound is hit (no worker racing the
    assertion: start=False, ticks driven manually), then close() +
    ServerClosedError for new work."""
    lm, params = lm48
    eng = GenerationEngine(lm, params, max_slots=1, max_len=48,
                           buckets=(8,), max_queue=2, start=False)
    a = eng.submit([1, 2], max_new_tokens=2)
    b = eng.submit([3, 4], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        eng.submit([5, 6], max_new_tokens=2)
    for _ in range(16):
        eng._tick_once()
        if a.done and b.done:
            break
    assert len(a.result(timeout=5)) == 2
    assert len(b.result(timeout=5)) == 2
    eng.close()
    with pytest.raises(ServerClosedError):
        eng.submit([7], max_new_tokens=1)


def test_close_drains(lm48):
    """close() completes every admitted AND queued session before
    returning — shutdown keeps every promise it admitted."""
    lm, params = lm48
    eng = GenerationEngine(lm, params, max_slots=2, max_len=48,
                           buckets=(16,))
    streams = [eng.submit(p, max_new_tokens=6) for p in _prompts(5, seed=9)]
    eng.close()
    for s in streams:
        assert len(s.result(timeout=1)) == 6


def test_prefill_failure_never_strands(lm48, tele):
    """A prefill-executable failure fails the popped session's stream
    in-band (the session is in neither the queue nor a slot when the
    admission forward raises — the tick handler alone would strand it
    forever) and the engine keeps serving afterwards on a fresh slab."""
    lm, params = lm48
    eng = GenerationEngine(lm, params, max_slots=2, max_len=48,
                           buckets=(8,), start=False)

    class Boom(RuntimeError):
        pass

    def bad_prefill(bucket):
        def fn(*a, **k):
            raise Boom("device error")
        return fn

    eng._prefill_fn = bad_prefill
    s = eng.submit([1, 2, 3], max_new_tokens=4)
    eng._tick_once()
    with pytest.raises(Boom):
        s.result(timeout=1)
    with pytest.raises(Boom):
        list(s)
    del eng.__dict__["_prefill_fn"]      # heal; slab was reallocated
    s2 = eng.submit([4, 5], max_new_tokens=3)
    for _ in range(8):
        eng._tick_once()
        if s2.done:
            break
    assert len(s2.result(timeout=5)) == 3
    eng.close()


def test_idle_zero_overhead(lm48, tele):
    """An idle engine ticks ZERO times: the scheduler parks on its
    condition variable, it does not poll."""
    lm, params = lm48
    with GenerationEngine(lm, params, max_slots=2, max_len=48,
                          buckets=(16,)) as eng:
        eng.generate(_prompts(1, seed=10)[0], max_new_tokens=4)
        deadline = time.monotonic() + 5
        while eng._has_work() and time.monotonic() < deadline:
            time.sleep(0.005)
        ticks0 = _counter("serving.generation.ticks")
        time.sleep(0.3)
        assert _counter("serving.generation.ticks") == ticks0


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


def test_router_balance(lm48):
    """24 idle-fleet submissions spread evenly (rotating tie-break), all
    complete, and placement tracks occupancy."""
    lm, params = lm48
    engines = [GenerationEngine(lm, params, max_slots=4, max_len=48,
                                buckets=(16,)) for _ in range(3)]
    with GenerationRouter(engines) as router:
        streams = [router.submit(p, max_new_tokens=5)
                   for p in _prompts(24, seed=11)]
        for s in streams:
            assert len(s.result(timeout=60)) == 5
        counts = [e.sessions_submitted for e in engines]
    assert sum(counts) == 24
    assert all(4 <= c <= 12 for c in counts), counts


def test_router_failover_when_full(lm48):
    """A saturated replica is skipped; only a fully-saturated fleet
    raises QueueFullError."""
    lm, params = lm48
    e1 = GenerationEngine(lm, params, max_slots=1, max_len=48,
                          buckets=(8,), max_queue=1, start=False)
    e2 = GenerationEngine(lm, params, max_slots=1, max_len=48,
                          buckets=(8,), max_queue=1, start=False)
    router = GenerationRouter([e1, e2])
    streams = [router.submit([1, 2], max_new_tokens=2) for _ in range(2)]
    with pytest.raises(QueueFullError):
        router.submit([1, 2], max_new_tokens=2)
    for eng in (e1, e2):
        for _ in range(8):
            eng._tick_once()
    for s in streams:
        assert len(s.result(timeout=5)) == 2
    router.close()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_kv_cache_census(lm48):
    """The slab shows up under the kv_cache census category at its true
    byte size (live-view provider: the arrays are replaced every tick)."""
    lm, params = lm48
    memory.clear()
    try:
        with GenerationEngine(lm, params, max_slots=2, max_len=32,
                              buckets=(8,)) as eng:
            eng.generate([1, 2, 3], max_new_tokens=3)
            snap = memory.census(update=False)
            assert snap["categories"]["kv_cache"]["total"] == \
                eng.kv_slab_bytes()
            assert snap["categories"]["kv_cache"]["buffers"] == 2
    finally:
        memory.clear()


def test_generation_telemetry_and_report(lm48, tele, tmp_path, capsys):
    """serving.generation.* metrics populate (tokens, TTFT, fill ratio
    derived) and tools/telemetry_report.py renders the generation
    summary line."""
    lm, params = lm48
    tok0 = _counter("serving.generation.tokens")
    with GenerationEngine(lm, params, max_slots=2, max_len=48,
                          buckets=(16,)) as eng:
        streams = [eng.submit(p, max_new_tokens=4)
                   for p in _prompts(6, seed=12)]
        for s in streams:
            s.result(timeout=60)
    assert _counter("serving.generation.tokens") - tok0 == 24
    snap = telemetry.snapshot()
    assert snap["histograms"]["serving.generation.ttft_us"]["count"] >= 6
    assert 0 < snap["derived"]["serving.generation.slot_fill_ratio"] <= 1
    path = tmp_path / "telemetry.json"
    path.write_text(json.dumps(snap))
    from tools import telemetry_report

    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "generation:" in out and "TTFT" in out


# ---------------------------------------------------------------------------
# acceptance: 1k concurrent ragged streaming sessions
# ---------------------------------------------------------------------------


def test_tick_span_tree(lm48):
    """A tick that decodes a live session and admits a queued one is ONE
    live span tree: tick > sweep, decode, admit > prefill > prefill.fetch,
    commit > commit.fetch, in the order the tick did them. Nothing is
    emitted per session per tick (generation.decode_tick is gone); the
    session root carries its token count instead."""
    lm, params = lm48
    a, b = _prompts(2, seed=6)
    prev = tracing.enabled()
    tracing.enable()
    tracing.reset()
    try:
        with GenerationEngine(lm, params, max_slots=2, max_len=48,
                              buckets=(16,)) as eng:
            sa = eng.submit(a, max_new_tokens=30)
            next(iter(sa))                  # A is live and decoding
            sb = eng.submit(b, max_new_tokens=4)
            assert len(sa.result(timeout=60)) == 30
            assert len(sb.result(timeout=60)) == 4
        spans = [e for e in tracing.peek_events() if e.get("ph") == "X"]
        worst = tracing.tick_recorder.worst()
    finally:
        tracing.reset()
        tracing.enable(prev)
    assert not [e for e in spans if e["name"] == "generation.decode_tick"]

    def kids(parent):
        return sorted((e for e in spans if e["args"].get("parent_id")
                       == parent["args"]["span_id"]), key=lambda e: e["ts"])

    def names(events):
        return [e["name"] for e in events]

    ticks = [e for e in spans if e["name"] == "generation.tick"]
    both = [t for t in ticks
            if "generation.decode" in names(kids(t))
            and any(names(kids(k)) == ["generation.prefill"]
                    for k in kids(t) if k["name"] == "generation.admit")]
    assert both, [names(kids(t)) for t in ticks]
    tick = both[0]
    assert names(kids(tick)) == ["generation.decode", "generation.sweep",
                                 "generation.admit", "generation.commit"]
    by = {e["name"]: e for e in kids(tick)}
    (prefill,) = kids(by["generation.admit"])
    assert {"bucket", "slot", "waited_us"} <= set(prefill["args"])
    assert prefill["args"]["bucket"] == 16 and prefill["args"]["waited_us"] >= 0
    assert names(kids(prefill)) == ["generation.prefill.fetch"]
    assert names(kids(by["generation.commit"])) == ["generation.commit.fetch"]
    assert by["generation.commit"]["args"]["kind"] == "plain"
    # every tick: a handful of spans, whatever the number of live sessions
    assert max(len(kids(t)) for t in ticks) <= 4
    assert worst is not None and worst["name"] == "generation.tick"
    # the per-session trees: queued + prefill + evict under a root that
    # carries the session's token count
    roots = [e for e in spans if e["name"] == "generation.session"]
    assert sorted(r["args"]["tokens"] for r in roots) == [4, 30]
    for r in roots:
        assert names(kids(r)) == ["generation.queued", "generation.prefill",
                                  "generation.evict"]


def test_queue_wait_recorded_once_per_prefill(lm48, tele):
    """serving.generation.queue_wait_us (submit -> start of the admission)
    is recorded beside the prefills counter: same count, and a session
    that waited for a slot shows a longer wait than one that did not."""
    lm, params = lm48
    hist = telemetry.histogram("serving.generation.queue_wait_us")
    n0, p0 = hist.count, _counter("serving.generation.prefills")
    with GenerationEngine(lm, params, max_slots=1, max_len=48,
                          buckets=(16,)) as eng:
        streams = [eng.submit(p, max_new_tokens=6)
                   for p in _prompts(5, seed=7)]
        for s in streams:
            assert len(s.result(timeout=60)) == 6
    assert _counter("serving.generation.prefills") - p0 == 5
    assert hist.count - n0 == 5
    assert hist.snapshot()["max"] > 0


def test_1k_sessions_acceptance(tele):
    """1000 ragged-length streaming sessions through one 16-slot engine:
    all complete, zero steady-state compiles, sampled sessions bit-exact
    vs sequential decode, and the decode stays ONE executable (the O(1)
    structural pin) throughout."""
    lm, params = _model(max_len=32, n_layers=1, d_model=16, vocab=32)
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 32, rng.randint(2, 14)).astype(np.int32)
               for _ in range(1000)]
    budgets = [int(rng.randint(3, 12)) for _ in range(1000)]
    eng = GenerationEngine(lm, params, max_slots=16, max_len=32,
                           buckets=(8, 16))
    serving.warmup(eng)
    m0 = eng.cache.misses
    streams = [None] * 1000
    errors = []

    def submitter(lo, hi):
        try:
            for i in range(lo, hi):
                while True:
                    try:
                        streams[i] = eng.submit(prompts[i],
                                                max_new_tokens=budgets[i])
                        break
                    except QueueFullError:
                        time.sleep(0.002)   # backpressure: retry later
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submitter, args=(k * 125, (k + 1) * 125))
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    results = [s.result(timeout=120) for s in streams]
    assert all(len(r) == b for r, b in zip(results, budgets))
    assert eng.cache.misses - m0 == 0, "1k-session run compiled mid-stream"
    assert len([k for k in eng.cache.keys() if k[0] == "decode"]) == 1
    eng.close()
    with GenerationEngine(lm, params, max_slots=16, max_len=32,
                          buckets=(8, 16)) as ref:
        for i in range(0, 1000, 111):     # sampled sequential parity
            assert ref.generate(prompts[i],
                                max_new_tokens=budgets[i]) == results[i]


# ---------------------------------------------------------------------------
# the names the benchmark takes from the program (benchmark/serve_programs.py
# and the serving runners' telemetry tuples) that no other test holds
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_programs(lm48):
    """`{kind: lowered module text}` for every program kind the engine
    compiles: a plain engine warms prefill and decode, one with the prefix
    cache and speculation on warms fork, suffix_prefill and verify."""
    lm, params = lm48
    found = {}
    for features in ({}, {"prefix_cache": True, "spec_k": 2}):
        eng = GenerationEngine(lm, params, max_slots=2, max_len=48,
                               buckets=(8,), start=False, **features)
        eng.warm()
        for key in eng.cache.keys():
            args, kwargs = eng.cache._entry_stats[key]["avals"]
            jitted = eng.cache._entries[key]._fn
            found[key[0]] = jitted.lower(*args, **kwargs).as_text()
        eng.close()
    return found


@pytest.mark.parametrize("kind", ["prefill", "decode", "fork",
                                  "suffix_prefill", "verify"])
def test_engine_program_is_named_jit_fn(engine_programs, kind):
    """A device trace shows an execution under its module's name, and
    `benchmark/serve_programs.py` finds the engine's programs by the one
    pattern `^jit_fn\\(`: a builder whose inner function is renamed would
    drop out of `decode_ms_p50`, `prefill_ms_p50`,
    `prefill_share_of_tick_pct` and the roofline shares without an error
    (PERF.md section 7 has the order in which a rename can be made)."""
    assert engine_programs[kind].startswith("module @jit_fn ")


@pytest.fixture(scope="module")
def tiny_run_telemetry(lm48):
    """Counter and histogram movements of 5 requests of 6 tokens each
    through a 2-slot engine, telemetry on."""
    lm, params = lm48
    pre = "serving.generation."
    counters = ("ticks", "tick_slots", "decode_tokens", "prefills", "tokens")
    histograms = ("tick_us", "prefill_us")

    def mark():
        m = {c: telemetry.counter(pre + c).value for c in counters}
        for h in histograms:
            snap = telemetry.histogram(pre + h).snapshot()
            m[h + ".count"], m[h + ".sum"] = snap["count"], snap["sum"]
        return m

    prev = telemetry.enabled()
    telemetry.enable()
    try:
        before = mark()
        with GenerationEngine(lm, params, max_slots=2, max_len=48,
                              buckets=(16,)) as eng:
            streams = [eng.submit(p, max_new_tokens=6)
                       for p in _prompts(5, seed=21)]
            for s in streams:
                assert len(s.result(timeout=60)) == 6
        after = mark()
    finally:
        telemetry.enable(prev)
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("name", ["decode_tokens", "tick_us", "prefill_us"])
def test_benchmark_telemetry_name_moves(tiny_run_telemetry, name):
    """`batch_occupancy_pct` is `decode_tokens` over `tick_slots`, and the
    runners difference `tick_us` and `prefill_us` over the window."""
    moved = tiny_run_telemetry
    assert moved["tokens"] == 30 and moved["prefills"] == 5
    if name == "decode_tokens":
        # a prefill yields a request's first token, the decodes the rest;
        # no decode is counted for a slot that was swept dead
        assert moved["decode_tokens"] == moved["tokens"] - moved["prefills"]
        assert moved["decode_tokens"] <= moved["tick_slots"]
    elif name == "tick_us":
        assert moved["tick_us.count"] == moved["ticks"] > 0
        assert moved["tick_us.sum"] > 0
    else:
        assert moved["prefill_us.count"] == moved["prefills"]
        assert moved["prefill_us.sum"] > 0
