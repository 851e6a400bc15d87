#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

Drives the two user-facing main paths once, through the entry points a user
calls, at the full width of the models the repo supports, in ONE process:

* train — ResNet-50 v1, batch 32, 3x224x224, 1000 classes: symbolic
  `Module.fused_step` in fp32, then hybridized gluon + `Trainer`
  (`multi_precision`) in bfloat16;
* serve — `TransformerLM` at GPT-2-small width behind a `GenerationEngine`
  (continuous batching, prefix cache), 4 client threads x 3 streamed prompts,
  plus the Pallas flash kernel in the compiled full forward; then a small
  `HybridLM` (one period of the Granite 4.0-H pattern: 9 Mamba-2 layers and a
  grouped-query attention layer) behind the same engine, whose cache holds a
  recurrent state beside the K/V slab.

`--multichip` runs ONLY the four-device phase and what it is compared with
(transformer train step on sp=2,tp=2 and dp=4 meshes vs one device; the
`Module` fused step under `MXNET_SPMD` vs one device).

It refuses to start (non-zero exit, nothing built) unless
`jax.devices()[0].platform == "tpu"`; any phase that fails raises, so the exit
code is non-zero; the LAST line of stdout is one JSON object
`{"ok": true, "device": {"platform", "kind", "count"}}` and everything else
worth knowing (versions, cache directory, native runtime, per-phase compile
and steady seconds, which attention path each program contains) is printed on
earlier lines. Weights and data come from `--seed`.
"""
import argparse
import contextlib
import json
import os
import sys
import threading
import time

import numpy as np

# Sizes. The defaults are the real ones; tests/python/unittest/test_chip_smoke.py
# shrinks them (and steers the platform check and the device context) from the
# test, so the control flow runs on CPU — there is no option for that here.
REQUIRED_PLATFORM = "tpu"
RESNET = dict(batch=32, size=224, classes=1000, steps=6, multichip_batch=128,
              multichip_steps=3)
# GPT-2 small (the block TransformerLM implements). Vocab 50304, not the
# published 50257: the tp mesh of --multichip shards the embedding's vocab dim
# (`param_specs`: embed over 'tp'), an odd 50257 does not divide by 2, and one
# model serves both phases. 50304 = 393 x 128, the usual GPT-2 padding.
LM = dict(vocab_size=50304, d_model=768, n_heads=12, d_ff=3072, n_layers=12,
          max_len=1024, dtype="bfloat16")
SERVE = dict(max_slots=8, buckets=(64, 256, 1024), clients=4,
             prompts_per_client=3, min_prompt=16, max_prompt=900,
             shared_prefix=256, max_new_tokens=32)
# One period of the granite-4.0-h pattern at a small width: head sizes, state
# size and chunk as published (64, 128, 256), so both kernels take their case.
HYBRID = dict(vocab_size=50304, hidden_size=512, shared_intermediate_size=2048,
              layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
              num_attention_heads=8, num_key_value_heads=2,
              attention_multiplier=0.015625, mamba_n_heads=16, mamba_d_head=64,
              mamba_d_state=128, mamba_chunk_size=256, max_len=1024,
              dtype="bfloat16")
HYBRID_SERVE = dict(max_slots=4, buckets=(64, 256), prompts=(20, 64, 150, 255,
                                                             90, 33),
                    max_new_tokens=16)
LM_TRAIN = dict(batch=8, seq=1024, steps=2)
# SGD(momentum 0.9, wd 1e-4) on ONE repeated batch: half the usual
# 0.1 x batch/256 so the loss falls step over step in bfloat16 too.
LEARNING_RATE = 0.005

# Tolerances, with their reasons.
#
# fp32 ResNet-50 logits, TPU vs the same traced function on the host CPU: the
# TPU's DEFAULT matmul/conv precision rounds both multiplicands to bfloat16
# (8-bit mantissa, 2^-9 relative rounding each) and accumulates in fp32. Over
# the 53 convolutions of ResNet-50, re-normalised by BatchNorm, the rounding
# errors add like a random walk: a typical logit is off by ~sqrt(53) * 2^-9 =
# 1.4e-2 of the logit scale, and the worst of 32,000 logits by ~4x that.
# Measured on the v5e: 5.3e-2 (and 3.8e-5 at highest precision).
RESNET_LOGIT_RTOL = 1e-1
# The same forward under jax.default_matmul_precision("highest") must agree
# with the CPU to fp32 reassociation noise: it separates "precision" from
# "wrong program".
RESNET_LOGIT_RTOL_HIGHEST = 1e-3
# bf16 LM logits from two evaluation orders of a 12-layer bf16 network (decode
# path vs full forward; flash kernel vs XLA attention): bf16 keeps 8 bits, and
# the residual stream is re-rounded after every one of ~50 ops, so logits agree
# to about 2^-5 of their scale. A greedy token may differ from the reference
# argmax only where the reference's top-2 gap is below this.
LM_LOGIT_RTOL = 2 ** -5
# Loss of the same train step on different meshes (different reduction
# orders, bf16 compute for the LM; TPU default precision for ResNet).
LOSS_RTOL = 2e-2


def log(msg):
    print(msg, flush=True)


@contextlib.contextmanager
def env_var(name, value):
    """`name` set to `value` (None: unset) for the block — the repo's gates
    (MXNET_SPMD, MXNET_PALLAS_ATTENTION) are read from the environment."""
    prev = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev


def require_platform():
    """Refuse to run — before any model is built — unless jax found the
    accelerator. Returns jax.devices()."""
    import jax

    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        raise SystemExit(
            f"chip_smoke.py: needs a {REQUIRED_PLATFORM} device, jax found "
            f"{devs[0].platform} ({devs}); nothing was run")
    return devs


def device_context(i=0):
    import mxnet_tpu as mx

    return mx.tpu(i)


def resnet_symbol(classes):
    """ResNet-50 v1 as a Symbol (imagenet stem); its logits are `fc1`."""
    from mxnet_tpu.models.resnet import resnet50_symbol

    return resnet50_symbol(num_classes=classes, image_shape=(3, 224, 224))


def resnet_gluon(classes):
    from mxnet_tpu.gluon.model_zoo import vision

    return vision.resnet50_v1(classes=classes)


def assert_kernel_in(text, what):
    """A compiled program that should hold the Pallas kernel and does not is
    a failure, not a fallback."""
    assert "tpu_custom_call" in text, (
        f"{what}: no tpu_custom_call in the compiled program — the Pallas "
        f"kernel is absent")


# ---------------------------------------------------------------------------
# accounting: the repo's CompileCache ledger + jax's own compile/cache events
# ---------------------------------------------------------------------------

class CompileEvents:
    """Counts what jax itself did: XLA backend compiles (with seconds) and
    persistent-cache hits/misses, so a second process on the same cache
    directory shows its reads."""

    def __init__(self):
        from jax import monitoring

        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1
            self.backend_compile_s += duration

    def snapshot(self):
        return (self.backend_compiles, self.backend_compile_s,
                self.cache_hits, self.cache_misses)

    def report(self, name, since):
        n, s, h, m = (a - b for a, b in zip(self.snapshot(), since))
        log(f"[{name}] jax: {n} XLA compiles/loads {s:.1f}s; persistent "
            f"cache hits={h} misses={m}")


def compile_mark(events):
    """(CompileCache misses over every cache name, XLA compiles or cache
    loads, their seconds) so far."""
    from mxnet_tpu import compile_cache

    return (sum(t["misses"] for t in compile_cache.name_totals().values()),
            events.backend_compiles, events.backend_compile_s)


def check_steps(name, losses, secs, marks):
    """Loss finite and lower at the last step than at the first; nothing
    compiled after step 2 — neither a CompileCache miss nor an XLA compile
    the ledger does not see (jax re-lowers a jitted step whose arguments
    change from uncommitted to committed). `marks[i]` is compile_mark()
    before step i. Returns the per-step line for the log."""
    assert all(np.isfinite(losses)), f"{name}: non-finite loss {losses}"
    assert losses[-1] < losses[0], f"{name}: loss did not fall: {losses}"
    assert marks[-1][:2] == marks[2][:2], (
        f"{name}: compiled after step 2 (misses, XLA compiles, seconds per "
        f"step boundary): {marks}")
    per_step = [f"{sec:.3f}s/{b[1] - a[1]}c{b[2] - a[2]:.0f}s"
                for sec, a, b in zip(secs, marks, marks[1:])]
    return ("per step (wall / XLA compiles / compile seconds): "
            + " ".join(per_step))


def report_programs(phase, seen):
    """Per-program first-call seconds (trace + compile, or a cache read) of
    every CompileCache entry not reported yet and worth a line (>= 1 s)."""
    from mxnet_tpu import compile_cache

    for cache in compile_cache.all_caches():
        for key, secs in list(cache.first_call_seconds.items()):
            ident = (id(cache), repr(key))
            if ident in seen:
                continue
            seen.add(ident)
            if secs >= 1.0:
                label = key[0] if isinstance(key, tuple) and key and \
                    isinstance(key[0], str) else "program"
                detail = "/".join(str(k) for k in key[1:4]
                                  if isinstance(k, (int, str)))
                log(f"[{phase}] compiled {cache.name}:{label}"
                    f"{'/' + detail if detail else ''} first_call_s={secs:.1f}")


def softmax_xent(probs, labels):
    p = np.asarray(probs, np.float64)
    idx = np.asarray(labels).astype(np.int64)
    return float(-np.log(np.maximum(p[np.arange(len(idx)), idx], 1e-30)).mean())


# ---------------------------------------------------------------------------
# train: ResNet-50 through Module.fused_step (fp32)
# ---------------------------------------------------------------------------

def resnet_batch(seed, batch, size, classes):
    rng = np.random.RandomState(seed)
    data = rng.uniform(-1, 1, (batch, 3, size, size)).astype(np.float32)
    label = rng.randint(0, classes, (batch,)).astype(np.float32)
    return data, label


def build_module(ctx, batch, size, classes, seed):
    import mxnet_tpu as mx

    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(resnet_symbol(classes), context=ctx)
    mod.bind(data_shapes=[("data", (batch, 3, size, size))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", LEARNING_RATE),
                                         ("momentum", 0.9), ("wd", 1e-4)))
    return mod


def module_steps(mod, batch_nd, label_np, steps, name, events):
    """`steps` fused steps on one repeated batch. Returns (losses, seconds per
    step, the per-step line of check_steps)."""
    losses, secs, marks = [], [], [compile_mark(events)]
    for i in range(steps):
        t0 = time.perf_counter()
        took = mod.fused_step(batch_nd)
        assert took is True, (
            f"{name}: Module.fused_step returned {took!r} at step {i} — the "
            f"eager path would have run in silence")
        probs = mod.get_outputs()[0].asnumpy()  # host fetch: the step is done
        secs.append(time.perf_counter() - t0)
        losses.append(softmax_xent(probs, label_np))
        marks.append(compile_mark(events))
    return losses, secs, check_steps(name, losses, secs, marks)


def check_logits_vs_cpu(mod, data, name):
    """First-batch fp32 logits: the Module's own graph function, traced once,
    run on the accelerator and on jax.devices("cpu")[0]."""
    import jax
    from mxnet_tpu.symbol.executor import _graph_fn

    logits_sym = mod._symbol.get_internals()["fc1_output"]
    arg_names = logits_sym.list_arguments()
    aux_names = logits_sym.list_auxiliary_states()
    fn = _graph_fn(logits_sym, arg_names, aux_names, True)
    ex = mod._exec
    args = tuple(jax.numpy.asarray(data) if n == "data" else ex.arg_dict[n]._data
                 for n in arg_names)
    auxs = tuple(ex.aux_dict[n]._data for n in aux_names)
    key = jax.random.PRNGKey(0)
    dev = args[1].devices().pop()
    cpu = jax.devices("cpu")[0]

    def run(target, precision=None):
        placed = jax.device_put((key, args, auxs), target)
        with jax.default_matmul_precision(precision) if precision else \
                contextlib.nullcontext():
            out, _ = jax.jit(fn)(*placed)
        return np.asarray(out[0], np.float64)

    ref = run(cpu)
    scale = np.abs(ref).max()
    assert np.isfinite(ref).all() and scale > 0
    got = run(dev)
    assert got.shape == ref.shape and np.isfinite(got).all()
    err = np.abs(got - ref).max() / scale
    hi = np.abs(run(dev, "highest") - ref).max() / scale
    log(f"[{name}] fp32 logits {got.shape} vs host-CPU reference: max|d|/max|ref| "
        f"default precision {err:.2e} (tol {RESNET_LOGIT_RTOL:.0e}), "
        f"highest precision {hi:.2e} (tol {RESNET_LOGIT_RTOL_HIGHEST:.0e})")
    assert err <= RESNET_LOGIT_RTOL, f"{name}: logits off by {err:.3e}"
    assert hi <= RESNET_LOGIT_RTOL_HIGHEST, (
        f"{name}: logits off by {hi:.3e} even at highest precision — not a "
        f"precision effect")


def phase_train_module(seed, events, seen):
    import jax
    import mxnet_tpu as mx

    name = "train/module-fp32"
    ev0 = events.snapshot()
    t_phase = time.perf_counter()
    ctx = device_context(0)
    dev = ctx.jax_device
    batch, size, classes = RESNET["batch"], RESNET["size"], RESNET["classes"]
    data, label = resnet_batch(seed, batch, size, classes)
    mod = build_module(ctx, batch, size, classes, seed)
    check_logits_vs_cpu(mod, data, name)
    b = mx.io.DataBatch([mx.nd.array(data, ctx=ctx)],
                        [mx.nd.array(label, ctx=ctx)])
    losses, secs, per_step = module_steps(mod, b, label, RESNET["steps"],
                                          name, events)
    for n in mod._param_names:
        devs = mod._exec.arg_dict[n]._data.devices()
        assert devs == {dev}, f"{name}: parameter {n} on {devs}, not {dev}"
    report_programs(name, seen)
    log(f"[{name}] ResNet-50 b={batch} {size}x{size} Module.fused_step x"
        f"{len(losses)}: loss {losses[0]:.4f} -> {losses[-1]:.4f}; first step "
        f"{secs[0]:.1f}s (compile), steady {np.median(secs[2:]):.4f}s/step "
        f"(smoke timing, host fetch per step); "
        f"{len(mod._param_names)} params on {dev}")
    log(f"[{name}] {per_step}")
    timing_sanity(mod, b, name)
    events.report(name, ev0)
    log(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")


def timing_sanity(mod, batch_nd, name, n=5):
    """Printed, not asserted on: does block_until_ready block here? Wall time
    of n fused steps to dispatch-return, to block_until_ready, and to a host
    value fetch. If block_until_ready blocks, the last two agree and the
    first is smaller."""
    import jax

    first = mod._param_names[0]

    def run():
        for _ in range(n):
            assert mod.fused_step(batch_nd) is True
        return mod._exec.arg_dict[first]._data

    jax.device_get(run())
    t0 = time.perf_counter()
    w = run()
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(w)
    t_block = time.perf_counter() - t0
    jax.device_get(w)
    t_after = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.device_get(run())
    t_fetch = time.perf_counter() - t0
    log(f"[{name}] timing sanity, {n} fused steps: dispatch returned "
        f"{t_dispatch:.4f}s, block_until_ready {t_block:.4f}s, +host fetch "
        f"{t_after:.4f}s; separate loop to host fetch {t_fetch:.4f}s")


# ---------------------------------------------------------------------------
# train: ResNet-50 through gluon + Trainer (bfloat16, multi_precision)
# ---------------------------------------------------------------------------

def phase_train_gluon(seed, events, seen):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer, loss as gloss

    name = "train/gluon-bf16"
    ev0 = events.snapshot()
    t_phase = time.perf_counter()
    ctx = device_context(0)
    dev = ctx.jax_device
    batch, size, classes = RESNET["batch"], RESNET["size"], RESNET["classes"]
    data, label = resnet_batch(seed, batch, size, classes)
    mx.random.seed(seed)
    net = resnet_gluon(classes)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize(static_alloc=True)
    net.cast("bfloat16")
    sce = gloss.SoftmaxCrossEntropyLoss()
    sce.hybridize()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": LEARNING_RATE, "momentum": 0.9,
                       "wd": 1e-4, "multi_precision": True})
    x = mx.nd.array(data, ctx=ctx).astype("bfloat16")
    y = mx.nd.array(label, ctx=ctx)
    losses, secs, marks = [], [], [compile_mark(events)]
    for _ in range(RESNET["steps"]):
        t0 = time.perf_counter()
        with autograd.record():
            loss = sce(net(x), y)
        loss.backward()
        trainer.step(batch)
        losses.append(float(loss.asnumpy().astype(np.float64).mean()))
        secs.append(time.perf_counter() - t0)
        marks.append(compile_mark(events))
    per_step = check_steps(name, losses, secs, marks)
    params = list(net.collect_params().values())
    for p in params:
        arr = p.data()._data
        assert arr.devices() == {dev}, (
            f"{name}: parameter {p.name} on {arr.devices()}, not {dev}")
    assert any(str(p.data().dtype) == "bfloat16" for p in params)
    report_programs(name, seen)
    log(f"[{name}] ResNet-50 b={batch} {size}x{size} hybridized "
        f"record/backward/Trainer.step x{len(losses)}: loss {losses[0]:.4f} "
        f"-> {losses[-1]:.4f}; first step {secs[0]:.1f}s (compile), steady "
        f"{np.median(secs[2:]):.4f}s/step (smoke timing, host fetch per "
        f"step); {len(params)} params on {dev}")
    log(f"[{name}] {per_step}")
    events.report(name, ev0)
    log(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")


# ---------------------------------------------------------------------------
# serve: GPT-2-small TransformerLM behind the GenerationEngine
# ---------------------------------------------------------------------------

def attention_path(jitted, avals):
    """Which attention a jitted program contains, from its lowered text (no
    compile): the Pallas kernel lowers to a tpu_custom_call."""
    args, kwargs = avals
    text = jitted.lower(*args, **kwargs).as_text()
    return "pallas" if "tpu_custom_call" in text else "xla"


def make_prompts(seed, vocab):
    """clients x prompts_per_client prompts of min..max tokens; client 0's
    first two share a `shared_prefix`-token prefix."""
    rng = np.random.RandomState(seed)
    s = SERVE
    prompts = [[rng.randint(0, vocab, (int(rng.randint(
        s["min_prompt"], s["max_prompt"] + 1)),)).astype(np.int32)
        for _ in range(s["prompts_per_client"])] for _ in range(s["clients"])]
    prefix = rng.randint(0, vocab, (s["shared_prefix"],)).astype(np.int32)
    tail = max(s["min_prompt"], s["buckets"][0] // 2)
    for j in (0, 1):
        prompts[0][j] = np.concatenate(
            [prefix, rng.randint(0, vocab, (tail + 8 * j,)).astype(np.int32)])
    # the ends of the range are always exercised
    prompts[1][0] = prompts[1][0][:s["min_prompt"]]
    prompts[2][0] = rng.randint(0, vocab, (s["max_prompt"],)).astype(np.int32)
    return prompts


def phase_serve(seed, events, seen):
    import jax

    from mxnet_tpu import compile_cache, parallel as par, serving
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig
    from mxnet_tpu.serving import GenerationEngine

    name = "serve/gpt2-small"
    ev0 = events.snapshot()
    t_phase = time.perf_counter()
    dev = device_context(0).jax_device
    mesh = par.create_mesh(devices=[dev], dp=1)
    cfg = TransformerLMConfig(**LM)
    lm = TransformerLM(cfg, mesh)
    params = lm.init_params(jax.random.PRNGKey(seed))
    for k, v in params.items():
        assert v.devices() == {dev}, f"{name}: {k} on {v.devices()}"
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    s = SERVE
    eng = GenerationEngine(lm, params, max_slots=s["max_slots"],
                           max_len=cfg.max_len, buckets=s["buckets"],
                           prefix_cache=True)
    try:
        t0 = time.perf_counter()
        warm = serving.warmup(eng)
        t_warm = time.perf_counter() - t0
        log(f"[{name}] {n_params / 1e6:.1f}M params {cfg.dtype}, vocab "
            f"{cfg.vocab_size}; engine slots={s['max_slots']} buckets="
            f"{list(eng.prefill_buckets)} prefix_cache=on; warmup compiled "
            f"{warm['compiles']} programs in {t_warm:.1f}s")
        report_programs(name, seen)
        for key in eng.cache.keys():
            st = eng.cache._entry_stats.get(key)
            if st is not None:
                path = attention_path(eng.cache._entries[key]._fn, st["avals"])
                log(f"[{name}] program generation:{'/'.join(map(str, key))} "
                    f"attention={path}")
                if key[0] == "decode":
                    # the decode holds the slab kernel exactly when the
                    # model's shape test says so (on the chip: yes, block
                    # logged); the greedy parity below is its on-chip check
                    block = eng._slab_block
                    log(f"[{name}] decode slab access: "
                        f"{'kernel, block ' + str(block) if block else 'xla'}")
                    assert path == ("pallas" if block else "xla"), (
                        f"{name}: decode program holds attention={path}, "
                        f"the model's decode_block says {block}")
        misses0 = compile_cache.named_stats("generation")["misses"]
        xla0 = events.backend_compiles

        prompts = make_prompts(seed, cfg.vocab_size)
        results = [[None] * s["prompts_per_client"] for _ in prompts]
        errors = []

        # Client 0 sends the shared-prefix pair before the others start:
        # under concurrent traffic another session's cache insert can take
        # the last free slot and the next admission then evicts the LRU
        # entry — which may be the first prompt's — so the hit would depend
        # on thread timing. The remaining ten prompts run concurrently.
        pair_done = threading.Event()

        def client(ci):
            try:
                if ci:
                    pair_done.wait(timeout=600)
                for pi, prompt in enumerate(prompts[ci]):
                    if ci == 0 and pi == 2:
                        pair_done.set()
                    t_sub = time.perf_counter()
                    stream = eng.submit(prompt,
                                        max_new_tokens=s["max_new_tokens"])
                    toks, ttft = [], None
                    for tok in stream:  # streamed, token by token
                        if ttft is None:
                            ttft = time.perf_counter() - t_sub
                        toks.append(int(tok))
                    results[ci][pi] = dict(
                        tokens=toks, ttft=ttft,
                        total=time.perf_counter() - t_sub,
                        cached_prefix=stream.cached_prefix_len)
            except BaseException as e:  # noqa: BLE001 — re-raised in main
                errors.append(e)
            finally:
                pair_done.set()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(s["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
            assert not t.is_alive(), f"{name}: a client thread hung"
        t_traffic = time.perf_counter() - t0
        if errors:
            raise errors[0]
        flat = [r for per in results for r in per]
        for r in flat:
            assert r is not None and len(r["tokens"]) == s["max_new_tokens"], (
                f"{name}: a stream returned "
                f"{None if r is None else len(r['tokens'])} tokens")
            assert all(0 <= t < cfg.vocab_size for t in r["tokens"])
        misses1 = compile_cache.named_stats("generation")["misses"]
        assert misses1 == misses0, (
            f"{name}: {misses1 - misses0} compiles after warm-up")
        hit = results[0][1]["cached_prefix"]
        assert hit >= eng._prefix_min, (
            f"{name}: the shared {s['shared_prefix']}-token prefix did not "
            f"register a prefix-cache hit (cached_prefix_len={hit})")
        n_tok = sum(len(r["tokens"]) for r in flat)
        log(f"[{name}] {len(flat)} streamed requests "
            f"({min(len(p) for per in prompts for p in per)}-"
            f"{max(len(p) for per in prompts for p in per)} prompt tokens) "
            f"x {s['max_new_tokens']} new tokens from {s['clients']} client "
            f"threads in {t_traffic:.2f}s ({n_tok} tokens); median TTFT "
            f"{np.median([r['ttft'] for r in flat]):.4f}s (smoke timing); "
            f"compiles after warm-up: engine {misses1 - misses0}, XLA "
            f"{events.backend_compiles - xla0}; prefix-cache hit: "
            f"{hit} of {len(prompts[0][1])} prompt tokens forked")

        greedy_parity(name, lm, params, prompts[1][1], results[1][1]["tokens"],
                      cfg)
    finally:
        eng.close()
    events.report(name, ev0)
    log(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")


def phase_serve_hybrid(seed, events, seen):
    """A small hybrid model through the same engine: more requests than
    slots, zero compiles after warm-up, which path the decode program holds
    for each kind of state, and greedy parity with the plain re-forward."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import compile_cache, parallel as par, serving
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    from mxnet_tpu.serving import GenerationEngine

    name = "serve/hybrid"
    ev0 = events.snapshot()
    t_phase = time.perf_counter()
    dev = device_context(0).jax_device
    cfg = HybridLMConfig(**HYBRID)
    lm = HybridLM(cfg, par.create_mesh(devices=[dev], dp=1))
    params = jax.jit(lm.init_params)(jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    s = HYBRID_SERVE
    eng = GenerationEngine(lm, params, max_slots=s["max_slots"],
                           max_len=cfg.max_len, buckets=s["buckets"],
                           prefix_cache=False, spec_k=0)
    try:
        warm = serving.warmup(eng)
        kv, _, ssm, _ = eng._kv
        block = eng._slab_block
        state_kernel = lm.state_kernel(ssm.shape, ssm.dtype)
        log(f"[{name}] {n_params / 1e6:.1f}M params {cfg.dtype}, "
            f"{lm.n_recurrent} mamba + {lm.n_attention} attention layers; cache "
            f"K/V {tuple(kv.shape)} state {tuple(ssm.shape)} "
            f"{eng.kv_slab_bytes() / 2**20:.0f} MiB; warmup compiled "
            f"{warm['compiles']} programs in {warm['seconds']:.1f}s")
        log(f"[{name}] decode K/V access: "
            f"{'kernel, block ' + str(block) if block else 'xla'}; "
            f"state update: {'kernel' if state_kernel else 'xla'}")
        report_programs(name, seen)
        key = next(k for k in eng.cache.keys() if k[0] == "decode")
        args, kwargs = eng.cache._entry_stats[key]["avals"]
        text = eng.cache._entries[key]._fn.lower(*args, **kwargs).as_text()
        if block or state_kernel:
            assert_kernel_in(text, f"{name}: decode program")
        misses0 = compile_cache.named_stats("generation")["misses"]
        rng = np.random.RandomState(seed)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in s["prompts"]]
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=s["max_new_tokens"])
                   for p in prompts]
        got = [st.result(timeout=600) for st in streams]
        t_traffic = time.perf_counter() - t0
        misses1 = compile_cache.named_stats("generation")["misses"]
        assert misses1 == misses0, (
            f"{name}: {misses1 - misses0} compiles after warm-up")
        log(f"[{name}] {len(got)} streamed requests over {s['max_slots']} "
            f"slots ({min(s['prompts'])}-{max(s['prompts'])} prompt tokens) x"
            f" {s['max_new_tokens']} new tokens in {t_traffic:.2f}s; "
            f"compiles after warm-up: engine {misses1 - misses0}")
        # greedy parity with the plain re-forward, teacher-forced
        worst, exact, total = 0.0, 0, 0
        for p, g in zip(prompts, got):
            seq = np.concatenate([p, g[:-1]])
            rows = np.asarray(jax.jit(lm.forward)(
                params, jnp.asarray(seq[None])))[0, len(p) - 1:]
            scale = np.abs(rows).max()
            gaps = rows.max(-1) - rows[np.arange(len(g)), np.asarray(g)]
            worst = max(worst, float(gaps.max() / scale))
            exact += int((rows.argmax(-1) == np.asarray(g)).sum())
            total += len(g)
        log(f"[{name}] greedy parity: {exact}/{total} tokens equal the "
            f"re-forward argmax; worst logit gap of a generated token "
            f"{worst:.2e} of max|logit| (tol {LM_LOGIT_RTOL:.1e})")
        assert worst <= LM_LOGIT_RTOL, (
            f"{name}: generated tokens differ from the re-forward by "
            f"{worst:.3e} of the logit scale")
    finally:
        eng.close()
    events.report(name, ev0)
    log(f"[{name}] phase {time.perf_counter() - t_phase:.1f}s")


def compiled_forward(lm, params, tokens, kernel):
    """lm.forward compiled with the flash kernel on (the default on the TPU
    backend) or forced off. Returns (logits fp32 numpy, compiled text,
    compile seconds)."""
    import jax

    gate = os.environ.get("MXNET_PALLAS_ATTENTION") if kernel else "0"
    with env_var("MXNET_PALLAS_ATTENTION", gate):
        t0 = time.perf_counter()
        compiled = jax.jit(lambda p, t: lm.forward(p, t)).lower(
            params, tokens).compile()
        secs = time.perf_counter() - t0
    logits = np.asarray(compiled(params, tokens)[0], np.float32)
    return logits, compiled.as_text(), secs


def greedy_parity(name, lm, params, prompt, generated, cfg):
    """One prompt's greedy tokens against a plain full re-forward
    (`lm.forward`, teacher-forced on what the engine produced) at L=max_len —
    which is also where the Pallas flash kernel first runs on a chip."""
    import jax.numpy as jnp

    n, g = len(prompt), len(generated)
    seq = np.zeros((1, cfg.max_len), np.int32)
    seq[0, :n] = prompt
    seq[0, n:n + g] = generated
    tokens = jnp.asarray(seq)
    on, text_on, s_on = compiled_forward(lm, params, tokens, kernel=True)
    assert_kernel_in(text_on, f"{name}: lm.forward L={cfg.max_len}")
    off, text_off, s_off = compiled_forward(lm, params, tokens, kernel=False)
    assert "tpu_custom_call" not in text_off
    assert on.shape == (cfg.max_len, cfg.vocab_size) and np.isfinite(on).all()
    scale = np.abs(off).max()
    kdiff = np.abs(on - off).max() / scale
    log(f"[{name}] lm.forward L={cfg.max_len} compiled with attention=pallas "
        f"({s_on:.1f}s) and attention=xla ({s_off:.1f}s): logits "
        f"max|d|/max|ref| {kdiff:.2e} (tol {LM_LOGIT_RTOL:.1e})")
    assert kdiff <= LM_LOGIT_RTOL, f"{name}: kernel logits off by {kdiff:.3e}"
    # position n-1+i predicts generated[i]
    rows = off[n - 1:n - 1 + g]
    ref_top = rows.argmax(-1)
    gaps = rows.max(-1) - rows[np.arange(g), np.asarray(generated)]
    exact = int((ref_top == np.asarray(generated)).sum())
    tol = LM_LOGIT_RTOL * scale
    log(f"[{name}] greedy parity, prompt of {n} tokens: {exact}/{g} tokens "
        f"equal the re-forward argmax; worst reference-logit gap of a "
        f"generated token {gaps.max():.4f} (tol {tol:.4f} = "
        f"{LM_LOGIT_RTOL:.1e} x max|logit| {scale:.3f})")
    assert (gaps <= tol).all(), (
        f"{name}: generated tokens differ from the re-forward beyond the "
        f"tolerance at positions {np.nonzero(gaps > tol)[0].tolist()}")


# ---------------------------------------------------------------------------
# --multichip: what exists only across chips, and what it is compared with
# ---------------------------------------------------------------------------

def distinct_shard_devices(arrays):
    return {sh.device for a in arrays for sh in a.addressable_shards}


def lm_train_losses(devices, axes, seed, name):
    """LM_TRAIN['steps'] steps of TransformerLM.make_train_step on a mesh of
    `devices` shaped by `axes`. Returns (losses, compiled text, param devices)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig

    cfg = TransformerLMConfig(**LM)
    mesh = par.create_mesh(devices=devices, **axes)
    lm = TransformerLM(cfg, mesh)
    params = lm.init_params(jax.random.PRNGKey(seed))
    step, init_opt = lm.make_train_step(lr=1e-3)
    opt_state = init_opt(params)
    rng = np.random.RandomState(seed)
    shape = (LM_TRAIN["batch"], min(LM_TRAIN["seq"], cfg.max_len))
    toks = lm.shard_tokens(rng.randint(0, cfg.vocab_size, shape))
    tgts = lm.shard_tokens(rng.randint(0, cfg.vocab_size, shape))
    losses = []
    with mesh:
        t0 = time.perf_counter()
        compiled = step.lower(params, opt_state, toks, tgts,
                              jnp.asarray(0)).compile()
        t_compile = time.perf_counter() - t0
        text = compiled.as_text()
        t0 = time.perf_counter()
        for i in range(LM_TRAIN["steps"]):
            params, opt_state, loss = compiled(params, opt_state, toks, tgts,
                                               jnp.asarray(i))
            losses.append(float(loss))
        t_steps = time.perf_counter() - t0
    used = distinct_shard_devices(params.values())
    log(f"[{name}] mesh {dict(mesh.shape)}: compile {t_compile:.1f}s, "
        f"{len(losses)} steps {t_steps:.2f}s (smoke timing), losses "
        f"{[round(l, 4) for l in losses]}, parameter shards on "
        f"{len(used)} device(s)")
    return losses, text, used


def assert_losses_match(name, got, ref):
    assert all(np.isfinite(got)), f"{name}: non-finite loss {got}"
    for a, b in zip(got, ref):
        assert abs(a - b) <= LOSS_RTOL * abs(b), (
            f"{name}: loss {got} vs one-device {ref} beyond {LOSS_RTOL}")


def phase_multichip_lm(devices, seed):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from __graft_entry__ import _factor_mesh

    n = len(devices)
    name = "multichip/lm-train"
    ref, text1, _ = lm_train_losses(devices[:1], dict(dp=1), seed,
                                    name + "/1dev")
    assert_kernel_in(text1, f"{name}: one-device train step (flash forward)")
    dp, sp, tp = _factor_mesh(n)
    got, text, used = lm_train_losses(devices, dict(dp=dp, sp=sp, tp=tp), seed,
                                      f"{name}/sp{sp}tp{tp}")
    assert len(used) == n, f"{name}: shards on {len(used)} devices, not {n}"
    assert "collective-permute" in text, (
        f"{name}: no collective-permute (the sp ring) in the compiled step")
    assert "all-reduce" in text, f"{name}: no all-reduce in the compiled step"
    assert_kernel_in(text, f"{name}: sp={sp} ring hop (block_partials_pallas)")
    assert_losses_match(f"{name}/sp{sp}tp{tp}", got, ref)
    got, text, used = lm_train_losses(devices, dict(dp=n), seed,
                                      f"{name}/dp{n}")
    assert len(used) == n, f"{name}: shards on {len(used)} devices, not {n}"
    assert "all-reduce" in text, f"{name}: no all-reduce in the dp step"
    assert_kernel_in(text, f"{name}: dp={n} step (flash forward per shard)")
    assert_losses_match(f"{name}/dp{n}", got, ref)


def module_spmd_losses(spec, seed, name, events):
    """RESNET['multichip_steps'] fused steps of the ResNet-50 Module at the
    global batch, under MXNET_SPMD=`spec` (None: one device). Returns
    (losses, devices holding parameter shards)."""
    import mxnet_tpu as mx

    batch, size, classes = (RESNET["multichip_batch"], RESNET["size"],
                            RESNET["classes"])
    with env_var("MXNET_SPMD", spec):
        ctx = device_context(0)
        data, label = resnet_batch(seed, batch, size, classes)
        mod = build_module(ctx, batch, size, classes, seed)
        b = mx.io.DataBatch([mx.nd.array(data, ctx=ctx)],
                            [mx.nd.array(label, ctx=ctx)])
        t0 = time.perf_counter()
        losses, secs, per_step = module_steps(
            mod, b, label, RESNET["multichip_steps"], name, events)
        assert mod._spmd_failed is False, (
            f"{name}: the SPMD plan fell back to the replicated step")
        used = distinct_shard_devices(
            mod._exec.arg_dict[n]._data for n in mod._param_names)
        collectives = {}
        if spec:
            assert mod._spmd is not None, f"{name}: no SPMD plan was built"
            cache = mod._spmd.cache
            (key,) = [k for k in cache.keys() if k[0] == "fused_step"]
            collectives = cache.entry_collectives(key) or {}
    log(f"[{name}] MXNET_SPMD={spec or '(unset)'} global batch {batch}: "
        f"first step {secs[0]:.1f}s (compile), {len(losses)} steps "
        f"{time.perf_counter() - t0:.1f}s, losses "
        f"{[round(l, 4) for l in losses]}, parameter shards on "
        f"{len(used)} device(s), collectives "
        f"{ {k: v['count'] for k, v in collectives.items()} }; {per_step}")
    return losses, used, collectives


def phase_multichip_module(devices, seed, events):
    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError

    n = len(devices)
    name = "multichip/module-resnet50"
    # the reference's own idiom names every device; here that must not bind
    # on the first one in silence
    try:
        mx.mod.Module(mx.sym.Variable("data"),
                      context=[device_context(i) for i in range(n)])
    except MXNetError as e:
        assert "MXNET_SPMD" in str(e)
        log(f"[{name}] Module(context=[{n} devices]) is refused with the "
            f"MXNET_SPMD advice, not bound on device 0")
    else:
        raise AssertionError(f"{name}: a {n}-device context list was accepted")
    ref, used, _ = module_spmd_losses(None, seed, name + "/1dev", events)
    assert len(used) == 1
    for spec, want in ((f"dp={n}", ("all-reduce",)),
                       (f"fsdp={n // 2},tp=2", ("all-reduce", "all-gather"))):
        got, used, coll = module_spmd_losses(spec, seed, f"{name}/{spec}",
                                             events)
        assert len(used) == n, (
            f"{name}/{spec}: shards on {len(used)} devices, not {n}")
        for kind in want:
            assert coll.get(kind, {}).get("count", 0) > 0, (
                f"{name}/{spec}: no {kind} in the compiled step: {coll}")
        assert_losses_match(f"{name}/{spec}", got, ref)


# ---------------------------------------------------------------------------

def describe_environment(devs):
    import jax
    import jaxlib

    import mxnet_tpu  # noqa: F401 — places the compile cache
    from mxnet_tpu import compile_cache, lib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string, nothing more
        libtpu = "unknown"
    log(f"[env] python {sys.version.split()[0]} jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu}")
    log(f"[env] devices: {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform})")
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    log(f"[env] compile cache: {compile_cache.persistent_cache_dir()} "
        f"({'JAX_COMPILATION_CACHE_DIR' if external else 'in-checkout default'}"
        f"; jax config says {jax.config.jax_compilation_cache_dir})")
    built = lib.build_native(force=True)
    engine = lib.native_engine() if built else None
    log(f"[env] native host runtime: rebuilt from src/ = {built}; engine = "
        f"{'native librt_tpu.so' if engine is not None else 'python'}")
    assert built and engine is not None, "native host runtime did not build"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-device phase and its one-device "
                         "comparisons (needs four chips)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    devs = require_platform()
    describe_environment(devs)
    events = CompileEvents()
    seen = set()
    if args.multichip:
        assert len(devs) >= 4, f"--multichip needs 4 devices, found {len(devs)}"
        ev0 = events.snapshot()
        phase_multichip_lm(devs[:4], args.seed)
        phase_multichip_module(devs[:4], args.seed, events)
        events.report("multichip", ev0)
    else:
        phase_train_module(args.seed, events, seen)
        phase_train_gluon(args.seed, events, seen)
        phase_serve(args.seed, events, seen)
        phase_serve_hybrid(args.seed, events, seen)
    log(f"[total] {time.perf_counter() - t_start:.1f}s wall; jax compiled or "
        f"loaded {events.backend_compiles} programs in "
        f"{events.backend_compile_s:.1f}s; persistent cache hits="
        f"{events.cache_hits} misses={events.cache_misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
