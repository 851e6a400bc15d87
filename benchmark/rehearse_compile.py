#!/usr/bin/env python3
"""Compile a cell's programs at the REAL sizes for a *described* v5e (no chip
attached) and print `memory_analysis` — what fixes `max_slots` and the batch
sizes before any chip time. Never a chip run: nothing here is a time.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py --workload <name> \
        [--batch-per-chip N] [--max-slots N]

Training cells: the program builds its step from its own graph, so one real
step is driven here on the CPU at the real size (minutes), and every program
the repo's CompileCache then holds is lowered again for the described chip
from the recorded argument shapes. A dp cell is rehearsed at its per-chip batch
on one described chip (the sharded step holds the same activations per chip,
plus the all-reduced gradients, ~0.1 GB).
Serving cells: the engine's decode and prefill programs are lowered from
shapes alone (the same wrappers as `GenerationEngine._decode_fn/_prefill_fn`).
"""
import argparse
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

BUDGET_BYTES = 14.5e9


def report(name, compiled, seconds):
    ma = compiled.memory_analysis()
    live = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    print(f"[rehearse] {name}: arguments {ma.argument_size_in_bytes / 1e9:.3f}"
          f" GB, outputs {ma.output_size_in_bytes / 1e9:.3f} GB (aliased "
          f"{ma.alias_size_in_bytes / 1e9:.3f}), temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB -> {live / 1e9:.3f} GB live"
          f" ({'fits' if live <= BUDGET_BYTES else 'DOES NOT FIT'} "
          f"{BUDGET_BYTES / 1e9} GB); compiled in {seconds:.0f}s", flush=True)
    return live


def described_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return topo.devices[0], SingleDeviceSharding(topo.devices[0])


def recompile_caches(one_chip):
    """Every program in the repo's CompileCaches, lowered again for the
    described chip from its recorded argument shapes."""
    import jax

    from mxnet_tpu import compile_cache

    def retarget(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        return x

    worst = 0.0
    for cache in compile_cache.all_caches():
        for key, st in list(cache._entry_stats.items()):
            fn = cache._entries.get(key)
            target = getattr(fn, "_fn", fn)
            if not hasattr(target, "lower"):
                continue
            args, kwargs = jax.tree_util.tree_map(retarget, st["avals"])
            t0 = time.perf_counter()
            try:
                compiled = target.lower(*args, **kwargs).compile()
            except Exception as e:  # noqa: BLE001 — report and go on
                print(f"[rehearse] {cache.name}:{key!r:.80}: {e!r:.300}")
                continue
            label = key[0] if isinstance(key, tuple) and key and \
                isinstance(key[0], str) else repr(key)[:40]
            worst = max(worst, report(f"{cache.name}:{label}", compiled,
                                      time.perf_counter() - t0))
    return worst


def rehearse_train(cell, config, job, batch):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    import train_common

    ctx = mx.cpu(0)
    data, label = train_common.make_pool(0, batch, 1, config)
    t0 = time.perf_counter()
    if job["runner"] == "train_module":
        from runners.train_module import build_symbol

        mod = mx.mod.Module(build_symbol(config), context=ctx)
        mod.bind(data_shapes=[("data", data.shape)],
                 label_shapes=[("softmax_label", label.shape)])
        mod.init_params(mx.init.Xavier())
        o = job["optimizer"]
        mod.init_optimizer(optimizer=o["name"], optimizer_params=(
            ("learning_rate", o["learning_rate"]), ("momentum", o["momentum"]),
            ("wd", o["wd"])))
        took = mod.fused_step(mx.io.DataBatch([mx.nd.array(data)],
                                              [mx.nd.array(label)]))
        assert took is True, "fused_step was not taken"
        mod.get_outputs()[0].asnumpy()
    else:
        from mxnet_tpu.gluon import Trainer, loss as gloss
        from runners.train_gluon import build_net

        net = build_net(config)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.hybridize(static_alloc=True)
        if job["dtype"] != "float32":
            net.cast(job["dtype"])
        o = job["optimizer"]
        trainer = Trainer(net.collect_params(), o["name"], {
            "learning_rate": o["learning_rate"], "momentum": o["momentum"],
            "wd": o["wd"], "multi_precision": bool(o.get("multi_precision"))})
        sce = gloss.SoftmaxCrossEntropyLoss()
        sce.hybridize()
        x = mx.nd.array(data).astype(job["dtype"])
        y = mx.nd.array(label)
        with autograd.record():
            loss = sce(net(x), y)
        loss.backward()
        trainer.step(batch)
        float(loss.asnumpy().astype(np.float64).mean())
    print(f"[rehearse] one real step of {cell} at batch {batch} on the CPU "
          f"took {time.perf_counter() - t0:.0f}s", flush=True)
    _, one_chip = described_chip()
    return recompile_caches(one_chip)


def rehearse_serve(config, job, max_slots):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import TransformerLM
    from runners.serve_engine import lm_config

    dev, _ = described_chip()
    cfg = lm_config(config)
    mesh = par.create_mesh(devices=[dev], dp=1)
    lm = TransformerLM(cfg, mesh)
    sh = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    # the weights' shapes, without a device to hold them
    cpu_lm = TransformerLM(cfg, par.create_mesh(devices=jax.devices()[:1],
                                                dp=1))
    shapes = jax.eval_shape(cpu_lm.init_params, jax.random.PRNGKey(0))
    params = {k: sds(v.shape, v.dtype) for k, v in shapes.items()}
    max_len = job["engine"]["max_len"]
    slab = sds((max_slots, cfg.n_layers, cfg.n_heads, max_len,
                cfg.d_model // cfg.n_heads), jnp.dtype(cfg.dtype))

    def decode(params, ck, cv, tokens, positions):
        logits, ck, cv = lm.decode_step(params, ck, cv, tokens, positions)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), ck, cv

    def prefill(params, ck, cv, toks, length, slot):
        logits, ck, cv = lm.prefill(params, ck, cv, toks, length, slot)
        return jnp.argmax(logits).astype(jnp.int32), ck, cv

    t0 = time.perf_counter()
    compiled = jax.jit(decode, donate_argnums=(1, 2)).lower(
        params, slab, slab, sds((max_slots,), jnp.int32),
        sds((max_slots,), jnp.int32)).compile()
    worst = report(f"decode, {max_slots} slots x {max_len}", compiled,
                   time.perf_counter() - t0)
    for bucket in job["engine"]["buckets"]:
        t0 = time.perf_counter()
        compiled = jax.jit(prefill, donate_argnums=(1, 2)).lower(
            params, slab, slab, sds((bucket,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32)).compile()
        worst = max(worst, report(f"prefill bucket {bucket}", compiled,
                                  time.perf_counter() - t0))
    return worst


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch-per-chip", type=int)
    ap.add_argument("--max-slots", type=int)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

    import harness

    _, _, config, job = harness.load_cell(args.workload)
    print(f"[rehearse] {args.workload}: a compile for a described v5e, not a "
          f"chip run", flush=True)
    if job["runner"] == "serve_engine":
        worst = rehearse_serve(config, job,
                               args.max_slots or job["engine"]["max_slots"])
    else:
        worst = rehearse_train(args.workload, config, job,
                               args.batch_per_chip or job["batch_per_chip"])
    print(f"[rehearse] largest program: {worst / 1e9:.3f} GB live of "
          f"{BUDGET_BYTES / 1e9} GB", flush=True)
    return 0 if worst <= BUDGET_BYTES else 1


if __name__ == "__main__":
    sys.exit(main())
