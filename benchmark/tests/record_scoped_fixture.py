#!/usr/bin/env python3
"""Records the small TPU trace of programs that NAME their work, which
test_program_scopes.py checks the by-scope reading against
(`recorded_v5e_scoped.xplane.pb`; the profiler writes each program's compiled
module into the file itself). Run once on the chip, in a
compile cache no earlier build has filled (jax's cache key leaves the names
out, so an executable out of an older cache carries none):

    JAX_COMPILATION_CACHE_DIR=chiprun_out/scoped/cache \
        python3 benchmark/tests/record_scoped_fixture.py chiprun_out/scoped

Two programs of the framework itself, at a tiny size: six fused steps of a
symbol net through `Module.fused_step` (`jit_step`: a convolution, BatchNorm,
ReLU, pooling, a dense layer, the loss, the SGD update) and a two-layer
`TransformerLM` behind a `GenerationEngine` that serves three requests
(`jit_fn` three times over: the decode and two prefill buckets; the
`mx:generation.prefill` spans carry `bucket` and `tokens`). What the test
has to find is printed: the `[scopes]` table of the file.
"""
import glob
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


def main(out_dir):
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_fixture.py: needs the chip")
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.io.io import DataDesc
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig
    from mxnet_tpu.serving.generation import GenerationEngine

    import program_scopes
    import trace_reduce

    ctx = mx.tpu(0)
    batch = 8
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3), pad=(1, 1),
                             name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.Convolution(net, num_filter=16, kernel=(1, 1), name="conv2")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1), name="pool1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc1")
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(net, name="softmax"),
                        context=ctx)
    mod.bind([DataDesc("data", (batch, 3, 32, 32))],
             [DataDesc("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params=(
        ("learning_rate", 0.1), ("momentum", 0.9)))
    rng = np.random.RandomState(0)
    step_batch = mx.io.DataBatch(
        [nd.array(rng.uniform(-1, 1, (batch, 3, 32, 32)).astype(np.float32),
                  ctx=ctx)],
        [nd.array(rng.randint(0, 10, batch).astype(np.float32), ctx=ctx)])

    def step():
        if mod.fused_step(step_batch) is not True:
            raise SystemExit("the fused step was not taken")
        mod.get_outputs()[0].asnumpy()

    lm = TransformerLM(TransformerLMConfig(
        vocab_size=256, d_model=128, n_heads=2, d_ff=256, n_layers=2,
        max_len=64, dtype="bfloat16"),
        par.create_mesh(devices=jax.devices()[:1], dp=1))
    params = lm.init_params(jax.random.PRNGKey(0))
    eng = GenerationEngine(lm, params, max_slots=2, max_len=64,
                           buckets=(16, 32))
    eng.warm()
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (9, 20, 30)]

    step()
    eng.submit(prompts[0], max_new_tokens=2).result(timeout=120)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(6):
        step()
    for p in prompts:
        eng.submit(p, max_new_tokens=4).result(timeout=120)
    jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    kept = os.path.join(out_dir, "recorded_v5e_scoped.xplane.pb")
    shutil.copy(path, kept)
    trace = trace_reduce.load(kept, n_devices=1)
    print(kept, os.path.getsize(kept), "bytes")
    for line in program_scopes.load(kept, trace).table():
        print("[scopes]", line)
    # what a fusion's own op_name says against the convolution it carries
    for program, rows in sorted(program_scopes.hlo_programs(kept).items()):
        for name, (opcode, op_name, inner) in sorted(rows.items()):
            if opcode == "fusion" and any(o == "convolution"
                                          for o, _ in inner):
                print("[carried]", program, name, op_name, "|",
                      program_scopes._carried(inner))


if __name__ == "__main__":
    main(sys.argv[1])
