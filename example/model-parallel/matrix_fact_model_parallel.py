"""Model parallelism: parameters too big for one device, sharded across
the mesh (parity: `example/model-parallel/matrix_factorization/` — the
reference splits the embedding tables across GPUs with `group2ctx`;
here the same split is a GSPMD sharding annotation and XLA inserts the
collectives).

TPU-native notes: `PartitionRules` maps parameter names to
`PartitionSpec`s — user/item tables shard row-wise on the `tp` axis, the
dense head replicates. ONE jitted SPMD train step runs on the whole
mesh; there is no per-device code, no explicit send/recv (reference:
ctx-group assignment in `graph_executor.cc`). Run on the 8-virtual-CPU
mesh (default here) or a real TPU slice unchanged.

  python example/model-parallel/matrix_fact_model_parallel.py --epochs 6
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..")))

# 8 virtual CPU devices unless the caller brings real ones; both env knob
# and config must land before the first backend init (see __graft_entry__)
if "--real-devices" not in sys.argv:
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
else:
    import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mxnet_tpu.parallel import PartitionRules

parser = argparse.ArgumentParser(
    description="embedding tables sharded across a tp mesh axis",
    formatter_class=argparse.ArgumentDefaultsHelpFormatter)
parser.add_argument("--epochs", type=int, default=6)
parser.add_argument("--batch-size", type=int, default=512)
parser.add_argument("--n-users", type=int, default=4096)
parser.add_argument("--n-items", type=int, default=2048)
parser.add_argument("--rank", type=int, default=16)
parser.add_argument("--n-ratings", type=int, default=16384)
parser.add_argument("--lr", type=float, default=0.05)
parser.add_argument("--seed", type=int, default=0)
parser.add_argument("--real-devices", action="store_true",
                    help="use whatever jax.devices() provides instead of "
                         "the 8-virtual-CPU mesh")


def main(args):
    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("tp",))
    print(f"mesh: {len(devs)} devices on axis 'tp'")

    rng = np.random.RandomState(args.seed)
    u_true = rng.normal(0, 1, (args.n_users, args.rank))
    v_true = rng.normal(0, 1, (args.n_items, args.rank))
    users = rng.randint(0, args.n_users, args.n_ratings)
    items = rng.randint(0, args.n_items, args.n_ratings)
    ratings = ((u_true[users] * v_true[items]).sum(axis=1)
               + rng.normal(0, 0.1, args.n_ratings)).astype(np.float32)

    # the reference assigns each table to a ctx group; here a rule table
    # shards each embedding row-wise over 'tp' and replicates the rest
    rules = PartitionRules(rules=[
        (r"^(user|item)_table$", P("tp", None)),
    ], default=P())
    params = {
        "user_table": rng.normal(0, 0.1, (args.n_users, args.rank)).astype(np.float32),
        "item_table": rng.normal(0, 0.1, (args.n_items, args.rank)).astype(np.float32),
    }
    params = {
        k: jax.device_put(v, rules.sharding_for(mesh, k, v.shape))
        for k, v in params.items()
    }
    for k, v in params.items():
        print(f"{k}: shape {v.shape} sharding {v.sharding.spec}")

    repl = NamedSharding(mesh, P())

    def loss_fn(params, u, i, r):
        # row-gather from the SHARDED tables: XLA turns this into a
        # collective gather across tp shards automatically
        pu = params["user_table"][u]
        pv = params["item_table"][i]
        pred = (pu * pv).sum(axis=1)
        return ((pred - r) ** 2).mean()

    # Adam state lives in the SAME sharded layout as its parameter —
    # GSPMD shards the optimizer, too (ZeRO comes free with the rules)
    state = {k: {"m": jnp.zeros_like(v), "v": jnp.zeros_like(v), "t": jnp.zeros(())}
             for k, v in params.items()}
    b1, b2, eps = 0.9, 0.999, 1e-8

    @jax.jit
    def train_step(params, state, u, i, r):
        loss, g = jax.value_and_grad(loss_fn)(params, u, i, r)
        new_p, new_s = {}, {}
        for k in params:
            t = state[k]["t"] + 1
            m = b1 * state[k]["m"] + (1 - b1) * g[k]
            v = b2 * state[k]["v"] + (1 - b2) * g[k] * g[k]
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            new_p[k] = params[k] - args.lr * mhat / (jnp.sqrt(vhat) + eps)
            new_s[k] = {"m": m, "v": v, "t": t}
        return new_p, new_s, loss

    nb = args.n_ratings // args.batch_size
    first = last = None
    for epoch in range(args.epochs):
        tot = 0.0
        for b in range(nb):
            sl = slice(b * args.batch_size, (b + 1) * args.batch_size)
            u = jax.device_put(users[sl], repl)
            i = jax.device_put(items[sl], repl)
            r = jax.device_put(ratings[sl], repl)
            params, state, loss = train_step(params, state, u, i, r)
            tot += float(loss)
        if first is None:
            first = tot / nb
        last = tot / nb
        print(f"epoch {epoch} mse {tot / nb:.4f}")

    # updated tables AND their Adam state must still be sharded (the
    # optimizer step preserved the GSPMD layout; nothing silently
    # gathered to one device)
    spec = params["user_table"].sharding.spec
    mspec = state["user_table"]["m"].sharding.spec
    print(f"final_table_sharding: {spec}")
    print(f"adam_m_sharding: {mspec}")
    print(f"first_mse: {first:.4f}")
    print(f"final_mse: {last:.4f}")
    return last


if __name__ == "__main__":
    main(parser.parse_args())
