"""Multi-host process group + the `dist_tpu_sync` KVStore.

Replaces ps-lite entirely (SURVEY.md §5): the reference runs a scheduler +
N server processes + M workers over ZMQ (`kvstore_dist.h:44`,
`kvstore_dist_server.h:155`), shards big keys across servers
(`EncodeDefaultKey:533`), and applies the optimizer server-side
(`ApplyUpdates:346`). On TPU there are no servers: every host joins one
SPMD process group (`jax.distributed`), arrays are global, and a push is an
AllReduce over ICI (DCN across slices) inside a tiny jitted program.
update_on_kvstore maps to an updater applied on the replicated aggregate —
identical math on every process, no server round-trip.

Data plane design (round-4 rewrite — no host bounce):

* values stay jax Arrays end-to-end; a push builds one **global** array
  whose leading axis is the device count (this process's contribution on
  its local device 0, zeros elsewhere — `make_array_from_single_device_arrays`,
  no host numpy copies), then runs one cached jitted ``sum(axis=0)`` with a
  fully-replicated output sharding: XLA lowers that to the AllReduce.
* keys are **bucketed**: one flattened+concatenated buffer per dtype per
  push call (cap `MXNET_KVSTORE_DIST_BUCKET_SIZE` elements), one collective
  per bucket — the reference's key batching (`MXNET_UPDATE_AGGREGATION_SIZE`,
  `kvstore_nccl.h`).
* 2-bit gradient compression (`gradient_compression.cc:45`): each worker
  quantizes with its own error-feedback residual, the packed uint32 words
  (16× smaller) ride one all-gather, and a single fused program dequantizes
  every worker's words and sums them (`..gradient_compression`).
* row_sparse pushes ship (indices, rows) padded to the max worker count —
  an all-gather of the occupied rows only; the full dense gradient is never
  materialized (reference `EncodeRowSparseKey`, `kvstore_dist.h:676`).
"""
from __future__ import annotations

import functools
import os
import time as _time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import default_mesh
from .. import telemetry
from ..base import getenv, register_env
from ..compile_cache import CompileCache
from ..kvstore import KVStoreBase
from . import collectives as coll

register_env("MXNET_UPDATE_AGGREGATION_SIZE", 0,
             "max KEYS fused into one dist-push collective bucket (the "
             "reference's update aggregation, kvstore_nccl.h); 0 = no "
             "key cap, element-size capping only")

# the in-store collective programs (sum/gather/fused-dequant), named so
# `named_stats("dist")` attributes wire recompiles (were anonymous
# lru_caches — the class tpulint's executable-cache rule now flags).
# track_memory=False: one tiny program per bucket layout — the /memory
# scrape's per-entry AOT analysis would re-pay a compile each
_dist_cache = CompileCache("dist", track_memory=False)

_initialized = False


def init_process_group(coordinator=None, num_processes=None, process_id=None):
    """Initialise jax.distributed from args or env (no-op single process).

    Env rendezvous keeps the reference's names working where they map:
    `DMLC_PS_ROOT_URI`/`DMLC_PS_ROOT_PORT` → coordinator address,
    `DMLC_NUM_WORKER` → process count, `DMLC_WORKER_ID` → process id
    (ps-lite's scheduler rendezvous, minus the scheduler).

    `MXNET_DIST_PLATFORM=cpu` (set by `tools/launch.py --launcher local`)
    forces the CPU backend with gloo cross-process collectives *before* the
    backend initialises — multi-worker correctness runs need no TPU.
    """
    global _initialized
    if _initialized:
        return
    coordinator = coordinator or _env_coordinator()
    if coordinator is None:
        _initialized = True  # single-process
        return
    platform = os.environ.get("MXNET_DIST_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
        if platform == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    num_processes = num_processes or int(
        os.environ.get("MXNET_NUM_PROCESSES", os.environ.get("DMLC_NUM_WORKER", "1")))
    if process_id is None:
        process_id = int(
            os.environ.get("MXNET_PROCESS_ID", os.environ.get("DMLC_WORKER_ID", "0")))
    kwargs = dict(coordinator_address=coordinator,
                  num_processes=num_processes, process_id=process_id)
    # bounded rendezvous: without a timeout a worker whose coordinator died
    # (or whose fleet never fully launched) hangs forever with no hint
    from ..base import getenv

    timeout_s = int(getenv("MXNET_INIT_TIMEOUT_S"))
    if timeout_s:
        kwargs["initialization_timeout"] = timeout_s
    try:
        jax.distributed.initialize(**kwargs)
    except Exception as e:
        from ..log import get_logger

        get_logger("mxnet_tpu.dist").error(
            "process group rendezvous failed: coordinator=%s rank=%d/%d "
            "(%r). Check that the coordinator host:port is reachable, that "
            "ALL %d workers launched, and that every rank in [0, %d) is "
            "claimed exactly once (MXNET_PROCESS_ID / DMLC_WORKER_ID).",
            coordinator, process_id, num_processes, e,
            num_processes, num_processes)
        raise
    _initialized = True
    # arm the elastic heartbeat lease (no-op unless MXNET_ELASTIC=1 with a
    # shared lease dir and real peers): from here on a dead worker raises
    # WorkerLostError inside collectives instead of parking the fleet
    from . import elastic

    elastic.ensure_started()


def _env_coordinator():
    if os.environ.get("MXNET_COORDINATOR"):
        return os.environ["MXNET_COORDINATOR"]
    uri = os.environ.get("DMLC_PS_ROOT_URI")
    if not uri:
        return None
    port = os.environ.get("DMLC_PS_ROOT_PORT", "9091")
    return f"{uri}:{port}"


def process_rank():
    return jax.process_index()


def process_count():
    return jax.process_count()


def device_count():
    return len(jax.devices())


# -- cached collective programs ----------------------------------------------

@functools.lru_cache(maxsize=None)
def _collective_mesh():
    """Flat 1-D mesh over every device in the job."""
    return Mesh(np.array(jax.devices()), ("procdev",))


def _sum_over_devices_fn():
    # jit caches per input shape/dtype; one wrapper suffices for all keys
    def build():
        mesh = _collective_mesh()
        return jax.jit(lambda x: x.sum(axis=0),
                       out_shardings=NamedSharding(mesh, P()))

    return _dist_cache.get_or_build(("sum",), build)


def _gather_fn():
    """Replicate a device-sharded stack everywhere (AllGather)."""
    def build():
        mesh = _collective_mesh()
        return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))

    return _dist_cache.get_or_build(("gather",), build)


def _dequant_sum_fn(segments, threshold, dtype_str):
    return _dist_cache.get_or_build(
        ("dequant", segments, threshold, dtype_str),
        lambda: _build_dequant_sum(segments, threshold, dtype_str))


def _build_dequant_sum(segments, threshold, dtype_str):
    """One fused program: dequantize every worker's packed 2-bit words for a
    whole key bucket and sum over workers. ``segments`` is a static tuple of
    (word_start, word_count, shape) per key."""
    from ..gradient_compression import dequantize_2bit

    mesh = _collective_mesh()
    dtype = jnp.dtype(dtype_str)

    def body(packed_stack):  # (n_dev, total_words) uint32
        outs = []
        for (ws, wc, shape) in segments:
            seg = packed_stack[:, ws:ws + wc]
            de = jax.vmap(lambda p: dequantize_2bit(p, shape, threshold, dtype))(seg)
            outs.append(de.sum(axis=0))
        return tuple(outs)

    return jax.jit(body, out_shardings=NamedSharding(mesh, P()))


def _make_global_stack(buf, fill=0):
    """Build the (n_dev, *buf.shape) global array: this process's ``buf`` on
    its first local device, a neutral ``fill`` on its other local devices
    (so a sum over axis 0 is the sum over processes, and gathers can filter
    the neutral rows). No host round-trip."""
    mesh = _collective_mesh()
    n_dev = len(jax.devices())
    sharding = NamedSharding(mesh, P("procdev"))
    local = jax.local_devices()
    shards = []
    for i, d in enumerate(local):
        if i == 0:
            shards.append(jax.device_put(jnp.expand_dims(buf, 0), d))
        else:
            shards.append(jax.device_put(
                jnp.full((1,) + buf.shape, fill, buf.dtype), d))
    return jax.make_array_from_single_device_arrays(
        (n_dev,) + tuple(buf.shape), sharding, shards)


def _collective_telemetry(name, buf, t0):
    """Record one collective: bytes on the wire (this process's
    contribution) and host-side dispatch latency. jax dispatch is async, so
    the latency histogram is the host cost of issuing the collective — the
    device-side time shows up in the XLA trace (`profiler.start`)."""
    telemetry.counter(f"dist.{name}_calls").inc()
    telemetry.counter(f"dist.{name}_bytes").inc(
        int(buf.size) * buf.dtype.itemsize)
    telemetry.histogram(f"dist.{name}_us").record(
        (_time.perf_counter() - t0) * 1e6)


def _guarded(fn, desc):
    """Dispatch one cross-process collective under the elastic lease guard
    when the runtime is armed (the guard thread also blocks on the result,
    so a wedge surfaces as WorkerLostError instead of a later silent
    hang); plain dispatch otherwise."""
    from . import elastic

    if elastic.active():
        return elastic.guard(lambda: jax.block_until_ready(fn()), desc=desc)
    return fn()


def _allreduce_sum(buf):
    """Sum ``buf`` over all worker processes; replicated result (one
    AllReduce on the wire)."""
    if jax.process_count() == 1 and jax.local_device_count() == len(jax.devices()):
        return buf
    tele = telemetry._enabled  # cached across the call (mid-call enable)
    t0 = _time.perf_counter() if tele else 0.0
    stack = _make_global_stack(buf)
    out = _guarded(lambda: _sum_over_devices_fn()(stack), "allreduce")
    if tele:
        _collective_telemetry("allreduce", buf, t0)
    return out.addressable_data(0)


def _allgather(buf, fill=0):
    """All-gather ``buf`` from every device → replicated (n_dev, *shape).
    Rows from non-primary local devices hold the neutral ``fill``."""
    tele = telemetry._enabled  # cached across the call (mid-call enable)
    t0 = _time.perf_counter() if tele else 0.0
    stack = _make_global_stack(buf, fill=fill)
    out = _guarded(lambda: _gather_fn()(stack), "allgather")
    if tele:
        _collective_telemetry("allgather", buf, t0)
    return out.addressable_data(0)


def _bucket_cap_elems(itemsize):
    """Elements per fused-collective bucket. `MXNET_KVSTORE_DIST_BUCKET_SIZE`
    (elements — the original knob) wins when set; otherwise the shared
    grad-sync sizing knob `MXNET_KVSTORE_BUCKET_MB` (bytes) applies, so one
    variable sizes both the in-store bucketing and `GradSync` buckets."""
    env = os.environ.get("MXNET_KVSTORE_DIST_BUCKET_SIZE")
    if env:
        return int(env)
    from .grad_sync import bucket_cap_bytes

    return max(1, bucket_cap_bytes() // max(int(itemsize), 1))


def _wire_dtype(dtype, fp32_wire):
    """16-bit keys ship over a bf16 wire by default (fp32 exponent range,
    half the bytes); `MXNET_KVSTORE_FP32_WIRE=1` restores the exact wire."""
    if jnp.dtype(dtype) in (jnp.float16, jnp.bfloat16):
        return jnp.float32 if fp32_wire else jnp.bfloat16
    return jnp.dtype(dtype)


class KVStoreDistTPUSync(KVStoreBase):
    """`kv.create('dist_tpu_sync')` / `'dist_sync'` / `'dist'`.

    Keeps the KVStore front API (init/push/pull/pushpull, `kvstore.py`;
    subclasses KVStoreBase so `isinstance` dispatch in
    `model._create_kvstore` accepts store instances) so Trainer/Module code
    is unchanged, but push+pull together are ONE AllReduce over every
    device in the mesh — per-bucket programs are compile-cached by shape.
    Keys live replicated on the mesh.

    Semantics vs reference (`kvstore_dist_server.h` sync mode): the server
    aggregated exactly num_workers pushes then answered pulls; here the
    collective IS the aggregation+broadcast, so a push must be made by all
    workers collectively (SPMD) — same contract sync training already obeys.
    """

    def __init__(self, mesh=None):
        init_process_group()
        super().__init__()         # _updater/_updater_func/_gc
        self.mesh = mesh or default_mesh()
        self._store = {}           # key -> replicated jax Array
        self._pending = {}         # key -> aggregated dense grad
        self._pending_rsp = {}     # key -> list of (idx int32 (m,), data (m, ...))
        self._optimizer = None

    # -- identity -----------------------------------------------------------

    @property
    def type(self):
        return "dist_tpu_sync"

    @property
    def rank(self):
        return process_rank()

    @property
    def num_workers(self):
        return process_count()

    # -- data plane ----------------------------------------------------------

    def _key_list(self, key, value):
        from ..base import MXNetError

        if isinstance(key, (list, tuple)):
            # survive `python -O`: a stripped assert would zip-truncate and
            # silently drop the tail keys of a grouped call
            if len(key) != len(value):
                raise MXNetError(
                    f"grouped call: {len(key)} keys but {len(value)} values")
            return list(key), list(value)
        return [key], [value]

    def init(self, key, value):
        """Set initial values (never compressed — reference inits bypass
        gradient compression, `tests/nightly/dist_sync_kvstore.py:274-284`)."""
        from ..base import MXNetError
        from ..ndarray import NDArray

        keys, vals = self._key_list(key, value)
        for k, v in zip(keys, vals):
            if k in self._store:
                raise MXNetError(f"key {k} already initialized")
            arr = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            self._store[k] = jnp.asarray(arr)

    def push(self, key, value, priority=0, ignore_sparse=True):
        """Aggregate grads over all workers into the pending buffer."""
        from ..base import MXNetError
        from ..kvstore import _nd_nbytes
        from ..ndarray import NDArray
        from ..ndarray.sparse import RowSparseNDArray

        tele = telemetry._enabled
        t0 = _time.perf_counter() if tele else 0.0
        keys, vals = self._key_list(key, value)
        if tele:
            telemetry.counter("kvstore.push_bytes").inc(sum(
                sum(_nd_nbytes(x) for x in v) if isinstance(v, (list, tuple))
                else _nd_nbytes(v) for v in vals))
        prios = list(priority) if isinstance(priority, (list, tuple)) \
            else [priority] * len(keys)
        dense_keys, dense_arrs, dense_prios = [], [], []
        for k, v, p in zip(keys, vals, prios):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized (call init first)")
            if isinstance(v, RowSparseNDArray):
                self._push_row_sparse(k, v)
                continue
            if isinstance(v, (list, tuple)):  # per-device list → local sum first
                arr = _local_sum([x._data if isinstance(x, NDArray) else x for x in v])
            else:
                arr = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            dense_keys.append(k)
            dense_arrs.append(arr)
            dense_prios.append(p)
        if dense_keys:
            if self._gc.active:
                self._push_dense_compressed(dense_keys, dense_arrs)
            else:
                self._push_dense(dense_keys, dense_arrs, dense_prios)
        if tele:
            telemetry.histogram("kvstore.push_us").record(
                (_time.perf_counter() - t0) * 1e6)

    def _push_dense(self, keys, arrs, priorities=None):
        """Bucketed allreduce: flatten+concat per dtype, one collective per
        bucket, split back per key. Grouped (multi-key) pushes fill buckets
        in priority order — least negative first, so the parameters the
        next forward pass consumes first are reduced first (the engine
        semantics the per-key `priority=-i` argument always promised).

        Wire dtype for 16-bit keys (round-5 verdict #9): fp16 gradients
        ship over a **bf16 wire** — the same bytes as the reference's
        native-dtype allreduce (`src/kvstore/comm.h:451`) but with fp32's
        exponent range, so large-key sums cannot overflow the way a raw
        fp16 wire can; bf16 keys stay bf16. `MXNET_KVSTORE_FP32_WIRE=1`
        restores the (exact, 2x bytes) fp32 wire for either."""
        order = range(len(keys))
        if priorities is not None and len(set(priorities)) > 1:
            order = sorted(order, key=lambda i: -priorities[i])
        buckets = []  # list of (keys, arrs)
        groups = {}
        for i in order:
            k, a = keys[i], arrs[i]
            groups.setdefault(str(a.dtype), []).append((k, a))
        # reference key-batching knob: cap KEYS per fused collective
        # too (kvstore_nccl.h update aggregation); 0 = elements only.
        # Read once per push — not per dtype group on the sync hot path
        key_cap = int(getenv("MXNET_UPDATE_AGGREGATION_SIZE", 0))
        for _, ka in groups.items():
            cap = _bucket_cap_elems(ka[0][1].dtype.itemsize)
            cur_k, cur_a, cur_n = [], [], 0
            for k, a in ka:
                if cur_k and (cur_n + a.size > cap
                              or (key_cap and len(cur_k) >= key_cap)):
                    buckets.append((cur_k, cur_a))
                    cur_k, cur_a, cur_n = [], [], 0
                cur_k.append(k)
                cur_a.append(a)
                cur_n += a.size
            if cur_k:
                buckets.append((cur_k, cur_a))
        fp32_wire = os.environ.get("MXNET_KVSTORE_FP32_WIRE", "0") == "1"
        tele = telemetry._enabled
        for bkeys, barrs in buckets:
            wire_dtype = _wire_dtype(barrs[0].dtype, fp32_wire)
            if tele:
                # exact wire-dispatch accounting: ONE collective per bucket
                # (the O(#buckets) contract test_grad_sync.py pins)
                telemetry.counter("dist.push_collectives").inc()
            if len(barrs) == 1:
                reduced = _allreduce_sum(barrs[0].astype(wire_dtype))
                parts = [reduced]
            else:
                flat = jnp.concatenate([a.reshape(-1).astype(wire_dtype) for a in barrs])
                red = _allreduce_sum(flat)
                parts, off = [], 0
                for a in barrs:
                    parts.append(red[off:off + a.size].reshape(a.shape))
                    off += a.size
            for k, a, p in zip(bkeys, barrs, parts):
                p = p.astype(a.dtype)
                pend = self._pending.get(k)
                self._pending[k] = p if pend is None else pend + p

    def _push_dense_compressed(self, keys, arrs):
        """2-bit compressed push: quantize locally (error feedback), ship
        packed words over one all-gather, dequantize+sum in one program."""
        segments, packs = [], []
        off = 0
        for k, a in zip(keys, arrs):
            packed = self._gc.quantize(k, a.astype(jnp.float32))
            segments.append((off, packed.shape[0], tuple(a.shape)))
            packs.append(packed)
            off += packed.shape[0]
        bucket = packs[0] if len(packs) == 1 else jnp.concatenate(packs)
        if telemetry._enabled:
            telemetry.counter("dist.push_collectives").inc()
        stack = _make_global_stack(bucket)  # fill=0 words dequantize to 0
        fn = _dequant_sum_fn(tuple(segments), float(self._gc.threshold), "float32")
        outs = _guarded(lambda: fn(stack), "compressed_push")
        for k, a, o in zip(keys, arrs, outs):
            p = o.addressable_data(0).astype(a.dtype)
            pend = self._pending.get(k)
            self._pending[k] = p if pend is None else pend + p

    def _push_row_sparse(self, k, v):
        """Ship only the occupied rows: all-gather (indices, rows) padded to
        the max per-worker row count (reference EncodeRowSparseKey,
        `kvstore_dist.h:676`); aggregation stays sparse until update time."""
        idx = v.indices._data.astype(jnp.int32)
        data = v.data._data
        n_proc = self.num_workers
        if n_proc == 1:
            if idx.size:
                self._pending_rsp.setdefault(k, []).append((idx, data))
            else:
                self._pending_rsp.setdefault(k, [])
            return
        counts = _allgather(jnp.asarray([idx.shape[0]], jnp.int32))
        cap = int(np.asarray(counts).max())
        self._pending_rsp.setdefault(k, [])
        if cap == 0:
            return
        row_shape = tuple(self._store[k].shape[1:])
        pad_idx = jnp.full((cap,), -1, jnp.int32).at[:idx.shape[0]].set(idx)
        pad_data = jnp.zeros((cap,) + row_shape, data.dtype)
        if idx.shape[0]:
            pad_data = pad_data.at[:idx.shape[0]].set(data)
        all_idx = np.asarray(_allgather(pad_idx, fill=-1))  # (n_dev, cap)
        all_data = _allgather(pad_data)                     # (n_dev, cap, ...)
        pieces_i, pieces_d = [], []
        for r in range(all_idx.shape[0]):
            valid = all_idx[r] >= 0
            if valid.any():
                pieces_i.append(jnp.asarray(all_idx[r][valid]))
                pieces_d.append(all_data[r][np.nonzero(valid)[0]])
        if pieces_i:
            self._pending_rsp[k].append(
                (jnp.concatenate(pieces_i), jnp.concatenate(pieces_d)))

    def _merged_rsp(self, k):
        """Merge pending sparse pieces: unique rows + segment sum."""
        pieces = self._pending_rsp.pop(k)
        if not pieces:
            return None
        idx = jnp.concatenate([p[0] for p in pieces])
        data = jnp.concatenate([p[1] for p in pieces])
        uniq, inv = jnp.unique(idx, return_inverse=True)
        summed = jax.ops.segment_sum(data, inv.reshape(-1), num_segments=uniq.shape[0])
        return uniq, summed

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        from ..base import MXNetError
        from ..kvstore import _nd_nbytes
        from ..ndarray import NDArray

        tele = telemetry._enabled
        t0 = _time.perf_counter() if tele else 0.0
        keys, outs = self._key_list(key, out)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized (call init first)")
            self._apply_pending(k)
            val = self._store[k]
            targets = o if isinstance(o, (list, tuple)) else [o]
            if tele:
                telemetry.counter("kvstore.pull_bytes").inc(
                    sum(_nd_nbytes(t) for t in targets))
            for t in targets:
                t._data = jnp.asarray(val, t.dtype)
        if tele:
            telemetry.histogram("kvstore.pull_us").record(
                (_time.perf_counter() - t0) * 1e6)

    def _apply_pending(self, k):
        from ..ndarray import NDArray
        from ..ndarray.sparse import RowSparseNDArray

        if k in self._pending_rsp:
            merged = self._merged_rsp(k)
            stored = self._store[k]
            if merged is None:
                # every worker pushed an empty row_sparse grad: with an
                # updater that's a no-op update; without one, stored becomes
                # the (all-zero) aggregate (kvstore_dist_server.h ApplyUpdates)
                if self._updater is None:
                    self._store[k] = jnp.zeros_like(stored)
                return
            uniq, summed = merged
            if self._updater is not None:
                grad = RowSparseNDArray(NDArray(summed.astype(stored.dtype)),
                                        NDArray(uniq.astype(jnp.int32)),
                                        tuple(stored.shape))
                w = NDArray(stored)
                self._updater(_key_index(k), grad, w)
                self._store[k] = w._data
            else:
                # sync mode without updater: stored = merged (CopyFromTo of
                # the row_sparse aggregate, kvstore_dist_server.h ApplyUpdates)
                dense = jnp.zeros_like(stored).at[uniq].set(summed.astype(stored.dtype))
                self._store[k] = dense
            return
        pend = self._pending.pop(k, None)
        if pend is None:
            return
        if self._updater is not None:
            stored = NDArray(self._store[k])
            self._updater(_key_index(k), NDArray(pend), stored)
            self._store[k] = stored._data
        else:
            self._store[k] = jnp.asarray(pend, self._store[k].dtype)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        self.pull(key, out if out is not None else value, priority)

    def allreduce_flat(self, value, priority=0):
        """One bucket = one AllReduce on the wire (`GradSync`'s collective):
        local-sum the per-device replicas, then one cross-worker collective
        over the flat buffer — no store, no updater, no per-key dispatch."""
        from ..kvstore import _nd_nbytes
        from ..ndarray import NDArray

        vals = value if isinstance(value, (list, tuple)) else [value]
        arrs = [v._data if isinstance(v, NDArray) else jnp.asarray(v)
                for v in vals]
        dtype = arrs[0].dtype
        fp32_wire = os.environ.get("MXNET_KVSTORE_FP32_WIRE", "0") == "1"
        wire = _wire_dtype(dtype, fp32_wire)
        # cast BEFORE the local-device sum: a flat fp16 bucket sums in the
        # wire dtype end-to-end, so neither the replica sum nor the
        # cross-worker sum can overflow fp16's exponent
        arrs = [a.astype(wire) for a in arrs]
        buf = arrs[0] if len(arrs) == 1 else _local_sum(arrs)
        if telemetry._enabled:
            telemetry.counter("dist.push_collectives").inc()
            telemetry.counter("dist.bucket_bytes").inc(
                int(buf.size) * buf.dtype.itemsize)
        reduced = _allreduce_sum(buf)
        return NDArray(reduced.astype(dtype))

    def reduce_scatter_flat(self, value, num_shards, shard_index,
                            priority=0):
        """Reduce-scatter across workers — the ZeRO-1 eager wire primitive
        next to `allreduce_flat`: each worker gets back only its
        1/num_shards slice of the cross-worker sum. This eager lane always
        ships the FULL allreduce bytes and slices host-side after the
        collective (gloo has no reduce-scatter primitive); the true
        (N-1)/N·B ReduceScatter exists only on the traced path, where XLA
        lowers zero1.py's psum + sharding constraint onto ICI."""
        from ..base import MXNetError
        from ..ndarray import NDArray

        vals = value if isinstance(value, (list, tuple)) else [value]
        n = int(vals[0].shape[0])
        if n % int(num_shards):
            raise MXNetError(
                f"reduce_scatter_flat: bucket length {n} not divisible "
                f"into {num_shards} shards (pad with pad_to_shards first)")
        step = n // int(num_shards)
        lo = step * int(shard_index)
        merged = self.allreduce_flat(value, priority)
        return NDArray(merged._data[lo:lo + step])

    @property
    def fused_step_compatible(self):
        """The fused train step may trace this store's gradient sync when
        the collective is expressible inside the module's (single-device)
        jitted program: a single-process group, where the cross-replica sum
        degenerates to the identity. Multi-host groups and compressed
        pushes keep the eager decomposition (per-push quantization needs
        host-side residual state)."""
        return process_count() == 1 and not self._gc.active

    def fused_grad_sync_fn(self, entries):
        """Traceable bucketed gradient sync for `Executor.fused_step`:
        flatten+concat each bucket and apply the cross-replica sum INSIDE
        the jitted step (the psum the eager push dispatches per bucket) —
        instead of falling back to eager whenever a kvstore is attached.
        With one process the sum over the replica group is the identity,
        but the bucket pack/reduce/unpack structure stays in the trace, so
        the wire dtype and key→bucket layout match the eager path exactly.

        ZeRO-1 composition (`MXNET_ZERO1=1`): the sharded update
        (`parallel/zero1.py`) runs downstream of this sync in the same
        trace and immediately re-constrains each bucket to the dp-sharded
        layout — XLA fuses the cross-replica sum + sharded constraint into
        ONE ReduceScatter (the reduce-scatter variant of this allreduce,
        arXiv:2004.13336), so no second wire pass is paid."""
        if not self.fused_step_compatible:
            return None
        from .grad_sync import bucket_assign, bucket_cap_bytes

        buckets = bucket_assign(list(entries), bucket_cap_bytes())
        shapes = [tuple(e[0]) for e in entries]
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        fp32_wire = os.environ.get("MXNET_KVSTORE_FP32_WIRE", "0") == "1"

        def sync(grads):
            out = list(grads)
            for b in buckets:
                wire = _wire_dtype(b.dtype, fp32_wire)
                parts = [out[k].reshape(-1).astype(wire) for k in b.keys]
                flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                # single-process group: sum over replicas == identity; the
                # multi-host lowering replaces this with lax.psum over the
                # dp axis of an SPMD trace
                off = 0
                for k in b.keys:
                    out[k] = flat[off:off + sizes[k]].reshape(
                        shapes[k]).astype(grads[k].dtype)
                    off += sizes[k]
            return tuple(out)

        return sync

    def pull_sparse_grad(self, key):
        """Hand back the merged pending row_sparse aggregate as
        (unique_rows, summed_data) WITHOUT applying it to the stored value
        or densifying — gluon Trainer's allreduce-then-update-locally flow
        (the reference pulls row_sparse grads the same lazy way)."""
        merged = self._merged_rsp(key) if key in self._pending_rsp else None
        if merged is None:
            val = self._store[key]
            return (jnp.zeros((0,), jnp.int32),
                    jnp.zeros((0,) + tuple(val.shape[1:]), val.dtype))
        return merged

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows (reference `PullRowSparseImpl`,
        `kvstore_dist.h:271`): result has the full logical shape with the
        deduplicated requested rows filled, everything else zero. A
        RowSparseNDArray ``out`` receives just (indices, rows) — O(rows),
        no dense table is built."""
        from ..ndarray import NDArray

        keys, outs = self._key_list(key, out)
        rids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids] * len(keys)
        for k, o, r in zip(keys, outs, rids):
            self._apply_pending(k)
            val = self._store[k]
            ridx = r._data if isinstance(r, NDArray) else jnp.asarray(r)
            ridx = jnp.unique(ridx.reshape(-1).astype(jnp.int32)) if ridx.size \
                else jnp.zeros((0,), jnp.int32)
            targets = o if isinstance(o, (list, tuple)) else [o]
            for t in targets:
                _fill_rows(t, val, ridx)

    # -- control plane -------------------------------------------------------

    def set_optimizer(self, optimizer):
        from .. import optimizer as opt_mod
        self._optimizer = optimizer
        self._updater = opt_mod.get_updater(optimizer)

    def _set_updater(self, updater):
        self._updater = updater

    def set_gradient_compression(self, compression_params):
        self._gc.set_params(compression_params)

    def barrier(self):
        """Fleet sync point, with straggler diagnostics. Under the elastic
        runtime (`MXNET_ELASTIC=1`) the straggler warning is promoted to a
        STRUCTURED timeout: the barrier runs under the heartbeat-lease
        guard, so a dead or wedged worker raises `WorkerLostError` within
        `MXNET_ELASTIC_GRACE_S` and the survivor can shrink+resume. On the
        non-elastic path a barrier slower than `MXNET_BARRIER_WARN_S`
        keeps the original behavior — log which rank noticed and how long
        it stalled, and keep waiting — because without a rendezvous to
        shrink through, aborting is strictly worse than diagnosing."""
        from ..base import getenv
        from ..log import get_logger
        from . import elastic

        warn_s = float(getenv("MXNET_BARRIER_WARN_S"))
        t0 = _time.monotonic()
        if elastic.active():
            elastic.guard(lambda: coll.barrier(self.mesh), desc="barrier")
        else:
            coll.barrier(self.mesh)
        elapsed = _time.monotonic() - t0
        if telemetry._enabled:
            # straggler wait: time THIS rank sat parked at the sync point —
            # p99 across steps is the fleet's straggler profile
            telemetry.histogram("dist.barrier_wait_us").record(elapsed * 1e6)
        if elapsed > warn_s:
            get_logger("mxnet_tpu.dist").warning(
                "barrier on rank %d/%d took %.1fs (threshold %.0fs) — a "
                "straggler or dead worker is holding the fleet",
                self.rank, self.num_workers, elapsed, warn_s)

    # save/load_optimizer_states inherit KVStoreBase's MXNetError-guarded
    # implementations (every rank runs the same updater on the replicated
    # aggregate, so local state IS the global state)


def _fill_rows(target, val, ridx):
    """Write the selected rows of ``val`` into ``target``: sparse targets
    get only (indices, rows); dense targets get the zero-padded full shape."""
    from ..ndarray import NDArray
    from ..ndarray.sparse import RowSparseNDArray

    if isinstance(target, RowSparseNDArray):
        rows = jnp.take(val, ridx, axis=0) if ridx.size else \
            jnp.zeros((0,) + tuple(val.shape[1:]), val.dtype)
        target._aux = {"data": NDArray(rows.astype(target.dtype)),
                       "indices": NDArray(ridx)}
        target._dense_cache = None
        target._aux_stale = False
        return
    result = jnp.zeros_like(val)
    if ridx.size:
        result = result.at[ridx].set(jnp.take(val, ridx, axis=0))
    target._data = jnp.asarray(result, target.dtype)


def _key_index(k):
    """String keys map through the SAME deterministic index as the local
    kvstore (`kvstore._str_key_int`) so optimizer states saved under one
    store type resume correctly under the other."""
    if isinstance(k, int):
        return k
    from ..kvstore import _str_key_int

    return _str_key_int(k)


def _local_sum(arrs):
    out = arrs[0]
    for a in arrs[1:]:
        out = out + jnp.asarray(a, out.dtype)
    return out
