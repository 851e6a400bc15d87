"""Predictor — a trained symbol bound for thread-safe, bucketed inference.

`BaseModule.predict` is a training-loop convenience: one caller, one eval
iterator, one bound batch shape — a concurrent, ragged request stream
through it either recompiles on every odd batch size or serializes callers
behind rebinds. The Predictor is the serving-side answer, composing two
pieces the training stack already proved out:

* **bucket-ladder executors** — one ``for_training=False`` executor per
  configured batch-size bucket (``MXNET_SERVING_BUCKETS``), every request
  padded up to the smallest bucket that fits via :func:`io.pad_arrays`
  (rows sliced back off the outputs, the partial-last-batch mechanism from
  the fused-step PR). Steady traffic therefore touches exactly
  ``len(buckets)`` compiled programs, no matter how ragged the sizes.
* **the named compile cache** — every bucket executable lives in ONE
  :class:`~mxnet_tpu.compile_cache.CompileCache` named ``"serving"``
  (shared across buckets; the per-executor cache is re-pointed at it), so
  warmup can pin the exact compile count and steady state can assert
  zero new misses (``compile.cache_hits/_misses`` counters, unconditional).

Weights are SHARED across bucket executors (the same NDArray objects are
bound into each), so N buckets cost N compiled programs but one copy of
the parameters. Inference never writes them.

Execution is serialized on one lock: a single device runs one computation
at a time — serving concurrency comes from batching (the
:class:`~mxnet_tpu.serving.batcher.DynamicBatcher`), not parallel dispatch.

Cross-bucket determinism note (pinned by test_serving.py): for row-
independent graphs, XLA:CPU produces bit-identical per-row results across
bucket sizes >= 2 and regardless of row position or padding; batch size 1
lowers to the vector codepath and can differ by 1 ulp. A ladder starting
at 2 gives bit-exact responses whether or not requests were coalesced.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import analysis
from .. import memory
from .. import ndarray as nd
from .. import telemetry
from .. import tracing
from ..base import MXNetError, getenv, register_env
from ..compile_cache import CompileCache
from ..io.io import DataDesc, pad_arrays

__all__ = ["Predictor", "bucket_ladder"]

register_env("MXNET_SERVING_BUCKETS", "1,2,4,8,16,32",
             "serving batch-size bucket ladder (comma-separated ints): "
             "every request/coalesced batch pads up to the smallest bucket "
             "that fits, so steady traffic reuses len(buckets) executables")
register_env("MXNET_SUBGRAPH_BACKEND", "TPU_FUSE",
             "subgraph rewrite backend auto-applied by Predictor.load / "
             "Predictor.from_module (conv+bn(+relu) folding for inference); "
             "set to NONE or 0 to opt out. Training-side bind only applies "
             "it when the variable is EXPLICITLY set (symbol.simple_bind "
             "semantics unchanged)")


def _serving_fused(symbol, arg_params, aux_params):
    """Apply the serving-side subgraph backend (default ``TPU_FUSE``,
    opt-out ``MXNET_SUBGRAPH_BACKEND=NONE``) to a checkpointed symbol and
    migrate parameters across the rewrite: BatchNorm moving statistics are
    *auxiliary* states of the original graph but plain *arguments* of the
    folded `_fused_conv_bn_relu` node, so they move from ``aux_params``
    into ``arg_params``. Returns (symbol, arg_params, aux_params) —
    unchanged when the backend is disabled, unregistered, or matches
    nothing."""
    import os

    backend = os.environ.get("MXNET_SUBGRAPH_BACKEND", "TPU_FUSE")
    if not backend or backend in ("NONE", "none", "0"):
        return symbol, arg_params, aux_params
    from ..symbol.subgraph import build_subgraph, list_subgraph_backends

    if backend not in list_subgraph_backends():
        return symbol, arg_params, aux_params
    fused = build_subgraph(symbol, backend)
    fused_args = set(fused.list_arguments())
    arg_params = dict(arg_params or {})
    aux_params = dict(aux_params or {})
    for name in list(aux_params):
        if name in fused_args and name not in arg_params:
            arg_params[name] = aux_params.pop(name)
    return fused, arg_params, aux_params


def bucket_ladder(buckets=None, env_var="MXNET_SERVING_BUCKETS"):
    """Normalize a bucket spec (None -> the ``env_var`` knob, a
    comma-separated string, or any int iterable) into an ascending,
    deduplicated tuple of positive sizes. ``env_var`` names the knob in
    error messages — the generation plane's ``prefill_ladder`` parses its
    ``MXNET_GENERATION_PREFILL_BUCKETS`` through here too."""
    if buckets is None:
        buckets = getenv(env_var)
    if isinstance(buckets, str):
        try:
            buckets = [int(tok) for tok in buckets.replace(" ", "").split(",")
                       if tok]
        except ValueError:
            raise MXNetError(
                f"{env_var} must be comma-separated ints, got {buckets!r}")
    out = tuple(sorted({int(b) for b in buckets}))
    if not out or out[0] < 1:
        raise MXNetError(f"serving buckets must be positive ints, got {out}")
    return out


class Predictor:
    """A ``(symbol, params)`` checkpoint bound for concurrent inference.

    Parameters
    ----------
    symbol : Symbol
        The inference graph. Loss heads are fine — e.g. ``SoftmaxOutput``
        emits probabilities at inference and its label input is bound to
        zeros (any argument ending in ``label`` that has no value in
        ``arg_params`` is treated this way; other unbound arguments raise,
        catching a checkpoint that is missing a weight).
    arg_params / aux_params : dict[str, NDArray]
        Trained parameters, e.g. from ``model.load_checkpoint``.
    data_shapes : list of (name, shape) or DataDesc
        The data inputs; the leading (batch) dimension is a placeholder —
        actual batch dims come from the bucket ladder.
    buckets : str | iterable of int | None
        Bucket ladder override (default ``MXNET_SERVING_BUCKETS``).
    retry_on : tuple of exception types
        What the batcher treats as a transient executor failure
        (``resilience.retry_call`` semantics; deadline always wins).
    """

    def __init__(self, symbol, arg_params, aux_params=None, data_shapes=None,
                 label_shapes=None, buckets=None, ctx=None,
                 retry_on=(OSError,)):
        from ..context import current_context

        if data_shapes is None:
            raise MXNetError(
                "Predictor needs data_shapes=[(name, shape), ...] — the "
                "batch dim is a placeholder, trailing dims bind the graph")
        self._symbol = symbol
        self._ctx = ctx if ctx is not None else current_context()
        self._data_descs = [d if isinstance(d, DataDesc) else DataDesc(*d)
                            for d in data_shapes]
        self._data_names = [d.name for d in self._data_descs]
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self.retry_on = tuple(retry_on)

        unknown = [n for n in self._data_names if n not in self._arg_names]
        if unknown:
            raise MXNetError(f"data inputs {unknown} are not arguments of "
                             f"the symbol ({self._arg_names})")

        def as_nd(v):
            return v if isinstance(v, nd.NDArray) else nd.array(v)

        arg_params = {k: as_nd(v) for k, v in (arg_params or {}).items()}
        self._arg_params = {n: arg_params[n] for n in self._arg_names
                            if n in arg_params and n not in self._data_names}
        self._aux_params = {k: as_nd(v) for k, v in (aux_params or {}).items()
                            if k in self._aux_names}
        missing_aux = [n for n in self._aux_names if n not in self._aux_params]
        if missing_aux:
            # as loud as a missing weight: zeros here would make e.g.
            # BatchNorm normalize with mean=0/var=0 and serve garbage
            # silently
            raise MXNetError(
                f"auxiliary states {missing_aux} have no value in "
                "aux_params — pass the checkpoint's aux_params (serving "
                "them as zeros would silently corrupt inference, e.g. "
                "BatchNorm moving statistics)")

        # label-style inputs: bound to zeros, shape (bucket,) + trail.
        # Explicit label_shapes wins; otherwise only *label-named* leftovers
        # qualify — any OTHER unbound argument is a missing weight and must
        # fail loudly, not silently serve zeros.
        self._label_trails = {}
        for l in (label_shapes or []):
            d = l if isinstance(l, DataDesc) else DataDesc(*l)
            self._label_trails[d.name] = tuple(d.shape[1:])
        missing = [n for n in self._arg_names
                   if n not in self._data_names
                   and n not in self._arg_params
                   and n not in self._label_trails]
        for n in list(missing):
            if n.endswith("label"):
                self._label_trails[n] = ()
                missing.remove(n)
        if missing:
            raise MXNetError(
                f"arguments {missing} have no value in arg_params and are "
                "not data inputs; pass them in arg_params (weights) or "
                "label_shapes (dummy label inputs)")

        # SPMD serving bind (MXNET_SPMD, parallel/spmd.py): the bound
        # weights are sharded IN PLACE over the one mesh before any
        # bucket executor binds them — every bucket shares the same
        # 1/N-resident buffers, GSPMD propagates the layout through the
        # for_training=False jits. Plan failure logs and stays
        # replicated (the serving twin of Module's _spmd_failed)
        self._spmd_mesh = None
        self._spmd_specs = None
        from ..parallel.spmd import spmd_enabled

        if spmd_enabled():
            from ..log import get_logger
            from ..parallel.spmd import place_serving_params

            try:
                self._spmd_mesh, self._spmd_specs = place_serving_params(
                    symbol, self._arg_params, self._aux_params)
            except Exception as e:  # noqa: BLE001 — bad spec/graph must
                # serve replicated, never fail the bind
                get_logger("mxnet_tpu.serving").warning(
                    "SPMD serving bind unavailable (%r); serving "
                    "replicated weights", e)

        self._buckets = bucket_ladder(buckets)
        self._cache = CompileCache("serving")
        self._execs = {}
        self._lock = analysis.make_rlock("serving.predictor")
        self._weights_version = 0     # bumped by swap_weights (rollout)
        # fleet health: /readyz reports warmup state per predictor
        # (serving.warmup sets _warmed; registration is weakly held)
        self._warmed = False
        from .health import attach_predictor

        self.health_name = attach_predictor(self)
        # memory census: the bound parameters are the serving side's
        # weight residency (SHARED across bucket executors — the census
        # dedupes by buffer, so N buckets still count one copy)
        memory.track("weights", list(self._arg_params.values())
                     + list(self._aux_params.values()))

    # -- construction conveniences ------------------------------------------

    @classmethod
    def load(cls, prefix, epoch=None, data_shapes=None, **kwargs):
        """Bind the newest (or given) ``prefix`` checkpoint for serving —
        ``model.load_checkpoint`` semantics, including corrupt-epoch
        fallback when ``epoch`` is None."""
        from ..model import load_checkpoint

        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        if symbol is None:
            raise MXNetError(f"no symbol json found for prefix {prefix!r} "
                             "(need prefix-symbol.json to serve)")
        symbol, arg_params, aux_params = _serving_fused(
            symbol, arg_params, aux_params)
        return cls(symbol, arg_params, aux_params,
                   data_shapes=data_shapes, **kwargs)

    @classmethod
    def from_module(cls, module, buckets=None, **kwargs):
        """Wrap a bound, initialized ``Module``. The Predictor takes COPIES
        of the current parameters (``get_params``), so continuing to train
        the module never mutates a live server."""
        if not (module.binded and module.params_initialized):
            raise MXNetError("from_module needs a bound module with "
                             "initialized parameters")
        arg_params, aux_params = module.get_params()
        kwargs.setdefault("label_shapes", getattr(module, "_label_shapes", None))
        symbol, arg_params, aux_params = _serving_fused(
            module.symbol, arg_params, aux_params)
        return cls(symbol, arg_params, aux_params,
                   data_shapes=module.data_shapes, buckets=buckets, **kwargs)

    # -- properties ----------------------------------------------------------

    @property
    def buckets(self):
        return self._buckets

    @property
    def max_batch(self):
        return self._buckets[-1]

    @property
    def data_names(self):
        return list(self._data_names)

    @property
    def output_names(self):
        return list(self._output_names)

    @property
    def cache(self):
        """The shared ``"serving"`` :class:`CompileCache` — ``.misses`` is
        the exact number of programs compiled so far."""
        return self._cache

    def bucket_for(self, rows):
        """Smallest bucket >= ``rows``, or None (caller chunks by
        :attr:`max_batch`)."""
        for b in self._buckets:
            if b >= rows:
                return b
        return None

    # -- binding -------------------------------------------------------------

    def _bind_bucket(self, bucket):
        """The ``for_training=False`` executor of one bucket (bound lazily;
        compile happens on its first forward). Weights/aux are the SHARED
        param NDArrays; its compile cache is re-pointed at the predictor's
        ``"serving"`` cache so all bucket compiles land in one ledger."""
        exec_ = self._execs.get(bucket)
        if exec_ is not None:
            return exec_
        with self._lock:
            exec_ = self._execs.get(bucket)
            if exec_ is not None:
                return exec_
            from ..symbol.executor import Executor

            shape_kwargs = {d.name: (bucket,) + tuple(d.shape[1:])
                            for d in self._data_descs}
            shape_kwargs.update({n: (bucket,) + trail
                                 for n, trail in self._label_trails.items()})
            arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shape_kwargs)
            dtypes = {d.name: d.dtype for d in self._data_descs}
            args = {}
            for n, s in zip(self._arg_names, arg_shapes):
                p = self._arg_params.get(n)
                if p is not None:
                    if tuple(p.shape) != tuple(s):
                        raise MXNetError(
                            f"parameter {n!r} has shape {tuple(p.shape)} but "
                            f"the graph infers {tuple(s)} — wrong checkpoint "
                            "for this symbol/data_shapes?")
                    args[n] = p
                else:
                    args[n] = nd.zeros(s, dtype=dtypes.get(n, "float32"))
            auxs = {}
            for n, s in zip(self._aux_names, aux_shapes):
                a = self._aux_params[n]
                if tuple(a.shape) != tuple(s):
                    raise MXNetError(
                        f"auxiliary state {n!r} has shape {tuple(a.shape)} "
                        f"but the graph infers {tuple(s)} — wrong "
                        "checkpoint for this symbol/data_shapes?")
                auxs[n] = a
            exec_ = Executor(self._symbol, self._ctx, args=args,
                             grad_req="null", aux_states=auxs)
            exec_._cache = self._cache
            self._execs[bucket] = exec_
            return exec_

    # -- compute -------------------------------------------------------------

    def _run(self, bucket, arrays):
        """Forward ``arrays`` (<= bucket rows, aligned with data_names)
        through the bucket executor; returns the UNSLICED outputs (bucket
        rows). Outputs are materialized before delivery so an execution
        failure surfaces HERE — retryable and attributable — never in a
        caller thread touching a lazy value later."""
        return self._run_wait(self._run_dispatch(bucket, arrays))

    def _run_dispatch(self, bucket, arrays):
        """Dispatch half of :meth:`_run`: pad + forward, NO drain. The
        returned pending handle must be settled with :meth:`_run_wait`;
        between the two the caller owns the host — the batcher's overlap
        lane stages its NEXT flush there while this one executes."""
        exec_ = self._bind_bucket(bucket)
        with tracing.span("serving.pad", cat="serving", bucket=bucket):
            padded, _ = pad_arrays(list(arrays), bucket)
        feed = dict(zip(self._data_names, padded))
        t0 = time.perf_counter() if telemetry._enabled else 0.0
        with self._lock, tracing.span("serving.forward", cat="serving",
                                      bucket=bucket):
            outs = list(exec_.forward(is_train=False, **feed))
        return outs, padded, t0

    def _run_wait(self, pending):
        """Drain a :meth:`_run_dispatch` handle: block on the outputs so
        an execution failure surfaces here (retryable), then account the
        batch. ``serving.compute_us`` spans dispatch->drained."""
        import jax

        outs, padded, t0 = pending
        jax.block_until_ready([o._data for o in outs])
        # in-flight batch residency: weak refs, swept as batches retire
        memory.track_transient("serving_batches", padded + outs)
        if telemetry._enabled:
            telemetry.histogram("serving.compute_us").record(
                (time.perf_counter() - t0) * 1e6)
        return outs

    # -- weight rollout ------------------------------------------------------

    @property
    def weights_version(self):
        """Version of the currently-bound weight set (0 until the first
        :meth:`swap_weights`)."""
        return self._weights_version

    def swap_weights(self, arg_params, aux_params=None, version=None):
        """Atomic zero-downtime weight flip: substitute new buffers into
        the SHARED param NDArrays every bucket executor binds, under the
        serving lock — an in-flight batch finishes on the old weights
        (``_run`` holds the same lock through its forward), the next
        flush reads the new ones. The incoming arrays are cast to the
        bound dtypes and must match the bound shapes exactly, so every
        warmed ``CompileCache("serving")`` entry is reused untouched:
        the swap compiles NOTHING (executor signatures are shape/dtype
        only, and weights are non-donated arguments).

        ``arg_params`` may be a :class:`~.rollout.WeightSet` (its version
        wins unless ``version`` is passed). Returns the new version, or
        None when ``version`` equals the current one (idempotent
        re-publish). Under an SPMD serving bind the new buffers are
        re-placed with the ORIGINAL sharding specs, so per-device
        residency is preserved across the flip."""
        import jax

        if hasattr(arg_params, "arg_params") and hasattr(arg_params,
                                                         "version"):
            ws = arg_params
            aux_params = ws.aux_params if aux_params is None else aux_params
            version = ws.version if version is None else version
            arg_params = ws.arg_params
        new_arg = dict(arg_params or {})
        new_aux = dict(aux_params or {})
        # mirror _serving_fused's aux->arg migration: a checkpoint
        # published by the training loop still carries e.g. BatchNorm
        # moving stats as aux, but the fused serving graph binds them
        # as plain arguments
        for n in list(new_aux):
            if n in self._arg_params and n not in new_arg:
                new_arg[n] = new_aux.pop(n)
        missing = ([n for n in self._arg_params if n not in new_arg]
                   + [n for n in self._aux_params if n not in new_aux])
        if missing:
            raise MXNetError(
                f"swap_weights: bound parameters {missing} are missing "
                "from the new weight set — a hot swap must cover every "
                "bound array (partial updates would serve a chimera)")
        staged = []
        for tgt_map, src, spmd in ((self._arg_params, new_arg, True),
                                   (self._aux_params, new_aux, False)):
            for n, tgt in tgt_map.items():
                arr = src[n]
                arr = (arr.asnumpy() if hasattr(arr, "asnumpy")
                       else np.asarray(arr))
                if tuple(arr.shape) != tuple(tgt.shape):
                    raise MXNetError(
                        f"swap_weights: parameter {n!r} has shape "
                        f"{tuple(arr.shape)} but the bound executors "
                        f"expect {tuple(tgt.shape)} — identical shapes/"
                        "dtypes are what make the swap compile-free")
                staged.append((n, tgt, arr, spmd))
        with self._lock:
            if version is not None and version == self._weights_version:
                if telemetry._enabled:
                    telemetry.counter("serving.weight_swap_noops").inc()
                return None
            for n, tgt, arr, spmd in staged:
                arr = arr.astype(tgt.dtype, copy=False)
                if self._spmd_mesh is not None:
                    from jax.sharding import NamedSharding, PartitionSpec

                    spec = (self._spmd_specs.get(n)
                            if spmd and self._spmd_specs else None)
                    data = jax.device_put(
                        arr, NamedSharding(self._spmd_mesh,
                                           spec if spec is not None
                                           else PartitionSpec()))
                else:
                    import jax.numpy as jnp

                    data = jnp.asarray(arr)
                tgt._data = data
            self._weights_version = (self._weights_version + 1
                                     if version is None else int(version))
            swapped_to = self._weights_version
        if telemetry._enabled:
            telemetry.counter("serving.weight_swaps").inc()
            telemetry.gauge("serving.weights_version").set(swapped_to)
        from .. import health

        if health._enabled:
            health.event("rollout_swap", predictor=self.health_name,
                         version=swapped_to)
        return swapped_to

    def warm_bucket(self, bucket):
        """Compile-ahead one bucket: run a zeros batch through it (a cache
        hit if already compiled)."""
        if bucket not in self._buckets:
            raise MXNetError(f"bucket {bucket} not in ladder {self._buckets}")
        zeros = [nd.zeros((bucket,) + tuple(d.shape[1:]), dtype=d.dtype)
                 for d in self._data_descs]
        self._run(bucket, zeros)

    def warmup(self, buckets=None):
        """Compile every bucket ahead of traffic — see
        :func:`mxnet_tpu.serving.warmup`."""
        from .warmup import warmup

        return warmup(self, buckets=buckets)

    def predict(self, data, always_output_list=False):
        """Synchronous single-caller inference: pad ``data`` up to its
        bucket (requests larger than :attr:`max_batch` are chunked), run,
        slice the padding back off. Returns one NDArray when the symbol has
        one output (list otherwise, or always with ``always_output_list``).
        Thread-safe; for concurrent traffic prefer a
        :class:`~mxnet_tpu.serving.batcher.DynamicBatcher`, which coalesces
        callers into shared batches instead of serializing them."""
        arrays = self._as_arrays(data)
        n = int(arrays[0].shape[0])
        parts, off = [], 0
        while off < n:
            take = min(n - off, self.max_batch)
            chunk = [a[off:off + take] for a in arrays]
            outs = self._run(self.bucket_for(take), chunk)
            parts.append([o[0:take] for o in outs])
            off += take
        if len(parts) == 1:
            outs = parts[0]
        else:
            outs = [nd.concatenate([p[i] for p in parts], axis=0)
                    for i in range(len(parts[0]))]
        return self._wrap_outputs(outs, always_output_list)

    # -- helpers -------------------------------------------------------------

    def _as_arrays(self, data):
        """Normalize one request (array, list/tuple aligned with
        data_names, or name->array dict) into a validated NDArray list."""
        if isinstance(data, dict):
            try:
                arrays = [data[n] for n in self._data_names]
            except KeyError as e:
                raise MXNetError(f"request is missing data input {e}")
        elif isinstance(data, (list, tuple)):
            arrays = list(data)
        else:
            arrays = [data]
        if len(arrays) != len(self._data_names):
            raise MXNetError(f"expected {len(self._data_names)} data inputs "
                             f"({self._data_names}), got {len(arrays)}")
        arrays = [a if isinstance(a, nd.NDArray) else nd.array(a)
                  for a in arrays]
        rows = {int(a.shape[0]) for a in arrays}
        if len(rows) != 1:
            raise MXNetError(f"ragged row counts across data inputs: {rows}")
        if rows.pop() == 0:
            raise MXNetError("empty request (0 rows)")
        for a, d in zip(arrays, self._data_descs):
            if tuple(a.shape[1:]) != tuple(d.shape[1:]):
                raise MXNetError(
                    f"input {d.name!r}: trailing shape {tuple(a.shape[1:])} "
                    f"does not match bound {tuple(d.shape[1:])}")
        return arrays

    def _wrap_outputs(self, outs, always_output_list=False):
        if len(outs) == 1 and not always_output_list:
            return outs[0]
        return list(outs)

    def stats(self):
        """{cache snapshot, ladder, bound buckets} — the serving half of
        ``compile_cache.stats()``."""
        return {"cache": self._cache.snapshot(),
                "buckets": list(self._buckets),
                "bound": sorted(self._execs),
                "weights_version": self._weights_version}
