"""NDArray-level op dispatch + autogenerated op namespaces.

Parity: the reference generates `mx.nd.*` functions at import time from the
C-API op registry (`python/mxnet/ndarray/register.py`, `_ctypes/ndarray.py:65`
`_imperative_invoke` → `MXImperativeInvokeEx` → `Imperative::Invoke`,
`src/c_api/c_api_ndarray.cc:132`, `src/imperative/imperative.cc:108`).

Here the same pipeline is: generated python fn → ``invoke_nd`` → compile-cached
one-op XLA program (+ tape recording via jax.vjp when autograd is on).
"""
from __future__ import annotations

import inspect
import sys
import types

import numpy as _np
import jax
import jax.numpy as jnp

from ..ops import registry as _reg
from .ndarray import NDArray

__all__ = ["invoke_nd", "populate_op_namespaces"]

_sig_cache = {}

# --- control-flow free-variable capture ------------------------------------
# While tracing a control-flow body (contrib.foreach / while_loop / cond),
# closure-captured NDArrays (layer weights used inside the body) must become
# explicit inputs of the traced function or reverse-mode grads to them are
# silently lost.  Control flow pushes frames here; every NDArray arg that
# reaches op dispatch is (a) substituted by its tracer if the frame maps it,
# or (b) collected as a discovered free variable during the discovery pass.
# This is the eager analogue of the reference's subgraph free-variable scan
# (`src/operator/control_flow.cc` input deduction).
import threading as _threading

_cf_state = _threading.local()


def _cf_frames():
    fr = getattr(_cf_state, "frames", None)
    if fr is None:
        fr = _cf_state.frames = []
    return fr


def _resolve_nd_data(a):
    frames = getattr(_cf_state, "frames", None)
    if frames:
        for fr in reversed(frames):
            sub = fr["subst"].get(id(a))
            if sub is not None:
                return sub
        top = frames[-1]
        if top["collect"] is not None and \
                not isinstance(a._data, jax.core.Tracer):
            top["collect"].setdefault(id(a), a)
    return a._data


def _op_attr_params(op):
    """Names of the op fn's keyword (defaulted) params, in declaration order,
    plus whether the fn takes *varargs tensors. Used to bind positional
    scalars the MXNet way (`nd.random.uniform(0, 1, shape=...)`,
    `nd.clip(x, 0, 1)`)."""
    key = id(op)
    cached = _sig_cache.get(key)
    if cached is None:
        try:
            sig = inspect.signature(op.fn)
            attr_params = [p.name for p in sig.parameters.values()
                           if p.default is not inspect.Parameter.empty
                           and p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                                          inspect.Parameter.KEYWORD_ONLY)]
            varargs = any(p.kind == inspect.Parameter.VAR_POSITIONAL
                          for p in sig.parameters.values())
        except (TypeError, ValueError):
            attr_params, varargs = [], True
        cached = (attr_params, varargs)
        _sig_cache[key] = cached
    return cached


def invoke_nd(op_name, *args, out=None, name=None, **kwargs):
    from .. import profiler as _prof

    # per-op profiling (the ProfileOperator engine wrap,
    # `threaded_engine.h:353`): jax dispatch is ASYNC, so the default
    # measurement is host dispatch cost and is labelled cat="dispatch".
    # With profile_all (or the older profile_sync spelling) the result is
    # blocked on, so the duration is true op completion time and the event
    # is labelled cat="operator" — the label never lies about what was
    # timed.
    if _prof.is_running():
        import time as _time

        t0 = _time.perf_counter()
        res = _invoke_nd_impl(op_name, *args, out=out, name=name, **kwargs)
        sync = _prof._config.get("profile_all") or _prof._config.get("profile_sync")
        if sync:
            import jax as _jax

            try:
                first = res[0] if isinstance(res, (list, tuple)) else res
                _jax.block_until_ready(first._data)
            except Exception:  # noqa: BLE001
                pass
        _prof.record_op(op_name, (_time.perf_counter() - t0) * 1e6,
                        cat="operator" if sync else "dispatch")
        return res
    return _invoke_nd_impl(op_name, *args, out=out, name=name, **kwargs)


def _invoke_nd_impl(op_name, *args, out=None, name=None, **kwargs):
    from .. import autograd
    from .. import random as _random
    from ..lazy import graph as _lazy

    op = _reg.get_op(op_name)
    explicit_ctx = kwargs.pop("ctx", None)
    if isinstance(explicit_ctx, str):
        from ..context import Context

        devt, _, devid = explicit_ctx.replace(")", "").partition("(")
        explicit_ctx = Context(devt, int(devid or 0))
    kwargs = {k: v for k, v in kwargs.items() if v is not None or k in ("axis",)}

    # lazy capture (MXNET_LAZY=1): inputs resolve to their pending graph
    # slots instead of concrete buffers; control-flow capture frames and
    # explicit-ctx creation ops (which need a real placement) stay eager
    lazy_graph = None
    if _lazy.enabled() and explicit_ctx is None \
            and not getattr(_cf_state, "frames", None):
        lazy_graph = _lazy.graph_for_thread()

    attr_params, has_varargs = _op_attr_params(op)
    nd_inputs = []  # aligned with `arrays`; None for non-NDArray (rng key) slots
    arrays = []
    scalar_cursor = 0
    if op.needs_rng:
        arrays.append(_random.next_key())
        nd_inputs.append(None)
    # A positional None keeps its slot when a LATER positional arg is an
    # array (jit/vjp treat None as an empty pytree), so an absent optional
    # tensor input (e.g. CTCLoss data_lengths) cannot shift the next one
    # into its place.  Trailing Nones are still dropped (gluon passes
    # bias=None / sample_weight=None liberally).
    def _is_arr(a):
        return isinstance(a, (NDArray, _np.ndarray)) or (
            hasattr(a, "shape") and hasattr(a, "dtype"))
    last_arr = -1
    for i, a in enumerate(args):
        if _is_arr(a):
            last_arr = i
    for i, a in enumerate(args):
        if isinstance(a, NDArray):
            # the lazy graph takes its own pending buffers as they are;
            # a recorded hybridized call's pending output materializes
            arrays.append(a._buf if lazy_graph is not None
                          and type(a._buf) is _lazy.LazyArray
                          else _resolve_nd_data(a))
            nd_inputs.append(a)
        elif a is None:
            if i < last_arr:
                arrays.append(None)
                nd_inputs.append(None)
            continue
        elif isinstance(a, (int, float, bool, str, tuple, list)) and not has_varargs:
            # positional scalar/shape → bind to next free keyword attr
            while scalar_cursor < len(attr_params) and attr_params[scalar_cursor] in kwargs:
                scalar_cursor += 1
            if scalar_cursor < len(attr_params):
                kwargs[attr_params[scalar_cursor]] = a
                scalar_cursor += 1
            else:
                arrays.append(jnp.asarray(a))
                nd_inputs.append(None)
        elif isinstance(a, (int, float, _np.ndarray, list, tuple)):
            arrays.append(jnp.asarray(a))
            nd_inputs.append(None)
        else:  # jax array / tracer passthrough (graph capture)
            arrays.append(a)
            nd_inputs.append(None)
    # schema check: unknown kwargs are errors, not silent no-ops
    # (DMLC_DECLARE_PARAMETER Init() role)
    _reg.validate_attrs(op, kwargs)
    if op.needs_mode and "_train" not in kwargs:
        kwargs["_train"] = bool(autograd.is_training())

    recording = autograd.is_recording()
    sparse_pb = None
    if recording and op.sparse_vjp is not None:
        # storage-aware backward (FInferStorageType role): the op elects a
        # row-sparse pullback for these inputs/attrs instead of dense
        # jax.vjp. The factory inspects concrete arrays, so such an op is
        # a per-op eager fallback under lazy capture.
        if lazy_graph is not None:
            arrays = _lazy.force_list(arrays)
            lazy_graph = None
        sparse_pb = op.sparse_vjp(arrays, kwargs)
    captured = None
    if lazy_graph is not None:
        captured = lazy_graph.record(op, arrays, kwargs, want_vjp=recording)
        if captured is None:
            # per-op fallback (unjittable op, hysteresis cool-off, tracer
            # input): run THIS op eagerly on materialized inputs; the rest
            # of the pending segment stays captured
            arrays = _lazy.force_list(arrays)
    if captured is not None:
        outs, vjp = captured
    elif recording and sparse_pb is None:
        outs, vjp = _reg.invoke_with_vjp(op_name, *arrays, **kwargs)
    elif recording:
        outs = _reg.invoke_raw(op_name, *arrays, **kwargs)
        vjp = autograd._PyPullback(lambda cts, _pb=sparse_pb: _pb(cts))
    else:
        outs = _reg.invoke_raw(op_name, *arrays, **kwargs)
        vjp = None

    single = not isinstance(outs, (tuple, list))
    outs_t = (outs,) if single else tuple(outs)

    # write mutated aux outputs back into their input NDArrays (BatchNorm
    # moving stats, optimizer states) — reference FMutateInputs semantics.
    n_user = len(outs_t)
    if op.mutate_aux:
        # static tuple, or callable(attrs) for variable-arity ops
        # (multi_sgd_* mutate one state per aggregated weight)
        aux_idx = (op.mutate_aux(kwargs) if callable(op.mutate_aux)
                   else op.mutate_aux)
        n_aux = len(aux_idx)
        n_user = len(outs_t) - n_aux
        offset = 1 if op.needs_rng else 0
        for j, in_idx in enumerate(aux_idx):
            target = nd_inputs[in_idx + offset]
            if target is not None:
                target._data = outs_t[n_user + j]

    ctx = next((a._ctx for a in nd_inputs if a is not None), None)
    place = None
    if ctx is None:
        from ..context import current_context

        ctx = explicit_ctx or current_context()
        # creation op with an explicit context: actually place the buffer
        # there (the label must not lie about the device)
        try:
            tgt = ctx.jax_device
            if not recording and outs_t and hasattr(outs_t[0], "devices") and \
                    tgt not in outs_t[0].devices():
                place = tgt
        except Exception:
            place = None

    out_nds = [NDArray(jax.device_put(o, place) if place is not None else o, ctx)
               for o in outs_t[:n_user]]

    if recording and vjp is not None:
        autograd._record_node(vjp, nd_inputs, out_nds, [
            jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs_t
        ])

    # out= in-place write (reference kWriteTo into provided output): share
    # the (possibly still-pending) buffer — reading src._data here would
    # be a materialization barrier and flush a 1-op segment per out= call
    if out is not None:
        outs_list = out if isinstance(out, (list, tuple)) else [out]
        for tgt, src in zip(outs_list, out_nds):
            tgt._data = src._buf
        return out if not isinstance(out, (list, tuple)) else list(outs_list)

    if len(out_nds) == 1:
        return out_nds[0]
    return out_nds


def _make_op_func(op_name, func_name):
    def fn(*args, **kwargs):
        return invoke_nd(op_name, *args, **kwargs)

    fn.__name__ = func_name
    op = _reg.get_op(op_name)
    fn.__doc__ = (op.doc or f"TPU-native op `{op_name}`.") + _reg.param_doc(op)
    return fn


def populate_op_namespaces(root_module_name="mxnet_tpu.ndarray", make_func=_make_op_func):
    """Create nd top-level / nd.op / nd._internal / nd.linalg / nd.random /
    nd.contrib function namespaces from the registry — the analogue of the
    reference's import-time codegen (`_init_ops`)."""
    root = sys.modules[root_module_name]
    sub = {}
    for ns in ("op", "_internal", "linalg", "random", "contrib", "image"):
        modname = f"{root_module_name}.{ns}"
        mod = sys.modules.get(modname)
        if mod is None:
            mod = types.ModuleType(modname)
            sys.modules[modname] = mod
        sub[ns] = mod
        setattr(root, ns, mod)

    seen = {}
    for name in _reg.list_ops():
        op = _reg.get_op(name)
        if id(op) in seen and name in seen[id(op)]:
            continue
        seen.setdefault(id(op), set()).add(name)
        f = make_func(op.name, name.lstrip("_"))
        setattr(sub["op"], name, f)
        if name.startswith("_linalg_"):
            setattr(sub["linalg"], name[len("_linalg_"):], f)
        elif name.startswith("_random_"):
            setattr(sub["random"], name[len("_random_"):], f)
        elif name.startswith("_sample_"):
            setattr(sub["random"], name[1:], f)  # nd.random.sample_uniform etc.
        elif name.startswith("_contrib_"):
            setattr(sub["contrib"], name[len("_contrib_"):], f)
        elif name.startswith("_image_"):
            setattr(sub["image"], name[len("_image_"):], f)
        if name.startswith("_"):
            setattr(sub["_internal"], name, f)
        else:
            if not hasattr(root, name):
                setattr(root, name, f)
            setattr(sub["op"], name, f)
    # convenience: random top-levels mirrored (mx.nd.random_uniform legacy names)
    for legacy in ("uniform", "normal", "randint"):
        if hasattr(sub["random"], legacy):
            setattr(root, legacy, getattr(sub["random"], legacy))
