"""Operator registry — the TPU-native answer to the reference's NNVM op
registry (`NNVM_REGISTER_OP` + `FCompute` dispatch, reference
`include/mxnet/op_attr_types.h:207-312`, `src/operator/`).

Every op is a **pure jax function** ``fn(*arrays, **attrs) -> array | tuple``.
There is no per-op kernel scheduling: invoking an op eagerly compiles (and
caches) a one-op XLA computation, exactly the "eager-by-compilation" design
from SURVEY.md §7 stage 2; under graph capture (CachedOp / Symbol executor)
the same fns are traced into one whole-graph XLA program — the limit case of
the reference's engine bulking (`threaded_engine.h:413`).

Shape/type inference (the reference's FInferShape/FInferType,
`infer_graph_attr_pass.cc:94,372`) is obtained for free via
``jax.eval_shape`` on the same fn — one source of truth.
"""
from __future__ import annotations

import functools
import threading

import jax

__all__ = ["Op", "register", "get_op", "list_ops", "invoke", "alias"]

_OPS: dict[str, "Op"] = {}


class Op:
    """A registered operator."""

    __slots__ = ("name", "fn", "num_outputs", "mutate_aux", "wrap_kwargs", "doc", "needs_rng",
                 "needs_mode", "tensor_opts", "sparse_vjp", "eager_only", "open_attrs",
                 "_schema_cache")

    def __init__(self, name, fn, num_outputs=1, mutate_aux=None, wrap_kwargs=None, needs_rng=False,
                 needs_mode=False, tensor_opts=(), sparse_vjp=None, eager_only=False,
                 open_attrs=False):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs  # int or callable(attrs)->int
        # RNG-consuming ops (samplers, Dropout): fn takes a jax PRNG key as its
        # FIRST array argument; the frontend fetches it from the active key
        # provider (mxnet_tpu.random) — the stateless-TPU-PRNG rendering of the
        # reference's ResourceRequest::kRandom (`include/mxnet/resource.h:38`).
        self.needs_rng = needs_rng
        # Train/predict-polymorphic ops (Dropout, BatchNorm): the frontend
        # injects `_train=autograd.is_training()` as a static attr so the
        # compile cache keys on it (reference: OpContext::is_train,
        # `include/mxnet/op_attr_types.h:67`).
        self.needs_mode = needs_mode
        # indices of *inputs* that receive extra outputs written back in-place
        # (optimizer ops, BatchNorm moving stats) — the functional rendering of
        # the reference's FMutateInputs (`op_attr_types.h`).
        self.mutate_aux = mutate_aux
        self.wrap_kwargs = wrap_kwargs  # canonicalize attrs before hashing/jit
        # names of OPTIONAL tensor inputs (defaulted-to-None fn params that
        # take arrays, e.g. CTCLoss data_lengths/label_lengths).  The
        # frontends keep their positional slots aligned (None placeholders in
        # nd, `__opt_in__` keyword binding in symbol) so an absent earlier
        # optional cannot shift a later one into its slot.
        self.tensor_opts = tuple(tensor_opts)
        # optional storage-type-aware pullback factory (the FInferStorageType
        # role, `include/mxnet/op_attr_types.h`): called (arrays, attrs) at
        # record time; returning a pullback makes backward emit row_sparse
        # cotangents for this op instead of dense ones; returning None keeps
        # the dense jax.vjp path.
        self.sparse_vjp = sparse_vjp
        # data-dependent output shape (boolean_mask): XLA cannot compile it,
        # so the op runs un-jitted on concrete arrays and raises inside
        # traced graphs (documented divergence from the reference's
        # dynamic-shape support on CPU)
        self.eager_only = eager_only
        # ops forwarding arbitrary user kwargs (Custom → CustomOpProp
        # constructors) opt out of strict-kwargs validation
        self.open_attrs = open_attrs
        self._schema_cache = None
        self.doc = fn.__doc__

    def n_out(self, attrs):
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def __repr__(self):
        return f"Op({self.name})"


def register(name, aliases=(), num_outputs=1, mutate_aux=None, wrap_kwargs=None, needs_rng=False,
             needs_mode=False, tensor_opts=(), sparse_vjp=None, eager_only=False,
             open_attrs=False):
    """Decorator: register a jax fn as operator ``name`` (+ aliases).

    ``eager_only`` (dynamic-shape ops, e.g. boolean_mask): the op bypasses
    the one-op jit cache and runs on concrete arrays. Such an op MUST be
    differentiable in its FIRST tensor input only — the autograd path
    closes over inputs 1.. as constants and returns None cotangents for
    them (they are shape-determining indices/masks by construction)."""

    def deco(fn):
        op = Op(name, fn, num_outputs=num_outputs, mutate_aux=mutate_aux, wrap_kwargs=wrap_kwargs,
                needs_rng=needs_rng, needs_mode=needs_mode, tensor_opts=tensor_opts,
                sparse_vjp=sparse_vjp, eager_only=eager_only, open_attrs=open_attrs)
        _OPS[name] = op
        for a in aliases:
            _OPS[a] = op
        return fn

    return deco


def alias(name, target):
    _OPS[name] = _OPS[target]


def get_op(name):
    op = _OPS.get(name)
    if op is None:
        raise AttributeError(f"Operator '{name}' is not registered")
    return op


def list_ops():
    return sorted(_OPS)


# Keys meaningful to the dispatch/frontend layer rather than any op fn.
_FRAMEWORK_ATTRS = frozenset({"name", "attr", "out", "ctx", "_train", "__opt_in__"})
# Reference performance-hint params (DMLC-declared on many ops) with no TPU
# meaning: accepted and ignored by design — they cannot change results, XLA
# owns scheduling/workspace. Semantic params are NEVER in this set.
_PERF_HINT_ATTRS = frozenset({"cudnn_off", "cudnn_tune", "workspace",
                              "cudnn_algo_verbose"})


def attr_schema(op):
    """The op's declared parameter schema, derived from its fn signature —
    the single source of truth (the `DMLC_DECLARE_PARAMETER` role,
    reference `src/operator/nn/convolution-inl.h`): {name: default} for
    every keyword (defaulted) parameter, None when the fn is fully open
    (*args/**kwargs only, e.g. add_n)."""
    cached = getattr(op, "_schema_cache", None)
    if cached is not None:
        return cached or None
    import inspect

    try:
        sig = inspect.signature(op.fn)
    except (TypeError, ValueError):
        op._schema_cache = {}
        return None
    params = list(sig.parameters.values())
    named = [p for p in params if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                                             inspect.Parameter.KEYWORD_ONLY)]
    if op.needs_rng and named and named[0].name == "key":
        # the PRNG key is injected by the frontend, never user-facing
        named = named[1:]
    if not named:
        op._schema_cache = {}
        return None
    schema = {p.name: (p.default if p.default is not inspect.Parameter.empty
                       else inspect.Parameter.empty)
              for p in named}
    op._schema_cache = schema
    return schema


def validate_attrs(op, attrs):
    """Reject unknown keyword arguments — the reference's dmlc::Parameter
    Init() throws on unknown/malformed kwargs; silently-ignored typos must
    not train wrong. Called by BOTH frontends (nd + symbol)."""
    if op.open_attrs:
        return  # op forwards arbitrary kwargs (Custom → user prop ctor)
    schema = attr_schema(op)
    if schema is None:
        return
    unknown = [k for k in attrs
               if k not in schema and k not in _FRAMEWORK_ATTRS
               and k not in _PERF_HINT_ATTRS]
    if unknown:
        from ..base import MXNetError

        valid = ", ".join(n for n in schema if not n.startswith("_"))
        raise MXNetError(
            f"operator {op.name}: unknown argument(s) {sorted(unknown)}. "
            f"Valid parameters: [{valid}]")


def param_doc(op):
    """Render the schema as a docstring 'Parameters' section (the role of
    the reference's generated op docs, `python/mxnet/ndarray/register.py`)."""
    schema = attr_schema(op)
    if not schema:
        return ""
    import inspect

    lines = ["", "Parameters (keyword)", "--------------------"]
    for n, d in schema.items():
        if n.startswith("_"):
            continue
        if d is inspect.Parameter.empty:
            lines.append(f"{n} : required tensor input")
        else:
            lines.append(f"{n} : optional, default={d!r}")
    return "\n".join(lines)


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


# The per-op jit caches live in named CompileCaches ("op_eager" for plain
# forwards, "op_vjp" for forward-with-residuals) instead of unbounded
# anonymous lru_caches: attr-churning code (a loop sweeping `axis=` or
# scalar values) used to grow executables without bound or accounting.
# Bounded LRU (MXNET_OP_CACHE_SIZE) + compile_cache.named_stats makes
# op-level compile accounting read exactly like the segment/executor-level
# caches in tools/telemetry_report.py.
_op_caches = {}
_op_caches_lock = threading.Lock()


def _op_cache(name):
    cache = _op_caches.get(name)
    if cache is None:
        with _op_caches_lock:
            cache = _op_caches.get(name)
            if cache is None:
                from ..base import getenv
                from ..compile_cache import CompileCache

                cache = _op_caches[name] = CompileCache(
                    name, maxsize=int(getenv("MXNET_OP_CACHE_SIZE", 1024)),
                    track_memory=False)
    return cache


def _scoped(op, arrays, attrs):
    """``op.fn`` under the op's device-side scope: every instruction it
    lowers to carries the op's name in its ``op_name``. Called only where
    the op is being traced into a program — an outer capture, or the first
    call of its own one-op program — so a steady eager call never enters
    it."""
    with jax.named_scope(op.name):
        return op.fn(*arrays, **attrs)


def _jitted(name, frozen_attrs, backend):
    """One-op XLA computation, cached by (op, attrs); jax caches by shapes.
    This is the eager compile cache — the role CachedOp's signature check
    plays in the reference (`cached_op.cc:295`)."""

    def build():
        op = _OPS[name]
        attrs = dict(frozen_attrs)
        return jax.jit(lambda *arrays: _scoped(op, arrays, attrs))

    return _op_cache("op_eager").get_or_build(
        (name, frozen_attrs, backend), build)


def bound_fn(name, **attrs):
    """The pure fn of op `name` with attrs closed over (un-jitted) — used by
    graph capture, autograd vjp, and eval_shape."""
    op = get_op(name)
    if op.wrap_kwargs is not None:
        attrs = op.wrap_kwargs(attrs)
    fn = op.fn
    # runtime **kw lets callers bind optional tensor inputs by name
    # (symbol executor `__opt_in__` path) on top of the static attrs
    return lambda *arrays, **kw: fn(*arrays, **attrs, **kw)


def _vjp_fwd_jitted(name, frozen_attrs):
    """jit-compiled forward-with-residuals: returns (outputs, vjp_partial).
    jax.vjp's pullback is a `tree_util.Partial` pytree, so it crosses the jit
    boundary; residuals stay on device. This is how the eager autograd tape
    avoids re-running forwards at backward time (reference keeps explicit
    FGradient graphs instead — here linearization is the compiler's job)."""

    def build():
        op = _OPS[name]
        attrs = dict(frozen_attrs)
        fn = lambda *arrays: _scoped(op, arrays, attrs)

        def fwd(*arrays):
            out, vjp = jax.vjp(fn, *arrays)
            return out, vjp

        return jax.jit(fwd)

    return _op_cache("op_vjp").get_or_build((name, frozen_attrs), build)


@jax.jit
def run_vjp(vjp_partial, cts):
    """Apply a stored pullback (jit-cached by pytree structure)."""
    return vjp_partial(cts)


def _in_trace(arrays):
    """True when any input is a tracer — i.e. we are being captured into an
    outer program (CachedOp / shape inference / user jit). In that case the
    per-op jit wrapper must be skipped: the outer jit compiles the whole
    graph anyway, and differentiating THROUGH a nested pjit boundary breaks
    primitives without transpose rules (reduce_window), while inlining keeps
    XLA free to fuse across ops (the whole point of capture)."""
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def invoke_with_vjp(name, *arrays, **attrs):
    """Invoke returning (outputs, vjp_partial) for tape recording."""
    op = get_op(name)
    if op.wrap_kwargs is not None:
        attrs = op.wrap_kwargs(attrs)
    if op.eager_only and not _in_trace(arrays):
        # differentiate wrt the data arg ONLY, closing over the rest as
        # CONCRETE values — a dynamic-shape op (boolean_mask) traces fine
        # once its shape-determining inputs are constants. Host pullback
        # (not run through the jitted run_vjp).
        # CONTRACT: eager_only ops are differentiable in their FIRST input
        # only (see register()); inputs 1.. receive None cotangents.
        from ..autograd import _PyPullback

        fn, rest = op.fn, arrays[1:]
        out, vjp1 = jax.vjp(lambda a0: fn(a0, *rest, **attrs), arrays[0])
        return out, _PyPullback(
            lambda cts: vjp1(cts) + tuple(None for _ in rest))
    if _in_trace(arrays):
        return jax.vjp(lambda *a: _scoped(op, a, attrs), *arrays)
    jfn = _vjp_fwd_jitted(op.name, _freeze(attrs))
    return jfn(*arrays)


def invoke_raw(name, *arrays, **attrs):
    """Invoke on raw jax arrays, eager, through the compile cache."""
    op = get_op(name)
    if op.wrap_kwargs is not None:
        attrs = op.wrap_kwargs(attrs)
    if _in_trace(arrays):
        return _scoped(op, arrays, attrs)
    if op.eager_only:
        return op.fn(*arrays, **attrs)
    jfn = _jitted(op.name, _freeze(attrs), None)
    return jfn(*arrays)


def invoke(name, *arrays, **attrs):
    """Alias of invoke_raw (NDArray-level dispatch lives in ndarray.register)."""
    return invoke_raw(name, *arrays, **attrs)
