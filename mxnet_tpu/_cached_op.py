"""CachedOp — a python callable captured as ONE compiled XLA program.

Parity: `src/imperative/cached_op.cc` (`CachedOp::Forward` :889 dispatching
to cached graphs keyed by input signature; `SetForwardGraph` :295 signature
match; `CachedOp::Backward` :1160) and the frontend handle
`python/mxnet/_ctypes/ndarray.py:105`.

TPU-native redesign: the reference captures an NNVM graph and replays it
node-by-node through the engine (optionally bulked, `StaticRunOps` :647).
Here capture *is* compilation: the wrapped python function is traced by
`jax.jit` into a single XLA computation — the limit case of engine bulking
(whole-program fusion, static buffer plan by XLA). The signature cache
(shape/dtype of every input, train flag) is jax's jit cache; `static_alloc`/
`static_shape` are accepted for API compatibility and are no-ops because
every CachedOp already gets a static memory plan from XLA.

Autograd: when recording, the forward runs through ``jax.vjp`` (compiled
with the forward) and ONE tape node is recorded whose pullback is the
whole-graph backward — exactly CachedOp::Backward's role.

RNG / train-mode: the compiled program takes a threefry base key as a
traced argument (fresh randomness each call, zero recompiles) and the
train flag is a static cache key — the reference achieves the same with
OpContext::is_train and per-op PRNG resources.
"""
from __future__ import annotations

import jax

from . import autograd
from . import random as _random
from . import tracing
from .compile_cache import CompileCache

__all__ = ["CachedOp"]


class CachedOp:
    """Wrap ``fn(*ndarrays) -> NDArray | list[NDArray]`` as a compiled op.

    ``fn`` must be pure python over NDArray ops (the same code the eager
    path runs): it is traced with tracer-backed NDArrays.
    """

    def __init__(self, fn, static_alloc=False, static_shape=False, inline_limit=2):
        self._fn = fn
        self._static_alloc = static_alloc  # accepted for parity; XLA always static-plans
        self._static_shape = static_shape
        self._n_out = None
        # signature-keyed executable cache (the reference's SetForwardGraph
        # :295 signature match) — input shape churn is counted, not silent.
        # Bounded so unbucketed shape churn caps memory, not just visibility
        self._cache = CompileCache("cached_op", maxsize=64)

    # -- tracing ------------------------------------------------------------

    def _traced(self, train):
        """The pure jax function: (key, *arrays) -> tuple of arrays."""
        from .ndarray.ndarray import NDArray

        fn = self._fn

        def run(key, *arrays):
            nds = [NDArray(a) for a in arrays]
            with autograd._RecordingStateScope(False, train):
                with _random.TraceKeyProvider(key):
                    outs = fn(*nds)
            if isinstance(outs, (list, tuple)):
                res = tuple(o._data for o in outs)
                # single output stays a bare leaf so the stored pullback's
                # cotangent convention matches the per-op tape nodes
                return res[0] if len(res) == 1 else res
            return outs._data

        return run

    def _jit_fwd(self, train, sig):
        return self._cache.get_or_build(
            ("fwd", train, sig), lambda: jax.jit(self._traced(train)))

    def _jit_fwd_vjp(self, train, sig):
        def build():
            base = self._traced(train)

            def fwd(key, *arrays):
                outs, vjp = jax.vjp(lambda *a: base(key, *a), *arrays)
                return outs, vjp

            return jax.jit(fwd)

        return self._cache.get_or_build(("fwd_vjp", train, sig), build)

    # -- call ---------------------------------------------------------------

    def __call__(self, *inputs, default_ctx=None):
        from .ndarray.ndarray import NDArray

        with tracing.span("cached_op.dispatch", cat="gluon"):
            arrays = []
            nd_inputs = []
            for a in inputs:
                if isinstance(a, NDArray):
                    arrays.append(a._data)
                    nd_inputs.append(a)
                else:
                    arrays.append(a)
                    nd_inputs.append(None)

            train = bool(autograd.is_training())
            recording = autograd.is_recording()
            key = _random.next_key()

            ctx = next((a._ctx for a in nd_inputs if a is not None),
                       default_ctx)
            # hashable dtype objects, not strings — this runs on every call.
            # Non-array inputs key by TYPE only: a python scalar is a traced
            # argument of the shared jit object (weak-typed), so a changing
            # value re-specializes inside jax, never in this cache — keying
            # on the value would compile one executable per distinct scalar
            sig = tuple((a.shape, a.dtype) if hasattr(a, "shape")
                        else (None, type(a).__name__) for a in arrays)

            if recording:
                outs, vjp = self._jit_fwd_vjp(train, sig)(key, *arrays)
            else:
                outs = self._jit_fwd(train, sig)(key, *arrays)
        outs_t = outs if isinstance(outs, tuple) else (outs,)
        if recording:
            with tracing.span("cached_op.record", cat="gluon"):
                out_nds = [NDArray(o, ctx) for o in outs_t]
                autograd._record_node(
                    vjp, nd_inputs, out_nds,
                    [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs_t])
        else:
            out_nds = [NDArray(o, ctx) for o in outs_t]

        self._n_out = len(out_nds)
        if len(out_nds) == 1:
            return out_nds[0]
        return out_nds
