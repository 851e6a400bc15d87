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

Autograd: a call under ``autograd.record()`` dispatches nothing. It keeps
its op, RNG key, train flag and input buffers (:class:`RecordedCall`), puts
ONE node on the tape and returns NDArrays whose buffer is a
:class:`PendingOutput` — shape and dtype from the function's jaxpr, traced
once per signature. ``backward()`` then runs forward and pullback of the
recorded calls as one program (:func:`backward_program`, differentiating
those jaxprs): the residuals are temporaries of that program, never its
outputs — CachedOp::Backward's role without a saved-tensor list. An output that is read first is forced through
the forward-only program inference uses.

A plain ``backward()`` over such calls launches nothing either
(``autograd._CallsBackward``): each wanted leaf's ``.grad`` holds a
:class:`PendingGrad` until ``Trainer.step`` runs forward, pullback and the
optimizer update as ONE donated program (:func:`backward_program` with an
``update`` stage: no gradient leaves it), or until a gradient or an output
is read, which runs the forward+pullback program as before.

RNG / train-mode: the compiled program takes a threefry base key as a
traced argument (fresh randomness each call, zero recompiles) and the
train flag is a static cache key — the reference achieves the same with
OpContext::is_train and per-op PRNG resources.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np
from jax.extend.core import jaxpr_as_fun

from . import autograd
from . import random as _random
from . import telemetry
from . import tracing
from .base import MXNetError
from .compile_cache import CompileCache

__all__ = ["CachedOp", "PendingGrad", "PendingOutput", "RecordedCall",
           "backward_program"]


class PendingOutput:
    """One output of a recorded call whose program has not run yet: what an
    NDArray holds as its buffer until ``backward()`` fills it or a read
    forces it. Shares the pending-buffer protocol of
    :class:`~mxnet_tpu.lazy.graph.LazyArray` (``shape``, ``dtype``,
    ``force(reason)``), which is all ``NDArray._data`` asks of one."""

    __slots__ = ("call", "shape", "dtype", "value")

    def __init__(self, call, aval):
        self.call = call      # dropped once the value is there
        self.shape = tuple(aval.shape)
        self.dtype = aval.dtype
        self.value = None

    def force(self, reason="value"):
        if self.value is None:
            # a deferred backward that still owes gradients computes the
            # outputs with them; a dead one leaves the forward-only program
            deferred = self.call.deferred
            if deferred is None or not deferred.run_if_owed():
                self.call.run_forward()
        return self.value

    def __repr__(self):
        state = "pending" if self.value is None else "realized"
        return f"PendingOutput({state}, shape={self.shape}, dtype={self.dtype})"


class PendingGrad:
    """The gradient of one leaf of a deferred ``backward()``
    (``autograd._CallsBackward``), in the leaf's ``.grad`` until
    ``Trainer.step`` takes the backward whole or a read forces it. The
    pending-buffer protocol of :class:`PendingOutput`. ``owner`` is dropped
    with the backward: by the forward+pullback program, which leaves
    ``value``, or by the one-program step, which leaves none — the
    gradient was a temporary of that program."""

    __slots__ = ("owner", "shape", "dtype", "value")

    def __init__(self, owner, shape, dtype):
        self.owner = owner
        self.shape = tuple(shape)
        self.dtype = dtype
        self.value = None

    @property
    def consumed(self):
        """Taken by the one-program step: there is no value and none can
        be made."""
        return self.value is None and self.owner is None

    def force(self, reason="value"):
        if self.value is None:
            if self.owner is None:
                raise MXNetError(
                    "this gradient was never materialized: Trainer.step ran "
                    "backward and the update as one program and the old "
                    "weights were donated to it. Read a gradient between "
                    "backward() and step() (the step then runs as two "
                    "programs), not after")
            self.owner.run()
        return self.value

    def __repr__(self):
        state = "consumed" if self.consumed else \
            "pending" if self.value is None else "realized"
        return f"PendingGrad({state}, shape={self.shape}, dtype={self.dtype})"


class RecordedCall:
    """What one ``CachedOp`` call under ``autograd.record()`` keeps — the
    tape node's payload. ``inputs`` are the buffers the arguments held at
    call time (a later in-place write to a parameter swaps the NDArray's
    buffer, not this one); an entry may be the :class:`PendingOutput` of an
    earlier recorded call."""

    __slots__ = ("op", "train", "sig", "trace", "key", "inputs", "outputs",
                 "deferred")

    def __init__(self, op, train, sig, trace, key, inputs):
        self.deferred = None  # the deferred backward that will fill outputs
        self.op = op
        self.train = train
        self.sig = sig
        self.trace = trace
        self.key = key
        self.inputs = inputs
        self.outputs = [PendingOutput(self, a) for a in trace.avals]

    @property
    def ran(self):
        return self.outputs[0].value is not None

    def fill(self, values):
        for out, v in zip(self.outputs, values):
            out.value = v
            out.call = None

    def run_forward(self):
        """Force the outputs through the forward-only program."""
        with tracing.span("cached_op.dispatch", cat="gluon"):
            args = [a.force() if type(a) is PendingOutput else a
                    for a in self.inputs]
            try:
                outs = self.op._jit_fwd(self.train, self.sig)(self.key, *args)
            except RuntimeError as e:
                explain_deleted_inputs(args, e)
                raise
        self.fill(outs if isinstance(outs, tuple) else (outs,))
        telemetry.counter("autograd.forced_forward").inc()


def explain_deleted_inputs(arrays, err):
    """A recorded call runs when its output is read or at ``backward()``,
    so a buffer it captured can have been donated meanwhile
    (``Trainer.step`` donates the weights): say so instead of jax's bare
    "Array has been deleted"."""
    if any(isinstance(a, jax.Array) and a.is_deleted() for a in arrays):
        raise MXNetError(
            "a hybridized call recorded under autograd.record() runs when "
            "its output is first read or at backward(), and an input buffer "
            "it captured has been donated since (e.g. by Trainer.step): "
            "read the output or call backward() before the update") from err


def backward_program(jaxprs, wiring, wanted, heads, emit, update=None):
    """Forward and pullback of recorded calls as ONE jitted function
    ``(keys, leaves, cts) -> (emitted outputs, grads of the wanted leaves)``
    — or, with ``update``, the whole training step.

    ``jaxprs``: the calls' closed jaxprs ``(key, *args) -> outputs`` in tape
    order. ``wiring[c][i]`` says where argument ``i`` of call ``c`` comes
    from: ``("l", slot)`` a leaf, or ``("o", c0, k)`` output ``k`` of the
    earlier call ``c0``. ``wanted``: the leaf slots to differentiate.
    ``heads``: ``(c, k, explicit)`` — the outputs cotangents enter at; an
    ``explicit`` one takes the next entry of ``cts``, the others get ones.
    ``emit``: the ``(c, k)`` outputs the program also returns (those nobody
    has computed yet). Nothing else leaves the program: the residuals are
    its temporaries.

    ``update``: ``Optimizer.fused_update``. The function is then
    ``(keys, weights, rest, states, lrs, wds, rescale) -> (emitted outputs,
    new weights, new states' leaves)``: ``weights`` are the wanted leaves
    and ``rest`` the others, each in slot order; the gradients go into
    ``update`` as values and do not leave; ``weights`` and ``states`` are
    donated, so every new weight and state is written over its old one."""
    fns = [jaxpr_as_fun(j) for j in jaxprs]

    def program(keys, leaves, cts):
        def forward(diff):
            vals = list(leaves)
            for slot, v in zip(wanted, diff):
                vals[slot] = v
            outs = []
            for fn, key, row in zip(fns, keys, wiring):
                outs.append(fn(key, *(
                    vals[s[1]] if s[0] == "l" else outs[s[1]][s[2]]
                    for s in row)))
            return (tuple(outs[c][k] for c, k, _ in heads),
                    tuple(outs[c][k] for c, k in emit))

        head_vals, pullback, emitted = jax.vjp(
            forward, tuple(leaves[s] for s in wanted), has_aux=True)
        explicit = iter(cts)
        head_cts = tuple(next(explicit) if given else _ones_ct(h)
                         for h, (_, _, given) in zip(head_vals, heads))
        (grads,) = pullback(head_cts)
        return emitted, grads

    if update is None:
        return jax.jit(program)

    def step(keys, weights, rest, states, lrs, wds, rescale):
        mine, others = iter(weights), iter(rest)
        slots = set(wanted)
        leaves = tuple(next(mine) if s in slots else next(others)
                       for s in range(len(weights) + len(rest)))
        emitted, grads = program(keys, leaves, ())
        # the update reads the gradients as the pullback rounds them: with
        # no barrier XLA carries them over in the pullback's wider
        # precision, and the step differs from its two-program form
        grads = jax.lax.optimization_barrier(grads)
        with jax.named_scope("optimizer.update"):
            new_ws, new_ss = update(list(weights), list(grads), list(states),
                                    lrs, wds, rescale)
        if jax.tree_util.tree_structure(tuple(new_ss)) \
                != jax.tree_util.tree_structure(tuple(states)):
            raise MXNetError("fused_update changed the structure of the "
                             "optimizer states")
        return emitted, tuple(new_ws), tuple(jax.tree_util.tree_leaves(new_ss))

    # tpulint: disable=donation-aliasing (autograd._CallsBackward.program builds it inside the op's CompileCache, audit="fused_step")
    return jax.jit(step, donate_argnums=(1, 3))


def _ones_ct(x):
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.ones(x.shape, x.dtype)
    return _np.zeros(x.shape, jax.dtypes.float0)


class _TraceOnce:
    """The op's function as a closed jaxpr ``(key, *args) -> outputs`` and
    the outputs' shapes, traced at the first call of a signature and kept:
    what a recorded call reads its outputs' shapes from and what
    ``backward()`` differentiates — the python function is not traced
    again under ``jax.vjp``. (``HybridBlock`` reads the output format this
    trace leaves behind; a steady loop only looks the entry up.)"""

    __slots__ = ("_fn", "jaxpr", "avals")

    def __init__(self, fn):
        self._fn = fn
        self.jaxpr = None

    def __call__(self, key, *bufs):
        if self.jaxpr is None:
            jaxpr = jax.make_jaxpr(self._fn)(key, *(
                jax.ShapeDtypeStruct(b.shape, b.dtype)
                if type(b) is PendingOutput else b for b in bufs))
            self.avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                          for a in jaxpr.out_avals]
            self.jaxpr = jaxpr
        return self


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
                # a single output is a bare leaf, not a 1-tuple
                return res[0] if len(res) == 1 else res
            return outs._data

        return run

    def _jit_fwd(self, train, sig):
        return self._cache.get_or_build(
            ("fwd", train, sig), lambda: jax.jit(self._traced(train)))

    def _trace(self, train, sig):
        return self._cache.get_or_build(
            ("jaxpr", train, sig), lambda: _TraceOnce(self._traced(train)))

    # -- call ---------------------------------------------------------------

    def __call__(self, *inputs, default_ctx=None):
        from .ndarray.ndarray import NDArray

        with tracing.span("cached_op.dispatch", cat="gluon"):
            train = bool(autograd.is_training())
            recording = autograd.is_recording()
            arrays = []
            nd_inputs = []
            for a in inputs:
                if isinstance(a, NDArray):
                    buf = a._buf
                    # a recorded call takes the still-pending output of an
                    # earlier one as it is (`sce(net(x), y)`); any other
                    # pending buffer materializes here
                    if not (recording and type(buf) is PendingOutput
                            and buf.value is None):
                        buf = a._data
                    arrays.append(buf)
                    nd_inputs.append(a)
                else:
                    arrays.append(a)
                    nd_inputs.append(None)

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
                # nothing is dispatched: backward() runs forward and
                # pullback together, a read before it the forward alone
                trace = self._trace(train, sig)(key, *arrays)
            else:
                outs = self._jit_fwd(train, sig)(key, *arrays)
        if recording:
            with tracing.span("cached_op.record", cat="gluon"):
                call = RecordedCall(self, train, sig, trace, key, arrays)
                out_nds = [NDArray(o, ctx) for o in call.outputs]
                autograd._record_node(call, nd_inputs, out_nds, trace.avals)
        else:
            outs_t = outs if isinstance(outs, tuple) else (outs,)
            out_nds = [NDArray(o, ctx) for o in outs_t]

        self._n_out = len(out_nds)
        if len(out_nds) == 1:
            return out_nds[0]
        return out_nds
