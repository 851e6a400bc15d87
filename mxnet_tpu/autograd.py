"""Autograd — imperative differentiation with MXNet semantics.

Parity: `python/mxnet/autograd.py` (record/pause/train_mode/predict_mode
scopes :122-194, mark_variables :216, backward :243, grad :270, Function
:365) over the reference's tape in `src/imperative/imperative.cc`
(RecordOp / Backward).

TPU-native design: instead of building an NNVM backward graph, every
recorded op stores the **pullback** returned by `jax.vjp` (compiled together
with the forward — see `ops.registry.invoke_with_vjp`). `backward()` walks
the tape in reverse applying pullbacks; each pullback application is itself
a jit-cached XLA program. Hybridized blocks record a single tape node whose
pullback is the whole-graph backward — the analogue of CachedOp::Backward
(`src/imperative/cached_op.cc:1160`).
"""
from __future__ import annotations

import threading

import numpy as _np
import jax
import jax.numpy as jnp

from . import tracing
from .base import MXNetError

__all__ = [
    "record", "pause", "train_mode", "predict_mode", "is_recording", "is_training",
    "set_recording", "set_training", "mark_variables", "backward", "grad", "Function",
]

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.tape = []
        # id(NDArray) -> [tape nodes referencing it]: lets a recorded
        # in-place write retarget only the nodes that actually touch the
        # array (O(uses), not O(tape)). Ids stay valid while indexed: the
        # node input/output lists hold strong references.
        _state.tape_index = {}
    return _state


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    prev = _st().recording
    _st().recording = bool(is_record)
    return prev


def set_training(train_mode):
    prev = _st().training
    _st().training = bool(train_mode)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._enter_is_record = is_record
        self._enter_train_mode = train_mode
        self._prev_is_record = None
        self._prev_train_mode = None

    def __enter__(self):
        if self._enter_is_record is not None:
            self._prev_is_record = set_recording(self._enter_is_record)
            if self._enter_is_record:
                st = _st()
                st.scope_depth = getattr(st, "scope_depth", 0) + 1
                # fresh OUTERMOST record scope starts a fresh tape (a previous
                # scope never backward()ed would otherwise leak nodes); a
                # record nested inside pause() must NOT wipe the outer tape.
                if st.scope_depth == 1 and not self._prev_is_record:
                    _clear_tape()
        if self._enter_train_mode is not None:
            self._prev_train_mode = set_training(self._enter_train_mode)
        return self

    def __exit__(self, ptype, value, trace):
        if self._enter_is_record is not None:
            if self._enter_is_record:
                st = _st()
                st.scope_depth = max(0, getattr(st, "scope_depth", 1) - 1)
            if self._prev_is_record != self._enter_is_record:
                set_recording(self._prev_is_record)
        if self._enter_train_mode is not None and self._prev_train_mode != self._enter_train_mode:
            set_training(self._prev_train_mode)


def record(train_mode=True):
    """Scope: ops executed inside are recorded on the tape."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# Tape
# ---------------------------------------------------------------------------


class _RowSparseCT:
    """A row-sparse cotangent flowing through the tape: (indices, rows) of a
    logically-dense grad. The reference expresses this as a row_sparse
    NDArray chosen by FInferStorageType (`include/mxnet/op_attr_types.h`
    FInferStorageType; Embedding's sparse grad `indexing_op.cc`); here it is
    the tape value type, deduplicated lazily at deposit time so chained
    accumulations stay O(touched rows)."""

    __slots__ = ("indices", "data", "shape", "dtype")

    def __init__(self, indices, data, shape, dtype):
        self.indices = indices      # int32 (k,)
        self.data = data            # (k, *shape[1:])
        self.shape = tuple(shape)
        self.dtype = dtype

    def __add__(self, other):
        if other is None or (isinstance(other, int) and other == 0):
            return self
        if isinstance(other, _RowSparseCT):
            return _RowSparseCT(jnp.concatenate([self.indices, other.indices]),
                                jnp.concatenate([self.data, other.data]),
                                self.shape, self.dtype)
        return self.densify() + other

    __radd__ = __add__

    def densify(self):
        out = jnp.zeros(self.shape, self.dtype)
        if self.indices.size:
            out = out.at[self.indices].add(self.data)
        return out

    def dedup(self):
        """(unique_rows, summed_data) — the canonical row_sparse form."""
        uniq, inv = jnp.unique(self.indices, return_inverse=True)
        summed = jax.ops.segment_sum(self.data, inv.reshape(-1),
                                     num_segments=uniq.shape[0])
        return uniq, summed


class _TapeNode:
    __slots__ = ("vjp", "inputs", "outputs", "out_avals")

    def __init__(self, vjp, inputs, outputs, out_avals):
        self.vjp = vjp            # tree_util.Partial pullback (device residuals)
        self.inputs = inputs      # list[NDArray|None] aligned with fn args
        self.outputs = outputs    # list[NDArray] (user outputs, prefix of avals)
        self.out_avals = out_avals  # ShapeDtypeStruct for ALL fn outputs


def _record_node(vjp, inputs, outputs, out_avals):
    st = _st()
    node = _TapeNode(vjp, inputs, outputs, out_avals)
    st.tape.append(node)
    idx = st.tape_index
    for a in list(inputs) + list(outputs):
        if a is not None:
            idx.setdefault(id(a), []).append(node)


def _retarget(frm, to):
    """Swap every tape reference to `frm` for `to` — the identity rewrite
    behind NDArray._recorded_setitem (the pre-write value becomes its own
    tape identity). O(nodes using frm) via the tape index."""
    st = _st()
    nodes = st.tape_index.pop(id(frm), [])
    for node in nodes:
        node.inputs = [to if a is frm else a for a in node.inputs]
        node.outputs = [to if a is frm else a for a in node.outputs]
    if nodes:
        st.tape_index.setdefault(id(to), []).extend(nodes)


def _clear_tape():
    _st().tape = []
    _st().tape_index = {}


def mark_variables(variables, gradients, grad_reqs="write"):
    """Parity `autograd.py:216`: associate grad buffers with arrays."""
    from .ndarray.ndarray import NDArray

    if isinstance(variables, NDArray):
        variables, gradients = [variables], [gradients]
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v.grad = g
        v.grad_req = req
        v._ag_marked = True


def _zero_ct(aval):
    if jnp.issubdtype(aval.dtype, jnp.floating) or jnp.issubdtype(aval.dtype, jnp.complexfloating):
        return jnp.zeros(aval.shape, aval.dtype)
    return _np.zeros(aval.shape, jax.dtypes.float0)


def _run_backward(heads, head_grads, retain_graph, deposit=True):
    tape = _st().tape
    # one span a backward (child: the deposit loop) — never one a node
    with tracing.span("autograd.backward", cat="autograd",
                      nodes=len(tape)):
        return _backward_pass(tape, heads, head_grads, retain_graph,
                              deposit)


def _backward_pass(tape, heads, head_grads, retain_graph, deposit):
    grad_map = {}  # id(NDArray) -> jnp cotangent

    for h, hg in zip(heads, head_grads):
        if hg is None:
            hg = jnp.ones(h.shape, h.dtype)
        else:
            hg = hg._data if hasattr(hg, "_data") else jnp.asarray(hg)
        grad_map[id(h)] = grad_map.get(id(h), 0) + hg

    for node in reversed(tape):
        if not any(id(o) in grad_map for o in node.outputs):
            continue
        cts = []
        for i, aval in enumerate(node.out_avals):
            if i < len(node.outputs) and id(node.outputs[i]) in grad_map:
                g = grad_map[id(node.outputs[i])]
                if isinstance(g, _RowSparseCT):
                    g = g.densify()  # a pullback consumes dense cotangents
                if getattr(g, "dtype", None) != aval.dtype:
                    g = jnp.asarray(g, aval.dtype)  # else: already usable
                cts.append(g)
            else:
                cts.append(_zero_ct(aval))
        cts = tuple(cts) if len(node.out_avals) > 1 else cts[0]
        if isinstance(node.vjp, _PyPullback):
            in_cts = node.vjp(cts)
        else:
            from .ops.registry import run_vjp

            in_cts = run_vjp(node.vjp, cts)
        for nd_in, ct in zip(node.inputs, in_cts):
            if nd_in is None or ct is None:
                continue
            if hasattr(ct, "dtype") and ct.dtype == jax.dtypes.float0:
                continue
            prev = grad_map.get(id(nd_in))
            grad_map[id(nd_in)] = ct if prev is None else prev + ct

    # deposit into marked variables honoring grad_req
    if deposit:
        with tracing.span("autograd.deposit", cat="autograd"):
            for node in tape:
                for nd_in in node.inputs:
                    _deposit(nd_in, grad_map)
            for h in heads:
                _deposit(h, grad_map)

    if not retain_graph:
        _clear_tape()
    return grad_map


def _deposit(nd_in, grad_map):
    from .ndarray.ndarray import NDArray
    from .ndarray.sparse import RowSparseNDArray

    if nd_in is None or not getattr(nd_in, "_ag_marked", False):
        return
    g = grad_map.get(id(nd_in))
    if g is None or nd_in.grad is None:
        return
    if isinstance(g, _RowSparseCT) and isinstance(nd_in.grad, RowSparseNDArray):
        # sparse cotangent into a row_sparse grad buffer: never densify
        uniq, summed = g.dedup()
        if nd_in.grad_req == "add" and nd_in.grad.indices.size:
            old = nd_in.grad
            cat = _RowSparseCT(
                jnp.concatenate([old.indices._data.astype(jnp.int32), uniq]),
                jnp.concatenate([old.data._data, summed.astype(old.data.dtype)]),
                g.shape, g.dtype)
            uniq, summed = cat.dedup()
        nd_in.grad._aux = {"data": NDArray(summed.astype(nd_in.grad.dtype)),
                           "indices": NDArray(uniq.astype(jnp.int32))}
        nd_in.grad._dense_cache = None
        nd_in.grad._aux_stale = False
    else:
        if isinstance(g, _RowSparseCT):
            g = g.densify()
        # avoid a per-parameter re-wrap dispatch when the cotangent already
        # has the right dtype (the common case: ~#params calls per step)
        if getattr(g, "dtype", None) != nd_in.grad.dtype:
            g = jnp.asarray(g, nd_in.grad.dtype)
        if nd_in.grad_req == "write":
            nd_in.grad._data = g
        elif nd_in.grad_req == "add":
            nd_in.grad._data = nd_in.grad._data + g
    nd_in._fresh_grad = True  # cleared by Trainer._update (stale-grad check)
    grad_map[id(nd_in)] = None  # only deposit once


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads w.r.t. all marked variables
    (parity `autograd.py:243` → MXAutogradBackwardEx)."""
    from .ndarray.ndarray import NDArray

    if isinstance(heads, NDArray):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]
    _run_backward(heads, head_grads, retain_graph)


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Return grads of heads w.r.t. variables without touching .grad buffers
    (parity `autograd.py:270`). create_graph (2nd order) is not yet supported
    on the eager tape — use hybridized blocks + jax.grad composition."""
    from .ndarray.ndarray import NDArray

    if create_graph:
        raise MXNetError("create_graph=True is not supported on the eager tape; "
                         "hybridize and compose jax.grad instead")
    if isinstance(heads, NDArray):
        heads = [heads]
    single = isinstance(variables, NDArray)
    if single:
        variables = [variables]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]
    if retain_graph is None:
        retain_graph = create_graph

    grad_map = _run_backward(heads, head_grads, retain_graph=True, deposit=False)
    outs = []
    for v in variables:
        g = grad_map.get(id(v))
        if g is None:
            raise MXNetError("Cannot differentiate with respect to a variable the heads "
                             "do not depend on")
        if isinstance(g, _RowSparseCT):
            g = g.densify()
        outs.append(NDArray(jnp.asarray(g, v.dtype), v._ctx))
    if not retain_graph:
        _clear_tape()
    return outs[0] if single else outs


def get_symbol(x):
    raise MXNetError("autograd.get_symbol is not supported; use HybridBlock.export")


class Function:
    """Custom differentiable function (parity `autograd.py:365`).

    Subclass and implement ``forward``/``backward`` with NDArrays. The op is
    recorded as one tape node whose pullback calls the user's backward under
    pause().
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray

        with pause():
            outputs = self.forward(*inputs)
        single = isinstance(outputs, NDArray)
        outs = [outputs] if single else list(outputs)
        if is_recording():
            func = self

            def pullback(cts):
                cts_nd = [NDArray(jnp.asarray(c), outs[0]._ctx) for c in (cts if isinstance(cts, tuple) else (cts,))]
                with pause():
                    in_grads = func.backward(*cts_nd)
                if isinstance(in_grads, NDArray):
                    in_grads = [in_grads]
                return tuple(g._data if g is not None else None for g in in_grads)

            _record_node(
                _PyPullback(pullback),
                list(inputs),
                outs,
                [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs],
            )
        return outputs


class _PyPullback:
    """Wraps a python pullback so run_vjp's jit is bypassed (host callback)."""

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, cts):
        return self.fn(cts)
