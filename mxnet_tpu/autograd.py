"""Autograd — imperative differentiation with MXNet semantics.

Parity: `python/mxnet/autograd.py` (record/pause/train_mode/predict_mode
scopes :122-194, mark_variables :216, backward :243, grad :270, Function
:365) over the reference's tape in `src/imperative/imperative.cc`
(RecordOp / Backward).

TPU-native design: instead of building an NNVM backward graph, every
recorded op stores the **pullback** returned by `jax.vjp` (compiled together
with the forward — see `ops.registry.invoke_with_vjp`). `backward()` walks
the tape in reverse applying pullbacks; each pullback application is itself
a jit-cached XLA program. A hybridized block records a single tape node that
keeps the call itself (`_cached_op.RecordedCall`: op, key, input buffers),
not a pullback: where the heads reach nothing but such calls, `backward()`
runs their forward and pullback as ONE program (`_fused_backward`) — the
analogue of CachedOp::Backward (`src/imperative/cached_op.cc:1160`) with
the residuals as temporaries of that program; where eager nodes sit between
them, the walk runs that program for one call at a time.

The plain call — no head gradient, no ``retain_graph``, not ``grad()``,
every wanted leaf a dense ``grad_req="write"`` buffer, ``MXNET_FUSED_STEP``
on — does not even launch that program: it keeps its wiring
(`_CallsBackward`) and puts a pending buffer into each leaf's ``.grad``
(the protocol of `NDArray._buf`: `LazyArray`, `PendingOutput`,
`PendingGrad`). ``gluon.Trainer.step`` then runs forward, pullback and the
optimizer update as one donated program that returns no gradient; a read of
a gradient or of an output first runs forward and pullback as above.
Counters: ``autograd.backward_deferred``, ``autograd.deferred_forced``,
``trainer.fused_step``.
"""
from __future__ import annotations

import functools
import threading

import numpy as _np
import jax
import jax.numpy as jnp

from . import telemetry
from . import tracing
from .base import MXNetError, getenv

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
        # tree_util.Partial pullback (device residuals), a _PyPullback, or
        # a hybridized call's RecordedCall (no residuals: see _run_calls)
        self.vjp = vjp
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
    from ._cached_op import RecordedCall

    st = _st()
    dropped = sum(1 for node in st.tape
                  if type(node.vjp) is RecordedCall and not node.vjp.ran
                  and node.vjp.deferred is None)
    if dropped:
        # recorded, never read, never differentiated: no program ran
        telemetry.counter("autograd.recorded_calls_dropped").inc(dropped)
    st.tape = []
    st.tape_index = {}


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


def _run_backward(heads, head_grads, retain_graph, deposit=True,
                  variables=()):
    tape = _st().tape
    # one span a backward (children: a recorded calls' program launch, the
    # deposit loop) — never one a node
    with tracing.span("autograd.backward", cat="autograd",
                      nodes=len(tape)):
        return _backward_pass(tape, heads, head_grads, retain_graph,
                              deposit, variables)


def _dense_ct(g, dtype):
    """A cotangent as a pullback takes it: dense, of the output's dtype."""
    if isinstance(g, _RowSparseCT):
        g = g.densify()
    if getattr(g, "dtype", None) != dtype:
        g = jnp.asarray(g, dtype)  # else: already usable
    return g


def _accumulate(grad_map, nd, ct):
    prev = grad_map.get(id(nd))
    grad_map[id(nd)] = ct if prev is None else prev + ct


@functools.lru_cache(maxsize=64)
def _inexact(dtype):
    return bool(jnp.issubdtype(dtype, jnp.inexact))


class _CallsBackward:
    """Forward and pullback of recorded CachedOp calls (tape order) as ONE
    program, built and cached by ``_cached_op.backward_program`` in the
    last call's op: the wiring of one ``backward()``, run at once
    (:meth:`launch`) or kept whole — the deferred backward. Deferred
    (:meth:`defer`), it launches nothing: each wanted leaf's ``.grad``
    holds a ``PendingGrad`` until ``Trainer.step`` takes the program with
    its update stage (:meth:`consumed`), or a read of a gradient or of an
    output runs it as it would have run (:meth:`run`). Once a later
    ``backward()`` has overwritten every one of its gradients it is dead
    and never runs: its outputs, if read, take the forward-only program."""

    __slots__ = ("calls", "leaves", "leaf_nds", "wanted", "cts", "key",
                 "pending")

    def __init__(self, calls, leaves, leaf_nds, wanted, cts, key):
        self.calls = calls
        self.leaves = leaves        # the buffers the calls captured, by slot
        self.leaf_nds = leaf_nds    # the NDArray of each slot, or None
        self.wanted = wanted        # slots to differentiate
        self.cts = cts              # the explicit head cotangents
        self.key = key              # of the program, in the op's cache
        self.pending = None         # deferred: a PendingGrad a wanted slot

    @classmethod
    def wire(cls, nodes, out_cts, also):
        """``out_cts``: ``id(output NDArray) -> cotangent``, None standing
        for ones. Differentiated are the leaf inputs that are marked or
        whose id is in ``also``. None where the tape wires an input to an
        earlier call's output but another value flowed."""
        from ._cached_op import PendingOutput

        where = {id(o): (c, k) for c, node in enumerate(nodes)
                 for k, o in enumerate(node.outputs)}
        slots, leaves, leaf_nds, wiring = {}, [], [], []
        for c, node in enumerate(nodes):
            row = []
            for nd_in, buf in zip(node.inputs, node.vjp.inputs):
                src = where.get(id(nd_in)) if nd_in is not None else None
                if src is not None:
                    out = nodes[src[0]].vjp.outputs[src[1]]
                    if src[0] >= c or (buf is not out and buf is not out.value):
                        return None
                    row.append(("o",) + src)
                    continue
                if type(buf) is PendingOutput:
                    # pending, yet not this tape's to differentiate through
                    # (a detach()ed view, a call of a tape since dropped)
                    buf = buf.force()
                # one slot per (array, buffer): the program sums what several
                # calls send back to it; an array rewritten between two calls
                # is two leaves whose cotangents meet in grad_map
                slot_key = (id(nd_in), id(buf)) if nd_in is not None \
                    else len(leaves)
                slot = slots.get(slot_key)
                if slot is None:
                    slot = slots[slot_key] = len(leaves)
                    leaves.append(buf)
                    leaf_nds.append(nd_in)
                row.append(("l", slot))
            wiring.append(tuple(row))
        wanted = tuple(
            s for s, nd_in in enumerate(leaf_nds)
            if nd_in is not None and _inexact(leaves[s].dtype) and (
                id(nd_in) in also or (nd_in._ag_marked
                                      and nd_in.grad is not None
                                      and nd_in.grad_req != "null")))
        heads, cts, emit = [], [], []
        for c, node in enumerate(nodes):
            for k, (o, aval) in enumerate(zip(node.outputs, node.out_avals)):
                if id(o) not in out_cts:
                    continue
                ct = out_cts[id(o)]
                if ct is not None:
                    cts.append(_dense_ct(ct, aval.dtype))
                heads.append((c, k, ct is not None))
            if not node.vjp.ran:
                emit.extend((c, k) for k in range(len(node.outputs)))
        calls = [node.vjp for node in nodes]
        key = ("bwd", tuple((call.op, call.train, call.sig) for call in calls),
               tuple(wiring), wanted, tuple(heads), tuple(emit))
        return cls(calls, leaves, leaf_nds, wanted, cts, key)

    def program(self, update=None, update_key=()):
        """The program of this wiring from the last call's op's cache:
        forward and pullback, or with ``update`` (``update_key``: what it
        adds to the key) the whole step."""
        from ._cached_op import backward_program

        return self.calls[-1].op._cache.get_or_build(
            self.key + update_key,
            lambda: backward_program([call.trace.jaxpr for call in self.calls],
                                     *self.key[2:], update=update),
            audit="fused_step" if update else None)

    def fill(self, emitted):
        values = iter(emitted)
        for call in self.calls:
            if not call.ran:
                call.fill([next(values) for _ in call.outputs])
            call.deferred = None

    def launch(self):
        """Run forward and pullback; fill the outputs nobody had computed;
        the gradients of the wanted leaves."""
        from ._cached_op import explain_deleted_inputs

        program = self.program()
        with tracing.span("autograd.dispatch", cat="autograd",
                          calls=len(self.calls)):
            try:
                emitted, grads = program(
                    tuple(call.key for call in self.calls),
                    tuple(self.leaves), tuple(self.cts))
            except RuntimeError as e:
                explain_deleted_inputs(self.leaves, e)
                raise
        self.fill(emitted)
        return grads

    def launch_into(self, grad_map):
        for s, g in zip(self.wanted, self.launch()):
            _accumulate(grad_map, self.leaf_nds[s], g)

    # -- the deferred backward ----------------------------------------------

    def defer(self):
        """Leave the gradients pending where ``Trainer.step`` could take
        the whole backward: every wanted leaf a distinct marked array whose
        ``.grad`` is a dense buffer of its shape and dtype, written and not
        added to, and no head gradient given. False, with nothing done,
        where it is not so."""
        from ._cached_op import PendingGrad
        from .ndarray.ndarray import NDArray

        nds = [self.leaf_nds[s] for s in self.wanted]
        if self.cts or not nds or len({id(nd) for nd in nds}) != len(nds):
            return False
        for nd, s in zip(nds, self.wanted):
            grad, leaf = nd.grad, self.leaves[s]
            if not (nd._ag_marked and type(grad) is NDArray
                    and nd.grad_req == "write"
                    and grad._buf.dtype == leaf.dtype
                    and tuple(grad._buf.shape) == tuple(leaf.shape)):
                return False
        self.pending = [PendingGrad(self, self.leaves[s].shape,
                                    self.leaves[s].dtype)
                        for s in self.wanted]
        for nd, grad in zip(nds, self.pending):
            nd.grad._buf = grad
            nd._fresh_grad = True
        for call in self.calls:
            call.deferred = self
        telemetry.counter("autograd.backward_deferred").inc()
        return True

    def run(self):
        """A read came before ``Trainer.step``: forward and pullback now,
        as ``backward()`` would have run them."""
        with tracing.span("autograd.backward", cat="autograd",
                          nodes=len(self.calls)):
            grads = self.launch()
        for grad, value in zip(self.pending, grads):
            grad.value = value
            grad.owner = None
        telemetry.counter("autograd.fused_backward").inc()
        telemetry.counter("autograd.deferred_forced").inc()

    def run_if_owed(self):
        """Run, where a leaf's ``.grad`` still holds one of the pending
        gradients; where a later ``backward()`` has overwritten them all,
        let go of the calls (False: their outputs are the forward's)."""
        owed = any(self.leaf_nds[s].grad is not None
                   and self.leaf_nds[s].grad._buf is grad
                   for s, grad in zip(self.wanted, self.pending))
        if owed:
            self.run()
        else:
            for call in self.calls:
                call.deferred = None
        return owed

    def consumed(self, emitted):
        """``Trainer.step`` ran the program with its update stage: the
        outputs are there, the gradients were its temporaries."""
        self.fill(emitted)
        for grad in self.pending:
            grad.owner = None
        telemetry.counter("autograd.fused_backward").inc()


def _run_calls(nodes, out_cts, also, grad_map):
    """Forward and pullback of the recorded calls ``nodes`` as one program
    now; cotangents go into ``grad_map``. False, with nothing run, where
    the tape does not wire so (:meth:`_CallsBackward.wire`)."""
    calls = _CallsBackward.wire(nodes, out_cts, also)
    if calls is None:
        return False
    calls.launch_into(grad_map)
    return True


def _fused_backward(tape, head_cts, variables, grad_map, may_defer):
    """The whole backward as one program, where every tape node the heads
    reach is a recorded CachedOp call (the example loop: hybridized net,
    hybridized loss). False, with nothing done, where it is not so. With
    ``may_defer`` the program is left pending where it can be
    (:meth:`_CallsBackward.defer`)."""
    from ._cached_op import RecordedCall

    needed = {id(h) for h, _ in head_cts}
    nodes = []
    for node in reversed(tape):
        if any(id(o) in needed for o in node.outputs):
            if type(node.vjp) is not RecordedCall:
                return False
            nodes.append(node)
            needed.update(id(a) for a in node.inputs if a is not None)
    nodes.reverse()
    produced = {id(o) for node in nodes for o in node.outputs}
    # the program returns no intermediate's cotangent: autograd.grad with
    # respect to a call's output takes the walk
    if not nodes or any(id(v) in produced for v in variables):
        return False
    out_cts, rest = {}, []
    for h, ct in head_cts:
        if id(h) not in produced:
            rest.append((h, ct))
        elif id(h) in out_cts:  # a head given twice: the cotangents add
            out_cts[id(h)] = _head_ct(h, out_cts[id(h)]) + _head_ct(h, ct)
        else:
            out_cts[id(h)] = ct
    calls = _CallsBackward.wire(nodes, out_cts, {id(v) for v in variables})
    if calls is None:
        return False
    if may_defer and not rest and calls.defer():
        return True
    calls.launch_into(grad_map)
    for h, ct in rest:
        _accumulate(grad_map, h, _head_ct(h, ct))
    telemetry.counter("autograd.fused_backward").inc()
    return True


def _head_ct(h, ct):
    return jnp.ones(h.shape, h.dtype) if ct is None else ct


def _backward_pass(tape, heads, head_grads, retain_graph, deposit, variables):
    from ._cached_op import RecordedCall

    grad_map = {}  # id(NDArray) -> jnp cotangent
    head_cts = [(h, hg if hg is None else
                 hg._data if hasattr(hg, "_data") else jnp.asarray(hg))
                for h, hg in zip(heads, head_grads)]
    # the plain call may leave its gradients pending for Trainer.step,
    # which the switch of the fused update gates too
    may_defer = deposit and not retain_graph and not variables \
        and all(ct is None for _, ct in head_cts) \
        and getenv("MXNET_FUSED_STEP")
    if _fused_backward(tape, head_cts, variables, grad_map, may_defer):
        tape_walk = ()
    else:
        tape_walk = reversed(tape)
        for h, ct in head_cts:
            _accumulate(grad_map, h, _head_ct(h, ct))
    also = None

    for node in tape_walk:
        if not any(id(o) in grad_map for o in node.outputs):
            continue
        if type(node.vjp) is RecordedCall:
            # a recorded call between eager nodes: its own program, forward
            # recomputed inside, cotangents for what the walk goes on to
            if also is None:
                also = {id(o) for n in tape for o in n.outputs}
                also.update(id(v) for v in variables)
            _run_calls([node], {id(o): grad_map[id(o)] for o in node.outputs
                                if id(o) in grad_map}, also, grad_map)
            continue
        cts = []
        for i, aval in enumerate(node.out_avals):
            if i < len(node.outputs) and id(node.outputs[i]) in grad_map:
                cts.append(_dense_ct(grad_map[id(node.outputs[i])],
                                     aval.dtype))
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
            _accumulate(grad_map, nd_in, ct)

    # deposit into marked variables honoring grad_req
    if deposit and grad_map:
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

    grad_map = _run_backward(heads, head_grads, retain_graph=True,
                             deposit=False, variables=variables)
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
