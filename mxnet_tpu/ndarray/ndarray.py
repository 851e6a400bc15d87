"""NDArray — the framework's value type.

Parity: `include/mxnet/ndarray.h:82` + `python/mxnet/ndarray/ndarray.py`.

TPU-native redesign: an NDArray wraps a `jax.Array`. The reference's
engine-variable machinery (read/write vars, `WaitToRead/WaitToWrite`) is
subsumed by XLA's async dispatch — every jax op is enqueued asynchronously
and `wait_to_read` maps to `block_until_ready`. Mutation (`x[:] = v`,
``out=`` kwargs, optimizer updates) is rendered functionally: the wrapper
swaps its underlying buffer, which is exactly the version-bump the
reference's `ThreadedVar` performed (`threaded_engine.h:119`).

Divergence (documented): slicing returns a copy-on-write functional view,
not an aliased buffer; writes through a slice do not propagate to the
parent (XLA buffers are immutable). `__setitem__` on the parent works.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import MXNetError, np_dtype, integer_types, numeric_types
from ..context import Context, current_context, cpu
from .._cached_op import PendingGrad as _PendingGrad
from .._cached_op import PendingOutput as _PendingOutput
from ..lazy.graph import LazyArray as _LazyArray
from ..ops import registry as _reg

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange", "concatenate", "waitall"]


# What an NDArray may hold in place of a jax array: shape and dtype are
# known, ``force(reason)`` gives the array (an op the lazy graph captured;
# the output of a hybridized call recorded for autograd).
_PENDING = (_LazyArray, _PendingOutput, _PendingGrad)


def _dtype_name(dt):
    dt = _np.dtype(dt)
    name = dt.name
    return name


class NDArray:
    __slots__ = (
        "_buf", "_ctx", "grad", "grad_req", "_ag_marked", "_stype",
        "_fresh_grad", "__weakref__",
    )

    def __init__(self, data, ctx=None, stype="default"):
        self._buf = data
        self._ctx = ctx if ctx is not None else _ctx_of(data)
        self.grad = None
        self.grad_req = "null"
        self._ag_marked = False
        self._stype = stype
        # True once backward() has written this array's grad; cleared by
        # Trainer._update (reference NDArray::fresh_out_grad, trainer.py:401)
        self._fresh_grad = False

    # -- basic properties ---------------------------------------------------

    @property
    def _data(self):
        """The concrete jax array — THE materialization barrier. The
        buffer may be pending: under ``MXNET_LAZY=1`` a
        :class:`~mxnet_tpu.lazy.graph.LazyArray` (reading ``_data``
        flushes the owning segment, one fused XLA program), or the
        :class:`~mxnet_tpu._cached_op.PendingOutput` of a hybridized call
        recorded under ``autograd.record()`` (``backward()`` fills it; a
        read before that runs the forward-only program), or the
        :class:`~mxnet_tpu._cached_op.PendingGrad` a deferred
        ``backward()`` left in a ``.grad``. Either way the
        realized buffer is swapped in. Every concrete-value escape in the
        codebase —
        ``asnumpy``, kvstore pushes, checkpoint writes, executor feeds —
        reads through here, which is what makes the barrier audit
        structural rather than a site-by-site hunt. Metadata queries
        (``shape``/``dtype``/``ndim``/``size``) read ``_buf`` and never
        flush."""
        buf = self._buf
        if type(buf) in _PENDING:
            buf = buf.force()
            self._buf = buf
        return buf

    @_data.setter
    def _data(self, value):
        # a buffer swap IS the version bump: nodes that recorded the old
        # value keep referencing it (reference ThreadedVar versioning)
        self._buf = value

    @property
    def shape(self):
        return tuple(self._buf.shape)

    @property
    def dtype(self):
        return _np.dtype(self._buf.dtype)

    @property
    def ndim(self):
        return len(self._buf.shape)

    @property
    def size(self):
        n = 1
        for s in self._buf.shape:
            n *= int(s)
        return n

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return self._stype

    @property
    def handle(self):
        return self._data  # "handle" is the jax array itself

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return f"\n{_np.asarray(self._data)}\n<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>"

    def __str__(self):
        return self.__repr__()

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple elements is ambiguous.")

    # -- conversion ---------------------------------------------------------

    def asnumpy(self):
        """Blocking copy to host (reference `WaitToRead` + copy)."""
        buf = self._buf
        if type(buf) in _PENDING:
            self._buf = buf = buf.force("asnumpy")
        return _np.asarray(buf)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def astype(self, dtype, copy=True):
        return _invoke("Cast", self, dtype=_dtype_name(np_dtype(dtype)))

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device), ctx)

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other._ctx.jax_device)
            return other
        if isinstance(other, Context):
            return self.as_in_context(other)
        raise TypeError(f"copyto does not support type {type(other)}")

    def copy(self):
        return NDArray(jnp.array(self._data), self._ctx)

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sp

        return _sp.cast_storage(self, stype)

    # -- engine-var parity --------------------------------------------------

    def wait_to_read(self):
        buf = self._buf
        if type(buf) in _PENDING:
            self._buf = buf = buf.force("wait")
        buf.block_until_ready()

    def wait_to_write(self):
        self.wait_to_read()

    # -- autograd -----------------------------------------------------------

    def attach_grad(self, grad_req="write", stype=None):
        """Allocate gradient buffer (parity `ndarray.py attach_grad`).
        ``stype='row_sparse'`` allocates a row-sparse buffer: backward then
        deposits only the touched rows (never the dense table)."""
        if stype == "row_sparse":
            from .sparse import RowSparseNDArray

            self.grad = RowSparseNDArray(
                NDArray(jnp.zeros((0,) + tuple(self.shape[1:]), self.dtype)),
                NDArray(jnp.zeros((0,), jnp.int32)),
                tuple(self.shape), self._ctx)
        else:
            self.grad = NDArray(jnp.zeros(self.shape, self.dtype), self._ctx)
        self.grad_req = grad_req
        self._ag_marked = True

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd

        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def detach(self):
        # shares the (possibly still-pending) buffer — detaching must not
        # force a segment flush
        out = NDArray(self._buf, self._ctx)
        return out

    # -- shape ops (methods) ------------------------------------------------

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if not shape:
            shape = kwargs.get("shape")
        return _invoke("Reshape", self, shape=shape, reverse=kwargs.get("reverse", False))

    def reshape_like(self, other):
        return _invoke("Reshape", self, shape=other.shape)

    def expand_dims(self, axis):
        return _invoke("expand_dims", self, axis=axis)

    def squeeze(self, axis=None):
        return _invoke("squeeze", self, axis=axis)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return _invoke("transpose", self, axes=axes if axes else None)

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return _invoke("Flatten", self)

    def flip(self, axis):
        return _invoke("reverse", self, axis=axis)

    def tile(self, reps):
        return _invoke("tile", self, reps=reps)

    def repeat(self, repeats, axis=None):
        return _invoke("repeat", self, repeats=repeats, axis=axis)

    def swapaxes(self, dim1, dim2):
        return _invoke("SwapAxis", self, dim1=dim1, dim2=dim2)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return _invoke("SliceChannel", self, num_outputs=num_outputs, axis=axis,
                       squeeze_axis=squeeze_axis)

    def slice(self, begin, end, step=None):
        return _invoke("slice", self, begin=begin, end=end, step=step)

    def slice_axis(self, axis, begin, end):
        return _invoke("slice_axis", self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0, mode="clip"):
        return _invoke("take", self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        return _invoke("pick", self, index, axis=axis, keepdims=keepdims)

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return _invoke("one_hot", self, depth=depth, on_value=on_value, off_value=off_value,
                       dtype=dtype)

    def broadcast_to(self, shape):
        return _invoke("broadcast_to", self, shape=shape)

    def broadcast_like(self, other):
        return _invoke("broadcast_like", self, other)

    def diag(self, k=0):
        return _invoke("diag", self, k=k)

    # -- reductions ---------------------------------------------------------

    def sum(self, axis=None, keepdims=False, **kw):
        return _invoke("sum", self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        return _invoke("mean", self, axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False, **kw):
        return _invoke("prod", self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return _invoke("max", self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return _invoke("min", self, axis=axis, keepdims=keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return _invoke("norm", self, ord=ord, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return _invoke("argmax", self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return _invoke("argmin", self, axis=axis, keepdims=keepdims)

    def argsort(self, axis=-1, is_ascend=True):
        return _invoke("argsort", self, axis=axis, is_ascend=is_ascend)

    def sort(self, axis=-1, is_ascend=True):
        return _invoke("sort", self, axis=axis, is_ascend=is_ascend)

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return _invoke("topk", self, axis=axis, k=k, ret_typ=ret_typ, is_ascend=is_ascend)

    def clip(self, a_min, a_max):
        return _invoke("clip", self, a_min=a_min, a_max=a_max)

    def abs(self):
        return _invoke("abs", self)

    def sign(self):
        return _invoke("sign", self)

    def exp(self):
        return _invoke("exp", self)

    def log(self):
        return _invoke("log", self)

    def sqrt(self):
        return _invoke("sqrt", self)

    def square(self):
        return _invoke("square", self)

    def sigmoid(self):
        return _invoke("sigmoid", self)

    def tanh(self):
        return _invoke("tanh", self)

    def relu(self):
        return _invoke("relu", self)

    def softmax(self, axis=-1):
        return _invoke("softmax", self, axis=axis)

    def log_softmax(self, axis=-1):
        return _invoke("log_softmax", self, axis=axis)

    def dot(self, other, transpose_a=False, transpose_b=False):
        return _invoke("dot", self, other, transpose_a=transpose_a, transpose_b=transpose_b)

    def as_nd_ndarray(self):
        return self

    # -- arithmetic ---------------------------------------------------------

    def _binary(self, other, op, scalar_op, rop=None):
        if isinstance(other, NDArray):
            return _invoke(op, self, other)
        if isinstance(other, numeric_types):
            return _invoke(scalar_op, self, scalar=float(other))
        return NotImplemented

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return _invoke("_rminus_scalar", self, scalar=float(o))

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return _invoke("_rdiv_scalar", self, scalar=float(o))

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return _invoke("_rmod_scalar", self, scalar=float(o))

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return _invoke("_rpower_scalar", self, scalar=float(o))

    def __neg__(self):
        return _invoke("negative", self)

    def __abs__(self):
        return _invoke("abs", self)

    def __eq__(self, o):
        if o is None:
            return False
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __iadd__(self, o):
        out = self.__add__(o)
        self._data = out._data
        return self

    def __isub__(self, o):
        out = self.__sub__(o)
        self._data = out._data
        return self

    def __imul__(self, o):
        out = self.__mul__(o)
        self._data = out._data
        return self

    def __itruediv__(self, o):
        out = self.__truediv__(o)
        self._data = out._data
        return self

    # -- indexing -----------------------------------------------------------

    def __getitem__(self, key):
        from .. import autograd

        if not autograd.is_recording():
            # lazy capture (MXNET_LAZY=1): basic int/slice reads record a
            # `slice` node into the pending segment instead of forcing a
            # flush — optimizer/eval code that slices mid-loop keeps its
            # whole segment fused (ROADMAP lazy item; segments-unchanged
            # + bit-parity pinned by test_lazy.py)
            lazied = self._lazy_basic_getitem(key)
            if lazied is not None:
                return lazied
        key = _convert_index(key)
        if autograd.is_recording():
            # recorded read: gradients must flow through slicing
            # (`ops/indexing._ag_getitem`; scatter-add back into the
            # source's cotangent via jax's gather vjp)
            from .register import invoke_nd

            return invoke_nd("_ag_getitem", self, key=(key,))
        out = self._data[key]
        return NDArray(out, self._ctx)

    def _basic_slice_key(self, key):
        """Normalize a basic int/slice key into (begin, end, step,
        int_axes) over explicit leading axes, or None for anything the
        slice/scatter ops cannot express statically (arrays, bools,
        Ellipsis, newaxis, negative steps)."""
        keys = key if isinstance(key, tuple) else (key,)
        if len(keys) > self.ndim or not all(
                isinstance(k, (slice, int, _np.integer))
                and not isinstance(k, (bool, _np.bool_)) for k in keys):
            # bools subclass int but mean mask/new-axis semantics, not a
            # position — they (and arrays/Ellipsis/None) stay eager
            return None
        begin, end, step, int_axes = [], [], [], []
        for d, k in enumerate(keys):
            if isinstance(k, (int, _np.integer)):
                k = int(k)
                if k < 0:
                    k += self.shape[d]
                if not 0 <= k < self.shape[d]:
                    return None  # out of range: the eager path raises
                begin.append(k); end.append(k + 1); step.append(1)
                int_axes.append(d)
            else:
                if k.step is not None and int(k.step) < 0:
                    return None  # negative-step windows stay eager
                # resolve to concrete ints (python slice semantics over
                # the STATIC shape) — the slice/scatter op attr parsers
                # take int tuples, not Nones
                b, e, s = k.indices(self.shape[d])
                begin.append(b); end.append(e); step.append(s)
        return tuple(begin), tuple(end), tuple(step), tuple(int_axes)

    def _lazy_basic_getitem(self, key):
        """The captured rendering of a basic read: `slice` (+ `reshape`
        to drop integer axes) recorded into the owning segment. Returns
        None when capture is off or the key is not basic — caller runs
        the eager path (which flushes a pending segment)."""
        from ..lazy import graph as _lazy

        if not _lazy.enabled():
            return None
        basic = self._basic_slice_key(key)
        if basic is None:
            return None
        begin, end, step, int_axes = basic
        from .register import invoke_nd

        if int_axes and len(int_axes) == self.ndim:
            return None  # scalar read — about to escape anyway; stay eager
        out = invoke_nd("slice", self, begin=begin, end=end, step=step)
        if int_axes:
            shape = tuple(s for d, s in enumerate(out.shape)
                          if d not in set(int_axes))
            out = invoke_nd("reshape", out, shape=shape)
        return out

    def __setitem__(self, key, value):
        from .. import autograd

        if autograd.is_recording() and self._recorded_setitem(key, value):
            return
        if not autograd.is_recording() and self._lazy_basic_setitem(key, value):
            return
        if isinstance(value, NDArray):
            value = value._data
        elif isinstance(value, (_np.ndarray, list, tuple, float, int)):
            value = jnp.asarray(value, dtype=self.dtype)
        # re-placed on THIS array's device: a value built from host data
        # lands uncommitted on jax's default device, which would move a
        # cpu()/tpu(1) array to chip 0 under an unchanged label — and, for a
        # weight on chip 0, leave it uncommitted, so the second fused step
        # (fed the first one's committed outputs) re-lowers and recompiles
        if isinstance(key, slice) and key == slice(None):
            self._data = _on_device(
                jnp.broadcast_to(value, self.shape).astype(self.dtype),
                self._ctx)
            return
        key = _convert_index(key)
        self._data = _on_device(
            self._data.at[key].set(value.astype(self.dtype)
                                   if hasattr(value, "astype") else value),
            self._ctx)

    def _lazy_basic_setitem(self, key, value):
        """The captured rendering of a basic write: `_slice_assign(_scalar)`
        recorded into the pending segment, the result's buffer swapped in
        (the swap IS the version bump — nodes that recorded the old value
        keep referencing it). Returns False when capture is off / the key
        or value is not basic — caller runs the eager scatter (which
        flushes a pending segment)."""
        from ..lazy import graph as _lazy

        if not _lazy.enabled():
            return False
        basic = self._basic_slice_key(key)
        if basic is None:
            return False
        begin, end, step, _int_axes = basic
        from .register import invoke_nd

        if isinstance(value, numeric_types):
            out = invoke_nd("_slice_assign_scalar", self, begin=begin,
                            end=end, step=step, scalar=float(value))
        else:
            if not isinstance(value, NDArray):
                try:
                    value = NDArray(jnp.asarray(value, dtype=self.dtype),
                                    self._ctx)
                except (TypeError, ValueError):
                    return False
            out = invoke_nd("_slice_assign", self, value,
                            begin=begin, end=end, step=step)
        # share the PENDING buffer (out._buf) instead of reading
        # out._data — reading it would flush the very segment the write
        # just joined (the PR 10 out= precedent)
        self._buf = out._buf
        return True

    def _recorded_setitem(self, key, value):
        """Differentiable sliced write (`nd[a:b] = v` inside autograd.record).

        The reference forbids in-place writes to arrays in the graph
        (`imperative.cc` RecordOp's AGInfo check); here the write is
        FUNCTIONAL — `_slice_assign` (`matrix_op.cc:477`) — so gradients
        flow both around the window (to the pre-write value) and into the
        window (to `value`). The pre-write value becomes a fresh tape
        identity; if `self` was a marked leaf the mark (and grad buffer)
        moves to it, so `self.grad` after backward is the gradient wrt the
        value `self` held when recording reached this write.

        Returns True when the write was handled (basic int/slice keys);
        advanced (array) keys fall back to the raw in-place path."""
        keys = key if isinstance(key, tuple) else (key,)
        if not all(isinstance(k, (slice, int, _np.integer))
                   and not isinstance(k, (bool, _np.bool_)) for k in keys) \
                or len(keys) > self.ndim:
            # bools subclass int but mean mask/new-axis semantics, not a
            # position (the _basic_slice_key guard) — raw path handles them
            return False
        begin, end, step = [], [], []
        for k in keys:
            if isinstance(k, (int, _np.integer)):
                k = int(k)
                if k < 0:
                    k += self.shape[len(begin)]
                begin.append(k); end.append(k + 1); step.append(1)
            else:
                if k.step is not None and int(k.step) < 0:
                    return False  # negative-step writes stay on the raw path
                begin.append(k.start); end.append(k.stop); step.append(k.step or 1)
        old = NDArray(self._buf, self._ctx)
        old.grad, old.grad_req = self.grad, self.grad_req
        old._ag_marked, self._ag_marked = self._ag_marked, False
        from .. import autograd
        from .register import invoke_nd

        autograd._retarget(self, old)
        if isinstance(value, numeric_types):
            out = invoke_nd("_slice_assign_scalar", old, begin=tuple(begin),
                            end=tuple(end), step=tuple(step),
                            scalar=float(value))
        else:
            if not isinstance(value, NDArray):
                value = NDArray(jnp.asarray(value, dtype=self.dtype), self._ctx)
            out = invoke_nd("_slice_assign", old, value, begin=tuple(begin),
                            end=tuple(end), step=tuple(step))
        autograd._retarget(out, self)
        self._data = out._data
        return True

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # -- pickling ------------------------------------------------------------

    def __getstate__(self):
        import copyreg

        buf = self._buf
        if not (type(buf) is _PendingGrad and buf.consumed):
            # materialize: a pending lazy buffer must not pickle (a gradient
            # the one-program step consumed has no value: it goes as it is)
            self._data
        names = copyreg._slotnames(type(self))
        return (None, {n: getattr(self, n) for n in names
                       if n != "__weakref__" and hasattr(self, n)})

    def __setstate__(self, state):
        _, slots = state
        for k, v in (slots or {}).items():
            setattr(self, k, v)

    # -- serialization ------------------------------------------------------

    def save(self, fname):
        from .utils import save

        save(fname, self)


def _convert_index(key):
    if isinstance(key, NDArray):
        return key._data.astype(jnp.int32)
    if isinstance(key, tuple):
        return tuple(_convert_index(k) for k in key)
    if isinstance(key, _np.ndarray):
        return key
    return key


def _ctx_of(data):
    try:
        dev = list(data.devices())[0]
        if dev.platform == "cpu":
            return cpu(dev.id)
        from ..context import tpu

        return tpu(_accel_index(dev))
    except Exception:
        return cpu(0)


def _accel_index(dev):
    import jax as _jax

    accels = [d for d in _jax.local_devices() if d.platform != "cpu"]
    for i, d in enumerate(accels):
        if d == dev:
            return i
    return 0


def _invoke(op_name, *args, **kwargs):
    from .register import invoke_nd

    return invoke_nd(op_name, *args, **kwargs)


# ---------------------------------------------------------------------------
# creation helpers (parity: python/mxnet/ndarray/utils.py + ndarray.py)
# ---------------------------------------------------------------------------


def _on_device(jarr, ctx):
    """`jarr` committed to the device `ctx` names. On a CPU-only process a
    cpu context leaves the array where jax put it (one device, nothing to
    pin). A traced value, and an array sharded over several devices (an
    SPMD-placed weight), is left alone: its placement is not the label's."""
    if ctx.device_type in ("cpu", "cpu_pinned", "cpu_shared") and _default_is_cpu():
        return jarr
    if isinstance(jarr, jax.core.Tracer) or (
            isinstance(jarr, jax.Array)
            and len(jarr.sharding.device_set) > 1):
        return jarr
    return jax.device_put(jarr, ctx.jax_device)


def _place(jarr, ctx):
    ctx = ctx if ctx is not None else current_context()
    return NDArray(_on_device(jarr, ctx), ctx)


def _default_is_cpu():
    return jax.default_backend() == "cpu"


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        src = source_array._data
        dtype = dtype or source_array.dtype
    else:
        src = _np.asarray(source_array)
        if dtype is None:
            dtype = src.dtype if src.dtype != _np.float64 else _np.float32
    return _place(jnp.asarray(src, dtype=np_dtype(dtype)), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    return _place(jnp.zeros(_shape_t(shape), dtype=np_dtype(dtype)), ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    return _place(jnp.ones(_shape_t(shape), dtype=np_dtype(dtype)), ctx)


def full(shape, val, ctx=None, dtype=None, **kwargs):
    return _place(jnp.full(_shape_t(shape), val, dtype=np_dtype(dtype)), ctx)


def arange(start, stop=None, step=1.0, repeat=1, infer_range=False, ctx=None, dtype="float32"):
    out = jnp.arange(start, stop, step, dtype=np_dtype(dtype))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return _place(out, ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return _invoke("Concat", *arrays, dim=axis, num_args=len(arrays))


def _shape_t(shape):
    if isinstance(shape, integer_types):
        return (int(shape),)
    return tuple(int(s) for s in shape)


_PY_SCALAR_FN = {
    "broadcast_add": lambda a, b: a + b, "broadcast_sub": lambda a, b: a - b,
    "broadcast_mul": lambda a, b: a * b, "broadcast_div": lambda a, b: a / b,
    "broadcast_mod": lambda a, b: a % b, "broadcast_power": lambda a, b: a ** b,
    "broadcast_maximum": max, "broadcast_minimum": min,
    "broadcast_hypot": lambda a, b: (a * a + b * b) ** 0.5,
    "broadcast_equal": lambda a, b: float(a == b),
    "broadcast_not_equal": lambda a, b: float(a != b),
    "broadcast_greater": lambda a, b: float(a > b),
    "broadcast_greater_equal": lambda a, b: float(a >= b),
    "broadcast_lesser": lambda a, b: float(a < b),
    "broadcast_lesser_equal": lambda a, b: float(a <= b),
}


def _ufunc_helper(lhs, rhs, fn_array, fn_scalar, rfn_scalar=None):
    """Dispatch array/scalar combinations (parity `ndarray.py _ufunc_helper`)."""
    from .register import invoke_nd

    if isinstance(lhs, numeric_types):
        if isinstance(rhs, numeric_types):
            return _PY_SCALAR_FN[fn_array](lhs, rhs)
        return invoke_nd(rfn_scalar or fn_scalar, rhs, scalar=float(lhs))
    if isinstance(rhs, numeric_types):
        return invoke_nd(fn_scalar, lhs, scalar=float(rhs))
    return invoke_nd(fn_array, lhs, rhs)


def maximum(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_maximum", "_maximum_scalar")


def minimum(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_minimum", "_minimum_scalar")


def power(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_power", "_power_scalar", "_rpower_scalar")


def hypot(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_hypot", "_hypot_scalar")


def add(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_add", "_plus_scalar")


def subtract(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_sub", "_minus_scalar", "_rminus_scalar")


def multiply(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_mul", "_mul_scalar")


def divide(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_div", "_div_scalar", "_rdiv_scalar")


def modulo(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_mod", "_mod_scalar", "_rmod_scalar")


def equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_equal", "_equal_scalar")


def not_equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_not_equal", "_not_equal_scalar")


def greater(lhs, rhs):
    # scalar-lhs mirrors to the opposite comparison: 2 > x  ==  x < 2
    return _ufunc_helper(lhs, rhs, "broadcast_greater", "_greater_scalar", "_lesser_scalar")


def greater_equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_greater_equal", "_greater_equal_scalar",
                         "_lesser_equal_scalar")


def lesser(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_lesser", "_lesser_scalar", "_greater_scalar")


def lesser_equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_lesser_equal", "_lesser_equal_scalar",
                         "_greater_equal_scalar")


def true_divide(lhs, rhs):
    return divide(lhs, rhs)


def waitall():
    """Block until all async work completes (parity `mx.nd.waitall`) —
    including every thread's pending lazy segment."""
    from ..lazy.graph import flush_all

    flush_all("wait")
    jax.effects_barrier() if hasattr(jax, "effects_barrier") else None
    try:
        jax.block_until_ready(jnp.zeros(()))
    except Exception:
        pass


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor._ctx)


def onehot_encode(indices, out):
    res = _invoke("one_hot", indices, depth=out.shape[1])
    out._data = res._data
    return out
