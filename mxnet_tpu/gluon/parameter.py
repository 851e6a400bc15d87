"""gluon.Parameter / ParameterDict.

Parity: `python/mxnet/gluon/parameter.py` (Parameter with deferred
allocation, grad_req, per-context replicas; ParameterDict with prefix
namespacing, save/load :854,879).

TPU-native notes: per-context replicas exist for API parity with the
reference's multi-GPU data parallelism; the TPU-first scaling path keeps ONE
logical parameter and shards it over a `jax.sharding.Mesh` (see
`mxnet_tpu.parallel`). `Parameter.shard_spec` carries the GSPMD annotation —
the redesign of the reference's `group2ctx` model parallelism
(`graph_executor.cc:920 AssignContext`).
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from .._cached_op import PendingGrad
from ..context import Context, cpu, current_context
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import initializer

__all__ = ["DeferredInitializationError", "Parameter", "Constant", "ParameterDict", "tensor_types"]

tensor_types = (NDArray,)


class DeferredInitializationError(MXNetError):
    """Error for unfinished deferred initialization (parity parameter.py:40)."""


def _shape_complete(shape):
    return shape is not None and all(int(s) > 0 for s in shape)


class Parameter:
    """A Container holding parameters (weights) of Blocks.

    Parity: `gluon/parameter.py class Parameter`. ``grad_req`` in
    {'write', 'add', 'null'}; shape entries of 0 mean unknown (deferred
    init resolved on first forward).
    """

    def __init__(self, name, grad_req="write", shape=None, dtype=_np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default",
                 shard_spec=None):
        self._var = None
        self._data = None           # dict: dev-key -> NDArray
        self._grad = None
        self._ctx_list = None
        self._deferred_init = ()
        self.name = name
        self._shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self.shard_spec = shard_spec
        self.grad_req = grad_req
        self.attributes = {}
        self._trainer = None

    def _set_trainer(self, trainer):
        if self._trainer is not None and trainer is not None and \
                self._trainer is not trainer and self._stype != "default":
            raise RuntimeError(
                f"Failed to set the trainer for Parameter '{self.name}' because it was "
                f"already set. More than one trainers for a sparse Parameter is not "
                f"supported.")
        self._trainer = trainer

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={_np.dtype(self.dtype).name})"

    # -- properties ---------------------------------------------------------

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null"), f"grad_req must be one of write/add/null, got {req}"
        if not self._differentiable:
            req = "null"
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                for arr in self._data.values():
                    arr.grad = None
                    arr.grad_req = "null"
        elif self._data is not None and self._grad is None:
            self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(int(s) for s in new_shape) if new_shape is not None else None
            return
        assert len(self._shape) == len(new_shape) and all(
            j in (0, i) for i, j in zip(new_shape, self._shape)), \
            f"Expected shape {new_shape} is incompatible with given shape {self._shape}"
        self._shape = tuple(int(s) for s in new_shape)

    @property
    def stype(self):
        return self._stype

    # -- init ---------------------------------------------------------------

    def initialize(self, init=None, ctx=None, default_init=initializer.Uniform(),
                   force_reinit=False):
        """Initialize parameter/gradient arrays (parity parameter.py:360)."""
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None and self.init is not None:
            init = self.init
        # DELIBERATE DIVERGENCE from the reference: init stays None when
        # the param merely inherits the GLOBAL default_init —
        # _finish_deferred_init then routes through the name-suffix
        # dispatch (weight->init_weight, bias->zeros, ...). The reference
        # instead resolves default_init into the InitDesc `__init__` attr,
        # so a raw non-suffix name ('transitions') silently takes the
        # global initializer there; here it raises 'Unknown initialization
        # pattern'. The stricter behavior is intentional — an unmatched
        # name fails loudly instead of training with a surprise init — and
        # collapsing default_init into init here would also force e.g.
        # Xavier onto a 1-d "bias" param. Pinned (as a divergence) by
        # test_custom_named_parameter_init_dispatch.
        if not _shape_complete(self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError(f"Cannot initialize Parameter '{self.name}' because it has "
                             f"invalid shape: {self._shape}.")
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        assert _shape_complete(self._shape), \
            f"Cannot initialize Parameter '{self.name}' because it has " \
            f"invalid shape: {self._shape}. Please specify in_units, " \
            f"in_channels, etc for `Block`s."
        from .. import autograd
        with autograd.pause():
            if data is None:
                data = nd.zeros(self._shape, dtype=self.dtype, ctx=cpu())
                # `init` was resolved in initialize(): explicit arg > param.init
                # > default_init (reference parameter.py _finish_deferred_init).
                # A param-specific init rides the InitDesc `__init__` attr so
                # it applies REGARDLESS of the name suffix (the reference's
                # mechanism — a custom-named param like a CRF transition
                # matrix must not hit the weight/bias pattern fallback).
                attrs = {}
                # attrs ride the RESOLVED initializer (explicit arg >
                # param.init — resolved in initialize()), never self.init
                # directly, or an explicit initialize(init=...) would lose
                # to the stored one
                if init is not None:
                    init_obj = initializer.create(init)
                    # the attr route is a dumps/loads round trip, so only
                    # REGISTERED initializer classes can ride it; ad-hoc
                    # ones (Constant's closure Init) already bypass the
                    # suffix dispatch themselves
                    if type(init_obj).__name__.lower() in \
                            initializer._INIT_REGISTRY:
                        attrs["__init__"] = init_obj.dumps()
                initializer.create(init if init is not None else default_init)(
                    initializer.InitDesc(self.name, attrs), data)
            self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        if isinstance(ctx_list, Context):
            ctx_list = [ctx_list]
        self._ctx_list = list(ctx_list)
        self._data = {}
        for c in self._ctx_list:
            self._data[self._dev_key(c)] = data.copyto(c)
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        from .. import autograd
        self._grad = {}
        for k, arr in self._data.items():
            if self._grad_stype == "row_sparse":
                from ..ndarray.sparse import RowSparseNDArray
                import jax.numpy as jnp

                g = RowSparseNDArray(
                    nd.NDArray(jnp.zeros((0,) + tuple(arr.shape[1:]), arr.dtype)),
                    nd.NDArray(jnp.zeros((0,), jnp.int32)),
                    tuple(arr.shape), arr.context)
            else:
                g = nd.zeros(arr.shape, dtype=arr.dtype, ctx=arr.context)
            self._grad[k] = g
            autograd.mark_variables(arr, g, self._grad_req)

    @staticmethod
    def _dev_key(ctx):
        return (ctx.device_type, ctx.device_id)

    def _check_and_get(self, arr_dict, ctx):
        if arr_dict is not None:
            if ctx is list:
                return list(arr_dict.values())
            if ctx is None:
                if len(arr_dict) == 1:
                    return next(iter(arr_dict.values()))
                ctx = current_context()
            if isinstance(ctx, list):
                return [self._check_and_get(arr_dict, c) for c in ctx]
            key = self._dev_key(ctx)
            if key in arr_dict:
                return arr_dict[key]
            raise RuntimeError(f"Parameter '{self.name}' was not initialized on context {ctx}. "
                               f"It was only initialized on {self._ctx_list}.")
        if self._deferred_init:
            raise DeferredInitializationError(
                f"Parameter '{self.name}' has not been initialized yet because "
                f"initialization was deferred. Actual initialization happens during "
                f"the first forward pass. Please pass one batch of data through "
                f"the network before accessing Parameters.")
        raise RuntimeError(
            f"Parameter '{self.name}' has not been initialized. Note that you should "
            f"initialize parameters and create Trainer with Block.collect_params() "
            f"instead of Block.params because the later does not include Parameters "
            f"of nested child Blocks")

    # -- accessors ----------------------------------------------------------

    def data(self, ctx=None):
        return self._check_and_get(self._data, ctx)

    def list_data(self):
        self._check_and_get(self._data, list)
        return [self._data[self._dev_key(c)] for c in self._ctx_list]

    def grad(self, ctx=None):
        if self._data is not None and self._grad is None:
            raise RuntimeError(f"Cannot get gradient array for Parameter '{self.name}' "
                               f"because grad_req='null'")
        return self._check_and_get(self._grad, ctx)

    def list_grad(self):
        if self._data is not None and self._grad is None:
            raise RuntimeError(f"Cannot get gradient array for Parameter '{self.name}' "
                               f"because grad_req='null'")
        self._check_and_get(self._grad, list)
        return [self._grad[self._dev_key(c)] for c in self._ctx_list]

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return self._deferred_init[1]
            raise RuntimeError(f"Parameter '{self.name}' has not been initialized")
        return self._ctx_list

    def set_data(self, data):
        """Set this parameter's value on all contexts."""
        self.shape = data.shape
        if self._data is None:
            assert self._deferred_init, \
                f"Parameter '{self.name}' has not been initialized"
            init, ctx, default_init, _ = self._deferred_init
            self._deferred_init = (init, ctx, default_init,
                                   data if isinstance(data, NDArray) else nd.array(data))
            return
        from .. import autograd
        with autograd.pause():
            for k, arr in self._data.items():
                src = data if isinstance(data, NDArray) else nd.array(data)
                arr._data = src.copyto(arr.context)._data

    def zero_grad(self):
        if self._grad is None:
            return
        from .. import autograd
        with autograd.pause():
            for g in self._grad.values():
                buf = g._buf
                if type(buf) is PendingGrad and buf.owner is not None:
                    # the deferred backward runs now: its outputs must
                    # not wait for an update that donates what they need
                    buf.force("zero_grad")
                g[:] = 0

    def reset_ctx(self, ctx):
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = next(iter(self._data.values()))
            self._init_impl(data, ctx)
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx, default_init, data)
        else:
            raise ValueError(f"Cannot reset context for Parameter '{self.name}' because it "
                             "has not been initialized.")

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        from .. import autograd
        with autograd.pause():
            for k in list(self._data):
                self._data[k] = self._data[k].astype(dtype)
            if self._grad is not None:
                for k in list(self._grad):
                    g = self._grad[k]
                    if type(g._buf) is PendingGrad and g._buf.consumed:
                        # the one-program step left no value to cast
                        self._grad[k] = nd.zeros(g.shape, dtype=dtype,
                                                 ctx=g.context)
                    else:
                        self._grad[k] = g.astype(dtype)
                    autograd.mark_variables(self._data[k], self._grad[k], self._grad_req)

    def var(self):
        """The Symbol representing this parameter (symbolic API)."""
        if self._var is None:
            from .. import symbol
            self._var = symbol.var(self.name, shape=self.shape, dtype=self.dtype,
                                   lr_mult=self.lr_mult, wd_mult=self.wd_mult,
                                   init=self.init)
        return self._var

    def row_sparse_data(self, row_id):
        """Rows of this parameter selected by ``row_id`` as a
        RowSparseNDArray (parity `gluon/parameter.py row_sparse_data`).

        The reference requires `stype='row_sparse'` and pulls the rows from
        the trainer's kvstore (dist servers hold the authority copy). The
        TPU design stores the weight dense in HBM (gathers are XLA-native);
        when a dist trainer is attached the rows are refreshed through
        `kvstore.row_sparse_pull` first, then gathered — only O(rows)
        touches the host/wire, never the full table."""
        from ..base import MXNetError
        from ..ndarray.sparse import RowSparseNDArray
        import jax.numpy as jnp

        if self._stype != "row_sparse" and self._grad_stype != "row_sparse":
            raise MXNetError(
                f"Parameter '{self.name}' is not sparse (stype={self._stype}, "
                f"grad_stype={self._grad_stype}); use data() instead")
        if not isinstance(row_id, NDArray):
            row_id = nd.array(row_id, dtype="int64")
        trainer = getattr(self, "_trainer", None)
        if trainer is not None and getattr(trainer, "_kvstore", None) is not None \
                and "dist" in trainer._kvstore.type:
            trainer._row_sparse_pull(self, row_id)
        arr = self._check_and_get(self._data, None)
        return self._gather_rows(arr, row_id)

    @staticmethod
    def _gather_rows(arr, row_id):
        from ..ndarray.sparse import RowSparseNDArray
        import jax.numpy as jnp

        uniq = jnp.unique(row_id._data.reshape(-1).astype(jnp.int32)) \
            if row_id.size else jnp.zeros((0,), jnp.int32)
        rows = jnp.take(arr._data, uniq, axis=0) if uniq.size else \
            jnp.zeros((0,) + tuple(arr.shape[1:]), arr.dtype)
        return RowSparseNDArray(NDArray(rows), NDArray(uniq), tuple(arr.shape),
                                arr.context)

    def list_row_sparse_data(self, row_id):
        """One RowSparseNDArray per context, aligned with list_ctx()
        (parity gluon/parameter.py list_row_sparse_data)."""
        trainer = getattr(self, "_trainer", None)
        if trainer is not None and getattr(trainer, "_kvstore", None) is not None \
                and "dist" in trainer._kvstore.type:
            trainer._row_sparse_pull(self, row_id)
        arrs = self._check_and_get(self._data, list)
        return [self._gather_rows(a, row_id) for a in arrs]


class Constant(Parameter):
    """A constant parameter (never updated by gradients).

    Parity: `gluon/parameter.py class Constant`.
    """

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        self.value = value

        class Init(initializer.Initializer):
            def _init_weight(self2, _, arr):
                arr[:] = value.asnumpy()

            # constants may have any name; bypass suffix dispatch entirely
            _init_default = _init_weight
            _init_bias = _init_weight
            _init_gamma = _init_weight
            _init_beta = _init_weight

        # instance passed directly (initializer.create accepts instances) —
        # no global-registry mutation, so same-named constants can't collide
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=Init(), differentiable=False)


class ParameterDict:
    """A dictionary managing a set of Parameters (parity gluon/parameter.py)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}  # OrderedDict semantics (py3.7 dicts ordered)
        self._shared = shared

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __repr__(self):
        name = self._prefix + " " if self._prefix else ""
        return f"{name}(\n" + "\n".join(f"  {v}" for v in self.values()) + "\n)"

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._shared._params[name]
        return None

    def get(self, name, **kwargs):
        """Retrieve or create a Parameter ``self.prefix + name``."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    existing = getattr(param, k)
                    if k == "shape" and v is not None and existing is not None:
                        param.shape = v
                        continue
                    assert v is None or v == existing or (k == "dtype" and
                            _np.dtype(v) == _np.dtype(existing)), \
                        f"Cannot retrieve Parameter '{name}' because desired attribute " \
                        f"does not match with stored for attribute '{k}': " \
                        f"desired '{v}' vs stored '{getattr(param, k)}'"
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError(f"No constant named '{name}'. Please specify value "
                               "if you want to create a new constant.")
            param = Constant(name, value)
            self._params[name] = param
        elif value is not None:
            assert isinstance(param, Constant), \
                f"Parameter '{name}' already exists but it is not a constant."
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    f"Cannot update self with other because they have different " \
                    f"Parameters with the same name '{k}'"
            else:
                self._params[k] = v

    def initialize(self, init=initializer.Uniform(), ctx=None, verbose=False,
                   force_reinit=False):
        if verbose:
            init.set_verbosity(verbose=verbose)
        for v in self.values():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        """Save parameters to an .params file (reference NDArray dict format,
        `ndarray.cc:1578` / `c_api.cc MXNDArraySave`)."""
        arg_dict = {}
        for param in self.values():
            weight = param._reduce() if hasattr(param, "_reduce") else param.data(
                param.list_ctx()[0]).copyto(cpu())
            if not param.name.startswith(strip_prefix):
                raise ValueError(f"Prefix '{strip_prefix}' is to be stripped before saving, "
                                 f"but Parameter's name '{param.name}' does not start "
                                 f"with '{strip_prefix}'")
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False, ignore_extra=False,
             restore_prefix=""):
        if restore_prefix:
            for name in self.keys():
                assert name.startswith(restore_prefix), \
                    f"restore_prefix is '{restore_prefix}' but Parameter name '{name}' " \
                    f"does not start with it"
        lprefix = len(restore_prefix)
        loaded = nd.load(filename)
        arg_dict = {(restore_prefix + k[4:] if k.startswith("arg:") or k.startswith("aux:")
                     else restore_prefix + k): v for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    f"Parameter '{name[lprefix:]}' is missing in file '{filename}'"
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    f"Parameter '{name[lprefix:]}' loaded from file '{filename}' is not " \
                    f"present in ParameterDict"
                continue
            self[name]._load_init(arg_dict[name])

    def list_ctx(self):
        s = set()
        for v in self.values():
            s.update(v.list_ctx())
        return sorted(s, key=str)


def _load_init(self, data, ctx=None):
    """Initialize a Parameter directly from a loaded array."""
    if self.shape is not None and any(self.shape):
        for self_dim, data_dim in zip(self.shape, data.shape):
            assert self_dim in (0, data_dim), \
                f"Failed loading Parameter '{self.name}' from saved params: " \
                f"shape incompatible expected {self.shape} vs saved {data.shape}"
        self.shape = tuple(i if i != 0 else j for i, j in zip(self.shape, data.shape))
    if self.dtype is not None:
        data = data.astype(self.dtype, copy=False)
    if self._data is None:
        if self._deferred_init:
            ctx = self._deferred_init[1]
        elif ctx is None:
            ctx = [cpu()]
        self._init_impl(data, ctx)
    else:
        self.set_data(data)
    self._deferred_init = ()


Parameter._load_init = _load_init
