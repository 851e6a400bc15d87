"""gluon.Block / HybridBlock — the neural-network container API.

Parity: `python/mxnet/gluon/block.py` (`Block`:127 — children/params/
name-scope/`__call__`:535; `HybridBlock`:671 — `_build_cache`:748 creating an
`ndarray.CachedOp`:785, `hybridize`:832, deferred shape inference).

TPU-native redesign: hybridize does NOT lower to a Symbol graph — the same
eager NDArray code is traced by `jax.jit` into one XLA program (see
`mxnet_tpu._cached_op.CachedOp`). Deferred parameter-shape inference runs
the forward under `jax.eval_shape` (abstract evaluation — zero FLOPs), the
analogue of the reference's symbolic `infer_shape` pass
(`infer_graph_attr_pass.cc:94`).
"""
from __future__ import annotations

import copy
import re
import threading

import numpy as _np
import jax

from ..base import MXNetError
from ..context import Context, cpu, current_context
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import name as _name
from .. import tracing
from .._cached_op import CachedOp
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    """Name-manager scope for Blocks (parity block.py:35)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                if not hasattr(_name.NameManager._current, "value"):
                    _name.NameManager._current.value = _name.NameManager()
                prefix = _name.NameManager._current.value.get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        self._name_scope = _name.Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


def _flatten(args, inout_str):
    """Flatten nested list/tuple structure of NDArrays (parity block.py:57)."""
    if isinstance(args, NDArray):
        return [args], int(0)
    if args is None:
        return [None], int(-1)
    assert isinstance(args, (list, tuple)), \
        f"{inout_str} must be (nested) list of NDArray, but got {type(args)}"
    flat = []
    fmts = []
    for i in args:
        arg, fmt = _flatten(i, inout_str)
        flat.extend(arg)
        fmts.append(fmt)
    return flat, fmts


def _regroup(args, fmt):
    if isinstance(fmt, int):
        if fmt == -1:
            return None, args
        if fmt == 0:
            return args[0], args[1:]
        return args[:fmt], args[fmt:]
    ret = []
    for i in fmt:
        res, args = _regroup(args, i)
        ret.append(res)
    return ret, args


class Block:
    """Base class for all neural network layers and models
    (parity `gluon/block.py:127`)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = {}
        self._forward_pre_hooks = {}

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(f"  ({key}): {_indent(repr(block), 2)}"
                           for key, block in self.__dict__.items()
                           if isinstance(block, Block))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(value, type(existing)):
                raise TypeError(f"Changing attribute type for {self.name} from "
                                f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or self._reg_params[name] is value, \
                f"Overriding Parameter attribute {name} is not allowed. " \
                f"If you want to share parameters between blocks, please set " \
                f"'params' at Block construction instead."
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        """This block's direct ParameterDict (no children)."""
        return self._params

    def collect_params(self, select=None):
        """Return a ParameterDict with this block's and all children's
        Parameters, optionally filtered by regex ``select``.

        Direct Parameter attributes (``self.w = Parameter(...)``) are
        included under ``"<block_name>.<attr>"`` keys and fully support
        imperative training, ``initialize`` and ``save_parameters`` /
        ``load_parameters`` (which key by attribute path). They are NOT
        visible to the 1.x symbolic surfaces — ``HybridBlock.export`` and
        prefix-keyed ``ParameterDict.save/load`` — which match the
        ParameterDict-created prefixed names; use ``self.params.get``
        for parameters that must round-trip through symbol JSON.

        The result is IDENTITY-deduplicated: a Parameter shared across
        blocks (tied weights held as a direct attribute on two blocks)
        appears exactly once, under its first-encountered key — two keys
        for one Parameter would register it twice in ``Trainer``, which
        then double-applies its update with two separate optimizer slots
        (the reference's name-keyed ParameterDict dedupes tied params
        naturally)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        seen = set()

        def merge(items):
            fresh = {}
            for name, p in items:
                if id(p) in seen:
                    continue
                seen.add(id(p))
                fresh[name] = p
            ret.update(fresh)

        # direct Parameter ATTRIBUTES (2.x style: `self.w = Parameter(...)`)
        # live in _reg_params only; without this they would be saved by
        # save_parameters (which walks _reg_params) yet invisible to
        # initialize()/Trainer — silently untrained parameters. Keyed by
        # the block's unique instance name (user-chosen Parameter names
        # like "weight" repeat across sibling layers).
        lib_params = set(map(id, self.params.values()))
        direct = {f"{self.name}.{attr}": p
                  for attr, p in self._reg_params.items()
                  if id(p) not in lib_params}
        if not select:
            merge(self.params.items())
            merge(direct.items())
        else:
            pattern = re.compile(select)
            merge((name, value) for name, value in self.params.items()
                  if pattern.match(name))
            merge((name, value) for name, value in direct.items()
                  if pattern.match(name))
        for cld in self._children.values():
            merge(cld.collect_params(select=select).items())
        return ret

    def _check_container_with_block(self):
        children = set(self._children.values())
        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and not k.startswith("_"):
                items = v.values() if isinstance(v, dict) else v
                for item in items:
                    if isinstance(item, Block) and item not in children:
                        import warnings
                        warnings.warn(f'"{item}" is an unregistered container with Blocks. '
                                      f"Note that Blocks inside the list, tuple or dict will "
                                      f"not be registered automatically. Make sure to register "
                                      f"them using register_child() or switching to "
                                      f"nn.Sequential/nn.HybridSequential instead.")

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn):
        """Apply ``fn`` recursively to every child then self."""
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from .. import initializer
        if init is None:
            init = initializer.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def save_parameters(self, filename, deduplicate=False):
        """Save parameters to file (reference `block.py save_parameters`;
        format = NDArray-dict `.params`, `ndarray.cc:1578`)."""
        params = self._collect_params_with_prefix()
        arg_dict = {key: val._reduce() if hasattr(val, "_reduce")
                    else val.data(val.list_ctx()[0]).copyto(cpu())
                    for key, val in params.items()}
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in i for i in loaded.keys()):
            # legacy loading: use full-name ParameterDict load
            del loaded
            self.collect_params().load(filename, ctx, allow_missing, ignore_extra,
                                       self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    f"Parameter '{name}' is missing in file '{filename}', which contains " \
                    f"parameters: {_brief_print_list(loaded.keys())}. Set allow_missing=True " \
                    f"to ignore missing parameters."
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    f"Parameter '{name}' loaded from file '{filename}' is not present in "
                    f"ParameterDict, which contains parameters "
                    f"{_brief_print_list(params.keys())}. Set ignore_extra=True to ignore.")
            if name in params:
                params[name]._load_init(loaded[name], ctx)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # MXNet<=1.3 names kept as aliases
    save_params = save_parameters
    load_params = load_parameters

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        if _PARAM_OVERRIDE.get() is not None:
            # inside a CachedOp capture (or its shape-only pass): the
            # block's device-side scope, so nested blocks nest in the
            # program's op_names. Never in the eager call path
            with jax.named_scope(self._name):
                out = self.forward(*args)
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        """Override to implement forward computation using NDArray."""
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a table of layers/params (parity block.py summary)."""
        summary = []
        hooks = []

        def _register(block):
            def hook(blk, inp, out):
                n_params = sum(int(_np.prod(p.shape)) for p in blk._reg_params.values()
                               if p.shape is not None)
                out0 = out[0] if isinstance(out, (list, tuple)) else out
                summary.append((blk.name, type(blk).__name__,
                                getattr(out0, "shape", None), n_params))
            hooks.append(block.register_forward_hook(hook))

        self.apply(_register)
        try:
            self(*inputs)
            print(f"{'Layer (type)':<44}{'Output Shape':<24}{'Param #':<12}")
            print("=" * 80)
            total = 0
            for name, cls, shape, n in summary:
                print(f"{name + ' (' + cls + ')':<44}{str(shape):<24}{n:<12}")
                total += n
            print("=" * 80)
            print(f"Total params: {total}")
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks_dict):
        self._hooks_dict = hooks_dict
        self.id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1

    def detach(self):
        self._hooks_dict.pop(self.id, None)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    if len(lines) == 1:
        return s_
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line for line in lines)


def _brief_print_list(lst, limit=7):
    lst = list(lst)
    if len(lst) > limit:
        return ", ".join(map(repr, lst[:limit // 2])) + ", ..., " + \
            ", ".join(map(repr, lst[-limit // 2:]))
    return ", ".join(map(repr, lst))


class HybridBlock(Block):
    """A Block that can be captured into a single compiled XLA program.

    Parity: `gluon/block.py:671`. ``hybrid_forward(self, F, x, *args,
    **params)`` receives ``F = mxnet_tpu.ndarray`` in BOTH modes — there is
    no separate symbol tracing language; hybridization is jax tracing of the
    identical code (SURVEY.md §7 stage 3).
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._cached_op = None
        self._active = False
        self._flags = {}
        self._in_fmt = None

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, (HybridBlock, Parameter)):
            self._clear_cached_op()

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                f"Children of HybridBlock must also be HybridBlock, but {str(block)} has "
                f"type {str(type(block))}. If you are using Sequential, please try "
                f"HybridSequential instead.")
        super().register_child(block, name)
        self._clear_cached_op()

    def hybridize(self, active=True, static_alloc=False, static_shape=False):
        self._active = active
        self._flags = {"static_alloc": static_alloc, "static_shape": static_shape}
        self._clear_cached_op()
        super().hybridize(active, static_alloc=static_alloc, static_shape=static_shape)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def _clear_cached_op(self):
        self._cached_op = None

    # -- deferred shape inference ------------------------------------------

    def infer_shape(self, *args):
        """Infer (and set) deferred parameter shapes from input shapes.

        Leaf layers with deferred params (Dense, Conv, norms) override this
        to set shapes directly from the input. The generic version runs the
        whole subtree's forward under ``jax.eval_shape`` (abstract
        evaluation, zero FLOPs): each leaf hit mid-trace catches its own
        DeferredInitializationError and resolves itself from its (shaped)
        tracer inputs. This replaces the reference's symbolic InferShape
        pass (`infer_graph_attr_pass.cc:94`) with the compiler's own
        abstract interpreter."""
        self._generic_infer_shape(*args)

    def infer_type(self, *args):
        self._generic_infer_shape(*args)

    def _generic_infer_shape(self, *args):
        from .. import autograd
        if getattr(self, "_in_shape_inference", False):
            raise NotImplementedError(
                f"{type(self).__name__} has uninitialized parameters with unknown shape "
                f"and does not override `infer_shape`. Construct it with fully-specified "
                f"shapes (in_units/in_channels) or implement `infer_shape`.")
        self._in_shape_inference = True
        try:
            from .. import random as _random
            flat, fmt = _flatten(args, "input")
            avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) if isinstance(a, NDArray) else a
                     for a in flat]
            # concrete base key fetched OUTSIDE the abstract trace (a key
            # minted inside eval_shape would be a tracer and poison the
            # process-global eager provider)
            base_key = _random.next_key()

            def run(*tracers):
                nds = [NDArray(t) if not isinstance(t, NDArray) else t for t in tracers]
                re_args, _ = _regroup(list(nds), fmt)
                if not isinstance(re_args, (list, tuple)):
                    re_args = [re_args]
                # empty (non-None) override map forces the eager code path in
                # every nested hybridized block without providing values; the
                # trace key provider keeps abstract keys out of the eager PRNG
                token = _PARAM_OVERRIDE.set({})
                token2 = _SHAPE_INFER.set(True)
                try:
                    with autograd._RecordingStateScope(False, None):
                        with _random.TraceKeyProvider(base_key):
                            out = self.forward(*re_args)
                finally:
                    _SHAPE_INFER.reset(token2)
                    _PARAM_OVERRIDE.reset(token)
                flat_out, _ = _flatten(out, "output")
                return [o._data for o in flat_out]

            jax.eval_shape(run, *avals)
            # shapes are now known everywhere; materialize OUTSIDE the trace
            for p in self.collect_params().values():
                if p._deferred_init:
                    p._finish_deferred_init()
        finally:
            self._in_shape_inference = False

    # -- forward ------------------------------------------------------------

    def _build_cache(self):
        """Create the CachedOp: params are leading inputs, then data
        (reference `_build_cache` block.py:748)."""
        params = self._cached_graph_params = list(self.collect_params().values())

        def fn(*arrays):
            n = len(params)
            param_arrays, inputs = arrays[:n], arrays[n:]
            # bind traced param values into the blocks for the duration of
            # the trace via a value override
            overrides = {id(p): a for p, a in zip(params, param_arrays)}
            token = _PARAM_OVERRIDE.set(overrides)
            try:
                args, _ = _regroup(list(inputs), self._in_fmt)
                if not isinstance(args, (list, tuple)):
                    args = [args]
                out = self.hybrid_forward_dispatch(*args)
            finally:
                _PARAM_OVERRIDE.reset(token)
            flat, self._out_fmt = _flatten(out, "output")
            return flat

        self._cached_op = CachedOp(fn, **self._flags)

    def hybrid_forward_dispatch(self, *args):
        """Run this block's forward with params fetched (possibly traced)."""
        return self.forward(*args)

    def _call_cached_op(self, *args):
        if self._cached_op is None:
            flat_args, self._in_fmt = _flatten(args, "input")
            self._build_cache()
        else:
            flat_args, fmt = _flatten(args, "input")
            if fmt != self._in_fmt:
                self._in_fmt = fmt
                self._build_cache()
                flat_args, _ = _flatten(args, "input")
        params = self._cached_graph_params
        # one span a call (children: gather here, dispatch and record in
        # CachedOp.__call__): where the host's share of a gluon step goes
        with tracing.span("cached_op.call", cat="gluon", params=len(params)):
            with tracing.span("cached_op.gather", cat="gluon"):
                try:
                    param_nds = [p.data() for p in params]
                except DeferredInitializationError:
                    self._deferred_infer_shape(*args)
                    for p in params:
                        if p._deferred_init:
                            p._finish_deferred_init()
                    param_nds = [p.data() for p in params]
            out = self._cached_op(*(param_nds + list(flat_args)))
            if isinstance(out, NDArray):
                out = [out]
            ret, _ = _regroup(list(out), self._out_fmt)
        return ret

    def _deferred_infer_shape(self, *args):
        try:
            self.infer_shape(*args)
        except Exception as e:
            error_msg = f"Deferred initialization failed because shape cannot be " \
                        f"inferred. {e}"
            raise ValueError(error_msg) from e

    def __call__(self, *args):
        return super().__call__(*args)

    def forward(self, x, *args):
        """Defines the forward computation; calls hybrid_forward with
        ``F = mxnet_tpu.ndarray`` (NDArray inputs) or ``F =
        mxnet_tpu.symbol`` (Symbol inputs — the reference's symbolic
        hybridization path, used by `export`)."""
        from ..symbol.symbol import Symbol as _Sym

        if isinstance(x, _Sym):
            from .. import symbol as _sym_api

            params = {k: v.var() for k, v in self._reg_params.items()}
            return self.hybrid_forward(_sym_api, x, *args, **params)
        if self._active and _PARAM_OVERRIDE.get() is None:
            return self._call_cached_op(x, *args)
        try:
            params = {k: _param_value(v) for k, v in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer_shape(x, *args)
            if not _SHAPE_INFER.get():
                # real (non-abstract) call: materialize now
                for p in self._reg_params.values():
                    if p._deferred_init:
                        p._finish_deferred_init()
            params = {k: _param_value(v) for k, v in self._reg_params.items()}
        return self.hybrid_forward(nd, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Override to implement forward computation using NDArray ops via F."""
        raise NotImplementedError

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Export `path-symbol.json` + `path-####.params` for deployment
        (reference block.py HybridBlock.export): the forward is re-traced
        SYMBOLICALLY (F=symbol) so the emitted json round-trips through
        `SymbolBlock.imports` and the Module checkpoint loader."""
        from .. import symbol as _sym_api

        n_in = len(self._in_fmt) if isinstance(getattr(self, "_in_fmt", None),
                                               (list, tuple)) else 1
        if n_in == 1:
            data_syms = [_sym_api.var("data")]
        else:
            data_syms = [_sym_api.var(f"data{i}") for i in range(n_in)]
        out = self(*data_syms)
        if not isinstance(out, (list, tuple)):
            out = [out]
        sym = _sym_api.Group(list(out)) if len(out) > 1 else out[0]
        sym.save(f"{path}-symbol.json", remove_amp_cast=remove_amp_cast)

        arg_names = set(sym.list_arguments())
        aux_names = set(sym.list_auxiliary_states())
        arg_dict = {}
        for name, param in self.collect_params().items():
            if name in arg_names:
                arg_dict[f"arg:{name}"] = param._reduce() if hasattr(param, "_reduce") \
                    else param.data(param.list_ctx()[0]).copyto(cpu())
            elif name in aux_names:
                arg_dict[f"aux:{name}"] = param.data(param.list_ctx()[0]).copyto(cpu())
        fname = f"{path}-{epoch:04d}.params"
        nd.save(fname, arg_dict)
        return sym
        return fname


# During CachedOp tracing, Parameter.data() values are overridden with
# tracer-backed NDArrays; contextvar maps id(Parameter) -> jax value.
import contextvars

_PARAM_OVERRIDE = contextvars.ContextVar("mxnet_tpu_param_override", default=None)
# True while the shape-only abstract pass runs: params must NOT materialize
# inside the trace (a buffer created there would be a leaked tracer)
_SHAPE_INFER = contextvars.ContextVar("mxnet_tpu_shape_infer", default=False)


def _param_value(p):
    overrides = _PARAM_OVERRIDE.get()
    if overrides is not None and id(p) in overrides:
        v = overrides[id(p)]
        return v if isinstance(v, NDArray) else NDArray(v)
    if _SHAPE_INFER.get() and p._data is None:
        from .parameter import _shape_complete
        if _shape_complete(p.shape):
            import jax.numpy as jnp
            # abstract stand-in: shape/dtype only, value never escapes
            return NDArray(jnp.zeros(p.shape, p.dtype))
    return p.data()


class SymbolBlock(HybridBlock):
    """A Block wrapping a pre-built Symbol graph (reference `block.py:952`):
    the deserialization target of `HybridBlock.export` /
    `model.save_checkpoint`. Parameters are the symbol's non-input
    arguments; the graph executes as one jitted program through the same
    machinery as the symbolic Executor."""

    def __init__(self, outputs, inputs, params=None):
        from ..symbol.symbol import Symbol, Group

        # bypass HybridBlock prefix machinery: param names must match the
        # symbol's argument names exactly
        super().__init__(prefix="", params=None)
        self._params = ParameterDict("", shared=params)

        if isinstance(inputs, Symbol):
            inputs = list(inputs) if len(inputs) > 1 else [inputs]
        if isinstance(outputs, (list, tuple)):
            outputs = Group(list(outputs)) if len(outputs) > 1 else outputs[0]
        self._sym = outputs
        self._input_names = [i.name for i in inputs]

        arg_names = self._sym.list_arguments()
        aux_names = set(self._sym.list_auxiliary_states())
        self._param_order = []
        for name in arg_names + sorted(aux_names):
            if name in self._input_names:
                continue
            grad_req = "null" if name in aux_names else "write"
            p = self.params.get(name, grad_req=grad_req,
                                allow_deferred_init=True)
            self._reg_params[name] = p
            self._param_order.append(name)
        self._graph_fns = {}

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None,
                allow_missing=False, ignore_extra=False):
        """Load an exported model: `SymbolBlock.imports('m-symbol.json',
        ['data'], 'm-0000.params')` (reference block.py SymbolBlock.imports)."""
        from .. import symbol as _sym_api

        sym = _sym_api.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym_api.var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.collect_params().load(param_file, ctx=ctx,
                                      allow_missing=allow_missing,
                                      ignore_extra=ignore_extra)
        return ret

    def _sb_fn(self, train):
        fn = self._graph_fns.get(train)
        if fn is None:
            from ..symbol.executor import _graph_fn

            aux = self._sym.list_auxiliary_states()
            args = [n for n in self._input_names +
                    [p for p in self._param_order if p not in aux]]
            # _graph_fn wants arg order = the order we pass arrays in
            fn = _graph_fn(self._sym, args, aux, train)
            self._graph_fns[train] = fn
        return fn

    def forward(self, x, *args):
        from ..symbol.symbol import Symbol as _Sym
        from .. import random as _random
        from .. import autograd as _ag

        if isinstance(x, _Sym):
            raise MXNetError("SymbolBlock cannot be re-traced symbolically")
        inputs = [x] + [a for a in args if a is not None]
        if len(inputs) != len(self._input_names):
            raise MXNetError(f"SymbolBlock expects {len(self._input_names)} "
                             f"inputs {self._input_names}, got {len(inputs)}")
        # finish deferred param init from input shapes
        try:
            for name in self._param_order:
                self._reg_params[name].data()
        except DeferredInitializationError:
            shapes = {n: tuple(i.shape) for n, i in zip(self._input_names, inputs)}
            arg_shapes, _, aux_shapes = self._sym.infer_shape_partial(**shapes)
            arg_names = self._sym.list_arguments()
            aux_names = self._sym.list_auxiliary_states()
            for n, s in list(zip(arg_names, arg_shapes)) + list(zip(aux_names, aux_shapes)):
                if n in self._reg_params and s is not None:
                    p = self._reg_params[n]
                    if p._data is None:
                        p.shape = s
                        if p._deferred_init:
                            p._finish_deferred_init()
                        else:
                            p.initialize()
        aux_set = set(self._sym.list_auxiliary_states())
        train = bool(_ag.is_training())
        fn = self._sb_fn(train)
        key = _random.next_key()
        arg_arrays = tuple(i._data for i in inputs) + tuple(
            self._reg_params[n].data()._data for n in self._param_order
            if n not in aux_set)
        aux_arrays = tuple(self._reg_params[n].data()._data
                           for n in self._sym.list_auxiliary_states())
        outs, aux_new = fn(key, arg_arrays, aux_arrays)
        if train:
            for n, a in zip(self._sym.list_auxiliary_states(), aux_new):
                self._reg_params[n].data()._data = a
        out_nds = [NDArray(o) for o in outs]
        return out_nds[0] if len(out_nds) == 1 else out_nds
