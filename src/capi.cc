// capi.cc — the flat C ABI over the mxnet_tpu runtime.
//
// Role parity: /root/reference/src/c_api/c_api.cc +
// /root/reference/include/mxnet/c_api.h (the MXNET_DLL surface every
// non-Python frontend binds). The reference's C API fronts its C++
// engine; ours fronts the Python/JAX runtime by embedding (or attaching
// to) a CPython interpreter — the tpu compute path IS the XLA program
// built by the Python layer, so the flat ABI delegates op dispatch to it
// rather than duplicating a second op registry in C++.
//
// Covered slice (verdict order #6, extended round 5):
//   MXGetVersion, MXGetLastError, MXListAllOpNames, MXRandomSeed,
//   MXNDArrayCreate / Free / GetShape / GetDType / GetContext /
//     SyncCopyFromCPU / SyncCopyToCPU / Reshape / Slice / At /
//     Save / Load / GetGrad,
//   MXImperativeInvoke (op invoke-by-name, string-typed attrs — the
//     c_api_ndarray.cc:132 role),
//   MXSymbolCreateFromJSON / MXSymbolSaveToJSON / MXSymbolFree /
//     MXSymbolListArguments / MXSymbolListOutputs,
//   MXAutogradSetIsRecording / SetIsTraining / MarkVariables / Backward —
//     enough for a NON-PYTHON frontend to train (the client test runs a
//     full sgd regression loop with zero python imports).
//
// Conventions (mirroring the reference ABI):
//   * every call returns 0 on success, -1 on failure; the message is
//     retrievable via MXGetLastError() (thread-local).
//   * NDArrayHandle / SymbolHandle are opaque; free with the matching
//     *Free call.
//   * pointers returned by GetShape / SaveToJSON / ListAllOpNames and the
//     output array of MXImperativeInvoke stay valid until the next call
//     of the same function on the same thread.
//   * dtype codes follow the reference's mshadow enum
//     (float32=0 float64=1 float16=2 uint8=3 int32=4 int8=5 int64=6)
//     with tpu extensions bfloat16=7, bool=8.
//
// Host modes:
//   * loaded into an existing Python process (ctypes/cffi): attaches via
//     PyGILState, never re-initialises the interpreter.
//   * loaded from a plain C/C++ host: Py_InitializeEx on first call; set
//     MXNET_TPU_ROOT (or run from the repo root) so `import mxnet_tpu`
//     resolves.

#include <Python.h>

#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

#define MX_API extern "C" __attribute__((visibility("default")))

typedef void* NDArrayHandle;
typedef void* SymbolHandle;

namespace {

thread_local std::string g_last_error;
thread_local std::vector<int64_t> g_shape_buf;
thread_local std::string g_json_buf;
thread_local std::vector<std::string> g_name_store;
thread_local std::vector<const char*> g_name_ptrs;
thread_local std::vector<NDArrayHandle> g_out_handles;

std::mutex g_boot_mutex;
PyObject* g_helpers = nullptr;  // dict holding the helper functions

// The Python half of the bridge. Kept tiny: marshal C types <-> the real
// runtime objects (NDArray, Symbol). Attrs arrive as strings and are
// coerced with ast.literal_eval (the DMLC string-param parsing role).
const char kHelperSrc[] = R"PY(
import ast, os, sys

try:
    import mxnet_tpu as mx
except ImportError:
    for p in (os.environ.get('MXNET_TPU_ROOT'), os.getcwd()):
        if p and p not in sys.path:
            sys.path.insert(0, p)
    import mxnet_tpu as mx

import numpy as np
import jax.numpy as jnp
from mxnet_tpu.ndarray.register import invoke_nd
from mxnet_tpu.ops import registry as _reg
from mxnet_tpu.symbol import symbol as _symbol

_DT = {0: 'float32', 1: 'float64', 2: 'float16', 3: 'uint8',
       4: 'int32', 5: 'int8', 6: 'int64', 7: 'bfloat16', 8: 'bool'}
_DT_REV = {v: k for k, v in _DT.items()}


def capi_create(shape, dtype):
    return mx.nd.zeros(tuple(shape), dtype=_DT[dtype])


def capi_shape(arr):
    return tuple(int(d) for d in arr.shape)


def capi_dtype(arr):
    dt = arr.dtype
    name = dt.name if hasattr(dt, 'name') else str(dt)
    return _DT_REV[name]


def capi_from_bytes(arr, buf):
    np_dt = np.dtype(arr.dtype)
    want = int(np.prod(arr.shape, dtype=np.int64)) * np_dt.itemsize
    if len(buf) != want:
        raise ValueError('byte size mismatch: got %d, want %d' % (len(buf), want))
    arr._data = jnp.asarray(
        np.frombuffer(buf, dtype=np_dt).reshape(arr.shape))


def capi_to_bytes(arr):
    return np.asarray(arr._data).tobytes()


def _coerce(v):
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return v


def capi_invoke(name, inputs, keys, vals):
    attrs = {k: _coerce(v) for k, v in zip(keys, vals)}
    out = invoke_nd(name, *inputs, **attrs)
    return list(out) if isinstance(out, (list, tuple)) else [out]


def capi_list_ops():
    return list(_reg.list_ops())


def capi_sym_from_json(s):
    return _symbol.load_json(s)


def capi_sym_to_json(sym):
    return sym.tojson()


def capi_sym_arguments(sym):
    return list(sym.list_arguments())


def capi_sym_outputs(sym):
    return list(sym.list_outputs())


def capi_get_context(arr):
    dev = getattr(arr, '_ctx', None)
    kind = getattr(dev, 'device_type', 'cpu')
    # reference dev_type codes (c_api.h): cpu=1, accelerator=2
    return (1, 0) if str(kind).startswith('cpu') else \
        (2, int(getattr(dev, 'device_id', 0)))


def capi_reshape(arr, dims):
    return arr.reshape(tuple(int(d) for d in dims))


def capi_slice(arr, begin, end):
    return arr[int(begin):int(end)]


def capi_at(arr, idx):
    return arr[int(idx)]


def capi_save(fname, arrs, keys):
    if keys:
        mx.nd.save(fname, dict(zip(keys, arrs)))
    else:
        mx.nd.save(fname, list(arrs))


def capi_load(fname):
    out = mx.nd.load(fname)
    if isinstance(out, dict):
        return list(out.keys()), list(out.values())
    return [], list(out)


def capi_random_seed(seed):
    mx.random.seed(int(seed))


def capi_set_recording(flag):
    from mxnet_tpu import autograd
    return int(autograd.set_recording(bool(flag)))


def capi_set_training(flag):
    from mxnet_tpu import autograd
    return int(autograd.set_training(bool(flag)))


_GRAD_REQ = {0: 'null', 1: 'write', 3: 'add'}


def capi_mark_variables(variables, reqs, gradients):
    from mxnet_tpu import autograd
    autograd.mark_variables(list(variables), list(gradients),
                            [_GRAD_REQ[int(r)] for r in reqs])


def capi_backward(outputs, ograds, retain_graph):
    from mxnet_tpu import autograd
    autograd.backward(list(outputs),
                      head_grads=list(ograds) if ograds else None,
                      retain_graph=bool(retain_graph))


def capi_get_grad(arr):
    if arr.grad is None:
        raise ValueError('NDArray has no gradient buffer (mark it first)')
    return arr.grad
)PY";

void set_error(const char* msg) { g_last_error = msg ? msg : "unknown error"; }

void set_error_from_py() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  std::string msg = "python error";
  if (value != nullptr) {
    PyObject* s = PyObject_Str(value);
    if (s != nullptr) {
      const char* c = PyUnicode_AsUTF8(s);
      if (c != nullptr) msg = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
  g_last_error = msg;
}

// RAII GIL acquisition that also boots the interpreter when this library
// is hosted by a plain C process (the reference's ABI needs no host
// runtime; ours needs the interpreter that owns the XLA client).
class Gil {
 public:
  Gil() {
    if (!Py_IsInitialized()) {
      std::lock_guard<std::mutex> lk(g_boot_mutex);
      if (!Py_IsInitialized()) {
        Py_InitializeEx(0);
        PyEval_SaveThread();  // release so PyGILState_Ensure is uniform
      }
    }
    state_ = PyGILState_Ensure();
  }
  ~Gil() { PyGILState_Release(state_); }
  Gil(const Gil&) = delete;
  Gil& operator=(const Gil&) = delete;

 private:
  PyGILState_STATE state_;
};

// GIL must be held. Lazily execs the helper source (which imports the
// framework — slow the first time: backend init).
int ensure_helpers() {
  if (g_helpers != nullptr) return 0;
  PyObject* dict = PyDict_New();
  if (dict == nullptr) {
    set_error_from_py();
    return -1;
  }
  PyDict_SetItemString(dict, "__builtins__", PyEval_GetBuiltins());
  PyObject* res = PyRun_String(kHelperSrc, Py_file_input, dict, dict);
  if (res == nullptr) {
    set_error_from_py();
    Py_DECREF(dict);
    return -1;
  }
  Py_DECREF(res);
  g_helpers = dict;  // intentionally immortal
  return 0;
}

// GIL must be held; returns a borrowed ref or nullptr (+error set).
PyObject* helper(const char* name) {
  if (ensure_helpers() != 0) return nullptr;
  PyObject* fn = PyDict_GetItemString(g_helpers, name);
  if (fn == nullptr) set_error((std::string("missing helper: ") + name).c_str());
  return fn;
}

}  // namespace

MX_API int MXGetVersion(int* out) {
  *out = 10500;  // API parity level: reference fork is MXNet 1.5.0
  return 0;
}

MX_API const char* MXGetLastError() { return g_last_error.c_str(); }

MX_API int MXNDArrayCreate(const int64_t* shape, int ndim, int dtype,
                           NDArrayHandle* out) {
  Gil gil;
  PyObject* fn = helper("capi_create");
  if (fn == nullptr) return -1;
  PyObject* shp = PyList_New(ndim);
  if (shp == nullptr) {
    set_error_from_py();
    return -1;
  }
  for (int i = 0; i < ndim; ++i)
    PyList_SET_ITEM(shp, i, PyLong_FromLongLong(shape[i]));
  PyObject* arr = PyObject_CallFunction(fn, "Oi", shp, dtype);
  Py_DECREF(shp);
  if (arr == nullptr) {
    set_error_from_py();
    return -1;
  }
  *out = static_cast<NDArrayHandle>(arr);  // ownership -> caller
  return 0;
}

MX_API int MXNDArrayFree(NDArrayHandle h) {
  if (h == nullptr) return 0;
  Gil gil;
  Py_DECREF(static_cast<PyObject*>(h));
  return 0;
}

MX_API int MXNDArrayGetShape(NDArrayHandle h, int* out_ndim,
                             const int64_t** out_shape) {
  Gil gil;
  PyObject* fn = helper("capi_shape");
  if (fn == nullptr) return -1;
  PyObject* tup = PyObject_CallFunction(fn, "O", static_cast<PyObject*>(h));
  if (tup == nullptr) {
    set_error_from_py();
    return -1;
  }
  Py_ssize_t n = PyTuple_Size(tup);
  g_shape_buf.resize(static_cast<size_t>(n));
  for (Py_ssize_t i = 0; i < n; ++i)
    g_shape_buf[static_cast<size_t>(i)] =
        PyLong_AsLongLong(PyTuple_GET_ITEM(tup, i));
  Py_DECREF(tup);
  *out_ndim = static_cast<int>(n);
  *out_shape = g_shape_buf.data();
  return 0;
}

MX_API int MXNDArrayGetDType(NDArrayHandle h, int* out) {
  Gil gil;
  PyObject* fn = helper("capi_dtype");
  if (fn == nullptr) return -1;
  PyObject* v = PyObject_CallFunction(fn, "O", static_cast<PyObject*>(h));
  if (v == nullptr) {
    set_error_from_py();
    return -1;
  }
  *out = static_cast<int>(PyLong_AsLong(v));
  Py_DECREF(v);
  return 0;
}

MX_API int MXNDArraySyncCopyFromCPU(NDArrayHandle h, const void* data,
                                    size_t size_bytes) {
  Gil gil;
  PyObject* fn = helper("capi_from_bytes");
  if (fn == nullptr) return -1;
  PyObject* buf = PyBytes_FromStringAndSize(static_cast<const char*>(data),
                                            static_cast<Py_ssize_t>(size_bytes));
  if (buf == nullptr) {
    set_error_from_py();
    return -1;
  }
  PyObject* r =
      PyObject_CallFunction(fn, "OO", static_cast<PyObject*>(h), buf);
  Py_DECREF(buf);
  if (r == nullptr) {
    set_error_from_py();
    return -1;
  }
  Py_DECREF(r);
  return 0;
}

MX_API int MXNDArraySyncCopyToCPU(NDArrayHandle h, void* data,
                                  size_t size_bytes) {
  Gil gil;
  PyObject* fn = helper("capi_to_bytes");
  if (fn == nullptr) return -1;
  PyObject* b = PyObject_CallFunction(fn, "O", static_cast<PyObject*>(h));
  if (b == nullptr) {
    set_error_from_py();
    return -1;
  }
  char* src = nullptr;
  Py_ssize_t n = 0;
  if (PyBytes_AsStringAndSize(b, &src, &n) != 0) {
    set_error_from_py();
    Py_DECREF(b);
    return -1;
  }
  if (static_cast<size_t>(n) != size_bytes) {
    set_error("MXNDArraySyncCopyToCPU: size mismatch");
    Py_DECREF(b);
    return -1;
  }
  std::memcpy(data, src, static_cast<size_t>(n));
  Py_DECREF(b);
  return 0;
}

MX_API int MXImperativeInvoke(const char* op_name, int num_inputs,
                              NDArrayHandle* inputs, int* num_outputs,
                              NDArrayHandle** outputs, int num_params,
                              const char** keys, const char** vals) {
  Gil gil;
  PyObject* fn = helper("capi_invoke");
  if (fn == nullptr) return -1;
  PyObject* ins = PyList_New(num_inputs);
  PyObject* ks = PyList_New(num_params);
  PyObject* vs = PyList_New(num_params);
  if (ins == nullptr || ks == nullptr || vs == nullptr) {
    set_error_from_py();
    Py_XDECREF(ins);
    Py_XDECREF(ks);
    Py_XDECREF(vs);
    return -1;
  }
  for (int i = 0; i < num_inputs; ++i) {
    PyObject* o = static_cast<PyObject*>(inputs[i]);
    Py_INCREF(o);
    PyList_SET_ITEM(ins, i, o);
  }
  for (int i = 0; i < num_params; ++i) {
    PyObject* k = PyUnicode_FromString(keys[i]);
    PyObject* v = PyUnicode_FromString(vals[i]);
    if (k == nullptr || v == nullptr) {  // e.g. invalid UTF-8 in a raw char*
      set_error_from_py();
      Py_XDECREF(k);
      Py_XDECREF(v);
      Py_DECREF(ins);
      Py_DECREF(ks);
      Py_DECREF(vs);
      return -1;
    }
    PyList_SET_ITEM(ks, i, k);
    PyList_SET_ITEM(vs, i, v);
  }
  PyObject* outs = PyObject_CallFunction(fn, "sOOO", op_name, ins, ks, vs);
  Py_DECREF(ins);
  Py_DECREF(ks);
  Py_DECREF(vs);
  if (outs == nullptr) {
    set_error_from_py();
    return -1;
  }
  Py_ssize_t n = PyList_Size(outs);
  g_out_handles.clear();
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* o = PyList_GET_ITEM(outs, i);
    Py_INCREF(o);  // each output handle is caller-owned
    g_out_handles.push_back(static_cast<NDArrayHandle>(o));
  }
  Py_DECREF(outs);
  *num_outputs = static_cast<int>(n);
  *outputs = g_out_handles.data();
  return 0;
}

MX_API int MXListAllOpNames(int* out_size, const char*** out_array) {
  Gil gil;
  PyObject* fn = helper("capi_list_ops");
  if (fn == nullptr) return -1;
  PyObject* lst = PyObject_CallFunction(fn, nullptr);
  if (lst == nullptr) {
    set_error_from_py();
    return -1;
  }
  Py_ssize_t n = PyList_Size(lst);
  g_name_store.clear();
  g_name_ptrs.clear();
  g_name_store.reserve(static_cast<size_t>(n));
  for (Py_ssize_t i = 0; i < n; ++i) {
    const char* c = PyUnicode_AsUTF8(PyList_GET_ITEM(lst, i));
    if (c == nullptr) PyErr_Clear();  // never leave an exception pending
    g_name_store.emplace_back(c != nullptr ? c : "");
  }
  Py_DECREF(lst);
  for (const auto& s : g_name_store) g_name_ptrs.push_back(s.c_str());
  *out_size = static_cast<int>(n);
  *out_array = g_name_ptrs.data();
  return 0;
}

MX_API int MXSymbolCreateFromJSON(const char* json, SymbolHandle* out) {
  Gil gil;
  PyObject* fn = helper("capi_sym_from_json");
  if (fn == nullptr) return -1;
  PyObject* sym = PyObject_CallFunction(fn, "s", json);
  if (sym == nullptr) {
    set_error_from_py();
    return -1;
  }
  *out = static_cast<SymbolHandle>(sym);
  return 0;
}

MX_API int MXSymbolSaveToJSON(SymbolHandle h, const char** out_json) {
  Gil gil;
  PyObject* fn = helper("capi_sym_to_json");
  if (fn == nullptr) return -1;
  PyObject* s = PyObject_CallFunction(fn, "O", static_cast<PyObject*>(h));
  if (s == nullptr) {
    set_error_from_py();
    return -1;
  }
  const char* c = PyUnicode_AsUTF8(s);
  g_json_buf = c != nullptr ? c : "";
  Py_DECREF(s);
  *out_json = g_json_buf.c_str();
  return 0;
}

MX_API int MXSymbolFree(SymbolHandle h) {
  if (h == nullptr) return 0;
  Gil gil;
  Py_DECREF(static_cast<PyObject*>(h));
  return 0;
}

// ---------------------------------------------------------------------------
// round-5 surface extension: context/reshape/slice, save/load, symbol
// introspection, RNG seed and the autograd slice — enough for a non-python
// frontend to TRAIN (create -> mark -> record -> invoke -> backward -> read
// grads), mirroring include/mxnet/c_api.h MXAutograd*/MXNDArray* names.
// ---------------------------------------------------------------------------

namespace {

// Call helper(name) with `args`; on success returns the result object
// (new ref), else records the error and returns null.
PyObject* call_helper(const char* name, PyObject* args) {
  PyObject* fn = helper(name);
  if (fn == nullptr) {
    Py_XDECREF(args);
    return nullptr;
  }
  PyObject* out = PyObject_CallObject(fn, args);
  Py_XDECREF(args);
  if (out == nullptr) set_error_from_py();
  return out;
}

// Unpack a python list of NDArrays into g_out_handles (caller-owned refs).
int store_handle_list(PyObject* lst, int* out_size, NDArrayHandle** outputs) {
  Py_ssize_t n = PyList_Size(lst);
  g_out_handles.clear();
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* o = PyList_GET_ITEM(lst, i);
    Py_INCREF(o);
    g_out_handles.push_back(static_cast<NDArrayHandle>(o));
  }
  *out_size = static_cast<int>(n);
  *outputs = g_out_handles.data();
  return 0;
}

// Unpack a python list of strings into the name stores.
int store_name_list(PyObject* lst, int* out_size, const char*** out_array) {
  Py_ssize_t n = PyList_Size(lst);
  g_name_store.clear();
  g_name_ptrs.clear();
  for (Py_ssize_t i = 0; i < n; ++i) {
    const char* c = PyUnicode_AsUTF8(PyList_GET_ITEM(lst, i));
    if (c == nullptr) PyErr_Clear();
    g_name_store.emplace_back(c != nullptr ? c : "");
  }
  for (const auto& s : g_name_store) g_name_ptrs.push_back(s.c_str());
  *out_size = static_cast<int>(n);
  *out_array = g_name_ptrs.data();
  return 0;
}

thread_local std::vector<std::string> g_load_names;
thread_local std::vector<const char*> g_load_name_ptrs;

}  // namespace

MX_API int MXNDArrayGetContext(NDArrayHandle h, int* out_dev_type,
                               int* out_dev_id) {
  Gil gil;
  PyObject* out = call_helper("capi_get_context",
                              Py_BuildValue("(O)", static_cast<PyObject*>(h)));
  if (out == nullptr) return -1;
  int ok = PyArg_ParseTuple(out, "ii", out_dev_type, out_dev_id);
  Py_DECREF(out);
  if (!ok) {
    set_error_from_py();
    return -1;
  }
  return 0;
}

MX_API int MXNDArrayReshape(NDArrayHandle h, int ndim, const int64_t* dims,
                            NDArrayHandle* out) {
  Gil gil;
  PyObject* shape = PyList_New(ndim);
  if (shape == nullptr) {
    set_error_from_py();
    return -1;
  }
  for (int i = 0; i < ndim; ++i)
    PyList_SET_ITEM(shape, i, PyLong_FromLongLong(dims[i]));
  PyObject* o = call_helper(
      "capi_reshape", Py_BuildValue("(ON)", static_cast<PyObject*>(h), shape));
  if (o == nullptr) return -1;
  *out = static_cast<NDArrayHandle>(o);
  return 0;
}

MX_API int MXNDArraySlice(NDArrayHandle h, int64_t begin, int64_t end,
                          NDArrayHandle* out) {
  Gil gil;
  PyObject* o = call_helper(
      "capi_slice", Py_BuildValue("(OLL)", static_cast<PyObject*>(h),
                                  static_cast<long long>(begin),
                                  static_cast<long long>(end)));
  if (o == nullptr) return -1;
  *out = static_cast<NDArrayHandle>(o);
  return 0;
}

MX_API int MXNDArrayAt(NDArrayHandle h, int64_t idx, NDArrayHandle* out) {
  Gil gil;
  PyObject* o = call_helper(
      "capi_at", Py_BuildValue("(OL)", static_cast<PyObject*>(h),
                               static_cast<long long>(idx)));
  if (o == nullptr) return -1;
  *out = static_cast<NDArrayHandle>(o);
  return 0;
}

MX_API int MXNDArraySave(const char* fname, int num, NDArrayHandle* handles,
                         const char** keys) {
  Gil gil;
  PyObject* arrs = PyList_New(num);
  PyObject* ks = keys != nullptr ? PyList_New(num) : PyList_New(0);
  if (arrs == nullptr || ks == nullptr) {
    set_error_from_py();
    Py_XDECREF(arrs);
    Py_XDECREF(ks);
    return -1;
  }
  for (int i = 0; i < num; ++i) {
    PyObject* o = static_cast<PyObject*>(handles[i]);
    Py_INCREF(o);
    PyList_SET_ITEM(arrs, i, o);
    if (keys != nullptr) {
      PyObject* k = PyUnicode_FromString(keys[i]);
      if (k == nullptr) {
        set_error_from_py();
        Py_DECREF(arrs);
        Py_DECREF(ks);
        return -1;
      }
      PyList_SET_ITEM(ks, i, k);
    }
  }
  PyObject* out = call_helper("capi_save",
                              Py_BuildValue("(sNN)", fname, arrs, ks));
  if (out == nullptr) return -1;
  Py_DECREF(out);
  return 0;
}

MX_API int MXNDArrayLoad(const char* fname, int* out_size,
                         NDArrayHandle** out_arr, int* out_name_size,
                         const char*** out_names) {
  Gil gil;
  PyObject* out = call_helper("capi_load", Py_BuildValue("(s)", fname));
  if (out == nullptr) return -1;
  PyObject* names = PyTuple_GetItem(out, 0);
  PyObject* arrs = PyTuple_GetItem(out, 1);
  if (names == nullptr || arrs == nullptr) {
    set_error_from_py();
    Py_DECREF(out);
    return -1;
  }
  store_handle_list(arrs, out_size, out_arr);
  g_load_names.clear();
  g_load_name_ptrs.clear();
  for (Py_ssize_t i = 0; i < PyList_Size(names); ++i) {
    const char* c = PyUnicode_AsUTF8(PyList_GET_ITEM(names, i));
    if (c == nullptr) PyErr_Clear();
    g_load_names.emplace_back(c != nullptr ? c : "");
  }
  for (const auto& s : g_load_names) g_load_name_ptrs.push_back(s.c_str());
  *out_name_size = static_cast<int>(g_load_names.size());
  *out_names = g_load_name_ptrs.data();
  Py_DECREF(out);
  return 0;
}

MX_API int MXSymbolListArguments(SymbolHandle h, int* out_size,
                                 const char*** out_array) {
  Gil gil;
  PyObject* out = call_helper(
      "capi_sym_arguments", Py_BuildValue("(O)", static_cast<PyObject*>(h)));
  if (out == nullptr) return -1;
  store_name_list(out, out_size, out_array);
  Py_DECREF(out);
  return 0;
}

MX_API int MXSymbolListOutputs(SymbolHandle h, int* out_size,
                               const char*** out_array) {
  Gil gil;
  PyObject* out = call_helper(
      "capi_sym_outputs", Py_BuildValue("(O)", static_cast<PyObject*>(h)));
  if (out == nullptr) return -1;
  store_name_list(out, out_size, out_array);
  Py_DECREF(out);
  return 0;
}

MX_API int MXRandomSeed(int seed) {
  Gil gil;
  PyObject* out = call_helper("capi_random_seed",
                              Py_BuildValue("(i)", seed));
  if (out == nullptr) return -1;
  Py_DECREF(out);
  return 0;
}

MX_API int MXAutogradSetIsRecording(int is_recording, int* prev) {
  Gil gil;
  PyObject* out = call_helper("capi_set_recording",
                              Py_BuildValue("(i)", is_recording));
  if (out == nullptr) return -1;
  if (prev != nullptr) *prev = static_cast<int>(PyLong_AsLong(out));
  Py_DECREF(out);
  return 0;
}

MX_API int MXAutogradSetIsTraining(int is_training, int* prev) {
  Gil gil;
  PyObject* out = call_helper("capi_set_training",
                              Py_BuildValue("(i)", is_training));
  if (out == nullptr) return -1;
  if (prev != nullptr) *prev = static_cast<int>(PyLong_AsLong(out));
  Py_DECREF(out);
  return 0;
}

MX_API int MXAutogradMarkVariables(int num, NDArrayHandle* var_handles,
                                   unsigned* reqs_array,
                                   NDArrayHandle* grad_handles) {
  Gil gil;
  PyObject* vars = PyList_New(num);
  PyObject* reqs = PyList_New(num);
  PyObject* grads = PyList_New(num);
  if (vars == nullptr || reqs == nullptr || grads == nullptr) {
    set_error_from_py();
    Py_XDECREF(vars);
    Py_XDECREF(reqs);
    Py_XDECREF(grads);
    return -1;
  }
  for (int i = 0; i < num; ++i) {
    PyObject* v = static_cast<PyObject*>(var_handles[i]);
    PyObject* g = static_cast<PyObject*>(grad_handles[i]);
    Py_INCREF(v);
    Py_INCREF(g);
    PyList_SET_ITEM(vars, i, v);
    PyList_SET_ITEM(grads, i, g);
    PyList_SET_ITEM(reqs, i, PyLong_FromUnsignedLong(reqs_array[i]));
  }
  PyObject* out = call_helper("capi_mark_variables",
                              Py_BuildValue("(NNN)", vars, reqs, grads));
  if (out == nullptr) return -1;
  Py_DECREF(out);
  return 0;
}

MX_API int MXAutogradBackward(int num_output, NDArrayHandle* output_handles,
                              NDArrayHandle* ograd_handles,
                              int retain_graph) {
  Gil gil;
  PyObject* outs = PyList_New(num_output);
  if (outs == nullptr) {
    set_error_from_py();
    return -1;
  }
  for (int i = 0; i < num_output; ++i) {
    PyObject* o = static_cast<PyObject*>(output_handles[i]);
    Py_INCREF(o);
    PyList_SET_ITEM(outs, i, o);
  }
  PyObject* ograds = nullptr;
  if (ograd_handles != nullptr) {
    ograds = PyList_New(num_output);
    if (ograds == nullptr) {
      set_error_from_py();
      Py_DECREF(outs);
      return -1;
    }
    for (int i = 0; i < num_output; ++i) {
      PyObject* o = static_cast<PyObject*>(ograd_handles[i]);
      Py_INCREF(o);
      PyList_SET_ITEM(ograds, i, o);
    }
  } else {
    ograds = PyList_New(0);
  }
  PyObject* out = call_helper(
      "capi_backward",
      Py_BuildValue("(NNi)", outs, ograds, retain_graph));
  if (out == nullptr) return -1;
  Py_DECREF(out);
  return 0;
}

MX_API int MXNDArrayGetGrad(NDArrayHandle h, NDArrayHandle* out) {
  Gil gil;
  PyObject* o = call_helper("capi_get_grad",
                            Py_BuildValue("(O)", static_cast<PyObject*>(h)));
  if (o == nullptr) return -1;
  *out = static_cast<NDArrayHandle>(o);
  return 0;
}
