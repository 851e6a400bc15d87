"""Loader for the native C++ runtime (librt_tpu.so).

The reference loads libmxnet.so via ctypes (`python/mxnet/base.py`); here the
native library provides the host-side runtime only (dependency engine for
IO/checkpoint ordering, RecordIO reader, shared-memory arena) — compute is
XLA. The .so is a build product of the tracked sources in `src/`: it is
(re)built with `make -C src` whenever it is missing or older than any of
them, so nothing loaded here comes from a stale or foreign file. Without a
toolchain, or when the build fails, the reason is printed and the pure-python
engine is used.
"""
from __future__ import annotations

import ctypes
import os
import sys
import threading

_lib = None
_lib_tried = False
_engine = None
_lock = threading.Lock()

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(os.path.dirname(_HERE), "src")
_LIB_PATH = os.path.join(_HERE, "_native", "librt_tpu.so")


# files of src/ that go into OTHER build products (the C ABI, the native
# self-test), not into librt_tpu.so
_NOT_RT_SOURCES = ("capi.cc", "test_runtime.cc")


def _stale():
    """True when librt_tpu.so is missing or one of its sources is newer."""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    if not os.path.isdir(_SRC_DIR):
        return False  # installed without sources: the .so is all there is
    return any(e.is_file() and e.name not in _NOT_RT_SOURCES
               and e.stat().st_mtime > built for e in os.scandir(_SRC_DIR))


def build_native(force=False):
    """Build librt_tpu.so from src/ when stale (``force``: unconditionally).
    Returns True when an up-to-date library is in place afterwards; a build
    that cannot run or fails says why on stderr. Serialized across
    processes by a lock file, so concurrent first imports build once."""
    import fcntl
    import shutil
    import subprocess

    if not force and not _stale():
        return True
    if not os.path.isdir(_SRC_DIR) or shutil.which("make") is None:
        print("mxnet_tpu: native runtime not built (no src/ or no `make`); "
              "using the python engine", file=sys.stderr)
        return False
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    with open(_LIB_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not force and not _stale():
            return True  # another process built it while we waited
        # stale: the default target (librt_tpu.so and, where python3-config
        # exists, the C ABI). forced: librt_tpu.so alone, unconditionally
        cmd = ["make", "-C", _SRC_DIR]
        if force:
            cmd += ["-B", os.path.relpath(_LIB_PATH, _SRC_DIR)]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=300)
        except subprocess.TimeoutExpired:
            print("mxnet_tpu: native runtime build timed out; using the "
                  "python engine", file=sys.stderr)
            return False
    if proc.returncode != 0 or _stale():
        print(f"mxnet_tpu: native runtime build failed (exit "
              f"{proc.returncode}); using the python engine\n"
              f"{proc.stdout}{proc.stderr}", file=sys.stderr)
        return False
    return True


def get_lib():
    global _lib, _lib_tried
    with _lock:
        if not _lib_tried:
            _lib_tried = True
            if os.environ.get("MXNET_BUILD_NATIVE", "1") == "1":
                ok = build_native()
            else:
                ok = not _stale()
            if ok:
                _lib = ctypes.CDLL(_LIB_PATH)
    return _lib


def native_available():
    return get_lib() is not None


def native_engine():
    """Python-facing handle to the native host engine; None if not built."""
    global _engine
    lib = get_lib()
    if lib is None:
        return None
    with _lock:
        if _engine is None:
            from .native_engine import NativeEngine

            nthreads = int(os.environ.get("MXNET_CPU_WORKER_NTHREADS", "4"))
            _engine = NativeEngine(lib, num_threads=nthreads)
    return _engine


def native_recordio(path):
    """Native mmap RecordIO index for `path`; None if the .so isn't built."""
    lib = get_lib()
    if lib is None:
        return None
    from .native_engine import NativeRecordIO

    return NativeRecordIO(lib, path)


def shared_memory(name, size=None, create=False):
    """Named POSIX shm segment (CPUSharedStorageManager role); None if the
    .so isn't built."""
    lib = get_lib()
    if lib is None:
        return None
    from .native_engine import SharedMemoryArena

    return SharedMemoryArena(lib, name, size=size, create=create)


_imgpipe = None


def native_imgpipe(num_threads=4):
    """Native JPEG decode+augment pipe; None when the .so (or its libjpeg
    support) is absent."""
    global _imgpipe
    lib = get_lib()
    if lib is None:
        return None
    with _lock:
        if _imgpipe is None:
            from .native_engine import NativeImagePipe

            try:
                _imgpipe = NativeImagePipe(lib, num_threads=num_threads)
            except OSError:
                _imgpipe = False
    return _imgpipe or None


def shm_unlink(name):
    """Unlink a named shm segment without attaching (cleanup of segments
    whose content will never be read — abandoned DataLoader batches)."""
    lib = get_lib()
    if lib is None:
        return
    from .native_engine import _bind

    _bind(lib).rt_shm_unlink(name.encode())
