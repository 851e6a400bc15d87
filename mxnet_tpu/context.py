"""Device contexts.

Parity with the reference's `python/mxnet/context.py` (`Context`, `cpu()`,
`gpu()`, thread-local default-context stack) redesigned for TPU: a Context
names a jax device. ``gpu(i)`` is kept as an alias for accelerator ``i`` so
reference scripts run unchanged; the native accelerator constructor is
``tpu(i)``. `Context.device_typeid` numbering keeps the reference's values
(cpu=1, gpu=2, cpu_pinned=3, cpu_shared=5) plus tpu=6 so serialized contexts
round-trip.
"""
from __future__ import annotations

import threading

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]

_devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
_devstr2type = {v: k for k, v in _devtype2str.items()}


def _jax():
    import jax

    return jax


class Context:
    """A device context. ``with mx.tpu(0):`` sets the default device for
    array creation, mirroring `python/mxnet/context.py:39`."""

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = _devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return _devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- TPU-native part ----------------------------------------------------

    @property
    def jax_device(self):
        """The concrete jax device this context names."""
        jax = _jax()
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _platform_devices("cpu")
            if not devs:
                devs = jax.devices()  # single-platform builds
            return devs[min(self.device_id, len(devs) - 1)]
        devs = _accelerator_devices()
        if not devs:
            # never a silent CPU stand-in: a script that names the chip and
            # runs without one would report CPU work as device work
            raise RuntimeError(
                f"{self}: this process has no accelerator (jax found only "
                f"{jax.default_backend()!r} devices); use mx.cpu() to run "
                f"on the host")
        if self.device_id >= len(devs):
            raise ValueError(f"{self} does not name an available device ({len(devs)} present)")
        return devs[self.device_id]

    def empty_cache(self):
        """Parity no-op: XLA owns the HBM allocator."""


def _platform_devices(platform):
    """Addressable devices of a platform. A Context names a device THIS
    process can touch — in a multi-process job `jax.devices()` includes
    other workers' (non-addressable) devices, which eager ops must never
    device_put to (reference contexts are per-process for the same reason)."""
    jax = _jax()
    try:
        return [d for d in jax.local_devices() if d.platform == platform]
    except RuntimeError:
        return []


def _accelerator_devices():
    """All non-cpu addressable jax devices."""
    jax = _jax()
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias for the i-th accelerator so reference scripts run unchanged."""
    return Context("gpu", device_id)


def num_tpus():
    return len(_accelerator_devices())


def num_gpus():
    return num_tpus()


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


def default_accelerator():
    """tpu(0) if an accelerator is present else cpu(0)."""
    return tpu(0) if num_tpus() > 0 else cpu(0)
