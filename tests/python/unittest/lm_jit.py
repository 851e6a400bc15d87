"""One jit wrapper a (model, method, kernel policy) for the model tests'
`_prefill` / `_decode` helpers: a fresh `jax.jit(lm.decode_step)` a call
traces and compiles every decode step again (half a second a step on the
CPU). The policy is in the key because a trace reads it: a test that flips
`MXNET_PALLAS_ATTENTION` gets the other formulation's program."""
import os

import jax

_JITTED = {}


def jitted(lm, method):
    key = (id(lm), method, os.environ.get("MXNET_PALLAS_ATTENTION"),
           os.environ.get("MXNET_PALLAS_INTERPRET"))
    if key not in _JITTED:
        # the wrapper holds the bound method, so `lm` (and its id) lives on
        _JITTED[key] = jax.jit(getattr(lm, method))
    return _JITTED[key]
