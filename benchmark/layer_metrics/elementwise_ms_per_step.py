"""`elementwise_ms_per_step` — layer: kernels. Device time a step of
everything the program named that is no convolution or dense layer:
`BatchNorm`, `Activation`, `Pooling`, the residual adds (`elemwise_add`,
`_plus`), the loss (`SoftmaxOutput`), `optimizer.update` — the activation
traffic — forward and backward, over the step program's executions in the
traced window. What is left of a step beside this and `conv_ms_per_step` is
`async-copy` and `unscoped` (the `[scopes]` table). Should move
`train_images_per_s`.
"""
import program_scopes
from layer_metrics import conv_ms_per_step


@program_scopes.reader
def read(obs, run):
    ms = program_scopes.step_ms(
        obs, run, lambda path: program_scopes.is_named(path)
        and not conv_ms_per_step.is_conv(path))
    return ms if ms else None
