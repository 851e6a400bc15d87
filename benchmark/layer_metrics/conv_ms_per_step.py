"""`conv_ms_per_step` — layer: kernels. Device time a step of the
convolutions and the dense layers, forward and backward: the operations of
the step program charged to a `Convolution` or `FullyConnected` scope
(`Convolution:<node>` in a Module step, `<block>/Convolution` in a gluon
capture; program_scopes.py charges a fusion to the convolution it carries),
over the step program's executions in the traced window. Should move
`train_images_per_s`.
"""
import program_scopes

KINDS = {"Convolution", "FullyConnected"}


def is_conv(path):
    return program_scopes.innermost(path) in KINDS


@program_scopes.reader
def read(obs, run):
    ms = program_scopes.step_ms(obs, run, is_conv)
    return ms if ms else None
