"""`conv_mxu_pct` — layer: kernels. The share of the published bf16 peak the
convolutions alone reach: the step's FLOPs on this chip — forward and backward
by the function the configuration names as `train_flops`, as `mfu_pct` counts
them — over `conv_ms_per_step`, over the peak. Every FLOP `mfu_pct` counts is
a convolution's or a dense layer's, so this is the MFU of the step with
everything else taken out. Higher is better. Should move `train_images_per_s`.
"""
import importlib

import program_scopes
from layer_metrics import conv_ms_per_step


@program_scopes.reader
def read(obs, run):
    ms = conv_ms_per_step.read(obs, run)
    if ms is None or "train_flops" not in run.config:
        return None
    module, _, function = run.config["train_flops"].partition(":")
    per_item = getattr(importlib.import_module(module), function)(run.config)
    flops = per_item * obs["items_per_step"] / run.chips
    return 100.0 * flops / (ms * 1e-3) / run.peaks["bf16_flops_per_s"]
