"""`mfu_pct` — layer: kernels. Model FLOP/s utilisation: forward + backward FLOPs
per item (image, token) from the configuration's shapes — by the function the
configuration names as `train_flops` (`<module>:<function>` under benchmark/;
nothing recomputed is counted) — times the items per second of the steps
inside the profiler window (host clock), over chips times the published bf16
peak. Should move `train_images_per_s`.
"""
import importlib


def read(obs, run):
    if obs.get("traced_step_s") is None or "train_flops" not in run.config:
        return None
    module, _, function = run.config["train_flops"].partition(":")
    per_item = getattr(importlib.import_module(module), function)(run.config)
    rate = obs["items_per_step"] / obs["traced_step_s"]
    return 100.0 * per_item * rate / (run.chips
                                      * run.peaks["bf16_flops_per_s"])
