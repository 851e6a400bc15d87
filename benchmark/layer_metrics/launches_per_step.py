"""`launches_per_step` — layer: step builder. Executions of compiled XLA programs
on device 0 inside the traced window over its steps (device trace, line
`XLA Modules`). One fused step is 1. Should move `train_images_per_s`.
"""


def read(obs, run):
    if obs.get("traced_step_s") is None:
        return None
    tr = obs["trace"]
    return len(tr.launches(0)) / (tr.window_s / obs["traced_step_s"])
