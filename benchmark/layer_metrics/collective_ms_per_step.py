"""`collective_ms_per_step` — layer: sharding plan. Union of the all-reduce /
all-gather / reduce-scatter / collective-permute / all-to-all operations'
intervals on device 0 over the traced steps (device trace, line `XLA Ops`).
Absent on one chip. Should move `train_images_per_s`.
"""


def read(obs, run):
    tr = obs["trace"]
    if obs.get("traced_step_s") is None or tr.collective_s(0) == 0:
        return None
    return tr.collective_s(0) / (tr.window_s / obs["traced_step_s"]) * 1e3
