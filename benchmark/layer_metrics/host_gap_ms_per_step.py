"""`host_gap_ms_per_step` — layer: user loop. Device-0 idle time inside the
traced window over the steps it held (device trace; steps from the host's step
period inside the profiler window). Should move `train_images_per_s`.
"""


def read(obs, run):
    if obs.get("traced_step_s") is None:
        return None
    tr = obs["trace"]
    return tr.idle_s(0) / (tr.window_s / obs["traced_step_s"]) * 1e3
