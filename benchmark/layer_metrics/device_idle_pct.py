"""`device_idle_pct` — layer: device. 1 minus the union of the intervals in which
an operation ran on device 0 over the traced window (first operation's start
to last operation's end). Should move `train_images_per_s`.
"""


def read(obs, run):
    tr = obs["trace"]
    return 100.0 * tr.idle_s(0) / tr.window_s
