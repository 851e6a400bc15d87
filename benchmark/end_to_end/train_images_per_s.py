"""`train_images_per_s` — work completed per second: images of the steps that
ended inside the window (all chips of the cell together) over the window's
seconds, host clock. Both edges of the window and every step end follow a
blocking host fetch of a step's outputs (the accuracy metric reads them every
batch, as `fit` and the gluon example do): the window opens two steps after
the last warm-up step, when the loop runs at its own pace, and closes at the
end of the first step that ends `--seconds` later (train_common.StepClock).
A stall — a recompile, a collection pause, a slow batch — costs its whole
length, as it costs the user; the median step is on the `[window]` line as a
diagnostic only.
"""


def read(obs, run):
    if "step_ends" not in obs:
        return None
    t0, t1 = obs["window"]
    return obs["items_per_step"] * len(obs["step_ends"]) / (t1 - t0)
