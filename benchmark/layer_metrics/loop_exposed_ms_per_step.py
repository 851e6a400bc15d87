"""`loop_exposed_ms_per_step` — layer: user loop. Device-0 idle time that lies
under `fit`'s own phases outside the step program — `mx:step.data*` (the
iterator's `next`, `prepare` + `stage_batch`), `mx:step.sync` (the metric's
host fetch) and `mx:step.update` — over the steps of the traced window
(device trace; attribution in program_spans.py). None for a program that
writes no `mx:` span. Should move `train_images_per_s`.
"""
import program_spans

SPANS = ("step.data", "step.sync", "step.update")


def read(obs, run):
    return program_spans.exposed_ms_per_step(obs, run, SPANS)
