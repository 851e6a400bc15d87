"""`dispatch_exposed_ms_per_step` — layer: step builder. Device-0 idle time that
lies under the program's own step-building spans — `mx:cached_op.*`
(`HybridBlock._call_cached_op`: parameter gather, signature and jitted call,
tape record), `mx:autograd.*`, `mx:trainer.*` and `mx:fused.dispatch` — over
the steps of the traced window (device trace; attribution in
program_spans.py: each gap cut at span boundaries, innermost span). A low value beside
a large `uncovered` says the host time is outside them: the eager loss, the
Block's Python. None for a program that writes no `mx:` span. Should move
`train_images_per_s`.
"""
import program_spans

SPANS = ("cached_op.", "autograd.", "trainer.", "fused.dispatch")


def read(obs, run):
    return program_spans.exposed_ms_per_step(obs, run, SPANS)
