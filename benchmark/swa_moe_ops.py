"""Which device operations of a serving trace belong to the window/full
attention expert model's kernels, and what the engine counted of them. The
profiler's `XLA Ops` line names an event by its whole HLO instruction and
carries no scope (`ssm_ops.py`), so an operation is recognised by its
instruction's own name:

* the grouped product is `moe_ops.GROUPED_PRODUCT`'s (`%gmm`, or XLA's
  lowering of `lax.ragged_dot`);
* the decode attention is the Pallas kernel `kv128_attend`
  (`mxnet_tpu/ops/pallas_window.py`), one call a layer, full members and
  rings alike;
* the prefill attention is the Pallas kernel `swa_prefill_attend`, one call a
  layer; its result `[query heads, L, head size]` names the bucket it ran.

Every function returns None where it finds nothing to read (another program,
a model of another family, a trace without a device plane).
"""
import re

import moe_ops
import ssm_ops

KV128_ATTEND = r"^%?kv128_attend"
SWA_PREFILL_ATTEND = r"^%?swa_prefill_attend[.\d]* = \w+\[\d+,(\d+),\d+\]"


def applies(run):
    return "sliding_window" in run.config and "layer_types" in run.config \
        and "moe_intermediate_size" in run.config


def _ops_and_programs(obs, run):
    path = run.tracer.xplane_path()
    if path is None or not applies(run):
        return None
    ops = ssm_ops._device_ops(path)
    decode, prefill = ssm_ops.engine_programs(obs["trace"])
    return (ops, decode, prefill) if ops else None


def _decode_seconds(obs, run, pattern):
    """`(seconds, decode executions)` of the operations matching `pattern`
    inside the decode program's executions of the traced window."""
    found = _ops_and_programs(obs, run)
    if found is None or not found[1]:
        return None
    ops, decode, _ = found
    seconds = ssm_ops._seconds(ops, pattern, decode)
    return (seconds, len(decode)) if seconds > 0 else None


def grouped_product_seconds(obs, run):
    return _decode_seconds(obs, run, moe_ops.GROUPED_PRODUCT)


def kv128_attend_seconds(obs, run):
    return _decode_seconds(obs, run, KV128_ATTEND)


def prefill_attend(obs, run):
    """`(seconds, {bucket length: kernel calls})` of the prefill attention
    kernel inside the prefill programs' executions of the traced window."""
    found = _ops_and_programs(obs, run)
    if found is None or not found[2]:
        return None
    ops, _, prefill = found
    rx = re.compile(SWA_PREFILL_ATTEND)
    calls = {}
    for text, start, end in ops:
        m = rx.search(text)
        if m and any(s <= start and end <= e for s, e in prefill):
            calls[int(m.group(1))] = calls.get(int(m.group(1)), 0) + 1
    seconds = ssm_ops._seconds(ops, SWA_PREFILL_ATTEND, prefill)
    return (seconds, calls) if seconds > 0 and calls else None


def counted_in_window(obs):
    """Decode dispatches of the measured window and, a dispatch, the experts
    hit (summed over the layers) and the K/V rows the live slots attend
    (summed over the layers of both kinds): from the engine's counters,
    which the decode program's own routing and positions feed."""
    tele = obs.get("telemetry")
    if not tele or not tele.get("tick_slots") \
            or not tele.get("kv_rows_live_window"):
        return None
    decodes = tele["tick_slots"] / obs["max_slots"]
    return (decodes, tele["experts_hit"] / decodes,
            (tele["kv_rows_live_full"] + tele["kv_rows_live_window"])
            / decodes)
