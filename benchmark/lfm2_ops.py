"""What a traced run of the LFM2 expert cell read of its two decode kernels,
for the `lfm2_*` readers: the device time of the grouped products
(`moe_ops.GROUPED_PRODUCT`: the Pallas `gmm` on the chip, else XLA's lowering
of `lax.ragged_dot`) and of the slab kernel `decode_update_attend`
(`mxnet_tpu/ops/pallas_decode.py`, one call an attention layer) inside the
decode executions (`ssm_ops.py`'s reading of the `XLA Ops` line: an operation
is recognised by its instruction's own name), and what the engine counted a
decode dispatch. Every function returns None where it finds nothing to read
(another program, a model of another family, a program that lacks the kernel
or the counter, a trace without a device plane).
"""
import moe_ops
import ssm_ops

SLAB_ATTEND = r"^%?decode_update_attend"


def applies(run):
    return "conv_L_cache" in run.config \
        and "moe_intermediate_size" in run.config


def _decode_seconds(obs, run, pattern):
    """`(seconds, decode executions)` of the operations matching `pattern`
    inside the decode program's executions of the traced window."""
    path = run.tracer.xplane_path()
    if path is None or not applies(run):
        return None
    ops = ssm_ops._device_ops(path)
    decode, _ = ssm_ops.engine_programs(obs["trace"])
    if not ops or not decode:
        return None
    seconds = ssm_ops._seconds(ops, pattern, decode)
    return (seconds, len(decode)) if seconds > 0 else None


def grouped_product_seconds(obs, run):
    return _decode_seconds(obs, run, moe_ops.GROUPED_PRODUCT)


def slab_attend_seconds(obs, run):
    return _decode_seconds(obs, run, SLAB_ATTEND)


def counted_in_window(obs, run):
    """Decode dispatches of the measured window and, a dispatch, the live
    slots, the experts hit (summed over the expert layers) and the K/V rows
    the live slots attend (summed over the attention layers): from the
    engine's counters, which the decode program's own routing and positions
    feed."""
    tele = obs.get("telemetry")
    if not applies(run) or not tele or not tele.get("tick_slots") \
            or not tele.get("experts_hit") \
            or not tele.get("kv_rows_live_full"):
        return None
    decodes = tele["tick_slots"] / obs["max_slots"]
    return (decodes, tele.get("state_slots_live", 0) / decodes,
            tele["experts_hit"] / decodes,
            tele["kv_rows_live_full"] / decodes)
