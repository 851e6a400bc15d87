"""What a traced run of the gated-delta-rule hybrid cell read of its two
decode kernels, for the `gdn_*` readers: the device time of `kv128_attend`
inside the decode executions (`ssm_ops.py`'s reading of the `XLA Ops` line:
an operation is recognised by its instruction's own name), and what the
engine counted a decode dispatch. Every function returns None where it finds
nothing to read (another program, a model of another family, a program that
lacks the kernel or the counter, a trace without a device plane).
"""
import ssm_ops

KV128_ATTEND = r"^%?kv128_attend"


def applies(run):
    return "linear_key_head_dim" in run.config


def kv128_attend_seconds(obs, run):
    """`(seconds, decode executions)` of the decode attention kernel inside
    the decode program's executions of the traced window."""
    path = run.tracer.xplane_path()
    if path is None or not applies(run):
        return None
    ops = ssm_ops._device_ops(path)
    decode, _ = ssm_ops.engine_programs(obs["trace"])
    if not ops or not decode:
        return None
    seconds = ssm_ops._seconds(ops, KV128_ATTEND, decode)
    return (seconds, len(decode)) if seconds > 0 else None


def counted_in_window(obs):
    """Decode dispatches of the measured window and, a dispatch, the live
    state slots and the K/V rows the live slots attend (summed over the full
    layers): from the engine's counters."""
    tele = obs.get("telemetry")
    if not tele or not tele.get("tick_slots") \
            or not tele.get("state_slots_live") \
            or not tele.get("kv_rows_live_full"):
        return None
    decodes = tele["tick_slots"] / obs["max_slots"]
    return (decodes, tele["state_slots_live"] / decodes,
            tele["kv_rows_live_full"] / decodes)
