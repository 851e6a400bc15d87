"""Which device operations of a serving trace belong to the expert layer's
grouped product and to the latent decode attention, and what the engine
counted of them. The profiler's `XLA Ops` line names an event by its whole
HLO instruction and carries no scope (`ssm_ops.py`), so an operation is
recognised by its instruction's own name:

* the grouped product is the Pallas grouped matmul the model calls on the
  chip — `%gmm.1 = bf16[256,4096] custom-call(...)` (jax's megablox) — or,
  where the model keeps `lax.ragged_dot`, XLA's lowering of it on the TPU,
  `%ragged-dot-none.7 = bf16[256,4096] custom-call(...)` with its
  `%ragged-dot-metadata.3` (both seen on the v5e, PR 31);
* the latent decode attention is the Pallas kernel `latent_attend`
  (`mxnet_tpu/ops/pallas_latent.py`), one call a layer.

Every function returns None where it finds nothing to read (another program,
a model without experts, a trace without a device plane).
"""
import ssm_ops

GROUPED_PRODUCT = r"^%?(?:gmm|ragged-dot)"
LATENT_ATTEND = r"^%?latent_attend"


def _decode_seconds(obs, run, pattern):
    """`(seconds, decode executions)` of the operations matching `pattern`
    inside the decode program's executions of the traced window."""
    path = run.tracer.xplane_path()
    if path is None or "kv_lora_rank" not in run.config:
        return None
    ops = ssm_ops._device_ops(path)
    decode, _ = ssm_ops.engine_programs(obs["trace"])
    if not ops or not decode:
        return None
    seconds = ssm_ops._seconds(ops, pattern, decode)
    return (seconds, len(decode)) if seconds > 0 else None


def grouped_product_seconds(obs, run):
    return _decode_seconds(obs, run, GROUPED_PRODUCT)


def latent_attend_seconds(obs, run):
    return _decode_seconds(obs, run, LATENT_ATTEND)


def routed_in_window(obs):
    """Decode dispatches of the measured window and, a dispatch, the held
    experts hit (summed over the expert layers) and the latent rows the
    live slots attend (a layer): from the engine's counters, which the
    decode program's own routing feeds."""
    tele = obs.get("telemetry")
    if not tele or not tele.get("tick_slots") \
            or not tele.get("latent_rows_live"):
        return None
    decodes = tele["tick_slots"] / obs["max_slots"]
    return (decodes, tele["experts_hit"] / decodes,
            tele["latent_rows_live"] / decodes)
