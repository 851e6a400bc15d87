"""`ssm_state_update_roofline_pct` — layer: kernels. The state update's share
of its memory roofline: each live slot's recurrent state read once and
written once (`ssm_bytes.state_update_min_bytes`, from the live state slots a
tick that the engine counts — `serving.generation.state_slots_live`; the
counter `state_bytes_touched` is this plus the convolution window, which
other operations move) over the published HBM bandwidth, over
`ssm_state_ms_per_tick`. The update is bound by bytes: two FLOPs a byte.
Should move `itl_p90_ms`.
"""
import ssm_bytes
import ssm_ops


def read(obs, run):
    found = ssm_ops.state_update_seconds(obs, run)
    live = ssm_ops.decodes_in_window(obs)
    if found is None or live is None:
        return None
    seconds, executions = found
    least = ssm_bytes.state_update_min_bytes(run.config, live[1])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / (seconds / executions)
