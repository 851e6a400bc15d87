"""`gdn_state_update_roofline_pct` — layer: kernels. The state update's share
of its memory roofline: each live slot's state of every linear layer read once
and written once (`gdn_bytes.state_update_min_bytes`, from the live state
slots a tick that the engine counts — `serving.generation.state_slots_live`)
over the published HBM bandwidth, over `gdn_state_ms_per_tick`. The update is
bound by bytes: 8 FLOPs an entry of state, 1 a byte moved. Should move
`itl_p90_ms`.
"""
import gdn_bytes
import gdn_ops
import program_scopes


@program_scopes.reader
def read(obs, run):
    counted = gdn_ops.counted_in_window(obs)
    if not gdn_ops.applies(run) or counted is None:
        return None
    ms = program_scopes.decode_ms(obs, run, {"gdn.state_update"})
    if ms is None:
        return None
    least = gdn_bytes.state_update_min_bytes(run.config, counted[1])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / (ms / 1e3)
