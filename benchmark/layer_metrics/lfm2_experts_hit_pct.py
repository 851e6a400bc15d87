"""`lfm2_experts_hit_pct` — layer: model step. Experts that at least one token
of a tick chose, over the experts x expert layers x decode dispatches of the
window: the engine's counters `serving.generation.experts_hit` and
`tick_slots`, the first fed by the decode program's own routing. What share of
the expert weights a tick must read, so it should move `itl_p90_ms`.
"""
import lfm2_bytes
import lfm2_ops


def read(obs, run):
    counted = lfm2_ops.counted_in_window(obs, run)
    if counted is None:
        return None
    return 100.0 * counted[2] / (run.config["num_experts"]
                                 * lfm2_bytes.expert_layers(run.config))
