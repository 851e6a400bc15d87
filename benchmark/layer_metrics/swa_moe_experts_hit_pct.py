"""`swa_moe_experts_hit_pct` — layer: model step. Experts that at least one
token of a tick chose, over the experts held x layers x decode dispatches of
the window: the engine's counters `serving.generation.experts_hit` and
`tick_slots`, the first fed by the decode program's own routing. What share of
the expert weights a tick must read, so it should move `itl_p90_ms`.
"""
import swa_moe_ops


def read(obs, run):
    if not swa_moe_ops.applies(run):
        return None
    counted = swa_moe_ops.counted_in_window(obs)
    if counted is None:
        return None
    return 100.0 * counted[1] / (run.config["num_experts"]
                                 * run.config["num_hidden_layers"])
