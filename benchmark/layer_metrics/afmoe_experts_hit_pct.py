"""`afmoe_experts_hit_pct` — layer: model step. Held experts that at least one
token of a tick chose, over the experts held x EXPERT layers x decode
dispatches of the window: the engine's counters
`serving.generation.experts_hit` and `tick_slots`, the first fed by the decode
program's own routing. What share of the held expert weights a tick must read
(~40% where 32 slots choose 4 of 256 and 32 are held), so lower is fewer
bytes; it should move `itl_p90_ms`.
"""
import afmoe_bytes
import swa_moe_ops


def read(obs, run):
    if not afmoe_bytes.applies(run):
        return None
    counted = swa_moe_ops.counted_in_window(obs)
    if counted is None:
        return None
    return 100.0 * counted[1] / (run.config["num_experts"]
                                 * afmoe_bytes.expert_layers(run.config))
