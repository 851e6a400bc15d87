"""`experts_hit_pct` — layer: model step. Held experts that at least one
token of a tick chose, over the experts held x expert layers x decode
dispatches of the window: the engine's counters
`serving.generation.experts_hit` and `tick_slots`, the first fed by the
decode program's own routing. What share of the expert weights a tick must
read, so it should move `itl_p90_ms`.
"""
import moe_ops


def read(obs, run):
    if "num_experts" not in run.config:
        return None
    routed = moe_ops.routed_in_window(obs)
    if routed is None:
        return None
    layers = run.config["num_hidden_layers"] \
        - run.config["first_k_dense_replace"]
    return 100.0 * routed[1] / (run.config["num_experts"] * layers)
