"""`batch_occupancy_pct` — layer: serving scheduler. Slots that produced a token
over slots swept, across the window's decode ticks: the engine's counters
`serving.generation.decode_tokens` / `serving.generation.tick_slots` (the
latter grows by `max_slots` per decode). Should move `itl_p90_ms` (a fuller slab is more tokens a tick at the same gap).
"""


def read(obs, run):
    tele = obs.get("telemetry")
    if not tele or not tele["tick_slots"]:
        return None
    return 100.0 * tele["decode_tokens"] / tele["tick_slots"]
