"""`exposed_collective_pct` — layer: sharding plan. The share of device 0's
collective time during which no other operation ran there (device trace).
"""


def read(obs, run):
    tr = obs["trace"]
    if tr.collective_s(0) == 0:
        return None
    return 100.0 * tr.exposed_collective_s(0) / tr.collective_s(0)
