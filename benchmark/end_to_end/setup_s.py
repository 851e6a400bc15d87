"""`setup_s` — process start to the opening of the measured window: imports,
weights, compilation or cache loads, warm-up, and for a serving cell the
unmeasured lead-in of arrivals (host clock).
"""


def read(obs, run):
    return obs["setup_s"]
