"""The generator of closed-loop serving traffic, beside `traffic_gen.py` (the
open loop's). A closed loop has no arrival times: `workers.count` workers each
submit their next request the moment their previous one has finished, so the
load is a fixed concurrency. What the traffic file and `--seed` decide is the
sequence of requests the workers draw from, in order.

As in `traffic_gen.py`, the offered work is the same for every seed and the
seed only moves it around: the lengths are the fixed quantile grid of the
stated distributions (`traffic_gen.length_grid`) over `workers.pool_requests`
requests, which the seed permutes; every prompt is distinct random tokens of
the whole vocabulary. Prompt + output is cut to `max_total` by shortening the
output. A run that needs more requests than the pool holds starts it again.
"""
import numpy as np

import traffic_gen


def pool(traffic, vocab_size, seed):
    """The requests, in the order the workers draw them: dicts of `prompt`
    (int32 array) and `max_new_tokens`."""
    n = int(traffic["workers"]["pool_requests"])
    rng = np.random.default_rng([int(seed), 0x636c6f736564])
    prompts = rng.permutation(traffic_gen.length_grid(traffic["prompt_len"],
                                                      n))
    outputs = rng.permutation(traffic_gen.length_grid(traffic["output_len"],
                                                      n))
    outputs = np.maximum(1, np.minimum(
        outputs, int(traffic["max_total"]) - prompts))
    return [dict(prompt=rng.integers(0, vocab_size, int(prompts[i]),
                                     dtype=np.int32),
                 max_new_tokens=int(outputs[i])) for i in range(n)]


def worker_starts(traffic):
    """Seconds after the start of the lead-in at which each worker submits
    its first request: evenly over `workers.ramp_s`."""
    w = traffic["workers"]
    return [i * float(w["ramp_s"]) / int(w["count"])
            for i in range(int(w["count"]))]
