"""The one generator of serving traffic. A traffic mix is a data file of
parameters (`benchmark/traffic/<name>.json`); this turns it and `--seed` into a
plan: for each request the time it is due, its prompt tokens and how many
tokens it asks for. The program receives only the generated requests.

So that a cell's numbers repeat across seeds, the offered load is the same for
every seed, and the seed only moves it around:

* arrivals — an open loop at `arrivals.rate_per_s`. Exactly
  `round(rate x seconds)` requests fall in a phase, at sorted uniform times: a
  Poisson process conditioned on its count.
* lengths — a fixed grid of quantiles of the stated distribution (log-normal
  by its median and sigma, clipped to [min, max]), which the seed permutes.
  Prompt + output is cut to `max_total` by shortening the output.
* tokens — every prompt is distinct random tokens. (Bursts and prompts that
  share document prefixes come with the benchmark PR that adds their cells:
  PERF.md, Open questions.)

Three phases share the rate and the distributions, each with its own grid: a
lead-in of `arrivals.lead_in_s` (unmeasured; fills the slab to its steady
occupancy), the measured window of `--seconds`, and a tail of
`arrivals.tail_s` that keeps arriving while the window's requests finish.
"""
import math

import numpy as np

PHASES = ("lead_in", "window", "tail")


def _normal_quantile(p):
    """Inverse normal CDF by Acklam's rational approximation (relative error
    under 1.2e-9; lengths are rounded to whole tokens anyway)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    if p < 0.02425:
        q = math.sqrt(-2 * math.log(p))
        return (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    if p > 1 - 0.02425:
        return -_normal_quantile(1 - p)
    q = p - 0.5
    r = q * q
    return (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
            + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                            + b[4]) * r + 1)


def length_grid(spec, n):
    """`n` lengths: the quantiles (i + 0.5)/n of the distribution `spec`,
    rounded and clipped. The same for every seed."""
    if n == 0:
        return np.zeros(0, np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    vals = [math.exp(mu + sigma * _normal_quantile((i + 0.5) / n))
            for i in range(n)]
    lo = spec.get("min", 1)
    hi = spec.get("max", max(vals))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def arrival_times(rng, n, seconds):
    """`n` due times in [0, seconds): sorted uniforms."""
    return np.sort(rng.uniform(0.0, seconds, n))


def plan(traffic, vocab_size, seed, seconds):
    """The list of requests, sorted by due time (seconds relative to the
    opening of the measured window; the lead-in's are negative). Each is a
    dict: `phase`, `due_s`, `prompt` (int32 array), `max_new_tokens`."""
    arr = traffic["arrivals"]
    rate = float(arr["rate_per_s"])
    spans = {"lead_in": (-float(arr["lead_in_s"]), float(arr["lead_in_s"])),
             "window": (0.0, float(seconds)),
             "tail": (float(seconds), float(arr["tail_s"]))}
    rng = np.random.default_rng([int(seed), 0x7261666669])
    requests = []
    for phase in PHASES:
        start, length = spans[phase]
        n = int(round(rate * length))
        due = start + arrival_times(rng, n, length)
        prompts = rng.permutation(length_grid(traffic["prompt_len"], n))
        outputs = rng.permutation(length_grid(traffic["output_len"], n))
        outputs = np.maximum(1, np.minimum(
            outputs, int(traffic["max_total"]) - prompts))
        for i in range(n):
            requests.append(dict(
                phase=phase, due_s=float(due[i]),
                prompt=rng.integers(0, vocab_size, int(prompts[i]),
                                    dtype=np.int32),
                max_new_tokens=int(outputs[i])))
    requests.sort(key=lambda r: r["due_s"])
    return requests
