"""`swa_prefill_attend_mxu_pct` — layer: kernels. The prefill attention's
share of the MXU's peak: the FLOPs its masks admit
(`swa_moe_bytes.prefill_attend_flops`: every admitted (query, key) pair scores
and adds its value in every query head — the causal triangle in a full layer,
the band of `sliding_window` keys in a window layer; nothing masked is
counted) for the buckets the traced window's prefills ran — each kernel call
names its bucket in its result's shape (swa_moe_ops.py) — over the published
bf16 peak, over the device time of the kernel `swa_prefill_attend` inside the
prefill executions. A prefill stalls every live stream, so it should move
`itl_p90_ms`.
"""
import swa_moe_bytes
import swa_moe_ops


def read(obs, run):
    found = swa_moe_ops.prefill_attend(obs, run)
    if found is None:
        return None
    seconds, calls = found
    layers = run.config["num_hidden_layers"]
    flops = sum(n / layers * swa_moe_bytes.prefill_attend_flops(run.config, b)
                for b, n in calls.items())
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / seconds
