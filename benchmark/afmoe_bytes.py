"""Operations and bytes the `afmoe` block needs (`mxnet_tpu/models/
window_moe.py` built for `model_type` afmoe; configuration keys as published
for Trinity-Large-Preview), from a configuration's shapes. Kept with the
benchmark, beside `flops.py`, `ssm_bytes.py`, `moe_bytes.py` and
`swa_moe_bytes.py`, so that no PR that claims a gain can change the
yardstick. A multiply-add is 2 FLOPs. Nothing recomputed and nothing masked
is counted. `num_experts` in the configuration counts the experts HELD on
this chip; expert layers (`num_hidden_layers - num_dense_layers`) are counted
apart from layers.

What this block has that `swa_moe_bytes.py` does not know: the output gate's
projection, the two head norms, four norms a layer instead of two, the leading
dense MLP, the shared expert and the router's selection bias. What the two
share — a K/V row's bytes, an expert's bytes, the pairs a band admits — is
`swa_moe_bytes.py`'s own function of the same keys (tests/test_afmoe_bytes.py
holds them equal), so the kernels both models run are read by one yardstick.
"""
from flops import DTYPE_BYTES

from swa_moe_bytes import (attend_flops_per_row, attend_min_seconds,  # noqa: F401
                           band_pairs, cache_bytes, expert_bytes,
                           expert_param_count, experts_min_bytes,
                           kv_bytes_per_row, layer_counts,
                           prefill_attend_flops)


def applies(run):
    return run.config.get("model_type") == "afmoe"


def _itemsize(config):
    return DTYPE_BYTES[config["dtype"]]


def expert_layers(config):
    return config["num_hidden_layers"] - config["num_dense_layers"]


def attention_param_count(config):
    """One layer's attention: W_q, W_k, W_v, the output gate's W_gate, W_o
    (no bias) and the two head norms."""
    d, hd = config["hidden_size"], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return d * (2 * hq + 2 * hk) * hd + hq * hd * d + 2 * hd


def dense_mlp_param_count(config):
    """A leading layer's MLP: gate, up and down projections."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def shared_expert_param_count(config):
    return config["num_shared_experts"] * expert_param_count(config)


def router_param_count(config):
    """The router over ALL the model's experts (`published.num_experts`
    where the file is a chip's share) and its selection bias: float32."""
    total = config.get("published", {}).get("num_experts",
                                             config["num_experts"])
    return config["hidden_size"] * total + total


def replicated_param_count(config):
    """What every chip of the deployment holds whole and a decode tick reads
    once whatever is routed: every layer's attention and four norms, the
    dense layers' MLP, every expert layer's router and shared expert, the
    final norm and this chip's slice of the output head (the embedding is
    read by row)."""
    d = config["hidden_size"]
    return config["num_hidden_layers"] * (attention_param_count(config)
                                          + 4 * d) \
        + config["num_dense_layers"] * dense_mlp_param_count(config) \
        + expert_layers(config) * (router_param_count(config)
                                   + shared_expert_param_count(config)) \
        + d + d * config["vocab_size"]


def param_count(config):
    return replicated_param_count(config) \
        + config["vocab_size"] * config["hidden_size"] \
        + expert_layers(config) * config["num_experts"] \
        * expert_param_count(config)


def _router_widening(config):
    """The router and its bias stay float32 whatever the dtype: the bytes
    that adds."""
    return expert_layers(config) * router_param_count(config) \
        * (4 - _itemsize(config))


def weight_bytes(config):
    """Bytes of the weights as served: everything in `dtype` but the router
    and its bias, which stay float32."""
    return param_count(config) * _itemsize(config) + _router_widening(config)


def decode_tick_min_bytes(config, experts_hit, rows):
    """The least a decode tick must move: the replicated weights once, each
    HIT expert once (`experts_hit` summed over the expert layers), the live
    K/V rows of every layer (`rows` summed over the full members and the
    rings)."""
    return replicated_param_count(config) * _itemsize(config) \
        + _router_widening(config) + experts_min_bytes(config, experts_hit) \
        + rows * kv_bytes_per_row(config)

