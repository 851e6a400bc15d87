"""Operations and bytes the latent-attention / expert block needs
(`mxnet_tpu/models/latent_moe.py`; configuration keys as published for
`sarvam_mla`, the DeepSeek-V2/V3 family's), from a configuration's shapes.
Kept with the benchmark, beside `flops.py` and `ssm_bytes.py`, so that no PR
that claims a gain can change the yardstick. A multiply-add is 2 FLOPs.
Nothing recomputed is counted. `num_experts` in the configuration counts the
experts HELD on this chip.
"""
from flops import DTYPE_BYTES


def _itemsize(config):
    return DTYPE_BYTES[config["dtype"]]


def _layers(config):
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def attention_param_count(config):
    """One layer's attention: W_q, the query norm, W_dkv, the latent's
    norm, W_ukv, W_o."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    q = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    r = config["kv_lora_rank"]
    return d * h * q + q + d * (r + config["qk_rope_head_dim"]) + r \
        + r * h * (config["qk_nope_head_dim"] + config["v_head_dim"]) \
        + h * config["v_head_dim"] * d


def expert_param_count(config):
    """One routed expert: gate, up and down projections."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_param_count(config):
    """The router over ALL the model's experts (`published.num_experts`
    where the file is a chip's share) and its selection bias."""
    total = config.get("published", {}).get("num_experts",
                                             config["num_experts"])
    return config["hidden_size"] * total + total


def replicated_param_count(config):
    """What every chip of the deployment holds whole and a decode tick reads
    once: attention, norms, the dense layers' MLP, router, shared expert,
    the final norm and the output head (the embedding is read by row)."""
    d = config["hidden_size"]
    dense, expert = _layers(config)
    every = attention_param_count(config) + 2 * d
    return (dense + expert) * every \
        + dense * 3 * d * config["intermediate_size"] \
        + expert * (router_param_count(config)
                    + config["num_shared_experts"]
                    * expert_param_count(config)) \
        + d + d * config["vocab_size"]


def param_count(config):
    _, expert = _layers(config)
    return replicated_param_count(config) \
        + config["vocab_size"] * config["hidden_size"] \
        + expert * config["num_experts"] * expert_param_count(config)


def weight_bytes(config):
    """Bytes of the weights as served: everything in `dtype` but the router
    and its bias, which stay float32."""
    _, expert = _layers(config)
    return param_count(config) * _itemsize(config) \
        + expert * router_param_count(config) * (4 - _itemsize(config))


def expert_bytes(config):
    return expert_param_count(config) * _itemsize(config)


def latent_bytes_per_row(config):
    """What the cache holds of one position in one layer: the normalised
    latent and the rotated shared key."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * _itemsize(config)


def latent_slab_bytes(config, slots, max_len):
    return slots * max_len * config["num_hidden_layers"] \
        * latent_bytes_per_row(config)


def attend_flops_per_row(config):
    """FLOPs of the absorbed decode attention a cached row a layer: every
    head scores the row (latent + rotary width) and adds its latent to the
    weighted sum."""
    r = config["kv_lora_rank"]
    return config["num_attention_heads"] \
        * (2 * (r + config["qk_rope_head_dim"]) + 2 * r)


def attend_min_seconds(config, live_rows, peaks):
    """The least time the latent decode attention of one tick can take:
    `live_rows` are the rows the live slots attend in ONE layer (the
    engine's `latent_rows_live` a tick); every layer reads them once. The
    larger of the bytes over the HBM bandwidth and the FLOPs over the peak."""
    rows = live_rows * config["num_hidden_layers"]
    return max(rows * latent_bytes_per_row(config) / peaks["hbm_bytes_per_s"],
               rows * attend_flops_per_row(config)
               / peaks["bf16_flops_per_s"])


def experts_min_bytes(config, experts_hit):
    """The least the grouped product of one tick must move: each HIT
    expert's weights once (`experts_hit` summed over the expert layers)."""
    return experts_hit * expert_bytes(config)


def decode_tick_min_bytes(config, experts_hit, live_rows):
    """The least a decode tick must move: the replicated weights once, each
    hit expert once, the live latent rows of every layer."""
    _, expert = _layers(config)
    replicated = replicated_param_count(config) * _itemsize(config) \
        + expert * router_param_count(config) * (4 - _itemsize(config))
    return replicated + experts_min_bytes(config, experts_hit) \
        + live_rows * config["num_hidden_layers"] \
        * latent_bytes_per_row(config)
