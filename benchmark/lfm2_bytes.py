"""Operations and bytes the LFM2 expert block needs
(`mxnet_tpu/models/hybrid.py` as the `lfm2_moe` block; configuration keys as
published), from a configuration's shapes alone. Kept with the benchmark,
beside `flops.py`, `gdn_bytes.py` and `swa_moe_bytes.py`, so that no PR that
claims a gain can change the yardstick. A multiply-add is 2 FLOPs. Nothing
recomputed and nothing masked is counted: each function gives the LEAST work
whatever implements it. The layers built are the first `num_hidden_layers` of
`layer_types`; every expert of a layer is held here.
"""
from flops import DTYPE_BYTES

ROUTER_BYTES = 4         # the router and its bias stay float32
CONV, FULL = "conv", "full_attention"


def _itemsize(config):
    return DTYPE_BYTES[config["dtype"]]


def layer_counts(config):
    """`(conv layers, attention layers)` among the layers built."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count(CONV), kinds.count(FULL)


def expert_layers(config):
    """The layers behind the leading dense ones: a whole layer of experts
    each."""
    return config["num_hidden_layers"] - config["num_dense_layers"]


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def expert_param_count(config):
    """One routed expert: w1, w3 (in) and w2 (out)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def dense_mlp_param_count(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def conv_operator_param_count(config):
    """One short-convolution operator: in (D -> 3 D), the taps, out."""
    d = config["hidden_size"]
    return 3 * d * d + config["conv_L_cache"] * d + d * d


def attention_param_count(config):
    """One attention operator: W_q, W_k, W_v, W_o and the two head norms."""
    d, hd = config["hidden_size"], head_dim(config)
    return 2 * d * d + 2 * d * config["num_key_value_heads"] * hd + 2 * hd


def router_param_count(config):
    """An expert layer's router over all the experts, with its selection
    bias under `use_expert_bias`."""
    return (config["hidden_size"] + bool(config.get("use_expert_bias"))) \
        * config["num_experts"]


def replicated_param_count(config):
    """What a decode tick reads once whatever is routed, in the served
    dtype: every operator, the dense MLPs, every norm, and the tied table
    (read whole as the head; the rows it gives as the embedding are among
    them)."""
    d = config["hidden_size"]
    n_conv, n_full = layer_counts(config)
    return n_conv * conv_operator_param_count(config) \
        + n_full * attention_param_count(config) \
        + config["num_dense_layers"] * dense_mlp_param_count(config) \
        + 2 * d * config["num_hidden_layers"] + d \
        + config["vocab_size"] * d


def param_count(config):
    return replicated_param_count(config) + expert_layers(config) * (
        router_param_count(config)
        + config["num_experts"] * expert_param_count(config))


def router_bytes(config):
    return expert_layers(config) * router_param_count(config) * ROUTER_BYTES


def replicated_bytes(config):
    return replicated_param_count(config) * _itemsize(config) \
        + router_bytes(config)


def expert_bytes(config):
    return expert_param_count(config) * _itemsize(config)


def weight_bytes(config):
    """Bytes of the weights as served: everything in `dtype` but the
    routers, which stay float32."""
    return replicated_bytes(config) + expert_layers(config) \
        * config["num_experts"] * expert_bytes(config)


def kv_bytes_per_row(config):
    """What the cache holds of one position in ONE attention layer: a key
    and a value for every K/V head."""
    return 2 * config["num_key_value_heads"] * head_dim(config) \
        * _itemsize(config)


def kv_bytes_per_position(config):
    return layer_counts(config)[1] * kv_bytes_per_row(config)


def window_bytes_per_slot(config):
    """The last `conv_L_cache - 1` values of `B * x` of every conv layer."""
    return layer_counts(config)[0] * (config["conv_L_cache"] - 1) \
        * config["hidden_size"] * _itemsize(config)


def routed_bytes_per_slot(config):
    """`routed`: what the last decode step chose, int32."""
    return expert_layers(config) * config["num_experts_per_tok"] * 4


def cache_bytes(config, slots, max_len):
    return slots * (max_len * kv_bytes_per_position(config)
                    + window_bytes_per_slot(config)
                    + routed_bytes_per_slot(config))


def experts_min_bytes(config, experts_hit):
    """The least a tick's grouped products must move: each HIT expert's
    weights once (`experts_hit` summed over the expert layers)."""
    return experts_hit * expert_bytes(config)


def attend_min_bytes(config, kv_rows):
    """The least a tick's decode attention must move: the K/V rows the live
    slots attend, `kv_rows` summed over the attention layers (the engine's
    counter `kv_rows_live_full`), each read once."""
    return kv_rows * kv_bytes_per_row(config)


def decode_tick_min_bytes(config, live_slots, experts_hit, kv_rows):
    """The least a decode tick must move: every replicated weight once,
    each hit expert once, the live K/V rows, and each live slot's windows in
    and out."""
    return replicated_bytes(config) + experts_min_bytes(config, experts_hit) \
        + attend_min_bytes(config, kv_rows) \
        + 2 * live_slots * window_bytes_per_slot(config)


def prefill_attend_flops(config, length):
    """One attention layer's prefill over `length` positions: every causal
    (query, key) pair scores and adds its value in every query head; nothing
    masked is counted."""
    pairs = length * (length + 1) // 2
    return 4 * pairs * config["num_attention_heads"] * head_dim(config)
