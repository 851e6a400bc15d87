"""Operations and bytes the window/full-attention expert block needs
(`mxnet_tpu/models/window_moe.py`; configuration keys as published for
`mellum`), from a configuration's shapes. Kept with the benchmark, beside
`flops.py`, `ssm_bytes.py` and `moe_bytes.py`, so that no PR that claims a
gain can change the yardstick. A multiply-add is 2 FLOPs. Nothing recomputed
and nothing masked is counted. `num_experts` in the configuration counts the
experts HELD on this chip.
"""
from flops import DTYPE_BYTES

FULL, WINDOW = "full_attention", "sliding_attention"


def _itemsize(config):
    return DTYPE_BYTES[config["dtype"]]


def layer_counts(config):
    """`(full layers, window layers)` among the `num_hidden_layers` built:
    the first of the published pattern."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count(FULL), kinds.count(WINDOW)


def attention_param_count(config):
    """One layer's attention: W_q, W_k, W_v, W_o (no bias, no norm)."""
    d, hd = config["hidden_size"], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return d * (hq + 2 * hk) * hd + hq * hd * d


def expert_param_count(config):
    """One routed expert: gate, up and down projections."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_param_count(config):
    """The router over ALL the model's experts (`published.num_experts`
    where the file is a chip's share)."""
    return config["hidden_size"] * config.get("published", {}).get(
        "num_experts", config["num_experts"])


def replicated_param_count(config):
    """What a decode tick reads once whatever is routed: every layer's
    attention, norms and router, the final norm and the output head (the
    embedding is read by row)."""
    d = config["hidden_size"]
    every = attention_param_count(config) + 2 * d + router_param_count(config)
    return config["num_hidden_layers"] * every + d + d * config["vocab_size"]


def param_count(config):
    return replicated_param_count(config) \
        + config["vocab_size"] * config["hidden_size"] \
        + config["num_hidden_layers"] * config["num_experts"] \
        * expert_param_count(config)


def _router_widening(config):
    """The router stays float32 whatever the dtype: the bytes that adds."""
    return config["num_hidden_layers"] * router_param_count(config) \
        * (4 - _itemsize(config))


def weight_bytes(config):
    """Bytes of the weights as served: everything in `dtype` but the
    router, which stays float32."""
    return param_count(config) * _itemsize(config) + _router_widening(config)


def expert_bytes(config):
    return expert_param_count(config) * _itemsize(config)


def kv_bytes_per_row(config):
    """What the cache holds of one position in one layer: a key and a value
    for every K/V head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] \
        * _itemsize(config)


def cache_bytes(config, slots, max_len):
    """`(full members, ring members)`: a full layer pre-pays `max_len` rows
    a slot, a window layer a ring of `sliding_window`."""
    full, window = layer_counts(config)
    row = kv_bytes_per_row(config)
    return (full * slots * max_len * row,
            window * slots * min(config["sliding_window"], max_len) * row)


def attend_flops_per_row(config):
    """FLOPs of the decode attention a cached row a layer: every query head
    scores its K/V head's key and adds its value. 8 FLOPs a cache byte at 8
    query heads a K/V head (16 a cached number)."""
    return config["num_attention_heads"] * 4 * config["head_dim"]


def attend_min_seconds(config, rows, peaks):
    """The least time the decode attention of one tick can take: `rows` are
    the K/V rows the live slots attend summed over the layers of both kinds
    (the engine's `kv_rows_live_full` + `kv_rows_live_window` a tick). The
    larger of the bytes over the HBM bandwidth and the FLOPs over the peak:
    8 FLOPs a byte is far under the v5e's ridge (240), so bytes bind."""
    return max(rows * kv_bytes_per_row(config) / peaks["hbm_bytes_per_s"],
               rows * attend_flops_per_row(config)
               / peaks["bf16_flops_per_s"])


def experts_min_bytes(config, experts_hit):
    """The least the grouped product of one tick must move: each HIT
    expert's weights once (`experts_hit` summed over the layers)."""
    return experts_hit * expert_bytes(config)


def decode_tick_min_bytes(config, experts_hit, rows):
    """The least a decode tick must move: the replicated weights once, each
    hit expert once, the live K/V rows of every layer."""
    return replicated_param_count(config) * _itemsize(config) \
        + _router_widening(config) + experts_min_bytes(config, experts_hit) \
        + rows * kv_bytes_per_row(config)


def band_pairs(length, window=None):
    """(query, key) pairs the mask of a sequence of `length` admits: causal,
    and with a window only the keys `(q - window, q]`."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def prefill_attend_flops(config, length):
    """FLOPs of the attention of ONE prefill of `length` positions over all
    the layers built: every admitted pair scores (2 hd) and adds its value
    (2 hd), in every query head."""
    full, window = layer_counts(config)
    per_pair = config["num_attention_heads"] * 4 * config["head_dim"]
    return per_pair * (full * band_pairs(length)
                       + window * band_pairs(length,
                                             config["sliding_window"]))
