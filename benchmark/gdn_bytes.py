"""Bytes the gated-delta-rule / full-attention hybrid block needs
(`mxnet_tpu/models/hybrid.py` as the Olmo-Hybrid block; configuration keys as
published for `olmo_hybrid`), from a configuration's shapes alone. Kept with
the benchmark, beside `flops.py` and `ssm_bytes.py`, so that no PR that claims
a gain can change the yardstick. Nothing recomputed is counted. The layers
built are the first `num_hidden_layers` of `layer_types`.
"""
from flops import DTYPE_BYTES

STATE_BYTES = 4          # the recurrent state is float32 whatever the dtype
LINEAR, FULL = "linear_attention", "full_attention"


def _itemsize(config):
    return DTYPE_BYTES[config["dtype"]]


def layer_counts(config):
    """`(linear layers, full layers)` among the layers built."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count(LINEAR), kinds.count(FULL)


def _linear_dims(config):
    return (config["linear_num_value_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"])


def conv_channels(config):
    """The three convolved streams side by side: q | k | v."""
    h, dk, dv = _linear_dims(config)
    return h * (2 * dk + dv)


def linear_mixer_param_count(config):
    """One gated-delta-rule mixer: the projections of q, k, v, the output
    gate, `a` and `b`; three depthwise convolutions without bias; `dt_bias`
    and `A_log`; the output norm of a head; the output projection."""
    d = config["hidden_size"]
    h, _, dv = _linear_dims(config)
    return d * (conv_channels(config) + h * dv + 2 * h) \
        + config["linear_conv_kernel_dim"] * conv_channels(config) \
        + 2 * h + dv + h * dv * d


def full_mixer_param_count(config):
    """One full-attention mixer: W_q, W_k, W_v, W_o and the two whole-vector
    norms of q and k (no grouped queries in the published model, so K and V
    are as wide as Q)."""
    d = config["hidden_size"]
    kv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    return 2 * d * d + 2 * d * kv + d + kv


def mlp_param_count(config):
    """The gated MLP and the layer's two norms."""
    d = config["hidden_size"]
    return 3 * d * config["intermediate_size"] + 2 * d


def head_param_count(config):
    """The untied output head and the final norm."""
    d = config["hidden_size"]
    return config["vocab_size"] * d + d


def param_count(config):
    n_linear, n_full = layer_counts(config)
    return n_linear * (linear_mixer_param_count(config)
                       + mlp_param_count(config)) \
        + n_full * (full_mixer_param_count(config) + mlp_param_count(config)) \
        + config["vocab_size"] * config["hidden_size"] \
        + head_param_count(config)


def weight_bytes(config):
    return param_count(config) * _itemsize(config)


def linear_mixer_weight_bytes(config):
    """The linear mixers' own weights (without the layers' MLPs)."""
    return layer_counts(config)[0] * linear_mixer_param_count(config) \
        * _itemsize(config)


def state_page_bytes(config):
    """One slot's state of one linear layer: `heads x dk x dv`, float32."""
    h, dk, dv = _linear_dims(config)
    return h * dk * dv * STATE_BYTES


def state_bytes_per_slot(config):
    return layer_counts(config)[0] * state_page_bytes(config)


def conv_bytes_per_slot(config):
    """The last `kernel - 1` convolution inputs of every linear layer."""
    return layer_counts(config)[0] * (config["linear_conv_kernel_dim"] - 1) \
        * conv_channels(config) * _itemsize(config)


def kv_bytes_per_row(config):
    """What the cache holds of one position in ONE full layer: a key and a
    value for every K/V head."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * hd * _itemsize(config)


def kv_bytes_per_position(config):
    return layer_counts(config)[1] * kv_bytes_per_row(config)


def state_update_min_bytes(config, live_slots):
    """The least a tick's state updates must move: each live slot's state
    of every linear layer read once and written once."""
    return 2 * live_slots * state_bytes_per_slot(config)


def attend_min_bytes(config, kv_rows):
    """The least a tick's decode attention must move: the K/V rows the live
    slots attend, `kv_rows` summed over the full layers (the engine's
    counter `kv_rows_live_full`), each read once."""
    return kv_rows * kv_bytes_per_row(config)


def decode_tick_min_bytes(config, live_slots, kv_rows):
    """The least a decode tick must move: every weight once but the
    embedding table (read a row a slot; the head is untied and read whole),
    each live slot's recurrent and convolution state read and written once,
    and the live K/V rows of the full layers."""
    d, item = config["hidden_size"], _itemsize(config)
    weights = weight_bytes(config) \
        - (config["vocab_size"] - live_slots) * d * item
    state = state_bytes_per_slot(config) + conv_bytes_per_slot(config)
    return weights + 2 * live_slots * state + attend_min_bytes(config,
                                                               kv_rows)
