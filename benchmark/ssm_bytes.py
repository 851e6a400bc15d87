"""Operations and bytes the hybrid state-space / attention block needs
(`mxnet_tpu/models/hybrid.py`; configuration keys as published for
`granitemoehybrid`), from a configuration's shapes. Kept with the benchmark,
beside `flops.py`, so that no PR that claims a gain can change the yardstick.
A multiply-add is 2 FLOPs. Nothing recomputed is counted.
"""
from flops import DTYPE_BYTES

STATE_BYTES = 4          # the recurrent state is float32 whatever the dtype


def _kinds(config):
    types = config["layer_types"]
    return types.count("mamba"), types.count("attention")


def mamba_inner(config):
    return config["mamba_n_heads"] * config["mamba_d_head"]


def conv_channels(config):
    return mamba_inner(config) + 2 * config["mamba_n_groups"] \
        * config["mamba_d_state"]


def hybrid_param_count(config):
    d, f = config["hidden_size"], config["shared_intermediate_size"]
    head = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * head
    inner, conv = mamba_inner(config), conv_channels(config)
    heads = config["mamba_n_heads"]
    mlp = d * 2 * f + f * d + 2 * d                 # gated MLP + two norms
    attention = 2 * d * d + 2 * d * kv
    mamba = d * (inner + conv + heads) + conv * config["mamba_d_conv"] \
        + conv + 3 * heads + inner + inner * d
    n_mamba, n_attention = _kinds(config)
    return config["vocab_size"] * d + d + n_mamba * (mlp + mamba) \
        + n_attention * (mlp + attention)


def hybrid_weight_bytes(config):
    return hybrid_param_count(config) * DTYPE_BYTES[config["dtype"]]


def mamba_weight_bytes(config):
    """The Mamba mixers' own weights (without the layers' MLPs)."""
    d = config["hidden_size"]
    inner, conv = mamba_inner(config), conv_channels(config)
    heads = config["mamba_n_heads"]
    per = d * (inner + conv + heads) + conv * config["mamba_d_conv"] \
        + conv + 3 * heads + inner + inner * d
    return _kinds(config)[0] * per * DTYPE_BYTES[config["dtype"]]


def ssm_state_bytes_per_slot(config):
    """The recurrent state of one session: every Mamba layer's `[heads,
    head_dim, d_state]`, float32."""
    return _kinds(config)[0] * mamba_inner(config) \
        * config["mamba_d_state"] * STATE_BYTES


def conv_state_bytes_per_slot(config):
    """The last `d_conv - 1` convolution inputs of every Mamba layer."""
    return _kinds(config)[0] * (config["mamba_d_conv"] - 1) \
        * conv_channels(config) * DTYPE_BYTES[config["dtype"]]


def kv_bytes_per_position(config):
    """K and V rows of one position: the attention layers only."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return 2 * _kinds(config)[1] * config["num_key_value_heads"] * head \
        * DTYPE_BYTES[config["dtype"]]


def state_update_min_bytes(config, live_slots):
    """The least a tick's state update must move: each live slot's
    recurrent state read once and written once."""
    return 2 * live_slots * ssm_state_bytes_per_slot(config)


def hybrid_decode_tick_min_bytes(config, live_slots, live_positions):
    """The least a decode tick must move: every weight once (the embedding
    is read whole by the tied head), each live slot's recurrent and
    convolution state read and written once, and the live K/V rows of the
    live slots — `live_positions` is the sum of the live slots' lengths."""
    state = ssm_state_bytes_per_slot(config) \
        + conv_state_bytes_per_slot(config)
    return hybrid_weight_bytes(config) + 2 * live_slots * state \
        + live_positions * kv_bytes_per_position(config)


def ssd_scan_flops(config, tokens):
    """FLOPs of the chunked scan over `tokens` positions of one sequence,
    all Mamba layers: per chunk of Q positions the C.B^T scores (2 Q Q N),
    and per head the masked-decay matmul (2 Q Q P), the carried state's
    read-out (2 Q N P) and its update (2 Q N P). Elementwise work (the decay
    matrix, the gates) is not counted."""
    q = config["mamba_chunk_size"]
    chunks = -(-tokens // q)
    n, p, h = (config["mamba_d_state"], config["mamba_d_head"],
               config["mamba_n_heads"])
    per_chunk = 2 * q * q * n + h * (2 * q * q * p + 4 * q * n * p)
    return _kinds(config)[0] * chunks * per_chunk
