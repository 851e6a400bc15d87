"""Operations and bytes the algorithm needs, from a configuration's shapes.
Kept with the benchmark so that no PR that claims a gain can change the
yardstick. A multiply-add is 2 FLOPs. Nothing recomputed is counted.
"""

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


# ---------------------------------------------------------------------------
# ResNet (bottleneck), forward FLOPs per image from the config's shapes
# ---------------------------------------------------------------------------

def resnet_convs(config):
    """Every convolution / dense layer of the bottleneck ResNet as
    `(c_in, c_out, kernel, out_h, out_w)`. The configuration's `arch` says
    where a stage's stride sits: `preact_symbol` (models/resnet.py, the
    source repo's symbols/resnet.py) strides the 3x3; `v1_gluon` (model_zoo
    BottleneckV1) strides the first 1x1."""
    arch = config["arch"]
    if arch not in ("preact_symbol", "v1_gluon"):
        raise ValueError(f"unknown ResNet arch {arch!r}")
    size = config["image_size"]
    convs = []
    size = size // 2                                   # 7x7 stride 2
    convs.append((config["image_channels"], config["stem_filters"], 7,
                  size, size))
    size = size // 2                                   # 3x3 max-pool stride 2
    c_in = config["stem_filters"]
    for stage, (units, c_out) in enumerate(zip(config["units"],
                                               config["stage_filters"])):
        mid = c_out // config["bottleneck_ratio"]
        for unit in range(units):
            stride = 2 if (unit == 0 and stage > 0) else 1
            out = size // stride
            if arch == "v1_gluon":
                convs.append((c_in, mid, 1, out, out))     # strided 1x1
                convs.append((mid, mid, 3, out, out))
            else:
                convs.append((c_in, mid, 1, size, size))
                convs.append((mid, mid, 3, out, out))      # strided 3x3
            convs.append((mid, c_out, 1, out, out))
            if unit == 0:                                  # projection shortcut
                convs.append((c_in, c_out, 1, out, out))
            c_in, size = c_out, out
    convs.append((c_in, config["num_classes"], 1, 1, 1))   # the classifier
    return convs


def resnet_forward_flops(config):
    return sum(2 * ci * co * k * k * h * w
               for ci, co, k, h, w in resnet_convs(config))


def resnet_train_flops_per_image(config):
    """Forward + backward: the backward pass computes a gradient for the
    input and one for the weights of every layer, each as many FLOPs as the
    forward (the first layer's input gradient, which nobody needs, is
    counted too: the usual 3x rule)."""
    return 3 * resnet_forward_flops(config)


# ---------------------------------------------------------------------------
# GPT-2 block (models/transformer.py), serving
# ---------------------------------------------------------------------------

def gpt2_param_count(config):
    d, f, v = config["n_embd"], config["n_inner"], config["vocab_size"]
    per_layer = (d * 3 * d) + (d * d) + (d * f) + f + (f * d) + d + 4 * d
    return v * d + config["n_positions"] * d + 2 * d \
        + config["n_layer"] * per_layer


def gpt2_weight_bytes(config):
    return gpt2_param_count(config) * DTYPE_BYTES[config["dtype"]]


def gpt2_kv_bytes_per_position(config):
    """K and V rows of one position, all layers."""
    return 2 * config["n_layer"] * config["n_embd"] \
        * DTYPE_BYTES[config["dtype"]]


def gpt2_decode_tick_min_bytes(config, live_positions):
    """The least a decode tick must move: every weight once (the position
    table only as rows, so it is left out) and the live K/V rows of the live
    slots — `live_positions` is the sum of the live slots' lengths."""
    d = config["n_embd"]
    weights = gpt2_weight_bytes(config) \
        - config["n_positions"] * d * DTYPE_BYTES[config["dtype"]]
    return weights + live_positions * gpt2_kv_bytes_per_position(config)
