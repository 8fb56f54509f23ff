"""Operations and bytes that the algorithms need, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
count: XLA's own ``cost_analysis`` counts whatever HLO a PR leaves
(recomputation counted, a Pallas call counted as nothing)."""

import math


def _same_out(size, stride):
    return math.ceil(size / stride)


def resnet_convs(sizes, image):
    """(out_h, out_w, kh, kw, cin, cout) of every convolution of the
    bottleneck ResNet (stride on the 3x3), and the spatial size it ends at.
    """
    convs, width = [], sizes["width"]
    hw = _same_out(image, 2)
    convs.append((hw, hw, 7, 7, 3, width))
    hw = _same_out(hw, 2)  # 3x3 max pool, stride 2
    cin = width
    for stage, blocks in enumerate(sizes["stage_sizes"]):
        cmid = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            out = _same_out(hw, stride)
            convs.append((hw, hw, 1, 1, cin, cmid))
            convs.append((out, out, 3, 3, cmid, cmid))
            convs.append((out, out, 1, 1, cmid, cmid * 4))
            if stride != 1 or cin != cmid * 4:
                convs.append((out, out, 1, 1, cin, cmid * 4))
            hw, cin = out, cmid * 4
    return convs, cin


def resnet_forward_macs(sizes, image):
    """Multiply-accumulates of one sample's forward pass: convolutions and
    the classifier (normalisation and pooling are not matrix work)."""
    convs, cin = resnet_convs(sizes, image)
    macs = sum(h * w * kh * kw * ci * co for h, w, kh, kw, ci, co in convs)
    return macs + cin * sizes["num_classes"]


def resnet_train_flops_per_sample(sizes, image):
    """Forward plus backward: 2 per multiply-accumulate, and the backward
    pass costs twice the forward (one product for the inputs' gradient,
    one for the weights')."""
    return 2 * 3 * resnet_forward_macs(sizes, image)


def resnet_group_norm_elements(sizes, image):
    """Elements of every GroupNorm's input for one sample (each
    convolution is followed by one)."""
    convs, _ = resnet_convs(sizes, image)
    return sum(h * w * co for h, w, _, _, _, co in convs)


def group_norm_step_bytes(sizes, image, batch, itemsize=2):
    """Bytes a train step's GroupNorms must move: forward reads x and
    writes y; backward reads x and dy and writes dx.  A fused residual's
    extra read and the per-group statistics are left out, so the count is
    a little low and the share it gives never too high."""
    return 5 * itemsize * batch * resnet_group_norm_elements(sizes, image)


def group_norm_step_flops(sizes, image, batch):
    """About 8 operations an element forward, 14 backward."""
    return 22 * batch * resnet_group_norm_elements(sizes, image)


# -- the decoder ---------------------------------------------------------


def decoder_layer_params(sizes):
    d, inner = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["num_attention_heads"] * sizes["head_dim"]
    return sizes["num_hidden_layers"] * (4 * d * hd + 3 * d * inner)


def decoder_params(sizes):
    """All parameters: layers, embedding table and output head."""
    return (decoder_layer_params(sizes)
            + 2 * sizes["vocab_size"] * sizes["hidden_size"])


def _attention_flops(sizes, keys):
    """Scores and weighted values of one query over ``keys`` keys, all
    layers: 2 products of 2 operations each per head element."""
    hd = sizes["num_attention_heads"] * sizes["head_dim"]
    return sizes["num_hidden_layers"] * 4 * hd * keys


def prefill_flops(sizes, prompt_len):
    """A prompt's real tokens through every layer, causal attention over
    what precedes each, and the head once, for the first new token."""
    causal_keys = prompt_len * (prompt_len + 1) // 2
    return (2 * decoder_layer_params(sizes) * prompt_len
            + _attention_flops(sizes, causal_keys)
            + 2 * sizes["hidden_size"] * sizes["vocab_size"])


def decode_flops(sizes, position):
    """One new token from the token at ``position`` (0-based)."""
    return (2 * decoder_layer_params(sizes)
            + _attention_flops(sizes, position + 1)
            + 2 * sizes["hidden_size"] * sizes["vocab_size"])


def flash_forward_call(sizes, length, itemsize=2):
    """(flops, bytes) of one causal attention forward over ``length``
    positions of one sequence, one layer: q, k, v read and o written."""
    hd = sizes["num_attention_heads"] * sizes["head_dim"]
    flops = 4 * hd * (length * (length + 1) // 2)
    return flops, 4 * length * hd * itemsize
