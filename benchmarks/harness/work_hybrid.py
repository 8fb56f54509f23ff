"""Operations and bytes that a hybrid attention + state-space decoder
(Falcon-H1's block) needs, from shapes alone — ``harness.work``'s counts
for a block with grouped K/V heads and a Mamba-2 mixer beside attention.
Kept with the benchmark so that no PR that claims a gain can change the
count."""


def _ssm_dims(sizes):
    """(B or C's width, the convolution's channels, the input
    projection's width)."""
    gn = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    conv_dim = sizes["mamba_d_ssm"] + 2 * gn
    return gn, conv_dim, (sizes["mamba_d_ssm"] + conv_dim
                          + sizes["mamba_n_heads"])


def layer_matmul_params(sizes):
    """One block's matrix parameters: q, k, v, out; the mixer's two
    projections; gate, up, down."""
    d, inner = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["head_dim"]
    q_dim = sizes["num_attention_heads"] * hd
    kv_dim = sizes["num_key_value_heads"] * hd
    _, _, in_dim = _ssm_dims(sizes)
    return (2 * d * q_dim + 2 * d * kv_dim
            + d * in_dim + sizes["mamba_d_ssm"] * d + 3 * d * inner)


def layer_small_params(sizes):
    """One block's vectors: two norms, the convolution's kernel and bias,
    dt_bias, A_log, D, the gated norm's weight."""
    _, conv_dim, _ = _ssm_dims(sizes)
    return (2 * sizes["hidden_size"]
            + (sizes["mamba_d_conv"] + 1) * conv_dim
            + 3 * sizes["mamba_n_heads"] + sizes["mamba_d_ssm"])


def params(sizes):
    """All parameters: blocks, embedding table, final norm, head."""
    return (sizes["num_hidden_layers"]
            * (layer_matmul_params(sizes) + layer_small_params(sizes))
            + 2 * sizes["vocab_size"] * sizes["hidden_size"]
            + sizes["hidden_size"])


def _attention_flops(sizes, keys):
    """Scores and weighted values of one query over ``keys`` keys, all
    blocks: 2 products of 2 operations per QUERY head element."""
    q_dim = sizes["num_attention_heads"] * sizes["head_dim"]
    return sizes["num_hidden_layers"] * 4 * q_dim * keys


def _recurrence_flops(sizes):
    """One token through one block's recurrence in its token-by-token
    form: per head P x N state elements, each decayed and added to (3
    operations) and read against C (2)."""
    return 5 * (sizes["mamba_n_heads"] * sizes["mamba_d_head"]
                * sizes["mamba_d_state"])


def _ssd_flops(sizes, length):
    """A prompt of ``length`` tokens through one block's recurrence in
    its chunked (SSD) form, chunks of Q: per chunk the scores C B^T
    (2 Q^2 N a head), their product with x (2 Q^2 P), the chunk's state
    (2 Q P N) and the entering state's read (2 Q P N)."""
    q = sizes["mamba_chunk_size"]
    chunks = -(-length // q)
    h, p, n = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
               sizes["mamba_d_state"])
    return chunks * h * (2 * q * q * (n + p) + 4 * q * p * n)


def _conv_flops(sizes):
    _, conv_dim, _ = _ssm_dims(sizes)
    return 2 * sizes["mamba_d_conv"] * conv_dim


def prefill_flops(sizes, prompt_len):
    """A prompt's real tokens through every block (causal attention over
    what precedes each, the mixer in its chunked form) and the head
    once, for the first new token."""
    layers = sizes["num_hidden_layers"]
    causal_keys = prompt_len * (prompt_len + 1) // 2
    return (2 * layers * layer_matmul_params(sizes) * prompt_len
            + _attention_flops(sizes, causal_keys)
            + layers * (_ssd_flops(sizes, prompt_len)
                        + _conv_flops(sizes) * prompt_len)
            + 2 * sizes["hidden_size"] * sizes["vocab_size"])


def decode_flops(sizes, position):
    """One new token from the token at ``position`` (0-based)."""
    layers = sizes["num_hidden_layers"]
    return (2 * layers * layer_matmul_params(sizes)
            + _attention_flops(sizes, position + 1)
            + layers * (_recurrence_flops(sizes) + _conv_flops(sizes))
            + 2 * sizes["hidden_size"] * sizes["vocab_size"])


def state_bytes_per_slot(sizes, conv_itemsize=2):
    """One slot's recurrent state (float32) and convolution tail, all
    blocks."""
    _, conv_dim, _ = _ssm_dims(sizes)
    per_layer = (4 * sizes["mamba_n_heads"] * sizes["mamba_d_head"]
                 * sizes["mamba_d_state"]
                 + conv_itemsize * (sizes["mamba_d_conv"] - 1) * conv_dim)
    return sizes["num_hidden_layers"] * per_layer


def kv_bytes_per_row(sizes, itemsize=2):
    """One cached position's K and V, all blocks."""
    return (2 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"]
            * sizes["head_dim"] * itemsize)


def decode_step_bytes(sizes, live_slots, kv_rows, itemsize=2):
    """Bytes one decode step must move: every weight but the embedding
    table once (the table gives one row a slot), each live slot's state
    read and written, the K/V rows that hold a token read once."""
    weights = (params(sizes)
               - sizes["vocab_size"] * sizes["hidden_size"]) * itemsize
    return (weights + 2 * state_bytes_per_slot(sizes, itemsize) * live_slots
            + kv_bytes_per_row(sizes, itemsize) * kv_rows)
