"""Operations and bytes that a latent-attention decoder with a chip's share
of dropless experts (Kimi-K2's block) needs, from shapes and the engine's
routing counters alone — ``harness.work``'s counts for that block.  Kept
with the benchmark so that no PR that claims a gain can change the count.

Nothing here can read a share over 100%: a routed expert is counted only
for the (token, choice) assignments that LANDED on an expert held here (the
engine's ``expert_assignments_here``), attention in the form each path
must run (expanded over a prompt, absorbed at decode) over real positions,
and a decode step's bytes hold each routed expert only where a token
TOUCHED it (``expert_steps_touched``).  A cache row counts as the
``kv_lora_rank + qk_rope_head_dim`` numbers it holds, not the lane padding
it is stored with.
"""


def layer_counts(sizes):
    """(leading dense layers, expert layers)."""
    dense = sizes["first_k_dense_replace"]
    return dense, sizes["num_hidden_layers"] - dense


def attention_params(sizes):
    """One block's attention matrices: the two query projections, the
    joint key/value down projection, the per-head up projection, out.
    The absorbed form multiplies a token through the same counts (the up
    projection's halves, as ``Wuk^T q`` and ``Wuv o``)."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, v = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    return (d * q_rank + q_rank * heads * (nope + rope)
            + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * d)


def expert_params(sizes):
    """One routed expert: gate, up, down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def outside_params(sizes):
    """An expert layer's matrices outside its routed experts: attention,
    the shared expert(s), the router at its published width."""
    return (attention_params(sizes)
            + sizes["n_shared_experts"] * expert_params(sizes)
            + sizes["hidden_size"] * sizes["published"]["n_routed_experts"])


def dense_layer_params(sizes):
    return (attention_params(sizes)
            + 3 * sizes["hidden_size"] * sizes["intermediate_size"])


def token_params(sizes):
    """The matrices every token goes through, all layers (no routed
    expert, no embedding, no head)."""
    dense, experts = layer_counts(sizes)
    return dense * dense_layer_params(sizes) + experts * outside_params(sizes)


def small_params(sizes):
    """The vectors: four norms a block, the selection bias, the final
    norm."""
    dense, experts = layer_counts(sizes)
    norms = (2 * sizes["hidden_size"] + sizes["q_lora_rank"]
             + sizes["kv_lora_rank"])
    return ((dense + experts) * norms
            + experts * sizes["published"]["n_routed_experts"]
            + sizes["hidden_size"])


def params(sizes):
    """All parameters held here: the blocks with ``n_routed_experts``
    experts a layer, the embedding's and the head's slice."""
    _, experts = layer_counts(sizes)
    return (token_params(sizes)
            + experts * sizes["n_routed_experts"] * expert_params(sizes)
            + small_params(sizes)
            + 2 * sizes["vocab_size"] * sizes["hidden_size"])


def expanded_attention_flops(sizes, pairs):
    """Scores and weighted values over ``pairs`` (query, key) pairs, all
    layers, at head sizes qk = nope + rope and v."""
    per_pair = 2 * sizes["num_attention_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"])
    return sizes["num_hidden_layers"] * per_pair * pairs


def absorbed_attention_flops(sizes, keys):
    """One query's scores and weighted rows over ``keys`` latent rows, all
    layers: every head against the whole row, values its latent part."""
    per_key = 2 * sizes["num_attention_heads"] * (
        2 * sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
    return sizes["num_hidden_layers"] * per_key * keys


def routed_flops(sizes, assignments_here):
    """The routed experts' products for that many (token, choice)
    assignments that landed here."""
    return 2 * expert_params(sizes) * assignments_here


def head_flops(sizes):
    return 2 * sizes["hidden_size"] * sizes["vocab_size"]


def prefill_flops(sizes, prompt_len, here_share):
    """A prompt's real tokens through every layer in the expanded form
    and the head once; of its ``top_k x expert layers`` assignments a
    token, the share ``here_share`` landed here."""
    _, experts = layer_counts(sizes)
    assignments = prompt_len * sizes["num_experts_per_tok"] * experts
    return (2 * token_params(sizes) * prompt_len
            + expanded_attention_flops(
                sizes, prompt_len * (prompt_len + 1) // 2)
            + routed_flops(sizes, assignments * here_share)
            + head_flops(sizes))


def decode_flops(sizes, position, here_share):
    """One new token from the token at ``position`` (0-based), in the
    absorbed form."""
    _, experts = layer_counts(sizes)
    assignments = sizes["num_experts_per_tok"] * experts
    return (2 * token_params(sizes)
            + absorbed_attention_flops(sizes, position + 1)
            + routed_flops(sizes, assignments * here_share)
            + head_flops(sizes))


def latent_row_bytes(sizes, itemsize=2):
    """One cached position, one layer: the normed latent and the rotated
    key."""
    return (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]) * itemsize


def decode_step_bytes(sizes, touched, latent_rows, itemsize=2):
    """Bytes one decode step must move: every matrix outside the routed
    experts and the head's slice once (the embedding gives one row a
    slot), each routed expert that got a token once (``touched``: summed
    over the expert layers), the latent rows that hold a token, every
    layer's."""
    weights = (token_params(sizes) + small_params(sizes)
               + sizes["vocab_size"] * sizes["hidden_size"])
    return (itemsize * (weights + touched * expert_params(sizes))
            + sizes["num_hidden_layers"] * latent_row_bytes(sizes, itemsize)
            * latent_rows)


def latent_decode_call(sizes, heads_rows, latent_rows, itemsize=2):
    """(flops, bytes) of ONE call of the latent decode read (one layer,
    one step): ``latent_rows`` rows in use over the grid, fetched once,
    against ``heads_rows`` = live slots x heads query rows in, as many
    rows of ``kv_lora_rank`` out."""
    flops = absorbed_attention_flops(sizes, latent_rows) \
        // sizes["num_hidden_layers"]
    width = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    moved = itemsize * (latent_rows * width
                        + heads_rows * (width + sizes["kv_lora_rank"]))
    return flops, moved


def grouped_products(sizes, assignments_here, touched, itemsize=2):
    """(flops, bytes) of an expert layer's three grouped products for
    ``assignments_here`` rows over ``touched`` experts: each touched
    expert's matrices once, the rows in and out."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    moved = itemsize * (touched * expert_params(sizes)
                        + assignments_here * (2 * d + 3 * f))
    return routed_flops(sizes, assignments_here), moved


def flash_forward_call(sizes, length, itemsize=2):
    """(flops, bytes) of ONE causal attention forward in the expanded
    form over ``length`` real positions of one prompt, one layer: query
    and key heads of ``nope + rope``, value heads of ``v`` (the numbers
    they hold, not what a kernel pads them to); q, k, v read, o written."""
    heads = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    flops = expanded_attention_flops(sizes, length * (length + 1) // 2) \
        // sizes["num_hidden_layers"]
    return flops, itemsize * length * heads * 2 * (qk + sizes["v_head_dim"])

