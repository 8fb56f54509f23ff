"""Autoregressive generation for CloudLM: prefill + KV-cache decode.

TPU-first decode loop: the whole generation is ONE ``lax.scan`` — static
trip count, static shapes, no host round-trips — so XLA compiles a single
program for the full sampling run.  The KV cache is a pair of
``[L, B, S, H, hd]`` buffers carried through the scan; each step appends
one position per sequence (per-row ``cur_len`` write indices lower to a
scatter, so ragged prompt lengths need no host-side padding games).

The reference has no inference path at all (it launches training jobs —
SURVEY.md §1); this module is framework capability beyond parity, built
on the same layer primitives as training (``transformer.qkv_project``,
``layers.rmsnorm_apply``) so cache decode is numerically equivalent to a
full re-forward — tested against exactly that in
tests/unit/test_generation.py.

Sharding: under a mesh, batch shards over dp/fsdp and heads over tp via
the usual logical-axis constraints.  The slot-grid program family
(insert/decode-chunk/prefill-chunk/finalize, plus the prefix-pool
copy/save pair) runs unchanged under a serving TP(xSP) mesh: the slot
KV cache and block pool shard by attention head, params per the rules
table, and logits reshard to replicated exactly once per forward — at
the sampling boundary (``cloud_tpu.serving`` builds that mesh from
``ServeConfig.mesh_shape``; greedy outputs stay token-identical to the
single-chip path).  ``pp``/``zigzag_sp`` layouts are training-only and
rejected up front.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from cloud_tpu.models import layers, mla as mla_lib
from cloud_tpu.models import moe as moe_lib, ssm as ssm_lib
from cloud_tpu.models import transformer
from cloud_tpu.parallel import mesh as mesh_lib
from cloud_tpu.parallel.sharding import DEFAULT_RULES, ShardingRules, shard_constraint


@dataclasses.dataclass(frozen=True)
class SampleConfig:
    """Sampling hyperparameters (all static — they specialize the compile).

    ``temperature=0`` means greedy (argmax); ``repetition_penalty`` /
    ``top_k`` / ``top_p`` apply in that order when set.  ``eos_id``
    stops a sequence: the eos token itself is emitted, and every slot
    after it holds ``pad_id``; ``min_new_tokens`` suppresses eos until
    that many tokens have been generated.
    """

    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_id: Optional[int] = None
    pad_id: int = 0
    #: > 1.0 discourages tokens already generated this run (CTRL-style:
    #: positive logits divided by, negative multiplied by the penalty).
    #: Applies to greedy decoding too.
    repetition_penalty: float = 1.0
    #: eos is masked out of the logits for the first this-many sampled
    #: tokens (forces a minimum generation length).
    min_new_tokens: int = 0


def sample_logits(rng, logits, sample: SampleConfig, *, seen=None,
                  allow_eos=None):
    """One sampling step: logits [B, V] f32 -> token ids [B].

    ``seen``: optional [B, V] bool — tokens already generated (the
    repetition-penalty mask).  ``allow_eos``: optional [B] bool — False
    masks ``eos_id`` out of the distribution (min_new_tokens).
    """
    if sample.repetition_penalty != 1.0 and seen is not None:
        penalized = jnp.where(
            logits > 0, logits / sample.repetition_penalty,
            logits * sample.repetition_penalty,
        )
        logits = jnp.where(seen, penalized, logits)
    if sample.eos_id is not None and allow_eos is not None:
        eos_col = logits[:, sample.eos_id]
        logits = logits.at[:, sample.eos_id].set(
            jnp.where(allow_eos, eos_col, -jnp.inf)
        )
    if sample.temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / sample.temperature
    if sample.top_k is not None:
        kth = jax.lax.top_k(logits, sample.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if sample.top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumulative = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with mass >= top_p (the cutoff token
        # itself stays includable, hence the shift-by-one).  The top
        # token always survives — at top_p=0.0 the strict < would
        # otherwise keep nothing and sample from all -inf garbage.
        keep = cumulative - probs < sample.top_p
        keep = keep.at[..., 0].set(True)
        threshold = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1)


#: The cache leaves that hold a recurrent state, not K/V rows.
STATE_LEAVES = ("ssm", "conv")

#: The program scope (``layers.SCOPES``) a state leaf's reads and writes in
#: the layer loop go by: the convolution's tail with the projections
#: around it, the recurrent state with its step.
_STATE_SCOPES = {"ssm": "ssm_state", "conv": "ssm_proj"}

#: The cache leaf of a latent-attention model (``TransformerConfig.latent``):
#: one row a token a layer, ``[c | k_pe | 0]`` [L, B, S, row_width], key
#: and value of every head at once (models/mla.py).
LATENT_LEAF = "latent"


def _rows_leaf(cache):
    """The leaf whose axes 0-2 are (layer, row, position): K, or the
    latent rows."""
    return cache["k"] if "k" in cache else cache[LATENT_LEAF]


def _init_cache(config: transformer.TransformerConfig, b: int, s: int,
                rules: ShardingRules, mesh, kv_quant: bool = False):
    """KV cache pytree [L, B, S, H, hd].

    ``kv_quant=True`` stores K/V as int8 with per-(position, head) f32
    scales [L, B, S, H, 1] — the cache is re-read WHOLE every decode
    step, so at long context its bytes are the decode bandwidth; int8
    quarters them vs f32 (halves vs a bf16 cache).  The scales ride the
    same pytree so every cache operation (the layer loop's in-place
    scatter and indexed read, beam repeat/reorder) maps over its
    leaves.

    With ``config.ssm`` two leaves WITHOUT a position axis ride along
    (:data:`STATE_LEAVES`): ``ssm`` [L, B, H, P, N] float32, each row's
    recurrent state, and ``conv`` [L, B, W - 1, conv_dim], the last
    inputs of its causal convolution.  A row's state is whole whatever
    its context holds: nothing masks a stale one, so whoever arms a row
    writes both leaves whole and whoever skips a row leaves both alone.
    """
    if config.latent is not None:
        if kv_quant:
            _refuse_unless_kv_rows(config, "an int8 cache (kv_quant)")
        return {LATENT_LEAF: jnp.zeros(
            (config.num_layers, b, s, config.latent.row_width),
            config.dtype)}
    shape = (config.num_layers, b, s, config.kv_heads, config.head_dim)

    def constrain(x):
        return shard_constraint(x, None, "batch", None, "heads", None,
                                rules=rules, mesh=mesh)

    if not kv_quant:
        cache = {"k": constrain(jnp.zeros(shape, config.dtype)),
                 "v": constrain(jnp.zeros(shape, config.dtype))}
    else:
        scale_shape = shape[:-1] + (1,)
        cache = {
            "k": constrain(jnp.zeros(shape, jnp.int8)),
            "k_scale": constrain(jnp.ones(scale_shape, jnp.float32)),
            "v": constrain(jnp.zeros(shape, jnp.int8)),
            "v_scale": constrain(jnp.ones(scale_shape, jnp.float32)),
        }
    if config.ssm is not None:
        m = config.ssm
        cache["ssm"] = jnp.zeros(
            (config.num_layers, b, m.num_heads, m.head_dim, m.state_dim),
            ssm_lib.STATE_DTYPE)
        cache["conv"] = jnp.zeros(
            (config.num_layers, b, m.conv_width - 1, m.conv_dim),
            config.dtype)
    return cache


def _quantize_kv(x):
    """Per-(..., head) vector int8: returns (q, scale[..., 1])."""
    from cloud_tpu.models.quantization import quantize_array

    return quantize_array(x, axis=-1)


def _cache_attention(q, cache_l, cur_len, *, chunk_causal: bool = False):
    """q [B, Tq, H, hd] against the layer cache {k, v[, *_scale]}
    [B, S, H, hd]; key j of row i is valid iff j < cur_len[i].  f32
    softmax, finite mask value (matching ops.flash_attention's semantics
    for fully-masked rows).

    ``chunk_causal=True`` treats the queries as CONSECUTIVE cache
    positions starting at ``cur_len - 1`` (the chunk-prefill case): key
    j is valid for query t iff ``j < cur_len + t`` — causal over the
    chunk, full visibility over everything already in the cache.

    Quantized caches use POST-SCALE algebra — scores = (q . k_q) *
    k_scale folded into the [B, H, Tq, S] scores, and v_scale folded
    into the softmax weights — so the int8 arrays feed the einsums
    directly and no dequantized full-width cache ever materializes.
    """
    k_cache, v_cache = cache_l["k"], cache_l["v"]
    s = k_cache.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    b, t_q, h, hd = q.shape
    group = h // k_cache.shape[2]
    if group > 1:
        # Grouped K/V heads: the ``group`` query heads that read one K/V
        # head line up as extra query rows of that head (row t * group +
        # g), so the products below never see a repeated cache.
        q = q.reshape(b, t_q, h // group, group, hd).transpose(
            0, 1, 3, 2, 4).reshape(b, t_q * group, h // group, hd)

    def fold(scores_like, kv_scale):
        # [B, S, H, 1] -> [B, H, 1, S] broadcast over the query dim.
        return scores_like * jnp.transpose(kv_scale, (0, 2, 3, 1))

    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32),
        k_cache.astype(jnp.float32),
    ) * scale
    if "k_scale" in cache_l:
        scores = fold(scores, cache_l["k_scale"])
    if chunk_causal:
        # [B, Tq, S]: query t sits at cache position cur_len - 1 + t.
        valid = jnp.arange(s)[None, None, :] < (
            cur_len[:, None, None]
            + (jnp.arange(q.shape[1]) // group)[None, :, None]
        )
        scores = jnp.where(valid[:, None, :, :], scores, -1e30)
    else:
        valid = jnp.arange(s)[None, :] < cur_len[:, None]  # [B, S]
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    if "v_scale" in cache_l:
        weights = fold(weights, cache_l["v_scale"])
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", weights, v_cache.astype(jnp.float32)
    )
    if group > 1:
        out = out.reshape(b, t_q, group, h // group, hd).transpose(
            0, 1, 3, 2, 4).reshape(b, t_q, h, hd)
    return out.astype(q.dtype)


def _mlp(layer_params, y, config, rules, live=None, held=None, layer=None):
    """The block's MLP on ``y`` [B, T, D] and, from a dropless expert
    layer, what it counted of its routing (else None); ``live`` [B, T]
    keeps padding and idle rows off the experts.  ``held``: the routed
    experts' matrices of EVERY layer of the stack (:func:`_split_experts`),
    of which this is layer ``layer``."""
    if moe_lib.counts_routing(config.moe):
        mlp = layer_params["mlp"]
        return moe_lib.dropless_mlp_apply(
            mlp if held is None else dict(mlp, **held), y, config.moe,
            live=live, layer=None if held is None else layer)
    if config.moe is not None:
        out, _ = moe_lib.moe_mlp_apply(layer_params["mlp"], y, config.moe)
        return out, None
    return transformer.mlp_apply(layer_params["mlp"], y, config, rules), None


def _mlp_scope(config):
    """The program scope of what stands around a block's MLP (the
    residual adds, the norm before it): the scope of the norm's first
    reader, so that a fusion of them goes by a neighbour's name."""
    return "mlp" if config.moe is None else "moe_route"


def _stacks(params, config):
    """The layer stacks in order, each ``(stacked params, its
    configuration, its first layer's index)``: the leading dense layers,
    where the model has them, then the scanned ``params["layers"]``."""
    dense = config.leading_dense_layers
    if not dense:
        return [(params["layers"], config, 0)]
    return [(params["dense_layers"], config.dense_stack, 0),
            (params["layers"], config, dense)]


def _split_experts(stack_params, config):
    """``stack_params`` as ``(what a scan over the layers slices, the
    routed experts' stacked matrices)``: a dropless expert layer's three
    stacks stay OUT of the scan's operands and go to the grouped products
    whole, with the layer's index — sliced out a layer, each would be
    copied (352 MB) before every call.  ``(stack_params, None)`` for any
    other stack."""
    if not moe_lib.counts_routing(config.moe):
        return stack_params, None
    mlp = stack_params["mlp"]
    held = {name: mlp[name] for name in moe_lib.EXPERT_LEAVES}
    rest = {name: leaf for name, leaf in mlp.items() if name not in held}
    return dict(stack_params, mlp=rest), held


def _sum_routing(counted):
    """One routing vector for a program from what its expert stacks
    counted a layer (``counted``: [layers, ...] arrays, or None)."""
    return sum(c.reshape(-1, c.shape[-1]).sum(0) for c in counted
               if c is not None)


def _paged_attended(kind, q, cache_l, cur_len, paged):
    """Route one attention through ``ops.paged_attention`` (the
    read-in-place path).  ``paged`` holds its keywords: the layer to
    read when ``cache_l`` holds the stacked leaves, the per-layer pool
    slice, the block table, and the dispatch knobs; KV writes stay in
    the slot row (suffix positions never overlap pool-backed pages —
    prefix hits are block-aligned), so only the READ side changes."""
    from cloud_tpu import ops

    fn = {
        "decode": ops.paged_decode_attention,
        "chunk": ops.paged_chunk_attention,
        "verify": ops.paged_verify_attention,
    }[kind]
    return fn(q, cache_l, cur_len, **paged)


def _scan_layers(params, cache, x, positions, write_cols, config, rules,
                 mesh, *, kind, slot=None, pool=None, block_table=None,
                 use_pallas=None, with_routing: bool = False):
    """The layer stack over a KV cache that rides the scan as the CARRY.

    The one spelling of "scan the layers with the cache carried", shared
    by :func:`_decode_step` (``kind="decode"``),
    :func:`prefill_chunk_program` (``"chunk"``) and
    :func:`verify_chunk_program` (``"verify"``).  The scan runs over
    ``(params["layers"], layer index)`` and carries ``(x, cache)``:
    layer ``l`` scatters its new k/v (and, for an int8 cache, the
    scales) straight into the stacked ``[L, B, S, H, hd]`` leaves at
    ``[l, row, write_cols]``, then attends over layer ``l`` of the
    carried leaves, read by index.  The cache is never a scan ``xs`` /
    ``ys`` operand (XLA would slice every layer out, stack it into a
    fresh whole-cache buffer and copy that back), so the update is in
    place in the donated buffer.

    ``x`` is [B, T, D] with ``positions`` [B, T] the tokens' absolute
    (consecutive) cache positions; ``write_cols`` [B, T] is where each
    token's k/v lands, and an out-of-range entry SUPPRESSES that write
    (drop-mode scatter; how the slot grid keeps inactive rows from
    stomping a frozen position).  Write comes before attend, so a
    query sees its own key: key j of row i is valid for query t iff
    ``j <= positions[i, t]``.  ``slot`` (a traced scalar, with B == 1)
    makes that one row of the grid both the write row and the only row
    attended over; ``None`` means x's rows ARE the cache's rows.

    The decode read (``kind="decode"``, ``slot=None``, K/V not int8)
    goes through the paged path (:func:`_paged_attended`) whenever its
    kernel would run: on a TPU, for every eligible shape, with nothing
    to switch it on.
    It takes the carried leaves WHOLE with the layer index, fetches only
    the pages that hold a row's tokens, and a row that does not advance
    (its write suppressed) is given length 0: nothing of it is read,
    and its output lanes, zeros, are masked by whoever suppressed the
    write.  Off the chip the read is :func:`_cache_attention` over layer
    ``l`` read by index.  ``block_table`` routes every kind's read
    through the paged path, with ``pool`` scanned alongside as a
    read-only ``xs`` operand; for the chunk and verify kinds the layer
    (or the slot's row of it) is still sliced out per layer — never
    stacked back.  Returns ``(x, cache)``.

    With ``config.ssm`` (``kind="decode"`` only: one token a row) the
    carried cache also holds each row's recurrent state; layer ``l``
    reads it, advances it by the token and writes it back at ``[l]``.
    A row whose K/V write is suppressed is FROZEN: its state and its
    convolution's tail come back bit for bit (the drop-mode scatter has
    no equivalent for a leaf without positions).  Wherever its kernel
    would run (``ops.ssm_state.takes_kernel``: a TPU, a float32 state in
    whole tiles) the ``ssm`` leaf is taken WHOLE with the layer index and
    the live rows, like K and V, and advanced in place: a frozen row's
    state is neither fetched nor written.  Elsewhere, and for the
    convolution's tail everywhere, the layer is read by index, advanced,
    and a frozen row rewritten with its own bytes.

    With ``config.latent`` (``kind="decode"`` only) the cache is the
    latent leaf: layer ``l`` scatters each row's ``[c | k_pe]`` at
    ``[l, row, write_cols]`` and attends in the ABSORBED form
    (``models/mla.py``) through ``ops.latent_attention``, which takes the
    carried leaf whole with the layer index and reads a live row's pages
    once for all heads (its kernel on a TPU, its jnp route elsewhere).

    A model with leading dense layers runs them first, then the scan over
    the expert layers (:func:`_stacks`); cache layer ``l`` is model layer
    ``l``.  ``with_routing`` appends what the dropless expert layers
    counted (``moe.ROUTING_HEAD``), summed over layers, idle rows left
    out: ``(x, cache, routing)``.
    """
    from cloud_tpu.ops import latent_attention, paged_attention, ssm_state

    b, t, _ = x.shape
    if kind != "decode" or slot is not None or block_table is not None:
        _refuse_unless_kv_rows(
            config, f"a {kind} pass over a slot's rows or a prefix pool")
    quantized = "k_scale" in cache
    attend_len = positions[:, 0] + 1
    chunked = kind != "decode"
    rows = (jnp.arange(b)[:, None] if slot is None
            else jnp.reshape(slot, (1, 1)))
    # (An int8 cache keeps the per-layer operand: its scale leaves are
    # re-laid-out whole for the kernel, so the less of them the better.)
    in_place = kind == "decode" and slot is None and not quantized
    paged = {"block_table": block_table, "use_pallas": use_pallas,
             "partitioned": mesh is not None, "mesh": mesh,
             "head_axes": rules.assignment("heads"),
             "batch_axes": rules.assignment("batch")}
    # Rows that advance (their write column is in range).
    live = (write_cols[:, 0] >= 0) & (
        write_cols[:, 0] < _rows_leaf(cache).shape[2])
    state_in_place = config.ssm is not None and ssm_state.takes_kernel(
        cache["ssm"], config.ssm.num_groups, use_pallas)

    def layer_of(leaf, l):
        if slot is None:
            return jax.lax.dynamic_index_in_dim(leaf, l, keepdims=False)
        zero = jnp.int32(0)
        return jax.lax.dynamic_slice(
            leaf, (l, slot, zero, zero, zero), (1, 1) + leaf.shape[2:]
        )[0]

    def latent_attend(att, y, cache, l):
        q_nope, q_pe, c, k_pe = mla_lib.project(att, y, positions, config)
        with layers.scope("cache_write"):
            cache = dict(cache, **{LATENT_LEAF: cache[LATENT_LEAF].at[
                l, rows, write_cols].set(
                    mla_lib.cache_rows(c, k_pe, config.latent, config.dtype),
                    mode="drop")})
        queries = mla_lib.absorbed_queries(att, q_nope[:, 0], q_pe[:, 0],
                                           config)
        with layers.scope("attn_read"):
            o_lat = latent_attention.latent_decode_attention(
                queries, cache[LATENT_LEAF], jnp.where(live, attend_len, 0),
                value_dim=config.latent.kv_rank,
                scale=mla_lib.softmax_scale(config.latent), layer=l)
        attended = mla_lib.absorbed_values(att, o_lat, config)
        return mla_lib.attention_out(att, attended[:, None], config), cache

    def kv_attend(layer_params, layer_slice, y, cache, l):
        q, k_new, v_new = transformer.qkv_project(
            layer_params["att"], y, positions, config
        )
        with layers.scope("cache_write"):
            updates = _kv_leaf_updates(k_new, v_new, config, quantized)
            cache = dict(cache, **{
                name: cache[name].at[l, rows, write_cols].set(update,
                                                              mode="drop")
                for name, update in updates.items()
            })
        kv = {name: cache[name] for name in updates}
        pool_l = layer_slice[2] if pool is not None else None
        take_kernel = (paged_attention.would_use_kernel(q, kv)
                       if use_pallas is None else use_pallas)
        with layers.scope("attn_read"):
            if in_place and (block_table is not None or take_kernel):
                attended = _paged_attended(
                    kind, q, kv, jnp.where(live, attend_len, 0),
                    dict(paged, layer=l, pool_l=pool_l))
            else:
                cache_l = {name: layer_of(leaf, l)
                           for name, leaf in kv.items()}
                attended = (
                    _cache_attention(q, cache_l, attend_len,
                                     chunk_causal=chunked)
                    if block_table is None else
                    _paged_attended(kind, q, cache_l, attend_len,
                                    dict(paged, pool_l=pool_l)))
        return transformer.attention_out(layer_params["att"], attended,
                                         config), cache

    def layer_body(stack_config, held, first, carry, layer_slice):
        x, cache = carry
        layer_params, l = layer_slice[:2]
        with layers.scope("attn_proj"):
            y = layers.rmsnorm_apply(layer_params["ln1"], x,
                                     eps=config.norm_eps)
        if config.latent is not None:
            mixed, cache = latent_attend(layer_params["att"], y, cache, l)
        else:
            mixed, cache = kv_attend(layer_params, layer_slice, y, cache, l)
        if config.ssm is not None:
            mixer = (layer_params["ssm"], y[:, 0])
            sizes = (config.ssm, config.multipliers, config.norm_eps)
            with layers.scope(_STATE_SCOPES["conv"]):
                held = {"conv": jax.lax.dynamic_index_in_dim(
                    cache["conv"], l, keepdims=False)}
            if state_in_place:
                ssm_out, cache["ssm"], tail = ssm_lib.ssm_step_in_place(
                    *mixer, cache["ssm"], l, live, held["conv"], *sizes)
                fresh = {"conv": tail}
            else:
                with layers.scope(_STATE_SCOPES["ssm"]):
                    held["ssm"] = jax.lax.dynamic_index_in_dim(
                        cache["ssm"], l, keepdims=False)
                ssm_out, state, tail = ssm_lib.ssm_step(
                    *mixer, held["ssm"], held["conv"], *sizes)
                fresh = {"ssm": state, "conv": tail}
            for name, new in fresh.items():
                with layers.scope(_STATE_SCOPES[name]):
                    keep = live.reshape((b,) + (1,) * (new.ndim - 1))
                    new = jnp.where(keep, new.astype(held[name].dtype),
                                    held[name])
                    cache[name] = jax.lax.dynamic_update_index_in_dim(
                        cache[name], new, l, axis=0)
            with layers.scope(_STATE_SCOPES["conv"]):
                mixed = mixed + ssm_out[:, None]
        with layers.scope(_mlp_scope(stack_config)):
            x = x + mixed
            y = layers.rmsnorm_apply(layer_params["ln2"], x,
                                     eps=config.norm_eps)
        mlp_out, counted = _mlp(
            layer_params, y, stack_config, rules,
            live=live[:, None] if kind == "decode" else None, held=held,
            layer=l - first)
        with layers.scope(_mlp_scope(stack_config)):
            x = x + mlp_out
        if chunked:
            x = shard_constraint(x, "batch", "seq", "act_embed",
                                 rules=rules, mesh=mesh)
        return (x, cache), counted

    counted = []
    for stack_params, stack_config, first in _stacks(params, config):
        last = first + jax.tree_util.tree_leaves(stack_params)[0].shape[0]
        scanned, held = _split_experts(stack_params, stack_config)
        xs = (scanned, jnp.arange(first, last))
        if pool is not None:
            xs += (pool if last - first == config.num_layers else
                   jax.tree_util.tree_map(lambda leaf: leaf[first:last],
                                          pool),)
        (x, cache), per_layer = jax.lax.scan(
            functools.partial(layer_body, stack_config, held, first),
            (x, cache), xs)
        counted.append(per_layer)
    if with_routing:
        return x, cache, _sum_routing(counted)
    return x, cache


def _prefill_layer(layer_params, x, positions, prompt_mask, prompt_lens,
                   config, rules, mesh, held=None, layer=None):
    """One block on the full prompt buffer [B, T, D], returning what the
    block leaves in a cache, raw: its k/v and, with ``config.ssm``, each
    row's state and convolution tail at its last real token.  Causal
    attention told the rows' lengths (``ops.flash_attention``: the
    kernel does no work past a length and leaves zeros there, the
    reference masks key-side; padded tail slots are later overwritten by
    decode before they can ever be attended); the mixer masks the padding
    itself (``ssm.ssd_prefill``).  With
    ``config.latent`` the block leaves each token's latent row and attends
    in the EXPANDED form (``mla.expanded_attention``).  Also returns what
    a dropless expert layer counted of its routing (else None), padding
    left out."""
    from cloud_tpu import ops

    with layers.scope("attn_proj"):
        y = layers.rmsnorm_apply(layer_params["ln1"], x,
                                 eps=config.norm_eps)
    if config.latent is not None:
        att = layer_params["att"]
        q_nope, q_pe, c, k_pe = mla_lib.project(att, y, positions, config)
        attended = mla_lib.expanded_attention(
            att, q_nope, q_pe, c, k_pe, prompt_lens, config, rules=rules,
            mesh=mesh)
        mixed = mla_lib.attention_out(att, attended, config)
        with layers.scope("cache_write"):
            left = {LATENT_LEAF: mla_lib.cache_rows(c, k_pe, config.latent,
                                                    config.dtype)}
    else:
        q, k, v = transformer.qkv_project(layer_params["att"], y, positions,
                                          config)
        with layers.scope("attn_read"):
            attended = ops.flash_attention(
                q, *transformer.repeat_kv(k, v, config), causal=True,
                lengths=prompt_lens, partitioned=mesh is not None,
                mesh=mesh, batch_axes=rules.assignment("batch"),
                head_axes=rules.assignment("heads"),
            )
        mixed = transformer.attention_out(layer_params["att"], attended,
                                          config)
        left = {"k": k, "v": v}
    if config.ssm is not None:
        ssm_out, left["ssm"], left["conv"] = ssm_lib.ssd_prefill(
            layer_params["ssm"], y, prompt_mask, prompt_lens, config.ssm,
            config.multipliers, config.norm_eps,
        )
        mixed = mixed + ssm_out
    with layers.scope(_mlp_scope(config)):
        x = x + mixed
        y = layers.rmsnorm_apply(layer_params["ln2"], x,
                                 eps=config.norm_eps)
    mlp_out, counted = _mlp(layer_params, y, config, rules,
                            live=prompt_mask, held=held, layer=layer)
    with layers.scope(_mlp_scope(config)):
        x = x + mlp_out
    x = shard_constraint(x, "batch", "seq", "act_embed", rules=rules,
                         mesh=mesh)
    return x, (left, counted)


def _final_logits(params, x, config):
    with layers.scope("head"):
        x = layers.rmsnorm_apply(params["ln_f"], x, eps=config.norm_eps)
    return transformer.lm_logits(params, x, config)


def _prefill_forward(params, prompt_tokens, prompt_lens, config, rules,
                     mesh, with_routing: bool = False):
    """The prompt forward pass alone: what every layer leaves in a
    cache, stacked and raw (pre-cast) — ``k`` / ``v``
    [L, B, T_prompt, kv_heads, hd] and, with ``config.ssm``, ``ssm``
    [L, B, H, P, N] and ``conv`` [L, B, W - 1, conv_dim] at each row's
    last real token — plus the next-token logits [B, V] at that
    position.  Where they land is the caller's business
    (:func:`_write_prefill`): :func:`_prefill` writes them at the origin
    of a fresh batch cache, :func:`insert_slot_program` into one row of
    a persistent slot grid.  A latent-attention model leaves ``latent``
    [L, B, T_prompt, row_width] in place of ``k`` / ``v``.
    ``with_routing`` appends what the dropless expert layers counted."""
    b, t_prompt = prompt_tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t_prompt), (b, t_prompt))
    prompt_mask = (positions < prompt_lens[:, None]).astype(jnp.int32)
    x = transformer.embed_tokens(params, prompt_tokens, config, rules, mesh)
    x = shard_constraint(x, "batch", "seq", "act_embed", rules=rules,
                         mesh=mesh)

    def prefill_body(stack_config, held, x, layer_slice):
        layer_params, *layer = layer_slice
        return _prefill_layer(layer_params, x, positions, prompt_mask,
                              prompt_lens, stack_config, rules, mesh,
                              held, *layer)

    lefts, counted = [], []
    for stack_params, stack_config, _ in _stacks(params, config):
        scanned, held = _split_experts(stack_params, stack_config)
        xs = (scanned,)
        if held is not None:
            xs += (jnp.arange(held["wi"].shape[0]),)
        x, (left, per_layer) = jax.lax.scan(
            functools.partial(prefill_body, stack_config, held), x, xs)
        lefts.append(left)
        counted.append(per_layer)
    with layers.scope("cache_write"):
        left = lefts[0] if len(lefts) == 1 else jax.tree_util.tree_map(
            lambda *parts: jnp.concatenate(parts, axis=0), *lefts)
    with layers.scope("head"):
        last_idx = (prompt_lens - 1)[:, None, None]
        last_x = jnp.take_along_axis(
            x, jnp.broadcast_to(last_idx, (b, 1, x.shape[-1])), axis=1
        )
    logits0 = _final_logits(params, last_x, config)[:, 0]
    # Sampling boundary: the one place the sharded generation path
    # resharding happens.  Under a tp mesh lm_logits comes back
    # vocab-sharded; argmax/categorical need the full row, so gather it
    # HERE (once per forward) and nowhere else.  No-op without a mesh.
    logits0 = shard_constraint(logits0, "batch", None, rules=rules,
                               mesh=mesh)
    if with_routing:
        return left, logits0, _sum_routing(counted)
    return left, logits0


def _kv_leaf_updates(k_raw, v_raw, config, quantized: bool):
    """Cache-leaf update arrays for raw (pre-cast) k/v activations:
    ``{"k", "v"}`` cast to the cache dtype, plus int8 + per-(position,
    head) scales when the cache is quantized.  The one spelling of
    "turn activations into cache bytes", shared by every cache writer —
    batch prefill (:func:`_write_prefill`), slot insert, and the
    chunk-prefill scatter (:func:`prefill_chunk_program`)."""
    if quantized:
        k_q, k_sc = _quantize_kv(k_raw)
        v_q, v_sc = _quantize_kv(v_raw)
        return {"k": k_q, "k_scale": k_sc, "v": v_q, "v_scale": v_sc}
    return {"k": k_raw.astype(config.dtype),
            "v": v_raw.astype(config.dtype)}


def _write_prefill(cache, left, start, config):
    """Write what a prefill left (:func:`_prefill_forward`) into
    ``cache`` at the 5-D ``start`` index ``(layer, row, 0, 0, 0)``: the
    k/v stacks (quantizing first when the cache is int8) and, where the
    cache holds them, each row's state and convolution tail WHOLE (a
    prefill starts at position 0, so the same index, cut to the leaf's
    rank, addresses a state leaf's layer and row, and a latent leaf's
    layer, row and position)."""
    with layers.scope("cache_write"):
        if LATENT_LEAF in cache:
            updates = {LATENT_LEAF: left[LATENT_LEAF].astype(
                cache[LATENT_LEAF].dtype)}
        else:
            updates = _kv_leaf_updates(left["k"], left["v"], config,
                                       "k_scale" in cache)
        for name in STATE_LEAVES:
            if name in cache:
                updates[name] = left[name].astype(cache[name].dtype)
        for name, val in updates.items():
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], val, start[:val.ndim])
    return cache


#: Rows of a prefill's row tile: the grain in which the prompt's length,
#: not the buffer's, bounds a prefill's work.  A tile of ``r`` rows costs
#: ``2 r P`` operations against ``2 P`` bytes of a layer's weights, so
#: below ``r`` = 197e12 / 819e9 = 240 rows (a v5e's peaks) a tile is
#: bound by reading the weights and skipping rows buys nothing; 256 sits
#: on that edge and 512 is compute-bound by two to one.  Derived from
#: nothing a user sets, so it is no option.
PREFILL_TILE_ROWS = 512


def prefill_widths(t_prompt: int, rules: ShardingRules = DEFAULT_RULES,
                   mesh=None):
    """The widths, ascending, at which a prompt buffer of ``t_prompt``
    rows can run its forward pass: the whole tiles above half the
    buffer (whoever buckets prompts sends a shorter one to the buffer
    below, so smaller widths would only be copies of the stack that
    nothing reaches).  A buffer that is not whole tiles, or holds fewer
    than two, has one width: its own.  So has every buffer under a mesh
    that shards ``seq``: a width that is not the buffer's fights that
    sharding."""
    tile = PREFILL_TILE_ROWS
    if t_prompt % tile or t_prompt < 2 * tile or _shards_seq(rules, mesh):
        return (t_prompt,)
    return tuple(range((t_prompt // 2 // tile + 1) * tile, t_prompt + 1,
                       tile))


def prefill_rows_computed(t_prompt: int, prompt_len: int,
                          rules: ShardingRules = DEFAULT_RULES,
                          mesh=None) -> int:
    """The rows of a ``t_prompt`` buffer that a prefill computes for a
    longest prompt of ``prompt_len`` tokens: the rule
    :func:`_prefill_into` applies, for whoever counts its work."""
    return next(w for w in prefill_widths(t_prompt, rules, mesh)
                if w >= min(prompt_len, t_prompt))


def prefill_flash_tiles(config, t_prompt: int, prompt_len: int,
                        rules: ShardingRules = DEFAULT_RULES, mesh=None):
    """``(run, width)``: the compute tiles the flash forward kernel runs a
    head a layer for a prompt of ``prompt_len`` tokens in a ``t_prompt``
    buffer, and the tiles of the whole causal triangle at the width the
    prefill runs it at (:func:`prefill_rows_computed`).  The kernel's own
    count (``ops.flash_attention.forward_tiles``) at the shapes
    :func:`_prefill_layer` hands it, for whoever counts its work; zeros
    where the prompt's attention is not the kernel's."""
    from cloud_tpu.ops.flash_attention import forward_tiles, takes_kernel

    width = prefill_rows_computed(t_prompt, prompt_len, rules, mesh)
    latent = config.latent
    d, dv = ((config.head_dim,) * 2 if latent is None
             else (latent.qk_dim, latent.v_dim))
    dtype = jnp.dtype(config.dtype)
    qk = jax.ShapeDtypeStruct((1, width, config.num_heads, d), dtype)
    v = jax.ShapeDtypeStruct((1, width, config.num_heads, dv), dtype)
    if not takes_kernel(qk, qk, v):
        return 0, 0
    sizes = dict(head_dim=d, value_dim=dv, itemsize=dtype.itemsize)
    return (forward_tiles(width, min(prompt_len, width), **sizes),
            forward_tiles(width, **sizes))


def _shards_seq(rules, mesh) -> bool:
    """Whether ``rules`` split the ``seq`` axis over more than one
    device of ``mesh``."""
    if mesh is None:
        return False
    axes = rules.assignment("seq")
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    return any(dict(mesh.shape).get(axis, 1) > 1 for axis in axes)


def _prefill_into(params, cache, prompt_tokens, prompt_lens, start, config,
                  rules, mesh, with_routing: bool = False):
    """The prompt forward pass (:func:`_prefill_forward`) written into
    ``cache`` at ``start`` (:func:`_write_prefill`), doing the prompt's
    work and not the buffer's: it runs at the smallest of
    :func:`prefill_widths` that holds the longest prompt, chosen on the
    device from the traced lengths (``lax.switch`` over static widths,
    so one executable still serves a buffer).  Rows at and past that
    width do no embedding-to-logits work — no projection, no MLP, no
    attention as query or key — and nothing is written for them: the
    cache keeps what it held there, stale and harmless.  Rows before it
    get the whole buffer's values (a causal stack never looks ahead).

    A buffer with one width (every buffer under a mesh that shards
    ``seq`` among them) traces to the whole-buffer program and nothing
    else.  Returns ``(cache, logits0)``, with ``with_routing`` also what
    the dropless expert layers counted."""
    widths = prefill_widths(prompt_tokens.shape[1], rules, mesh)

    def at(width):
        def forward(cache):
            left, *out = _prefill_forward(
                params, prompt_tokens[:, :width], prompt_lens, config,
                rules, mesh, with_routing=with_routing)
            return (_write_prefill(cache, left, start, config), *out)
        return forward

    if len(widths) == 1:
        return at(widths[0])(cache)
    longest = jnp.max(prompt_lens)
    index = sum((longest > w).astype(jnp.int32) for w in widths[:-1])
    return jax.lax.switch(index, [at(w) for w in widths], cache)


def _prefill(params, prompt_tokens, prompt_lens, config, s, rules, mesh,
             kv_quant: bool = False):
    """One forward over the prompt buffer (:func:`_prefill_into`):
    returns the KV cache (size ``s``, positions [0, prompt_len) filled)
    and the next-token logits [B, V] at each row's last real prompt
    position — shared by sampling and beam decoding."""
    b, _ = prompt_tokens.shape
    cache = _init_cache(config, b, s, rules, mesh, kv_quant=kv_quant)
    return _prefill_into(params, cache, prompt_tokens, prompt_lens,
                         (0, 0, 0, 0, 0), config, rules, mesh)


def _decode_step(params, cache, token, cur_len, config, rules, mesh,
                 write_pos=None, pool=None, block_table=None,
                 use_pallas=None, with_routing: bool = False):
    """One single-token decode step for every row at once: embed
    ``token`` [B], run the layer stack with the cache carried
    (:func:`_scan_layers`: each row's k/v written in place at its
    ``cur_len``, then attended over the whole valid prefix including
    the just-written position), return the updated cache and the
    next-token logits [B, V].  The shared inner loop of
    :func:`_decode_tokens`, :func:`beam_search`,
    :func:`decode_chunk_program` and :func:`draft_chunk_program`.

    ``write_pos`` overrides the write index per row; an out-of-range
    entry SUPPRESSES that row's write (drop-mode scatter).  The chunk
    scheduler uses it to keep inactive slots from stomping their frozen
    position — a row mid-way through a chunked prefill holds real KV
    there (see ``decode_chunk_program``).

    ``block_table`` [B, n_pages] (with the optional prefix ``pool``)
    routes attention through the paged read-in-place path.
    ``with_routing`` appends what the dropless expert layers counted."""
    x = transformer.embed_tokens(params, token[:, None], config, rules,
                                 mesh)
    wp = cur_len if write_pos is None else write_pos
    x, cache, *routing = _scan_layers(
        params, cache, x, cur_len[:, None], wp[:, None], config, rules,
        mesh, kind="decode", pool=pool, block_table=block_table,
        use_pallas=use_pallas, with_routing=with_routing,
    )
    logits = _final_logits(params, x, config)[:, 0]
    # Sampling boundary reshard (see _prefill_forward): vocab-sharded
    # logits gather to replicated exactly once per decode step.
    logits = shard_constraint(logits, "batch", None, rules=rules,
                              mesh=mesh)
    return (cache, logits, *routing)


def _decode_tokens(params, cache, logits0, prompt_lens, config, *,
                   max_new_tokens, sample, rng, rules, mesh):
    """The scan-decode half of :func:`generate`: from a filled KV cache
    and the prefill's next-token logits to ``(tokens, num_generated)``.

    Split out so the serving engine (``cloud_tpu.serving``) can dispatch
    prefill and decode as separately-compiled — and separately-spanned —
    programs; :func:`generate` composes the two plus the sequence
    stitching.  ``tokens`` is [B, max_new_tokens] (eos included where
    sampled, pad in every slot after it); ``num_generated`` counts the
    generated tokens per row, eos included.
    """
    b = logits0.shape[0]
    rng, step_rng = jax.random.split(rng)
    track_seen = sample.repetition_penalty != 1.0
    # Static gate: the allow-eos masking only enters the compiled loop
    # when min_new_tokens actually constrains something.
    need_min = sample.eos_id is not None and sample.min_new_tokens > 0
    allow0 = jnp.full((b,), False) if need_min else None
    tok0 = sample_logits(
        step_rng, logits0, sample, allow_eos=allow0
    ).astype(jnp.int32)
    rows_b = jnp.arange(b)
    seen0 = (
        jnp.zeros((b, config.vocab_size), bool).at[rows_b, tok0].set(True)
        if track_seen else jnp.zeros((), bool)  # static dummy carry slot
    )

    # --- decode: one lax.scan over max_new_tokens steps ---
    # ``post_eos`` marks tokens STRICTLY after an eos: the eos itself is a
    # real emitted token; later slots are pads whose compute is discarded.
    def step(carry, i):
        cache, cur_len, token, post_eos, seen, rng = carry
        cache, logits = _decode_step(
            params, cache, token, cur_len, config, rules, mesh
        )
        rng, step_rng = jax.random.split(rng)
        # This step samples generated-token index i+1.
        allow = (
            jnp.full((b,), i + 1 >= sample.min_new_tokens)
            if need_min else None
        )
        next_tok = sample_logits(
            step_rng, logits, sample,
            seen=seen if track_seen else None, allow_eos=allow,
        ).astype(jnp.int32)
        done = post_eos
        if sample.eos_id is not None:
            done = post_eos | (token == sample.eos_id)
        next_tok = jnp.where(done, jnp.int32(sample.pad_id), next_tok)
        if track_seen:
            # Unconditional: done rows only ever produce pad_id, whose
            # seen bit is unobservable (their sampling is discarded).
            seen = seen.at[rows_b, next_tok].set(True)
        cur_len = cur_len + jnp.where(post_eos, 0, 1)
        emitted = jnp.where(post_eos, jnp.int32(sample.pad_id), token)
        return (
            cache, cur_len, next_tok, done, seen, rng
        ), emitted

    # N-1 scan steps: step i consumes carried token i and samples token
    # i+1, so the last carried token needs no forward pass of its own —
    # it is emitted (and counted) directly from the final carry.  (With
    # max_new_tokens=1 the scan body never runs; tok0 came from prefill.)
    carry0 = (cache, prompt_lens, tok0,
              jnp.zeros((b,), bool), seen0, rng)
    (_, cur_len, last_tok, last_post, _, _), emitted = jax.lax.scan(
        step, carry0, jnp.arange(max_new_tokens - 1)
    )
    final_emit = jnp.where(last_post, jnp.int32(sample.pad_id), last_tok)
    final_len = cur_len + jnp.where(last_post, 0, 1)
    if max_new_tokens > 1:
        tokens = jnp.concatenate([emitted.T, final_emit[:, None]], axis=1)
    else:
        tokens = final_emit[:, None]
    return tokens, final_len - prompt_lens


def generate(
    params,
    prompt_tokens: jnp.ndarray,
    prompt_lens: jnp.ndarray,
    config: transformer.TransformerConfig,
    *,
    max_new_tokens: int,
    sample: SampleConfig = SampleConfig(temperature=0.0),
    rng: Optional[jax.Array] = None,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
    kv_quant: bool = False,
) -> Dict[str, Any]:
    """Generate ``max_new_tokens`` continuations for a batch of prompts.

    Args:
      prompt_tokens: [B, T_prompt] left-aligned token ids (rows shorter
        than T_prompt padded arbitrarily on the right).
      prompt_lens: [B] actual prompt lengths (1 <= len <= T_prompt).
      max_new_tokens: static decode trip count.
      sample: sampling configuration; default greedy.
      rng: PRNG key (required unless greedy).
      kv_quant: store the KV cache int8 with per-(position, head)
        scales (_init_cache docstring) — the long-context decode
        bandwidth knob; combine with int8 weights
        (models/quantization.py) for fully-narrow decoding.

    Returns dict with:
      ``tokens``: [B, max_new_tokens] generated ids — eos included where
        sampled, pad in every slot after it,
      ``sequences``: [B, T_prompt + max_new_tokens] prompt + generation
        stitched at each row's true length (pad elsewhere),
      ``num_generated``: [B] count of generated tokens including the eos.
    """
    mesh = mesh if mesh is not None else mesh_lib.get_global_mesh()
    _check_inference_supported(config, rules, mesh, "generation")
    if sample.temperature != 0.0 and rng is None:
        raise ValueError("non-greedy sampling needs an rng key")
    rng = jax.random.PRNGKey(0) if rng is None else rng

    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    b, t_prompt = prompt_tokens.shape
    # Documented domain is 1 <= len <= T_prompt; out-of-range lengths
    # would make last_idx negative (gather/scatter wrap silently under
    # jit) — clamp rather than corrupt.
    prompt_lens = jnp.clip(prompt_lens.astype(jnp.int32), 1, t_prompt)
    if max_new_tokens == 0:
        cols = jnp.arange(t_prompt)[None, :]
        return {
            "tokens": jnp.zeros((b, 0), jnp.int32),
            "sequences": jnp.where(
                cols < prompt_lens[:, None], prompt_tokens.astype(jnp.int32),
                jnp.int32(sample.pad_id),
            ),
            "num_generated": jnp.zeros((b,), jnp.int32),
        }
    s = t_prompt + max_new_tokens
    cache, logits0 = _prefill(params, prompt_tokens, prompt_lens, config,
                              s, rules, mesh, kv_quant=kv_quant)
    tokens, num_generated = _decode_tokens(
        params, cache, logits0, prompt_lens, config,
        max_new_tokens=max_new_tokens, sample=sample, rng=rng,
        rules=rules, mesh=mesh,
    )

    # Stitch prompt + generation at each row's true offset.  ``tokens`` is
    # already pad-masked past the eos, so the scatter needs no validity
    # gating.
    cols = jnp.arange(t_prompt)[None, :]
    prompt_clean = jnp.where(
        cols < prompt_lens[:, None], prompt_tokens.astype(jnp.int32),
        jnp.int32(sample.pad_id),
    )
    sequences = jnp.concatenate(
        [prompt_clean,
         jnp.full((b, max_new_tokens), sample.pad_id, jnp.int32)],
        axis=1,
    )
    gen_cols = prompt_lens[:, None] + jnp.arange(max_new_tokens)[None, :]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], gen_cols.shape)
    sequences = sequences.at[rows, gen_cols].set(tokens)
    return {
        "tokens": tokens,
        "sequences": sequences,
        "num_generated": num_generated,
    }


# --------------------------------------------------------------------------
# Continuous batching: slot-grid programs (the ``cloud_tpu.serving``
# iteration-level scheduler).  The unit of work is no longer a batch of
# requests but a persistent grid of ``num_slots`` decode slots over a
# static ``max_len`` KV cache: requests are prefilled INTO a free slot at
# their own bucket length (:func:`insert_slot_program`), decode advances
# every active slot by ``chunk_size`` tokens per dispatch
# (:func:`decode_chunk_program`), and a slot that finishes — per-slot
# ``max_new_tokens`` exhausted, or eos sampled — simply goes inactive
# mid-chunk and is refilled by the host between chunks.  Greedy outputs
# are token-for-token identical to :func:`generate` (same
# :func:`_decode_step`, same sampling order; the only dropped work is
# the forward pass generate() runs on post-finish pad tokens, which
# never influences emitted tokens).


def init_slot_cache(config, num_slots: int, max_len: int, *,
                    rules: ShardingRules = DEFAULT_RULES, mesh=None,
                    kv_quant: bool = False):
    """The persistent decode grid: a zeroed KV cache with ``num_slots``
    batch rows of ``max_len`` positions (``max_len`` must cover the
    largest prompt bucket plus the engine-wide ``max_new_tokens``).
    Allocated once per engine and carried through every insert/chunk
    program — slot reuse overwrites in place, never reallocates."""
    return _init_cache(config, num_slots, max_len, rules, mesh,
                       kv_quant=kv_quant)


def init_slot_state(config, num_slots: int, *,
                    sample: SampleConfig = SampleConfig(temperature=0.0)):
    """Per-slot scheduler state carried alongside the slot cache.

    ``pos`` — filled KV length (the next write index); ``tok`` — the
    last sampled, not-yet-consumed token; ``remaining`` — emissions this
    slot still owes; ``emitted`` — emissions so far (the
    ``min_new_tokens`` gate); ``active`` — whether the slot decodes.
    ``seen`` ([num_slots, vocab] bool) rides along only when the sample
    config applies a repetition penalty — the state pytree's structure
    is static per engine, so one chunk program serves the whole run.
    """
    state = {
        "pos": jnp.zeros((num_slots,), jnp.int32),
        "tok": jnp.full((num_slots,), sample.pad_id, jnp.int32),
        "remaining": jnp.zeros((num_slots,), jnp.int32),
        "emitted": jnp.zeros((num_slots,), jnp.int32),
        "active": jnp.zeros((num_slots,), bool),
    }
    if sample.repetition_penalty != 1.0:
        state["seen"] = jnp.zeros((num_slots, config.vocab_size), bool)
    return state


def insert_slot_program(
    params,
    cache,
    state,
    prompt_tokens: jnp.ndarray,
    prompt_len,
    slot,
    max_new_tokens,
    config: transformer.TransformerConfig,
    *,
    sample: SampleConfig = SampleConfig(temperature=0.0),
    rng: Optional[jax.Array] = None,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
):
    """Prefill one request into one slot of a live grid.

    ``prompt_tokens`` is a [1, bucket_len] padded prompt (the program
    specializes per bucket length — the compile grid is one insert
    program per prompt bucket, not per batch size); ``prompt_len`` /
    ``slot`` / ``max_new_tokens`` are traced int32 scalars, so one
    executable serves every slot and every per-request decode budget.
    The bucket decides the executable and the buffer, the prompt's
    length the work: the forward pass runs at the smallest of the
    buffer's widths that holds the prompt (:func:`_prefill_into`), all
    of them inside that one executable.  Writes the prompt's k/v into
    the slot's cache row, samples the first token from the prefill
    logits (exactly :func:`generate`'s ``tok0``), and arms the slot
    state: ``remaining = max_new_tokens - 1``, active unless the request
    is already finished (``max_new_tokens == 1`` or the first token
    sampled eos).  Stale cache beyond the new prompt — the rows of the
    width that ran hold the padding's k/v, the rows past it what the
    slot held before — is harmless: attention masks positions ``>= pos``
    and decode overwrites each position before it can become valid.
    That holds for K/V rows only: a recurrent state has no positions to
    mask, so the slot's state and convolution tail are overwritten WHOLE
    with the prompt's (a reused slot carries nothing over).  Returns
    ``(cache, state, first_token)`` and, for a model with dropless
    experts, a fourth result: what its expert layers counted of their
    routing over the prompt's real tokens (``moe.ROUTING_HEAD``).
    """
    t_prompt = prompt_tokens.shape[1]
    prompt_len = jnp.clip(jnp.asarray(prompt_len, jnp.int32), 1, t_prompt)
    lens = jnp.reshape(prompt_len, (1,))
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.int32(0)
    cache, logits0, *routing = _prefill_into(
        params, cache, prompt_tokens, lens, (zero, slot, zero, zero, zero),
        config, rules, mesh,
        with_routing=moe_lib.counts_routing(config.moe),
    )

    state, tok0 = _arm_slot(state, logits0, prompt_len, slot,
                            max_new_tokens, config, sample=sample, rng=rng)
    return (cache, state, tok0, *routing)


def _arm_slot(state, logits0, prompt_len, slot, max_new_tokens, config, *,
              sample: SampleConfig, rng):
    """Sample a just-prefilled slot's first token from its prefill
    logits (exactly :func:`generate`'s ``tok0``) and write the slot
    state — shared by :func:`insert_slot_program` (one-shot prefill) and
    :func:`finalize_slot_program` (the last chunk of a chunked
    prefill).  Returns ``(state, tok0)``."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    need_min = sample.eos_id is not None and sample.min_new_tokens > 0
    with layers.scope("head"):
        allow0 = jnp.full((1,), False) if need_min else None
        tok0 = sample_logits(
            rng, logits0, sample, allow_eos=allow0
        ).astype(jnp.int32)[0]

        max_new_tokens = jnp.asarray(max_new_tokens, jnp.int32)
        active0 = max_new_tokens > 1
        if sample.eos_id is not None:
            active0 = active0 & (tok0 != sample.eos_id)
        state = dict(state)
        state["pos"] = state["pos"].at[slot].set(prompt_len)
        state["tok"] = state["tok"].at[slot].set(tok0)
        state["remaining"] = state["remaining"].at[slot].set(
            max_new_tokens - 1)
        state["emitted"] = state["emitted"].at[slot].set(1)
        state["active"] = state["active"].at[slot].set(active0)
        if "seen" in state:
            row = jnp.zeros((config.vocab_size,), bool).at[tok0].set(True)
            state["seen"] = state["seen"].at[slot].set(row)
    return state, tok0


def decode_chunk_program(
    params,
    cache,
    state,
    config: transformer.TransformerConfig,
    *,
    chunk_size: int,
    sample: SampleConfig = SampleConfig(temperature=0.0),
    rng: Optional[jax.Array] = None,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
    pool=None,
    block_table=None,
    use_pallas=None,
    with_summary: bool = False,
):
    """Advance every active slot by up to ``chunk_size`` tokens.

    One ``lax.scan`` of ``chunk_size`` single-token steps over the whole
    grid (static shapes — ONE compile serves the entire serving run).
    Each step consumes every slot's carried token at its own ``pos``,
    samples the next, and emits it where the slot was active; a slot
    whose ``remaining`` hits zero or that samples eos deactivates
    *mid-chunk* and stops advancing (its residual lanes still flow
    through the compute — that is the static-shape price — but its
    ``pos`` freezes and its emissions are masked out).  Inactive slots
    contribute masked lanes only, and their cache writes are SUPPRESSED
    (drop-mode scatter at an out-of-range position): a slot mid-way
    through a chunked prefill already holds real prompt KV at its frozen
    position, so the old write-then-overwrite staleness argument no
    longer covers inactive rows.  The same out-of-range position FREEZES
    an inactive slot's recurrent state (:func:`_scan_layers`): a slot
    that finished mid-chunk, or waits empty, keeps its state bit for bit
    until an insert overwrites it.

    Returns ``(cache, state, tokens, valid)`` with ``tokens``/``valid``
    shaped [num_slots, chunk_size]: ``valid[s, i]`` marks a real
    emission (a prefix per row — slots only ever deactivate mid-chunk,
    reactivation happens between chunks via
    :func:`insert_slot_program`).

    ``block_table`` [num_slots, n_pages] (plus the prefix ``pool``)
    routes every step's attention through the paged read-in-place path
    (see :func:`_decode_step`); the defaults keep the trace
    byte-identical to the pre-paged program.

    ``with_summary=True`` appends a fifth result: a device int32
    ``[emitted_count, active_count]`` pair reduced from the emission
    mask inside the program, so a pipelined scheduler can learn a
    chunk's occupancy from a two-element host copy without
    materializing the full [num_slots, chunk_size] grids at dispatch
    time.  ``False`` (default) keeps the trace byte-identical to
    today's four-tuple.

    A model with dropless experts appends one more, last: what its expert
    layers counted of their routing (``moe.ROUTING_HEAD``), summed over
    the chunk's steps and layers, inactive slots left out — the engine
    reads it back with the tokens.
    """
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    num_slots = state["tok"].shape[0]
    rng = jax.random.PRNGKey(0) if rng is None else rng
    track_seen = sample.repetition_penalty != 1.0
    need_min = sample.eos_id is not None and sample.min_new_tokens > 0
    rows = jnp.arange(num_slots)
    with_routing = moe_lib.counts_routing(config.moe)

    def step(carry, step_rng):
        cache, state = carry
        active = state["active"]
        # Inactive slots write NOWHERE (out-of-range index -> drop-mode
        # scatter): their frozen position may hold a neighboring
        # occupant's real KV — a slot mid-way through a CHUNKED prefill
        # keeps its already-written prompt positions intact while the
        # grid decodes around it.  (Pre-chunked-prefill the write was
        # merely stale-but-harmless; now it would corrupt.)
        s = _rows_leaf(cache).shape[2]
        with layers.scope("head"):
            write_pos = jnp.where(active, state["pos"], jnp.int32(s))
        cache, logits, *routing = _decode_step(
            params, cache, state["tok"], state["pos"], config, rules, mesh,
            write_pos=write_pos, pool=pool, block_table=block_table,
            use_pallas=use_pallas, with_routing=with_routing,
        )
        with layers.scope("head"):
            allow = (
                state["emitted"] >= sample.min_new_tokens if need_min else None
            )
            tok = sample_logits(
                step_rng, logits, sample,
                seen=state["seen"] if track_seen else None, allow_eos=allow,
            ).astype(jnp.int32)
            tok = jnp.where(active, tok, jnp.int32(sample.pad_id))
            stride = active.astype(jnp.int32)
            new_state = dict(state)
            new_state["pos"] = state["pos"] + stride
            new_state["remaining"] = state["remaining"] - stride
            new_state["emitted"] = state["emitted"] + stride
            finished = new_state["remaining"] <= 0
            if sample.eos_id is not None:
                finished = finished | (tok == sample.eos_id)
            new_state["active"] = active & ~finished
            new_state["tok"] = jnp.where(active, tok, state["tok"])
            if track_seen:
                # Unconditional like _decode_tokens: inactive rows set the
                # pad bit in a row the next insert resets anyway.
                new_state["seen"] = state["seen"].at[rows, tok].set(True)
        return (cache, new_state), (tok, active, *routing)

    (cache, state), (toks, valid, *routing) = jax.lax.scan(
        step, (cache, state), jax.random.split(rng, chunk_size)
    )
    with layers.scope("head"):
        routing = [counted.sum(0) for counted in routing]
        if with_summary:
            summary = jnp.stack([
                valid.sum().astype(jnp.int32),
                state["active"].sum().astype(jnp.int32),
            ])
            return (cache, state, toks.T, valid.T, summary, *routing)
        return (cache, state, toks.T, valid.T, *routing)


# --------------------------------------------------------------------------
# Prefix caching + chunked prefill: the serving engine's prefill-side
# programs.  A prompt's KV for positions [0, n) depends only on the token
# ids at those positions (positions are absolute), so requests sharing a
# prefix can share its KV bytes: ``cloud_tpu.serving`` keeps a pool of
# KV *blocks* (:func:`init_prefix_pool`) keyed host-side by token-id
# prefixes, copies the longest cached prefix into a slot row
# (:func:`copy_prefix_program`), prefills only the uncached suffix in
# bounded chunks (:func:`prefill_chunk_program` — also the chunked-
# prefill primitive that keeps a long arrival from stalling in-flight
# decode), arms the slot from the final chunk's logits
# (:func:`finalize_slot_program`), and saves the prompt's new full
# blocks back to the pool (:func:`save_prefix_program`).  Greedy outputs
# stay token-identical to :func:`generate` — the chunk forward writes
# the same cache bytes and takes the same last-position logits as the
# one-shot prefill, just in pieces.


def init_prefix_pool(config, num_blocks: int, block_tokens: int, *,
                     rules: ShardingRules = DEFAULT_RULES, mesh=None,
                     kv_quant: bool = False):
    """The shared-prefix KV block pool: a zeroed cache pytree with
    ``num_blocks`` rows of ``block_tokens`` positions each (leaves
    [L, num_blocks, block_tokens, H, hd] — the same structure as the
    slot cache, so copies are per-leaf slicing).  Which block holds
    which token prefix is host-side bookkeeping
    (``serving.prefix_cache.PrefixCacheManager``)."""
    _refuse_unless_kv_rows(config, "the prefix pool")
    return _init_cache(config, num_blocks, block_tokens, rules, mesh,
                       kv_quant=kv_quant)


def copy_prefix_program(cache, pool, block_ids, slot):
    """Copy pool blocks into the head of one slot row: block i lands at
    positions ``[i * block_tokens, (i+1) * block_tokens)`` of slot
    ``slot``.  ``block_ids`` is a traced [n_blocks] int32 vector (the
    program specializes per prompt bucket: ``n_blocks = bucket_len //
    block_tokens``); entries padded past the real hit may be out of
    range — the gather clamps, and the garbage it copies lands at
    positions the suffix prefill overwrites (or that attention masks,
    beyond the prompt).  Pure data movement — no params, no forward
    pass; this is the whole point of a prefix hit.  Returns the cache.
    """
    slot = jnp.asarray(slot, jnp.int32)
    block_ids = jnp.asarray(block_ids, jnp.int32)
    n_blocks = block_ids.shape[0]
    zero = jnp.int32(0)
    out = dict(cache)
    for name, leaf in cache.items():
        pool_leaf = pool[name]
        bt = pool_leaf.shape[2]
        gathered = jnp.take(pool_leaf, block_ids, axis=1, mode="clip")
        l, _, _, h, w = gathered.shape
        flat = gathered.reshape(l, 1, n_blocks * bt, h, w)
        out[name] = jax.lax.dynamic_update_slice(
            leaf, flat, (zero, slot, zero, zero, zero)
        )
    return out


def save_prefix_program(pool, cache, slot, block_ids):
    """The reverse copy: capture a just-prefilled slot row's head into
    pool blocks (block i from positions ``[i * block_tokens, (i+1) *
    block_tokens)``).  Out-of-range ``block_ids`` entries are the SKIP
    sentinel — the scatter drops them — so already-cached blocks are
    never rewritten (their bytes could differ in float lsb from a
    different chunk partition, and in-flight slots may share them).
    Returns the pool."""
    slot = jnp.asarray(slot, jnp.int32)
    block_ids = jnp.asarray(block_ids, jnp.int32)
    n_blocks = block_ids.shape[0]
    zero = jnp.int32(0)
    out = dict(pool)
    for name, pool_leaf in pool.items():
        leaf = cache[name]
        bt = pool_leaf.shape[2]
        l, _, _, h, w = leaf.shape
        row = jax.lax.dynamic_slice(
            leaf, (zero, slot, zero, zero, zero),
            (l, 1, n_blocks * bt, h, w),
        )
        blocks = row.reshape(l, n_blocks, bt, h, w)
        out[name] = pool_leaf.at[:, block_ids].set(blocks, mode="drop")
    return out


def download_prefix_block(pool, block):
    """One pool block row as a host-transferable pytree: per leaf a
    ``[L, block_tokens, H, hd]`` slice (k/v, plus the scale leaves of a
    quantized pool) — the serialization :func:`save_prefix_program`
    writes, minus the block axis.  The serving engine's host-DRAM
    prefix tier demotes evicted blocks through this (``np.asarray`` of
    the result is the DRAM payload) and :func:`upload_prefix_block`
    restores them; ``block`` is a traced int32 scalar, so ONE
    executable serves every demotion."""
    block = jnp.asarray(block, jnp.int32)
    zero = jnp.int32(0)
    out = {}
    for name, leaf in pool.items():
        l, _, bt, h, w = leaf.shape
        row = jax.lax.dynamic_slice(
            leaf, (zero, block, zero, zero, zero), (l, 1, bt, h, w)
        )
        out[name] = row[:, 0]
    return out


def upload_prefix_block(pool, payload, block):
    """The reverse of :func:`download_prefix_block`: write a demoted
    block's host payload back into pool row ``block`` (a swap-in
    promotion).  ``payload`` leaves are ``[L, block_tokens, H, hd]``;
    ``block`` is a traced int32 scalar — one executable serves every
    swap-in.  Returns the pool."""
    block = jnp.asarray(block, jnp.int32)
    zero = jnp.int32(0)
    out = dict(pool)
    for name, leaf in pool.items():
        row = jnp.asarray(payload[name])[:, None]
        out[name] = jax.lax.dynamic_update_slice(
            leaf, row.astype(leaf.dtype), (zero, block, zero, zero, zero)
        )
    return out


def download_prefix_blocks(pool, blocks):
    """Batched :func:`download_prefix_block`: gather N pool rows in ONE
    dispatch.  ``blocks`` is ``[N]`` int32; the result's leaves are
    stacked ``[N, L, block_tokens, H, hd]`` — the caller unstacks into
    per-block payloads host-side.  Out-of-range indices clip (callers
    padding to a shape bucket discard those rows), and like the
    batched upload this turns a long KV-handoff export from N
    dynamic-slice dispatches into one gather."""
    blocks = jnp.asarray(blocks, jnp.int32)
    out = {}
    for name, leaf in pool.items():
        rows = jnp.take(leaf, blocks, axis=1, mode="clip")
        out[name] = jnp.moveaxis(rows, 1, 0)  # [N, L, bt, H, hd]
    return out


def upload_prefix_blocks(pool, payloads, blocks):
    """Batched :func:`upload_prefix_block`: write N host payloads into
    N pool rows in ONE dispatch.  ``payloads`` leaves are stacked
    ``[N, L, block_tokens, H, hd]``; ``blocks`` is ``[N]`` int32.  An
    out-of-range block index is dropped (``mode="drop"``), so callers
    can pad a partial batch to a fixed shape bucket with
    ``num_blocks`` sentinels instead of compiling one executable per
    batch size.  The KV-handoff import seam uses this: a long exported
    prefix is dozens of blocks, and one scatter beats dozens of
    single-row dynamic updates by the whole per-dispatch overhead."""
    blocks = jnp.asarray(blocks, jnp.int32)
    out = dict(pool)
    for name, leaf in pool.items():
        stacked = jnp.asarray(payloads[name]).astype(leaf.dtype)
        rows = jnp.moveaxis(stacked, 0, 1)  # [L, N, bt, H, hd]
        out[name] = leaf.at[:, blocks].set(rows, mode="drop")
    return out


def prefill_chunk_program(
    params,
    cache,
    chunk_tokens: jnp.ndarray,
    start,
    chunk_len,
    slot,
    config: transformer.TransformerConfig,
    *,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
    pool=None,
    block_table=None,
    use_pallas=None,
):
    """Prefill one bounded chunk of a prompt into one live slot row.

    ``chunk_tokens`` is a [1, chunk_width] padded token slice covering
    prompt positions ``[start, start + chunk_len)`` (the program
    specializes per chunk width only — ``start``/``chunk_len``/``slot``
    are traced int32 scalars, so ONE executable serves every slot,
    every offset, and every request).  Each layer writes the chunk's
    k/v into the slot row, then attends causally over the row —
    positions already filled (a copied prefix hit, earlier chunks) plus
    the chunk itself — so splitting a prefill into chunks writes the
    same cache bytes as the one-shot prefill.  Padded chunk positions
    write garbage past ``start + chunk_len``, which the next chunk (or
    decode, position by position) overwrites before attention can ever
    see it — the same staleness invariant as slot reuse.

    Returns ``(cache, logits)`` with ``logits`` [1, V] taken at the
    chunk's LAST REAL token; only the final chunk's logits mean
    anything (feed them to :func:`finalize_slot_program`).

    ``block_table`` [num_slots, n_pages] + ``pool`` route the
    chunk-causal attention through the paged read-in-place path: a
    prefix hit's pool-backed pages are read directly from the pool
    (the engine skips ``copy_prefix_program`` entirely), while the
    chunk's own writes land in the slot row as always — hits are
    block-aligned, so the suffix never overlaps a pool page.  Defaults
    keep the trace byte-identical to the pre-paged program.
    """
    c = chunk_tokens.shape[1]
    start = jnp.asarray(start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    positions = (start + jnp.arange(c))[None, :]
    table_row = None
    if block_table is not None:
        table_row = jax.lax.dynamic_slice(
            jnp.asarray(block_table, jnp.int32), (slot, jnp.int32(0)),
            (1, block_table.shape[1]),
        )

    x = transformer.embed_tokens(params, chunk_tokens, config, rules, mesh)
    x = shard_constraint(x, "batch", "seq", "act_embed", rules=rules,
                         mesh=mesh)
    x, cache = _scan_layers(
        params, cache, x, positions, positions, config, rules, mesh,
        kind="chunk", slot=slot, pool=pool, block_table=table_row,
        use_pallas=use_pallas,
    )
    last_idx = jnp.clip(chunk_len - 1, 0, c - 1)[None, None, None]
    last_x = jnp.take_along_axis(
        x, jnp.broadcast_to(last_idx, (1, 1, x.shape[-1])), axis=1
    )
    logits = _final_logits(params, last_x, config)[:, 0]
    # Sampling-boundary reshard (see _prefill_forward): the final
    # chunk's logits feed finalize_slot_program host-side, so they must
    # leave the program replicated, not vocab-sharded.
    logits = shard_constraint(logits, "batch", None, rules=rules,
                              mesh=mesh)
    return cache, logits


def finalize_slot_program(
    state,
    logits0: jnp.ndarray,
    prompt_len,
    slot,
    max_new_tokens,
    config: transformer.TransformerConfig,
    *,
    sample: SampleConfig = SampleConfig(temperature=0.0),
    rng: Optional[jax.Array] = None,
):
    """Arm one slot from a chunked prefill's final-chunk logits: sample
    the first token and write the slot state EXACTLY as
    :func:`insert_slot_program` would (same :func:`_arm_slot`), minus
    the prefill it no longer needs to do.  One compile serves the whole
    engine (logits shape is [1, V] regardless of bucket).  Returns
    ``(state, first_token)``."""
    prompt_len = jnp.asarray(prompt_len, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    return _arm_slot(state, logits0, prompt_len, slot, max_new_tokens,
                     config, sample=sample, rng=rng)


# --------------------------------------------------------------------------
# Speculative decoding: draft-and-verify on the slot grid.  Decode is one
# target-model dispatch per token per slot; at batch occupancy the per-step
# KV re-read dominates.  Speculation trades k cheap DRAFT-model steps for
# ONE wide target dispatch: :func:`draft_chunk_program` proposes a
# ``spec_k``-token window per active slot with a small draft model over
# its own slot cache, :func:`verify_chunk_program` scores every window
# position in a single target forward (the chunked-prefill attention
# shape), commits the greedily-accepted prefix — KV, ``pos``, emissions —
# and rewinds past the first mismatch so rejected cache rows are simply
# overwritten by the next window.  Greedy acceptance keeps outputs
# token-identical to the sequential path: every committed emission is the
# TARGET's own argmax over the same context bytes, the draft only decides
# how many of them one dispatch gets to commit.


def draft_chunk_program(
    params,
    cache,
    state,
    config: transformer.TransformerConfig,
    *,
    spec_k: int,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
):
    """Propose a ``spec_k``-token verify window for every slot with the
    DRAFT model: ``spec_k`` greedy single-token steps over the draft's
    own slot cache (one ``lax.scan`` — static shapes, ONE compile for
    the engine's life).

    Returns ``(cache, window)`` with ``window`` [num_slots, spec_k]:
    column 0 is each slot's carried token (``state["tok"]``, sampled
    but not yet consumed), columns 1.. the draft's greedy proposals.
    Each step writes its consumed token's k/v into the draft cache row
    (inactive slots' writes suppressed exactly like
    :func:`decode_chunk_program`), so after the verify commits an
    accepted prefix the draft cache already holds KV for every
    committed position — the next proposal round needs no catch-up
    forward.  The final step's proposal is discarded (that step exists
    to write the last window token's draft KV).  Draft sampling is
    plain argmax with none of the target's eos/min-token gating:
    proposals only steer ACCEPTANCE, never emissions, so a draft that
    proposes a masked token merely loses acceptance — it cannot change
    the output.  ``state`` is read-only here; the verify owns every
    state transition.
    """
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    _refuse_unless_kv_rows(config, "a draft's proposal window")
    s = cache["k"].shape[2]
    active = state["active"]

    def step(carry, _):
        cache, tok, pos = carry
        write_pos = jnp.where(active, pos, jnp.int32(s))
        cache, logits = _decode_step(
            params, cache, tok, pos, config, rules, mesh,
            write_pos=write_pos,
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt, pos + 1), tok

    (cache, _, _), consumed = jax.lax.scan(
        step, (cache, state["tok"], state["pos"]), None, length=spec_k
    )
    return cache, consumed.T  # [num_slots, spec_k]


def verify_chunk_program(
    params,
    cache,
    state,
    window: jnp.ndarray,
    config: transformer.TransformerConfig,
    *,
    sample: SampleConfig = SampleConfig(temperature=0.0),
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
    pool=None,
    block_table=None,
    use_pallas=None,
    with_summary: bool = False,
):
    """Score a draft window for every slot in ONE target forward and
    commit the accepted prefix.

    ``window`` is [num_slots, spec_k]: column 0 each slot's carried
    token, columns 1.. the draft proposals (what
    :func:`draft_chunk_program` returns).  The forward is the
    chunked-prefill shape batched over slots: each layer writes the
    window's k/v at per-slot positions ``pos..pos+k-1`` and attends
    ``chunk_causal`` over the whole row, so the logits after window
    position i are bit-for-bit what ``_decode_step`` would produce
    having consumed ``window[:, :i+1]`` one token at a time.  Greedy
    target emissions ``g_i`` then gate acceptance: draft token
    ``window[:, i]`` is accepted while it equals ``g_{i-1}``, and the
    committed emissions are ``g_0..g_a`` — the first mismatch
    position's own target token is itself a correct emission, so every
    dispatch commits at least one token per active slot (an
    all-rejected window degenerates to the non-speculative step).
    Emissions truncate at eos and the slot's ``remaining`` budget with
    the sequential path's exact semantics; ``pos`` advances only by the
    commit count, which IS the rewind: cache rows written past the
    first mismatch sit beyond ``pos`` where attention masks them
    (key j valid iff ``j < pos``) and the next window overwrites them
    before they could ever become valid — the same staleness invariant
    as slot reuse.

    Greedy-only (temperature 0, no repetition penalty — lossless
    speculative SAMPLING needs rejection resampling, which this grid
    does not do); ``eos_id``/``min_new_tokens`` are supported.  Returns
    ``(cache, state, toks, valid)`` shaped exactly like
    :func:`decode_chunk_program` — the serving engine's emission
    handling cannot tell the two apart.  ``with_summary=True`` appends
    the same device int32 ``[emitted_count, active_count]`` pair the
    chunk program grows, for the pipelined scheduler's drain.
    """
    if sample.temperature != 0.0 or sample.repetition_penalty != 1.0:
        raise ValueError(
            "speculative decoding requires greedy sampling "
            "(temperature=0, repetition_penalty=1); token-identical "
            "non-greedy speculation needs rejection resampling"
        )
    num_slots, k = window.shape
    window = window.astype(jnp.int32)
    active = state["active"]
    pos = state["pos"]
    s = cache["k"].shape[2]
    positions = pos[:, None] + jnp.arange(k)[None, :]  # [slots, k]

    x = transformer.embed_tokens(params, window, config, rules, mesh)
    x = shard_constraint(x, "batch", "seq", "act_embed", rules=rules,
                         mesh=mesh)
    # Inactive slots write NOWHERE (out-of-range -> drop-mode scatter):
    # same frozen-position protection as decode_chunk_program — a slot
    # mid-chunked-prefill holds real prompt KV at pos.
    write_idx = jnp.where(active[:, None], positions, jnp.int32(s))
    x, cache = _scan_layers(
        params, cache, x, positions, write_idx, config, rules, mesh,
        kind="verify", pool=pool, block_table=block_table,
        use_pallas=use_pallas,
    )
    logits = _final_logits(params, x, config)  # [slots, k, V]
    # Sampling boundary reshard (see _prefill_forward): once per forward.
    logits = shard_constraint(logits, "batch", None, None, rules=rules,
                              mesh=mesh)

    # Greedy emission per window position, with the sequential path's
    # eos allow gate: emission i is global emission (emitted + i + 1),
    # sampled when the slot's emitted count reads emitted + i.
    need_min = sample.eos_id is not None and sample.min_new_tokens > 0
    allow = None
    if need_min:
        allow = (
            state["emitted"][:, None] + jnp.arange(k)[None, :]
            >= sample.min_new_tokens
        ).reshape(num_slots * k)
    g = sample_logits(
        jax.random.PRNGKey(0), logits.reshape(num_slots * k, -1), sample,
        allow_eos=allow,
    ).astype(jnp.int32).reshape(num_slots, k)

    # Acceptance: emission i commits iff every draft token before it
    # matched the target's greedy choice — a leading-prefix property,
    # like every other gate below, so the final cumprod is belt and
    # braces, not a semantic.
    ones = jnp.ones((num_slots, 1), jnp.int32)
    if k > 1:
        match = (window[:, 1:] == g[:, :-1]).astype(jnp.int32)
        emit_ok = jnp.concatenate(
            [ones, jnp.cumprod(match, axis=1)], axis=1
        ).astype(bool)
    else:
        emit_ok = ones.astype(bool)
    emit_ok &= jnp.arange(k)[None, :] < state["remaining"][:, None]
    if sample.eos_id is not None:
        is_eos = (g == sample.eos_id).astype(jnp.int32)
        prior_eos = jnp.cumsum(is_eos, axis=1) - is_eos
        emit_ok &= prior_eos == 0  # the eos itself emits; nothing after
    emit_ok &= active[:, None]
    valid = jnp.cumprod(emit_ok.astype(jnp.int32), axis=1).astype(bool)

    toks = jnp.where(valid, g, jnp.int32(sample.pad_id))
    n = valid.sum(axis=1).astype(jnp.int32)  # commit count; 0 if inactive
    last_tok = jnp.take_along_axis(
        toks, jnp.maximum(n - 1, 0)[:, None], axis=1
    )[:, 0]
    new_state = dict(state)
    new_state["pos"] = pos + n
    new_state["remaining"] = state["remaining"] - n
    new_state["emitted"] = state["emitted"] + n
    finished = new_state["remaining"] <= 0
    if sample.eos_id is not None:
        finished = finished | ((n > 0) & (last_tok == sample.eos_id))
    new_state["active"] = active & ~finished
    new_state["tok"] = jnp.where(n > 0, last_tok, state["tok"])
    if with_summary:
        summary = jnp.stack([
            valid.sum().astype(jnp.int32),
            new_state["active"].sum().astype(jnp.int32),
        ])
        return cache, new_state, toks, valid, summary
    return cache, new_state, toks, valid


def draft_prefill_slot_program(
    params,
    cache,
    prompt_tokens: jnp.ndarray,
    prompt_len,
    slot,
    config: transformer.TransformerConfig,
    *,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
):
    """Prefill one request's prompt into the DRAFT model's slot cache
    row — the draft-side twin of :func:`insert_slot_program` minus the
    sampling (``tok0`` always comes from the TARGET's prefill logits;
    the draft only needs the prompt KV so its first proposal round can
    attend over real context).  Always a one-shot full-prompt forward,
    whatever the target side did: the draft is small by construction,
    so target prefix-cache hits and chunked prefills compose freely —
    the target reuses cached blocks while the draft just re-prefills
    from the prompt.  One program per prompt bucket
    (``prompt_len``/``slot`` traced).  Returns the cache.
    """
    t_prompt = prompt_tokens.shape[1]
    prompt_len = jnp.clip(jnp.asarray(prompt_len, jnp.int32), 1, t_prompt)
    lens = jnp.reshape(prompt_len, (1,))
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.int32(0)
    cache, _ = _prefill_into(
        params, cache, prompt_tokens, lens, (zero, slot, zero, zero, zero),
        config, rules, mesh
    )
    return cache


def check_inference_supported(config, rules, mesh, what: str = "inference"):
    """Public guard for callers that bypass :func:`generate`'s own checks
    (the serving engine validates once at startup, then dispatches the
    jit-friendly slot-grid programs)."""
    _check_inference_supported(config, rules, mesh, what)


def _check_inference_supported(config, rules, mesh, what: str):
    """Shared guard for the inference entry points: pp and zigzag layouts
    are training-only."""
    if transformer._is_pipelined(config, rules, mesh):
        raise ValueError(
            f"{what} runs the scanned layer stack; pp pipelining is "
            "training-only (drop the layers->pp rule for inference)"
        )
    if transformer._zigzag_active(config, mesh):
        raise ValueError(
            f"zigzag_sp is training-only; disable it for {what}"
        )
    if config.ssm is not None and mesh is not None:
        shape = dict(mesh.shape)
        if any(shape.get(axis, 1) > 1
               for axis in (mesh_lib.AXIS_TP, mesh_lib.AXIS_SP)):
            _refuse_unless_kv_rows(config, f"{what} under a tp/sp mesh")
    if config.latent is not None and mesh is not None and mesh.size > 1:
        _refuse_unless_kv_rows(config, f"{what} under a mesh")


#: The cache kinds that are not "K and V rows per head", each with the
#: configuration field that brings it and what its slot cache holds.  The
#: next kind adds a line.
CACHE_KINDS = (
    ("ssm", "a recurrent state",
     "a state and a convolution tail per row besides the K/V rows"),
    ("latent", "a latent-attention cache",
     "one latent row a token (key and value of every head at once), no "
     "K or V per head"),
)


def cache_kind(config):
    """``(field, name, holds)`` of the first of :data:`CACHE_KINDS` the
    configuration brings, or None for plain per-head K/V rows."""
    return next((kind for kind in CACHE_KINDS
                 if getattr(config, kind[0]) is not None), None)


def _refuse_unless_kv_rows(config, what: str):
    """The one error of every path that takes "a cache row is K and V per
    head, and a prefix's cache is its rows" for granted: a copied, paged,
    chunked, rewound, quantized, beam-reordered or head-sharded cache of
    such rows carries neither a recurrent state (whole at every token)
    nor a latent row (no heads to shard, one leaf to copy).  ROADMAP R4
    keeps the list of what is refused."""
    kind = cache_kind(config)
    if kind is not None:
        field, name, holds = kind
        raise NotImplementedError(
            f"{what} is not supported for a model with {name} "
            f"(TransformerConfig.{field}): its slot cache holds {holds}, "
            "and only the plain slot path (insert at a bucket, decode "
            "chunks, generate()) carries that"
        )


def beam_search(
    params,
    prompt_tokens: jnp.ndarray,
    prompt_lens: jnp.ndarray,
    config: transformer.TransformerConfig,
    *,
    num_beams: int,
    max_new_tokens: int,
    length_penalty: float = 1.0,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
    kv_quant: bool = False,
) -> Dict[str, Any]:
    """Beam decoding: the highest-scoring continuation per prompt.

    Length-penalized beam search over the KV-cache decoder, compiled as
    one ``lax.scan`` like :func:`generate`.  Prefill runs once per
    prompt; the cache tiles to ``B*K`` for decoding, and each step's
    beam reorder gathers the cache along the beam dim.

    Two hypothesis sets (the flax/t5x scheme): LIVE beams advance at raw
    sum-logprob; a beam that samples eos moves into a FINISHED set scored
    by ``sum_logprob / num_tokens**length_penalty`` and stops consuming
    compute slots.  Each step expands 2K candidates so the live set stays
    full even when K of them finish at once, and the final answer is the
    best penalized hypothesis across both sets — a finished hypothesis
    can never be evicted by a live beam that later collapses.

    Returns dict with ``tokens`` [B, max_new_tokens] (best hypothesis,
    pad after eos), ``scores`` [B] (its length-penalized log-prob), and
    ``num_generated`` [B] (token count including the eos).
    """
    mesh = mesh if mesh is not None else mesh_lib.get_global_mesh()
    _check_inference_supported(config, rules, mesh, "beam_search")
    _refuse_unless_kv_rows(config, "beam_search")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if max_new_tokens < 1:
        raise ValueError("beam_search needs max_new_tokens >= 1")

    b, t_prompt = prompt_tokens.shape
    k = num_beams
    s = t_prompt + max_new_tokens
    # Same clamp as generate(): out-of-domain lengths index out of range
    # silently under jit.
    prompt_lens = jnp.clip(prompt_lens.astype(jnp.int32), 1, t_prompt)
    vocab = config.vocab_size
    neg_inf = jnp.float32(-1e30)

    def penalize(sum_logprob, n):
        return sum_logprob / jnp.maximum(n.astype(jnp.float32), 1.0) ** (
            length_penalty
        )

    cache, logits0 = _prefill(params, prompt_tokens, prompt_lens, config,
                              s, rules, mesh, kv_quant=kv_quant)

    # Tile the cache/prompt state to B*K (beam-major inside each batch row).
    cache = jax.tree_util.tree_map(
        lambda a: jnp.repeat(a, k, axis=1), cache
    )  # leaves [L, B*K, S, H, ...]
    cur_len = jnp.repeat(prompt_lens, k)  # [B*K]

    # Seed the live set with the top-K first tokens.  An eos seed moves
    # straight to the finished set (its live copy is scored out).
    logprobs0 = jax.nn.log_softmax(logits0, axis=-1)  # [B, V]
    scores_l, tok0 = jax.lax.top_k(logprobs0, k)  # [B, K]
    tok0 = tok0.astype(jnp.int32)
    hist_l = jnp.full((b, k, max_new_tokens), pad_id, jnp.int32)
    hist_l = hist_l.at[:, :, 0].set(tok0)
    n_l = jnp.ones((b, k), jnp.int32)

    hist_f = jnp.full((b, k, max_new_tokens), pad_id, jnp.int32)
    scores_f = jnp.full((b, k), neg_inf)
    n_f = jnp.zeros((b, k), jnp.int32)
    if eos_id is not None:
        seed_eos = tok0 == eos_id
        scores_f = jnp.where(seed_eos, penalize(scores_l, n_l), scores_f)
        hist_f = jnp.where(seed_eos[:, :, None], hist_l, hist_f)
        n_f = jnp.where(seed_eos, n_l, n_f)
        scores_l = jnp.where(seed_eos, neg_inf, scores_l)

    def step(carry, i):
        (cache, cur_len, token, scores_l, hist_l, n_l,
         scores_f, hist_f, n_f) = carry
        cache, step_logits = _decode_step(
            params, cache, token.reshape(b * k), cur_len, config, rules,
            mesh,
        )
        logprobs = jax.nn.log_softmax(
            step_logits, axis=-1
        ).reshape(b, k, vocab)
        total = scores_l[:, :, None] + logprobs  # [B, K, V]

        # 2K candidates so the live set refills even if K of them finish.
        cand_scores, flat_idx = jax.lax.top_k(
            total.reshape(b, k * vocab), 2 * k
        )
        cand_parent = (flat_idx // vocab).astype(jnp.int32)   # [B, 2K]
        cand_tok = (flat_idx % vocab).astype(jnp.int32)
        cand_hist = jnp.take_along_axis(
            hist_l, cand_parent[:, :, None], axis=1
        ).at[:, :, i + 1].set(cand_tok)
        cand_n = jnp.take_along_axis(n_l, cand_parent, axis=1) + 1

        if eos_id is not None:
            cand_eos = cand_tok == eos_id
            # Merge finishing candidates (penalized) into the finished set.
            merged_scores = jnp.concatenate(
                [scores_f,
                 jnp.where(cand_eos, penalize(cand_scores, cand_n),
                           neg_inf)],
                axis=1,
            )  # [B, K + 2K]
            top_f, f_idx = jax.lax.top_k(merged_scores, k)
            merged_hist = jnp.concatenate([hist_f, cand_hist], axis=1)
            merged_n = jnp.concatenate([n_f, cand_n], axis=1)
            scores_f = top_f
            hist_f = jnp.take_along_axis(
                merged_hist, f_idx[:, :, None], axis=1
            )
            n_f = jnp.take_along_axis(merged_n, f_idx, axis=1)
            # Finishing candidates leave the live competition.
            cand_scores = jnp.where(cand_eos, neg_inf, cand_scores)

        # Keep the best K live candidates.
        scores_l, l_idx = jax.lax.top_k(cand_scores, k)  # [B, K]
        next_tok = jnp.take_along_axis(cand_tok, l_idx, axis=1)
        hist_l = jnp.take_along_axis(cand_hist, l_idx[:, :, None], axis=1)
        n_l = jnp.take_along_axis(cand_n, l_idx, axis=1)
        live_parent = jnp.take_along_axis(cand_parent, l_idx, axis=1)

        # Reorder the cache by the chosen live parents; all live beams
        # advance, so cur_len bumps uniformly.
        flat_parent = (
            jnp.arange(b)[:, None] * k + live_parent
        ).reshape(b * k)
        cache = jax.tree_util.tree_map(
            lambda a: jnp.take(a, flat_parent, axis=1), cache
        )
        cur_len = jnp.take(cur_len, flat_parent) + 1
        return (
            cache, cur_len, next_tok, scores_l, hist_l, n_l,
            scores_f, hist_f, n_f,
        ), None

    carry0 = (cache, cur_len, tok0, scores_l, hist_l, n_l,
              scores_f, hist_f, n_f)
    (_, _, _, scores_l, hist_l, n_l, scores_f, hist_f, n_f), _ = (
        jax.lax.scan(step, carry0, jnp.arange(max_new_tokens - 1))
    )

    # Final selection across both sets (live beams penalized now).
    all_scores = jnp.concatenate(
        [scores_f, penalize(scores_l, n_l)], axis=1
    )  # [B, 2K]
    all_hist = jnp.concatenate([hist_f, hist_l], axis=1)
    all_n = jnp.concatenate([n_f, n_l], axis=1)
    best = jnp.argmax(all_scores, axis=-1)  # [B]
    return {
        "tokens": jnp.take_along_axis(
            all_hist, best[:, None, None], axis=1
        )[:, 0],
        "scores": jnp.take_along_axis(all_scores, best[:, None], axis=1)[
            :, 0
        ],
        "num_generated": jnp.take_along_axis(all_n, best[:, None], axis=1)[
            :, 0
        ],
    }
