"""Shared functional layers: init/apply pairs with logical-axis sharding.

Design: parameters are plain dict pytrees; every layer has an ``init_*``
returning (params, logical_axes) in congruent structure, and an ``apply``
function.  Compute runs in the dtype of the inputs (bfloat16 on TPU — MXU
native), while parameters stay float32; callers cast activations, never
weights (the optimizer needs f32 master weights).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from cloud_tpu.parallel.sharding import ShardingRules, DEFAULT_RULES, shard_constraint


#: Named rematerialization policies for the layer-stack scans.  Memory /
#: recompute trade-offs on TPU (the remat policy on the scan is an
#: ablation axis):
#:
#: - "full": ``jax.checkpoint`` saving only the carry — minimum live
#:   activations (one layer's worth), backward re-runs the whole layer
#:   including its matmuls (~33% extra MXU FLOPs).
#: - "dots": save matmul OUTPUTS, recompute elementwise/norm chains —
#:   the backward never re-runs MXU work; extra memory is the saved
#:   projections, still far below no-remat's full residual set.  The
#:   usual best default for HBM-rich chips running compute-bound steps.
#: - "none": XLA keeps every residual (fastest when it fits).
REMAT_POLICIES = ("none", "full", "dots")


def remat_wrap(body, enabled: bool = True, policy: str = "full"):
    """Wrap a scan body with the named remat policy (see REMAT_POLICIES).

    A pure scheduling change: loss and gradients are bit-identical across
    policies (asserted in tests/unit/test_models_training.py); only the
    memory/recompute trade moves.
    """
    if not enabled or policy == "none":
        return body
    if policy == "full":
        return jax.checkpoint(body)
    if policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    raise ValueError(
        f"remat policy must be one of {REMAT_POLICIES}, got {policy!r}"
    )


#: The program scopes: the model's own sublayers, by which a device trace
#: names its time (docs/observability.md, "Program scopes").  One closed,
#: flat list.  Norms and residual adds have none of their own: XLA fuses
#: them into a neighbour, and a fusion goes by its root.
SCOPES = (
    "embed",        # the token table's rows
    "attn_proj",    # q/k/v or the latent projections, with their rotation
    "cache_write",  # the new rows' scatter; a prefill's write into a slot
    "attn_read",    # the paged / latent / flash kernel, or XLA's read
    "attn_out",     # attention's output projection
    "ssm_proj",     # a recurrent mixer's (Mamba-2's, the delta rule's)
                    # in- and out-projection, convolution, gate and norm
    "ssm_state",    # its state step (kernel or jnp); a prefill's chunked
                    # scan
    "mlp",          # the dense SwiGLU, and the shared expert
    "moe_route",    # router, top-k, sort, a block's placement and rows
    "moe_experts",  # the grouped products, the weight on their last input
    "moe_combine",  # the placement product, the shared expert's add
    "head",         # final norm, vocabulary product, sampling, slot state
)


def scope(name: str):
    """The program scope ``name`` (one of :data:`SCOPES`) over the
    operations traced inside it: the device-side twin of a span.  Metadata
    alone (``jax.named_scope``): the operations, and the cache's key for
    them, are what they were without it."""
    if name not in SCOPES:
        raise ValueError(f"no program scope {name!r}; there are {SCOPES}")
    return jax.named_scope(name)


def scaled(x, multiplier: float):
    """``x`` times a fixed scalar of the configuration (a muP multiplier);
    a multiplier of exactly 1 adds no operation, so a model that has none
    computes, bit for bit, what it computed before they existed."""
    return x if multiplier == 1.0 else x * multiplier


def dense_axes(in_axis: Optional[str], out_axis: Optional[str],
               use_bias: bool = True):
    """Logical axes for a dense layer's params — the single source of truth
    consumed by ``dense_init`` and every model's ``param_logical_axes``."""
    axes = {"kernel": (in_axis, out_axis)}
    if use_bias:
        axes["bias"] = (out_axis,)
    return axes


def dense_init(rng, in_dim: int, out_dim: int, *, in_axis: Optional[str],
               out_axis: Optional[str], use_bias: bool = True):
    """Kernel [in, out] with truncated-normal fan-in scaling."""
    stddev = 1.0 / math.sqrt(in_dim)
    k_rng, _ = jax.random.split(rng)
    params = {
        "kernel": jax.random.truncated_normal(
            k_rng, -2.0, 2.0, (in_dim, out_dim), jnp.float32
        )
        * stddev
    }
    if use_bias:
        params["bias"] = jnp.zeros((out_dim,), jnp.float32)
    return params, dense_axes(in_axis, out_axis, use_bias)


def materialize_matrix(params, name: str, dtype):
    """The (possibly int8-quantized) matrix ``name`` at compute width.

    Weight-only quantization stores ``{name}_q`` (int8) +
    ``{name}_scale`` (models/quantization.py); the dequant multiply is
    fused by XLA into the consuming matmul/gather, so only the narrow
    tensor crosses HBM.
    """
    if f"{name}_q" in params:
        return (
            params[f"{name}_q"].astype(dtype)
            * params[f"{name}_scale"].astype(dtype)
        )
    return params[name].astype(dtype)


def dense_apply(params, x, *, dtype=None):
    dtype = dtype or x.dtype
    if "kernel_q" in params:
        # Post-scale formulation: y = (x @ q) * scale.  The int8 kernel
        # feeds the matmul directly (a full-width q*scale intermediate
        # would be loop-invariant inside a decode scan and LICM could
        # hoist it, materializing the wide matrix once and streaming it
        # every step); the per-channel scale applies to the small output.
        q = params["kernel_q"].astype(dtype)
        scale = jnp.squeeze(params["kernel_scale"], axis=-2).astype(dtype)
        y = jnp.einsum("...i,io->...o", x, q) * scale
    else:
        y = jnp.einsum("...i,io->...o", x, params["kernel"].astype(dtype))
    if "bias" in params:
        y = y + params["bias"].astype(dtype)
    return y


def dense_heads_apply(params, x, heads: int, head_dim: int):
    """``dense_apply`` split into heads: [..., H * hd] -> [..., H, hd].

    The split comes after the product, behind an optimization barrier.
    Without it XLA folds the reshape into the product, which becomes a
    convolution that wants its kernel contraction-minor: inside a layer
    scan over stacked kernels that is a transposed copy of the whole stack
    per program execution, and each layer's slice staged in fast memory
    before the product.  With it the product is a plain dot whose operand
    is the stored kernel sliced in place.  The barrier is the identity:
    the numbers, and their gradients, are ``dense_apply``'s."""
    y = jax.lax.optimization_barrier(dense_apply(params, x))
    return y.reshape(y.shape[:-1] + (heads, head_dim))


def embedding_init(rng, vocab: int, dim: int, *, vocab_axis="vocab",
                   embed_axis="embed"):
    table = jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02
    return {"table": table}, {"table": (vocab_axis, embed_axis)}


def embedding_apply(params, token_ids, *, dtype=jnp.float32,
                    rules: ShardingRules = DEFAULT_RULES, mesh=None):
    """Table lookup with SPMD-friendly sharding.

    The table's dims are param-sharded (vocab over tp, embed over fsdp) but
    the lookup output wants activation sharding (batch/seq).  Left to
    itself XLA "involuntarily fully rematerializes" at the gather (observed
    in the r1 dryrun, spmd_partitioner.cc) — replicate the table explicitly
    (one clean all-gather, the ZeRO-3 gather-weights-per-use pattern) so
    the gather partitions by its index dims instead.  ``mesh`` falls back
    to the global mesh, like every shard_constraint.
    """
    if "table_q" in params:
        # Weight-only int8: gather narrow rows, then scale the gathered
        # rows (per-row scales) — the full-width table never materializes.
        # Same replicate constraint as the full-precision path: a sharded
        # table makes SPMD involuntarily rematerialize at the gather.
        table_q = shard_constraint(params["table_q"], None, None,
                                   rules=rules, mesh=mesh)
        table_scale = shard_constraint(params["table_scale"], None, None,
                                       rules=rules, mesh=mesh)
        rows = jnp.take(table_q, token_ids, axis=0).astype(dtype)
        scales = jnp.take(table_scale.astype(dtype), token_ids, axis=0)
        out = rows * scales
    else:
        table = params["table"].astype(dtype)
        table = shard_constraint(table, None, None, rules=rules, mesh=mesh)
        out = jnp.take(table, token_ids, axis=0)
    if token_ids.ndim == 2:
        out = shard_constraint(out, "batch", "seq", "act_embed", rules=rules,
                               mesh=mesh)
    return out


def layernorm_init(dim: int, *, axis: Optional[str] = None):
    return (
        {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)},
        {"scale": (axis,), "bias": (axis,)},
    )


def layernorm_apply(params, x, *, eps: float = 1e-6):
    # LN statistics in float32 for stability regardless of activation dtype.
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def rmsnorm_init(dim: int, *, axis: Optional[str] = None):
    return {"scale": jnp.ones((dim,), jnp.float32)}, {"scale": (axis,)}


def rmsnorm_apply(params, x, *, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * params["scale"]).astype(x.dtype)


def rotary_embedding(x, positions, *, base: float = 10000.0):
    """RoPE applied to [..., T, H, D] with positions [..., T]."""
    dim = x.shape[-1]
    half = dim // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., T, half]
    angles = angles[..., None, :]  # broadcast over heads: [..., T, 1, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def dropout(rng, x, rate: float):
    """Inverted dropout: identity when ``rng`` is None or ``rate`` == 0
    (the eval / deterministic path needs no branching at call sites)."""
    if rng is None or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def causal_attention(q, k, v, *, mask: Optional[jnp.ndarray] = None,
                     causal: bool = True):
    """Reference (non-ring, non-Pallas) attention: [B, T, H, D] layout.

    Single source of truth lives in ops/flash_attention (its jnp reference
    path); this wrapper keeps the historical layers.py entry point.  The
    finite -1e30 mask value means fully-masked rows softmax to uniform
    garbage instead of NaN; the loss mask drops such rows.
    """
    from cloud_tpu.ops.flash_attention import _reference

    return _reference(q, k, v, causal=causal, mask=mask)


def ulysses_eligible(num_heads: int, mesh,
                     rules: ShardingRules = DEFAULT_RULES) -> bool:
    """True when the Ulysses seq<->head all-to-all layout exists here.

    The all-to-all re-shards [B, T/sp, H_local, D] into [B, T, H_local/sp,
    D], so the LOCAL head group (num_heads / tp shards over the 'heads'
    axes) must divide by the sp axis size.  Factored out of
    :func:`sharded_attention` so tests can assert which path a config
    actually takes (an ineligible config silently falls back to ring
    attention — ADVICE r4: the only grad-checking Ulysses test was
    accidentally asserting the fallback).
    """
    from cloud_tpu.parallel import mesh as mesh_lib

    if mesh is None:
        return False
    shape = dict(mesh.shape)
    sp_size = shape.get(mesh_lib.AXIS_SP, 1)
    if sp_size <= 1:
        return False
    heads_axes = rules.assignment("heads")
    tp_shards = 1
    for axis_name in (
        heads_axes if isinstance(heads_axes, tuple) else (heads_axes,)
    ):
        if axis_name:
            tp_shards *= shape.get(axis_name, 1)
    local_heads = num_heads // max(tp_shards, 1)
    return local_heads % sp_size == 0


def sharded_attention(q, k, v, *, causal: bool,
                      mask: Optional[jnp.ndarray] = None,
                      rules: ShardingRules = DEFAULT_RULES, mesh=None,
                      zigzag: bool = False, ulysses: bool = False):
    """Mesh-aware attention dispatch over [B, T, H, D] tensors.

    The single routing point shared by CloudLM and BERT:

    - inside a partial-manual region (the pp pipeline body):
      ``partitioned=True`` dispatch, which there is the direct call (the
      shapes are the region's own).  No route puts the COMPILED kernel
      there yet (a nested shard_map verify-fails at the sdy level —
      "manual axis after free axis" — JAX refuses an unwrapped Mosaic
      call, libtpu refuses ``custom_partitioning`` on more than one
      chip), so on the chip auto-dispatch takes the jnp reference with a
      warning (ROADMAP S8); the interpreter's kernel is plain HLO and
      runs there as it is.
    - ``sp`` > 1 and ``ulysses``: sequence<->head re-sharding all-to-all
      (the DeepSpeed-Ulysses pattern) — each rank attends over the FULL
      sequence for its head group, so there are no ring hops at all:
      2 collectives in, 1 out, total comm O(1/sp) of the activations vs
      the ring's O(sp) K/V hops.  Requires local heads (H / tp) to
      divide by sp; indivisible head counts fall back to the ring.
    - ``sp`` > 1 otherwise: ring attention over the sequence axis
    - mesh present: ``partitioned=True`` dispatch — the kernels per
      (batch, heads) shard, split over the axes ``rules`` assign to them
    - otherwise: direct dispatch (kernel on TPU, jnp reference elsewhere)

    ``mask`` is a [B, T_k] valid-token padding mask; the flash kernels
    apply it key-side (flash_attention docstring).  With ``sp`` > 1 the
    mask shards over the sequence axis and rides the ring with its K/V
    block (zig-zag stays causal/unmasked — pretraining layout); on the
    Ulysses path every rank holds the full sequence, so the mask enters
    replicated over sp instead.
    """
    from functools import partial

    from jax.sharding import PartitionSpec

    from cloud_tpu import ops
    from cloud_tpu.parallel import mesh as mesh_lib
    from cloud_tpu.parallel import sharding as sharding_lib
    from cloud_tpu.parallel.ring_attention import ring_attention

    mesh = mesh or mesh_lib.get_global_mesh()
    sp_size = dict(mesh.shape).get(mesh_lib.AXIS_SP, 1) if mesh is not None else 1

    if sharding_lib.manual_context_mesh() is not None:
        return ops.flash_attention(q, k, v, causal=causal, mask=mask,
                                   partitioned=True)
    if sp_size > 1 and ulysses:
        from cloud_tpu.parallel import collectives

        batch_axes = rules.assignment("batch")
        heads_axes = rules.assignment("heads")
        if ulysses_eligible(q.shape[2], mesh, rules):
            spec = PartitionSpec(
                batch_axes, mesh_lib.AXIS_SP, heads_axes, None
            )

            def ulysses_fn(q_, k_, v_, m_=None):
                to_heads = partial(
                    collectives.all_to_all_seq_heads, axis=mesh_lib.AXIS_SP,
                    to_heads=True,
                )
                out = ops.flash_attention(
                    to_heads(q_), to_heads(k_), to_heads(v_),
                    causal=causal, mask=m_,
                )
                return collectives.all_to_all_seq_heads(
                    out, mesh_lib.AXIS_SP, to_heads=False
                )

            if mask is not None:
                # Each rank attends over the FULL sequence: the [B, T]
                # mask must arrive whole (replicated over sp).
                args = (q, k, v, mask)
                in_specs = (spec, spec, spec,
                            PartitionSpec(batch_axes, None))
            else:
                args, in_specs = (q, k, v), (spec, spec, spec)
            return jax.shard_map(
                ulysses_fn,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=spec,
                check_vma=False,
            )(*args)
        # Indivisible head group: fall through to the ring (which has no
        # divisibility requirement on heads).
    if sp_size > 1:
        from cloud_tpu.parallel.ring_attention import ring_attention_balanced

        if zigzag and mask is not None:
            # Neither ring variant carries mask plumbing for permuted
            # layouts: a natural-order [B, T] mask applied to
            # zig-zag-permuted K slots masks the WRONG tokens.  Refuse
            # for every zigzag call (causal or not) instead of silently
            # corrupting.
            raise ValueError(
                "padding masks are unsupported with zigzag_sp (the "
                "zig-zag layout is for unpadded pretraining batches); "
                "disable config.zigzag_sp for masked data"
            )
        batch_axes = rules.assignment("batch")
        heads_axes = rules.assignment("heads")
        spec = PartitionSpec(batch_axes, mesh_lib.AXIS_SP, heads_axes, None)
        if zigzag and causal:
            # Caller guarantees the sequence is in zig-zag layout
            # (zigzag_indices) — per-hop-balanced causal ring.
            ring_fn = partial(ring_attention_balanced, axis=mesh_lib.AXIS_SP)
            args, in_specs = (q, k, v), (spec, spec, spec)
        elif mask is not None:
            # The [B, T] padding mask shards over sp like k's sequence dim
            # and rides the ring with its block (ring_attention docstring).
            def ring_fn(q_, k_, v_, m_):
                return ring_attention(
                    q_, k_, v_, axis=mesh_lib.AXIS_SP, causal=causal,
                    mask=m_,
                )

            args = (q, k, v, mask)
            in_specs = (spec, spec, spec,
                        PartitionSpec(batch_axes, mesh_lib.AXIS_SP))
        else:
            ring_fn = partial(
                ring_attention, axis=mesh_lib.AXIS_SP, causal=causal
            )
            args, in_specs = (q, k, v), (spec, spec, spec)
        return jax.shard_map(
            ring_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=spec,
            # The online-softmax accumulators start replicated and become
            # axis-varying inside the fori_loop; skip VMA carry checking.
            check_vma=False,
        )(*args)
    if mesh is not None and sp_size == 1:
        return ops.flash_attention(
            q, k, v, causal=causal, mask=mask, partitioned=True, mesh=mesh,
            batch_axes=rules.assignment("batch"),
            head_axes=rules.assignment("heads"),
        )
    # No mesh at all: direct dispatch.
    return ops.flash_attention(q, k, v, causal=causal, mask=mask)


def attention_block_axes():
    return {
        "q": dense_axes("embed", "heads", use_bias=False),
        "k": dense_axes("embed", "heads", use_bias=False),
        "v": dense_axes("embed", "heads", use_bias=False),
        "out": dense_axes("heads", "embed", use_bias=False),
    }


def attention_block_init(rng, dim: int, num_heads: int, head_dim: int,
                         num_kv_heads: Optional[int] = None):
    """``num_kv_heads`` < ``num_heads``: grouped-query attention, the k
    and v projections that many heads wide."""
    rngs = jax.random.split(rng, 4)
    kv_heads = num_heads if num_kv_heads is None else num_kv_heads
    params = {}
    for name, r, (i, o) in [
        ("q", rngs[0], (dim, num_heads * head_dim)),
        ("k", rngs[1], (dim, kv_heads * head_dim)),
        ("v", rngs[2], (dim, kv_heads * head_dim)),
    ]:
        params[name], _ = dense_init(
            r, i, o, in_axis="embed", out_axis="heads", use_bias=False
        )
    params["out"], _ = dense_init(
        rngs[3], num_heads * head_dim, dim, in_axis="heads", out_axis="embed",
        use_bias=False,
    )
    return params, attention_block_axes()


def encoder_block_axes():
    """Axes for one pre/post-LN encoder block (attention + GELU MLP) —
    shared by BERT and ViT so the stacked-layer tables can't drift."""
    return {
        "att": attention_block_axes(),
        "ln1": {"scale": (None,), "bias": (None,)},
        "wi": dense_axes("embed", "mlp"),
        "wo": dense_axes("mlp", "embed"),
        "ln2": {"scale": (None,), "bias": (None,)},
    }


def encoder_block_init(rng, dim: int, num_heads: int, head_dim: int,
                       mlp_hidden: int):
    """Init for :func:`encoder_block_axes`'s block."""
    r_att, r_mlp1, r_mlp2 = jax.random.split(rng, 3)
    att, _ = attention_block_init(r_att, dim, num_heads, head_dim)
    ln1, _ = layernorm_init(dim)
    ln2, _ = layernorm_init(dim)
    wi, _ = dense_init(r_mlp1, dim, mlp_hidden, in_axis="embed",
                       out_axis="mlp")
    wo, _ = dense_init(r_mlp2, mlp_hidden, dim, in_axis="mlp",
                       out_axis="embed")
    return {"att": att, "ln1": ln1, "wi": wi, "wo": wo, "ln2": ln2}


def mlp_block_axes():
    return {
        "wi": dense_axes("embed", "mlp", use_bias=False),
        "wg": dense_axes("embed", "mlp", use_bias=False),
        "wo": dense_axes("mlp", "embed", use_bias=False),
    }


def mlp_block_init(rng, dim: int, hidden: int):
    r1, r2, r3 = jax.random.split(rng, 3)
    params = {}
    for name, r, (i, o), (ia, oa) in [
        ("wi", r1, (dim, hidden), ("embed", "mlp")),
        ("wg", r2, (dim, hidden), ("embed", "mlp")),
        ("wo", r3, (hidden, dim), ("mlp", "embed")),
    ]:
        params[name], _ = dense_init(r, i, o, in_axis=ia, out_axis=oa,
                                     use_bias=False)
    return params, mlp_block_axes()


def clamped_swiglu(gate, up, limit: float):
    """``silu(min(gate, limit)) * clip(up, -limit, limit)``: SwiGLU under
    a published ``swiglu_limit``."""
    return (jax.nn.silu(jnp.minimum(gate, limit))
            * jnp.clip(up, -limit, limit))


def mlp_block_apply(params, x, *, rules: ShardingRules = DEFAULT_RULES,
                    gate_multiplier: float = 1.0,
                    down_multiplier: float = 1.0,
                    limit: Optional[float] = None):
    """Gated (SwiGLU) MLP with tp-sharded hidden dim: ``wi`` is the gate
    (multiplied by ``gate_multiplier`` before the silu), ``wg`` the up
    projection, ``wo`` the down projection (its product multiplied by
    ``down_multiplier``); ``limit`` clamps both (:func:`clamped_swiglu`)."""
    with scope("mlp"):
        gate = scaled(dense_apply(params["wi"], x), gate_multiplier)
        if limit is None:
            h = jax.nn.silu(gate) * dense_apply(params["wg"], x)
        else:
            h = clamped_swiglu(gate, dense_apply(params["wg"], x), limit)
        h = shard_constraint(h, "batch", "seq", "mlp", rules=rules)
        return scaled(dense_apply(params["wo"], h), down_multiplier)
