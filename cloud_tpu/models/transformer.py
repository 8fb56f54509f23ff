"""CloudLM: the flagship decoder-only transformer.

Architecture: pre-RMSNorm, RoPE, SwiGLU MLP (optionally MoE), tied layer
stack scanned with ``lax.scan``.  Every tensor carries logical sharding
axes, so one model definition runs under any mesh layout the planner
produces:

- ``tp``: heads and MLP hidden sharded (kernels' ``heads``/``mlp`` axes)
- ``fsdp``: parameter ``embed`` axes sharded (ZeRO-3)
- ``sp`` > 1: attention runs as ring attention over sequence blocks
- ``pp`` > 1 with rules ``extended(layers="pp")``: the layer stack runs as
  a GPipe microbatched pipeline (``parallel/pipeline.py``) — stage-sharded
  weights, ``config.num_microbatches`` microbatches shift-registered over
  the ``pp`` axis via ppermute
- ``ep`` > 1: MoE expert dim sharded

The reference shipped no models — its golden workloads were user Keras
scripts (core/tests/testdata/).  CloudLM is this framework's built-in
long-context workload and the BERT/LM benchmark backbone.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from cloud_tpu.models import delta_net as delta_lib, layers, mla as mla_lib
from cloud_tpu.models import moe as moe_lib, ssm as ssm_lib
from cloud_tpu.parallel import mesh as mesh_lib
from cloud_tpu.parallel import pipeline as pipeline_lib
from cloud_tpu.parallel.sharding import DEFAULT_RULES, ShardingRules, shard_constraint


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """Fixed scalars on the block's products (muP, as Falcon-H1's config
    publishes them).  The default is today's arithmetic bit for bit:
    ``embedding=None`` reads sqrt(dim), and a multiplier of 1 adds no
    operation (``layers.scaled``)."""

    embedding: Optional[float] = None  # None -> sqrt(dim)
    attention_in: float = 1.0   # the normed input of q, k and v
    attention_out: float = 1.0  # attention's output projection
    key: float = 1.0            # the k projection, before RoPE
    ssm_in: float = 1.0         # the normed input of the mixer
    ssm_out: float = 1.0        # the mixer's output projection
    #: The mixer's input projection, by segment: z | x | B | C | dt.
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    mlp_gate: float = 1.0       # the gate product, before the silu
    mlp_down: float = 1.0       # the down projection's product
    lm_head: float = 1.0        # the logits

    def __post_init__(self):
        # A config read back from JSON (models/export.py) brings a list:
        # keep the field hashable, as a jit-static config has to be.
        object.__setattr__(self, "ssm", tuple(self.ssm))


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    dim: int = 768
    num_heads: int = 12
    #: K/V heads (grouped-query attention: query head h reads K/V head
    #: ``h // (num_heads / num_kv_heads)``); None -> ``num_heads``.
    num_kv_heads: Optional[int] = None
    head_dim: int = 64
    mlp_hidden: int = 3072
    max_seq_len: int = 2048
    moe: Optional[moe_lib.MoeConfig] = None  # None -> dense SwiGLU MLP
    #: A Mamba-2 mixer beside attention in EVERY block: both read the
    #: same normed input and their outputs are summed before the one
    #: residual add (models/ssm.py).  None -> attention alone.
    ssm: Optional[ssm_lib.SsmConfig] = None
    #: Latent attention (models/mla.py): low-rank query and key/value
    #: projections, a cache row of one latent vector a token.  ``head_dim``
    #: and ``num_kv_heads`` then size nothing.  None -> q, k, v per head.
    latent: Optional[mla_lib.LatentConfig] = None
    #: The first this-many layers keep a dense SwiGLU MLP of width
    #: ``dense_mlp_hidden`` where the rest have experts (``moe``): they
    #: are their own stack, ``params["dense_layers"]``, run before the
    #: scanned ``params["layers"]``.
    leading_dense_layers: int = 0
    dense_mlp_hidden: Optional[int] = None
    #: Layers of several kinds: the period of their mixers, each one of
    #: :data:`LAYER_KINDS` ("delta": the gated delta rule, ``delta``;
    #: "latent": latent attention, ``latent``).  The leading dense layers
    #: take the tuple's first entries; the stack after them is whole
    #: periods, its first layer at a period's start, and is scanned a
    #: period at a time.  Every kind has a parameter stack and cache leaves
    #: of its own (``params["layers"][kind]``; models/generation.py).
    #: () -> every layer alike.
    layer_kinds: Tuple[str, ...] = ()
    #: The gated delta-rule mixer of the "delta" layers
    #: (models/delta_net.py).
    delta: Optional[delta_lib.DeltaNetConfig] = None
    #: A second RMSNorm on each sublayer's output before its residual add
    #: (``layernorm_type: pre_post``): ``ln1_post`` and ``ln2_post``.
    post_norms: bool = False
    #: Every SwiGLU's clamp, dense and experts' (``layers.clamped_swiglu``).
    #: None: no clamp.
    swiglu_limit: Optional[float] = None
    multipliers: Multipliers = Multipliers()
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    #: Which remat policy when ``remat`` is on: "full" (save carry only)
    #: or "dots" (save matmul outputs, recompute elementwise — backward
    #: never re-runs MXU work).  See layers.remat_wrap.
    remat_policy: str = "full"
    rope_base: float = 10000.0
    #: Microbatch count for pipeline parallelism (pp > 1); None -> pp size.
    #: Bubble fraction is (pp-1)/(M+pp-1), so raise this to amortize it.
    num_microbatches: Optional[int] = None
    #: Tie the LM head to the token embedding (logits = x @ table^T):
    #: halves the vocab-parameter footprint and is standard for smaller
    #: LMs; init()/param_logical_axes() then carry no "head" entry.
    tied_embeddings: bool = False
    #: With sp > 1: run causal attention as the load-balanced zig-zag ring
    #: (parallel/ring_attention.py).  apply() permutes tokens/positions
    #: into the zig-zag layout internally and loss_fn gathers next-token
    #: targets through the permutation — callers keep feeding sequences in
    #: natural order.  Incompatible with pp (the pipeline path).
    zigzag_sp: bool = False
    #: With sp > 1: run attention as sequence<->head all-to-alls instead
    #: of ring hops (the DeepSpeed-Ulysses pattern; layers.sharded_attention
    #: docstring).  Total comm is O(1/sp) of the activations vs the ring's
    #: O(sp) K/V hops, but local heads (H / tp) must divide by sp —
    #: indivisible configs silently use the ring.  Mutually exclusive
    #: with zigzag_sp.
    ulysses_sp: bool = False
    #: Compute the training loss with the fused linear cross-entropy
    #: (ops/fused_cross_entropy.py): the [B, T, V] logits tensor and its
    #: log-softmax residual are never materialized — the vocab is scanned
    #: in chunks with an online logsumexp, and the backward recomputes
    #: chunk logits.  Saves ~2*B*T*V*4 bytes of HBM at the cost of one
    #: extra head matmul; the win grows with vocab_size and seq_len.
    #: Training only — apply()/generation still produce real logits.
    fused_ce: bool = False

    def __post_init__(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"num_kv_heads={self.num_kv_heads} must divide "
                f"num_heads={self.num_heads}"
            )
        if self.leading_dense_layers and (
                self.moe is None or self.dense_mlp_hidden is None
                or not 0 < self.leading_dense_layers < self.num_layers):
            raise ValueError(
                "leading_dense_layers needs expert layers after them (moe, "
                "fewer than num_layers) and their width (dense_mlp_hidden)"
            )
        object.__setattr__(self, "layer_kinds", tuple(self.layer_kinds))
        if self.layer_kinds:
            period = len(self.layer_kinds)
            mixers = {"delta": self.delta, "latent": self.latent}
            if (any(mixers.get(kind) is None for kind in self.layer_kinds)
                    or self.ssm is not None
                    or self.leading_dense_layers > period
                    or (self.num_layers - self.leading_dense_layers)
                    % period):
                raise ValueError(
                    f"layer_kinds {self.layer_kinds}: each kind needs its "
                    f"mixer's configuration (of {LAYER_KINDS}), no ssm, and "
                    "the layers after the leading dense ones whole periods "
                    "(the dense ones at most one)")

    @property
    def kv_heads(self) -> int:
        return (self.num_heads if self.num_kv_heads is None
                else self.num_kv_heads)

    @property
    def dense_stack(self) -> "TransformerConfig":
        """The configuration of the leading dense layers alone."""
        return dataclasses.replace(
            self, moe=None, mlp_hidden=self.dense_mlp_hidden,
            num_layers=self.leading_dense_layers, leading_dense_layers=0,
            dense_mlp_hidden=None,
            layer_kinds=self.layer_kinds[:self.leading_dense_layers])

    def stack_kinds(self, count: int) -> Tuple[str, ...]:
        """The kinds of a stack's ``count`` layers, from its first: the
        period repeated (this configuration is the stack's own:
        :attr:`dense_stack` for the leading dense layers)."""
        return tuple(self.layer_kinds[l % len(self.layer_kinds)]
                     for l in range(count))

    def layers_of(self, kind: str) -> int:
        """How many of the model's layers have a ``kind`` mixer; without
        ``layer_kinds``, every layer has the model's one mixer."""
        if not self.layer_kinds:
            return self.num_layers
        dense = self.leading_dense_layers
        return (self.layer_kinds[:dense].count(kind)
                + self.stack_kinds(self.num_layers - dense).count(kind))

    def kind_config(self, kind: str) -> "TransformerConfig":
        """The configuration a ``kind`` layer runs as: this one with that
        kind's mixer alone."""
        return dataclasses.replace(
            self, layer_kinds=(), num_layers=self.layers_of(kind),
            leading_dense_layers=0, dense_mlp_hidden=None,
            delta=self.delta if kind == "delta" else None,
            latent=self.latent if kind == "latent" else None)

    def scaled(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)


#: The mixers ``TransformerConfig.layer_kinds`` names.
LAYER_KINDS = ("delta", "latent")


#: Tiny config for tests/dry-runs.
TINY = TransformerConfig(
    vocab_size=256, num_layers=4, dim=64, num_heads=4, head_dim=16,
    mlp_hidden=128, max_seq_len=128, remat=False,
)

#: ~124M-parameter single-chip benchmark config (GPT-2-small shape).
SMALL = TransformerConfig(
    vocab_size=32000, num_layers=12, dim=768, num_heads=12, head_dim=64,
    mlp_hidden=3072, max_seq_len=1024,
)


def _layer_init(rng, config: TransformerConfig):
    """One layer (a kind's, for a model with ``layer_kinds``: its
    :meth:`~TransformerConfig.kind_config`)."""
    r_att, r_mlp, r_ssm, _ = jax.random.split(rng, 4)
    if config.delta is not None:
        mixer, mixer_axes = delta_lib.delta_init(r_att, config.dim,
                                                 config.delta)
        params, axes = {"delta": mixer}, {"delta": mixer_axes}
    elif config.latent is not None:
        att, att_axes = mla_lib.attention_init(
            r_att, config.dim, config.num_heads, config.latent)
        params, axes = {"att": att}, {"att": att_axes}
    else:
        att, att_axes = layers.attention_block_init(
            r_att, config.dim, config.num_heads, config.head_dim,
            config.num_kv_heads,
        )
        params, axes = {"att": att}, {"att": att_axes}
    ln1, ln1_axes = layers.rmsnorm_init(config.dim)
    ln2, ln2_axes = layers.rmsnorm_init(config.dim)
    if config.moe is not None:
        mlp, mlp_axes = moe_lib.moe_mlp_init(
            r_mlp, config.dim, config.mlp_hidden, config.moe
        )
    else:
        mlp, mlp_axes = layers.mlp_block_init(r_mlp, config.dim, config.mlp_hidden)
    params.update(ln1=ln1, mlp=mlp, ln2=ln2)
    axes.update(ln1=ln1_axes, mlp=mlp_axes, ln2=ln2_axes)
    if config.post_norms:
        for name in ("ln1_post", "ln2_post"):
            params[name], axes[name] = layers.rmsnorm_init(config.dim)
    if config.ssm is not None:
        params["ssm"], axes["ssm"] = ssm_lib.ssm_init(
            r_ssm, config.dim, config.ssm
        )
    return params, axes


def _stack_init(rngs, config: TransformerConfig):
    """A stack's layers from ``rngs``, stacked on a leading axis; with
    ``layer_kinds``, one such stack a kind (``config`` is the stack's
    own)."""
    if not config.layer_kinds:
        return jax.vmap(lambda r: _layer_init(r, config)[0])(rngs)
    kinds = config.stack_kinds(rngs.shape[0])
    return {kind: jax.vmap(
        lambda r, kind=kind: _layer_init(r, config.kind_config(kind))[0])(
            rngs[jnp.asarray([l for l, k in enumerate(kinds) if k == kind])])
        for kind in sorted(set(kinds))}


def init(rng, config: TransformerConfig) -> Dict[str, Any]:
    r_embed, r_layers, r_head, r_ln = jax.random.split(rng, 4)
    embed, _ = layers.embedding_init(r_embed, config.vocab_size, config.dim)
    layer_rngs = jax.random.split(r_layers, config.num_layers)
    dense = config.leading_dense_layers
    stacked = _stack_init(layer_rngs[dense:], config)
    ln_f, _ = layers.rmsnorm_init(config.dim)
    params = {"embed": embed, "layers": stacked, "ln_f": ln_f}
    if dense:
        params["dense_layers"] = _stack_init(layer_rngs[:dense],
                                             config.dense_stack)
    if not config.tied_embeddings:
        params["head"], _ = layers.dense_init(
            r_head, config.dim, config.vocab_size, in_axis="embed",
            out_axis="vocab", use_bias=False,
        )
    return params


def param_logical_axes(config: TransformerConfig):
    """Pytree congruent with init()'s output; leaves = logical axis tuples.

    The stacked layer dim gets the ``layers`` logical axis (maps to ``pp``
    under pipeline rules, replicated otherwise).
    """
    def stacked_axes(config):
        if config.layer_kinds:
            return {kind: stacked_axes(config.kind_config(kind))
                    for kind in sorted(set(config.layer_kinds))}
        _, layer_axes = _layer_init_axes(config)
        return jax.tree_util.tree_map(
            lambda ax: ("layers",) + tuple(ax), layer_axes,
            is_leaf=lambda x: isinstance(x, tuple),
        )

    axes = {
        "embed": {"table": ("vocab", "embed")},
        "layers": stacked_axes(config),
        "ln_f": {"scale": (None,)},
    }
    if config.leading_dense_layers:
        axes["dense_layers"] = stacked_axes(config.dense_stack)
    if not config.tied_embeddings:
        axes["head"] = {"kernel": ("embed", "vocab")}
    return axes


def _layer_init_axes(config: TransformerConfig):
    # Single source of truth: the same axes tables the layer init functions
    # return (layers.py / moe.py companions), composed per layer.
    if config.moe is not None:
        mlp_axes = moe_lib.moe_mlp_axes(config.moe)
    else:
        mlp_axes = layers.mlp_block_axes()
    if config.delta is not None:
        axes = {"delta": delta_lib.delta_axes()}
    else:
        axes = {"att": (mla_lib.attention_axes(config.latent)
                        if config.latent is not None
                        else layers.attention_block_axes())}
    axes.update(ln1={"scale": (None,)}, mlp=mlp_axes, ln2={"scale": (None,)})
    if config.post_norms:
        axes.update(ln1_post={"scale": (None,)}, ln2_post={"scale": (None,)})
    if config.ssm is not None:
        axes["ssm"] = ssm_lib.ssm_axes()
    return None, axes


def qkv_project(att_params, x, positions, config: TransformerConfig):
    """RoPE'd q/k and v projections [B, T, H, hd] — shared between the
    training forward pass and the generation path's prefill/decode (which
    must produce bit-identical projections for the KV cache to be
    equivalent to a full re-forward)."""
    mult = config.multipliers

    def proj(p, heads):
        return layers.dense_heads_apply(p, x, heads, config.head_dim)

    with layers.scope("attn_proj"):
        x = layers.scaled(x, mult.attention_in)
        q = layers.rotary_embedding(
            proj(att_params["q"], config.num_heads), positions,
            base=config.rope_base,
        )
        k = layers.rotary_embedding(
            layers.scaled(proj(att_params["k"], config.kv_heads), mult.key),
            positions, base=config.rope_base,
        )
        v = proj(att_params["v"], config.kv_heads)
    return q, k, v


def repeat_kv(k, v, config: TransformerConfig):
    """K/V [B, T, kv_heads, hd] at the query heads' count, for an
    attention that pairs heads one to one (query head h reads K/V head
    ``h // group``).  The cache keeps them unrepeated."""
    group = config.num_heads // config.kv_heads
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def attention_out(att_params, attended, config: TransformerConfig):
    """Attention's output projection on ``attended`` [B, T, H, hd]."""
    b, t = attended.shape[:2]
    with layers.scope("attn_out"):
        out = layers.dense_apply(att_params["out"],
                                 attended.reshape(b, t, -1))
        return layers.scaled(out, config.multipliers.attention_out)


def mlp_apply(mlp_params, y, config: TransformerConfig, rules):
    """The dense SwiGLU MLP with the configuration's multipliers."""
    mult = config.multipliers
    return layers.mlp_block_apply(
        mlp_params, y, rules=rules, gate_multiplier=mult.mlp_gate,
        down_multiplier=mult.mlp_down, limit=config.swiglu_limit,
    )


def embed_tokens(params, tokens, config: TransformerConfig, rules, mesh):
    """Token embeddings [..., D] in the compute dtype, times the
    embedding multiplier (sqrt(dim) unless the configuration gives
    one)."""
    with layers.scope("embed"):
        x = layers.embedding_apply(params["embed"], tokens,
                                   dtype=config.dtype, rules=rules,
                                   mesh=mesh)
        scale = config.multipliers.embedding
        return x * (math.sqrt(config.dim) if scale is None else scale)


def _attention(
    x, att_params, config: TransformerConfig, rules: ShardingRules,
    mesh, positions,
):
    if config.latent is not None:
        attended = mla_lib.expanded_attention(
            att_params, *mla_lib.project(att_params, x, positions, config),
            None, config, rules=rules, mesh=mesh)
        return mla_lib.attention_out(att_params, attended, config)
    q, k, v = qkv_project(att_params, x, positions, config)
    k, v = repeat_kv(k, v, config)
    q = shard_constraint(q, "batch", "seq", "heads", None, rules=rules, mesh=mesh)
    k = shard_constraint(k, "batch", "seq", "heads", None, rules=rules, mesh=mesh)
    v = shard_constraint(v, "batch", "seq", "heads", None, rules=rules, mesh=mesh)

    with layers.scope("attn_read"):
        attended = layers.sharded_attention(
            q, k, v, causal=True, rules=rules, mesh=mesh,
            zigzag=config.zigzag_sp, ulysses=config.ulysses_sp,
        )

    return attention_out(att_params, attended, config)


def _layer_compute(layer_params, x, aux, *, config, rules, mesh, positions):
    """One transformer block on (x [B, T, D], aux scalar) — the single
    source of truth shared by the scanned and pipelined layer stacks."""
    y = layers.rmsnorm_apply(layer_params["ln1"], x, eps=config.norm_eps)
    mixed = _attention(y, layer_params["att"], config, rules, mesh, positions)
    if config.ssm is not None:
        # The whole buffer is real tokens here; the states are the
        # generation path's business.
        lens = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        ssm_out, _, _ = ssm_lib.ssd_prefill(
            layer_params["ssm"], y, jnp.ones(x.shape[:2], jnp.int32), lens,
            config.ssm, config.multipliers, config.norm_eps,
        )
        mixed = mixed + ssm_out
    x = x + mixed
    y = layers.rmsnorm_apply(layer_params["ln2"], x, eps=config.norm_eps)
    if moe_lib.counts_routing(config.moe):
        mlp_out, _ = moe_lib.dropless_mlp_apply(layer_params["mlp"], y,
                                                config.moe,
                                                limit=config.swiglu_limit)
    elif config.moe is not None:
        mlp_out, layer_aux = moe_lib.moe_mlp_apply(
            layer_params["mlp"], y, config.moe
        )
        aux = aux + layer_aux
    else:
        mlp_out = mlp_apply(layer_params["mlp"], y, config, rules)
    x = x + mlp_out
    x = shard_constraint(x, "batch", "seq", "act_embed", rules=rules, mesh=mesh)
    return x, aux


def _is_pipelined(config: TransformerConfig, rules: ShardingRules, mesh) -> bool:
    if mesh is None:
        return False
    if dict(mesh.shape).get(mesh_lib.AXIS_PP, 1) <= 1:
        return False
    # .get, not .assignment(): custom rules tables without a "layers" entry
    # predate pipelining and must keep running the scan path.
    assignment = rules.rules.get("layers")
    if assignment is None:
        return False
    axes = assignment if isinstance(assignment, tuple) else (assignment,)
    return mesh_lib.AXIS_PP in axes


def _pipelined_stack(params, x, config, rules, mesh):
    """GPipe microbatched layer stack over the pp axis (pipeline.py)."""
    b, t, d = x.shape
    pp = dict(mesh.shape)[mesh_lib.AXIS_PP]
    m = config.num_microbatches or pp
    if b % m:
        raise ValueError(
            f"Global batch {b} not divisible by num_microbatches={m} "
            f"(pp={pp}); set config.num_microbatches accordingly."
        )
    x_mbs = x.reshape(m, b // m, t, d)
    x_mbs = shard_constraint(
        x_mbs, None, "batch", "seq", "act_embed", rules=rules, mesh=mesh
    )
    aux_mbs = jnp.zeros((m,), jnp.float32)

    def pipe_layer(layer_params, carry):
        xc, aux = carry
        mb, tc = xc.shape[0], xc.shape[1]
        positions = jnp.broadcast_to(jnp.arange(tc), (mb, tc))
        return _layer_compute(
            layer_params, xc, aux, config=config, rules=rules, mesh=mesh,
            positions=positions,
        )

    body = layers.remat_wrap(pipe_layer, config.remat,
                             config.remat_policy)
    x_mbs, aux_mbs = pipeline_lib.pipeline(
        body, params["layers"], (x_mbs, aux_mbs), mesh=mesh
    )
    x = x_mbs.reshape(b, t, d)
    x = shard_constraint(x, "batch", "seq", "act_embed", rules=rules, mesh=mesh)
    # Per-microbatch aux losses average to keep pp-independent scale
    # (gradient-accumulation semantics; batch-coupled aux differs from the
    # full-batch value by construction, like any microbatched MoE).
    return x, jnp.sum(aux_mbs) / m


def _zigzag_active(config: TransformerConfig, mesh) -> bool:
    if not config.zigzag_sp or mesh is None:
        return False
    return dict(mesh.shape).get(mesh_lib.AXIS_SP, 1) > 1


def apply(
    params,
    tokens: jnp.ndarray,
    config: TransformerConfig,
    *,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward pass: tokens [B, T] -> (logits [B, T, V], aux loss scalar).

    With ``config.zigzag_sp`` active, logits come back in the ZIG-ZAG
    sequence order (slot j corresponds to global position
    ``zigzag_indices(T, sp)[j]``) — ``loss_fn`` accounts for it; callers
    reading logits directly must gather through the inverse permutation.
    """
    x, aux = apply_hidden(params, tokens, config, rules=rules, mesh=mesh)
    logits = lm_logits(params, x, config)
    logits = shard_constraint(logits, "batch", "seq", "vocab", rules=rules,
                              mesh=mesh)
    return logits, aux


def apply_hidden(
    params,
    tokens: jnp.ndarray,
    config: TransformerConfig,
    *,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward pass up to the final norm: tokens -> (hidden [B, T, D], aux).

    The pre-head half of :func:`apply`, exposed so the fused
    cross-entropy loss (``config.fused_ce``) can consume hidden states
    without the head projection ever materializing [B, T, V] logits.
    """
    if config.layer_kinds:
        raise NotImplementedError(
            "a model with layer_kinds is served (models/generation.py), "
            "not trained: its reference forward pass is the benchmark's")
    mesh = mesh if mesh is not None else mesh_lib.get_global_mesh()
    b, t = tokens.shape
    zigzag = _zigzag_active(config, mesh)
    if config.zigzag_sp and config.ulysses_sp:
        raise ValueError(
            "zigzag_sp and ulysses_sp are mutually exclusive sp strategies"
        )
    if zigzag:
        if _is_pipelined(config, rules, mesh):
            raise ValueError("zigzag_sp is incompatible with pp pipelining")
        from cloud_tpu.parallel.ring_attention import zigzag_indices

        sp = dict(mesh.shape)[mesh_lib.AXIS_SP]
        perm = zigzag_indices(t, sp)
        tokens = jnp.take(tokens, perm, axis=1)
    x = embed_tokens(params, tokens, config, rules, mesh)
    x = shard_constraint(x, "batch", "seq", "act_embed", rules=rules, mesh=mesh)

    if _is_pipelined(config, rules, mesh):
        if config.leading_dense_layers:
            raise NotImplementedError(
                "pp pipelining runs ONE stack of identical layers; "
                "leading_dense_layers makes two")
        x, aux = _pipelined_stack(params, x, config, rules, mesh)
    else:
        positions = (
            jnp.broadcast_to(perm, (b, t)) if zigzag
            else jnp.broadcast_to(jnp.arange(t), (b, t))
        )

        def stack(carry, stack_params, config):
            def layer_body(carry, layer_params):
                x, aux = carry
                x, aux = _layer_compute(
                    layer_params, x, aux, config=config, rules=rules,
                    mesh=mesh, positions=positions,
                )
                return (x, aux), None

            body = layers.remat_wrap(layer_body, config.remat,
                                     config.remat_policy)
            return jax.lax.scan(body, carry, stack_params)[0]

        carry = (x, jnp.zeros((), jnp.float32))
        if config.leading_dense_layers:
            carry = stack(carry, params["dense_layers"], config.dense_stack)
        x, aux = stack(carry, params["layers"], config)

    x = layers.rmsnorm_apply(params["ln_f"], x, eps=config.norm_eps)
    return x, aux


def head_table(params, config: TransformerConfig):
    """``(table, layout)`` of the vocabulary projection — THE tying
    decision, single-sourced for :func:`lm_logits` (apply/generation)
    and the fused-CE loss so the two can't drift.  Layout "vd" = tied
    embedding table [V, D] (logits = x @ table^T); "dv" = dense head
    kernel [D, V]."""
    if config.tied_embeddings:
        embed = params["embed"]
        if "table_q" in embed:
            # Weight-only int8 (models/quantization.py): materialize at
            # full width for table consumers (fused_ce's chunked scan);
            # lm_logits takes the post-scale fast path instead.
            return layers.materialize_matrix(embed, "table", jnp.float32), "vd"
        return embed["table"], "vd"
    head = params["head"]
    extra = set(head) - {"kernel", "kernel_q", "kernel_scale"}
    if extra:
        # A bias (or any new head param) would be silently dropped by a
        # bare-table consumer; fail loudly instead — quantized or not.
        raise NotImplementedError(
            f"head has params beyond 'kernel' ({sorted(extra)}); "
            "head_table/fused_ce support bias-free heads only"
        )
    if "kernel_q" in head:
        return layers.materialize_matrix(head, "kernel", jnp.float32), "dv"
    return head["kernel"], "dv"


def lm_logits(params, x, config: TransformerConfig) -> jnp.ndarray:
    """Final vocabulary projection in f32, times the head's multiplier."""
    with layers.scope("head"):
        return layers.scaled(_head_product(params, x, config),
                             config.multipliers.lm_head)


def _head_product(params, x, config: TransformerConfig) -> jnp.ndarray:
    """The vocabulary projection in f32 (tying via :func:`head_table`,
    shared with the generation path and the fused-CE loss).

    Quantized heads take the post-scale path — ``(x @ q) * scale`` —
    so the int8 matrix feeds the matmul directly: a full-width
    ``q * scale`` intermediate would be loop-invariant inside the decode
    scan, and LICM hoisting it would stream the wide table every token.
    """
    x = x.astype(jnp.float32)
    if config.tied_embeddings and "table_q" in params["embed"]:
        embed = params["embed"]
        logits = jnp.einsum(
            "...d,vd->...v", x, embed["table_q"].astype(jnp.float32)
        )
        return logits * embed["table_scale"][:, 0].astype(jnp.float32)
    if not config.tied_embeddings and "kernel_q" in params["head"]:
        head = params["head"]
        extra = set(head) - {"kernel_q", "kernel_scale"}
        if extra:
            raise NotImplementedError(
                f"quantized head has extra params {sorted(extra)}"
            )
        logits = jnp.einsum(
            "...d,dv->...v", x, head["kernel_q"].astype(jnp.float32)
        )
        return logits * head["kernel_scale"][0].astype(jnp.float32)
    table, layout = head_table(params, config)
    table = table.astype(jnp.float32)
    if layout == "vd":
        return jnp.einsum("...d,vd->...v", x, table)
    return jnp.einsum("...d,dv->...v", x, table)


def loss_fn(
    params,
    batch: Dict[str, jnp.ndarray],
    config: TransformerConfig,
    *,
    rules: ShardingRules = DEFAULT_RULES,
    mesh=None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy; batch = {"tokens": [B, T]} (optionally
    "loss_mask" [B, T], gating the loss at each TARGET position)."""
    tokens = batch["tokens"]
    mesh = mesh if mesh is not None else mesh_lib.get_global_mesh()
    if config.fused_ce:
        if config.multipliers.lm_head != 1.0:
            raise NotImplementedError(
                "fused_ce reads the head's table directly and knows no "
                "lm_head multiplier"
            )
        hidden, aux = apply_hidden(params, tokens, config, rules=rules,
                                   mesh=mesh)
        # Pin the hidden states' layout before the chunked-CE scan:
        # without the constraint GSPMD is free to guess a layout for the
        # chunk intermediates (the [B, T, V] logits never materialize to
        # anchor one), and a bad guess inserts resharding inside the
        # vocab-chunk loop.  Mirrors the constraint `apply` puts on its
        # full logits (ADVICE round 5).
        hidden = shard_constraint(hidden, "batch", "seq", "act_embed",
                                  rules=rules, mesh=mesh)
        logits = None
    else:
        logits, aux = apply(params, tokens, config, rules=rules, mesh=mesh)
    mask = batch.get("loss_mask")
    t = tokens.shape[1]

    # Both layouts reduce to: slot j predicts global position pos[j] + 1,
    # with the final position carrying no target.  Natural order is the
    # identity permutation; zig-zag gathers targets through the
    # permutation rather than unpermuting the [B, T, V] logits (which
    # would all-to-all across sp shards).
    if _zigzag_active(config, mesh):
        from cloud_tpu.parallel.ring_attention import zigzag_indices

        pos = zigzag_indices(t, dict(mesh.shape)[mesh_lib.AXIS_SP])
    else:
        pos = jnp.arange(t)
    target_idx = jnp.clip(pos + 1, max=t - 1)
    targets = jnp.take(tokens, target_idx, axis=1)
    weights = (pos < t - 1).astype(jnp.float32)[None, :]  # [1, T]
    if mask is not None:
        weights = weights * jnp.take(
            mask.astype(jnp.float32), target_idx, axis=1
        )
    if config.fused_ce:
        from cloud_tpu.ops.fused_cross_entropy import (
            fused_linear_cross_entropy,
        )

        table, layout = head_table(params, config)
        ce = fused_linear_cross_entropy(
            hidden, table, targets, table_layout=layout, weights=weights,
        )
    else:
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            log_probs, targets[..., None], axis=-1
        )[..., 0]
        weights = jnp.broadcast_to(weights, nll.shape)
        ce = jnp.sum(nll * weights) / jnp.clip(jnp.sum(weights), 1.0)
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}
