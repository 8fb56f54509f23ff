"""Mixture-of-experts MLP, two routes.

**Capacity** (:func:`moe_mlp_apply`, the training path's): GShard-style
dense dispatch.  The dispatch/combine tensors keep everything as large
einsums — exactly what the MXU wants — and the stacked expert weights carry
the ``expert`` logical axis so they shard over the ``ep`` mesh axis.  Tokens
overflowing an expert's capacity are dropped (standard top-k capacity
routing).

**Dropless share** (:func:`dropless_mlp_apply`, ``MoeConfig.dropless``; the
serving path's): the layer is told which experts it HOLDS
(``[expert_offset, expert_offset + experts_held)`` of ``num_experts``, one
chip's share under expert parallelism), routes every token over all
``num_experts`` at the router's published width, and computes its own
experts' part of the result for the tokens routed to them, plus what every
chip computes alike (a shared expert).  What the absent experts would add
is left out and nothing stands in for their chips or their exchange.  No
token is ever dropped: the (token, choice) pairs that land here are sorted
by expert and go through three grouped matrix products
(``ops.grouped_matmul``) in blocks of ``ROW_BLOCK`` rows, as many blocks as
the routing asks for.  An expert that got no token is never read.  A
block's rows find their way back to their tokens as this file's other
route's do, through the MXU: one product with the block's 0/1 placement
matrix (:func:`_placed`), exact, where a scatter-add went a row at a time.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from cloud_tpu.models import layers


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    #: Router z-loss (ST-MoE): penalizes ``logsumexp(logits)^2`` to keep
    #: router logits small/stable in bf16 training.  0 disables.
    z_loss_weight: float = 0.0
    #: The dropless share (module docstring); everything below it needs it.
    dropless: bool = False
    #: Experts held here, ``[expert_offset, expert_offset + experts_held)``
    #: of ``num_experts``; None -> all of them.
    experts_held: Optional[int] = None
    expert_offset: int = 0
    #: The router's scores: "softmax" over the experts, or "sigmoid" of
    #: each logit alone.
    score: str = "softmax"
    #: The chosen scores are renormalised to sum 1, then times this.
    routed_scale: float = 1.0
    #: Width of the expert every token goes through beside the routed
    #: ones (0: none).
    shared_hidden: int = 0
    #: A parameter ``bias`` [num_experts] added to the scores for the
    #: CHOICE of experts alone (the aux-loss-free balancing bias); the
    #: weights come from the scores without it.
    selection_bias: bool = False

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router score {self.score!r}")
        share = (self.experts_held is not None or self.expert_offset
                 or self.score != "softmax" or self.routed_scale != 1.0
                 or self.shared_hidden or self.selection_bias)
        if share and not self.dropless:
            raise ValueError(
                "experts_held, expert_offset, score, routed_scale, "
                "shared_hidden and selection_bias describe the dropless "
                "share (MoeConfig.dropless); the capacity route knows none "
                "of them")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(
                f"experts [{self.expert_offset}, "
                f"{self.expert_offset + self.held}) are not among "
                f"{self.num_experts}")

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)


def moe_mlp_init(rng, dim: int, hidden: int, cfg: MoeConfig):
    r_router, r_wi, r_wg, r_wo = jax.random.split(rng, 4)
    router, _ = layers.dense_init(
        r_router, dim, cfg.num_experts, in_axis="embed", out_axis=None,
        use_bias=False,
    )

    def stack_init(r, i, o):
        rs = jax.random.split(r, cfg.held)
        return jax.vmap(
            lambda rr: layers.dense_init(
                rr, i, o, in_axis=None, out_axis=None, use_bias=False
            )[0]["kernel"]
        )(rs)

    params = {
        "router": router,
        "wi": stack_init(r_wi, dim, hidden),
        "wg": stack_init(r_wg, dim, hidden),
        "wo": stack_init(r_wo, hidden, dim),
    }
    if cfg.selection_bias:
        params["bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    if cfg.shared_hidden:
        params["shared"], _ = layers.mlp_block_init(
            jax.random.fold_in(rng, 4), dim, cfg.shared_hidden)
    return params, moe_mlp_axes(cfg)


def moe_mlp_axes(cfg: Optional[MoeConfig] = None):
    axes = {
        "router": layers.dense_axes("embed", None, use_bias=False),
        "wi": ("expert", "embed", "mlp"),
        "wg": ("expert", "embed", "mlp"),
        "wo": ("expert", "mlp", "embed"),
    }
    if cfg is not None and cfg.selection_bias:
        axes["bias"] = (None,)
    if cfg is not None and cfg.shared_hidden:
        axes["shared"] = layers.mlp_block_axes()
    return axes


def _capacity(tokens_per_batch: int, cfg: MoeConfig) -> int:
    cap = int(tokens_per_batch * cfg.capacity_factor * cfg.top_k / cfg.num_experts)
    return max(cap, cfg.top_k)


def moe_mlp_apply(
    params, x: jnp.ndarray, cfg: MoeConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply the MoE MLP to ``x`` [B, T, D].

    Returns (output [B, T, D], scalar load-balancing aux loss).
    """
    b, t, d = x.shape
    e = cfg.num_experts
    c = _capacity(t, cfg)

    with layers.scope("moe_route"):
        router_logits = layers.dense_apply(params["router"], x,
                                           dtype=jnp.float32)
        gates = jax.nn.softmax(router_logits, axis=-1)  # [B, T, E]

        # Top-k expert choice per token, gates renormalized over the
        # chosen k.
        top_gates, top_idx = jax.lax.top_k(gates, cfg.top_k)  # [B, T, K]
        top_gates = top_gates / jnp.clip(
            jnp.sum(top_gates, axis=-1, keepdims=True), 1e-9
        )

        # Position of each (token, choice) in its expert's buffer, via
        # cumsum over the flattened (T*K) routing sequence per batch row.
        choice_mask = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
        flat_mask = choice_mask.reshape(b, t * cfg.top_k, e)
        pos_in_expert = (
            jnp.cumsum(flat_mask, axis=1) - flat_mask
        ).reshape(b, t, cfg.top_k, e)
        within_capacity = pos_in_expert < c
        keep = choice_mask * within_capacity

        # combine[b,t,e,cap]: gate weight of token t's slot in expert e.
        slot_one_hot = jax.nn.one_hot(
            pos_in_expert.astype(jnp.int32), c, dtype=jnp.float32
        )
        combine = jnp.einsum(
            "btke,btk,btkec->btec", keep, top_gates.astype(jnp.float32),
            slot_one_hot
        )
        dispatch = (combine > 0.0).astype(x.dtype)  # [B, T, E, C]
        expert_in = jnp.einsum("btec,btd->becd", dispatch, x)

    with layers.scope("moe_experts"):
        # materialize_matrix: quantization-aware (wi/wg/wo may be stored
        # int8 + per-(expert, out) scales — models/quantization.py).
        wi = layers.materialize_matrix(params, "wi", x.dtype)
        wg = layers.materialize_matrix(params, "wg", x.dtype)
        wo = layers.materialize_matrix(params, "wo", x.dtype)
        h = jax.nn.silu(
            jnp.einsum("becd,edh->bech", expert_in, wi)
        ) * jnp.einsum("becd,edh->bech", expert_in, wg)
        expert_out = jnp.einsum("bech,ehd->becd", h, wo)
    with layers.scope("moe_combine"):
        out = jnp.einsum("btec,becd->btd", combine.astype(x.dtype),
                         expert_out)

    with layers.scope("moe_route"):
        # Load-balance loss: encourages uniform routing (Switch/GShard
        # form).
        fraction_routed = jnp.mean(choice_mask[..., 0, :], axis=(0, 1))
        mean_gate = jnp.mean(gates, axis=(0, 1))
        aux = jnp.sum(fraction_routed * mean_gate) * e * cfg.aux_loss_weight
        if cfg.z_loss_weight:
            z = jax.scipy.special.logsumexp(router_logits, axis=-1)  # [B, T]
            aux = aux + cfg.z_loss_weight * jnp.mean(z * z)
    return out, aux


# -- the dropless share ---------------------------------------------------

#: Rows (assignments) a block of the dropless route's grouped products: a
#: layer call with more (token, choice) pairs than this walks the ones
#: that landed here in blocks, as many as there are, so nothing is dropped
#: and nothing is sized for the worst routing.
ROW_BLOCK = 1024

#: What a dropless layer call counts of its routing, as the head of an
#: int32 vector [ROUTING_HEAD + experts_held]: the (token, choice)
#: assignments made, those that landed on an expert held here, the held
#: experts that got any; then the tokens each held expert got.
ROUTING_HEAD = 3


def counts_routing(cfg: Optional[MoeConfig]) -> bool:
    return cfg is not None and cfg.dropless


def route(params, flat, cfg: MoeConfig):
    """The router on tokens ``flat`` [N, D]: the chosen experts [N, K]
    (of all ``num_experts``) and their weights [N, K] float32 —
    renormalised over the chosen and times ``routed_scale``.  Scores in
    float32; with ``selection_bias`` the CHOICE is by score + bias and the
    weights by the score alone."""
    with layers.scope("moe_route"):
        kernel = params["router"]["kernel"]
        if kernel.dtype == flat.dtype == jnp.bfloat16:
            # bfloat16 products are exact in float32: one MXU pass gives what
            # the float32 product would.
            logits = jnp.einsum("nd,de->ne", flat, kernel,
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum(
                "nd,de->ne", flat.astype(jnp.float32),
                kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if cfg.score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        chosen_by = scores + params["bias"] if cfg.selection_bias else scores
        _, idx = jax.lax.top_k(chosen_by, cfg.top_k)
        weights = jnp.take_along_axis(scores, idx, axis=-1)
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), weights * cfg.routed_scale


#: The routed experts' stacked matrices among an expert layer's
#: parameters: a caller that scans the layers hands them over WHOLE,
#: with the layer's index (:func:`dropless_mlp_apply`), so that the
#: grouped products read them in place.
EXPERT_LEAVES = ("wi", "wg", "wo")


# Jitted (and inlined) for its tracing cache alone: a layer call traces a
# block twice at one shape (the first block and the loop's body), and
# tracing its three kernel calls is what a block costs at set-up.
@functools.partial(jax.jit, inline=True)
def _held_experts(params, rows, sizes, layer, weights):
    """SwiGLU of each row through ITS expert, times the row's weight:
    ``rows`` [R, D] sorted by held expert, ``sizes`` [held] rows an
    expert, ``weights`` [R] float32; the experts' matrices one layer's
    [E, .., ..], or every layer's with ``layer``.  The weight is a scalar
    a row and commutes with the last product: it goes on ``hidden`` in
    float32, before the cast that was always there, so the rows come out
    weighted under the two roundings they had unweighted."""
    from cloud_tpu.ops.grouped_matmul import grouped_matmul

    def product(x, name):
        w = layers.materialize_matrix(params, name, x.dtype)
        return grouped_matmul(x, w, sizes, layer=layer)

    gate = product(rows, "wi").astype(jnp.float32)
    hidden = jax.nn.silu(gate) * product(rows, "wg").astype(jnp.float32)
    return product((hidden * weights[:, None]).astype(rows.dtype), "wo")


def _exact(dtype):
    """The precision at which a product with a 0/1 matrix returns its
    other operand's numbers as they are: bfloat16 products are exact in
    the float32 they accumulate in; float32 takes the MXU's six passes."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _placement(tokens, valid, n: int, dtype):
    """A block's 0/1 placement matrix [n, R]: ``place[t, r]`` is 1 where
    sorted row ``r`` is a row of token ``t`` (``tokens[r] == t``) and
    ``valid[r]`` (it landed), else 0.  Both of a block's index operations
    over rows are products with it, which the MXU runs at its pace
    whatever the rows' order."""
    at = jnp.arange(n, dtype=tokens.dtype)[:, None]
    return ((tokens[None, :] == at) & valid[None, :]).astype(dtype)


def _drawn(flat, tokens, place):
    """Each row's token out of ``flat`` [n, D], [R, D]: a gather; where
    the rows are no fewer than the tokens (a decode step draws 512 from
    64) the placement's transpose times ``flat``: one nonzero a row, so
    exact, and zeros for a row that is not valid."""
    if flat.shape[0] > tokens.shape[0]:
        return jnp.take(flat, tokens, axis=0)
    return jax.lax.dot_general(
        place, flat, (((0,), (0,)), ((), ())), precision=_exact(flat.dtype),
        preferred_element_type=jnp.float32).astype(flat.dtype)


def _placed(y, valid, place):
    """The rows ``y`` [R, D] summed onto their tokens, [n, D] float32:
    ``place @ y``, ONE product in place of a scatter-add, which the chip
    runs one row after another whatever number of them landed.  A row
    that is not ``valid`` holds whatever the grouped product left there
    and is selected away first (0 x NaN is NaN).  Exact as the scatter's
    float32 adds were: every product is with 0 or 1."""
    y = jnp.where(valid[:, None], y, 0)
    return jax.lax.dot_general(
        place, y, (((1,), (0,)), ((), ())), precision=_exact(y.dtype),
        preferred_element_type=jnp.float32)


def dropless_mlp_apply(params, x: jnp.ndarray, cfg: MoeConfig, *,
                       live: Optional[jnp.ndarray] = None, layer=None):
    """The dropless share on ``x`` [B, T, D]: the sum, over a token's
    chosen experts that are held here, of weight x expert(token), plus the
    shared expert.  ``live`` [B, T] (nonzero = a real token) keeps
    padding and idle rows off the experts and out of the counts (their
    output is the shared expert's alone).  ``params`` holds one layer;
    with ``layer`` (a traced index) its :data:`EXPERT_LEAVES` are every
    layer's, stacked, and the grouped products read layer ``layer`` of
    them in place (a scan's slice of them would be copied whole at every
    call).  Returns ``(out [B, T, D], routing)`` with ``routing`` as
    :data:`ROUTING_HEAD` lays it out."""
    b, t, d = x.shape
    n, k, held = b * t, cfg.top_k, cfg.held
    rows_block = min(ROW_BLOCK, n * k)
    blocks = -(-(n * k) // rows_block)
    flat = x.reshape(n, d)
    idx, weights = route(params, flat, cfg)
    with layers.scope("moe_route"):
        real = (jnp.ones((n, 1), bool) if live is None
                else live.reshape(n, 1) != 0)
        local = idx - cfg.expert_offset
        here = (local >= 0) & (local < held) & real
        key = jnp.where(here, local, held).reshape(n * k)
        # A compare-and-sum, not n x k scalar scatter-adds one after
        # another.
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        landed = jnp.sum(sizes)
        routing = jnp.concatenate([
            jnp.stack([jnp.sum(real) * k, landed, jnp.sum(sizes > 0)]
                      ).astype(jnp.int32), sizes])
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, blocks * rows_block - n * k))
        flat_weights = weights.reshape(n * k)
        ends = jnp.cumsum(sizes)

    def block(i):
        """What block ``i`` of the sorted rows adds to the tokens, [n, D]
        float32."""
        with layers.scope("moe_route"):
            lo = i * rows_block
            ids = jax.lax.dynamic_slice(order, (lo,), (rows_block,))
            tokens = ids // k
            block_sizes = (jnp.clip(ends, lo, lo + rows_block)
                           - jnp.clip(ends - sizes, lo, lo + rows_block))
            valid = (lo + jnp.arange(rows_block)) < landed
            place = _placement(tokens, valid, n, flat.dtype)
            rows = _drawn(flat, tokens, place)
            row_weights = jnp.take(flat_weights, ids)
        with layers.scope("moe_experts"):
            y = _held_experts(params, rows, block_sizes, layer, row_weights)
        with layers.scope("moe_combine"):
            return _placed(y, valid, place)

    # The first block IS the result where no second one holds rows (every
    # decode step, most prompts): no accumulator is zeroed, read or added
    # to for it.
    out = block(0)
    if blocks > 1:
        def more(i, out):
            placed = block(i)
            with layers.scope("moe_combine"):
                return out + placed

        out = jax.lax.fori_loop(1, -(-landed // rows_block), more, out)
    with layers.scope("moe_combine"):
        out = out.astype(x.dtype).reshape(b, t, d)
    if cfg.shared_hidden:
        shared = layers.mlp_block_apply(params["shared"], x)
        with layers.scope("moe_combine"):
            out = out + shared
    return out, routing
