"""Plain reference of the Kimi-K2 block (``model_type: kimi_k2``, the
DeepSeek-V3 block: latent attention, a leading dense layer, then expert
layers with a sigmoid router, a selection bias, top-8 of 384 and a shared
expert), cut to ONE CHIP'S SHARE of an expert-parallel replica, and the
weights every run is made of.

Imports nothing of the program.  The weights come from the seed alone:
``make_params`` builds them for the system under test in the type they are
served in; the reference builds the same numbers again, one layer at a time,
and computes in float32 under ``jax.default_matmul_precision("highest")`` —
no kernels, no cache, no slots, no grouping of tokens by expert, one
sequence at a time, attention in its EXPANDED form only (per-head keys and
values made from the latent; the program's absorbed decode read has to agree
with it).

One block, ``x`` the residual stream [T, D], ``eps`` = rms_norm_eps::

    u    = rmsnorm(x; ln1)
    cq   = rmsnorm(u Wdq; q_norm)                               q_lora_rank
    q    = cq Wuq                      per head [q_nope | q_pe]  128 | 64
    [c | k_pe] = u Wdkv ;  c = rmsnorm(c; kv_norm)               512 | 64
    [k_nope | v] = c Wukv              per head                  128 | 128
    q_pe, k_pe rotated: pairs (2i, 2i+1), YaRN frequencies; k_pe is ONE
    vector a token, shared by all heads ;  k = [k_nope | k_pe]
    a    = softmax(scale q k^T + causal) v ;  scale = 192^-0.5 mscale(32, 1)^2
    x    = x + concat(a) Wo
    m    = rmsnorm(x; ln2)
    layer 0 (first_k_dense_replace = 1):
      x  = x + (silu(m Wgate) * (m Wup)) Wdown                   width 18432
    every later layer:
      s  = sigmoid(m Wr)               float32, ALL 384 experts
      chosen = top-8 of (s + b)        b = e_score_correction_bias
      w_i = s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor
      x  = x + sum over chosen i HELD HERE of w_i E_i(m) + E_shared(m)
    logits = rmsnorm(x_L; ln_f) Whead                  over the vocabulary's slice

**The share.**  ``sizes["n_routed_experts"]`` is the count of experts held
here, ``[expert_offset, expert_offset + n_routed_experts)`` of
``sizes["published"]["n_routed_experts"]``; the router keeps the published
width.  What the absent experts would add is left out — in the program
alike — and that partial result is what goes on to the next layer.  Expert
``e``'s weights are made from the seed and ``e``'s number among all the
experts, so every share of one seed holds the same model.

**Every seed the same work.**  The published ``b`` was trained so that the
experts' loads are even; random weights have had no such training.
``calibrate`` therefore fits ``b`` by the published aux-loss-free rule —
``b_i`` steps against the sign of expert ``i``'s excess load — for
``BIAS_STEPS`` steps on ``CALIBRATION_SEQUENCES x CALIBRATION_LENGTH`` seeded
random tokens carried
through the layers as the model itself computes them (in the served type: a
fit needs no more), layer by layer, each layer fitted on what the fitted
layers before it pass on.  One jitted program makes ``b`` for
``make_params`` and for ``score`` alike.

Points the published ``config.json`` does not settle are under ``assumed``
in the configuration file.  ``precision`` selects what a matrix product
sees: ``"f32"`` (the reference) or ``"fp8"`` (the control: operands rounded
to float8_e4m3 with one scale per tensor).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.common import fp8, root_key

#: The head is made and read in this many slices of the vocabulary.
VOCAB_SLICES = 4

#: Attention runs over this many heads at a time: the scores of 8 heads
#: over 4,608 positions are 680 MB in float32.
HEAD_BLOCK = 8

#: The fit of ``b``: sequences x tokens of seeded random ids (contexts as
#: long as the served prompts': the router's input moves with the context
#: its token has attended over), steps, and the first step's size (it falls
#: linearly to nothing).
CALIBRATION_SEQUENCES, CALIBRATION_LENGTH = 16, 2048
BIAS_STEPS, BIAS_STEP = 200, 0.01

#: A choice of experts is called a near-tie where the eighth and the ninth
#: of ``s + b`` lie closer than this: bfloat16 activations move a logit of
#: order 1 by about 4e-3 and a sigmoid by a quarter of that.
NEAR_TIE = 1e-3


def _vocab_slices(sizes):
    return VOCAB_SLICES if sizes["vocab_size"] % VOCAB_SLICES == 0 else 1


def _kernel(key, fan_in, fan_out, dtype):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
    return (w * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _norm_scale(key, dim):
    return 1.0 + 0.1 * jax.random.normal(key, (dim,), jnp.float32)


def _mlp_params(key, d, width, dtype):
    k = jax.random.split(key, 3)
    return {"wi": {"kernel": _kernel(k[0], d, width, dtype)},    # gate
            "wg": {"kernel": _kernel(k[1], d, width, dtype)},    # up
            "wo": {"kernel": _kernel(k[2], width, d, dtype)}}    # down


def _all_experts(sizes):
    return sizes["published"]["n_routed_experts"]


def layer_params(key, sizes, dtype, dense):
    """One block's weights; ``dense``: the leading layer's plain MLP,
    else the expert layer's share (``b`` zeros until it is fitted)."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, v = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    k = jax.random.split(key, 16)
    params = {
        "att": {
            "q_a": {"kernel": _kernel(k[0], d, q_rank, dtype)},
            "q_norm": {"scale": _norm_scale(k[1], q_rank)},
            "q_b": {"kernel": _kernel(k[2], q_rank, heads * (nope + rope),
                                      dtype)},
            "kv_a": {"kernel": _kernel(k[3], d, kv_rank + rope, dtype)},
            "kv_norm": {"scale": _norm_scale(k[4], kv_rank)},
            "kv_b": {"kernel": _kernel(k[5], kv_rank, heads * (nope + v),
                                       dtype)},
            "out": {"kernel": _kernel(k[6], heads * v, d, dtype)},
        },
        "ln1": {"scale": _norm_scale(k[7], d)},
        "ln2": {"scale": _norm_scale(k[8], d)},
    }
    if dense:
        params["mlp"] = _mlp_params(k[9], d, sizes["intermediate_size"],
                                    dtype)
        return params
    width, held = sizes["moe_intermediate_size"], sizes["n_routed_experts"]
    # An expert's weights come from its number among ALL the experts.
    numbers = sizes.get("expert_offset", 0) + jnp.arange(held)

    def experts(key, fan_in, fan_out):
        return jax.lax.map(
            lambda e: _kernel(jax.random.fold_in(key, e), fan_in, fan_out,
                              dtype), numbers)

    params["mlp"] = {
        "router": {"kernel": _kernel(k[10], d, _all_experts(sizes), dtype)},
        "bias": jnp.zeros((_all_experts(sizes),), jnp.float32),
        "wi": experts(k[11], d, width),
        "wg": experts(k[12], d, width),
        "wo": experts(k[13], width, d),
        "shared": _mlp_params(
            k[14], d, width * sizes["n_shared_experts"], dtype),
    }
    return params


def _sizes_key(sizes):
    """The sizes a program is specialised by: whole numbers and reals at
    the top level, and those of ``rope_scaling`` and ``published``."""
    def number(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def flat(group):
        return tuple(sorted((k, v) for k, v in group.items() if number(v)))

    return flat(sizes) + (("rope_scaling", flat(sizes["rope_scaling"])),
                          ("published", flat(sizes["published"])))


def _sizes(sizes_key):
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in sizes_key}


def _layer_counts(sizes):
    dense = sizes["first_k_dense_replace"]
    return dense, sizes["num_hidden_layers"] - dense


def _keys(seed, sizes):
    """(embedding, dense layers, expert layers, head slices, final norm,
    calibration tokens) keys.  Made eagerly and handed to the jitted
    programs as ARGUMENTS: a seed baked into a program would make every new
    seed a new program."""
    k_embed, k_layers, k_head, k_ln, k_fit = jax.random.split(
        root_key(seed), 5)
    dense, _ = _layer_counts(sizes)
    k_layers = jax.random.split(k_layers, sizes["num_hidden_layers"])
    return (k_embed, k_layers[:dense], k_layers[dense:],
            jax.random.split(k_head, _vocab_slices(sizes)), k_ln, k_fit)


def _embedding(key, sizes, dtype):
    return (jax.random.normal(
        key, (sizes["vocab_size"], sizes["hidden_size"]), jnp.float32)
        * 0.02).astype(dtype)


def _head_slice(key, sizes, dtype, slices):
    return _kernel(key, sizes["hidden_size"], sizes["vocab_size"] // slices,
                   dtype)


# -- the forward pass ---------------------------------------------------


def matmul(x, w, precision):
    if precision == "served":
        # The fit of ``b`` alone: operands as they are served, one pass.
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if precision == "f32":
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return jnp.matmul(fp8(x), fp8(w),
                          precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(sizes):
    """``rope_dim / 2`` frequencies: ``theta^(-2i/d)`` blended with that
    over ``factor`` by the linear ramp between the correction dimensions
    of ``beta_fast`` and ``beta_slow`` (the DeepSeek-V3 rotary's rule)."""
    d, theta, yarn = (sizes["qk_rope_head_dim"], float(sizes["rope_theta"]),
                      sizes["rope_scaling"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def correction(rotations):
        return (d * math.log(yarn["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(plain / yarn["factor"] * ramp + plain * (1.0 - ramp),
                       jnp.float32)


def _rope(x, sizes):
    """Interleaved RoPE on [T, .., rope_dim]: dimensions (2i, 2i + 1)
    are a pair, rotated in place."""
    yarn = sizes["rope_scaling"]
    angles = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
              * yarn_inv_freq(sizes))
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    factor = (_yarn_mscale(yarn["factor"], yarn["mscale"])
              / _yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]))
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def softmax_scale(sizes):
    yarn = sizes["rope_scaling"]
    scale = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5
    if yarn["mscale_all_dim"]:
        scale *= _yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def _attention(p, u, sizes, precision):
    t = u.shape[0]
    heads, kv_rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, v_dim = (sizes["qk_nope_head_dim"],
                         sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    eps = sizes["rms_norm_eps"]
    cq = _rmsnorm(matmul(u, p["q_a"]["kernel"], precision),
                  p["q_norm"]["scale"], eps)
    q = matmul(cq, p["q_b"]["kernel"], precision).reshape(
        t, heads, nope + rope)
    down = matmul(u, p["kv_a"]["kernel"], precision)
    c = _rmsnorm(down[:, :kv_rank], p["kv_norm"]["scale"], eps)
    up = matmul(c, p["kv_b"]["kernel"], precision).reshape(
        t, heads, nope + v_dim)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], sizes)], -1)
    # The rotated key is one vector a token, shared by every head.
    k_pe = _rope(down[:, kv_rank:], sizes)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, heads, rope))],
        -1)
    v = up[..., nope:]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def some_heads(block):
        qb, kb, vb = block
        scores = jnp.einsum("qhd,khd->hqk", qb, kb,
                            precision=jax.lax.Precision.HIGHEST
                            ) * softmax_scale(sizes)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          vb, precision=jax.lax.Precision.HIGHEST)

    block = math.gcd(HEAD_BLOCK, heads)

    def blocks(a):
        return a.reshape(t, heads // block, block, -1).transpose(1, 0, 2, 3)

    att = jax.lax.map(some_heads, (blocks(q), blocks(k), blocks(v)))
    att = att.transpose(1, 0, 2, 3).reshape(t, heads * v_dim)
    return matmul(att, p["out"]["kernel"], precision)


def _swiglu(gate, up, down, m, precision):
    return matmul(jax.nn.silu(matmul(m, gate, precision))
                  * matmul(m, up, precision), down, precision)


def _mlp(p, m, precision):
    return _swiglu(p["wi"]["kernel"], p["wg"]["kernel"], p["wo"]["kernel"],
                   m, precision)


def router_scores(p, m, precision):
    """``s`` [T, all experts] in float32: the sigmoid of the router."""
    return jax.nn.sigmoid(matmul(m, p["router"]["kernel"], precision))


def _choose(scores, bias, sizes):
    """The chosen experts [T, K] (by ``s + b``), their weights [T, K]
    (from ``s`` alone), the margin between the last chosen and the first
    left out, and the HELD experts' margin [T]: how far the nearest of
    the experts held here stands from changing sides (a chosen one from
    the first left out, one left out from the last chosen).  A choice that
    flips between two absent experts changes nothing here; one that takes
    a held expert in or out moves the token by that expert's whole output,
    and only a token whose held margin is under what rounding moves a
    score by can have that happen to it."""
    k = sizes["num_experts_per_tok"]
    by = scores + bias
    top, idx = jax.lax.top_k(by, k + 1)
    chosen = idx[:, :k]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = (weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
               * sizes["routed_scaling_factor"])
    offset = sizes.get("expert_offset", 0)
    held = by[:, offset:offset + sizes["n_routed_experts"]]
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    held_margin = jnp.min(jnp.where(held >= last_in, held - first_out,
                                    last_in - held), axis=-1)
    return chosen, weights, top[:, k - 1] - top[:, k], held_margin


def _expert_layer(p, m, sizes, precision):
    """The share's part of the expert layer on m [T, D], the share of
    near-tied choices and the held experts' margin [T] (:func:`_choose`):
    every held expert on every token, weighted by what the router gave it
    there (nothing where it was not chosen), plus the shared expert."""
    scores = router_scores(p, m, precision)
    chosen, weights, margin, held_margin = _choose(scores, p["bias"], sizes)
    offset = sizes.get("expert_offset", 0)

    def one_expert(out, held):
        e, gate, up, down = held
        weight = jnp.sum(jnp.where(chosen == offset + e, weights, 0.0), -1)
        return out + weight[:, None] * _swiglu(gate, up, down, m,
                                               precision), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros(m.shape, jnp.float32),
        (jnp.arange(sizes["n_routed_experts"]), p["wi"], p["wg"], p["wo"]))
    return (out + _mlp(p["shared"], m, precision),
            jnp.mean(margin < NEAR_TIE), held_margin)


def _layer(p, x, sizes, precision, dense):
    """One block on one sequence x [T, D], causal: (x, near-tie share,
    the held experts' margin [T]; a dense block has no choice to flip)."""
    eps = sizes["rms_norm_eps"]
    x = x + _attention(p["att"], _rmsnorm(x, p["ln1"]["scale"], eps), sizes,
                       precision)
    m = _rmsnorm(x, p["ln2"]["scale"], eps)
    if dense:
        return (x + _mlp(p["mlp"], m, precision), jnp.float32(0),
                jnp.full(x.shape[:1], jnp.inf, jnp.float32))
    out, near, held_margin = _expert_layer(p["mlp"], m, sizes, precision)
    return x + out, near, held_margin


# -- the fit of the selection bias --------------------------------------


def _fit_bias(scores, sizes):
    """``b`` [all experts] by the aux-loss-free rule on ``scores``
    [N, all experts]: each step counts the experts' loads under
    ``s + b`` and moves every ``b_i`` against the sign of its excess."""
    k = sizes["num_experts_per_tok"]
    experts = scores.shape[-1]

    def step(bias, i):
        _, idx = jax.lax.top_k(scores + bias, k)
        load = jnp.zeros((experts,), jnp.float32).at[idx.reshape(-1)].add(1)
        size = BIAS_STEP * (1.0 - i / BIAS_STEPS)
        return bias - size * jnp.sign(load - jnp.mean(load)), None

    bias, _ = jax.lax.scan(step, jnp.zeros((experts,), jnp.float32),
                           jnp.arange(BIAS_STEPS, dtype=jnp.float32))
    return bias


def _each(xs, fn):
    """``fn`` on one sequence of ``xs`` [N, T, D] after another, in
    float32, each written back IN PLACE in ``xs``'s type: the fit holds
    one copy of its tokens' residual stream and one sequence's scratch."""
    def one(i, xs):
        return xs.at[i].set(fn(xs[i].astype(jnp.float32)).astype(xs.dtype))

    return jax.lax.fori_loop(0, xs.shape[0], one, xs)


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _calibrate(keys, sizes_key, dtype):
    """``b`` of every expert layer [expert layers, all experts].  The
    residual stream is carried between the layers in the served type, as
    the program carries it."""
    sizes = _sizes(sizes_key)
    k_embed, k_dense, k_experts, _, _, k_fit = keys
    tokens = jax.random.randint(
        k_fit, (CALIBRATION_SEQUENCES, CALIBRATION_LENGTH), 1,
        sizes["vocab_size"])
    xs = jnp.take(_embedding(k_embed, sizes, dtype), tokens, axis=0)

    eps = sizes["rms_norm_eps"]

    def dense_layer(xs, key):
        p = layer_params(key, sizes, dtype, True)
        return _each(xs, lambda x: _layer(p, x, sizes, "served", True)[0]), \
            None

    def expert_layer(xs, key):
        p = layer_params(key, sizes, dtype, False)

        def normed(x):
            return _rmsnorm(x, p["ln2"]["scale"], eps)

        def attend(i, carry):
            xs, scores = carry
            x = xs[i].astype(jnp.float32)
            x = (x + _attention(p["att"], _rmsnorm(x, p["ln1"]["scale"], eps),
                                sizes, "served")).astype(xs.dtype)
            return xs.at[i].set(x), scores.at[i].set(router_scores(
                p["mlp"], normed(x.astype(jnp.float32)), "served"))

        xs, scores = jax.lax.fori_loop(
            0, xs.shape[0], attend,
            (xs, jnp.zeros(xs.shape[:2] + (_all_experts(sizes),),
                           jnp.float32)))
        bias = _fit_bias(scores.reshape(-1, scores.shape[-1]), sizes)
        fitted = dict(p["mlp"], bias=bias)
        return _each(xs, lambda x: x + _expert_layer(
            fitted, normed(x), sizes, "served")[0]), bias

    xs, _ = jax.lax.scan(dense_layer, xs, k_dense)
    _, biases = jax.lax.scan(expert_layer, xs, k_experts)
    return biases


def calibrate(seed, sizes, dtype=jnp.bfloat16):
    """``b`` of every expert layer: the one program both sides run."""
    return _calibrate(_keys(seed, sizes), _sizes_key(sizes), dtype)


# -- the weights ----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _build_params(keys, biases, sizes_key, dtype):
    sizes = _sizes(sizes_key)
    k_embed, k_dense, k_experts, k_head, k_ln, _ = keys
    v, d = sizes["vocab_size"], sizes["hidden_size"]
    n = k_head.shape[0]

    def expert_layer(at):
        key, bias = at
        p = layer_params(key, sizes, dtype, False)
        p["mlp"]["bias"] = bias
        return p

    def write(i, kernel):
        return jax.lax.dynamic_update_slice(
            kernel, _head_slice(k_head[i], sizes, dtype, n),
            (0, i * (v // n)))

    return {
        "embed": {"table": _embedding(k_embed, sizes, dtype)},
        # ``lax.map`` makes one layer at a time, so the float32 normals of
        # one layer are all the scratch it needs.
        "dense_layers": jax.lax.map(
            lambda key: layer_params(key, sizes, dtype, True), k_dense),
        "layers": jax.lax.map(expert_layer, (k_experts, biases)),
        "ln_f": {"scale": _norm_scale(k_ln, d)},
        "head": {"kernel": jax.lax.fori_loop(0, n, write,
                                             jnp.zeros((d, v), dtype))},
    }


def make_params(seed, sizes, dtype=jnp.bfloat16):
    """All weights from the seed, the layers of a kind stacked on a leading
    axis, on the device, in the type they are served in: ``b`` is fitted
    first (:func:`calibrate`), then one jitted call makes the rest."""
    keys = _keys(seed, sizes)
    biases = _calibrate(keys, _sizes_key(sizes), dtype)
    return _build_params(keys, biases, _sizes_key(sizes), dtype)


def params_shape(sizes, dtype=jnp.bfloat16):
    """``make_params``'s shapes and types, with nothing made."""
    _, experts = _layer_counts(sizes)
    biases = jax.ShapeDtypeStruct((experts, _all_experts(sizes)),
                                  jnp.float32)
    return jax.eval_shape(
        lambda b: _build_params(_keys(0, sizes), b, _sizes_key(sizes),
                                dtype), biases)


# -- the reference's reading -----------------------------------------------


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "dtype", "dense"),
                   donate_argnums=2)
def _apply_layer(key, bias, xs, sizes_key, precision, dtype, dense):
    sizes = _sizes(sizes_key)
    p = layer_params(key, sizes, dtype, dense)
    if not dense:
        p["mlp"]["bias"] = bias
    return jax.lax.map(lambda x: _layer(p, x, sizes, precision, dense), xs)


@functools.partial(jax.jit, static_argnames=("sizes_key", "dtype"))
def _embed(key, tokens, sizes_key, dtype):
    sizes = _sizes(sizes_key)
    return jnp.take(_embedding(key, sizes, dtype), tokens,
                    axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "dtype"))
def _head(k_head, k_ln, xs, rows, chosen, sizes_key, precision, dtype):
    """The logits' best, its token, the chosen token's logit and the
    row's standard deviation, a slice of the vocabulary at a time: the
    maximum, the chosen logit and the moments combine exactly across
    slices (Chan's update)."""
    sizes = _sizes(sizes_key)
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    n = k_head.shape[0]
    width = v // n
    picked = jnp.take_along_axis(xs, rows[:, :, None], axis=1)
    y = _rmsnorm(picked, _norm_scale(k_ln, d), sizes["rms_norm_eps"])

    def one_slice(carry, at):
        best, argmax, picked_logit, mean, m2 = carry
        i, key = at
        logits = matmul(y, _head_slice(key, sizes, dtype, n), precision)
        top = jnp.max(logits, -1)
        argmax = jnp.where(top > best, i * width + jnp.argmax(logits, -1),
                           argmax)
        local = chosen - i * width
        here = (local >= 0) & (local < width)
        value = jnp.take_along_axis(
            logits, jnp.clip(local, 0, width - 1)[:, :, None], axis=-1)[..., 0]
        seen = (i * width).astype(jnp.float32)
        slice_mean = jnp.mean(logits, -1)
        delta = slice_mean - mean
        total = seen + width
        m2 = (m2 + jnp.sum((logits - slice_mean[..., None]) ** 2, -1)
              + delta ** 2 * seen * width / total)
        return (jnp.maximum(best, top), argmax,
                jnp.where(here, value, picked_logit),
                mean + delta * width / total, m2), None

    zeros = jnp.zeros(rows.shape, jnp.float32)
    (best, argmax, picked_logit, _, m2), _ = jax.lax.scan(
        one_slice,
        (zeros - jnp.inf, jnp.zeros(rows.shape, jnp.int32), zeros, zeros,
         zeros), (jnp.arange(n), k_head))
    return {"best": best, "argmax": argmax, "chosen": picked_logit,
            "std": jnp.sqrt(m2 / v)}


def score(seed, sizes, tokens, rows, chosen, precision="f32",
          dtype=jnp.bfloat16):
    """The full forward pass over ``tokens`` [N, T] (right-padded; causal
    from the left, so the padding is inert), read at positions ``rows``
    [N, R]: for each the best logit, its token, the logit of ``chosen``
    [N, R] and the standard deviation of the row's logits, over the
    vocabulary's slice; ``near_ties``, the share of (token, expert
    layer) choices whose last chosen and first left-out expert lie within
    :data:`NEAR_TIE`; and ``held_margin`` [N, R], the least over the expert
    layers of the held experts' margin at that position
    (:func:`_choose`).  Layer by layer, so that one layer's weights are all
    it holds, and the head by slices of the vocabulary."""
    sizes_key = _sizes_key(sizes)
    keys = _keys(seed, sizes)
    k_embed, k_dense, k_experts, k_head, k_ln, _ = keys
    biases = _calibrate(keys, sizes_key, dtype)
    near, margins = [], []
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        xs = _embed(k_embed, jnp.asarray(tokens, jnp.int32), sizes_key,
                    dtype)
        for key in k_dense:
            xs, _, _ = _apply_layer(key, biases[0], xs, sizes_key,
                                    precision, dtype, True)
        for key, bias in zip(k_experts, biases):
            xs, near_l, margin = _apply_layer(key, bias, xs, sizes_key,
                                              precision, dtype, False)
            near.append(near_l)
            margins.append(np.asarray(
                jnp.take_along_axis(margin, rows, axis=1)))
        out = _head(k_head, k_ln, xs, rows, jnp.asarray(chosen, jnp.int32),
                    sizes_key, precision, dtype)
    out = {k: np.asarray(v) for k, v in out.items()}
    out["near_ties"] = float(np.mean([np.asarray(n) for n in near]))
    out["held_margin"] = np.min(margins, axis=0)
    return out
