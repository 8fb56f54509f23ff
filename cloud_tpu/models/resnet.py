"""ResNet-50 — the north-star benchmark workload (BASELINE.json config 2).

Functional NHWC implementation with GroupNorm instead of BatchNorm: GN has
no cross-replica state, so the model is a pure function (no mutable
batch-stats collections) and data-parallel scaling adds zero normalization
collectives — the TPU-idiomatic choice at pod scale, where sync-BN's
per-step all-reduces are an anti-pattern.  Conv kernels are HWIO; all
compute can run in bfloat16 (MXU) with float32 normalization statistics.

Reference analogue: the ResNet/CIFAR workloads users shipped through
``tfc.run()`` (e.g. core/tests/testdata/keras_tuner_cifar_example.py) and
the BASELINE.json north-star "Keras ResNet50 steps/sec/chip".
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from cloud_tpu.models import layers
from cloud_tpu.parallel.sharding import DEFAULT_RULES, ShardingRules


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: Sequence[int] = (3, 4, 6, 3)  # ResNet-50
    width: int = 64
    num_classes: int = 1000
    num_groups: int = 32
    dtype: Any = jnp.bfloat16


RESNET50 = ResNetConfig()
#: CIFAR-10-scale variant for tests and the CIFAR baseline config.
RESNET50_CIFAR = ResNetConfig(num_classes=10)
#: Tiny variant for notebooks/examples: one block per stage, narrow.
RESNET8_CIFAR = ResNetConfig(
    stage_sizes=(1, 1, 1, 1), width=16, num_classes=10, num_groups=8
)


def _conv_init(rng, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    std = (2.0 / fan_in) ** 0.5
    return {
        "kernel": jax.random.truncated_normal(
            rng, -2.0, 2.0, (kh, kw, cin, cout), jnp.float32
        )
        * std
    }


def _conv(params, x, *, stride=1, dtype=None):
    dtype = dtype or x.dtype
    return jax.lax.conv_general_dilated(
        x,
        params["kernel"].astype(dtype),
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _gn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def _gn(params, x, num_groups, activation=None, residual=None, *,
        mesh=None, batch_axes=None):
    # Dispatches to the fused Pallas kernel on TPU (one HBM read for
    # stats+normalize+affine, custom VJP); the jnp fallback inside is the
    # one-pass shifted-moments implementation this model used previously
    # (~12% faster than mean-then-var; see ops/group_norm.py for the
    # pivot-stability argument).  ``activation="relu"`` fuses the ReLU
    # epilogue in-kernel (saves one HBM round trip of the activation).
    from cloud_tpu import ops

    return ops.group_norm(
        x, params["scale"], params["bias"], num_groups=num_groups,
        activation=activation, residual=residual,
        mesh=mesh, batch_axes=batch_axes,
    )


def _bottleneck_init(rng, cin, cmid, stride):
    rs = jax.random.split(rng, 4)
    cout = cmid * 4
    block = {
        "conv1": _conv_init(rs[0], 1, 1, cin, cmid),
        "gn1": _gn_init(cmid),
        "conv2": _conv_init(rs[1], 3, 3, cmid, cmid),
        "gn2": _gn_init(cmid),
        "conv3": _conv_init(rs[2], 1, 1, cmid, cout),
        "gn3": _gn_init(cout),
    }
    if stride != 1 or cin != cout:
        block["proj"] = _conv_init(rs[3], 1, 1, cin, cout)
        block["gn_proj"] = _gn_init(cout)
    return block


def _bottleneck(params, x, cfg, stride, gn):
    residual = x
    y = gn(params["gn1"], _conv(params["conv1"], x), cfg.num_groups,
           activation="relu")
    y = gn(params["gn2"], _conv(params["conv2"], y, stride=stride),
           cfg.num_groups, activation="relu")
    if "proj" in params:
        residual = gn(
            params["gn_proj"], _conv(params["proj"], x, stride=stride),
            cfg.num_groups,
        )
    # Tail fusion: relu(gn3(conv3) + residual) in one kernel pass — the
    # separate add+relu re-read both [B,H,W,C] tensors from HBM.
    return gn(params["gn3"], _conv(params["conv3"], y), cfg.num_groups,
              activation="relu", residual=residual)


def init(rng, config: ResNetConfig = RESNET50) -> Dict[str, Any]:
    rngs = jax.random.split(rng, 2 + sum(config.stage_sizes))
    params: Dict[str, Any] = {
        "stem": _conv_init(rngs[0], 7, 7, 3, config.width),
        "gn_stem": _gn_init(config.width),
    }
    idx = 1
    cin = config.width
    for stage, num_blocks in enumerate(config.stage_sizes):
        cmid = config.width * (2**stage)
        for block in range(num_blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            params[f"stage{stage}_block{block}"] = _bottleneck_init(
                rngs[idx], cin, cmid, stride
            )
            cin = cmid * 4
            idx += 1
    head, _ = layers.dense_init(
        rngs[idx], cin, config.num_classes, in_axis=None, out_axis=None
    )
    params["head"] = head
    return params


def param_logical_axes(config: ResNetConfig = RESNET50):
    """ResNet scales by data parallelism: every parameter replicated
    (sharded only if the user extends the rules)."""
    params = jax.eval_shape(lambda r: init(r, config), jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(lambda leaf: (None,) * leaf.ndim, params)


def apply(params, images: jnp.ndarray, config: ResNetConfig = RESNET50, *,
          rules: ShardingRules = DEFAULT_RULES, mesh=None):
    """images [B, H, W, 3] -> logits [B, num_classes].

    Under a mesh (``mesh``, default the framework's global one) the fused
    GroupNorm kernel runs per batch shard: ``rules`` — the table the
    batch was sharded by — says over which mesh axes."""
    gn = functools.partial(_gn, mesh=mesh,
                           batch_axes=rules.assignment("batch"))
    x = images.astype(config.dtype)
    x = _conv(params["stem"], x, stride=2)
    x = gn(params["gn_stem"], x, config.num_groups, activation="relu")
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    for stage, num_blocks in enumerate(config.stage_sizes):
        for block in range(num_blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            x = _bottleneck(params[f"stage{stage}_block{block}"], x, config,
                            stride, gn)
    x = jnp.mean(x, axis=(1, 2))
    return layers.dense_apply(params["head"], x, dtype=jnp.float32)


def loss_fn(params, batch: Dict[str, jnp.ndarray],
            config: ResNetConfig = RESNET50, *,
            rules: ShardingRules = DEFAULT_RULES,
            mesh=None) -> Tuple[jnp.ndarray, Dict]:
    logits = apply(params, batch["image"], config, rules=rules, mesh=mesh)
    labels = batch["label"]
    log_probs = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(log_probs, labels[:, None], axis=-1))
    accuracy = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "accuracy": accuracy}
