"""The device-timing contract and the benchmark workloads, in ONE place.

JAX returns before the device finishes, and the first call after warmup
can recompile (committed vs uncommitted input shardings), so every
measurer times through :func:`chain_then_read_throughput`: dependent
steps chained on the device, then one host read of the last result.
"""

from __future__ import annotations

import time


def chain_then_read_throughput(step, state, batch, *, warmup=3, iters=20):
    """Steps/sec of ``step(state, batch) -> (state, metrics)``.

    Chains ``iters`` dependent steps (each consumes the prior state, so the
    device must execute all of them in order) then forces a host read of
    the final loss, which cannot return before the last step has run.
    ``warmup`` must chain >= 3 steps so the committed-sharding recompile is
    absorbed before timing (BASELINE.md "Timing methodology").
    """
    metrics = None
    for _ in range(warmup):
        state, metrics = step(state, batch)
    float(next(iter(metrics.values())))
    start = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, batch)
    float(next(iter(metrics.values())))
    return iters / (time.perf_counter() - start)


def decode_setup(*, batch_size: int = 4, prompt_len: int = 128,
                 params=None):
    """The generation-decode benchmark workload, built ONCE for every
    measurer: CloudLM SMALL, device-resident params
    and right-aligned full-length prompts.  Returns
    ``(config, params, prompts, lens)``."""
    import jax
    import numpy as np

    from cloud_tpu.models import transformer

    cfg = transformer.SMALL
    if params is None:
        params = transformer.init(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params)
    rng = np.random.default_rng(0)
    prompts = jax.device_put(
        rng.integers(1, cfg.vocab_size,
                     (batch_size, prompt_len)).astype(np.int32)
    )
    lens = jax.device_put(np.full((batch_size,), prompt_len, np.int32))
    return cfg, params, prompts, lens


def decode_tokens_per_sec(params, cfg, prompts, lens, *, max_new_tokens,
                          warmup: int = 1, iters: int = 4,
                          kv_quant: bool = False):
    """Greedy KV-cache decode throughput with the chain-then-read wait
    (each iteration's sequences are host-read)."""
    import functools
    import time as time_mod

    import jax
    import numpy as np

    from cloud_tpu.models import generation

    run = jax.jit(functools.partial(
        generation.generate, config=cfg, max_new_tokens=max_new_tokens,
        mesh=None, kv_quant=kv_quant,
    ))
    for _ in range(warmup):
        out = run(params, prompts, lens)
        float(out["sequences"].astype(np.float32).sum())
    start = time_mod.perf_counter()
    for _ in range(iters):
        out = run(params, prompts, lens)
        float(out["sequences"].astype(np.float32).sum())
    elapsed = time_mod.perf_counter() - start
    return iters * prompts.shape[0] * max_new_tokens / elapsed


def resnet_train_setup(*, imagenet_shape: bool, batch_size: int,
                       steps_per_dispatch: int = 1):
    """The ResNet benchmark workload, built ONCE for every measurer.

    Constructing it here keeps the config, optimizer, and synthetic batch
    of resnet50-cifar/resnet50-224 in lockstep across callers.  Returns
    ``(step, state, batch)`` with the step un-compiled (bench.py AOT
    lowers it for cost analysis; other callers may call it directly).

    ``steps_per_dispatch`` > 1 returns the FUSED variant instead —
    ``train.make_multi_step`` plus a K-stacked super-batch of distinct
    synthetic batches — so the fused context number times the same model,
    optimizer, and per-step batch shape as the headline.
    """
    import functools

    import jax
    import numpy as np
    import optax

    from cloud_tpu.models import resnet
    from cloud_tpu.training import train as train_lib

    if imagenet_shape:
        config, image_hw, num_classes = resnet.RESNET50, 224, 1000
    else:
        config, image_hw, num_classes = resnet.RESNET50_CIFAR, 32, 10
    tx = optax.sgd(0.1, momentum=0.9)
    state = train_lib.create_sharded_state(
        jax.random.PRNGKey(0),
        functools.partial(resnet.init, config=config),
        tx,
        mesh=None,
    )
    loss = functools.partial(resnet.loss_fn, config=config)
    rng = np.random.default_rng(0)
    shape = (batch_size, image_hw, image_hw, 3)
    if steps_per_dispatch > 1:
        shape = (steps_per_dispatch,) + shape
        step = train_lib.make_multi_step(
            loss, tx, steps_per_dispatch=steps_per_dispatch
        )
        label = rng.integers(
            0, num_classes, (steps_per_dispatch, batch_size)
        )
    else:
        step = train_lib.make_train_step(loss, tx)
        label = rng.integers(0, num_classes, batch_size)
    batch = jax.device_put({
        "image": rng.normal(size=shape).astype(np.float32),
        "label": label,
    })
    return step, state, batch


def fused_throughput(multi_step, state, super_batch, *, steps_per_dispatch,
                     warmup=1, iters=5):
    """Steps/sec (STEPS, not windows) of a K-fused multi-step dispatch.

    Delegates to :func:`chain_then_read_throughput` — a multi-step window
    has the same ``(state, batch) -> (state, metrics)`` shape, so the
    load-bearing timing contract stays in ONE place — and scales the
    windows/sec result by K.
    """
    return steps_per_dispatch * chain_then_read_throughput(
        multi_step, state, super_batch, warmup=warmup, iters=iters
    )
