"""Fused GroupNorm kernel tests (interpret mode; the TPU compile is
covered by tests/unit/test_chip_compile.py and chip_smoke.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu import parallel
from cloud_tpu.ops.group_norm import (
    _reference,
    group_norm,
    kernel_eligible,
)


def _rand(shape, seed=0, scale=3.0, offset=7.0):
    rng = np.random.default_rng(seed)
    # Large offset vs spread exercises the shifted-moments stability path.
    return jnp.asarray(
        rng.normal(size=shape) * scale + offset, jnp.float32
    )


class TestForward:
    @pytest.mark.parametrize("shape,groups", [
        ((3, 8, 8, 64), 32),
        ((2, 4, 4, 128), 32),
        ((2, 8, 4, 16), 8),
        ((1, 8, 8, 32), 32),  # groups clamped to channels
    ])
    def test_matches_reference(self, shape, groups):
        x = _rand(shape)
        scale = _rand((shape[-1],), seed=1, scale=0.5, offset=1.0)
        bias = _rand((shape[-1],), seed=2, scale=0.5, offset=0.0)
        got = group_norm(x, scale, bias, num_groups=groups,
                         use_pallas=True, interpret=True, partitioned=False)
        want = _reference(x, scale, bias, groups)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_bfloat16_io(self):
        x = _rand((2, 8, 8, 64)).astype(jnp.bfloat16)
        scale = jnp.ones((64,), jnp.float32)
        bias = jnp.zeros((64,), jnp.float32)
        got = group_norm(x, scale, bias, num_groups=32, use_pallas=True,
                         interpret=True, partitioned=False)
        assert got.dtype == jnp.bfloat16
        want = _reference(x, scale, bias, 32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2,
        )


class TestBackward:
    def test_grads_match_reference(self):
        x = _rand((2, 8, 8, 64))
        scale = _rand((64,), seed=1, scale=0.5, offset=1.0)
        bias = _rand((64,), seed=2, scale=0.5, offset=0.0)

        def loss(fn, x, s, b):
            y = fn(x, s, b)
            return jnp.sum(y * jnp.sin(y))

        got = jax.grad(
            lambda x, s, b: loss(
                lambda *a: group_norm(
                    *a, num_groups=32, use_pallas=True, interpret=True,
                    partitioned=False,
                ), x, s, b,
            ),
            argnums=(0, 1, 2),
        )(x, scale, bias)
        want = jax.grad(
            lambda x, s, b: loss(
                lambda *a: _reference(*a, num_groups=32), x, s, b
            ),
            argnums=(0, 1, 2),
        )(x, scale, bias)
        for g, w in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )


class TestDispatch:
    def test_cpu_auto_falls_back(self):
        x = _rand((2, 8, 8, 64))
        s, b = jnp.ones((64,)), jnp.zeros((64,))
        got = group_norm(x, s, b, num_groups=32)  # auto: CPU -> reference
        want = _reference(x, s, b, 32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_eligibility_rules(self):
        assert kernel_eligible(jnp.zeros((2, 8, 8, 64)), 32)
        assert not kernel_eligible(jnp.zeros((2, 8, 64)), 32)  # 3-D
        assert not kernel_eligible(jnp.zeros((2, 3, 3, 64)), 32)  # hw % 8
        assert not kernel_eligible(jnp.zeros((2, 8, 8, 48)), 32)  # c % g
        big = jnp.zeros((1, 64, 64, 2048))  # 32 MiB sample > VMEM budget
        assert not kernel_eligible(big, 32)

    def test_resnet_uses_kernel_under_interpret(self, monkeypatch):
        """The model wiring reaches the kernel (not the fallback) when
        interpret is forced — the same seam the dryrun gates on.  The
        trace counter is the proof; finite logits alone would stay green
        through a silent fallback."""
        import sys

        import cloud_tpu.ops.group_norm  # noqa: F401

        gn_mod = sys.modules["cloud_tpu.ops.group_norm"]
        monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
        from cloud_tpu.models import resnet

        cfg = resnet.ResNetConfig(
            stage_sizes=(1,), width=16, num_classes=10, num_groups=8,
            dtype=jnp.float32,
        )
        params = resnet.init(jax.random.PRNGKey(0), cfg)
        x = _rand((2, 8, 8, 3), scale=1.0, offset=0.0)
        before = gn_mod.KERNEL_TRACE_COUNT
        logits = resnet.apply(params, x, cfg)
        assert gn_mod.KERNEL_TRACE_COUNT > before, (
            "fused GroupNorm kernel never traced — silent fallback"
        )
        assert np.isfinite(np.asarray(logits)).all()


class TestPartitioned:
    def test_partitioned_matches_direct_under_mesh(self):
        mesh = parallel.MeshSpec({"dp": 2, "fsdp": 2, "tp": 2}).build()
        x = _rand((4, 8, 8, 64))
        scale = _rand((64,), seed=1, scale=0.5, offset=1.0)
        bias = _rand((64,), seed=2, scale=0.5, offset=0.0)

        def loss(x, s, b, partitioned):
            y = group_norm(
                x, s, b, num_groups=32, use_pallas=True, interpret=True,
                partitioned=partitioned, batch_axes=("dp", "fsdp"),
            )
            return jnp.sum(y * y)

        from jax.sharding import NamedSharding, PartitionSpec as P

        with parallel.use_mesh(mesh):
            xs = jax.device_put(
                x, NamedSharding(mesh, P(("dp", "fsdp"), None, None, None))
            )
            got = jax.jit(
                jax.value_and_grad(lambda *a: loss(*a, True),
                                   argnums=(0, 1, 2))
            )(xs, scale, bias)
        want = jax.value_and_grad(
            lambda *a: loss(*a, False), argnums=(0, 1, 2)
        )(x, scale, bias)
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )


class TestFusedRelu:
    """activation="relu": the kernel's in-VMEM epilogue must equal
    relu(group_norm(x)) exactly, forward AND backward (the backward
    gates the cotangent by the recomputed pre-activation sign), on the
    direct, partitioned, and jnp-reference routes."""

    def _args(self, shape=(3, 8, 8, 64), groups=32):
        x = _rand(shape, seed=2)
        c = shape[-1]
        scale = _rand((c,), seed=3, scale=0.3, offset=1.0)
        # Bias around zero so the relu gate cuts through the data.
        bias = _rand((c,), seed=4, scale=0.5, offset=0.0)
        return x, scale, bias, groups

    def _loss(self, fn):
        return lambda x, s, b: jnp.sum(fn(x, s, b) ** 2)

    def test_kernel_matches_unfused_fwd_and_grad(self):
        x, scale, bias, groups = self._args()

        def fused(x, s, b):
            return group_norm(x, s, b, num_groups=groups, use_pallas=True,
                              interpret=True, partitioned=False,
                              activation="relu")

        def unfused(x, s, b):
            return jnp.maximum(
                _reference(x, s, b, groups), 0.0
            )

        got = jax.value_and_grad(self._loss(fused), argnums=(0, 1, 2))(
            x, scale, bias
        )
        want = jax.value_and_grad(self._loss(unfused), argnums=(0, 1, 2))(
            x, scale, bias
        )
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )
        # The gate is live: some outputs must actually be clamped.
        assert float(jnp.mean(fused(x, scale, bias) == 0.0)) > 0.05

    def test_reference_route_matches_too(self):
        x, scale, bias, groups = self._args()
        fused = group_norm(x, scale, bias, num_groups=groups,
                           use_pallas=False, activation="relu")
        np.testing.assert_allclose(
            np.asarray(fused),
            np.maximum(np.asarray(_reference(x, scale, bias, groups)), 0.0),
            rtol=1e-6,
        )

    def test_partitioned_route_matches_direct(self):
        x, scale, bias, groups = self._args(shape=(4, 8, 8, 64))
        mesh = parallel.MeshSpec({"dp": 8}).build()

        def fused(part):
            def f(x, s, b):
                return group_norm(
                    x, s, b, num_groups=groups, use_pallas=True,
                    interpret=True, partitioned=part, activation="relu",
                    batch_axes="dp",
                )
            return f

        with parallel.use_mesh(mesh):
            got = jax.jit(jax.value_and_grad(
                self._loss(fused(True)), argnums=(0, 1, 2)
            ))(x, scale, bias)
        want = jax.value_and_grad(
            self._loss(fused(False)), argnums=(0, 1, 2)
        )(x, scale, bias)
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )

    def test_resnet_trains_with_fused_activation(self):
        """End to end: the model that uses the fusion still learns."""
        import functools

        import optax

        from cloud_tpu.models import resnet
        from cloud_tpu.training import train as train_lib

        cfg = resnet.ResNetConfig(
            stage_sizes=(1,), width=8, num_classes=4, num_groups=4
        )
        state = train_lib.create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(resnet.init, config=cfg),
            optax.sgd(0.05), mesh=None,
        )
        step = train_lib.make_train_step(
            functools.partial(resnet.loss_fn, config=cfg), optax.sgd(0.05)
        )
        rng = np.random.default_rng(0)
        batch = {
            "image": rng.normal(size=(8, 16, 16, 3)).astype(np.float32),
            "label": rng.integers(0, 4, 8),
        }
        losses = []
        for _ in range(6):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], losses


class TestResnetUnderTheCallersRules:
    def test_kernel_shards_follow_the_trainers_rules(self, monkeypatch):
        """A Trainer whose rules put the batch on ``tp`` hands the same
        rules to ``resnet.loss_fn``: every GroupNorm kernel of the step
        ``fit`` would dispatch then runs on a tp shard of the batch —
        not on the default table's dp/fsdp, not on the whole batch."""
        import functools

        import optax

        from cloud_tpu.models import resnet
        from cloud_tpu.parallel import sharding
        from cloud_tpu.training import trainer

        monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
        cfg = resnet.ResNetConfig(
            stage_sizes=(1,), width=8, num_classes=4, num_groups=4,
            dtype=jnp.float32,
        )
        mesh = parallel.MeshSpec({"tp": 4}).build(jax.devices()[:4])
        rng = np.random.default_rng(0)
        batch = {
            "image": rng.normal(size=(8, 16, 16, 3)).astype(np.float32),
            "label": rng.integers(0, 4, 8).astype(np.int32),
        }

        from cloud_tpu.ops import dispatch

        asked = []
        dividing_axes = dispatch.dividing_axes

        def spy(mesh, axes, size):
            asked.append(axes)
            return dividing_axes(mesh, axes, size)

        monkeypatch.setattr(dispatch, "dividing_axes", spy)

        def kernel_batches(rules):
            del asked[:]
            t = trainer.Trainer(
                functools.partial(resnet.loss_fn, config=cfg, rules=rules,
                                  mesh=mesh),
                optax.sgd(0.05), functools.partial(resnet.init, config=cfg),
                mesh=mesh, rules=rules,
                logical_axes=resnet.param_logical_axes(cfg),
            )
            t.init_state(jax.random.PRNGKey(0))
            text = t.lower_train_step(batch).as_text()
            # The stem's kernel sees [b, 8, 8, 8] after the stride-2 conv.
            return {b for b in (2, 8) if f"tensor<{b}x8x8x8xf32>" in text}

        on_tp = sharding.DEFAULT_RULES.extended(batch="tp")
        assert 2 in kernel_batches(on_tp)
        # Every norm of the model (stem, three in the block, projection).
        assert len(asked) >= 5 and set(asked) == {"tp"}, asked
        # The default table splits the batch over dp/fsdp, which this mesh
        # does not have: the kernel then sees the whole batch.
        assert 2 not in kernel_batches(sharding.DEFAULT_RULES)
        assert set(asked) == {("dp", "fsdp")}, asked


class TestFusedResidual:
    """residual=: [relu](gn(x) + r) in one kernel — must equal the
    unfused composition exactly, with gradients flowing to x, scale,
    bias, AND the residual, on every route."""

    def _args(self, shape=(3, 8, 8, 64), groups=32):
        x = _rand(shape, seed=5)
        r = _rand(shape, seed=6, scale=1.0, offset=0.0)
        c = shape[-1]
        scale = _rand((c,), seed=7, scale=0.3, offset=1.0)
        bias = _rand((c,), seed=8, scale=0.5, offset=0.0)
        return x, scale, bias, r, groups

    def _unfused(self, groups, relu):
        def f(x, s, b, r):
            y = _reference(x, s, b, groups) + r
            return jnp.maximum(y, 0.0) if relu else y
        return f

    @pytest.mark.parametrize("relu", [True, False])
    def test_kernel_matches_unfused(self, relu):
        x, scale, bias, r, groups = self._args()

        def fused(x, s, b, r):
            return group_norm(
                x, s, b, num_groups=groups, use_pallas=True, interpret=True,
                partitioned=False, residual=r,
                activation="relu" if relu else None,
            )

        loss = lambda fn: (
            lambda x, s, b, r: jnp.sum(fn(x, s, b, r) ** 2)
        )
        got = jax.value_and_grad(loss(fused), argnums=(0, 1, 2, 3))(
            x, scale, bias, r
        )
        want = jax.value_and_grad(
            loss(self._unfused(groups, relu)), argnums=(0, 1, 2, 3)
        )(x, scale, bias, r)
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )

    def test_partitioned_route_matches_direct(self):
        x, scale, bias, r, groups = self._args(shape=(4, 8, 8, 64))
        mesh = parallel.MeshSpec({"dp": 8}).build()

        def fused(part):
            def f(x, s, b, r):
                return group_norm(
                    x, s, b, num_groups=groups, use_pallas=True,
                    interpret=True, partitioned=part, residual=r,
                    activation="relu", batch_axes="dp",
                )
            return f

        loss = lambda fn: (
            lambda x, s, b, r: jnp.sum(fn(x, s, b, r) ** 2)
        )
        with parallel.use_mesh(mesh):
            got = jax.jit(jax.value_and_grad(
                loss(fused(True)), argnums=(0, 1, 2, 3)
            ))(x, scale, bias, r)
        want = jax.value_and_grad(
            loss(fused(False)), argnums=(0, 1, 2, 3)
        )(x, scale, bias, r)
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )

    def test_shape_mismatch_rejected(self):
        x, scale, bias, r, groups = self._args()
        with pytest.raises(ValueError, match="residual shape"):
            group_norm(x, scale, bias, num_groups=groups,
                       residual=r[:, :4])

    def test_reference_route_residual(self):
        x, scale, bias, r, groups = self._args()
        got = group_norm(x, scale, bias, num_groups=groups,
                         use_pallas=False, residual=r, activation="relu")
        want = np.maximum(
            np.asarray(_reference(x, scale, bias, groups)) + np.asarray(r),
            0.0,
        )
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)

    def test_large_block_drops_fusion_not_kernel(self, monkeypatch):
        """ResNet-224 stage-0 tails (56x56x256) exceed the residual VMEM
        budget: the dispatch must fall back to kernel-GN + XLA add/relu
        (the pre-fusion schedule), NOT to the jnp reference."""
        import sys

        # NB: ``import cloud_tpu.ops.group_norm`` yields the FUNCTION
        # (ops/__init__ rebinds the package attribute); the module lives
        # in sys.modules.
        gn_mod = sys.modules["cloud_tpu.ops.group_norm"]

        def boom(*a, **k):
            raise AssertionError("residual kernel ran on oversized block")

        monkeypatch.setattr(gn_mod, "_fwd_pallas_res", boom)
        calls = {"n": 0}
        real = gn_mod._fwd_pallas

        def spy(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(gn_mod, "_fwd_pallas", spy)
        shape, groups = (1, 56, 56, 256), 32
        x = _rand(shape, seed=9)
        r = _rand(shape, seed=10, scale=1.0, offset=0.0)
        scale = _rand((256,), seed=11, scale=0.3, offset=1.0)
        bias = _rand((256,), seed=12, scale=0.5, offset=0.0)
        got = group_norm(x, scale, bias, num_groups=groups, use_pallas=True,
                         interpret=True, partitioned=False, residual=r,
                         activation="relu")
        assert calls["n"] == 1  # the plain KERNEL ran (not the reference)
        want = np.maximum(
            np.asarray(_reference(x, scale, bias, groups)) + np.asarray(r),
            0.0,
        )
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=1e-4, atol=1e-4
        )

    def test_no_relu_backward_skips_residual_kernel(self, monkeypatch):
        """With activation=None, dres == dy exactly: the backward must
        not stream the residual through the fused bwd kernel at all."""
        import sys

        gn_mod = sys.modules["cloud_tpu.ops.group_norm"]

        def boom(*a, **k):
            raise AssertionError("residual bwd kernel ran with relu=False")

        monkeypatch.setattr(gn_mod, "_bwd_pallas_res", boom)
        x, scale, bias, r, groups = self._args()

        def f(x, s, b, r):
            return jnp.sum(group_norm(
                x, s, b, num_groups=groups, use_pallas=True, interpret=True,
                partitioned=False, residual=r,
            ) ** 2)

        _, grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3))(
            x, scale, bias, r
        )
        want = jax.value_and_grad(
            lambda x, s, b, r: jnp.sum(
                (_reference(x, s, b, groups) + r) ** 2
            ),
            argnums=(0, 1, 2, 3),
        )(x, scale, bias, r)[1]
        for g, w in zip(grads, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )
