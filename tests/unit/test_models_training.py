"""Model + training-stack tests on the virtual 8-device mesh.

Covers: every model trains (loss decreases), ring attention matches dense
attention exactly, MoE/pp/ep configurations compile and run, Trainer
callback protocol, checkpoint round-trip.
"""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from cloud_tpu import parallel
from cloud_tpu.models import bert, layers, mnist, moe, resnet, transformer
from cloud_tpu.parallel.ring_attention import ring_attention
from cloud_tpu.training import (
    Trainer,
    create_sharded_state,
    data,
    make_train_step,
)
from cloud_tpu.training import train as train_lib
from jax.sharding import PartitionSpec


def make_trainer(cfg, mesh, rules=parallel.DEFAULT_RULES, lr=1e-3):
    return Trainer(
        functools.partial(transformer.loss_fn, config=cfg, mesh=mesh, rules=rules),
        optax.adamw(lr),
        init_fn=functools.partial(transformer.init, config=cfg),
        mesh=mesh,
        logical_axes=transformer.param_logical_axes(cfg),
        rules=rules,
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense_attention(self, causal):
        """Ring attention over 4 sequence shards == single-device attention."""
        mesh = parallel.MeshSpec({"sp": 4}).build(jax.devices()[:4])
        b, t, h, d = 2, 32, 4, 16
        rng = jax.random.PRNGKey(0)
        rq, rk, rv = jax.random.split(rng, 3)
        q = jax.random.normal(rq, (b, t, h, d), jnp.float32)
        k = jax.random.normal(rk, (b, t, h, d), jnp.float32)
        v = jax.random.normal(rv, (b, t, h, d), jnp.float32)

        expected = layers.causal_attention(q, k, v, causal=causal)

        spec = PartitionSpec(None, "sp", None, None)
        ring = jax.jit(
            jax.shard_map(
                functools.partial(ring_attention, axis="sp", causal=causal),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )
        )
        np.testing.assert_allclose(
            np.asarray(ring(q, k, v)), np.asarray(expected), atol=2e-5
        )

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("interpret", [False, True])
    def test_masked_ring_matches_dense(self, causal, interpret):
        """The padding mask rides the ring with its K/V block: masked ring
        over 4 shards == dense masked attention (fwd + grad).  Padded-row
        q outputs are garbage by contract, so compare under the mask."""
        mesh = parallel.MeshSpec({"sp": 4}).build(jax.devices()[:4])
        b, t, h, d = 2, 32, 2, 8
        rng = jax.random.PRNGKey(1)
        rq, rk, rv = jax.random.split(rng, 3)
        q = jax.random.normal(rq, (b, t, h, d), jnp.float32)
        k = jax.random.normal(rk, (b, t, h, d), jnp.float32)
        v = jax.random.normal(rv, (b, t, h, d), jnp.float32)
        # Ragged valid lengths spanning shard boundaries.
        mask = np.zeros((b, t), np.int32)
        mask[0, :19] = 1
        mask[1, :32] = 1
        mask = jnp.asarray(mask)
        row_w = mask.astype(jnp.float32)[:, :, None, None]

        def dense_loss(q, k, v):
            out = layers.causal_attention(q, k, v, causal=causal, mask=mask)
            return jnp.sum((out * row_w) ** 2)

        spec = PartitionSpec(None, "sp", None, None)
        mask_spec = PartitionSpec(None, "sp")
        def ring_body(q_, k_, v_, m_):
            return ring_attention(
                q_, k_, v_, axis="sp", causal=causal, mask=m_,
                interpret=interpret,
            )

        ring = jax.shard_map(
            ring_body,
            mesh=mesh,
            in_specs=(spec, spec, spec, mask_spec),
            out_specs=spec,
            check_vma=False,
        )

        def ring_loss(q, k, v):
            out = ring(q, k, v, mask)
            return jnp.sum((out * row_w) ** 2)

        got = jax.jit(jax.value_and_grad(ring_loss, argnums=(0, 1, 2)))(
            q, k, v
        )
        want = jax.jit(jax.value_and_grad(dense_loss, argnums=(0, 1, 2)))(
            q, k, v
        )
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
            )

    def test_sharded_attention_routes_masked_sp_through_ring(self, monkeypatch):
        """Dispatch seam: sp>1 with a padding mask must take the ring (not
        the GSPMD reference fallback it used previously)."""
        import cloud_tpu.models.layers as layers_mod
        from cloud_tpu.parallel import ring_attention as ring_mod

        called = {}
        real = ring_mod.ring_attention

        def spy(q, k, v, **kw):
            called["mask"] = kw.get("mask") is not None
            return real(q, k, v, **kw)

        # sharded_attention imports ring_attention inside the function
        # body at call time, so patching the source module is sufficient.
        monkeypatch.setattr(ring_mod, "ring_attention", spy)

        mesh = parallel.MeshSpec({"sp": 4}).build(jax.devices()[:4])
        b, t, h, d = 2, 32, 2, 8
        q = jnp.ones((b, t, h, d), jnp.float32)
        mask = jnp.ones((b, t), jnp.int32)
        with parallel.use_mesh(mesh):
            out = layers_mod.sharded_attention(
                q, q, q, causal=False, mask=mask, mesh=mesh
            )
        assert out.shape == (b, t, h, d)
        assert called.get("mask") is True


class TestBalancedRingAttention:
    """Zig-zag causal ring == dense attention, for values and gradients."""

    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("interpret", [False, True])
    def test_matches_dense_causal(self, n, interpret):
        """interpret=True runs every square sub-attention through the
        Pallas kernels (the path real TPUs take)."""
        from cloud_tpu.parallel.ring_attention import (
            ring_attention_balanced,
            zigzag_indices,
        )

        b, t, h, d = 2, 64, 2, 8
        rng = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(rng, 3)
        q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
        k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
        v = jax.random.normal(k3, (b, t, h, d), jnp.float32)
        expected = layers.causal_attention(q, k, v, causal=True)

        perm = zigzag_indices(t, n)
        inv = zigzag_indices(t, n, inverse=True)
        mesh = parallel.MeshSpec({"sp": n}).build(jax.devices()[:n])
        spec = PartitionSpec(None, "sp", None, None)
        ring = jax.jit(
            jax.shard_map(
                functools.partial(
                    ring_attention_balanced, axis="sp", interpret=interpret
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )
        )
        out_zz = ring(q[:, perm], k[:, perm], v[:, perm])
        out = out_zz[:, inv]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), atol=2e-5
        )

    def test_gradients_match_dense(self):
        from cloud_tpu.parallel.ring_attention import (
            ring_attention_balanced,
            zigzag_indices,
        )

        b, t, h, d, n = 1, 32, 2, 8, 2
        rng = jax.random.PRNGKey(1)
        k1, k2, k3 = jax.random.split(rng, 3)
        q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
        k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
        v = jax.random.normal(k3, (b, t, h, d), jnp.float32)

        def dense_loss(q, k, v):
            out = layers.causal_attention(q, k, v, causal=True)
            # Position-weighted loss: catches any permutation mistakes a
            # symmetric mean would hide.
            w = jnp.arange(t, dtype=jnp.float32)[None, :, None, None]
            return jnp.mean(w * out.astype(jnp.float32) ** 2)

        perm = zigzag_indices(t, n)
        inv = zigzag_indices(t, n, inverse=True)
        mesh = parallel.MeshSpec({"sp": n}).build(jax.devices()[:n])
        spec = PartitionSpec(None, "sp", None, None)
        ring = jax.shard_map(
            functools.partial(ring_attention_balanced, axis="sp"),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )

        def ring_loss(q, k, v):
            out = ring(q[:, perm], k[:, perm], v[:, perm])[:, inv]
            w = jnp.arange(t, dtype=jnp.float32)[None, :, None, None]
            return jnp.mean(w * out.astype(jnp.float32) ** 2)

        dense_grads = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        ring_grads = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        for g, rg in zip(ring_grads, dense_grads):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(rg), atol=5e-5, rtol=1e-3
            )

    def test_zigzag_indices_round_trip(self):
        from cloud_tpu.parallel.ring_attention import zigzag_indices

        t, n = 48, 4
        perm = np.asarray(zigzag_indices(t, n))
        inv = np.asarray(zigzag_indices(t, n, inverse=True))
        assert sorted(perm.tolist()) == list(range(t))
        np.testing.assert_array_equal(perm[inv], np.arange(t))
        # Rank 0's shard holds chunks 0 and 2n-1 (first and last).
        chunk = t // (2 * n)
        shard0 = perm[: 2 * chunk]
        assert shard0[:chunk].tolist() == list(range(chunk))
        assert shard0[chunk:].tolist() == list(range(t - chunk, t))

    def test_bad_seq_len_raises(self):
        from cloud_tpu.parallel.ring_attention import zigzag_indices

        with pytest.raises(ValueError, match="divisible"):
            zigzag_indices(30, 4)

    @pytest.mark.slow
    def test_transformer_zigzag_matches_unsharded(self):
        """config.zigzag_sp end to end: loss AND param grads on an sp=4
        mesh equal the single-device natural-order baseline (callers feed
        natural-order tokens; the model owns the permutation).

        Slow tier: whole-transformer loss+grad parity on an 8-device CPU
        mesh (~15-25s on the rig); the op-level zigzag parity tests in
        this class stay fast."""
        cfg = transformer.TINY.scaled(dtype=jnp.float32, zigzag_sp=True)
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(3)
        batch = {
            "tokens": rng.integers(0, 255, (2, 64)).astype(np.int32),
            "loss_mask": (rng.random((2, 64)) > 0.2).astype(np.float32),
        }

        ref_cfg = transformer.TINY.scaled(dtype=jnp.float32)
        loss_ref, grads_ref = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, batch, ref_cfg, mesh=None)[0]
        )(params)

        mesh = parallel.MeshSpec({"sp": 4}).build(jax.devices()[:4])
        with parallel.use_mesh(mesh):
            sharded = train_lib.shard_batch(batch, mesh)
            loss_zz, grads_zz = jax.jit(
                jax.value_and_grad(
                    lambda p: transformer.loss_fn(
                        p, sharded, cfg, mesh=mesh
                    )[0]
                )
            )(params)
        np.testing.assert_allclose(float(loss_zz), float(loss_ref), rtol=1e-5)
        for g, rg in zip(
            jax.tree_util.tree_leaves(grads_zz),
            jax.tree_util.tree_leaves(grads_ref),
        ):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(rg), atol=1e-4, rtol=5e-3
            )

    def test_zigzag_with_pp_raises(self):
        cfg = transformer.TINY.scaled(zigzag_sp=True)
        mesh = parallel.MeshSpec({"pp": 2, "sp": 2, "dp": 2}).build()
        rules = parallel.DEFAULT_RULES.extended(layers="pp")
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((4, 32), jnp.int32)
        with pytest.raises(ValueError, match="incompatible"):
            transformer.apply(params, tokens, cfg, rules=rules, mesh=mesh)


class TestViT:
    @pytest.mark.parametrize("pooling", ["gap", "cls"])
    def test_trains_on_separable_data(self, pooling):
        from cloud_tpu.models import vit

        cfg = vit.VIT_TINY_CIFAR.scaled(
            dtype=jnp.float32, num_layers=2, pooling=pooling
        )
        rng = np.random.default_rng(0)
        n = 64
        labels = rng.integers(0, 2, n).astype(np.int32)
        # Class signal in the channel mean — linearly separable.
        images = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
        images += labels[:, None, None, None] * 2.0

        tr = Trainer(
            functools.partial(vit.loss_fn, cfg=cfg),
            optax.adam(1e-3),
            init_fn=functools.partial(vit.init, cfg=cfg),
        )
        tr.init_state(jax.random.PRNGKey(0))
        ds = data.ArrayDataset(
            {"image": images, "label": labels}, batch_size=16, shuffle=True
        )
        hist = tr.fit(ds, epochs=4)
        assert hist.history["loss"][-1] < hist.history["loss"][0]
        assert hist.history["accuracy"][-1] > 0.8

    def test_sharded_forward_matches_unsharded(self):
        from cloud_tpu.models import vit

        cfg = vit.VIT_TINY_CIFAR.scaled(dtype=jnp.float32, num_layers=2)
        params = vit.init(jax.random.PRNGKey(0), cfg)
        # Axes tree congruent with params (the zoo contract).
        jax.tree_util.tree_map(
            lambda p, a: None, params,
            vit.param_logical_axes(cfg),
            is_leaf=lambda x: isinstance(x, tuple) and not any(
                isinstance(e, dict) for e in x
            ),
        )
        rng = np.random.default_rng(1)
        images = jnp.asarray(
            rng.normal(size=(8, 32, 32, 3)), jnp.float32
        )
        plain = vit.apply(params, images, cfg)
        mesh = parallel.MeshSpec({"fsdp": 2, "dp": 2, "tp": 2}).build()
        with parallel.use_mesh(mesh):
            sharded = jax.jit(
                lambda p, x: vit.apply(p, x, cfg, mesh=mesh)
            )(params, images)
        np.testing.assert_allclose(
            np.asarray(plain), np.asarray(sharded), rtol=2e-4, atol=2e-4
        )

    def test_image_size_must_divide(self):
        from cloud_tpu.models import vit

        with pytest.raises(ValueError, match="divisible"):
            vit.init(
                jax.random.PRNGKey(0),
                vit.VIT_TINY_CIFAR.scaled(image_size=30),
            )


class TestGradAccumulation:
    def _setup(self):
        cfg = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        opt = optax.adamw(1e-3)
        state = train_lib.create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(transformer.init, config=cfg), opt, mesh=None,
        )
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, 255, (8, 16)).astype(np.int32)}
        return cfg, opt, state, batch

    def test_matches_full_batch_update(self):
        """Mean-reduced loss: 4 accumulated micro-batches produce the same
        gradients — and therefore the same updated params — as one full
        batch."""
        cfg, opt, state, batch = self._setup()
        loss = functools.partial(transformer.loss_fn, config=cfg, mesh=None)
        full = train_lib.make_train_step(loss, opt)
        accum = train_lib.make_train_step(loss, opt, accum_steps=4)
        # The step donates its input state — give each call its own copy.
        copy = lambda s: jax.tree_util.tree_map(jnp.copy, s)  # noqa: E731
        s_full, m_full = full(copy(state), batch)
        s_acc, m_acc = accum(copy(state), batch)
        np.testing.assert_allclose(
            float(m_full["loss"]), float(m_acc["loss"]), rtol=1e-6
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            s_full.params, s_acc.params,
        )

    def test_batch_must_divide(self):
        cfg, opt, state, batch = self._setup()
        loss = functools.partial(transformer.loss_fn, config=cfg, mesh=None)
        step = train_lib.make_train_step(loss, opt, accum_steps=3)
        with pytest.raises(ValueError, match="divisible"):
            step(state, batch)  # 8 % 3 != 0

    def test_stochastic_accumulation_uses_distinct_keys(self):
        """Each micro-batch gets its own dropout key: accumulating the
        SAME micro-batch twice must still see different masks (the loss
        for identical halves differs from a plain half-batch step)."""
        cfg = dataclasses.replace(bert.TINY, dropout_rate=0.3)
        opt = optax.adamw(1e-3)
        state = train_lib.create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(bert.init, cfg=cfg), opt, mesh=None,
            train_rng=jax.random.PRNGKey(7),
        )
        loss = functools.partial(bert.loss_fn, cfg=cfg)
        half = {
            "tokens": jnp.asarray([[1, 2, 3, 4]] * 2, jnp.int32),
            "label": jnp.asarray([0, 1], jnp.int32),
        }
        doubled = jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x, x]), half
        )
        accum = train_lib.make_train_step(
            loss, opt, stochastic=True, accum_steps=2
        )
        _, m = accum(state, doubled)
        # If both micro-batches used the SAME key, the accumulated loss
        # would equal a single half-batch evaluation exactly.
        single, _ = loss(
            train_lib.create_sharded_state(
                jax.random.PRNGKey(0),
                functools.partial(bert.init, cfg=cfg), opt, mesh=None,
            ).params,
            half,
            rng=jax.random.split(jax.random.PRNGKey(7))[1],
        )
        assert float(m["loss"]) != float(single)


class TestTiedEmbeddings:
    def test_no_head_params_and_trains(self):
        cfg = transformer.TINY.scaled(tied_embeddings=True)
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        assert "head" not in params
        assert "head" not in transformer.param_logical_axes(cfg)

        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 255, (64, 16)).astype(np.int32)
        mesh = parallel.MeshSpec({"fsdp": 4, "tp": 2}).build()
        tr = make_trainer(cfg, mesh)
        with parallel.use_mesh(mesh):
            tr.init_state(jax.random.PRNGKey(0))
            ds = data.ArrayDataset({"tokens": tokens}, batch_size=16)
            hist = tr.fit(ds, epochs=3)
        losses = hist.history["loss"]
        assert losses[-1] < losses[0]

    def test_generation_with_tied_head_matches_oracle(self):
        from cloud_tpu.models import generation

        cfg = transformer.TINY.scaled(
            tied_embeddings=True, dtype=jnp.float32, num_layers=2
        )
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, 255, (2, 6)).astype(np.int32)
        lens = np.asarray([3, 6], np.int32)
        got = generation.generate(
            params, jnp.asarray(prompt), jnp.asarray(lens), cfg,
            max_new_tokens=4,
            sample=generation.SampleConfig(temperature=0.0),
        )
        # Oracle: re-run the full forward per step, argmax last position.
        seqs = [list(prompt[i][: int(lens[i])]) for i in range(2)]
        want = []
        for _ in range(4):
            step_toks = []
            for i in range(2):
                toks = jnp.asarray(seqs[i], jnp.int32)[None, :]
                logits, _ = transformer.apply(params, toks, cfg, mesh=None)
                nxt = int(jnp.argmax(logits[0, -1]))
                seqs[i].append(nxt)
                step_toks.append(nxt)
            want.append(step_toks)
        np.testing.assert_array_equal(
            np.asarray(got["tokens"]), np.asarray(want).T
        )


class TestTransformer:
    def test_forward_shapes(self):
        cfg = transformer.TINY
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits, aux = transformer.apply(params, tokens, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        """Future tokens must not affect past logits."""
        cfg = transformer.TINY
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
        t2 = t1.at[:, -1].set(99)  # change only the last token
        l1, _ = transformer.apply(params, t1, cfg)
        l2, _ = transformer.apply(params, t2, cfg)
        np.testing.assert_allclose(
            np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]), atol=1e-5
        )

    def test_train_on_multi_axis_mesh_loss_decreases(self):
        mesh = parallel.MeshSpec({"fsdp": 2, "sp": 2, "tp": 2}).build()
        cfg = transformer.TINY
        with parallel.use_mesh(mesh):
            tr = make_trainer(cfg, mesh)
            tr.init_state(jax.random.PRNGKey(0))
            ds = data.synthetic_tokens(
                vocab_size=cfg.vocab_size, seq_len=32, batch_size=8, num_batches=4
            )
            hist = tr.fit(ds, epochs=3)
        losses = hist.history["loss"]
        assert losses[-1] < losses[0]

    def test_moe_ep_pp_mesh_trains(self):
        mesh = parallel.MeshSpec({"pp": 2, "fsdp": 2, "ep": 2}).build()
        cfg = transformer.TINY.scaled(moe=moe.MoeConfig(num_experts=4, top_k=2))
        rules = parallel.DEFAULT_RULES.extended(layers="pp")
        with parallel.use_mesh(mesh):
            tr = make_trainer(cfg, mesh, rules=rules)
            tr.init_state(jax.random.PRNGKey(0))
            ds = data.synthetic_tokens(
                vocab_size=cfg.vocab_size, seq_len=32, batch_size=8, num_batches=2
            )
            hist = tr.fit(ds, epochs=2)
        assert hist.history["loss"][-1] < hist.history["loss"][0]
        assert hist.history["aux"][0] > 0.0  # MoE balance loss active

    def test_params_actually_sharded(self):
        mesh = parallel.MeshSpec({"fsdp": 4, "tp": 2}).build()
        cfg = transformer.TINY
        state = create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(transformer.init, config=cfg),
            optax.adamw(1e-3),
            mesh,
            logical_axes=transformer.param_logical_axes(cfg),
        )
        # attention q kernel: [layers, embed(fsdp), heads(tp)]
        q_kernel = state.params["layers"]["att"]["q"]["kernel"]
        assert len(q_kernel.addressable_shards) == 8
        shard = q_kernel.addressable_shards[0].data
        assert shard.shape[1] == cfg.dim // 4
        assert shard.shape[2] == (cfg.num_heads * cfg.head_dim) // 2
        # optimizer state inherits the same layout
        mu = None
        for leaf in jax.tree_util.tree_leaves(state.opt_state):
            if leaf.shape == q_kernel.shape:
                mu = leaf
                break
        assert mu is not None
        assert mu.addressable_shards[0].data.shape == shard.shape


class TestMoeUnit:
    def test_router_z_loss(self):
        """z_loss adds a positive logsumexp^2 penalty whose gradient flows
        to the router kernel (and nothing else changes when disabled)."""
        cfg0 = moe.MoeConfig(num_experts=4, top_k=2)
        cfg1 = moe.MoeConfig(num_experts=4, top_k=2, z_loss_weight=1e-3)
        params, _ = moe.moe_mlp_init(jax.random.PRNGKey(0), 16, 32, cfg0)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        out0, a0 = moe.moe_mlp_apply(params, x, cfg0)
        out1, a1 = moe.moe_mlp_apply(params, x, cfg1)
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
        assert float(a1) > float(a0)
        g = jax.grad(
            lambda p: moe.moe_mlp_apply(p, x, cfg1)[1]
        )(params)
        assert float(jnp.abs(g["router"]["kernel"]).sum()) > 0

    def test_top1_routing_capacity(self):
        cfg = moe.MoeConfig(num_experts=2, top_k=1, capacity_factor=2.0)
        params, _ = moe.moe_mlp_init(jax.random.PRNGKey(0), 8, 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 8))
        out, aux = moe.moe_mlp_apply(params, x, cfg)
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()
        assert float(aux) >= 0.0


class TestMnist:
    def test_trains_to_high_accuracy_on_separable_data(self):
        rng = np.random.default_rng(0)
        n = 512
        labels = rng.integers(0, 10, n)
        images = np.zeros((n, 28, 28), np.float32)
        images[np.arange(n), labels, labels] = 1.0  # trivially separable
        mesh = parallel.MeshSpec({"dp": 8}).build()
        cfg = mnist.MnistConfig()
        tr = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            optax.adam(1e-2),
            init_fn=functools.partial(mnist.init, config=cfg),
            mesh=mesh,
            logical_axes=mnist.param_logical_axes(cfg),
        )
        tr.init_state(jax.random.PRNGKey(0))
        ds = data.ArrayDataset(
            {"image": images, "label": labels}, batch_size=64, shuffle=True
        )
        hist = tr.fit(ds, epochs=5)
        assert hist.history["accuracy"][-1] > 0.9


class TestResnet:
    @pytest.mark.slow
    def test_forward_and_one_step(self):
        cfg = resnet.RESNET50_CIFAR
        params = resnet.init(jax.random.PRNGKey(0), cfg)
        images = jnp.zeros((2, 32, 32, 3), jnp.float32)
        logits = resnet.apply(params, images, cfg)
        assert logits.shape == (2, 10)
        step = make_train_step(
            functools.partial(resnet.loss_fn, config=cfg), optax.sgd(0.1)
        )
        state = create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(resnet.init, config=cfg),
            optax.sgd(0.1),
            mesh=None,
        )
        batch = {
            "image": np.random.default_rng(0).normal(size=(4, 32, 32, 3)).astype(np.float32),
            "label": np.array([0, 1, 2, 3]),
        }
        new_state, metrics = step(state, batch)
        assert int(new_state.step) == 1
        assert np.isfinite(metrics["loss"])


class TestDropout:
    def test_identity_when_off(self):
        x = jnp.ones((4, 8))
        np.testing.assert_array_equal(
            np.asarray(layers.dropout(None, x, 0.5)), np.asarray(x)
        )
        np.testing.assert_array_equal(
            np.asarray(layers.dropout(jax.random.PRNGKey(0), x, 0.0)),
            np.asarray(x),
        )

    def test_scales_and_zeroes(self):
        x = jnp.ones((100, 100))
        y = np.asarray(layers.dropout(jax.random.PRNGKey(0), x, 0.25))
        assert set(np.unique(y)).issubset({0.0, np.float32(1 / 0.75)})
        # Keep fraction near 0.75, and the expectation is preserved.
        assert abs((y > 0).mean() - 0.75) < 0.02
        assert abs(y.mean() - 1.0) < 0.02

    def test_bert_dropout_stochastic_in_train_deterministic_in_eval(self):
        cfg = dataclasses.replace(bert.TINY, dropout_rate=0.1)
        params = bert.init(jax.random.PRNGKey(0), cfg)
        batch = {
            "tokens": jnp.asarray([[1, 2, 3, 4]] * 2, jnp.int32),
            "label": jnp.asarray([0, 1], jnp.int32),
        }
        l1, _ = bert.loss_fn(params, batch, cfg, rng=jax.random.PRNGKey(1))
        l2, _ = bert.loss_fn(params, batch, cfg, rng=jax.random.PRNGKey(2))
        l_eval1, _ = bert.loss_fn(params, batch, cfg)
        l_eval2, _ = bert.loss_fn(params, batch, cfg)
        assert float(l1) != float(l2)  # different masks, different loss
        assert float(l_eval1) == float(l_eval2)  # no rng -> deterministic

    def test_stochastic_train_step_threads_rng(self):
        cfg = dataclasses.replace(bert.TINY, dropout_rate=0.1)
        opt = optax.adam(1e-3)
        state = train_lib.create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(bert.init, cfg=cfg),
            opt, mesh=None, train_rng=jax.random.PRNGKey(7),
        )
        step = train_lib.make_train_step(
            functools.partial(bert.loss_fn, cfg=cfg), opt, stochastic=True
        )
        batch = {
            "tokens": jnp.asarray([[1, 2, 3, 4]] * 4, jnp.int32),
            "label": jnp.asarray([0, 1, 0, 1], jnp.int32),
        }
        rng_before = np.asarray(state.rng).copy()  # step donates the state
        s1, m1 = step(state, batch)
        assert not np.array_equal(np.asarray(s1.rng), rng_before)
        s2, m2 = step(s1, batch)
        # Same batch, fresh dropout mask -> different loss values.
        assert float(m1["loss"]) != float(m2["loss"])

    def test_trainer_fit_with_dropout(self):
        cfg = dataclasses.replace(bert.TINY, dropout_rate=0.1)
        rng = np.random.default_rng(0)
        n = 32
        labels = rng.integers(0, 2, n)
        tokens = np.where(
            labels[:, None] == 1,
            rng.integers(256, 512, (n, 8)),
            rng.integers(1, 256, (n, 8)),
        ).astype(np.int32)
        tr = Trainer(
            functools.partial(bert.loss_fn, cfg=cfg),
            optax.adam(1e-3),
            init_fn=functools.partial(bert.init, cfg=cfg),
            stochastic=True,
        )
        tr.init_state(jax.random.PRNGKey(0))
        assert tr.state.rng is not None
        ds = data.ArrayDataset(
            {"tokens": tokens, "label": labels}, batch_size=16
        )
        hist = tr.fit(ds, epochs=3)
        losses = hist.history["loss"]
        assert losses[-1] < losses[0]

    def test_stochastic_without_rng_raises(self):
        opt = optax.adam(1e-3)
        state = train_lib.create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(bert.init, cfg=bert.TINY),
            opt, mesh=None,
        )
        step = train_lib.make_train_step(
            functools.partial(bert.loss_fn, cfg=bert.TINY), opt,
            stochastic=True,
        )
        with pytest.raises(ValueError, match="train_rng"):
            step(state, {
                "tokens": jnp.zeros((2, 4), jnp.int32),
                "label": jnp.zeros((2,), jnp.int32),
            })


class TestBert:
    def test_bidirectional_and_trains(self):
        cfg = bert.TINY
        mesh = parallel.MeshSpec({"fsdp": 4, "tp": 2}).build()
        params = bert.init(jax.random.PRNGKey(0), cfg)
        tokens = jnp.array([[1, 2, 3, 4]], jnp.int32)
        # changing the LAST token changes the FIRST position's encoding
        enc1 = bert.encode(params, tokens, cfg)
        enc2 = bert.encode(params, tokens.at[:, -1].set(9), cfg)
        assert not np.allclose(np.asarray(enc1[:, 0]), np.asarray(enc2[:, 0]))

        rng = np.random.default_rng(0)
        n = 64
        labels = rng.integers(0, 2, n)
        tokens = np.where(
            labels[:, None] == 1,
            rng.integers(256, 512, (n, 16)),
            rng.integers(1, 256, (n, 16)),
        ).astype(np.int32)
        tr = Trainer(
            functools.partial(bert.loss_fn, cfg=cfg),
            optax.adam(1e-3),
            init_fn=functools.partial(bert.init, cfg=cfg),
            mesh=mesh,
            logical_axes=bert.param_logical_axes(cfg),
        )
        with parallel.use_mesh(mesh):
            tr.init_state(jax.random.PRNGKey(0))
            ds = data.ArrayDataset(
                {"tokens": tokens, "label": labels}, batch_size=16, shuffle=True
            )
            hist = tr.fit(ds, epochs=4)
        assert hist.history["accuracy"][-1] > 0.8


class TestTrainerProtocol:
    def test_callbacks_and_validation(self):
        events = []

        from cloud_tpu.training.trainer import Callback

        class Rec(Callback):
            def on_train_begin(self, trainer):
                events.append("train_begin")

            def on_epoch_end(self, epoch, logs, trainer):
                events.append(("epoch_end", epoch, "val_loss" in logs))

            def on_train_end(self, trainer):
                events.append("train_end")

        cfg = mnist.MnistConfig(hidden_dim=32)
        tr = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            optax.adam(1e-3),
            init_fn=functools.partial(mnist.init, config=cfg),
        )
        tr.init_state(jax.random.PRNGKey(0))
        arrays = {
            "image": np.zeros((32, 784), np.float32),
            "label": np.zeros((32,), np.int64),
        }
        ds = data.ArrayDataset(arrays, batch_size=16)
        tr.fit(ds, epochs=2, validation_data=ds, callbacks=[Rec()])
        assert events[0] == "train_begin"
        assert events[-1] == "train_end"
        assert ("epoch_end", 0, True) in events

    def test_early_stop_via_stop_training(self):
        from cloud_tpu.training.trainer import LambdaCallback

        def stop(step, logs, trainer):
            trainer.stop_training = True

        cfg = mnist.MnistConfig(hidden_dim=32)
        tr = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            optax.adam(1e-3),
            init_fn=functools.partial(mnist.init, config=cfg),
        )
        tr.init_state(jax.random.PRNGKey(0))
        ds = data.ArrayDataset(
            {"image": np.zeros((64, 784), np.float32),
             "label": np.zeros((64,), np.int64)},
            batch_size=8,
        )
        tr.fit(ds, epochs=3)
        # stop after first step of first epoch
        tr2 = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            optax.adam(1e-3),
            init_fn=functools.partial(mnist.init, config=cfg),
        )
        tr2.init_state(jax.random.PRNGKey(0))
        tr2.fit(ds, epochs=3, callbacks=[LambdaCallback(on_step_end=stop)])
        assert int(tr2.state.step) == 1


class TestEarlyStopping:
    """ADVICE r1: EarlyStopping semantics incl. the sharded-state restore."""

    class _FakeTrainer:
        def __init__(self, state=None):
            self.state = state
            self.stop_training = False

    def _run(self, cb, values, trainer=None):
        trainer = trainer or self._FakeTrainer()
        cb.on_train_begin(trainer)
        for epoch, v in enumerate(values):
            cb.on_epoch_end(epoch, {cb.monitor: v}, trainer)
            if trainer.stop_training:
                break
        cb.on_train_end(trainer)
        return trainer

    def test_min_mode_stops_after_patience(self):
        from cloud_tpu.training import EarlyStopping

        cb = EarlyStopping("loss", mode="min", patience=1)
        tr = self._run(cb, [3.0, 2.0, 2.5, 2.6, 1.0])
        assert tr.stop_training
        assert cb.stopped_epoch == 3  # two non-improving epochs after best

    def test_auto_mode_maximizes_accuracy(self):
        from cloud_tpu.training import EarlyStopping

        cb = EarlyStopping("val_accuracy", patience=0)
        tr = self._run(cb, [0.5, 0.7, 0.6])
        assert cb._sign == 1.0
        assert tr.stop_training and cb.stopped_epoch == 2

    def test_min_delta_counts_marginal_gains_as_stalls(self):
        from cloud_tpu.training import EarlyStopping

        cb = EarlyStopping("loss", mode="min", min_delta=0.5, patience=0)
        tr = self._run(cb, [3.0, 2.8, 2.7])  # improvements < 0.5
        assert tr.stop_training and cb.stopped_epoch == 1

    def test_missing_metric_is_tolerated(self):
        from cloud_tpu.training import EarlyStopping

        cb = EarlyStopping("val_loss", patience=0)
        trainer = self._FakeTrainer()
        cb.on_train_begin(trainer)
        cb.on_epoch_end(0, {"loss": 1.0}, trainer)
        assert not trainer.stop_training

    def test_best_shardings_initialized_in_init(self):
        """Restore paths must not depend on on_train_begin having run:
        a callback restored/reused with a host-side _best_state reaches
        on_train_end's device_put branch, which reads _best_shardings —
        previously only set in on_train_begin (AttributeError)."""
        from cloud_tpu.training import EarlyStopping

        cb = EarlyStopping("loss", restore_best_state=True)
        assert cb._best_shardings is None
        # Simulate a cross-process restore: host-array best state present,
        # on_train_begin never called in this process.
        cb._best_state = {"w": np.ones((2, 2), np.float32)}
        trainer = self._FakeTrainer()
        cb.on_train_end(trainer)  # must not raise AttributeError
        np.testing.assert_array_equal(
            np.asarray(trainer.state["w"]), np.ones((2, 2), np.float32)
        )

    def test_restore_best_state_preserves_values_and_shardings(self):
        from cloud_tpu.training import EarlyStopping

        cfg = mnist.MnistConfig(hidden_dim=16)
        mesh = parallel.MeshSpec({"fsdp": 8}).build()
        logical_axes = mnist.param_logical_axes(cfg)
        with parallel.use_mesh(mesh):
            state = create_sharded_state(
                jax.random.PRNGKey(0),
                functools.partial(mnist.init, config=cfg),
                optax.adam(1e-3),
                mesh,
                logical_axes=logical_axes,
            )
        trainer = self._FakeTrainer(state)
        best_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, state
        )
        best_host = jax.device_get(state)

        cb = EarlyStopping("loss", mode="min", patience=0,
                           restore_best_state=True)
        cb.on_train_begin(trainer)
        cb.on_epoch_end(0, {"loss": 1.0}, trainer)  # best snapshot here
        # Degrade the live state, then stall out.
        trainer.state = jax.tree_util.tree_map(lambda x: x + 1, state)
        cb.on_epoch_end(1, {"loss": 2.0}, trainer)
        cb.on_train_end(trainer)

        assert trainer.stop_training and cb.stopped_epoch == 1
        restored_host = jax.device_get(trainer.state)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            restored_host, best_host,
        )
        restored_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, trainer.state
        )
        flat_r = jax.tree_util.tree_leaves(restored_shardings)
        flat_b = jax.tree_util.tree_leaves(best_shardings)
        assert all(r == b for r, b in zip(flat_r, flat_b))


class TestTerminateOnNaN:
    def test_stops_on_nonfinite_loss(self):
        from cloud_tpu.training import TerminateOnNaN

        cfg = mnist.MnistConfig(hidden_dim=16)
        trainer = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            # Absurd LR: loss overflows to nan/inf within a few steps.
            optax.sgd(1e18),
            init_fn=functools.partial(mnist.init, config=cfg),
        )
        trainer.init_state(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        ds = data.ArrayDataset(
            {
                "image": (rng.normal(size=(64, 28, 28)) * 1e6).astype(
                    np.float32
                ),
                "label": rng.integers(0, 10, 64),
            },
            batch_size=16,
        )
        guard = TerminateOnNaN(check_every_n_steps=1)
        trainer.fit(ds, epochs=50, callbacks=[guard])
        assert guard.stopped_step is not None
        assert trainer.stop_training

    def test_finite_training_untouched(self):
        from cloud_tpu.training import TerminateOnNaN

        cfg = mnist.MnistConfig(hidden_dim=16)
        trainer = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            optax.adam(1e-3),
            init_fn=functools.partial(mnist.init, config=cfg),
        )
        trainer.init_state(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        ds = data.ArrayDataset(
            {
                "image": rng.normal(size=(64, 28, 28)).astype(np.float32),
                "label": rng.integers(0, 10, 64),
            },
            batch_size=16,
        )
        guard = TerminateOnNaN(check_every_n_steps=1)
        history = trainer.fit(ds, epochs=2, callbacks=[guard])
        assert guard.stopped_step is None
        assert len(history.history["loss"]) == 2


class TestCheckpoint:
    def test_save_restore_round_trip(self, tmp_path):
        from cloud_tpu.training.checkpoint import CheckpointManager

        cfg = mnist.MnistConfig(hidden_dim=16)
        state = create_sharded_state(
            jax.random.PRNGKey(0),
            functools.partial(mnist.init, config=cfg),
            optax.adam(1e-3),
            mesh=None,
        )
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(0, state)
        mgr.wait()
        restored = mgr.restore(0, template=jax.tree_util.tree_map(np.asarray, state))
        np.testing.assert_allclose(
            np.asarray(state.params["hidden"]["kernel"]),
            restored.params["hidden"]["kernel"],
        )
        mgr.close()

    @pytest.mark.slow
    def test_restore_directly_into_sharded_layout(self, tmp_path):
        """Pod resume: a checkpoint saved from a sharded mesh restores
        STRAIGHT into the target shardings (template = ShapeDtypeStruct +
        NamedSharding; no replicated host copy in the middle), and the
        restored state continues training with the same loss trajectory."""
        from cloud_tpu.training.checkpoint import CheckpointManager

        cfg = transformer.TINY
        mesh = parallel.MeshSpec({"fsdp": 4, "tp": 2}).build()
        logical_axes = transformer.param_logical_axes(cfg)
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, 255, (8, 16)).astype(np.int32)}

        with parallel.use_mesh(mesh):
            state = create_sharded_state(
                jax.random.PRNGKey(0),
                functools.partial(transformer.init, config=cfg),
                optax.sgd(0.1),
                mesh,
                logical_axes=logical_axes,
            )
            step = make_train_step(
                functools.partial(transformer.loss_fn, config=cfg, mesh=mesh),
                optax.sgd(0.1),
                logical_axes=logical_axes,
                mesh=mesh,
            )
            sharded = train_lib.shard_batch(batch, mesh)
            state, _ = step(state, sharded)
            _, ref_metrics = step(
                jax.tree_util.tree_map(lambda x: x.copy(), state), sharded
            )

            mgr = CheckpointManager(str(tmp_path / "ckpt"))
            mgr.save(1, state)
            mgr.wait()

            template = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
                state,
            )
            restored = mgr.restore(1, template=template)
            # Restored leaves carry the target shardings...
            for got, want in zip(
                jax.tree_util.tree_leaves(restored),
                jax.tree_util.tree_leaves(state),
            ):
                assert got.sharding == want.sharding
            # ...and training continues identically.
            _, metrics = step(restored, sharded)
            np.testing.assert_allclose(
                float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-6
            )
            mgr.close()


class TestPreemptionResume:
    """The recovery contract end-to-end (VERDICT r3 #3): a preempted
    node's replacement re-runs the SAME script; CheckpointCallback's
    default resume restores the latest step instead of retraining."""

    def _build(self, ckpt_dir, every=2):
        from cloud_tpu.training.checkpoint import CheckpointCallback
        from cloud_tpu.training.trainer import Trainer

        cfg = mnist.MnistConfig(hidden_dim=16)
        tr = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            optax.sgd(0.1),
            init_fn=functools.partial(mnist.init, config=cfg),
        )
        tr.init_state(jax.random.PRNGKey(0))
        ds = data.ArrayDataset(
            {"image": np.zeros((32, 784), np.float32),
             "label": np.zeros((32,), np.int64)},
            batch_size=8,
        )
        cb = CheckpointCallback(ckpt_dir, every_n_steps=every)
        return tr, ds, cb

    def test_resumes_at_checkpointed_step(self, tmp_path):
        from cloud_tpu.training import trainer as trainer_lib

        ckpt = str(tmp_path / "ckpt")
        # "First boot": train 4 steps, checkpoints at steps 2 and 4.
        tr1, ds, cb1 = self._build(ckpt)
        tr1.fit(ds, epochs=1, callbacks=[cb1])
        assert int(tr1.state.step) == 4

        # "Preemption + recreate": a FRESH process re-runs the script —
        # fresh Trainer, fresh state at step 0, same checkpoint dir.
        tr2, ds2, cb2 = self._build(ckpt)
        assert int(tr2.state.step) == 0
        seen = []
        spy = trainer_lib.LambdaCallback(
            on_step_end=lambda step, logs, t: seen.append(step)
        )
        tr2.fit(ds2, epochs=1, callbacks=[cb2, spy])
        # Resumed from step 4, so the epoch's steps are 5..8 — not 1..4.
        assert seen[0] == 5 and int(tr2.state.step) == 8
        # And the resumed params really are the checkpointed ones, not a
        # fresh init: weights at resume-time match tr1's final weights.
        tr3, _, cb3 = self._build(ckpt)
        cb3.on_train_begin(tr3)  # restore only, no training
        np.testing.assert_allclose(
            np.asarray(tr3.state.params["hidden"]["kernel"]),
            np.asarray(tr2.state.params["hidden"]["kernel"]),
            atol=1e-6, rtol=1e-5,
        )

    def test_resume_opt_out_and_fresh_dir(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        tr1, ds, cb1 = self._build(ckpt)
        tr1.fit(ds, epochs=1, callbacks=[cb1])

        from cloud_tpu.training.checkpoint import CheckpointCallback

        tr2, ds2, _ = self._build(ckpt)
        cb = CheckpointCallback(ckpt, every_n_steps=2, resume=False)
        tr2.fit(ds2, epochs=1, callbacks=[cb])
        assert int(tr2.state.step) == 4  # trained from scratch

        # Fresh empty dir: resume=True is a no-op.
        tr3, ds3, cb3 = self._build(str(tmp_path / "fresh"))
        tr3.fit(ds3, epochs=1, callbacks=[cb3])
        assert int(tr3.state.step) == 4


class TestArrayDataset:
    def test_batching_and_reiteration(self):
        ds = data.ArrayDataset(
            {"x": np.arange(10)}, batch_size=3, drop_remainder=True
        )
        batches = list(ds())
        assert len(batches) == 3 == len(ds)
        assert all(b["x"].shape == (3,) for b in batches)
        # re-iterable
        assert len(list(ds())) == 3

    def test_shuffle_determinism_per_epoch(self):
        ds = data.ArrayDataset(
            {"x": np.arange(100)}, batch_size=10, shuffle=True, seed=1
        )
        first = np.concatenate([b["x"] for b in ds()])
        second = np.concatenate([b["x"] for b in ds()])
        assert not np.array_equal(first, second)  # reshuffles each epoch
        assert set(first) == set(range(100))

    def test_unequal_lengths_rejected(self):
        with pytest.raises(ValueError, match="Unequal"):
            data.ArrayDataset({"a": np.zeros(3), "b": np.zeros(4)}, batch_size=1)


class TestLowPrecisionOptimizerState:
    """bf16-at-rest optimizer moments (the BERT adamw HBM attack):
    state dtypes, traffic accounting,
    and trajectory closeness to the f32 baseline."""

    def _problem(self):
        rng = np.random.default_rng(0)
        w_true = rng.normal(size=(32, 8)).astype(np.float32)
        x = rng.normal(size=(256, 32)).astype(np.float32)
        y = x @ w_true

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            loss = jnp.mean((pred - batch["y"]) ** 2)
            return loss, {"loss": loss}

        params = {"w": jnp.zeros((32, 8), jnp.float32)}
        return loss_fn, params, {"x": jnp.asarray(x), "y": jnp.asarray(y)}

    def _run(self, tx, steps=80):
        from cloud_tpu.training import train as train_lib

        loss_fn, params, batch = self._problem()
        state = train_lib.create_sharded_state(
            jax.random.PRNGKey(0), lambda rng: params, tx, mesh=None,
        )
        step = train_lib.make_train_step(loss_fn, tx)
        for _ in range(steps):
            state, metrics = step(state, batch)
        return state, float(metrics["loss"])

    def test_preset_stores_mu_bf16_nu_f32(self):
        from cloud_tpu.training import optimizers

        state, _ = self._run(optimizers.adamw(1e-2), steps=2)

        def find_adam(s):
            if hasattr(s, "mu"):
                return s
            if isinstance(s, tuple):
                for sub in s:
                    got = find_adam(sub)
                    if got is not None:
                        return got
            return None

        adam_state = find_adam(state.opt_state)
        assert adam_state is not None
        mu = jax.tree_util.tree_leaves(adam_state.mu)[0]
        nu = jax.tree_util.tree_leaves(adam_state.nu)[0]
        assert mu.dtype == jnp.bfloat16
        assert nu.dtype == jnp.float32

    def test_cast_state_halves_moment_bytes(self):
        import optax

        from cloud_tpu.training import optimizers

        loss_fn, params, _ = self._problem()
        f32 = optax.adamw(1e-2)
        cast = optimizers.cast_state(optax.adamw(1e-2))
        bytes_f32 = optimizers.optimizer_state_bytes(f32.init(params))
        bytes_cast = optimizers.optimizer_state_bytes(cast.init(params))
        n = sum(p.size for p in jax.tree_util.tree_leaves(params))
        # Both moments dropped from 4 to 2 bytes/param.
        assert bytes_f32 - bytes_cast == 4 * n

    def test_trajectory_close_to_f32(self):
        import optax

        from cloud_tpu.training import optimizers

        _, ref_loss = self._run(optax.adamw(0.05))
        _, mu16_loss = self._run(optimizers.adamw(0.05))
        _, cast_loss = self._run(
            optimizers.cast_state(optax.adamw(0.05))
        )
        assert ref_loss < 2.0  # the problem actually optimizes (from ~32)
        assert abs(mu16_loss - ref_loss) < 0.2 * max(ref_loss, 0.05)
        assert abs(cast_loss - ref_loss) < 0.4 * max(ref_loss, 0.05)

    def test_cast_state_predicate_keeps_selected_leaves_wide(self):
        import optax

        from cloud_tpu.training import optimizers

        loss_fn, params, _ = self._problem()
        # Cast only leaves matching mu's id path is awkward structurally;
        # the practical predicate is size/shape-based.  Keep every leaf
        # wide => byte count matches plain f32.
        cast_none = optimizers.cast_state(
            optax.adamw(1e-2), should_cast=lambda leaf: False
        )
        assert optimizers.optimizer_state_bytes(
            cast_none.init(params)
        ) == optimizers.optimizer_state_bytes(optax.adamw(1e-2).init(params))


class TestUlyssesAttention:
    """Ulysses sequence parallelism (sp via seq<->head all-to-all): exact
    equivalence with the dense single-device forward, gradients included,
    plus the padding-mask path and the indivisible-heads ring fallback."""

    def _setup(self, sp=4, tp=1, ulysses=True):
        cfg = transformer.TINY.scaled(
            dtype=jnp.float32, num_layers=2, ulysses_sp=ulysses
        )
        sizes = {"sp": sp}
        if tp > 1:
            sizes["tp"] = tp
        if sp * tp < 8:
            sizes["dp"] = 8 // (sp * tp)  # the rig mesh must use all 8
        mesh = parallel.MeshSpec(sizes).build()
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        tokens = rng.integers(1, 255, (2, 32)).astype(np.int32)
        return cfg, mesh, params, jnp.asarray(tokens)

    def test_matches_dense_forward_and_grad(self):
        # sp=2 x tp=2: TINY has 4 heads -> 2 local heads, divisible by
        # sp=2, so the Ulysses path REALLY runs (ADVICE r4: sp=4/tp=2 made
        # every grad assertion here silently test the ring fallback).
        from cloud_tpu.models import layers as layers_lib

        cfg, mesh, params, tokens = self._setup(sp=2, tp=2)
        assert layers_lib.ulysses_eligible(cfg.num_heads, mesh)

        def loss(p, cfg_, mesh_):
            logits, _ = transformer.apply(p, tokens, cfg_, mesh=mesh_)
            return jnp.mean(logits.astype(jnp.float32) ** 2)

        dense_cfg = cfg.scaled(ulysses_sp=False)
        want, want_grads = jax.value_and_grad(
            lambda p: loss(p, dense_cfg, None)
        )(params)
        with parallel.use_mesh(mesh):
            jitted = jax.jit(jax.value_and_grad(lambda p: loss(p, cfg, mesh)))
            # The compiled module must contain the seq<->head all-to-alls
            # (fwd + bwd) — proof the Ulysses path was taken, not the ring
            # (whose signature is collective-permute).
            hlo = jitted.lower(params).compile().as_text()
            assert "all-to-all" in hlo
            got, got_grads = jitted(params)
        np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
        for g, w in zip(
            jax.tree_util.tree_leaves(got_grads),
            jax.tree_util.tree_leaves(want_grads),
        ):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=5e-4, atol=5e-6
            )

    def test_mask_rides_replicated(self):
        from cloud_tpu.models import layers as layers_lib

        mesh = parallel.MeshSpec({"dp": 2, "sp": 4}).build()
        rng = np.random.default_rng(1)
        b, t, h, d = 2, 16, 4, 8
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
            for _ in range(3)
        )
        mask = jnp.asarray([[1] * 12 + [0] * 4, [1] * 16], jnp.int32)
        want = layers_lib.sharded_attention(
            q, k, v, causal=False, mask=mask, mesh=None
        )
        with parallel.use_mesh(mesh):
            got = jax.jit(
                lambda q_, k_, v_, m_: layers_lib.sharded_attention(
                    q_, k_, v_, causal=False, mask=m_, mesh=mesh,
                    ulysses=True,
                )
            )(q, k, v, mask)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    def test_indivisible_heads_fall_back_to_ring(self):
        # TINY has 4 heads; sp=8 > heads => Ulysses ineligible, ring runs
        # (which handles any head count) — same numbers either way.
        cfg, mesh, params, tokens = self._setup(sp=8, tp=1)
        dense_cfg = cfg.scaled(ulysses_sp=False)

        def logits_of(cfg_, mesh_):
            with parallel.use_mesh(mesh) if mesh_ is not None else (
                contextlib.nullcontext()
            ):
                out, _ = (
                    jax.jit(
                        lambda p: transformer.apply(
                            p, tokens, cfg_, mesh=mesh_
                        )
                    )(params)
                    if mesh_ is not None
                    else transformer.apply(params, tokens, cfg_, mesh=None)
                )
            return np.asarray(out)

        want = logits_of(dense_cfg, None)
        got = logits_of(cfg, mesh)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_zigzag_and_ulysses_refused_together(self):
        cfg, mesh, params, tokens = self._setup(sp=4)
        bad = cfg.scaled(zigzag_sp=True)
        with pytest.raises(ValueError, match="mutually exclusive"):
            with parallel.use_mesh(mesh):
                transformer.apply(params, tokens, bad, mesh=mesh)


class TestRematPolicies:
    """remat_wrap is a pure scheduling change: loss AND gradients must be
    identical across none/full/dots on every model that exposes the knob
    (the scan remat policy is an ablation axis — the ablation is only
    meaningful if numerics hold)."""

    def test_transformer_policies_identical(self):
        cfg0 = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
        params = transformer.init(jax.random.PRNGKey(0), cfg0)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(1, 255, (2, 16)).astype(np.int32)
        )}
        results = {}
        for name, cfg in {
            "none": cfg0.scaled(remat=False),
            "full": cfg0.scaled(remat=True, remat_policy="full"),
            "dots": cfg0.scaled(remat=True, remat_policy="dots"),
        }.items():
            val, grads = jax.value_and_grad(
                lambda p, c=cfg: transformer.loss_fn(p, batch, c, mesh=None)[0]
            )(params)
            results[name] = (float(val), grads)
        base_val, base_grads = results["none"]
        for name in ("full", "dots"):
            val, grads = results[name]
            np.testing.assert_allclose(val, base_val, rtol=1e-6)
            for g, b in zip(
                jax.tree_util.tree_leaves(grads),
                jax.tree_util.tree_leaves(base_grads),
            ):
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(b), rtol=1e-5, atol=1e-7
                )

    def test_bert_policies_identical(self):
        cfg0 = bert.TINY
        params = bert.init(jax.random.PRNGKey(0), cfg=cfg0)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": rng.integers(0, 500, (2, 16)).astype(np.int32),
            "label": rng.integers(0, 2, 2).astype(np.int64),
        }
        vals = {}
        for policy in ("none", "full", "dots"):
            cfg = dataclasses.replace(cfg0, remat=policy)
            val, grads = jax.value_and_grad(
                lambda p, c=cfg: bert.loss_fn(p, batch, cfg=c)[0]
            )(params)
            vals[policy] = (float(val), grads)
        base_val, base_grads = vals["none"]
        for policy in ("full", "dots"):
            val, grads = vals[policy]
            np.testing.assert_allclose(val, base_val, rtol=1e-5)
            for g, b in zip(
                jax.tree_util.tree_leaves(grads),
                jax.tree_util.tree_leaves(base_grads),
            ):
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(b), rtol=1e-4, atol=1e-6
                )

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="remat policy"):
            layers.remat_wrap(lambda c, x: (c, None), True, "everything")
