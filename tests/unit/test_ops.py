"""Pallas kernel logic tests, run in interpreter mode on CPU.

The reference implementations (pure jnp) are the ground truth; the
interpreter executes the same kernel code paths that Mosaic compiles on
TPU.  The real Mosaic compile is asked for, without a chip, in
``tests/unit/test_chip_compile.py``, and run on the chip by
``chip_smoke.py``.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu import parallel
from cloud_tpu.ops import flash_attention
from cloud_tpu.ops.flash_attention import _reference


def make_qkv(b=2, t=256, h=2, d=64, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(k1, (b, t, h, d), dtype),
        jax.random.normal(k2, (b, t, h, d), dtype),
        jax.random.normal(k3, (b, t, h, d), dtype),
    )


class TestFlashAttentionForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = make_qkv()
        ref = _reference(q, k, v, causal=causal, mask=None)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_uneven_blocks(self):
        # T=256 with block 128: multiple blocks, diagonal straddles them.
        q, k, v = make_qkv(t=256)
        ref = _reference(q, k, v, causal=True, mask=None)
        out = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=64, interpret=True
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_block_larger_than_seq_clamps(self):
        q, k, v = make_qkv(t=64)
        ref = _reference(q, k, v, causal=True, mask=None)
        out = flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512, interpret=True
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_bfloat16(self):
        q, k, v = make_qkv(dtype=jnp.bfloat16)
        ref = _reference(q, k, v, causal=True, mask=None).astype(jnp.float32)
        out = flash_attention(q, k, v, causal=True, interpret=True).astype(
            jnp.float32
        )
        np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)

    def test_mask_routes_to_reference(self):
        q, k, v = make_qkv(t=64)
        mask = jnp.ones((2, 64), bool).at[:, 48:].set(False)
        out = flash_attention(q, k, v, causal=True, mask=mask)
        ref = _reference(q, k, v, causal=True, mask=mask)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_kernel_applies_padding_mask(self, causal):
        # r2: the kernels apply [B, T_k] padding masks in-VMEM (BERT's
        # fine-tune path); previously any mask forced the reference path.
        q, k, v = make_qkv(t=128)
        mask = jnp.ones((2, 128), bool).at[0, 96:].set(False).at[1, 64:].set(False)
        out = flash_attention(q, k, v, causal=causal, mask=mask,
                              interpret=True)
        ref = _reference(q, k, v, causal=causal, mask=mask)
        # Compare only valid query rows: fully-masked rows are documented
        # as garbage (finite NEG_INF semantics) on both paths.
        np.testing.assert_allclose(
            np.asarray(out)[0, :96], np.asarray(ref)[0, :96],
            atol=5e-4, rtol=1e-3,
        )
        np.testing.assert_allclose(
            np.asarray(out)[1, :64], np.asarray(ref)[1, :64],
            atol=5e-4, rtol=1e-3,
        )

    def test_kernel_mask_grads_match_reference(self):
        q, k, v = make_qkv(t=128)
        mask = jnp.ones((2, 128), bool).at[:, 96:].set(False)
        row_mask = mask.astype(jnp.float32)[:, :, None, None]

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=False, mask=mask,
                                  interpret=True)
            return jnp.sum((out * row_mask) ** 2)

        def loss_ref(q, k, v):
            out = _reference(q, k, v, causal=False, mask=mask)
            return jnp.sum((out * row_mask) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_flash, g_ref):
            np.testing.assert_allclose(
                a, b, atol=5e-4, rtol=1e-3,
                err_msg=f"masked grad mismatch for {name}",
            )


def make_qkv_heads(t, d, dv, b=2, h=2, dtype=jnp.float32, seed=0):
    """q and k with heads of ``d``, v with heads of ``dv``."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(k1, (b, t, h, d), dtype),
        jax.random.normal(k2, (b, t, h, d), dtype),
        jax.random.normal(k3, (b, t, h, dv), dtype),
    )


@pytest.fixture
def small_tiles(monkeypatch):
    """Compute tiles of 16 rows, so that blocks of 32 hold two: the
    small stand-in for 256-row tiles inside 512- and 1024-row blocks."""
    import sys

    monkeypatch.setattr(sys.modules["cloud_tpu.ops.flash_attention"],
                        "TILE_Q_ROWS", 16)


class TestFlashForwardSchedule:
    """The forward kernel's schedule: a grid over the block pairs at or
    under the diagonal, tiles skipped past the length, masks only on a
    tile the diagonal crosses, a value head of its own size."""

    # Stand-ins for 1536, 2560 and 4096 under 512-row blocks: 3, 5 and 8
    # blocks of 32 rows, which are and are not a power of two of them.
    @pytest.mark.parametrize("t", [96, 160, 256])
    @pytest.mark.parametrize("d,dv", [(64, 64), (128, 128), (192, 128)])
    @pytest.mark.parametrize("length", [None, 1, "edge", "edge+1", "t"])
    def test_forward_against_reference(self, small_tiles, t, d, dv, length):
        from cloud_tpu.ops.flash_attention import (
            _reference_with_lse,
            flash_attention_with_lse,
        )

        b = 2
        q, k, v = make_qkv_heads(t, d, dv, b=b)
        length = {None: None, 1: 1, "edge": 64, "edge+1": 65, "t": t}[length]
        # Rows of one call may differ in length: the second row is whole.
        lengths = None if length is None else jnp.array([length, t])
        out, lse = flash_attention_with_lse(
            q, k, v, causal=True, lengths=lengths, block_q=32, block_k=32,
            interpret=True)
        assert out.shape == (b, t, 2, dv)
        assert np.isfinite(np.asarray(lse)).all()
        # ``lengths`` on a causal call stands for the parent's ``mask=``.
        mask = (None if lengths is None
                else jnp.arange(t)[None, :] < lengths[:, None])
        ref, ref_lse = _reference_with_lse(q, k, v, causal=True, mask=mask)
        for row in range(b):
            real = t if lengths is None else int(lengths[row])
            np.testing.assert_allclose(out[row, :real], ref[row, :real],
                                       atol=2e-5, rtol=1e-4)
            np.testing.assert_allclose(lse[row, :, :real],
                                       ref_lse[row, :, :real],
                                       atol=2e-5, rtol=1e-4)
            assert not np.asarray(out[row, real:]).any()

    @pytest.mark.parametrize("block_q,block_k", [(64, 32), (32, 64),
                                                 (64, 64), (48, 16),
                                                 (96, 48), (64, 16)])
    @pytest.mark.parametrize("fused", [1, 2, 4])
    def test_uneven_blocks_with_lengths(self, small_tiles, monkeypatch,
                                        block_q, block_k, fused):
        """Blocks that do and do not start on the fused groups' edges,
        tiles worked one, two and four at a time."""
        import sys

        monkeypatch.setattr(sys.modules["cloud_tpu.ops.flash_attention"],
                            "TILES_FUSED", fused)
        q, k, v = make_qkv_heads(192, 64, 32)
        lengths = jnp.array([70, 129])
        out = flash_attention(q, k, v, causal=True, lengths=lengths,
                              block_q=block_q, block_k=block_k,
                              interpret=True)
        mask = jnp.arange(192)[None, :] < lengths[:, None]
        ref = _reference(q, k, v, causal=True, mask=mask)
        for row, real in enumerate([70, 129]):
            np.testing.assert_allclose(out[row, :real], ref[row, :real],
                                       atol=2e-5, rtol=1e-4)
            assert not np.asarray(out[row, real:]).any()

    def test_lengths_bfloat16(self, small_tiles):
        q, k, v = make_qkv_heads(128, 192, 128, dtype=jnp.bfloat16)
        lengths = jnp.array([50, 128])
        out = flash_attention(q, k, v, causal=True, lengths=lengths,
                              block_q=32, block_k=32, interpret=True)
        mask = jnp.arange(128)[None, :] < lengths[:, None]
        ref = _reference(q, k, v, causal=True, mask=mask)
        for row, real in enumerate([50, 128]):
            np.testing.assert_allclose(
                np.asarray(out[row, :real], np.float32),
                np.asarray(ref[row, :real], np.float32),
                atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("causal", [True, False])
    def test_lengths_on_the_reference_path_are_the_mask(self, causal):
        q, k, v = make_qkv_heads(64, 32, 16)
        lengths = jnp.array([40, 64])
        mask = jnp.arange(64)[None, :] < lengths[:, None]
        out = flash_attention(q, k, v, causal=causal, lengths=lengths)
        ref = _reference(q, k, v, causal=causal, mask=mask)
        np.testing.assert_allclose(out, ref, atol=1e-6)

    def test_non_causal_lengths_are_a_key_side_mask_in_the_kernel(self):
        q, k, v = make_qkv_heads(128, 64, 64)
        lengths = jnp.array([96, 128])
        mask = jnp.arange(128)[None, :] < lengths[:, None]
        out = flash_attention(q, k, v, causal=False, lengths=lengths,
                              interpret=True)
        ref = _reference(q, k, v, causal=False, mask=mask)
        np.testing.assert_allclose(out[0, :96], ref[0, :96], atol=5e-4,
                                   rtol=1e-3)
        np.testing.assert_allclose(out[1], ref[1], atol=5e-4, rtol=1e-3)

    @pytest.mark.parametrize("with_lengths", [False, True])
    def test_grads_with_a_value_head_of_its_own(self, small_tiles,
                                                with_lengths):
        """The backward kernels take the value head's size from ``v``
        (``dp`` contracts over it, ``dv`` has it); with ``lengths`` the
        rows past a length carry no gradient."""
        t = 128
        q, k, v = make_qkv_heads(t, 96, 64)
        lengths = jnp.array([70, t]) if with_lengths else None
        mask = (None if lengths is None
                else jnp.arange(t)[None, :] < lengths[:, None])
        real = 1.0 if mask is None else mask.astype(jnp.float32)[
            :, :, None, None]

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=True, lengths=lengths,
                                  block_q=64, block_k=32, interpret=True)
            return jnp.sum(out ** 2)  # the rows past a length are zeros

        def loss_ref(q, k, v):
            out = _reference(q, k, v, causal=True, mask=mask)
            return jnp.sum((out * real) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        assert g_flash[2].shape == v.shape
        for name, a, b in zip("qkv", g_flash, g_ref):
            assert np.isfinite(np.asarray(a)).all(), name
            np.testing.assert_allclose(
                a, b, atol=5e-4, rtol=1e-3,
                err_msg=f"grad mismatch for {name}")

    @pytest.mark.parametrize("t,block_q,block_k,tile", [
        (96, 32, 32, 16), (160, 32, 32, 16), (256, 64, 32, 16),
        (256, 32, 64, 32), (4096, 1024, 512, 256), (2560, 512, 512, 256),
    ])
    def test_tiles_counted_are_the_tiles_with_work(self, monkeypatch, t,
                                                   block_q, block_k, tile):
        """The count behind ``flash_pairs_run`` / ``flash_pairs_width``
        against a walk over every (tile, key block): a tile runs where
        some real row of it attends some real key of the block.  The
        grid has one step a block pair at or under the diagonal, and at
        the full length every tile of it with work is counted."""
        import sys

        fa = sys.modules["cloud_tpu.ops.flash_attention"]
        monkeypatch.setattr(fa, "TILE_Q_ROWS", tile)
        schedule = fa._schedule(t, 128, 128, 2, block_q, block_k)
        assert schedule[:3] == (block_q, block_k, tile)

        def walk(length):
            return sum(
                1
                for row0 in range(0, t, tile)
                for key0 in range(0, t, block_k)
                if key0 <= min(row0 + tile, length) - 1 and row0 < length)

        pairs_q, pairs_k = fa._grid_pairs(t, schedule, True)
        assert len(pairs_q) == sum(
            -(-(i + 1) * block_q // block_k) for i in range(t // block_q))
        assert (np.diff(pairs_q) >= 0).all()  # by query block, keys rising
        for length in (1, tile, tile + 1, t // 2, t - block_q + 1, t):
            assert fa._tiles_run(t, schedule, length) == walk(length), length
        assert fa._tiles_run(t, schedule, None) == walk(t)
        # Every grid step holds at least one counted tile at full length.
        assert fa._tiles_run(t, schedule, None) >= len(pairs_q)

    def test_forward_tiles_follows_the_dispatchs_blocks(self):
        import sys

        fa = sys.modules["cloud_tpu.ops.flash_attention"]
        for t, d, dv in [(4096, 192, 128), (2048, 128, 128), (1536, 128, 128)]:
            schedule = fa._schedule(t, d, dv, 2)
            assert fa.forward_tiles(t, head_dim=d, value_dim=dv) == \
                fa._tiles_run(t, schedule, None)
            assert (fa.forward_tiles(t, t - 300, head_dim=d, value_dim=dv)
                    < fa.forward_tiles(t, head_dim=d, value_dim=dv))
        assert fa.forward_tiles(100, head_dim=64) == 0  # no block fits


class TestFlashAttentionWithLse:
    """The (out, lse) entry point ring attention folds through: both
    outputs must match the reference AND be differentiable — g_lse flows
    into the kernels as ds += p * g_lse."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        from cloud_tpu.ops.flash_attention import (
            _reference_with_lse,
            flash_attention_with_lse,
        )

        q, k, v = make_qkv()
        ref_out, ref_lse = _reference_with_lse(q, k, v, causal=causal,
                                               mask=None)
        out, lse = flash_attention_with_lse(
            q, k, v, causal=causal, interpret=True
        )
        np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_through_both_outputs(self, causal):
        """Loss mixes out and lse (like ring's merge) so the lse cotangent
        is nonzero — the pure-kernel grads must match the reference."""
        from cloud_tpu.ops.flash_attention import (
            _reference_with_lse,
            flash_attention_with_lse,
        )

        q, k, v = make_qkv(t=128)

        def loss(attn_fn, q, k, v):
            out, lse = attn_fn(q, k, v)
            return (
                jnp.mean(out.astype(jnp.float32) ** 2)
                + 0.3 * jnp.mean(jnp.sin(lse))
            )

        import functools

        ref_fn = functools.partial(
            _reference_with_lse, causal=causal, mask=None
        )
        kernel_fn = functools.partial(
            flash_attention_with_lse, causal=causal, interpret=True,
            block_q=64, block_k=64,
        )
        ref_val, ref_grads = jax.value_and_grad(
            functools.partial(loss, ref_fn), argnums=(0, 1, 2)
        )(q, k, v)
        val, grads = jax.value_and_grad(
            functools.partial(loss, kernel_fn), argnums=(0, 1, 2)
        )(q, k, v)
        np.testing.assert_allclose(val, ref_val, atol=1e-5, rtol=1e-5)
        for g, rg in zip(grads, ref_grads):
            np.testing.assert_allclose(g, rg, atol=5e-5, rtol=1e-3)


class TestRingWithKernelBlocks:
    """Ring attention's per-block kernel path (interpret mode) must agree
    with its jnp path and with dense single-device attention."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_interpret_kernel_blocks_match_dense(self, causal):
        import functools

        from jax.sharding import PartitionSpec

        from cloud_tpu import parallel
        from cloud_tpu.parallel.ring_attention import ring_attention

        b, t, h, d = 2, 256, 2, 32
        q, k, v = make_qkv(b=b, t=t, h=h, d=d)
        expected = _reference(q, k, v, causal=causal, mask=None)

        mesh = parallel.MeshSpec({"sp": 4}).build(jax.devices()[:4])
        spec = PartitionSpec(None, "sp", None, None)
        ring = jax.jit(
            jax.shard_map(
                functools.partial(
                    ring_attention, axis="sp", causal=causal,
                    use_pallas=True, interpret=True,
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )
        )
        np.testing.assert_allclose(
            np.asarray(ring(q, k, v)), np.asarray(expected), atol=2e-5
        )

    @pytest.mark.slow
    def test_gradients_flow_through_merge(self):
        """d(loss)/d(q,k,v) through the kernel-block ring == dense grads
        (the lse merge must backpropagate exactly)."""
        import functools

        from jax.sharding import PartitionSpec

        from cloud_tpu import parallel
        from cloud_tpu.parallel.ring_attention import ring_attention

        b, t, h, d = 1, 128, 2, 16
        q, k, v = make_qkv(b=b, t=t, h=h, d=d)

        def dense_loss(q, k, v):
            out = _reference(q, k, v, causal=True, mask=None)
            return jnp.mean(out.astype(jnp.float32) ** 2)

        mesh = parallel.MeshSpec({"sp": 2}).build(jax.devices()[:2])
        spec = PartitionSpec(None, "sp", None, None)
        ring = jax.shard_map(
            functools.partial(
                ring_attention, axis="sp", causal=True,
                use_pallas=True, interpret=True,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )

        def ring_loss(q, k, v):
            out = ring(q, k, v)
            return jnp.mean(out.astype(jnp.float32) ** 2)

        dense_grads = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        ring_grads = jax.jit(
            jax.grad(ring_loss, argnums=(0, 1, 2))
        )(q, k, v)
        for g, rg in zip(ring_grads, dense_grads):
            np.testing.assert_allclose(g, rg, atol=5e-5, rtol=1e-3)


class TestFlashAttentionBackward:
    def test_grads_match_reference(self):
        q, k, v = make_qkv()

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=True, interpret=True)
            return jnp.sum(out**2)

        def loss_ref(q, k, v):
            return jnp.sum(_reference(q, k, v, causal=True, mask=None) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_flash, g_ref):
            np.testing.assert_allclose(
                a, b, atol=5e-4, rtol=1e-3,
                err_msg=f"grad mismatch for {name}",
            )

    def test_grads_non_causal(self):
        q, k, v = make_qkv(t=128)
        g_flash = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=False, interpret=True) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(
                _reference(q, k, v, causal=False, mask=None) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


class TestDispatch:
    def test_cpu_falls_back_to_reference(self):
        # On the CPU test platform auto-dispatch must not pick the kernel.
        q, k, v = make_qkv(t=128)
        out = flash_attention(q, k, v, causal=True)
        ref = _reference(q, k, v, causal=True, mask=None)
        np.testing.assert_allclose(out, ref, atol=1e-6)

    def test_ragged_shapes_fall_back(self):
        # Auto-dispatch (use_pallas=None) must reject T=100: no multiple-
        # of-8 block divides it, so the 8-sublane tile can't be kept.
        from cloud_tpu.ops.flash_attention import _fit_block, _kernel_eligible

        q, k, v = make_qkv(t=100)
        assert _fit_block(100, 256) is None
        assert not _kernel_eligible(q, k, block_q=None, block_k=None)
        out = flash_attention(q, k, v, causal=True)  # default dispatch
        ref = _reference(q, k, v, causal=True, mask=None)
        np.testing.assert_allclose(out, ref, atol=1e-6)

    def test_fit_block(self):
        from cloud_tpu.ops.flash_attention import _fit_block

        assert _fit_block(256, 128) == 128  # exact divisor kept
        assert _fit_block(64, 512) == 64  # clamps to T
        assert _fit_block(768, 512) == 384  # shrinks to a divisor, not T
        assert _fit_block(384, 512) == 384
        assert _fit_block(100, 256) is None  # no 8-aligned divisor

    def test_kernel_eligibility_rules(self):
        from cloud_tpu.ops.flash_attention import _kernel_eligible

        q, k, v = make_qkv(t=256)
        assert _kernel_eligible(q, k, block_q=128, block_k=128)
        assert not _kernel_eligible(q, k, block_q=None, block_k=128)
        q2, k2, v2 = make_qkv(t=256, d=512)
        assert not _kernel_eligible(q2, k2, 128, 128)  # head_dim too large

    def test_undivisible_seq_interpret_uses_fit(self):
        # T=384: default blocks (256/512) don't divide it, but the fit
        # (128/384) does — the kernel path must run, not error.
        q, k, v = make_qkv(t=384)
        ref = _reference(q, k, v, causal=True, mask=None)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    def test_undivisible_blocks_raise_in_kernel_path(self):
        q, k, v = make_qkv(t=100)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(
                q, k, v, causal=True, use_pallas=True, block_q=64, block_k=64
            )

    def test_unalignable_seq_raises_in_kernel_path(self):
        # T=100 with default blocks clamps to block=100, which divides T
        # but breaks the 8-sublane tile: must be a clean ValueError, not a
        # Mosaic lowering failure.
        q, k, v = make_qkv(t=100)
        with pytest.raises(ValueError, match="multiples of 8"):
            flash_attention(q, k, v, causal=True, use_pallas=True)

    def test_transformer_still_trains(self):
        # The transformer's sp==1 path now routes through ops.flash_attention.
        import optax

        from cloud_tpu.models import transformer
        from cloud_tpu.training import train as train_lib

        config = transformer.TINY
        state = train_lib.create_sharded_state(
            jax.random.PRNGKey(0),
            lambda rng: transformer.init(rng, config),
            optax.adamw(1e-3),
            mesh=None,
        )
        step = train_lib.make_train_step(
            lambda p, b: transformer.loss_fn(p, b, config), optax.adamw(1e-3)
        )
        batch = {"tokens": np.zeros((2, 32), np.int32)}
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestFusedCrossEntropy:
    """ops/fused_cross_entropy: chunked online-logsumexp CE must match the
    naive logits+log_softmax path exactly (value and grads), across both
    table layouts, non-dividing chunk sizes, masks, and bf16 inputs."""

    def _naive(self, x, table, targets, layout="vd", weights=None):
        w_t = table.T if layout == "vd" else table
        logits = x.astype(jnp.float32) @ w_t.astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
        if weights is None:
            return jnp.mean(nll)
        w = jnp.broadcast_to(weights.astype(jnp.float32), nll.shape)
        return jnp.sum(nll * w) / jnp.clip(jnp.sum(w), 1.0)

    def _setup(self):
        from cloud_tpu.ops.fused_cross_entropy import (
            fused_linear_cross_entropy,
        )

        rng = np.random.default_rng(0)
        d, v = 16, 37  # v deliberately not a multiple of any chunk below
        x = jnp.asarray(rng.normal(size=(3, 4, d)), jnp.float32)
        table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32) * 0.5
        targets = jnp.asarray(rng.integers(0, v, (3, 4)))
        weights = jnp.asarray(rng.integers(0, 2, (3, 4)), jnp.float32)
        return fused_linear_cross_entropy, x, table, targets, weights

    @pytest.mark.parametrize("chunk", [8, 16, 64])
    @pytest.mark.parametrize("layout", ["vd", "dv"])
    def test_matches_naive_value_and_grads(self, chunk, layout):
        fused, x, table, targets, weights = self._setup()
        tbl = table if layout == "vd" else table.T

        def f(x, t):
            return fused(x, t, targets, table_layout=layout,
                         chunk_size=chunk, weights=weights)

        def g(x, t):
            return self._naive(x, t, targets, layout, weights)

        v1, grads1 = jax.value_and_grad(f, argnums=(0, 1))(x, tbl)
        v2, grads2 = jax.value_and_grad(g, argnums=(0, 1))(x, tbl)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
        for a, b in zip(grads1, grads2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )

    def test_bf16_inputs_f32_compute(self):
        fused, x, table, targets, _ = self._setup()
        xb = x.astype(jnp.bfloat16)
        got = float(fused(xb, table, targets, chunk_size=8))
        want = float(self._naive(xb, table, targets))
        assert abs(got - want) / max(abs(want), 1e-6) < 1e-2
        grad = jax.grad(
            lambda x: fused(x, table, targets, chunk_size=8)
        )(xb)
        assert grad.dtype == jnp.bfloat16

    def test_loss_fn_fused_matches_plain(self):
        """End to end through CloudLM: config.fused_ce flips the loss to
        the fused path with identical value and gradients (both head
        layouts — tied table [V,D] and dense head kernel [D,V])."""
        import functools

        from cloud_tpu.models import transformer

        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(1, 255, (2, 16)).astype(np.int32))
        mask = jnp.asarray(rng.integers(0, 2, (2, 16)).astype(np.int32))
        for tied in (False, True):
            cfg = transformer.TINY.scaled(
                dtype=jnp.float32, num_layers=2, tied_embeddings=tied
            )
            params = transformer.init(jax.random.PRNGKey(0), cfg)
            batch = {"tokens": tokens, "loss_mask": mask}
            v1, g1 = jax.value_and_grad(
                lambda p: transformer.loss_fn(p, batch, cfg, mesh=None)[0]
            )(params)
            v2, g2 = jax.value_and_grad(
                lambda p: transformer.loss_fn(
                    p, batch, cfg.scaled(fused_ce=True), mesh=None
                )[0]
            )(params)
            np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
            for a, b in zip(
                jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
                )

    def test_no_full_logits_in_fused_hlo(self):
        """The point of the op: no [N, V] tensor may appear in the
        compiled forward+backward module."""
        fused, x, table, targets, _ = self._setup()
        big_v, d = 4096, 16
        rng = np.random.default_rng(1)
        xb = jnp.asarray(rng.normal(size=(8, d)), jnp.float32)
        tbl = jnp.asarray(rng.normal(size=(big_v, d)), jnp.float32)
        tg = jnp.asarray(rng.integers(0, big_v, (8,)))
        jitted = jax.jit(jax.grad(
            lambda x, t: fused(x, t, tg, chunk_size=512)
        ))
        hlo = jitted.lower(xb, tbl).compile().as_text()
        # Neither orientation of a full logits tensor may exist.
        assert f"8,{big_v}" not in hlo
        assert f"{big_v},8" not in hlo


def _ineligible_group_norm():
    from cloud_tpu.ops import group_norm

    x = jnp.ones((2, 3, 3, 64))  # H*W = 9: not sublane-aligned
    return lambda **kw: group_norm(x, jnp.ones(64), jnp.zeros(64), **kw)


def _ineligible_flash():
    q = jnp.ones((1, 64, 2, 16))
    k = jnp.ones((1, 128, 2, 16))  # rectangular q/k: no kernel for it
    return lambda **kw: flash_attention(q, k, k, causal=False, **kw)


def _ineligible_paged():
    from cloud_tpu.ops import paged_decode_attention

    q = jnp.ones((1, 1, 2, 16))
    cache = {"k": jnp.ones((1, 4, 2, 16)), "v": jnp.ones((1, 4, 2, 16))}
    # A 4-token row is too short to page.
    return lambda **kw: paged_decode_attention(
        q, cache, jnp.asarray([4], jnp.int32), **kw
    )


@pytest.mark.parametrize("make", [
    _ineligible_group_norm, _ineligible_flash, _ineligible_paged,
], ids=["group_norm", "flash_attention", "paged_attention"])
def test_explicit_use_pallas_on_an_ineligible_shape_raises(make):
    """An explicit request for the kernel is never answered with the jnp
    reference; auto-dispatch on the same shape still is."""
    call = make()
    with pytest.raises(ValueError, match="use_pallas=True"):
        call(use_pallas=True)
    assert np.isfinite(np.asarray(call(), np.float32)).all()
    assert np.isfinite(np.asarray(call(use_pallas=False), np.float32)).all()


@pytest.mark.parametrize("axes,size,want", [
    (("dp", "fsdp"), 8, ("dp", "fsdp")),  # both divide: both taken
    (("dp", "fsdp"), 2, ("dp",)),         # taken while the product divides
    ("tp", 12, None),                     # the mesh has one tp device
    ("dp", 3, None),                      # does not divide: not split
    (None, 8, None),                      # the caller's rules say: not split
], ids=["both", "while-divides", "size-1-axis", "indivisible", "none"])
def test_dividing_axes_takes_the_callers_axes(axes, size, want):
    """The ops know no sharding rules of their own: a kernel is split
    over the mesh axes the caller names, as far as they divide."""
    from cloud_tpu.ops import dispatch

    mesh = parallel.MeshSpec({"dp": 2, "fsdp": 2}).build(jax.devices()[:4])
    assert dispatch.dividing_axes(mesh, axes, size) == want


def test_kernel_mesh_route_splits_by_the_callers_axes_not_a_default():
    """A caller whose rules put the batch on ``tp`` gets its kernel per
    tp shard — not per the default table's dp/fsdp (which this mesh does
    not even have), and not replicated."""
    from cloud_tpu.ops import group_norm

    mesh = parallel.MeshSpec({"tp": 4}).build(jax.devices()[:4])
    x = jnp.ones((8, 4, 4, 64))

    def shard_batches(**kw):
        text = str(jax.make_jaxpr(lambda x: group_norm(
            x, jnp.ones(64), jnp.zeros(64), use_pallas=True, interpret=True,
            mesh=mesh, **kw))(x))
        return "f32[2,4,4,64]" in text

    assert shard_batches(batch_axes="tp")
    assert not shard_batches(batch_axes=("dp", "fsdp"))
    assert not shard_batches()


def test_compiled_flash_kernel_is_refused_in_a_partial_manual_region():
    """No route puts the compiled kernel inside the pp pipeline body yet:
    an explicit request raises there instead of failing in the chip's
    compiler; the interpreter's kernel is plain HLO and runs."""
    from jax.sharding import PartitionSpec as P

    mesh = parallel.MeshSpec({"pp": 2, "dp": 2}).build(jax.devices()[:4])
    q = jnp.ones((2, 16, 2, 8))

    def in_pp_region(**kw):
        body = lambda q: flash_attention(q, q, q, use_pallas=True, **kw)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names={"pp"}))(q)

    with pytest.raises(NotImplementedError, match="partial-manual"):
        in_pp_region()
    assert np.isfinite(np.asarray(in_pp_region(interpret=True))).all()
