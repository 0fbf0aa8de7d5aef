"""Attention kernels: blockwise and Pallas vs the reference implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omldm_tpu.ops.attention import (
    attention,
    blockwise_attention,
    flash_attention_pallas,
    mha_reference,
)


def _qkv(b=2, l=64, h=4, dh=16, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, l, h, dh), jnp.float32)
    k = jax.random.normal(k2, (b, l, h, dh), jnp.float32)
    v = jax.random.normal(k3, (b, l, h, dh), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_k", [16, 24, 64])
def test_blockwise_matches_reference(causal, block_k):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_matches_reference(causal):
    q, k, v = _qkv(b=1, l=48, h=2, dh=8)
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention_pallas(
        q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_takes_a_k_tile_in_sub_blocks(causal):
    """A K tile of 256 keys is taken in two sub-blocks of 128, one
    online-softmax update each; the ragged last tile's padded keys (320 of
    512) stay masked in whichever sub-block they fall."""
    q, k, v = _qkv(b=1, l=320, h=1, dh=16)
    ref = mha_reference(q, k, v, causal=causal)
    out, lse = flash_attention_pallas(
        q, k, v, causal=causal, block_q=64, block_k=256, interpret=True,
        return_lse=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    one_piece = flash_attention_pallas(
        q, k, v, causal=causal, block_q=64, block_k=128, interpret=True,
        return_lse=True,
    )
    np.testing.assert_allclose(
        np.asarray(lse[:, :320]), np.asarray(one_piece[1][:, :320]), atol=1e-5)


def test_cross_chunk_offsets():
    """q_offset/kv_offset give exact causal masking across chunk boundaries
    (the contract ring attention depends on)."""
    q, k, v = _qkv(l=32)
    full = mha_reference(q, k, v, causal=True)
    # second half of queries attending over all keys with absolute positions
    out = blockwise_attention(
        q[:, 16:], k, v, causal=True, block_k=8, q_offset=16, kv_offset=0
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, 16:]), atol=1e-5)


def test_dispatch_entry_point():
    q, k, v = _qkv(l=32)
    ref = mha_reference(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_wrapper_is_differentiable():
    """The TPU dispatch path must be trainable: grads through the Pallas
    forward come from the blockwise-derived custom VJP."""
    from omldm_tpu.ops.attention import _flash_diff

    q, k, v = _qkv(b=1, l=32, h=2, dh=8)

    def loss_flash(q, k, v):
        return jnp.sum(_flash_diff(q, k, v, True, 0, 0, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestFlashBackwardPallas:
    """The Pallas flash backward (scores recomputed from the saved
    logsumexp; these rows fit one tile) must match the reference
    attention's autodiff gradients — causal, offsets, ragged lengths."""

    def _grads(self, fn, q, k, v):
        def loss(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.01))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        from omldm_tpu.ops.attention import _flash_diff

        rng = np.random.RandomState(0)
        b, l, h, dh = 2, 96, 2, 16
        q = jnp.asarray(rng.randn(b, l, h, dh).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(b, l, h, dh).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(b, l, h, dh).astype(np.float32) * 0.3)
        gp = self._grads(
            lambda q, k, v: _flash_diff(q, k, v, causal, 0, 0, True), q, k, v
        )
        gr = self._grads(
            lambda q, k, v: mha_reference(q, k, v, causal=causal), q, k, v
        )
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-3, atol=2e-4
            )

    def test_grads_match_with_offsets_and_ragged(self):
        from omldm_tpu.ops.attention import _flash_diff

        rng = np.random.RandomState(1)
        b, h, dh = 1, 2, 16
        lq, lk = 40, 72  # ragged: exercises both pad paths
        q = jnp.asarray(rng.randn(b, lq, h, dh).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(b, lk, h, dh).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(b, lk, h, dh).astype(np.float32) * 0.3)
        gp = self._grads(
            lambda q, k, v: _flash_diff(q, k, v, True, 32, 0, True), q, k, v
        )
        gr = self._grads(
            lambda q, k, v: mha_reference(q, k, v, causal=True, q_offset=32),
            q, k, v,
        )
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-3, atol=2e-4
            )

    def test_forward_lse_matches_reference_logsumexp(self):
        from omldm_tpu.ops.attention import flash_attention_pallas

        rng = np.random.RandomState(2)
        b, l, h, dh = 1, 64, 2, 16
        q = jnp.asarray(rng.randn(b, l, h, dh).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(b, l, h, dh).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(b, l, h, dh).astype(np.float32) * 0.3)
        _, lse = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                        return_lse=True)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
        qi = jnp.arange(l)[:, None]
        ki = jnp.arange(l)[None, :]
        s = jnp.where(qi >= ki, s, -1e30)
        ref = jax.scipy.special.logsumexp(s, axis=-1)  # [b, h, l]
        got = np.asarray(lse)[:, :l, 0].reshape(b, h, l)
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-4, atol=1e-4)


def _bwd_case(lq, lk, dh, seed=3, b=1, h=2):
    rng = np.random.RandomState(seed)
    draw = lambda l: jnp.asarray(rng.randn(b, l, h, dh).astype(np.float32) * 0.3)
    return draw(lq), draw(lk), draw(lk), draw(lq)  # q, k, v, cotangent


def _flash_grads(q, k, v, g, causal, q_offset=0, kv_offset=0):
    """The Pallas backward (interpreted) from the Pallas forward's
    residuals, in tiles of 16 x 32, SMALLER than the row, so that the sweeps,
    the skipped blocks and the clamped index maps are walked."""
    from omldm_tpu.ops.attention import flash_backward

    where = dict(causal=causal, q_offset=q_offset, kv_offset=kv_offset,
                 block_q=16, block_k=32, interpret=True)
    out, lse = flash_attention_pallas(q, k, v, return_lse=True, **where)
    return flash_backward(q, k, v, out, lse, g, **where)


def _reference_grads(q, k, v, g, causal, q_offset=0, kv_offset=0):
    _, vjp = jax.vjp(
        lambda q, k, v: mha_reference(
            q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset),
        q, k, v)
    return vjp(g)


def _bwd_path_counts(fn):
    """What ``fn`` adds under ``flash_bwd_path`` at trace time."""
    from omldm_tpu.utils import tracing

    before = tracing.RECORDER.counts("flash_bwd_path")
    result = fn()
    after = tracing.RECORDER.counts("flash_bwd_path")
    return result, {k: v - before.get(k, 0) for k, v in after.items()
                    if v != before.get(k, 0)}


class TestFlashBackwardTiled:
    """The backward with SEVERAL tiles on each axis, on both of its paths:
    one kernel where the head's whole-length dQ accumulator fits
    ``ONE_PASS_DQ_BYTES``, the dq and dk/dv kernels where it does not."""

    # lq, lk, q_offset: square over 6 x 3 tiles; the ragged cross-chunk case
    # (both pads, a last Q tile of 8 rows, a last K tile of 8 keys)
    SHAPES = {"square": (96, 96, 0), "ragged_offset": (40, 72, 32)}

    @pytest.mark.parametrize("path", ["one_pass", "two_pass"])
    @pytest.mark.parametrize("shape", sorted(SHAPES))
    @pytest.mark.parametrize("dh", [16, 128])
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal, dh, shape, path, monkeypatch):
        from omldm_tpu.ops import attention

        lq, lk, q_offset = self.SHAPES[shape]
        if path == "two_pass":  # no row fits
            monkeypatch.setattr(attention, "ONE_PASS_DQ_BYTES", 0)
        q, k, v, g = _bwd_case(lq, lk, dh)
        got, counts = _bwd_path_counts(
            lambda: _flash_grads(q, k, v, g, causal, q_offset))
        assert counts == {path: 1}
        want = _reference_grads(q, k, v, g, causal, q_offset)
        for a, b_ in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("shape", sorted(SHAPES))
    @pytest.mark.parametrize("causal", [False, True])
    def test_one_pass_equals_two_pass(self, causal, shape, monkeypatch):
        """The same sums staged two ways: equal to float32 rounding."""
        from omldm_tpu.ops import attention

        lq, lk, q_offset = self.SHAPES[shape]
        q, k, v, g = _bwd_case(lq, lk, 16, seed=4)
        one = _flash_grads(q, k, v, g, causal, q_offset)
        monkeypatch.setattr(attention, "ONE_PASS_DQ_BYTES", 0)
        two = _flash_grads(q, k, v, g, causal, q_offset)
        for a, b_ in zip(one, two):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("path", ["one_pass", "two_pass"])
    def test_fully_masked_first_block(self, path, monkeypatch):
        """``q_offset < kv_offset``: the first 24 query rows see no key at
        all and the first K tile is masked for every row of the first Q
        tile, which is the case the kept guard serves: their output and
        every gradient through them is zero, the other rows' are right."""
        from omldm_tpu.ops import attention

        if path == "two_pass":
            monkeypatch.setattr(attention, "ONE_PASS_DQ_BYTES", 0)
        q, k, v, g = _bwd_case(64, 64, 16, seed=5)
        where = dict(causal=True, q_offset=8, kv_offset=32)
        out = flash_attention_pallas(
            q, k, v, block_q=16, block_k=32, interpret=True, **where)
        assert not np.asarray(out[:, :24]).any()
        got = _flash_grads(q, k, v, g, **where)
        assert not np.asarray(got[0][:, :24]).any()
        # the reference gives unseeing rows a uniform softmax; take them out
        # of its loss to compare the rest
        seen = (jnp.arange(64) >= 24)[None, :, None, None]
        want = _reference_grads(q, k, v, jnp.where(seen, g, 0.0), **where)
        for a, b_ in zip(got, want):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-5)

    def test_path_follows_the_row_length(self):
        """``_flash_diff`` (what ``attention()`` calls on a TPU) reads the
        path from the shapes: what a head's ``dQ`` keeps resident (the
        float32 ``[Lq, dh]`` accumulator and two buffers of the ``dq`` block,
        the width as the lanes pad it) against ``ONE_PASS_DQ_BYTES``."""
        from omldm_tpu.ops import attention

        def trace(lq, dh, dtype=jnp.bfloat16):
            x = jax.ShapeDtypeStruct((1, lq, 1, dh), dtype)
            loss = lambda q, k, v: jnp.sum(
                attention._flash_diff(q, k, v, True, 0, 0, True)
                .astype(jnp.float32))
            return _bwd_path_counts(
                lambda: jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), x, x, x))[1]

        fits = attention.ONE_PASS_DQ_BYTES // (128 * (4 + 2 * 2))
        assert fits >= 8192  # the benchmark's rows: bfloat16 at head width 128
        assert trace(fits, 128) == {"one_pass": 1}
        assert trace(fits + 1, 128) == {"two_pass": 1}
        # a narrower head fills the same lanes; float32 doubles the dq block
        assert trace(fits, 64) == {"one_pass": 1}
        assert trace(2 * fits, 64) == {"two_pass": 1}
        assert trace(fits, 128, jnp.float32) == {"two_pass": 1}
        assert trace(fits // 2, 128, jnp.float32) == {"one_pass": 1}
