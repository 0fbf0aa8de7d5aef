"""Transformer family: forward shapes, training, and sharded-equivalence.

The sharded-equivalence tests are the load-bearing ones: a SeqTrainer step
over a real (dp, sp, tp) mesh must match the single-device step bit-for-bit
(up to fp tolerance) — this pins down ring attention, the Megatron psums,
the MoE all_to_all dispatch, and the gradient psums inserted by shard_map's
varying-axis tracking, all at once.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omldm_tpu.models.transformer import (
    AxisSpec,
    TransformerConfig,
    init_transformer,
    lm_loss,
    transformer_forward,
)
from omldm_tpu.parallel.seq_trainer import SeqTrainer, make_seq_mesh

CFG = TransformerConfig(
    vocab_size=32, d_model=16, n_heads=2, n_layers=2, d_ff=32,
    max_len=64, objective="lm",
)


def _copy_batch(rng, b, l, vocab):
    """Repeating-pattern sequences: next token is predictable."""
    base = rng.randint(1, vocab, size=(b, 4))
    toks = np.tile(base, (1, l // 4 + 1))[:, : l + 1]
    return (
        toks[:, :-1].astype(np.int32),
        toks[:, 1:].astype(np.int32),
        np.ones((b, l), np.float32),
    )


def test_forward_shapes():
    params = init_transformer(CFG, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = transformer_forward(CFG, params, tokens)
    assert logits.shape == (2, 16, CFG.vocab_size)

    ccfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=64, objective="classify", n_classes=3, causal=False,
    )
    cparams = init_transformer(ccfg, jax.random.PRNGKey(0))
    out = transformer_forward(ccfg, cparams, tokens)
    assert out.shape == (2, 3)


def test_moe_forward_matches_shapes_and_is_finite():
    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=64, n_experts=4,
    )
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = transformer_forward(cfg, params, tokens)
    assert logits.shape == (2, 16, 32)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_single_device_training_learns_copy_task():
    rng = np.random.RandomState(0)
    trainer = SeqTrainer(CFG, mesh=make_seq_mesh(1, 1, 1), lr=3e-3, seed=1)
    tokens, targets, mask = _copy_batch(rng, 8, 16, CFG.vocab_size)
    first = float(np.asarray(trainer.step(tokens, targets, mask)))
    for _ in range(60):
        loss = trainer.step(tokens, targets, mask)
    assert float(np.asarray(loss)) < first * 0.5
    assert trainer.fitted == 61 * 8 * 16


@pytest.mark.parametrize("dp,sp,tp", [
    (2, 2, 2),
    (1, 4, 2),
    (4, 1, 2),
    (2, 4, 1),
])
def test_sharded_step_matches_single_device(dp, sp, tp):
    rng = np.random.RandomState(1)
    tokens, targets, mask = _copy_batch(rng, 4, 16, CFG.vocab_size)

    ref = SeqTrainer(CFG, mesh=make_seq_mesh(1, 1, 1), lr=1e-2, seed=3)
    shr = SeqTrainer(CFG, mesh=make_seq_mesh(dp, sp, tp), lr=1e-2, seed=3)
    for _ in range(3):
        l_ref = ref.step(tokens, targets, mask)
        l_shr = shr.step(tokens, targets, mask)
    np.testing.assert_allclose(
        float(np.asarray(l_ref)), float(np.asarray(l_shr)), atol=1e-4
    )
    p_ref, p_shr = ref.host_params(), shr.host_params()
    flat_ref = jax.tree_util.tree_leaves(p_ref)
    flat_shr = jax.tree_util.tree_leaves(p_shr)
    for a, b in zip(flat_ref, flat_shr):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_moe_expert_parallel_matches_dense_dispatch():
    """EP all_to_all routing == single-device dense dispatch when capacity
    is ample (no token drops)."""
    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=64, n_experts=4, capacity_factor=4.0,
    )
    rng = np.random.RandomState(2)
    tokens, targets, mask = _copy_batch(rng, 4, 16, cfg.vocab_size)
    ref = SeqTrainer(cfg, mesh=make_seq_mesh(1, 1, 1), lr=1e-2, seed=5)
    shr = SeqTrainer(cfg, mesh=make_seq_mesh(4, 2, 1), lr=1e-2, seed=5)
    for _ in range(2):
        l_ref = ref.step(tokens, targets, mask)
        l_shr = shr.step(tokens, targets, mask)
    np.testing.assert_allclose(
        float(np.asarray(l_ref)), float(np.asarray(l_shr)), atol=1e-4
    )


@pytest.mark.parametrize("dp,sp,tp", [(2, 1, 2), (2, 2, 1), (1, 4, 2)])
def test_classify_objective_sharded(dp, sp, tp):
    """classify must sequence-shard its tokens too — replicating them over
    sp would double-count keys in ring attention."""
    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=64, objective="classify", n_classes=2, causal=False,
    )
    rng = np.random.RandomState(3)
    tokens = rng.randint(0, 32, size=(8, 16)).astype(np.int32)
    labels = (tokens.sum(axis=1) % 2).astype(np.int32)
    ref = SeqTrainer(cfg, mesh=make_seq_mesh(1, 1, 1), lr=1e-2, seed=7)
    shr = SeqTrainer(cfg, mesh=make_seq_mesh(dp, sp, tp), lr=1e-2, seed=7)
    for _ in range(3):
        l_ref = ref.step(tokens, labels)
        l_shr = shr.step(tokens, labels)
    np.testing.assert_allclose(
        float(np.asarray(l_ref)), float(np.asarray(l_shr)), atol=1e-4
    )


def test_step_many_matches_sequential_steps():
    """One scanned launch over T batches == T step() calls (dense + mesh)."""
    rng = np.random.RandomState(5)
    batches = [_copy_batch(rng, 4, 16, CFG.vocab_size) for _ in range(4)]
    seq = SeqTrainer(CFG, mesh=make_seq_mesh(2, 2, 2), lr=1e-2, seed=11)
    for b in batches:
        seq.step(*b)
    many = SeqTrainer(CFG, mesh=make_seq_mesh(2, 2, 2), lr=1e-2, seed=11)
    losses = many.step_many(
        np.stack([b[0] for b in batches]),
        np.stack([b[1] for b in batches]),
        np.stack([b[2] for b in batches]),
    )
    assert losses.shape == (4,)
    assert many.fitted == seq.fitted == 4 * 4 * 16
    for a, b in zip(
        jax.tree_util.tree_leaves(seq.host_params()),
        jax.tree_util.tree_leaves(many.host_params()),
    ):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_remat_matches_no_remat():
    """jax.checkpoint rematerialization changes memory, not math — sharded
    training with remat equals the plain single-device run."""
    rng = np.random.RandomState(7)
    tokens, targets, mask = _copy_batch(rng, 4, 16, CFG.vocab_size)
    plain = SeqTrainer(CFG, mesh=make_seq_mesh(1, 1, 1), lr=1e-2, seed=17)
    rcfg = dataclasses.replace(CFG, remat=True)
    remat = SeqTrainer(rcfg, mesh=make_seq_mesh(2, 2, 2), lr=1e-2, seed=17)
    for _ in range(3):
        l_a = plain.step(tokens, targets, mask)
        l_b = remat.step(tokens, targets, mask)
    np.testing.assert_allclose(
        float(np.asarray(l_a)), float(np.asarray(l_b)), atol=1e-4
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.host_params()),
        jax.tree_util.tree_leaves(remat.host_params()),
    ):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_fused_loss_chunk_matches_unfused():
    """The fused chunked cross-entropy (loss_chunk > 0: per-chunk head
    matmul + checkpointed logsumexp, no [T, V] logits tensor) equals the
    whole-tensor log_softmax path in loss AND gradients to f32 reduction
    order — including ragged chunking and masked tokens."""
    import jax.flatten_util as fu

    from omldm_tpu.models.transformer import lm_loss

    rng = np.random.RandomState(11)
    tokens, targets, _ = _copy_batch(rng, 3, 24, CFG.vocab_size)
    mask = jnp.asarray((rng.rand(3, 24) > 0.2).astype(np.float32))
    params = init_transformer(CFG, jax.random.PRNGKey(3))
    fused_cfg = dataclasses.replace(CFG, loss_chunk=13)  # ragged: 72 % 13 != 0

    l_plain = lm_loss(CFG, params, tokens, targets, mask)
    l_fused = lm_loss(fused_cfg, params, tokens, targets, mask)
    np.testing.assert_allclose(
        float(l_plain), float(l_fused), rtol=1e-6, atol=1e-6
    )
    g_plain, _ = fu.ravel_pytree(
        jax.grad(lambda p: lm_loss(CFG, p, tokens, targets, mask))(params)
    )
    g_fused, _ = fu.ravel_pytree(
        jax.grad(lambda p: lm_loss(fused_cfg, p, tokens, targets, mask))(params)
    )
    np.testing.assert_allclose(
        np.asarray(g_plain), np.asarray(g_fused), rtol=1e-4, atol=1e-6
    )


def test_fused_loss_trains_sharded():
    """The fused loss composes with the sharded trainer (dp x sp x tp):
    same loss trajectory as the unfused single-device run."""
    rng = np.random.RandomState(12)
    tokens, targets, mask = _copy_batch(rng, 4, 16, CFG.vocab_size)
    plain = SeqTrainer(CFG, mesh=make_seq_mesh(1, 1, 1), lr=1e-2, seed=5)
    fcfg = dataclasses.replace(CFG, loss_chunk=16)
    fused = SeqTrainer(fcfg, mesh=make_seq_mesh(2, 2, 2), lr=1e-2, seed=5)
    for _ in range(3):
        l_a = plain.step(tokens, targets, mask)
        l_b = fused.step(tokens, targets, mask)
    np.testing.assert_allclose(
        float(np.asarray(l_a)), float(np.asarray(l_b)), atol=1e-4
    )


def test_bf16_mixed_precision_trains_and_matches_sharded():
    """bf16 compute keeps fp32 master weights: training works, and the
    sharded step still equals single-device (same bf16 compute path)."""
    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_len=64, dtype=jnp.bfloat16,
    )
    rng = np.random.RandomState(6)
    tokens, targets, mask = _copy_batch(rng, 4, 16, cfg.vocab_size)
    ref = SeqTrainer(cfg, mesh=make_seq_mesh(1, 1, 1), lr=1e-2, seed=13)
    shr = SeqTrainer(cfg, mesh=make_seq_mesh(2, 2, 2), lr=1e-2, seed=13)
    first = float(np.asarray(ref.step(tokens, targets, mask)))
    shr.step(tokens, targets, mask)
    for _ in range(30):
        l_ref = ref.step(tokens, targets, mask)
        l_shr = shr.step(tokens, targets, mask)
    assert float(np.asarray(l_ref)) < first * 0.7  # learns despite bf16
    # bf16 accumulation differs slightly shard-vs-single; loose tolerance
    np.testing.assert_allclose(
        float(np.asarray(l_ref)), float(np.asarray(l_shr)), atol=0.15
    )
    # master weights stay fp32
    assert ref.host_params()["embed"].dtype == np.float32


def test_lm_loss_perfect_prediction_near_zero():
    """Sanity: a model that always predicts the right token has ~0 loss —
    checked by training until the copy task is nearly solved."""
    rng = np.random.RandomState(4)
    trainer = SeqTrainer(CFG, mesh=make_seq_mesh(1, 1, 1), lr=5e-3, seed=9)
    tokens, targets, mask = _copy_batch(rng, 8, 16, CFG.vocab_size)
    for _ in range(200):
        loss = trainer.step(tokens, targets, mask)
    assert float(np.asarray(loss)) < 0.5


def test_moe_dense_applies_capacity_like_ep():
    """The dense path must enforce the SAME per-expert capacity rule as the
    EP path: under routing imbalance, over-capacity tokens drop to the
    residual in BOTH deployments (a model trained dense and served
    expert-parallel computes the same function)."""
    import jax
    import jax.numpy as jnp

    from omldm_tpu.models.transformer import (
        _moe_block_dense,
        _moe_block_ep,
    )
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.RandomState(0)
    d, f, e = 8, 16, 4
    b, lc = 2, 8  # T = 16 tokens
    layer = {
        # router rigged so EVERY token picks expert 0 -> maximal imbalance
        "router": jnp.asarray(
            np.concatenate(
                [np.full((d, 1), 5.0), np.zeros((d, e - 1))], axis=1
            ).astype(np.float32)
        ),
        "w1": jnp.asarray(rng.randn(e, d, f).astype(np.float32) * 0.1),
        "w2": jnp.asarray(rng.randn(e, f, d).astype(np.float32) * 0.1),
    }
    x = jnp.asarray(np.abs(rng.randn(b, lc, d)).astype(np.float32) * 0.5)

    cf = 1.0  # cap = T/E = 4 slots on expert 0; 12 of 16 tokens must drop
    out_dense = _moe_block_dense(layer, x, cf)
    t = out_dense.reshape(-1, d)
    nonzero = np.count_nonzero(np.abs(np.asarray(t)).sum(axis=1) > 1e-9)
    assert nonzero == 4, f"expected cap=4 kept tokens, got {nonzero}"

    # ep=1 EP path == dense path exactly, including the dropped tokens
    mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))
    ep_fn = jax.shard_map(
        lambda xx: _moe_block_ep(layer, xx, "ep", cf),
        mesh=mesh,
        in_specs=P(),
        out_specs=P(),
        check_vma=False,
    )
    out_ep = ep_fn(x)
    np.testing.assert_allclose(
        np.asarray(out_dense), np.asarray(out_ep), atol=1e-5
    )
