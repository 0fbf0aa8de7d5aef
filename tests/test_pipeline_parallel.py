"""Pipeline parallelism: pipelined loss/training == single-device exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omldm_tpu.models.transformer import TransformerConfig
from omldm_tpu.parallel.pipeline_parallel import PPTrainer, make_pp_mesh

CFG = TransformerConfig(
    vocab_size=32, d_model=16, n_heads=2, n_layers=4, d_ff=32, max_len=32,
)


def _batch(rng, b, l, vocab):
    base = rng.randint(1, vocab, size=(b, 4))
    toks = np.tile(base, (1, l // 4 + 1))[:, : l + 1]
    return (
        toks[:, :-1].astype(np.int32),
        toks[:, 1:].astype(np.int32),
        np.ones((b, l), np.float32),
    )


@pytest.mark.parametrize("dp,pp,n_micro", [
    (1, 4, 4),
    (2, 2, 2),
    (1, 2, 8),
    (2, 4, 2),
])
def test_pp_matches_single_device(dp, pp, n_micro):
    rng = np.random.RandomState(0)
    tokens, targets, mask = _batch(rng, 8, 16, CFG.vocab_size)
    ref = PPTrainer(CFG, mesh=make_pp_mesh(1, 1), n_micro=n_micro, lr=1e-2, seed=2)
    shr = PPTrainer(CFG, mesh=make_pp_mesh(dp, pp), n_micro=n_micro, lr=1e-2, seed=2)
    for _ in range(3):
        l_ref = ref.step(tokens, targets, mask)
        l_shr = shr.step(tokens, targets, mask)
    np.testing.assert_allclose(
        float(np.asarray(l_ref)), float(np.asarray(l_shr)), atol=1e-4
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(ref.host_params()),
        jax.tree_util.tree_leaves(shr.host_params()),
    ):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_pp_training_learns():
    rng = np.random.RandomState(1)
    tokens, targets, mask = _batch(rng, 8, 16, CFG.vocab_size)
    tr = PPTrainer(CFG, mesh=make_pp_mesh(2, 4), n_micro=2, lr=3e-3, seed=3)
    first = float(np.asarray(tr.step(tokens, targets, mask)))
    for _ in range(50):
        loss = tr.step(tokens, targets, mask)
    assert float(np.asarray(loss)) < first * 0.5
    assert tr.fitted == 51 * 8 * 16


def test_pp_validates_divisibility():
    with pytest.raises(ValueError, match="not divisible by pp"):
        PPTrainer(
            TransformerConfig(vocab_size=8, d_model=8, n_heads=1, n_layers=3,
                              d_ff=8, max_len=8),
            mesh=make_pp_mesh(1, 2),
        )
