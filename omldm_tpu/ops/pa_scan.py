"""Pallas kernel: fused per-record Passive-Aggressive scan.

The exact per-record PA update is inherently sequential (each projection
depends on the previous weights), which the reference runs one JVM call per
record (MLPipeline.pipePoint, hs_err_pid77107.log:111) and the generic JAX
path runs as ``lax.scan`` over per-record dots — correct, but each scan step
is a tiny HLO loop iteration. This kernel keeps the weight vector in VMEM
and sweeps the whole micro-batch in one pallas program: one HBM read for the
batch, one weight write-back, no per-step dispatch.

Used by ``PAClassifier.update_per_record`` when ``usePallas`` is set in the
learner hyper-parameters (and transparently in interpret mode on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# lane width of the TPU vector unit; feature dim is padded to a multiple
LANE = 128


def _pa_kernel(x_ref, y_ref, m_ref, w0_ref, w_out_ref, loss_ref, *, variant: str, C: float):
    # every ref is 2-D ([1, D] weights, [1, LANE] loss): when the cohort
    # engine vmaps this call, each operand becomes a (squeezed, full, full)
    # block, which Mosaic accepts only if the last TWO dims are whole — a
    # 1-D [D] ref would turn into a one-row block of a [C, D] array
    B = x_ref.shape[0]

    def body(i, carry):
        w, acc = carry
        x = x_ref[pl.ds(i, 1), :]  # [1, D]
        ys = jnp.where(y_ref[i, 0] > 0.0, 1.0, -1.0)
        margin = jnp.sum(w * x)
        hinge = jnp.maximum(0.0, 1.0 - ys * margin)
        sq = jnp.maximum(jnp.sum(x * x), 1e-12)
        if variant == "PA":
            tau = hinge / sq
        elif variant == "PA-I":
            tau = jnp.minimum(C, hinge / sq)
        else:  # PA-II
            tau = hinge / (sq + 1.0 / (2.0 * C))
        m = m_ref[i, 0]
        return w + (tau * ys * m) * x, acc + hinge * m

    w, loss_sum = jax.lax.fori_loop(0, B, body, (w0_ref[...], jnp.float32(0.0)))
    w_out_ref[...] = w
    # TPU VMEM stores must be vector-shaped: broadcast the scalar loss sum
    loss_ref[...] = jnp.full((1, LANE), loss_sum, jnp.float32)


@functools.partial(jax.jit, static_argnames=("variant", "C", "interpret"))
def pa_scan_update(w, x, y, mask, variant: str = "PA-I", C: float = 0.01,
                   interpret: bool = False):
    """Exact sequential PA pass over a micro-batch.

    w[D], x[B, D], y[B], mask[B] -> (new_w[D], mean_loss). Pads D to the
    TPU lane width; padding columns carry zeros and do not affect the math."""
    B, D = x.shape
    pad = (-D) % LANE
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        w = jnp.pad(w, (0, pad))
    y2 = y.reshape(B, 1)
    m2 = mask.reshape(B, 1)
    new_w, loss_vec = pl.pallas_call(
        functools.partial(_pa_kernel, variant=variant, C=float(C)),
        out_shape=(
            jax.ShapeDtypeStruct((1, D + pad), jnp.float32),
            jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        ),
        interpret=interpret,
    )(x.astype(jnp.float32), y2.astype(jnp.float32), m2.astype(jnp.float32),
      w.astype(jnp.float32).reshape(1, D + pad))
    total = jnp.maximum(jnp.sum(mask), 1.0)
    return new_w[0, :D], loss_vec[0, 0] / total
