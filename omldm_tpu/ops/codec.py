"""Quantization kernels for the hub<->spoke transport codec.

The GM/FGM protocols cut communication by *skipping* synchronizations;
this module cuts the cost of the synchronizations that do happen, by
shrinking every shipped parameter vector (1-bit-SGD / QSGD-lineage lossy
compression with error feedback — see PAPERS.md, communication-efficient
distributed SGD). Two families of kernels live here:

- **Host kernels** (numpy): exact affine int8, fp16 round-trips, and
  top-k delta sparsification, used by the host-plane transport codec
  (``omldm_tpu.runtime.codec``) at the message ship boundary.
- **Device kernels** (jax, jit-friendly): quantize-dequantize (QDQ)
  twins of the host kernels for the SPMD engine, applied to the vectors
  entering/leaving the protocol collectives inside the compiled step.
  They are pure elementwise/reduction ops — no ``shard_map`` or
  collective primitives of their own.

Error feedback is the CALLER's job (the transport codec keeps per-stream
residual accumulators; the SPMD step keeps an ``ef`` state leaf): the
kernels here are stateless and deterministic, so sender-side encode and
receiver-side decode of the same bytes always agree.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

# wire bytes per parameter element, by codec kind (the int8 affine meta —
# scale + zero point, two float32 — is accounted per LEAF, not per element)
BYTES_PER_ELEMENT = {"none": 4.0, "fp16": 2.0, "int8": 1.0}
# per-leaf metadata bytes on the wire (shape/dtype ride in the in-process
# object header, matching how payload_size counts raw ndarrays: buffer only)
LEAF_META_BYTES = {"none": 0, "fp16": 0, "int8": 8}


# --- host kernels (numpy) ---


def fp16_encode(x: np.ndarray) -> np.ndarray:
    """Lossy fp32 -> fp16 cast (2 bytes/element on the wire)."""
    return np.asarray(x, np.float16)


def fp16_decode(q: np.ndarray, dtype=np.float32) -> np.ndarray:
    return np.asarray(q, dtype)


def int8_affine_encode(
    x: np.ndarray,
) -> Tuple[np.ndarray, np.float32, np.float32]:
    """Per-leaf affine (asymmetric) quantization to uint8.

    ``q = round((x - zero) / scale)`` with ``zero = min(x)`` and
    ``scale = (max(x) - min(x)) / 255`` — 1 byte/element + 8 bytes of
    (scale, zero) metadata. Returns ``(q, scale, zero)``.
    """
    x = np.asarray(x, np.float32)
    if x.size == 0:
        return x.astype(np.uint8), np.float32(1.0), np.float32(0.0)
    lo = np.float32(x.min())
    hi = np.float32(x.max())
    if not (np.isfinite(lo) and np.isfinite(hi)):
        # FAIL LOUDLY: quantizing a non-finite leaf would silently encode
        # garbage (NaN -> rint -> undefined uint8) and ship it as a
        # plausible-looking model. Non-finite state is a sender-side
        # corruption the model-integrity guard exists to catch BEFORE the
        # ship boundary; the codec must never launder it.
        raise ValueError(
            "int8 codec: non-finite values in leaf "
            f"(min={x.min()!r}, max={x.max()!r}); refusing to encode"
        )
    scale = np.float32((hi - lo) / 255.0)
    if not np.isfinite(scale) or scale <= 0:
        # degenerate range (constant/zero leaf, or a subnormal span whose
        # /255 underflows): scale 1 with zero-point ``lo`` encodes every
        # element as q=0 -> decode == lo exactly — a lossless passthrough
        # that leaves NO error-feedback residual behind
        scale = np.float32(1.0)
    q = np.clip(np.rint((x - lo) / scale), 0, 255).astype(np.uint8)
    return q, scale, lo


def int8_affine_decode(
    q: np.ndarray, scale: float, zero: float, dtype=np.float32
) -> np.ndarray:
    return (np.asarray(q, np.float32) * np.float32(scale) + np.float32(zero)).astype(
        dtype
    )


def int8_quantization_step(x: np.ndarray) -> float:
    """The affine grid step for ``x`` — the per-element round-trip error
    bound (|decode(encode(x)) - x| <= step/2 elementwise... the clip at
    the range ends makes the bound exactly one full step)."""
    x = np.asarray(x, np.float32)
    if x.size == 0:
        return 0.0
    return max(float(x.max() - x.min()) / 255.0, 0.0)


def topk_encode(
    delta: np.ndarray, k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k magnitude sparsification of a (flat) delta vector.

    Returns ``(idx int32, val float32)`` of the k largest-|.| entries
    (8 bytes/kept element on the wire). The dropped mass is the caller's
    error-feedback residual — it ships on a later sync."""
    flat = np.asarray(delta, np.float32).ravel()
    k = max(min(int(k), flat.size), 0)
    if k == 0:
        return np.zeros((0,), np.int32), np.zeros((0,), np.float32)
    if k >= flat.size:
        idx = np.arange(flat.size, dtype=np.int32)
        return idx, flat.copy()
    part = np.argpartition(np.abs(flat), flat.size - k)[flat.size - k:]
    idx = np.sort(part).astype(np.int32)
    return idx, flat[idx]


def topk_decode(
    idx: np.ndarray, val: np.ndarray, size: int, dtype=np.float32
) -> np.ndarray:
    """Scatter a top-k (idx, val) delta back into a dense flat vector."""
    out = np.zeros((int(size),), dtype)
    out[np.asarray(idx, np.int64)] = np.asarray(val, dtype)
    return out


# --- device kernels (jax; QDQ = quantize-dequantize at the ship boundary) ---


def qdq_fp16(x):
    """fp32 -> fp16 -> fp32 round-trip, jit-friendly: the values that
    cross the (emulated) wire are exactly fp16-representable."""
    import jax.numpy as jnp

    return x.astype(jnp.float16).astype(jnp.float32)


def qdq_int8(x):
    """Symmetric per-vector int8 QDQ: ``scale = max|x| / 127``,
    ``q = clip(round(x / scale))``, returns ``q * scale``. Symmetric (no
    zero point) keeps the kernel a pure map-reduce — the natural form
    inside a compiled collective step; the host codec's affine variant
    buys ~1 bit of extra precision on skewed leaves at the cost of
    per-leaf metadata."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    return q * scale


def make_qdq(kind: str):
    """The device QDQ kernel for a codec kind (None for ``none``)."""
    if kind in (None, "none"):
        return None
    if kind == "fp16":
        return qdq_fp16
    if kind == "int8":
        return qdq_int8
    raise ValueError(
        f"no device QDQ kernel for codec {kind!r} (topk is a host-plane "
        "transport codec: the collective engine's allreduce needs dense "
        "operands)"
    )
