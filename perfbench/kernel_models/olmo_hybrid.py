"""Operations and bytes one launch of the hybrid decoder NEEDS, from its
shapes alone (``kernel_model.py``'s rule: the algorithm's work, not a
program's, so a share built on them compares implementations and cannot pass
100%). A launch is one SGD step on ``rows`` rows of ``tokens`` tokens:
forward and backward, no recomputation counted (backward = 2 x forward).

``arch`` is the learner's ``dataStructure`` (the published keys).
"""

from __future__ import annotations

F32 = 4
LINEAR, FULL = "linear_attention", "full_attention"


def matmul_parameters(arch: dict) -> int:
    """Parameters that a token multiplies (every matrix but the embedding,
    which is a gather)."""
    d, f, v = arch["hidden_size"], arch["intermediate_size"], arch["vocab_size"]
    h, dk, dv = arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    linear = 2 * d * h * dk + 3 * d * h * dv + 2 * d * h + 3 * d * f
    full = 4 * d * d + 3 * d * f
    kinds = arch["layer_types"]
    return kinds.count(LINEAR) * linear + kinds.count(FULL) * full + d * v


def delta_rule_flops(arch: dict, rows: int, tokens: int) -> int:
    """The recurrence a position a head: ``S k`` (2 dv dk), the decay (dv dk),
    one rank-1 update (2 dv dk), ``S q`` (2 dv dk); forward and backward, all
    linear layers."""
    h, dk, dv = arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    return 3 * 7 * dv * dk * h * rows * tokens * arch["layer_types"].count(LINEAR)


def delta_rule_bytes(arch: dict, rows: int, tokens: int) -> int:
    """Float32 ``q, k, v, beta, g`` in and ``o`` out a position a head in the
    forward pass (2 dk + 2 dv + 2 values); in the backward pass the same read
    again with ``do`` and the five gradients written: three times the forward's
    bytes. The state never leaves the chip's fast memory in the count."""
    h, dk, dv = arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    return 3 * (2 * dk + 2 * dv + 2) * F32 * h * rows * tokens * arch["layer_types"].count(LINEAR)


def flash_attn_flops(arch: dict, rows: int, tokens: int) -> int:
    """Causal softmax attention: ``Q K^T`` and ``P V`` over the lower triangle
    (2 L^2 hidden forward), forward and backward, all full layers."""
    return 3 * 2 * tokens * tokens * arch["hidden_size"] * rows * arch["layer_types"].count(FULL)


def launch_counts(arch: dict, rows: int, tokens: int) -> dict:
    matmul = 3 * 2 * matmul_parameters(arch) * rows * tokens
    delta, attn = delta_rule_flops(arch, rows, tokens), flash_attn_flops(arch, rows, tokens)
    return {
        "model_flops": matmul + delta + attn,
        "matmul_flops": matmul,
        "delta_rule_flops": delta,
        "delta_rule_bytes": delta_rule_bytes(arch, rows, tokens),
        "flash_attn_flops": attn,
    }
