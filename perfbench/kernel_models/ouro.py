"""Operations one launch of the looped decoder NEEDS, from its shapes alone
(``kernel_model.py``'s rule: the algorithm's work, not a program's, so a share
built on them compares implementations and cannot pass 100%). A launch is one
SGD step on ``rows`` rows of ``tokens`` tokens: forward and backward of all
``total_ut_steps`` applications of the stack and of the head at every loop
step, no recomputation counted (backward = 2 x forward).

``arch`` is the learner's ``dataStructure`` (the published keys).
"""

from __future__ import annotations


def matmul_parameters(arch: dict) -> int:
    """Parameters that a token multiplies over the whole loop: the stack's
    matrices and the head once a loop step, the gate's weights at every step
    but the last (the embedding is a gather)."""
    d, f, v = arch["hidden_size"], arch["intermediate_size"], arch["vocab_size"]
    width = arch["num_attention_heads"] * arch["head_dim"]
    steps = arch["total_ut_steps"]
    layer = 4 * d * width + 3 * d * f
    return steps * (arch["num_hidden_layers"] * layer + d * v) + (steps - 1) * d


def head_flops(arch: dict, rows: int, tokens: int) -> int:
    """The head's product at every loop step, forward and backward."""
    return 3 * 2 * arch["hidden_size"] * arch["vocab_size"] * arch["total_ut_steps"] * rows * tokens


def flash_attn_flops(arch: dict, rows: int, tokens: int) -> int:
    """Causal softmax attention: ``Q K^T`` and ``P V`` over the lower triangle
    (2 L^2 heads head_dim forward), forward and backward, every layer
    application."""
    width = arch["num_attention_heads"] * arch["head_dim"]
    applications = arch["num_hidden_layers"] * arch["total_ut_steps"]
    return 3 * 2 * tokens * tokens * width * rows * applications


def launch_counts(arch: dict, rows: int, tokens: int) -> dict:
    matmul = 3 * 2 * matmul_parameters(arch) * rows * tokens
    attn = flash_attn_flops(arch, rows, tokens)
    return {
        "model_flops": matmul + attn,
        "matmul_flops": matmul,
        "flash_attn_flops": attn,
        "head_flops": head_flops(arch, rows, tokens),
    }
