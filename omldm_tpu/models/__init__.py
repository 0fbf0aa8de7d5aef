"""Sequence-model family: TPU-native transformers (dense + MoE).

The reference has no sequence models (SURVEY.md section 2.4 — its learners
are per-record online models over feature vectors); this package is the
framework's long-context extension, built on the attention kernels in
omldm_tpu.ops and sharded by omldm_tpu.parallel.seq_trainer.

The ``LM`` learner's models (``learners/seq_lm.py`` picks one by the
request's ``model_type``) are modules of their own, imported by name:
``olmo_hybrid`` (gated delta-rule and full-attention layers) and ``ouro`` (a
looped decoder: one stack applied several times, rotary positions, an exit
gate); ``blocks`` holds what both are built from.
"""

from omldm_tpu.models.decode import forward_with_cache, generate, init_kv_cache
from omldm_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_forward,
)

__all__ = [
    "TransformerConfig",
    "init_transformer",
    "transformer_forward",
    "init_kv_cache",
    "forward_with_cache",
    "generate",
]
