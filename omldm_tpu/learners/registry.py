"""Learner registry: the reference's learner allowlist, plus extensions.

Reference counterpart: ``ValidLists.learners = PA, RegressorPA, ORR, SVM,
MultiClassPA, K-means, NN, HT``
(reference: src/main/scala/omldm/utils/parsers/requestStream/PipelineMap.scala:66-69).
``Softmax`` is an extension (BASELINE.md config 5: multiclass softmax +
hashed features), and so is ``LM`` (a language model over token rows).
"""

from __future__ import annotations

from typing import Dict, Type

from omldm_tpu.api.requests import LearnerSpec
from omldm_tpu.learners.base import Learner
from omldm_tpu.learners.hoeffding_tree import HoeffdingTree
from omldm_tpu.learners.kmeans import KMeans
from omldm_tpu.learners.linear import (
    ORR,
    PAClassifier,
    PARegressor,
    RFFSVM,
    SoftmaxClassifier,
)
from omldm_tpu.learners.multiclass_pa import MultiClassPA
from omldm_tpu.learners.nn import NeuralNetwork
from omldm_tpu.learners.seq_lm import SequenceLM

LEARNERS: Dict[str, Type[Learner]] = {
    "PA": PAClassifier,
    "RegressorPA": PARegressor,
    "ORR": ORR,
    "SVM": RFFSVM,
    "MultiClassPA": MultiClassPA,
    "K-means": KMeans,
    "NN": NeuralNetwork,
    "HT": HoeffdingTree,
    # extension beyond the reference allowlist
    "Softmax": SoftmaxClassifier,
    # a decoder trained on token rows (models/olmo_hybrid.py)
    "LM": SequenceLM,
}

# Learners the reference forces onto the SingleLearner protocol (one central
# model; workers forward raw tuples) — FlinkSpoke.scala:203-210.
SINGLE_LEARNER_ONLY = frozenset({"HT", "K-means"})


def is_valid_learner(name: str) -> bool:
    return name in LEARNERS


def make_learner(spec: LearnerSpec) -> Learner:
    """Instantiate a learner from a request's LearnerSpec; raises KeyError on
    unknown names (the control plane validates against the allowlist first,
    PipelineMap.scala:22-47).

    ``dataStructure: {"sparse": true}`` selects the padded-COO sparse
    variant of the linear learners (the reference's SparseVector inputs,
    DataPointParser.scala:4,20-47) — inputs arrive as (idx, val) pairs and
    updates are gather/scatter over a dense device weight vector."""
    if spec.data_structure and spec.data_structure.get("sparse"):
        from omldm_tpu.learners.sparse_linear import SPARSE_LEARNERS

        cls = SPARSE_LEARNERS.get(spec.name)
        if cls is None:
            raise KeyError(
                f"learner {spec.name!r} has no sparse variant "
                f"(available: {sorted(SPARSE_LEARNERS)})"
            )
        return cls(spec.hyper_parameters, spec.data_structure)
    cls = LEARNERS[spec.name]
    return cls(spec.hyper_parameters, spec.data_structure)
