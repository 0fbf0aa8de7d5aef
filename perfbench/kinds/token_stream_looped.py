"""The kind ``token_stream_looped``: the kind ``token_stream`` (a language
model trained online on rows of token ids through the dense fused, overlapped
file route of a ``StreamJob``, compared with a plain reference on its losses,
its parameters and its forecasts) for a model whose Create request names
other published keys: a looped decoder (``model_type: ouro``; one stack of
layers applied ``total_ut_steps`` times).

``kinds/token_stream.py`` fixes its model's keys, its test size and its
stand-ins at module level and fills the request from them, so a second model
is a second kind. This file loads that module and keeps everything of it that
is not the model: the records (``Rows``, ``draw_rows``, ``render``, ``Pool``),
the job (``System``), what is compared and how (``Readings``,
``Kind.compare``, ``initial_gaps``, ``leaf_update_gaps``), the traced run's
table (``launch_scopes``) and the readers' numbers (``launches``,
``scope_ms``, ``scope_share``, ``producer_busy_share``, ``flops`` from
``kernel_models/<reference>``). Its own: ``ARCH_KEYS``, ``TINY``,
``STAND_INS``, ``create_request`` and a ``Kind`` whose request carries those
keys. See ``kinds/token_stream.py`` for what a configuration's file and a
cell's file of either kind hold.
"""

from __future__ import annotations

import copy
import os
import threading
from typing import Dict, List, Optional, Tuple

from perfbench import harness

base = harness.load_module(os.path.dirname(os.path.abspath(__file__)), "token_stream")

Rows, Pool, System, Readings = base.Rows, base.Pool, base.System, base.Readings
draw_rows, render, scaled = base.draw_rows, base.render, base.scaled
distance, leaf_update_gaps, initial_gaps = base.distance, base.leaf_update_gaps, base.initial_gaps
launch_scopes, LEAF_CHANGE_FLOOR = base.launch_scopes, base.LEAF_CHANGE_FLOOR

ARCH_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
             "total_ut_steps", "early_exit_threshold")

# the configuration at a size a CPU test holds: four heads of 16, a
# feed-forward 2.75 times the hidden size as published, three layers looped
# four times, rows that are no multiple of any block. The step size is cut
# with the widths, as ``token_stream.TINY`` explains: every matrix feeds a
# norm, so the objective's curvature along it goes as 1 / |W|^2
TINY = {
    "learning_rate": 5e-4,
    "arch": {"vocab_size": 96, "hidden_size": 64, "intermediate_size": 176, "num_hidden_layers": 3,
             "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16},
    "rows": 8,
    "traffic": {"tokens_per_row": 150, "part_rows": 4},
}

# the control (parameters in the precision below the float32 the
# configuration states) and the faults a cell of this kind can have: each
# leaves out a part of the looped model
STAND_INS = [("bfloat16", None), ("float32", "one_loop"), ("float32", "no_step_norm"),
             ("float32", "last_step_loss"), ("float32", "no_entropy"), ("float32", "no_rope"),
             ("float32", "no_output_norms"), ("float32", "half_loss")]


def create_request(config: dict, tokens_per_row: int, seed: int) -> dict:
    """The Create request of one run: the file's, with the learner's
    ``dataStructure`` filled from the architecture's top-level keys."""
    create = copy.deepcopy(config["create"])
    ds = {k: config[k] for k in ARCH_KEYS}
    ds["nFeatures"] = int(tokens_per_row)
    create["learner"]["dataStructure"] = ds
    create["learner"]["hyperParameters"]["seed"] = int(seed) % (2**31 - 1)
    return create


def require_model(learner: dict) -> None:
    """Fail at once where the program does not know the request's model (a
    program from before it builds its one model whatever ``model_type`` says,
    and would train that for minutes before the comparison finds out)."""
    from omldm_tpu.api.requests import LearnerSpec
    from omldm_tpu.learners.registry import make_learner

    built = make_learner(LearnerSpec(learner["name"], hyper_parameters=learner["hyperParameters"],
                                     data_structure=learner["dataStructure"]))
    want = learner["dataStructure"]["total_ut_steps"]
    if getattr(built.cfg, "total_ut_steps", None) != want:
        raise RuntimeError(f"the program's learner {learner['name']!r} does not build model_type "
                           f"{learner['dataStructure']['model_type']!r}: no loop of {want} steps in its configuration")


class Kind(base.Kind):
    """``token_stream.Kind`` with this module's request (the base's
    ``__init__`` fills its own model's keys, so its fields are set here)."""

    def __init__(self, config: dict, cell: dict, seed: int, here: str):
        self.config, self.cell, self.seed, self.here = config, cell, seed, here
        traffic = cell["traffic"]
        self.tokens_per_row = int(traffic["tokens_per_row"])
        self.vocab = int(config["vocab_size"])
        self.batch = int(config["job_flags"]["batchSize"])
        self.n_pool = int(config["rows"]) if traffic["kind"] == "closed_loop" else 0
        self.create = create_request(config, self.tokens_per_row, seed)
        require_model(self.create["learner"])
        # the configuration as the reference reads it
        self.resolved = dict(config, create=self.create)
        self.counters: Dict[str, float] = {}
        self.probe_losses: List[float] = []
        self.first_update_norm = 0.0
        self.initial: Optional[dict] = None
        self.final: Optional[dict] = None
        self.scope_of: Optional[Dict[str, str]] = None
        self.leaf_gaps: Dict[str, Tuple[float, float]] = {}
        self._files_seen = 0
        self._scope_ms: Optional[Dict[str, float]] = None
        self._want_scopes = False
        self._pool: Optional[Pool] = None
        self._pool_lock = threading.Lock()
