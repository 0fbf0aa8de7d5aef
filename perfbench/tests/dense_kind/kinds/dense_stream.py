"""The kind ``dense_stream``, used by tests alone: two-class softmax regression
over dense numeric records through the dense fused file route of a
``StreamJob`` (chained stages, a weight matrix, predictions that carry no id).
It is the proof that a configuration of another kind is new files only:
``harness.py``, ``generator.py`` and ``run.py`` know nothing in here.
"""

from __future__ import annotations

import copy
import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from perfbench import generator as gen
from perfbench import harness

TINY: dict = {}  # the configuration is a test's size as it stands

STAND_INS = [("bfloat16", None), ("float32", "state_unchanged"),
             ("float32", "half_batch"), ("float32", "answer_altered")]


def scaled(config: dict, scale: dict) -> dict:
    config = copy.deepcopy(config)
    config["rows"] = scale.get("rows", config["rows"])
    return config


@dataclass
class Rows:
    x: np.ndarray  # [n, dim] float32, as printed (4 decimals)
    y: np.ndarray  # [n] uint8 in {0, 1}


def draw_rows(rng, n: int, dim: int, rule_seed: int) -> Rows:
    w = np.random.default_rng(rule_seed).normal(0.0, 1.0, dim)
    x = np.round(rng.standard_normal((n, dim)), 4)
    return Rows(x.astype(np.float32), (x @ w > 0).astype(np.uint8))


def render(rows: Rows, forecast: bool) -> gen.Rendered:
    """Training lines, or forecast lines whose feature 0 is the forecast's
    id: the dense route's predictions carry the features and no id."""
    lines = []
    for k, (x, y) in enumerate(zip(rows.x.tolist(), rows.y.tolist())):
        feats = ", ".join("%.4f" % v for v in x)
        lines.append(('{"numericalFeatures": [%s], "operation": "forecasting"}\n' % feats) if forecast else
                     ('{"numericalFeatures": [%s], "target": %.1f, "operation": "training"}\n' % (feats, y)))
    blob = "".join(lines).encode()
    offsets = np.zeros(len(lines) + 1, np.int64)
    np.cumsum([len(l) for l in lines], out=offsets[1:])
    return gen.Rendered(np.frombuffer(blob, np.uint8), offsets)


class Pool:
    """The seeded pool of training rows a closed loop replays."""

    def __init__(self, seed: int, n_rows: int, dim: int, rule_seed: int):
        self.lines = render(draw_rows(gen.rng_for(seed, gen.STREAM_POOL), n_rows, dim, rule_seed), False)

    def spans(self, a: int, b: int) -> List[memoryview]:
        return [self.lines.span(a, b)]


class System:
    def __init__(self, config: dict, on_prediction):
        import jax

        from omldm_tpu.__main__ import build_job as cli_build_job

        job, _sinks = cli_build_job(dict(config["job_flags"]))
        job.set_sinks(on_prediction=on_prediction, on_response=lambda r: None,
                      on_performance=lambda r: None)
        job.process_event("requests", json.dumps(config["create"]))
        job.ensure_deployed(int(config["create"]["learner"]["dataStructure"]["nFeatures"]))
        bridge = job.fused_file_bridge()
        if bridge is None or not bridge.supports_overlapped_ingest() or hasattr(bridge, "_launch_coo"):
            raise RuntimeError("the job does not take the dense fused, overlapped file route")
        self.job, self.bridge = job, bridge
        self._marker = jax.jit(lambda s: s + 0)

    def hand_over(self, path: str) -> None:
        self.job.run_file_fused(path)

    def marker(self):
        return self._marker(self.bridge.trainer.state["step"])

    def wait(self) -> None:
        import jax

        jax.block_until_ready(self.bridge.trainer.state)

    def close(self) -> Dict[str, int]:
        self.job.terminate()
        return {"fitted": int(self.bridge.trainer.fitted), "holdout": len(self.bridge.test_set)}


class Kind:
    def __init__(self, config: dict, cell: dict, seed: int, here: str):
        self.config, self.cell, self.seed, self.here = config, cell, seed, here
        self.dim = int(config["create"]["learner"]["dataStructure"]["nFeatures"])
        self.batch = int(config["job_flags"]["batchSize"])
        self.rule_seed = int(config["label_rule_seed"])
        # a cell that offers no training rows in its window has no pool
        self.n_pool = int(config["rows"]) if cell["traffic"]["kind"] == "closed_loop" else 0
        # forward and backward of one row: two classes over dim + 1 weights
        self.flops_per_row = 3 * 2 * 2 * (self.dim + 1)
        self.counters: Dict[str, float] = {}
        self.probe_w: List[np.ndarray] = []
        self.probe_losses: List[float] = []

    def training_records(self, n: int) -> gen.Rendered:
        self.probe_rows = draw_rows(gen.rng_for(self.seed, gen.STREAM_PROBE), n, self.dim, self.rule_seed)
        return render(self.probe_rows, False)

    def forecast_records(self, n: int) -> gen.Rendered:
        rows = draw_rows(gen.rng_for(self.seed, gen.STREAM_FORECAST), n, self.dim, self.rule_seed)
        rows.x[:, 0] = np.arange(n)
        self.forecast_rows = rows
        return render(rows, True)

    def pool(self) -> Pool:
        return Pool(self.seed, self.n_pool, self.dim, self.rule_seed)

    def build(self, on_prediction) -> System:
        return System(self.config, on_prediction)

    @staticmethod
    def keep(pred) -> tuple:
        return int(pred.data_instance.numerical_features[0]), float(pred.value)

    def after_probe_file(self, system: System) -> None:
        trainer = system.bridge.trainer
        self.probe_losses += [l for l, _ in trainer.curve_slice()]
        self.probe_w.append(np.asarray(trainer.state["params"]["W"]).reshape(self.dim + 1, 2).copy())

    def reference(self, plans: list, precision: str = "float32", fault: Optional[str] = None):
        module = harness.load_module(os.path.join(self.here, "reference"), self.config["reference"])
        ref = module.build(self.config, precision=precision, fault=fault)
        ref.w_after = []
        for plan in plans:
            ref.feed_file(plan.kind, plan.index, self.probe_rows, self.forecast_rows)
            ref.w_after.append(ref.W.copy())
        return ref

    def checks(self, plans: list, answers: List[tuple], counts: dict) -> Dict[str, dict]:
        return self.compare(self.reference(plans), self.probe_w, self.probe_losses, answers, counts)

    def control(self, plans: list, precision: str = "float32", fault: Optional[str] = None) -> Dict[str, dict]:
        stand_in = self.reference(plans, precision=precision, fault=fault)
        answers = [(fid, value, 0.0) for fid, value, _gap in stand_in.answers]
        counts = {"offered_rows": len(self.probe_rows.y), "fitted": stand_in.fitted, "holdout": stand_in.holdout,
                  "offered_forecasts": sum(p.n_forecast for p in plans), "probe_answers": len(answers)}
        return self.compare(self.reference(plans), stand_in.w_after, stand_in.losses, answers, counts)

    def compare(self, ref, got_w, got_losses, got_answers, counts) -> Dict[str, dict]:
        out = {"rows_lost": counts["offered_rows"] - counts["fitted"] - counts["holdout"]}
        ids = [a[0] for a in got_answers]
        out["forecasts_bad"] = (counts["offered_forecasts"] - len(set(ids))) + (len(ids) - len(set(ids)))
        # the probe's answers, where the reference's two logits are apart
        want = {fid: answer for fid, answer, gap in ref.answers if gap > 1e-4}
        got = {fid: value for fid, value, _t in got_answers[: counts["probe_answers"]]}
        out["answers_wrong"] = sum(got.get(fid) != answer for fid, answer in want.items())
        n = max(len(got_losses), len(ref.losses))
        gaps = [1.0] * n
        for i in range(min(len(got_losses), len(ref.losses))):
            gaps[i] = abs(got_losses[i] - ref.losses[i]) / max(abs(ref.losses[i]), 1e-3)
        out["loss_gap"] = max(gaps) if gaps else 1.0
        norm = lambda v: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
        out["w_diff_rel"] = max(norm(g - w) / max(norm(w), 1e-30) for g, w in zip(got_w, ref.w_after))
        limits = self.cell["limits"]
        return {k: {"value": float(v), "limit": float(limits[k])} for k, v in out.items()}

    def traced_extras(self) -> Dict[str, float]:
        return {}
