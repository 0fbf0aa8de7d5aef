"""Plain reference of the dense test deployment: numpy, float32, nothing of
the program. From the structured values of the same records, in the same
order, it does what the configuration's file states:

- keeps the 8-of-10 holdout: of every ten training rows the last two go to a
  ring of ``holdout_cap`` rows, and once the ring is full the oldest row
  re-enters training at the evicting row's place;
- stages rows; a full stage of ``chain x batch`` rows trains as ``chain``
  steps of ``batch`` rows, a file's last partial stage as whole batches and
  then padded steps of ``tail_batch`` rows; each step is one SGD step of
  two-class softmax regression on the mean gradient of its valid rows;
- answers a forecast (the class with the larger logit) from the weights as
  they stand after the stages launched before it.

``precision="bfloat16"`` is the control; ``fault`` plants ``state_unchanged``,
``half_batch`` (the mean taken over the rest) or ``answer_altered``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

TRAIN, FORECAST = 0, 1
FAULTS = (None, "state_unchanged", "half_batch", "answer_altered")


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    x = np.asarray(x, np.float32)
    b = np.ascontiguousarray(x).reshape(-1).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return b.view(np.float32).reshape(x.shape)


class ReferenceJob:
    def __init__(self, dim: int, lr: float, batch: int, chain: int, tail_batch: int,
                 holdout_cap: int, precision: str = "float32", fault: Optional[str] = None):
        if precision not in ("float32", "bfloat16") or fault not in FAULTS:
            raise ValueError((precision, fault))
        self.lr, self.batch, self.chain = np.float32(lr), batch, chain
        self.tail_batch, self.holdout_cap = min(batch, tail_batch), holdout_cap
        self.q = _bf16 if precision == "bfloat16" else (lambda x: x)
        self.fault = fault
        self.W = np.zeros((dim + 1, 2), np.float32)
        self.seen = 0
        self.ring: List[Tuple[np.ndarray, float]] = []
        self.stage: List[Tuple[np.ndarray, float]] = []
        self.fitted = 0
        self.losses: List[float] = []
        # per forecast: (id, answer, gap between the two logits)
        self.answers: List[Tuple[int, float, float]] = []

    def _logits(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        xb = np.concatenate([x, np.ones((len(x), 1), np.float32)], axis=1)
        return self.q(self.q(xb) @ self.W), xb

    def _step(self, rows) -> None:
        q = self.q
        x = np.stack([r[0] for r in rows])
        y = np.asarray([r[1] for r in rows], np.int64)
        self.fitted += len(rows)
        if self.fault == "half_batch":
            keep = max(len(rows) // 2, 1)
            x, y = x[:keep], y[:keep]
        logits, xb = self._logits(x)
        z = logits - logits.max(axis=1, keepdims=True)
        logp = z - np.log(np.sum(np.exp(z), axis=1, keepdims=True, dtype=np.float32))
        self.losses.append(float(np.mean(-logp[np.arange(len(y)), y], dtype=np.float32)))
        if self.fault == "state_unchanged":
            return
        probs = q(np.exp(logp))
        probs[np.arange(len(y)), y] -= np.float32(1)
        grad = q(q(xb).T @ probs / np.float32(len(y)))
        self.W = q(self.W - self.lr * grad)

    def _launch_full(self) -> None:
        for k in range(self.chain):
            self._step(self.stage[k * self.batch : (k + 1) * self.batch])
        del self.stage[: self.chain * self.batch]

    def _flush(self) -> None:
        while len(self.stage) >= self.batch:
            self._step(self.stage[: self.batch])
            del self.stage[: self.batch]
        while self.stage:
            self._step(self.stage[: self.tail_batch])
            del self.stage[: self.tail_batch]

    def feed_file(self, kind: np.ndarray, index: np.ndarray, train, forecast) -> None:
        """One file, events in order; ``train`` and ``forecast`` hold ``x``
        (and ``y``) that ``index`` points into."""
        for k, i in zip(kind, index):
            if k == TRAIN:
                row = (train.x[i], float(train.y[i]))
                place = self.seen % 10
                self.seen += 1
                if place >= 8 and self.holdout_cap > 0:
                    self.ring.append(row)
                    if len(self.ring) <= self.holdout_cap:
                        continue
                    row = self.ring.pop(0)
                self.stage.append(row)
                if len(self.stage) >= self.chain * self.batch:
                    self._launch_full()
            else:
                logits, _ = self._logits(forecast.x[i : i + 1])
                answer = float(np.argmax(logits[0]))
                if self.fault == "answer_altered":
                    answer = 1.0 - answer
                self.answers.append((int(i), answer, float(abs(logits[0, 1] - logits[0, 0]))))
        self._flush()

    @property
    def holdout(self) -> int:
        return len(self.ring)


def build(config: dict, precision: str = "float32", fault: Optional[str] = None) -> ReferenceJob:
    learner = config["create"]["learner"]
    hp = learner["hyperParameters"]
    if learner["name"] != "Softmax" or int(hp["nClasses"]) != 2:
        raise ValueError("this reference implements two-class softmax regression")
    return ReferenceJob(
        dim=int(learner["dataStructure"]["nFeatures"]), lr=float(hp["learningRate"]),
        batch=int(config["job_flags"]["batchSize"]),
        chain=int(config["create"]["trainingConfiguration"]["extra"]["stageChain"]),
        tail_batch=int(config["program_constants"]["tail_batch"]),
        holdout_cap=int(config["job_flags"]["testSetSize"]),
        precision=precision, fault=fault,
    )
