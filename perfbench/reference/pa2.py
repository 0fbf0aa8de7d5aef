"""Plain reference of the Criteo PA deployment: numpy, float32, no kernels.

It imports nothing of the program and takes nothing the program made. From the
structured values of the same rows, in the same order, it does what the
configuration's file states:

- hashes categorical field ``i`` with value ``cat`` by the CRC-32 (zlib
  polynomial) of ``"{i}={cat}"`` into ``hash_space`` buckets after the numeric
  slots, with the sign taken from bit 1 of the hash (its own table-driven
  CRC, checked against zlib by the self-check);
- keeps the 8-of-10 holdout: of every ten training rows the last two go to a
  ring of ``holdout_cap`` rows, and once the ring is full the oldest row
  re-enters training at the evicting row's place;
- trains in batches of ``batch`` rows, a file's last partial stage in padded
  steps of ``tail_batch`` rows, each step one PA-II update with the mean taken
  over the step's valid rows;
- answers a forecast from the weights as they stand after the launches
  before it.

``precision="bfloat16"`` is the control: the same arithmetic with weights and
every intermediate rounded to bfloat16 (the step a later PR would be tempted
to take, since it halves the bytes of the weight vector). ``fault`` plants one
of the faults the harness's own tests must catch: ``state_unchanged``,
``half_batch`` (the mean taken over the rest), ``answer_altered``, and
``wrong_bucket`` (categoricals hashed into half the buckets: weights land
where the configuration puts none).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

TRAIN, FORECAST = 0, 1

_POLY = 0xEDB88320


def _crc_table() -> np.ndarray:
    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(t & 1, np.uint32(_POLY) ^ (t >> 1), t >> 1).astype(np.uint32)
    return t


_TABLE = _crc_table()
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)


def crc32_bytes(cols: np.ndarray) -> np.ndarray:
    """CRC-32 of each row of a [n, L] uint8 matrix."""
    c = np.full(cols.shape[0], 0xFFFFFFFF, np.uint32)
    for k in range(cols.shape[1]):
        c = _TABLE[(c ^ cols[:, k]) & np.uint32(0xFF)] ^ (c >> np.uint32(8))
    return c ^ np.uint32(0xFFFFFFFF)


def hash_field(i: int, values: np.ndarray) -> np.ndarray:
    """CRC-32 of ``f"{i}={value:08x}"`` for each 32-bit value."""
    prefix = np.frombuffer(f"{i}=".encode(), np.uint8)
    shifts = np.arange(28, -4, -4, dtype=np.uint32)
    hexes = _HEX[(values[:, None].astype(np.uint32) >> shifts) & np.uint32(15)]
    cols = np.concatenate(
        [np.broadcast_to(prefix, (len(values), len(prefix))), hexes], axis=1
    )
    return crc32_bytes(cols)


def encode(nums: np.ndarray, cats: np.ndarray, hash_space: int,
           buckets: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Rows as (index, value) pairs: numeric j at slot j with its value (a
    missing 0 adds nothing), categorical i at ``n_num + crc % hash_space``
    with value +1 or -1, and the bias (value 1) at the last weight."""
    n, n_num = nums.shape
    n_cat = cats.shape[1]
    idx = np.empty((n, n_num + n_cat + 1), np.int64)
    val = np.empty((n, n_num + n_cat + 1), np.float32)
    idx[:, :n_num] = np.arange(n_num)
    val[:, :n_num] = nums.astype(np.float32)
    for i in range(n_cat):
        h = hash_field(i, cats[:, i])
        idx[:, n_num + i] = n_num + (h.astype(np.int64) % (buckets or hash_space))
        val[:, n_num + i] = np.where((h >> np.uint32(1)) & np.uint32(1), -1.0, 1.0)
    idx[:, -1] = n_num + hash_space
    val[:, -1] = 1.0
    return idx, val


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    x = np.asarray(x, np.float32)
    b = np.ascontiguousarray(x).reshape(-1).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return b.view(np.float32).reshape(x.shape)


class ReferenceJob:
    def __init__(self, n_num: int, hash_space: int, C: float, batch: int,
                 tail_batch: int, holdout_cap: int, precision: str = "float32",
                 fault: Optional[str] = None):
        if precision not in ("float32", "bfloat16"):
            raise ValueError(precision)
        if fault not in (None, "state_unchanged", "half_batch", "answer_altered", "wrong_bucket"):
            raise ValueError(fault)
        self.n_num, self.hash_space = n_num, hash_space
        self.C, self.batch, self.tail_batch = C, batch, tail_batch
        self.holdout_cap = holdout_cap
        self.q = _bf16 if precision == "bfloat16" else (lambda x: x)
        self.fault = fault
        self.w = np.zeros(n_num + hash_space + 1, np.float32)
        self.seen = 0  # training rows so far: the place in the 0-9 cycle
        self.ring: List[Tuple[np.ndarray, np.ndarray, float]] = []
        self.stage: List[Tuple[np.ndarray, np.ndarray, float]] = []
        self.fitted = 0
        self.losses: List[float] = []
        self.touched: List[np.ndarray] = []
        # per forecast: (id, answer, margin, sum of |terms|)
        self.answers: List[Tuple[int, float, float, float]] = []

    # -- one PA-II step over the staged rows ----------------------------------

    def _step(self, rows) -> None:
        q = self.q
        idx = np.stack([r[0] for r in rows])
        val = np.stack([r[1] for r in rows])
        y = np.asarray([r[2] for r in rows], np.float32)
        n_valid = len(rows)
        if self.fault == "half_batch":
            keep = max(n_valid // 2, 1)
            idx, val, y = idx[:keep], val[:keep], y[:keep]
        ys = np.where(y > 0, np.float32(1), np.float32(-1))
        margins = q(np.sum(q(self.w[idx] * val), axis=1, dtype=np.float32))
        hinge = np.maximum(np.float32(0), q(np.float32(1) - ys * margins))
        sq = np.maximum(np.sum(val * val, axis=1, dtype=np.float32), np.float32(1e-12))
        tau = q(hinge / (sq + np.float32(1.0 / (2.0 * self.C))))
        coef = q(tau * ys / np.float32(len(y)))
        self.losses.append(float(np.mean(hinge, dtype=np.float32)))
        self.fitted += n_valid
        self.touched.append(np.unique(idx))
        if self.fault == "state_unchanged":
            return
        np.add.at(self.w, idx.ravel(), q(coef[:, None] * val).ravel())
        if self.q is _bf16:
            t = self.touched[-1]
            self.w[t] = _bf16(self.w[t])

    def _launch_full(self) -> None:
        while len(self.stage) >= self.batch:
            self._step(self.stage[: self.batch])
            del self.stage[: self.batch]

    def _flush_tail(self) -> None:
        while self.stage:
            self._step(self.stage[: self.tail_batch])
            del self.stage[: self.tail_batch]

    # -- the stream -----------------------------------------------------------

    def feed_file(self, kind: np.ndarray, index: np.ndarray, train, forecast,
                  forecast_ids=None) -> None:
        """One file, events in order. ``train`` and ``forecast`` are blocks of
        structured rows (``nums``, ``cats``, ``target``) that ``index`` points
        into."""
        t_sel = index[kind == TRAIN]
        f_sel = index[kind == FORECAST]
        # the planted hashing fault folds the hash into half the buckets
        buckets = self.hash_space // 2 if self.fault == "wrong_bucket" else None
        t_idx, t_val = encode(train.nums[t_sel], train.cats[t_sel], self.hash_space, buckets)
        t_y = train.target[t_sel].astype(np.float32)
        if len(f_sel):
            f_idx, f_val = encode(forecast.nums[f_sel], forecast.cats[f_sel], self.hash_space, buckets)
        ti = fi = 0
        for k in kind:
            if k == TRAIN:
                row = (t_idx[ti], t_val[ti], float(t_y[ti]))
                ti += 1
                place = self.seen % 10
                self.seen += 1
                if place >= 8 and self.holdout_cap > 0:
                    self.ring.append(row)
                    if len(self.ring) <= self.holdout_cap:
                        continue
                    row = self.ring.pop(0)
                self.stage.append(row)
                if len(self.stage) >= self.batch:
                    self._launch_full()
            else:
                terms = self.q(self.w[f_idx[fi]] * f_val[fi])
                margin = float(self.q(np.sum(terms, dtype=np.float32)))
                answer = 1.0 if margin >= 0 else -1.0
                if self.fault == "answer_altered":
                    answer = -answer
                fid = int(f_sel[fi] if forecast_ids is None else forecast_ids[f_sel[fi]])
                self.answers.append((fid, answer, margin, float(np.sum(np.abs(terms)))))
                fi += 1
        self._flush_tail()

    @property
    def holdout(self) -> int:
        return len(self.ring)

    def touched_indices(self) -> np.ndarray:
        if not self.touched:
            return np.zeros(0, np.int64)
        return np.unique(np.concatenate(self.touched))


def build(config: dict, precision: str = "float32", fault: Optional[str] = None) -> ReferenceJob:
    """The reference of a configuration's file (``"reference": "pa2"``)."""
    learner = config["create"]["learner"]
    hp = learner["hyperParameters"]
    if learner["name"] != "PA" or hp.get("variant") != "PA-II":
        raise ValueError("this reference implements the PA-II classifier")
    return ReferenceJob(
        n_num=int(config["schema"]["numeric_fields"]),
        hash_space=int(learner["dataStructure"]["hashSpace"]),
        C=float(hp["C"]),
        batch=int(config["job_flags"]["batchSize"]),
        tail_batch=int(config["program_constants"]["tail_batch"]),
        holdout_cap=int(config["job_flags"]["testSetSize"]),
        precision=precision, fault=fault,
    )
