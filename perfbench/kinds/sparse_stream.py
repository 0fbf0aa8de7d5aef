"""The kind ``sparse_stream``: one online learner over a hashed sparse click
stream (numeric and categorical fields), fed through the fused, overlapped
file route of a ``StreamJob`` (``run_file_fused``), compared on its one weight
vector.

Everything the benchmark knows about this kind of deployment is here: the
records and how they are rendered, how the job is built and driven, what is
kept of a prediction and of the probe, the comparison with the plain reference
(``reference/<config["reference"]>.py``), the control and the traced run's
extras. ``harness.py`` finds this file by the ``kind`` a configuration's file
names and knows none of it (``perfbench/README.md``, "Adding a configuration
of a new kind").

Rendering is vectorised: a block of rows is a fixed-width byte matrix whose
unused positions hold 0, and dropping the 0 bytes leaves variable-width JSON
lines. No Python loop runs per row.
"""

from __future__ import annotations

import copy
import json
import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from perfbench import generator as gen
from perfbench import harness

NUM_WIDTH = 7  # digits of numeric_cap (1,000,000)
ID_WIDTH = 9
BLOCK = 1 << 14  # rows drawn and rendered at a time: small, reused buffers

_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)

# the configuration at a size a CPU test holds
TINY = {"hash_space": 1 << 10, "rows": 1 << 15, "traffic": {"part_rows": 8192}}

# the control (the precision below the float32 the configuration states) and
# the faults a one-chip cell of this kind can have
STAND_INS = [("bfloat16", None), ("float32", "state_unchanged"),
             ("float32", "half_batch"), ("float32", "answer_altered"),
             ("float32", "wrong_bucket")]


def scaled(config: dict, scale: dict) -> dict:
    """The configuration at a hash space and pool a CPU test can hold (the
    self-check and the tests under ``perfbench/tests``; never a chip run)."""
    config = copy.deepcopy(config)
    ds = config["create"]["learner"]["dataStructure"]
    ds["hashSpace"] = scale["hash_space"]
    ds["nFeatures"] = config["schema"]["numeric_fields"] + scale["hash_space"]
    config["rows"] = scale["rows"]
    return config


# --- records -----------------------------------------------------------------


@dataclass(frozen=True)
class Schema:
    """What a configuration's file says about its records."""

    vocab: Tuple[int, ...]
    n_num: int
    zipf_s: float
    log_mean: Tuple[float, ...]
    log_sigma: float
    cap: int
    missing: float
    rule_seed: int
    noise: float

    @classmethod
    def from_config(cls, config: dict) -> "Schema":
        s, a = config["schema"], config["assumed"]
        if len(a["numeric_log_mean"]) != s["numeric_fields"]:
            raise ValueError("numeric_log_mean needs one entry per numeric field")
        if len(s["vocabularies"]) != s["categorical_fields"]:
            raise ValueError("vocabularies needs one entry per categorical field")
        if a["numeric_cap"] >= 10 ** NUM_WIDTH + 1:
            raise ValueError(f"numeric_cap needs more than {NUM_WIDTH} digits")
        return cls(
            vocab=tuple(int(v) for v in s["vocabularies"]),
            n_num=int(s["numeric_fields"]),
            zipf_s=float(a["zipf_exponent"]),
            log_mean=tuple(float(m) for m in a["numeric_log_mean"]),
            log_sigma=float(a["numeric_log_sigma"]),
            cap=int(a["numeric_cap"]),
            missing=float(a["missing_share"]),
            rule_seed=int(a["label_rule_seed"]),
            noise=float(a["label_noise"]),
        )


@dataclass
class Rows:
    """Structured values of a block of records (what the reference reads)."""

    nums: np.ndarray  # [n, n_num] int32, 0 = missing
    cats: np.ndarray  # [n, n_cat] uint32, printed as 8 hex characters
    target: np.ndarray  # [n] uint8 in {0, 1}

    def __len__(self) -> int:
        return self.nums.shape[0]

    def take(self, sel) -> "Rows":
        return Rows(self.nums[sel], self.cats[sel], self.target[sel])


def _zipf_ranks(rng, n: int, vocab: int, s: float) -> np.ndarray:
    """Ranks in [1, vocab] from a bounded Zipf law, by the inverse of the
    continuous (bounded Pareto) distribution function."""
    u = rng.random(n)
    if vocab == 1:
        return np.ones(n, np.int64)
    if abs(s - 1.0) < 1e-9:
        r = np.exp(u * np.log(vocab + 1.0))
    else:
        a = 1.0 - s
        r = (u * ((vocab + 1.0) ** a - 1.0) + 1.0) ** (1.0 / a)
    return np.clip(np.floor(r).astype(np.int64), 1, vocab)


def draw_rows(rng, n: int, schema: Schema) -> Rows:
    z = rng.standard_normal((n, schema.n_num), dtype=np.float32)
    z = np.exp(z * np.float32(schema.log_sigma) + np.asarray(schema.log_mean, np.float32))
    nums = np.minimum(z, np.float32(schema.cap)).astype(np.int32)
    nums[rng.random((n, schema.n_num), dtype=np.float32) < schema.missing] = 0
    n_cat = len(schema.vocab)
    cats = np.empty((n, n_cat), np.uint32)
    for f, vocab in enumerate(schema.vocab):
        rank = _zipf_ranks(rng, n, vocab, schema.zipf_s).astype(np.uint64)
        # odd multiplier: distinct ranks of one field print distinct values
        cats[:, f] = (
            (rank * np.uint64(0x9E3779B1) + np.uint64(f) * np.uint64(0x85EBCA6B))
            & np.uint64(0xFFFFFFFF)
        ).astype(np.uint32)
    # the fixed linear rule: weights from rule_seed, never from --seed
    rule = np.random.default_rng(schema.rule_seed)
    a = rule.normal(0.0, 1.0, schema.n_num)
    b = rule.normal(0.0, 1.0, n_cat)
    sign = 1.0 - 2.0 * (((cats * np.uint32(0xC2B2AE35)) >> np.uint32(15)) & np.uint32(1)).astype(np.float32)
    x = np.log1p(nums.astype(np.float32))
    x = x - np.log1p(np.exp(np.asarray(schema.log_mean, np.float32)))  # roughly centred
    score = x @ a * 0.3 + sign @ b * 0.5
    score = score + rng.normal(0.0, schema.noise, n)
    return Rows(nums, cats, (score > 0).astype(np.uint8))


_POW10 = 10 ** np.arange(9, -1, -1, dtype=np.int64)


def _digits(vals: np.ndarray, width: int) -> np.ndarray:
    """ASCII decimal digits of non-negative ``vals`` [...] as [..., width]
    uint8, leading zeros left as 0 bytes (at least one digit is kept)."""
    pw = _POW10[-width:].astype(vals.dtype if vals.dtype.itemsize >= 4 else np.int64)
    d = (vals[..., None] // pw) % 10
    n_digits = np.ones(vals.shape, np.int8)
    for p in pw[:-1][::-1]:
        n_digits += vals >= p
    lead = np.arange(width) < (width - n_digits)[..., None]
    return np.where(lead, 0, d + 48).astype(np.uint8)


class _Layout:
    """Column layout of the fixed-width matrix of one record kind: a template
    row holding the constant bytes, and where the variable fields go."""

    def __init__(self, n_num: int, n_cat: int, forecast: bool):
        row = bytearray()

        def const(text: bytes) -> None:
            row.extend(text)

        def hole(width: int) -> int:
            at = len(row)
            row.extend(bytes(width))
            return at

        self.id_at = None
        if forecast:
            const(b'{"id": ')
            self.id_at = hole(ID_WIDTH)
            const(b', "numericalFeatures": [')
        else:
            const(b'{"numericalFeatures": [')
        # numeric j: NUM_WIDTH digit columns, then ", " (the last one "]" and
        # one unused column), so the fields sit at a fixed pitch
        self.num_pitch = NUM_WIDTH + 2
        self.num_at = len(row)
        for j in range(n_num):
            hole(NUM_WIDTH)
            const(b", " if j + 1 < n_num else b"]\0")
        const(b', "categoricalFeatures": [')
        # categorical f: quote, 8 hex columns, quote, ", " (the last one "]")
        self.cat_pitch = 12
        self.cat_at = len(row)
        for f in range(n_cat):
            const(b'"')
            hole(8)
            const(b'", ' if f + 1 < n_cat else b'"]\0')
        self.target_at = None
        if forecast:
            const(b', "operation": "forecasting"}\n')
        else:
            const(b', "target": ')
            self.target_at = hole(1)
            const(b', "operation": "training"}\n')
        self.template = np.frombuffer(bytes(row), np.uint8)
        self.width = len(row)
        self.n_num, self.n_cat = n_num, n_cat


_LAYOUTS: dict = {}
_SHIFTS = np.arange(28, -4, -4, dtype=np.uint32)


def _render_block(rows: Rows, ids: Optional[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
    n, n_num = rows.nums.shape
    n_cat = rows.cats.shape[1]
    key = (n_num, n_cat, ids is not None)
    lay = _LAYOUTS.get(key)
    if lay is None:
        lay = _LAYOUTS[key] = _Layout(*key)
    mat = np.empty((n, lay.width), np.uint8)
    mat[:] = lay.template
    if ids is not None:
        mat[:, lay.id_at : lay.id_at + ID_WIDTH] = _digits(ids.astype(np.int64), ID_WIDTH)
    num = mat[:, lay.num_at : lay.num_at + n_num * lay.num_pitch]
    numpart = np.ascontiguousarray(num).reshape(n, n_num, lay.num_pitch)
    numpart[:, :, :NUM_WIDTH] = _digits(rows.nums, NUM_WIDTH)
    num[:] = numpart.reshape(n, -1)
    cat = mat[:, lay.cat_at : lay.cat_at + n_cat * lay.cat_pitch]
    catpart = np.ascontiguousarray(cat).reshape(n, n_cat, lay.cat_pitch)
    catpart[:, :, 1:9] = _HEX[(rows.cats[:, :, None] >> _SHIFTS) & np.uint32(15)]
    cat[:] = catpart.reshape(n, -1)
    if lay.target_at is not None:
        mat[:, lay.target_at] = rows.target + 48
    keep = mat != 0
    return mat[keep], keep.sum(axis=1)


def render(rows: Rows, ids: Optional[np.ndarray] = None) -> gen.Rendered:
    """Training lines (``ids`` None) or forecast lines carrying ``"id"``."""
    parts, lens = [], []
    for s in range(0, len(rows), BLOCK):
        sel = slice(s, s + BLOCK)
        flat, ln = _render_block(rows.take(sel), None if ids is None else ids[sel])
        parts.append(flat)
        lens.append(ln)
    offsets = np.zeros(len(rows) + 1, np.int64)
    if lens:
        np.cumsum(np.concatenate(lens), out=offsets[1:])
    data = np.concatenate(parts) if parts else np.zeros(0, np.uint8)
    return gen.Rendered(data, offsets)


class Pool:
    """The seeded pool of training rows a window replays: row ``r`` lives in
    block ``r // BLOCK``, and every block is drawn from its own stream of the
    seed, so blocks can be made in any order, by any thread, and are never
    all held at once (a window's worth of JSON is hundreds of MB)."""

    def __init__(self, seed: int, schema: Schema, n_rows: int):
        if n_rows % BLOCK:
            raise ValueError(f"pool rows must be a multiple of {BLOCK}")
        self.seed, self.schema, self.n_rows = seed, schema, n_rows
        self._last: Optional[Tuple[int, gen.Rendered]] = None

    def block(self, b: int) -> gen.Rendered:
        if self._last is not None and self._last[0] == b:
            return self._last[1]
        rows = draw_rows(gen.rng_for(self.seed, gen.STREAM_POOL, b), BLOCK, self.schema)
        rendered = render(rows)
        self._last = (b, rendered)
        return rendered

    def spans(self, a: int, b: int) -> List[memoryview]:
        """The lines of rows ``[a, b)``, ``0 <= a <= b <= n_rows``."""
        out = []
        while a < b:
            blk = a // BLOCK
            lo = a - blk * BLOCK
            hi = min(b - blk * BLOCK, BLOCK)
            out.append(self.block(blk).span(lo, hi))
            a = blk * BLOCK + hi
        return out


# --- the system under test ---------------------------------------------------


class System:
    """The job as ``python -m omldm_tpu`` builds it from the configuration's
    flags, its Create request through the normal entry, behind the four
    calls the windows make."""

    def __init__(self, config: dict, on_prediction):
        import jax

        from omldm_tpu.__main__ import build_job as cli_build_job

        job, _sinks = cli_build_job(dict(config["job_flags"]))
        job.set_sinks(on_prediction=on_prediction, on_response=lambda r: None,
                      on_performance=lambda r: None)
        job.process_event("requests", json.dumps(config["create"]))
        job.ensure_deployed(int(config["create"]["learner"]["dataStructure"]["nFeatures"]))
        bridge = job.fused_file_bridge()
        if bridge is None or not bridge.supports_overlapped_ingest():
            raise RuntimeError("the job does not qualify for the fused, overlapped file route")
        self.job, self.bridge = job, bridge
        # the back-pressure marker: compiled by its first call, after the probe
        self._marker = jax.jit(lambda s: s + 0)

    def hand_over(self, path: str) -> None:
        self.job.run_file_fused(path)

    def marker(self):
        """A device value that exists once the device has finished every file
        handed over so far."""
        return self._marker(self.bridge.trainer.state["step"])

    def wait(self) -> None:
        import jax

        jax.block_until_ready(self.bridge.trainer.state)

    def close(self) -> Dict[str, int]:
        """The job's normal termination report (which also evaluates the
        holdout on the device) and where every row offered went."""
        report = self.job.terminate()
        stats = report.to_dict()["statistics"][0] if report is not None else {}
        return {"fitted": int(stats.get("fitted", self.bridge.trainer.fitted)),
                "holdout": len(self.bridge.test_set)}


class DenseWeights:
    """A weight vector as the program holds it, copied to the host."""

    def __init__(self, w: np.ndarray):
        self.w = w

    def take(self, idx: np.ndarray) -> np.ndarray:
        return self.w[idx]

    def sumsq(self) -> float:
        return float(np.sum(np.square(self.w, dtype=np.float64)))


class SparseWeights:
    """A weight vector that is zero outside ``idx`` (sorted): how a stand-in
    reference hands its weights over without a copy of the whole vector."""

    def __init__(self, idx: np.ndarray, val: np.ndarray):
        self.idx, self.val = idx, val

    def take(self, idx: np.ndarray) -> np.ndarray:
        if not len(self.idx):
            return np.zeros(len(idx), np.float32)
        at = np.minimum(np.searchsorted(self.idx, idx), len(self.idx) - 1)
        return np.where(self.idx[at] == idx, self.val[at], np.float32(0))

    def sumsq(self) -> float:
        return float(np.sum(np.square(self.val, dtype=np.float64)))


# --- the kind ----------------------------------------------------------------


class Kind:
    """One run's records, probe readings and comparison. The readers find
    ``batch`` and ``max_nnz`` through ``ctx``."""

    def __init__(self, config: dict, cell: dict, seed: int, here: str):
        self.config, self.cell, self.seed, self.here = config, cell, seed, here
        self.schema = Schema.from_config(config)
        ds = config["create"]["learner"]["dataStructure"]
        self.hash_space = int(ds["hashSpace"])
        self.max_nnz = int(ds["maxNnz"])
        self.batch = int(config["job_flags"]["batchSize"])
        self.n_pool = int(config["rows"])
        traffic = cell["traffic"]
        if traffic["kind"] == "closed_loop" and int(traffic["part_rows"]) % self.batch:
            raise ValueError("part_rows must hold whole launches")
        self.counters: Dict[str, float] = {}
        self.probe_w: List[DenseWeights] = []
        self.probe_losses: List[float] = []

    # -- records --------------------------------------------------------------

    def training_records(self, n: int) -> gen.Rendered:
        """The probe's ``n`` training records, as lines."""
        self.probe_rows = draw_rows(gen.rng_for(self.seed, gen.STREAM_PROBE), n, self.schema)
        return render(self.probe_rows)

    def forecast_records(self, n: int) -> gen.Rendered:
        """The run's ``n`` forecast records (probe first), as lines with ids
        ``0..n-1``."""
        self.forecast_rows = draw_rows(gen.rng_for(self.seed, gen.STREAM_FORECAST), max(n, 1), self.schema)
        return render(self.forecast_rows, ids=np.arange(max(n, 1)))

    def pool(self) -> Pool:
        """The pool of training records a window replays (one per thread that
        cuts files from it)."""
        return Pool(self.seed, self.schema, self.n_pool)

    # -- the job --------------------------------------------------------------

    def build(self, on_prediction) -> System:
        return System(self.config, on_prediction)

    @staticmethod
    def keep(pred) -> tuple:
        """What the sink keeps of a prediction: its forecast's id and the
        answer."""
        return pred.data_instance.id, float(pred.value)

    def after_probe_file(self, system: System) -> None:
        """What the reference is compared with: per-step losses and the
        weight vector after each probe file."""
        trainer = system.bridge.trainer
        self.probe_losses += [l for l, _ in trainer.curve_slice()]
        w = np.asarray(trainer.state["params"]["w"]).reshape(-1)
        self.probe_w.append(DenseWeights(w))

    # -- the comparison -------------------------------------------------------

    def reference(self, plans: list, precision: str = "float32", fault: Optional[str] = None):
        """The plain reference over the probe files (nothing of the program).
        After each file it keeps the weights it has touched so far (it is zero
        everywhere else)."""
        module = harness.load_module(os.path.join(self.here, "reference"), self.config["reference"])
        ref = module.build(self.config, precision=precision, fault=fault)
        ref.w_after, ref.touched_after = [], []
        for plan in plans:
            ref.feed_file(plan.kind, plan.index, self.probe_rows, self.forecast_rows)
            touched = ref.touched_indices()
            ref.touched_after.append(touched)
            ref.w_after.append(ref.w[touched].copy())
        return ref

    def checks(self, plans: list, answers: List[tuple], counts: dict) -> Dict[str, dict]:
        """Every number compared, beside its limit, for what the program left
        behind after the probe files ``plans``."""
        return self.compare(self.reference(plans), self.probe_w, self.probe_losses, answers, counts)

    def control(self, plans: list, precision: str = "float32", fault: Optional[str] = None) -> Dict[str, dict]:
        """The numbers compared when the reference, in a lower precision or
        with a fault planted, stands in the program's place (no program, no
        window: the probe files alone)."""
        if getattr(self, "_sound", None) is None:
            self._sound = self.reference(plans)
        sound = self._sound
        stand_in = self.reference(plans, precision=precision, fault=fault)
        weights = [SparseWeights(t, w) for t, w in zip(stand_in.touched_after, stand_in.w_after)]
        answers = [(fid, value, 0.0) for fid, value, _m, _s in stand_in.answers]
        n_forecasts = sum(p.n_forecast for p in plans)
        counts = {
            "offered_rows": len(self.probe_rows), "fitted": stand_in.fitted,
            "holdout": stand_in.holdout, "offered_forecasts": n_forecasts,
            "probe_answers": len(answers),
        }
        return self.compare(sound, weights, stand_in.losses, answers, counts)

    def compare(self, ref, got_w: list, got_losses: List[float],
                got_answers: List[tuple], counts: dict) -> Dict[str, dict]:
        """Every number compared, beside its limit."""
        limits = self.cell["limits"]
        out: Dict[str, float] = {}
        out["rows_lost"] = counts["offered_rows"] - counts["fitted"] - counts["holdout"]
        # forecasts: every id exactly once (probe and window)
        ids = [a[0] for a in got_answers]
        expected = counts["offered_forecasts"]
        out["forecasts_bad"] = (expected - len(set(ids))) + (len(ids) - len(set(ids)))
        # the probe's answers, where the reference's margin is not a rounding
        # of zero (1e-4 of the sum of its terms' sizes)
        ref_by_id = {a[0]: a for a in ref.answers}
        mismatch = judged = 0
        for fid, value, _t in got_answers[: counts["probe_answers"]]:
            r = ref_by_id.get(fid)
            if r is None:
                mismatch += 1
                continue
            _, answer, margin, scale = r
            if abs(margin) <= 1e-4 * scale and scale > 0:
                continue
            judged += 1
            mismatch += value != answer
        mismatch += max(len(ref.answers) - counts["probe_answers"], 0)
        out["probe_pred_mismatch"] = mismatch
        self.counters["probe_answers_judged"] = judged
        # per-step loss
        n = max(len(got_losses), len(ref.losses))
        gaps = [1.0] * n
        for i in range(min(len(got_losses), len(ref.losses))):
            gaps[i] = abs(got_losses[i] - ref.losses[i]) / max(abs(ref.losses[i]), 1e-3)
        out["loss_gap"] = max(gaps) if gaps else 1.0
        # the update after the first file, the change after the last
        norm = lambda v: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
        for name, k in (("first_update_norm_gap", 0), ("change_norm_gap", len(ref.w_after) - 1)):
            want = norm(ref.w_after[k])
            have = norm(got_w[k].take(ref.touched_after[k]))
            out[name] = abs(have - want) / max(want, 1e-30)
        last = len(ref.w_after) - 1
        touched = ref.touched_after[last]
        want = ref.w_after[last]
        have = got_w[last].take(touched)
        out["w_diff_rel"] = norm(have - want) / max(norm(want), 1e-30)
        total = got_w[last].sumsq()
        inside = float(np.sum(np.square(have, dtype=np.float64)))
        out["w_stray_share"] = max(total - inside, 0.0) / max(float(np.sum(np.square(want, dtype=np.float64))), 1e-30)
        return {k: {"value": float(v), "limit": float(limits[k])} for k, v in out.items()}

    # -- the traced run's extras ----------------------------------------------

    def traced_extras(self) -> Dict[str, float]:
        """The repo's sparse parser alone over pool bytes (host-only code, host
        clock), for the parse layer's metric. Traced runs only, before the
        window."""
        from omldm_tpu.ops.native import SparseFastParser

        pool = self.pool()
        blocks = [bytes(pool.block(b).data) for b in range(min(self.n_pool // BLOCK, 16))]
        blob = b"".join(blocks)
        n_rows = len(blocks) * BLOCK
        parser = SparseFastParser(self.schema.n_num, self.hash_space, self.max_nnz, n_threads=0)
        parser.parse(blocks[0])
        t = time.perf_counter()
        reps = 0
        while time.perf_counter() - t < 0.5:
            parser.parse(blob)
            reps += 1
        return {"parser_rows": n_rows * reps, "parser_s": time.perf_counter() - t,
                "parser_threads": parser.n_threads}
