"""The one general traffic generator: Criteo-shaped records and the open-loop
schedule, made from ``--seed`` and the parameters in a configuration's file
(schema, distributions) and a cell's file (rates, poll, part sizes).

Nothing here is specific to one cell: a later cell is a new data file. The
program under test sees only the files this module renders (JSON lines of the
fast schema); the plain reference gets the structured values of the same rows.

Rendering is vectorised: a block of rows is a fixed-width byte matrix whose
unused positions hold 0, and dropping the 0 bytes leaves variable-width JSON
lines. No Python loop runs per row.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

NUM_WIDTH = 7  # digits of numeric_cap (1,000,000)
ID_WIDTH = 9
BLOCK = 1 << 14  # rows drawn and rendered at a time: small, reused buffers

_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)

# streams of one seed: each draw below names its stream, so adding a draw
# never shifts another
STREAM_POOL, STREAM_FORECAST, STREAM_PROBE, STREAM_ARRIVALS = 1, 2, 3, 4


def rng_for(seed: int, stream: int, block: int = 0) -> np.random.Generator:
    """``--seed`` may exceed 32 signed bits; SeedSequence takes any size."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream, block]))


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


# --- rendering ---------------------------------------------------------------


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


@dataclass
class Rendered:
    """JSON lines of a block of rows: ``data[offsets[i]:offsets[i+1]]`` is
    line ``i`` with its newline."""

    data: np.ndarray  # uint8, flat
    offsets: np.ndarray  # int64 [n + 1]

    def span(self, a: int, b: int) -> memoryview:
        return memoryview(self.data)[int(self.offsets[a]) : int(self.offsets[b])]


def render(rows: Rows, ids: Optional[np.ndarray] = None) -> Rendered:
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
    return Rendered(data, offsets)


class Pool:
    """The seeded pool of training rows a window replays: row ``r`` lives in
    block ``r // BLOCK``, and every block is drawn from its own stream of the
    seed, so blocks can be made in any order, by any thread, and are never
    all held at once (a window's worth of JSON is hundreds of MB)."""

    def __init__(self, seed: int, schema: Schema, n_rows: int):
        if n_rows % BLOCK:
            raise ValueError(f"pool rows must be a multiple of {BLOCK}")
        self.seed, self.schema, self.n_rows = seed, schema, n_rows
        self._last: Optional[Tuple[int, Rendered]] = None

    def block(self, b: int) -> Rendered:
        if self._last is not None and self._last[0] == b:
            return self._last[1]
        rows = draw_rows(rng_for(self.seed, STREAM_POOL, b), BLOCK, self.schema)
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


# --- files -------------------------------------------------------------------


class MemFile:
    """A file that lives in memory (``memfd_create``) and is opened by path.
    The program reads a path like any other; nothing is written to a disk,
    which every run of every later check would otherwise pay for."""

    def __init__(self, pieces: Sequence, label: str = "perfbench"):
        self.fd = os.memfd_create(label)
        self.size = 0
        for piece in pieces:
            view = memoryview(piece)
            while len(view):
                done = os.write(self.fd, view)
                view = view[done:]
                self.size += done
        self.path = f"/proc/{os.getpid()}/fd/{self.fd}"

    def close(self) -> None:
        if self.fd >= 0:
            os.close(self.fd)
            self.fd = -1


# --- event lists -------------------------------------------------------------

TRAIN, FORECAST = 0, 1


@dataclass
class FilePlan:
    """One file handed to the job: events in stream order. ``kind[i]`` is
    TRAIN (``index[i]`` a row of the training block) or FORECAST (``index[i]``
    a row of the forecast block). ``created`` is the forecast's creation time
    in seconds from the window's start (NaN for training rows)."""

    kind: np.ndarray
    index: np.ndarray
    due: float = 0.0
    created: Optional[np.ndarray] = None

    @property
    def n_train(self) -> int:
        return int((self.kind == TRAIN).sum())

    @property
    def n_forecast(self) -> int:
        return int((self.kind == FORECAST).sum())


def file_pieces(plan: FilePlan, train, forecast: Optional[Rendered]) -> List[memoryview]:
    """The bytes of ``plan``: runs of consecutive training rows are spans of
    ``train`` (a :class:`Pool`, or a :class:`Rendered` block for the probe),
    forecasts one line each of ``forecast``."""
    pieces: List[memoryview] = []
    kind, index = plan.kind, plan.index
    n = len(kind)
    # boundaries: where the kind changes, or a training run stops being
    # consecutive (the pool wrapped)
    brk = np.ones(n + 1, bool)
    if n > 1:
        same = (kind[1:] == kind[:-1]) & (kind[1:] == TRAIN) & (index[1:] == index[:-1] + 1)
        brk[1:n] = ~same
    cuts = np.nonzero(brk)[0]
    for a, b in zip(cuts[:-1], cuts[1:]):
        if kind[a] == TRAIN and isinstance(train, Pool):
            pieces.extend(train.spans(int(index[a]), int(index[b - 1]) + 1))
        elif kind[a] == TRAIN:
            pieces.append(train.span(int(index[a]), int(index[b - 1]) + 1))
        else:
            pieces.append(forecast.span(int(index[a]), int(index[a]) + 1))
    return pieces


def train_plan(start: int, n: int, n_pool: int) -> FilePlan:
    """``n`` training rows of the pool from ``start``, wrapping."""
    idx = (start + np.arange(n, dtype=np.int64)) % n_pool
    return FilePlan(np.zeros(n, np.int8), idx)


def arrival_times(seed: int, rate: float, seconds: float, arrivals_seed: int,
                  poll_s: float) -> np.ndarray:
    """Creation times of a Poisson stream of ``rate`` a second over
    ``seconds``. The stream itself is fixed by ``arrivals_seed`` (a cell's
    parameter): ``rate * seconds`` exponential gaps scaled to fill the window.
    ``--seed`` reorders it slice by slice: the arrivals of each ``poll_s``
    slice keep their offsets inside the slice, and the slices change places.
    So every run has the same forecasts-per-slice counts and the same
    clumps, in another order, and two seeds do the same work."""
    n = int(round(rate * seconds))
    if n <= 0:
        return np.zeros(0)
    gaps = np.random.default_rng(arrivals_seed).exponential(1.0, n)
    gaps *= seconds * n / (n + 1.0) / gaps.sum()
    times = np.cumsum(gaps)
    n_slices = int(np.ceil(seconds / poll_s - 1e-9))
    which = np.minimum((times / poll_s).astype(np.int64), n_slices - 1)
    new_place = rng_for(seed, STREAM_ARRIVALS).permutation(n_slices)
    return np.sort(times + (new_place[which] - which) * poll_s)


def paced_plans(rate_rows: float, forecast_times: np.ndarray, seconds: float,
                poll_s: float, n_pool: int, start_row: int = 0,
                first_forecast: int = 0) -> List[FilePlan]:
    """Open-loop slices: training row j is created at ``j / rate_rows``,
    forecast i at ``forecast_times[i]``; what was created in
    ``[k * poll_s, (k + 1) * poll_s)`` is one file, in creation order, due at
    the slice's end. Rows are dealt to slices by whole-number arithmetic, so
    a slice's row count never depends on how a quotient rounds."""
    n_slices = int(np.ceil(seconds / poll_s - 1e-9))
    per_slice = rate_rows * poll_s
    bounds = np.floor(np.arange(n_slices + 1) * per_slice + 1e-6).astype(np.int64)
    f_slice = np.minimum((forecast_times / poll_s).astype(np.int64), n_slices - 1)
    f_edges = np.searchsorted(f_slice, np.arange(n_slices + 1))
    plans = []
    for k in range(n_slices):
        rows = np.arange(bounds[k], bounds[k + 1], dtype=np.int64)
        fs = np.arange(f_edges[k], f_edges[k + 1], dtype=np.int64)
        # a forecast goes after the rows created before it
        at = np.searchsorted(rows / rate_rows, forecast_times[fs], side="right")
        kind = np.zeros(len(rows) + len(fs), np.int8)
        index = np.empty(len(rows) + len(fs), np.int64)
        created = np.full(len(rows) + len(fs), np.nan)
        f_pos = at + np.arange(len(fs))
        kind[f_pos] = FORECAST
        index[f_pos] = first_forecast + fs
        created[f_pos] = forecast_times[fs]
        index[kind == TRAIN] = (start_row + rows) % n_pool
        plans.append(FilePlan(kind, index, due=(k + 1) * poll_s, created=created))
    return plans


def spread_forecasts(n_train: int, n_forecast: int, first_forecast: int = 0,
                     start_row: int = 0) -> FilePlan:
    """A probe file: ``n_train`` rows with ``n_forecast`` forecasts spread
    evenly through them (the last one after the last row)."""
    at = ((np.arange(n_forecast) + 1) * n_train) // max(n_forecast, 1)
    kind = np.zeros(n_train + n_forecast, np.int8)
    index = np.empty(n_train + n_forecast, np.int64)
    f_pos = at + np.arange(n_forecast)
    kind[f_pos] = FORECAST
    index[f_pos] = first_forecast + np.arange(n_forecast)
    index[kind == TRAIN] = start_row + np.arange(n_train)
    return FilePlan(kind, index)
