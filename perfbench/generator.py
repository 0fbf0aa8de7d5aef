"""The schedule every kind of deployment shares: the streams of one ``--seed``,
files that live in memory, the event lists of probe, part and slice files,
and the open-loop arrivals, made from ``--seed`` and the parameters in a
cell's file (rates, poll, part sizes).

Nothing here is specific to one cell or to one kind of record: a later cell is
a new data file, and what a record is and how it is drawn and rendered belongs
to the configuration's kind (``perfbench/kinds/<kind>.py``), which hands this
module lines (:class:`Rendered`, or a pool with ``spans(a, b)``). The program
under test sees only the files cut from them here.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

# streams of one seed: each draw names its stream, so adding a draw never
# shifts another
STREAM_POOL, STREAM_FORECAST, STREAM_PROBE, STREAM_ARRIVALS = 1, 2, 3, 4


def rng_for(seed: int, stream: int, block: int = 0) -> np.random.Generator:
    """``--seed`` may exceed 32 signed bits; SeedSequence takes any size."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream, block]))


@dataclass
class Rendered:
    """The lines of a block of records: ``data[offsets[i]:offsets[i+1]]`` is
    line ``i`` with its newline."""

    data: np.ndarray  # uint8, flat
    offsets: np.ndarray  # int64 [n + 1]

    def span(self, a: int, b: int) -> memoryview:
        return memoryview(self.data)[int(self.offsets[a]) : int(self.offsets[b])]


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
    ``train`` (a kind's pool, which answers ``spans(a, b)`` with a list, or a
    :class:`Rendered` block for the probe), forecasts one line each of
    ``forecast``."""
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
        if kind[a] == TRAIN and hasattr(train, "spans"):
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
        index[kind == TRAIN] = (start_row + rows) % max(n_pool, 1)  # no pool, no rows
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
