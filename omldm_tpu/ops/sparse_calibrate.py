"""Calibration harness for the sparse update's dispatch.

A sparse linear learner's update is a gather (the margins) and a scatter-add
(the update) over the same indices; ``ops.sparse.sparse_update`` has two
formulations of the pair with very different cost models:

- ``scatter``: ``jnp.take`` and XLA's native scatter-add, slot by slot: the
  natural form everywhere, and on the TPU a cost per slot (gather) and per
  distinct address (scatter) whatever the addresses;
- ``plan``: one sort of the launch's indices shared by both halves
  (``index_plan``): duplicates combined, the weight vector addressed once
  per distinct index, everything else by sorts and scans. Wins where a
  launch is large and duplicate-heavy enough that its six sorts cost less
  than the slots they save at the weight vector; a launch with more
  distinct addresses than a quarter of its slots runs the plain pair
  behind the plan's first sort.

This module measures both over a (D, batch, nnz) grid with a
hashed-categorical duplicate profile (each COO slot draws from a ~1k-value
vocabulary, the Criteo/Avazu shape the sparse path exists for), timing the
update AS THE LEARNER RUNS IT (margins, a coefficient that depends on
them, the scatter), persists the per-backend table next to this file
(``sparse_dispatch.json``), and ``sparse_update`` dispatches from the table
at trace time (nearest grid point in log2 space). Re-run on new hardware:

    python -m omldm_tpu.ops.sparse_calibrate            # full grid
    python -m omldm_tpu.ops.sparse_calibrate --smoke    # CI-sized grid

Off the CPU the full grid also holds the shapes the benchmark's cells run
(``CELL_GRID``: 2^28 + 14 weights; a 1 GiB vector is not for a shared CPU
host). Writes merge per backend, so one backend's calibration does not
clobber another's section; ``--out`` writes an alternate table.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time
from typing import Dict, List, Optional

import numpy as np

DEFAULT_TABLE = os.path.join(os.path.dirname(__file__), "sparse_dispatch.json")

_cache: Dict[str, object] = {"path": None, "mtime": None, "table": None}


def load_table(path: Optional[str] = None) -> Optional[dict]:
    """Cached table read (mtime-invalidated; None when absent/corrupt)."""
    path = path or DEFAULT_TABLE
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return None
    if _cache["path"] == path and _cache["mtime"] == mtime:
        return _cache["table"]  # type: ignore[return-value]
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(table, dict) or "backends" not in table:
        return None
    _cache.update(path=path, mtime=mtime, table=table)
    return table


def lookup_winner(backend: str, d: int, n_updates: int) -> Optional[str]:
    """Winner at the nearest measured (D, updates) grid point for this
    backend — log2-space nearest neighbor, since both axes are decade
    scales. None when the backend has no measured section (callers fall
    back to the pre-calibration guess)."""
    table = load_table()
    if table is None:
        return None
    section = table.get("backends", {}).get(str(backend))
    if not section:
        return None
    entries = section.get("entries") or []
    best, best_dist = None, None
    ld, ln = math.log2(max(d, 1)), math.log2(max(n_updates, 1))
    for e in entries:
        try:
            dist = abs(math.log2(max(int(e["d"]), 1)) - ld) + abs(
                math.log2(max(int(e["updates"]), 1)) - ln
            )
            winner = str(e["winner"])
        except (KeyError, TypeError, ValueError):
            continue
        if best_dist is None or dist < best_dist:
            best, best_dist = winner, dist
    return best


# --- measurement -----------------------------------------------------------


def _gen_updates(d: int, batch: int, nnz: int, seed: int = 0):
    """Hashed-categorical update profile: each COO slot draws from its own
    ~1k-value vocabulary inside [0, d) — the duplicate structure of the
    Criteo/Avazu streams, which is exactly what the index plan exists to
    exploit."""
    rng = np.random.RandomState(seed)
    vocab_n = min(1000, max(d // nnz, 2))
    idx = np.empty((batch, nnz), np.int32)
    for k in range(nnz):
        vocab = rng.randint(0, d, size=vocab_n)
        idx[:, k] = vocab[rng.randint(0, vocab_n, size=batch)]
    val = rng.randn(batch, nnz).astype(np.float32)
    coef = rng.randn(batch).astype(np.float32)
    return idx, val, coef


def _measure_kernel(name: str, d: int, idx, val, coef, steps: int,
                    repeats: int = 3) -> float:
    """Updates/sec of one formulation of the whole update: ``steps``
    applications chained in ONE jitted scan (so per-dispatch overhead does
    not dominate), w donated, best-of-``repeats``."""
    import jax
    import jax.numpy as jnp

    from omldm_tpu.ops.sparse import sparse_update

    def chain(w, ii, vv, cc):
        def body(ww, _):
            margins, add, _ = sparse_update(ww, ii, vv, impl=name)
            # the coefficient hangs on the margins, as a learner's does:
            # the gather cannot be dropped or moved behind the scatter
            return add(ww, cc - 1e-3 * margins), None

        w, _ = jax.lax.scan(body, w, None, length=steps)
        return w

    chain = jax.jit(chain, donate_argnums=0)
    w = jnp.zeros((d,), jnp.float32)
    ii, vv, cc = jnp.asarray(idx), jnp.asarray(val), jnp.asarray(coef)
    w = chain(w, ii, vv, cc).block_until_ready()  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        w = chain(w, ii, vv, cc).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return steps * idx.size / best


def measure_entry(d: int, batch: int, nnz: int, steps: int) -> dict:
    from omldm_tpu.ops.sparse import IMPLS

    idx, val, coef = _gen_updates(d, batch, nnz)
    n = idx.size
    rates = {
        name: round(_measure_kernel(name, d, idx, val, coef, steps), 1)
        for name in IMPLS
    }
    # what sparse_update runs by DEFAULT there
    winner = max(IMPLS, key=rates.__getitem__)
    dup = n / max(len(np.unique(idx)), 1)
    return {
        "d": d,
        "batch": batch,
        "nnz": nnz,
        "updates": n,
        "duplicate_factor": round(dup, 2),
        "rates_updates_per_sec": rates,
        "winner": winner,
    }


FULL_GRID = [
    (d, batch, nnz)
    for d in (1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20)
    for batch in (1024, 4096)
    for nnz in (8, 40)
]
# CI-sized: covers both sides of the guessed 2^16 crossover in seconds
SMOKE_GRID = [(1 << 12, 256, 8), (1 << 16, 256, 8), (1 << 18, 256, 8)]
# what the benchmark's cells run (BENCHMARK.json, criteo_pa_2e28): 2^28 + 14
# weights; a launch of 4096 rows, a tail step of 256, a forecast's padded
# batch of 16, each of maxNnz 40 plus the bias slot
CELL_GRID = [((1 << 28) + 14, batch, 41) for batch in (4096, 256, 16)]


def calibrate(grid: List[tuple], steps: int, out: Optional[str] = None,
              tag: str = "") -> dict:
    """Measure the grid on the CURRENT backend and merge the section into
    the table at ``out`` (other backends' sections are preserved)."""
    import jax

    backend = jax.default_backend()
    entries = []
    for d, batch, nnz in grid:
        e = measure_entry(d, batch, nnz, steps)
        entries.append(e)
        print(
            f"  d=2^{int(math.log2(d))} batch={batch} nnz={nnz} "
            f"dup={e['duplicate_factor']}x -> {e['winner']} "
            f"{e['rates_updates_per_sec']}"
        )
    out = out or DEFAULT_TABLE
    table = load_table(out) or {"version": 1, "backends": {}}
    table["note"] = (
        "sparse update dispatch crossover table — generated by "
        "python -m omldm_tpu.ops.sparse_calibrate; "
        "sparse_update (ops/sparse.py) reads the nearest "
        "(d, updates) entry for the active backend at trace time"
    )
    table["backends"][backend] = {
        "generated_by": (
            f"python -m omldm_tpu.ops.sparse_calibrate {tag}".strip()
        ),
        "steps_per_sample": steps,
        "entries": entries,
    }
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=1, sort_keys=False)
        f.write("\n")
    os.replace(tmp, out)
    _cache["path"] = None  # force reload on next lookup
    print(f"wrote {backend} section ({len(entries)} entries) -> {out}")
    return table


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized grid: seconds, exercises the table format and both "
        "sides of the guessed crossover",
    )
    ap.add_argument("--out", default=None, help="table path (default: "
                    "ops/sparse_dispatch.json)")
    ap.add_argument("--steps", type=int, default=None,
                    help="chained kernel applications per timing sample")
    args = ap.parse_args(argv)
    import jax

    grid = SMOKE_GRID if args.smoke else FULL_GRID
    if not args.smoke and jax.default_backend() != "cpu":
        grid = grid + CELL_GRID
    steps = args.steps or (4 if args.smoke else 16)
    calibrate(grid, steps, out=args.out,
              tag="--smoke" if args.smoke else "")


if __name__ == "__main__":
    main()
