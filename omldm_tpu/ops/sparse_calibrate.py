"""Calibration harness for the sparse scatter dispatch.

`sparse_scatter_add_auto` (ops/sparse.py) has three formulations of the
same w[idx] += coef*val update with very different cost models:

- ``scatter``: XLA's native scatter-add — serializes per update row on TPU
  but is the natural form everywhere else;
- ``mxu``: the kron-factored one-hot matmul (ops/sparse.py:52) — trades
  ~2*2*D FLOPs per update for the serialization, wins only where the chip's
  matmul rate beats the scatter element rate times D;
- ``segsum``: sort + segmented pre-combine — collapses duplicate hashed
  indices before the scatter, wins when the duplicate factor is high enough
  that the (vectorized) sort costs less than the serialized duplicate adds.

Round 5 shipped the mxu dispatch on a GUESSED ``D >= 2^16`` threshold with
no measured crossover. This module replaces the guess:
it measures all three kernels over a (D, batch, nnz) grid with a
hashed-categorical duplicate profile (each COO slot draws from a ~1k-value
vocabulary, the Criteo/Avazu shape the sparse path exists for), persists
the per-backend crossover table next to this file
(``sparse_dispatch.json``), and `sparse_scatter_add_auto` dispatches from
the table at trace time (nearest grid point in log2 space). Re-run on new
hardware:

    python -m omldm_tpu.ops.sparse_calibrate            # full grid
    python -m omldm_tpu.ops.sparse_calibrate --smoke    # CI-sized grid

Writes merge per backend, so a TPU calibration does not clobber the CPU
section. ``OMLDM_SPARSE_SCATTER_TABLE`` points the lookup (and the writer)
at an alternate table path; ``OMLDM_SPARSE_SCATTER`` bypasses the table
entirely (ops/sparse.py).
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
ENV_TABLE = "OMLDM_SPARSE_SCATTER_TABLE"

# skip a kernel whose intermediate working set would not fit a modest host
# (the mxu one-hot operands are [2n, D/512 + 512] bf16 — at D=2^20 and
# n=160k that is >1 GB, pointless to measure on CPU and an OOM risk in CI)
MXU_BYTES_CAP = 1 << 28


def table_path() -> str:
    return os.environ.get(ENV_TABLE, "").strip() or DEFAULT_TABLE


_cache: Dict[str, object] = {"path": None, "mtime": None, "table": None}


def load_table(path: Optional[str] = None) -> Optional[dict]:
    """Cached table read (mtime-invalidated; None when absent/corrupt)."""
    path = path or table_path()
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
    Criteo/Avazu streams (benchmarks/run_benchmarks.py stream gen), which
    is exactly what the segsum pre-combine exists to exploit."""
    rng = np.random.RandomState(seed)
    vocab_n = min(1000, max(d // nnz, 2))
    idx = np.empty((batch, nnz), np.int32)
    for k in range(nnz):
        vocab = rng.randint(0, d, size=vocab_n)
        idx[:, k] = vocab[rng.randint(0, vocab_n, size=batch)]
    val = rng.randn(batch, nnz).astype(np.float32)
    coef = rng.randn(batch).astype(np.float32)
    return idx, val, coef


def _measure_kernel(fn, d: int, idx, val, coef, steps: int,
                    repeats: int = 3) -> float:
    """Updates/sec for one kernel: ``steps`` applications chained in ONE
    jitted scan (so per-dispatch overhead does not dominate), w donated,
    best-of-``repeats``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(w, ii, vv, cc):
        def body(ww, _):
            return fn(ww, ii, cc, vv), None

        w, _ = jax.lax.scan(body, w, None, length=steps)
        return w

    w = jnp.zeros((d,), jnp.float32)
    ii, vv, cc = jnp.asarray(idx), jnp.asarray(val), jnp.asarray(coef)
    chain(w, ii, vv, cc).block_until_ready()  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        chain(w, ii, vv, cc).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return steps * idx.size / best


def measure_entry(d: int, batch: int, nnz: int, steps: int) -> dict:
    from omldm_tpu.ops.sparse import MXU_LANES, SCATTER_IMPLS

    idx, val, coef = _gen_updates(d, batch, nnz)
    n = idx.size
    rates: Dict[str, Optional[float]] = {}
    for name, fn in SCATTER_IMPLS.items():
        if name == "mxu":
            r = -(-d // MXU_LANES)
            est = 2 * (2 * n) * (r + MXU_LANES)  # bf16 one-hot operands
            if est > MXU_BYTES_CAP:
                rates[name] = None
                continue
        rates[name] = round(_measure_kernel(fn, d, idx, val, coef, steps), 1)
    measured = {k: v for k, v in rates.items() if v is not None}
    winner = max(measured, key=measured.get)  # type: ignore[arg-type]
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
    out = out or table_path()
    table = load_table(out) or {
        "version": 1,
        "note": (
            "sparse scatter dispatch crossover table — generated by "
            "python -m omldm_tpu.ops.sparse_calibrate; "
            "sparse_scatter_add_auto (ops/sparse.py) reads the nearest "
            "(d, updates) entry for the active backend at trace time"
        ),
        "backends": {},
    }
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
                    "$OMLDM_SPARSE_SCATTER_TABLE or ops/sparse_dispatch.json)")
    ap.add_argument("--steps", type=int, default=None,
                    help="chained kernel applications per timing sample")
    args = ap.parse_args(argv)
    grid = SMOKE_GRID if args.smoke else FULL_GRID
    steps = args.steps or (4 if args.smoke else 16)
    calibrate(grid, steps, out=args.out,
              tag="--smoke" if args.smoke else "")


if __name__ == "__main__":
    main()
