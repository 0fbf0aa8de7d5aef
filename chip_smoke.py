"""chip_smoke.py — the standing proof that the system starts on the chip.

    python3 chip_smoke.py

One process drives the system's main path once on every local TPU chip,
through the entry points a user would call (``python -m omldm_tpu``'s
``main``, ``MLPipeline``, ``SeqTrainer``), each configuration at its full
width, and checks what comes out. Data is generated here from a seed (the
chip machine has no network). The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

It exits non-zero, with no such line, when jax finds no TPU, when it is run
away from the repository, or when any check of any leg fails. There is no
handler around a leg: the first failed check ends the run.

Legs (each a function that takes its sizes, so tests/test_chip_smoke.py can
run it tiny on the CPU with interpreted kernels):

- ``stream_fused``  BASELINE config 1 through the CLI's fused file route;
- ``stream_mixed``  eight same-spec host-plane tenants, train + forecast;
- ``stream_sparse`` BASELINE config 3 (PA-II, 13 + 2^18 hashed features);
- ``kernels``       the Pallas kernels that are on by default on a TPU.

One process holds the chip: the legs run in this process, one after another.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import re
import sys
import tempfile
import time
from typing import Dict, Iterator, List, Tuple

import numpy as np


def _require(ok: bool, what: str) -> None:
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


# --- compile accounting ------------------------------------------------------


class CompileClock:
    """Seconds jax spent in backend compilation (persistent-cache retrieval
    included, so a warm cache shows as fewer seconds) and the cache's
    hit/miss counts, read from jax's own monitoring events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.compiles: List[Tuple[float, str]] = []  # (seconds, program)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == self._COMPILE:
            self.seconds += seconds
            self.compiles.append((seconds, str(kw.get("fun_name", "?"))))

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.hits, self.misses


# --- seeded streams ----------------------------------------------------------


def write_dense_stream(path: str, n: int, dim: int, seed: int) -> None:
    """``n`` linearly separable training records of ``dim`` numeric
    features (the HIGGS shape at dim 28), as JSON lines."""
    rng = np.random.RandomState(seed)
    w = rng.randn(dim)
    fmt = (
        '{"numericalFeatures": [' + ", ".join(["%.4f"] * dim)
        + '], "target": %.1f, "operation": "training"}'
    )
    with open(path, "w") as f:
        done = 0
        while done < n:
            m = min(50_000, n - done)
            x = np.round(rng.randn(m, dim), 4)
            y = (x @ w > 0).astype(np.float64)
            rows = np.concatenate([x, y[:, None]], axis=1).tolist()
            f.write("\n".join(fmt % tuple(r) for r in rows) + "\n")
            done += m


def write_forecast_stream(path: str, n: int, dim: int, seed: int) -> None:
    """``n`` forecasting records; feature 0 carries the row's index so each
    prediction can be matched to the row that asked for it."""
    rng = np.random.RandomState(seed)
    x = np.round(rng.randn(n, dim), 4)
    x[:, 0] = np.arange(n)
    with open(path, "w") as f:
        for row in x.tolist():
            f.write(json.dumps(
                {"numericalFeatures": row, "operation": "forecasting"}
            ) + "\n")


def write_sparse_stream(path: str, n: int, n_num: int, n_cat: int,
                        seed: int) -> None:
    """Criteo-shaped records: ``n_num`` numerics + ``n_cat`` categorical
    strings from a 1000-value vocabulary each."""
    rng = np.random.RandomState(seed)
    w = rng.randn(n_num)
    fmt = (
        '{"numericalFeatures": [' + ", ".join(["%.4f"] * n_num)
        + '], "categoricalFeatures": ['
        + ", ".join(f'"f{j}_v%d"' for j in range(n_cat))
        + '], "target": %.1f, "operation": "training"}'
    )
    with open(path, "w") as f:
        done = 0
        while done < n:
            m = min(20_000, n - done)
            x = np.round(rng.randn(m, n_num), 4)
            y = (x @ w > 0).astype(np.float64)
            cats = rng.randint(0, 1000, size=(m, n_cat))
            f.write("\n".join(
                fmt % (*x[i].tolist(), *cats[i].tolist(), y[i])
                for i in range(m)
            ) + "\n")
            done += m


def write_requests(path: str, requests: List[dict]) -> None:
    with open(path, "w") as f:
        for r in requests:
            f.write(json.dumps(r) + "\n")


# --- the CLI, in this process ------------------------------------------------


@contextlib.contextmanager
def _capture_job() -> Iterator[list]:
    """Run ``omldm_tpu.__main__.main`` untouched, but keep a reference to
    the StreamJob it builds: the checks need the trained state's placement
    and the holdout sizes, which the CLI's report does not carry."""
    import omldm_tpu.__main__ as cli

    jobs: list = []
    real = cli.build_job

    def build_job(flags):
        job, sinks = real(flags)
        jobs.append(job)
        return job, sinks

    cli.build_job = build_job
    try:
        yield jobs
    finally:
        cli.build_job = real


def run_cli(argv: List[str], perf_path: str):
    """``python -m omldm_tpu <argv>`` in this process. Returns the finished
    job, the final performance report, and the seconds ``main`` took
    (compilation included; the leg's own wall also counts writing the
    stream)."""
    from omldm_tpu.__main__ import main

    t0 = time.perf_counter()
    with _capture_job() as jobs:
        rc = main(argv + ["--performanceOut", perf_path])
    cli_s = time.perf_counter() - t0
    _require(rc == 0, f"CLI exit code {rc}")
    _require(len(jobs) == 1, "the CLI built exactly one job")
    with open(perf_path) as f:
        reports = [json.loads(line) for line in f if line.strip()]
    finals = [r for r in reports if "statistics" in r]
    _require(bool(finals), "the CLI wrote a final performance report")
    return jobs[0], finals[-1], round(cli_s, 1)


def _require_native_parser() -> None:
    from omldm_tpu.ops.native import fast_parser_available

    _require(
        fast_parser_available(),
        "the native parser is loaded (a Python-parser run is not the route "
        "under test)",
    )


def _check_spmd_run(run, rows: int, n_chips: int, batch: int,
                    launches: int, chance_loss: float) -> dict:
    """The checks stream_fused and stream_sparse share: rows conserved,
    enough full staged launches, final loss below ``chance_loss`` (the
    loss of the zero-weight model), state on every chip."""
    import jax

    job, report, cli_s = run
    [bridge] = job.spmd_bridges.values()
    _require(bridge.supports_fused_ingest(),
             "the bridge qualifies for the fused C ingest route")
    [stats] = report["statistics"]
    fitted = int(stats["fitted"])
    holdout = len(bridge.test_set)
    _require(
        fitted + holdout == rows,
        f"fitted {fitted} + holdout {holdout} == rows written {rows}",
    )
    full_launch = bridge.chain * bridge.dp * batch
    _require(bridge.dp == n_chips, f"mesh dp {bridge.dp} == chips {n_chips}")
    _require(
        fitted >= launches * full_launch,
        f"at least {launches} full staged launches per chip "
        f"({fitted} fitted, {full_launch} rows per launch)",
    )
    final_loss = float(stats["learningCurve"][-1])
    _require(
        math.isfinite(final_loss) and final_loss < chance_loss,
        f"final loss {final_loss} below the untrained {chance_loss:.4f}",
    )
    leaves = jax.tree_util.tree_leaves(bridge.trainer.state["params"])
    devices = sorted(
        {s.device.id for leaf in leaves for s in leaf.addressable_shards}
    )
    _require(
        len(devices) == n_chips,
        f"trained state has shards on {n_chips} distinct devices, "
        f"found {devices}",
    )
    return {
        "cli_s": cli_s,
        "rows": rows, "fitted": fitted, "holdout": holdout,
        "final_loss": round(final_loss, 4), "score": stats.get("score"),
        "state_devices": devices, "rows_per_launch": full_launch,
    }


# --- legs --------------------------------------------------------------------


def leg_stream_fused(workdir: str, n_chips: int, *, dim: int = 28,
                     batch: int = 4096, chain: int = 32,
                     launches: int = 3, test_set: int = 64) -> dict:
    """BASELINE configuration 1 at full width through ``python -m omldm_tpu
    --trainingData f.jsonl --requests r.jsonl``: Softmax with 2 classes,
    ``engine: spmd``, Synchronous, the fused C parse->holdout->stage loop
    feeding chained device steps."""
    _require_native_parser()
    # `launches` full stages per chip, the holdout, and a ragged tail
    rows = launches * chain * batch * n_chips + test_set + batch // 2
    data = os.path.join(workdir, "fused.jsonl")
    reqs = os.path.join(workdir, "fused_requests.jsonl")
    write_dense_stream(data, rows, dim, seed=0)
    write_requests(reqs, [{
        "id": 0,
        "request": "Create",
        "learner": {
            "name": "Softmax",
            "hyperParameters": {"learningRate": 0.05, "nClasses": 2},
            "dataStructure": {"nFeatures": dim},
        },
        "preProcessors": [],
        "trainingConfiguration": {
            "protocol": "Synchronous",
            "engine": "spmd",
            "extra": {"stageChain": chain},
        },
    }])
    run = run_cli([
        "--trainingData", data, "--requests", reqs,
        "--parallelism", str(n_chips), "--batchSize", str(batch),
        "--testSetSize", str(test_set),
    ], os.path.join(workdir, "fused_perf.jsonl"))
    # cross-entropy of the untrained 2-class model is ln 2
    return _check_spmd_run(run, rows, n_chips, batch, launches,
                           chance_loss=math.log(2.0))


def leg_stream_sparse(workdir: str, n_chips: int, *, n_num: int = 13,
                      n_cat: int = 26, hash_space: int = 1 << 18,
                      max_nnz: int = 40, batch: int = 4096,
                      launches: int = 24, test_set: int = 64) -> dict:
    """BASELINE configuration 3 at full width through the fused sparse CLI
    route: PA-II over 13 numeric + 26 categorical fields hashed into 2^18,
    which puts XLA's scatter at D = 13 + 2^18 on the chip. The sparse
    bridge stages one [dp, batch] group per launch (no chaining), so a
    launch is ``batch`` rows per chip."""
    _require_native_parser()
    rows = launches * batch * n_chips + test_set + batch // 2
    data = os.path.join(workdir, "sparse.jsonl")
    reqs = os.path.join(workdir, "sparse_requests.jsonl")
    write_sparse_stream(data, rows, n_num, n_cat, seed=0)
    write_requests(reqs, [{
        "id": 0,
        "request": "Create",
        "learner": {
            "name": "PA",
            "hyperParameters": {"C": 0.1, "variant": "PA-II"},
            "dataStructure": {
                "sparse": True, "nFeatures": n_num + hash_space,
                "hashSpace": hash_space, "maxNnz": max_nnz,
            },
        },
        "preProcessors": [],
        "trainingConfiguration": {
            "protocol": "Synchronous", "engine": "spmd", "syncEvery": 4,
        },
    }])
    run = run_cli([
        "--trainingData", data, "--requests", reqs,
        "--parallelism", str(n_chips), "--batchSize", str(batch),
        "--testSetSize", str(test_set),
    ], os.path.join(workdir, "sparse_perf.jsonl"))
    # hinge loss of the untrained (zero-weight) model is 1
    out = _check_spmd_run(run, rows, n_chips, batch, launches,
                          chance_loss=1.0)
    out["model_width"] = n_num + hash_space
    [bridge] = run[0].spmd_bridges.values()
    out.update(_check_state_view_is_free(bridge.trainer, batch, max_nnz))
    return out


# --- the static guard on the sparse step's state view ------------------------

# the opcodes a relayout of a vector leaf compiles to (PERF.md section 5,
# PR 26: a reduce over two unit axes, a constant fill, a one-trip while
# around a dynamic-slice, a copy). Moving a small vector into faster memory
# (copy-start, slice-start), which the compiler chooses at this leg's 1 MB,
# is not among them.
_HLO_PASSES = ("reduce", "broadcast", "copy", "while", "dynamic-slice")


def hlo_computations(text: str) -> Dict[str, List[Tuple[str, str, str, str]]]:
    """Compiled HLO text -> {computation: [(name, opcode, shape, line)]}.
    The entry computation is filed under ``"ENTRY"`` as well."""
    head = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
    inst = re.compile(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\("
    )
    comps: Dict[str, List[Tuple[str, str, str, str]]] = {}
    cur = None
    for line in text.splitlines():
        m = head.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            if m.group(1):
                comps["ENTRY"] = cur
            continue
        m = inst.match(line)
        if m and cur is not None:
            cur.append((m.group(1), m.group(3), m.group(2), line))
    return comps


def _hlo_wide(shape: str, n: int) -> bool:
    return any(
        math.prod(int(d) for d in dims.split(",") if d) >= n
        for dims in re.findall(r"[a-z]+\d+\[([\d,]*)\]", shape)
    )


def _hlo_called(line: str) -> List[str]:
    names: List[str] = []
    for group in re.findall(
        r"(?:calls|to_apply|body|condition|true_computation|"
        r"false_computation|branch_computations)=(\{[^}]*\}|[^\s,]+)",
        line,
    ):
        names += [
            g.strip().lstrip("%") for g in group.strip("{}").split(",")
        ]
    return names


def hlo_wide_passes(text: str, n: int,
                    every_branch: bool = False) -> List[Tuple[str, str, str]]:
    """The instructions of a compiled program whose result holds ``n`` or
    more elements and that are a relayout's (``_HLO_PASSES``) or a fusion
    that does not write in place, outside the branch a conditional takes
    when its predicate holds (the protocol's sync): (computation, name,
    opcode). A fusion writes in place when it holds the scatter, or when its
    root is a ``dynamic-update-slice`` (on a TPU the plan's final add is the
    kernel ``sparse_tile_scatter_add``, a ``tpu_custom_call`` that no rule
    here names, and such a slice for the vector's partial last tile: PR 39).
    The false branch, which every step runs, is walked; with
    ``every_branch`` the other is too (on one chip the sync moves nothing
    either: it returns the donated vector)."""
    comps = hlo_computations(text)
    found: List[Tuple[str, str, str]] = []
    seen = set()

    def holds_scatter(comp: str) -> bool:
        return any(
            op == "scatter" or any(holds_scatter(c) for c in _hlo_called(line))
            for _, op, _, line in comps.get(comp, ())
        )

    def in_place(comp: str) -> bool:
        return holds_scatter(comp) or any(
            op == "dynamic-update-slice" and line.lstrip().startswith("ROOT ")
            for _, op, _, line in comps.get(comp, ())
        )

    def walk(comp: str) -> None:
        if comp in seen:
            return
        seen.add(comp)
        for name, op, shape, line in comps.get(comp, ()):
            called = _hlo_called(line)
            if op == "conditional":
                m = re.search(r"false_computation=%?([\w.\-]+)", line)
                # index form: branch 0 is the one taken when the predicate
                # is false
                branches = called if every_branch else [
                    m.group(1) if m else called[0]
                ]
                for c in branches:
                    walk(c)
                continue
            if op == "fusion":
                if _hlo_wide(shape, n) and not any(
                    in_place(c) for c in called
                ):
                    found.append((comp, name, op))
                continue
            for c in called:
                walk(c)
            if op in _HLO_PASSES and _hlo_wide(shape, n):
                found.append((comp, name, op))

    walk("ENTRY")
    return found


def hlo_aliased_parameters(text: str) -> List[int]:
    """Parameter numbers the module's ``input_output_alias`` donates."""
    head = next(
        (l for l in text.splitlines() if "input_output_alias=" in l), ""
    )
    return sorted(
        int(k) for k in re.findall(r"\((\d+), \{\}, \w+-alias\)", head)
    )


def _check_state_view_is_free(trainer, batch: int, max_nnz: int) -> dict:
    """From the compiled step and predict programs themselves: entering the
    step, leaving it and serving from it move no vector leaf. Outside the
    sync branch the only pass over the model's width is the scatter (on one
    chip inside it too: the sync returns the donated vector), every vector
    leaf the state holds is donated and updated in place, and the predict
    program makes no such pass at all."""
    import jax
    import jax.numpy as jnp

    n = trainer.flat_size
    dp = trainer.dp
    shapes = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=l.sharding),
        trainer.state,
    )
    x = (
        jax.ShapeDtypeStruct((dp, batch, max_nnz), jnp.int32),
        jax.ShapeDtypeStruct((dp, batch, max_nnz), jnp.float32),
    )
    y = jax.ShapeDtypeStruct((dp, batch), jnp.float32)
    step_text = trainer._step.lower(shapes, x, y, y).compile().as_text()
    one_chip = dp * trainer.hub == 1
    passes = hlo_wide_passes(step_text, n, every_branch=one_chip)
    _require(
        not passes,
        f"{'in every branch' if one_chip else 'outside the sync branch'} no "
        f"op of the step but the scatter has a result of {n} or more "
        f"elements, found {passes}",
    )
    entry = hlo_computations(step_text)["ENTRY"]
    vectors = sorted(
        int(line.split("parameter(")[1].split(")")[0])
        for _, op, shape, line in entry
        if op == "parameter" and _hlo_wide(shape, n)
    )
    aliased = hlo_aliased_parameters(step_text)
    held = [
        l for l in jax.tree_util.tree_leaves(trainer.state) if l.ndim == 1
    ]
    _require(
        len(vectors) == len(held) and set(vectors) <= set(aliased),
        f"the state's {len(held)} vector leaves (parameters {vectors}) are "
        f"aliased input to output, aliased are {aliased}",
    )
    predict_fn, _ = trainer._serve_fns()
    xp = tuple(jax.ShapeDtypeStruct((16, max_nnz), a.dtype) for a in x)
    passes = hlo_wide_passes(
        predict_fn.lower(shapes, xp).compile().as_text(), n
    )
    _require(
        not passes,
        f"the predict program has no result of {n} or more elements, found "
        f"{passes}",
    )
    return {"wide_passes": 0, "vector_leaves_aliased": len(vectors)}


def leg_stream_mixed(workdir: str, n_chips: int, *, tenants: int = 8,
                     dim: int = 28, blocks: int = 24,
                     block_rows: int = 8192, forecasts: int = 2048,
                     batch: int = 256) -> dict:
    """The route every plane lives on: ``tenants`` same-spec host-plane
    pipelines (``cohort: auto`` gangs them into one stacked launch), a
    training file through the packed block route, a forecasting file, and
    ``--predictionsOut``.

    The CLI interleaves its sources round-robin, one event each: a packed
    block of ``block_rows`` training rows, one forecast row, one request.
    So tenant k is created in round k and misses what arrived before it;
    tenant 0 is deployed from the pre-Create backlog and misses nothing."""
    import jax

    _require_native_parser()
    rows = blocks * block_rows
    data = os.path.join(workdir, "mixed.jsonl")
    fore = os.path.join(workdir, "mixed_forecast.jsonl")
    reqs = os.path.join(workdir, "mixed_requests.jsonl")
    preds = os.path.join(workdir, "mixed_predictions.jsonl")
    write_dense_stream(data, rows, dim, seed=1)
    write_forecast_stream(fore, forecasts, dim, seed=2)
    write_requests(reqs, [{
        "id": k,
        "request": "Create",
        "learner": {
            "name": "Softmax",
            "hyperParameters": {"learningRate": 0.05, "nClasses": 2},
            "dataStructure": {"nFeatures": dim},
        },
        "preProcessors": [],
        "trainingConfiguration": {"protocol": "Synchronous"},
    } for k in range(tenants)])
    job, report, cli_s = run_cli([
        "--trainingData", data, "--forecastingData", fore, "--requests", reqs,
        "--parallelism", str(n_chips), "--batchSize", str(batch),
        "--ingestBatch", str(block_rows), "--predictionsOut", preds,
    ], os.path.join(workdir, "mixed_perf.jsonl"))

    stats = {int(s["pipeline"]): s for s in report["statistics"]}
    _require(sorted(stats) == list(range(tenants)),
             f"a report for each of {tenants} tenants")
    # rows conserved per tenant: every row a tenant's nets were offered is
    # either fitted or in its holdout, and tenant 0 was offered all of them
    for k in range(tenants):
        nets = [spoke.nets[k] for spoke in job.spokes]
        offered = sum(net.holdout_count for net in nets)
        held = sum(len(net.test_set) for net in nets)
        fitted = int(stats[k]["fitted"])
        _require(
            fitted + held == offered,
            f"tenant {k}: fitted {fitted} + holdout {held} == "
            f"rows offered {offered}",
        )
        _require(int(stats[k]["programLaunches"]) > 0,
                 f"tenant {k}: programLaunches above 0")
    _require(
        sum(net.holdout_count for net in
            (spoke.nets[0] for spoke in job.spokes)) == rows,
        f"tenant 0 was offered all {rows} rows written",
    )
    # one prediction per forecast row per tenant, for every row that
    # arrived after the last Create (well past round `tenants`)
    served: Dict[int, List[int]] = {}
    with open(preds) as f:
        for line in f:
            p = json.loads(line)
            row = int(p["dataInstance"]["numericalFeatures"][0])
            served.setdefault(row, []).append(int(p["mlpId"]))
    settled = range(2 * tenants, forecasts)
    for row in settled:
        _require(
            sorted(served.get(row, [])) == list(range(tenants)),
            f"forecast row {row}: one prediction from each tenant, got "
            f"{sorted(served.get(row, []))}",
        )
    _require(
        all(len(ids) == len(set(ids)) for ids in served.values()),
        "no forecast row was answered twice by one tenant",
    )
    engines = [s.cohorts for s in job.spokes if s.cohorts is not None]
    _require(
        bool(engines) and all(e.cohorts for e in engines),
        "cohort: auto ganged the same-spec tenants on every spoke",
    )
    # where the host plane's state lives: the cohorts' stacked trees, and
    # the trees of any pipeline that stayed detached
    trees = [c.stacked for e in engines for c in e.cohorts.values()]
    trees += [
        net.pipeline.state for spoke in job.spokes
        for net in spoke.nets.values() if net.pipeline._cohort is None
    ]
    state_devices = sorted({
        d.id for leaf in jax.tree_util.tree_leaves(trees)
        if isinstance(leaf, jax.Array) for d in leaf.devices()
    })
    return {
        "cli_s": cli_s,
        "rows": rows,
        "fitted": [int(stats[k]["fitted"]) for k in range(tenants)],
        "predictions": sum(len(v) for v in served.values()),
        "programLaunches": [
            int(stats[k]["programLaunches"]) for k in range(tenants)
        ],
        "cohort_vmap": engines[0].use_vmap,
        "state_devices": state_devices,
    }


def _kernel_pa_pipeline(*, dim: int, batch: int, n_batches: int) -> dict:
    """A host-plane PA pipeline with ``perRecord: true`` twice on one
    stream: as it dispatches by itself (the Pallas scan on a TPU, the same
    kernel interpreted elsewhere) and with ``usePallas: false`` (lax.scan).
    """
    import jax

    from omldm_tpu.api.requests import LearnerSpec
    from omldm_tpu.pipelines import MLPipeline

    on_tpu = jax.devices()[0].platform == "tpu"
    hp = {"C": 0.5, "variant": "PA-I"}
    # off the chip the default is the lax scan; force the (interpreted)
    # kernel there so the comparison still has a kernel on one side
    kernel_hp = hp if on_tpu else {**hp, "usePallas": True}
    kernel = MLPipeline(LearnerSpec("PA", hyper_parameters=kernel_hp),
                        dim=dim, per_record=True)
    plain = MLPipeline(LearnerSpec("PA", hyper_parameters={**hp, "usePallas": False}),
                       dim=dim, per_record=True)
    rng = np.random.RandomState(3)
    w = rng.randn(dim)
    mask = np.ones(batch, np.float32)
    x0 = rng.randn(batch, dim).astype(np.float32)
    lowered = kernel._fit.lower(
        kernel.state, x0, (x0 @ w > 0).astype(np.float32), mask
    ).as_text()
    compiled = "tpu_custom_call" in lowered
    _require(
        compiled == on_tpu,
        f"the PA scan lowers to a compiled Mosaic kernel on a TPU "
        f"(interpret=False): custom call present={compiled}, tpu={on_tpu}",
    )
    for i in range(n_batches):
        x = x0 if i == 0 else rng.randn(batch, dim).astype(np.float32)
        y = (x @ w > 0).astype(np.float32)
        kernel.fit(x, y, mask)
        plain.fit(x, y, mask)
    wk = kernel.get_flat_params()[0]
    wp = plain.get_flat_params()[0]
    err = float(np.max(np.abs(wk - wp)))
    _require(np.isfinite(wk).all() and err < 1e-4,
             f"PA kernel weights agree with lax.scan to 1e-4 (err {err})")
    return {"pa_pipeline_w_err": err, "pa_kernel_compiled": compiled}


def _kernel_lm_steps(n_chips: int, *, vocab: int, d_model: int, n_heads: int,
                     n_layers: int, d_ff: int, seq_len: int, batch: int,
                     steps: int, bf16: bool) -> dict:
    """``SeqTrainer.step`` on the widest LM the repository runs; mesh
    (1,1,1) on one chip and (1,2,2) on four."""
    import jax.numpy as jnp

    from omldm_tpu.models.transformer import TransformerConfig
    from omldm_tpu.parallel.seq_trainer import SeqTrainer, make_seq_mesh

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_len=seq_len,
        dtype=jnp.bfloat16 if bf16 else jnp.float32,
        loss_chunk=min(1024, seq_len),
    )
    mesh_shape = (1, 2, 2) if n_chips >= 4 else (1, 1, 1)
    trainer = SeqTrainer(cfg, mesh=make_seq_mesh(*mesh_shape), lr=1e-3)
    rng = np.random.RandomState(4)
    tokens = rng.randint(0, vocab, size=(batch, seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    losses = [
        float(np.asarray(trainer.step(tokens, targets))) for _ in range(steps)
    ]
    _require(all(math.isfinite(v) for v in losses), f"LM losses finite {losses}")
    _require(losses[-1] < losses[0], f"LM loss falling {losses}")
    return {"lm_mesh": list(mesh_shape),
            "lm_losses": [round(v, 4) for v in losses]}


def _kernel_numerics(*, seq_len: int, long_len: int, heads: int, dh: int,
                     pa_dim: int, pa_batch: int) -> dict:
    """Flash forward (plain, causal, chunked-query offsets, long context),
    the ``attention`` entry, the backward kernel against
    ``mha_reference`` autodiff, and the PA scan against the exact numpy
    recurrence. On a TPU the kernels are compiled; elsewhere interpreted."""
    import jax
    import jax.numpy as jnp

    from omldm_tpu.ops.attention import (
        _flash_diff, attention, flash_attention_pallas, mha_reference,
    )
    from omldm_tpu.ops.pa_scan import pa_scan_update

    interpret = jax.devices()[0].platform != "tpu"
    out = {}
    rng = np.random.RandomState(0)
    shape = (2, seq_len, heads, dh)
    q, k, v = (jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)
               for _ in range(3))

    # jitted, so each reference is one program and not one per eager op
    reference = jax.jit(mha_reference, static_argnames=("causal", "q_offset"))

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a - b)))

    for causal in (False, True):
        out[f"flash_err_causal_{causal}"] = max_err(
            flash_attention_pallas(q, k, v, causal=causal, interpret=interpret),
            reference(q, k, v, causal=causal),
        )
    # chunked-query offsets (the ring/Ulysses entry pattern)
    lo, hi = seq_len // 4, seq_len // 2
    out["flash_err_offset"] = max_err(
        flash_attention_pallas(q[:, lo:hi], k, v, causal=True, q_offset=lo,
                               interpret=interpret),
        reference(q[:, lo:hi], k, v, causal=True, q_offset=lo),
    )
    # long context: K/V far beyond what one program could stage in VMEM
    ql, kl, vl = (
        jnp.asarray(rng.randn(1, long_len, 1, dh).astype(np.float32) * 0.1)
        for _ in range(3)
    )
    out["longctx_finite"] = bool(jnp.isfinite(
        flash_attention_pallas(ql, kl, vl, causal=True, interpret=interpret)
    ).all())

    # the entry point (Pallas by default on a TPU), forward and backward;
    # off the chip the same custom-VJP kernels run interpreted
    def fn(a, b, c):
        if interpret:
            return _flash_diff(a, b, c, True, 0, 0, True)
        return attention(a, b, c, causal=True)

    out["entry_err"] = max_err(
        jax.jit(fn)(q, k, v), reference(q, k, v, causal=True)
    )

    def grads(f):
        return jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(f(a, b, c) ** 2), argnums=(0, 1, 2)
        ))

    gp = grads(fn)(q, k, v)
    gr = grads(lambda a, b, c: mha_reference(a, b, c, causal=True))(q, k, v)
    out["bwd_err"] = max(max_err(a, b) for a, b in zip(gp, gr))

    # pa_scan against the exact numpy recurrence
    w0 = np.zeros(pa_dim, np.float32)
    x = rng.randn(pa_batch, pa_dim).astype(np.float32)
    y = (x @ rng.randn(pa_dim) > 0).astype(np.float32)
    new_w, pa_loss = pa_scan_update(
        jnp.asarray(w0), jnp.asarray(x), jnp.asarray(y),
        jnp.ones(pa_batch, jnp.float32), variant="PA-I", C=0.5,
        interpret=interpret,
    )
    w = w0.copy()
    hinge_sum = 0.0
    for i in range(pa_batch):
        ys = 1.0 if y[i] > 0 else -1.0
        hinge = max(0.0, 1.0 - ys * float(w @ x[i]))
        tau = min(0.5, hinge / max(float(x[i] @ x[i]), 1e-12))
        w = w + tau * ys * x[i]
        hinge_sum += hinge
    out["pa_w_err"] = float(np.max(np.abs(np.asarray(new_w) - w)))
    out["pa_loss_err"] = abs(float(pa_loss) - hinge_sum / pa_batch)
    # the same kernel under vmap, as the cohort engine gangs per-record PA
    # tenants off the CPU: eight members in one launch against eight solos
    members = 8
    xs = jnp.asarray(rng.randn(members, pa_batch, pa_dim).astype(np.float32))
    ys = (xs @ jnp.asarray(rng.randn(pa_dim).astype(np.float32)) > 0)
    ys = ys.astype(jnp.float32)
    ws = jnp.zeros((members, pa_dim), jnp.float32)
    ms = jnp.ones((members, pa_batch), jnp.float32)

    def one(w_, x_, y_, m_):
        return pa_scan_update(w_, x_, y_, m_, variant="PA-I", C=0.5,
                              interpret=interpret)[0]

    ganged = jax.vmap(one)(ws, xs, ys, ms)
    solos = jnp.stack([one(ws[i], xs[i], ys[i], ms[i]) for i in range(members)])
    out["pa_vmap_err"] = max_err(ganged, solos)

    # the QK^T dot rides the MXU at default (bf16-pass) precision
    for key in ("flash_err_causal_False", "flash_err_causal_True",
                "flash_err_offset", "entry_err"):
        _require(out[key] < 5e-3, f"{key} {out[key]} < 5e-3")
    _require(out["longctx_finite"], "long-context flash output finite")
    _require(out["bwd_err"] < 2e-2, f"bwd_err {out['bwd_err']} < 2e-2")
    _require(out["pa_w_err"] < 1e-4, f"pa_w_err {out['pa_w_err']} < 1e-4")
    _require(out["pa_loss_err"] < 1e-4,
             f"pa_loss_err {out['pa_loss_err']} < 1e-4")
    _require(out["pa_vmap_err"] < 1e-6,
             f"pa_vmap_err {out['pa_vmap_err']} < 1e-6")
    return out


# the kernels leg's full sizes: the host plane's default batch; the widest
# LM the repository runs (benchmarks/run_benchmarks.py:_longctx_bench at
# L=4096); the shapes of the kernels' numeric checks
PA_FULL = dict(dim=28, batch=256, n_batches=8)
LM_FULL = dict(vocab=8192, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
               seq_len=4096, batch=2, steps=3, bf16=True)
NUMERICS_FULL = dict(seq_len=1024, long_len=32768, heads=4, dh=64,
                     pa_dim=29, pa_batch=512)


def leg_kernels(n_chips: int, *, pa=PA_FULL, lm=LM_FULL,
                numerics=NUMERICS_FULL) -> dict:
    """What is on by default on a TPU and has no CLI entry yet: the
    per-record PA scan, the LM step through the flash kernels, and the
    kernels' numeric checks."""
    return {
        **_kernel_pa_pipeline(**pa),
        **_kernel_lm_steps(n_chips, **lm),
        **_kernel_numerics(**numerics),
    }


# --- entry -------------------------------------------------------------------


def main() -> int:
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    platform, kind, count = devices[0].platform, devices[0].device_kind, len(devices)
    if platform != "tpu":
        print(
            f"chip_smoke.py needs a TPU; jax found platform={platform!r} "
            f"device_kind={kind!r} count={count}. No leg ran.",
            file=sys.stderr,
        )
        return 1
    # away from the repository this import fails, before anything is printed
    from omldm_tpu.utils.compile_cache import enable_compile_cache

    print(f"platform: {platform}  device_kind: {kind}  count: {count}",
          flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    n_chips = jax.local_device_count()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        legs = (
            ("stream_fused", lambda: leg_stream_fused(workdir, n_chips)),
            ("stream_mixed", lambda: leg_stream_mixed(workdir, n_chips)),
            ("stream_sparse", lambda: leg_stream_sparse(workdir, n_chips)),
            ("kernels", lambda: leg_kernels(n_chips)),
        )
        for name, leg in legs:
            t0 = time.perf_counter()
            s0, h0, m0 = clock.snapshot()
            n0 = len(clock.compiles)
            detail = leg()
            s1, h1, m1 = clock.snapshot()
            slowest = sorted(clock.compiles[n0:], reverse=True)[:3]
            print(json.dumps({
                "leg": name, "ok": True,
                "wall_s": round(time.perf_counter() - t0, 1),
                "compile_s": round(s1 - s0, 1),
                "cache_hits": h1 - h0, "cache_misses": m1 - m0,
                "slowest_compiles": [[n, round(s, 1)] for s, n in slowest],
                **detail,
            }), flush=True)
    seconds, hits, misses = clock.snapshot()
    print(
        f"all legs ok: wall {time.perf_counter() - t_start:.1f}s, "
        f"compile {seconds:.1f}s, cache hits {hits}, misses {misses}",
        flush=True,
    )
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": kind, "count": count},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
