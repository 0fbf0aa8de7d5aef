"""Multi-process streaming deployment: N ingest partitions, one global mesh.

Reference counterpart: the Flink job runs N parallel subtasks across a
cluster, fed by partitioned Kafka topics (reference: README.md:21-29,
parallelism 16 at src/main/scala/omldm/utils/DefaultJobParameters.scala:5),
and EVERY feature of the framework works in that deployment: many
concurrent pipelines (SpokeLogic.scala:28-29 keeps a Map[Int, wrapper] per
subtask), the full Create/Update/Query/Delete control plane
(PipelineMap.scala:37-57 broadcast to all workers), and checkpoint/restore
of operator state (FlinkSpoke.scala:233-334). The TPU-native deployment is
one PYTHON PROCESS per host, joined through ``jax.distributed``:

- each process owns an ingest partition (a strided slice of a shared file,
  or an assigned set of Kafka partitions — the role of Flink's per-subtask
  Kafka partition assignment, KafkaUtils.scala:11-31) and stages rows for
  its own mesh shard;
- each batch is assembled into ONE globally-sharded array with
  ``host_local_array`` and trained by the standard :class:`SPMDTrainer`
  step — protocol sync is the same XLA collective whether the workers
  share a host or not (ICI within a slice, DCN across);
- the CONTROL PLANE lives on process 0: request lines are broadcast to
  every process over the collective fabric itself (a padded uint8 array,
  replicated-out jit) — control messages ride the same links as training
  traffic, no side channel. Every process hosts the same pipeline map
  (keyed by networkId, the multi-process form of SpokeLogic.scala:28-29);
  Create/Update deploy, Delete tears down, Query answers COLLECTIVELY
  (the union-holdout eval and the worker-0 parameter gather are lockstep
  programs) and process 0 emits the bucketed QueryResponse;
- statistics merge with psum-style reductions into the reference's
  JobStatistics schema (StatisticsOperator.scala:110-127) and process 0
  emits the report;
- checkpoints snapshot the SHARED fleet state once (gathered collectively,
  written by process 0) plus each process's partition cursor and local
  buffers, at synchronized pump points — restore resumes every process
  from the same consistent cut (the role of Flink's checkpoint barriers +
  FlinkSpoke.scala:233-334 operator state).

Single-process every piece degrades to local behavior, so the same code
runs a laptop test and a pod deployment. CLI (ParameterTool-style flags,
shared with ``python -m omldm_tpu``):

    python -m omldm_tpu \
        --coordinator 127.0.0.1:9876 --processes 2 --processId 0 \
        --requests reqs.jsonl --trainingData train.jsonl \
        --performanceOut perf.jsonl
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from omldm_tpu.api.requests import Request, RequestType
from omldm_tpu.api.responses import QueryResponse
from omldm_tpu.api.stats import JobStatistics, Statistics
from omldm_tpu.config import JobConfig
from omldm_tpu.runtime.control import PipelineManager
from omldm_tpu.runtime.databuffers import ArrayHoldout
from omldm_tpu.runtime.responses import ResponseMerger

CONTROL_CAP = 1 << 16  # fixed broadcast buffer: 64 KiB of request lines

# rows read from the source between synchronized pump points
CHUNK_ROWS = 4096


# --- elastic rescale-restore helpers (pure, unit-tested) -------------------


def rescale_shard_map(old_n: int, new_n: int, pid: int) -> List[int]:
    """Old-process checkpoint shards owned by NEW process ``pid`` when an
    ``old_n``-process snapshot restores across ``new_n`` processes: old
    shard q merges into survivor ``q % new_n`` — the distributed twin of
    the in-process shrink's ``id % n_new`` merge (StreamJob.rescale).
    Under grow this degenerates to identity for ``pid < old_n`` and the
    empty list for the seeded new processes; at ``old_n == new_n`` it is
    exactly ``[pid]`` (the pre-rescale restore path)."""
    return [q for q in range(old_n) if q % new_n == pid]


def _interleave_perm(lengths: Sequence[int]) -> List[int]:
    """Flat row indices that round-robin across blocks of the given
    lengths (block rows are laid out back to back): [b0[0], b1[0], ...,
    b0[1], b1[1], ...]. Merged per-process stripes stay a fair stream-
    order mix — the holdout/pending interleave of the in-process
    ``Spoke.absorb`` (SpokeLogic.scala:37-50 semantics)."""
    offsets = np.cumsum([0] + list(lengths))
    perm: List[int] = []
    for j in range(max(lengths, default=0)):
        for i, n in enumerate(lengths):
            if j < n:
                perm.append(int(offsets[i]) + j)
    return perm


def _interleave_rows(blocks: List[np.ndarray]) -> np.ndarray:
    """Round-robin row interleave of [n_i, ...] arrays (see
    :func:`_interleave_perm`)."""
    cat = np.concatenate(blocks)
    return cat[_interleave_perm([b.shape[0] for b in blocks])]


def _saved_leaf_positions(
    state: Any, protocol: str, n_saved: int, source: str
) -> List[int]:
    """Where each leaf of the live fleet ``state`` (``tree_leaves`` order)
    sits in the fleet file ``source``, which holds ``n_saved`` leaves and
    names them by that order alone. The file of a pipeline under a protocol
    that no longer keeps ``est`` or ``center`` (``spmd.READ_UNDER``) may date
    from when it did: it then holds one leaf more for each, the last the
    state let go first, and the positions they had there are skipped. Any
    other difference in count is refused: restored by index, every later
    leaf would land on the wrong one."""
    import jax

    from omldm_tpu.parallel.spmd import unread_leaves

    def keys(tree):
        return [
            str(getattr(path[0], "key", path[0]))
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
        ]

    live = keys(state)
    if n_saved == len(live):
        return list(range(n_saved))
    unread = unread_leaves(protocol)
    extra = n_saved - len(live)
    if 0 < extra <= len(unread):
        # ``est`` went first: a file with fewer extras than the protocol
        # leaves unread lacks the earlier ones
        gone = unread[len(unread) - extra:]
        then = keys({**state, **{k: 0 for k in gone}})
        return [i for i, k in enumerate(then) if k not in gone]
    raise ValueError(
        f"{source} holds {n_saved} leaves, the state of a {protocol} pipeline "
        f"{len(live)} ({', '.join(live)}): it is not a snapshot of this "
        "pipeline"
    )


def _rescale_fleet_leaf(full: np.ndarray, key: str, dp_new: int) -> np.ndarray:
    """Redistribute one gathered fleet-state leaf (leading axis = the
    global dp worker rows) across a NEW worker-row count:

    - grow: new rows seed from the fleet model — a copy of worker row 0
      (the replica queries/evals read), exactly the in-process grow's
      seed-from-spoke-0; per-row accumulators that must not inflate the
      fleet totals (EF residuals, cum_loss) seed at zero instead;
    - shrink: retired row q merges into survivor ``q % dp_new`` — model
      state (params/preps) merges by group MEAN (rows are fed round-robin
      stripes, so equal weight is the faithful merge; the next protocol
      round would average them anyway), fleet-total accumulators
      (cum_loss) by group SUM, codec EF residuals reset (the model they
      were computed against is gone — the reset_streams analogue), and
      round-accounting counters (step/syncs/clock/accepted/est/...) keep
      the SURVIVOR row's own values so every surviving worker stays on
      the round schedule it checkpointed at."""
    dp_old = full.shape[0]
    if dp_new == dp_old:
        return full
    if dp_new > dp_old:
        if key in ("ef", "cum_loss"):
            extra = np.zeros((dp_new - dp_old,) + full.shape[1:], full.dtype)
        else:
            extra = np.repeat(full[:1], dp_new - dp_old, axis=0)
        return np.concatenate([full, extra], axis=0)
    if key in ("params", "preps"):
        return np.stack(
            [
                full[w::dp_new].mean(axis=0).astype(full.dtype)
                for w in range(dp_new)
            ]
        )
    if key == "cum_loss":
        return np.stack(
            [
                full[w::dp_new].sum(axis=0).astype(full.dtype)
                for w in range(dp_new)
            ]
        )
    if key == "ef":
        return np.zeros((dp_new,) + full.shape[1:], full.dtype)
    return full[:dp_new]


def _merge_cursors(cursors: List[Any]) -> Any:
    """One process's resume cursor from the per-process cursors of an
    N-process snapshot. Kafka cursors (``{"data": {...}, "requests":
    {...}}``) UNION across processes — the new partition stripe scatters
    old assignments across every new process, so each one needs the full
    per-partition offset map (max wins where a stale superset entry
    collides with the owner's newer value). File cursors (row ints /
    ``{"bytes", "lines"}`` dicts) are fleet-global and identical at a
    synchronized pump point, so the first shard speaks for everyone."""
    cursors = [c for c in cursors if c is not None]
    if not cursors:
        return None
    head = cursors[0]
    if isinstance(head, dict) and "data" in head:
        data: Dict[str, int] = {}
        requests: Dict[str, int] = {}
        for c in cursors:
            for k, v in (c.get("data") or {}).items():
                data[k] = max(int(v), data.get(k, 0))
            for k, v in (c.get("requests") or {}).items():
                requests[k] = max(int(v), requests.get(k, 0))
        return {"data": data, "requests": requests}
    return head


def _mesh_and_procs(coordinator, num_processes, process_id):
    """Join the process group (if any) and build the global dp mesh."""
    import jax

    from omldm_tpu.parallel.multihost import initialize_multihost

    pid, nproc = initialize_multihost(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    from omldm_tpu.parallel.mesh import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh(dp=n_dev, hub=1)
    return mesh, pid, nproc


def _atomic_write_bytes(path: str, data: bytes) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _atomic_write_json(path: str, obj: Any) -> None:
    _atomic_write_bytes(path, json.dumps(obj).encode("utf-8"))


def _atomic_savez(path: str, arrays: Dict[str, np.ndarray]) -> str:
    import hashlib
    import io

    buf = io.BytesIO()
    np.savez(buf, **arrays)
    data = buf.getvalue()
    # through the fsync'd writer: the checkpoint barrier orders the
    # LATEST flip after these writes, but durability needs the fsync.
    # The sha256 of the bytes-as-written is returned so the snapshot
    # metadata can pin every file's content — restore verifies the
    # digests before trusting (or even loading) a generation.
    _atomic_write_bytes(path, data)
    return hashlib.sha256(data).hexdigest()


def _file_sha256(path: str) -> str:
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


class _DistPipeline:
    """One pipeline's state on THIS process — the per-subtask wrapper map
    entry (SpokeLogic.scala:28-29): the shared SPMD trainer plus this
    partition's holdout split, pending/forecast buffers and predictions."""

    def __init__(self, request: Request, raw_line: str, dim: int,
                 trainer, test_cap: int, stage_cap: int,
                 sparse: bool = False, max_nnz: int = 0):
        self.request = request
        self.raw_line = raw_line  # original JSON, for checkpoint manifests
        self.dim = dim
        self.trainer = trainer
        self.stage_cap = stage_cap
        # sparse (padded-COO) pipelines buffer (idx, val) row pairs — the
        # reference's SparseVector data model works in its cluster
        # deployment too (DataPointParser.scala:4,20-47)
        self.sparse = sparse
        self.max_nnz = max_nnz
        if sparse:
            from omldm_tpu.runtime.databuffers import SparseHoldout

            self.test_set = SparseHoldout(test_cap, max_nnz)
        else:
            self.test_set = ArrayHoldout(test_cap, dim)
        self.holdout_count = 0
        self.pend_x: List[np.ndarray] = []   # dense rows, or COO idx
        self.pend_v: List[np.ndarray] = []   # COO val (sparse only)
        self.pend_y: List[np.ndarray] = []
        self.pend_n = 0
        self.fore_x: List[np.ndarray] = []   # dense rows, or COO idx
        self.fore_v: List[np.ndarray] = []   # COO val (sparse only)
        self.fore_n = 0
        self.predictions: List[float] = []
        self.steps_run = 0
        # pump-granularity learning curve: (global mean loss of the pump's
        # last step, cumulative GLOBAL rows staged) — the distributed form
        # of the PS's incremental curve slices (FlinkHub.scala:101-116)
        self.curve: List[Tuple[float, int]] = []
        self.global_rows = 0
        # cached per-pipeline jitted collective programs
        self._eval_jit = None
        self._predict_jit = None
        self._accepted_jit = None
        self._gather_params_jit = None
        self._gather_state_jit = None
        self._counters_jit = None


class DistributedStreamJob:
    """Streaming pipelines trained across every process's devices.

    The training contract mirrors the in-process SPMD bridge: 8-of-10
    holdout split per partition (FlinkSpoke.scala:94-104 semantics, applied
    to the partition the way each Flink subtask applies it to its own
    split), staged [local_dp, B, D] micro-batches, one collective step per
    full stage across ALL processes in lockstep. Every collective-bearing
    method must be called at synchronized points with identical arguments
    on every process (request lines are broadcast to guarantee this)."""

    def __init__(
        self,
        config: JobConfig,
        coordinator: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
    ):
        import jax

        self.config = config
        self.mesh, self.pid, self.nproc = _mesh_and_procs(
            coordinator, num_processes, process_id
        )
        self._jax = jax
        self.dp_global = self.mesh.shape["dp"]
        self.dp_local = max(self.dp_global // self.nproc, 1)
        self.pipeline_manager = PipelineManager()
        self.pipelines: Dict[int, _DistPipeline] = {}
        self.dim: Optional[int] = None  # stream width, set by first deploy
        self.hash_dims = 0  # trailing hashed-categorical slots within dim
        self.stream_mode: Optional[str] = None  # "dense"|"sparse", pinned
        self.sparse_hash_space = 0  # COO hashed tail width (sparse mode)
        self.responses: List[QueryResponse] = []
        self.response_merger = ResponseMerger(self.responses.append)
        self.orphan_predictions: List[Tuple[int, float]] = []
        # liveness callback invoked mid-deploy: a fleet-scale Create
        # wave (or a restore redeploying it) constructs pipelines for
        # far longer than a heartbeat window, and a worker that is
        # provably alive must not read as beat-silent
        self.beat_hook: Optional[Callable[[], None]] = None
        # per-pipeline collective programs shared across pipelines whose
        # trainers agree on the full static signature — one compiled
        # executable per CONFIG, not per pipeline (the fleet-scale mmap
        # budget; parallel.spmd shares the step programs the same way)
        self._prog_cache: Dict[tuple, Any] = {}
        self.start_time = time.time()
        # overload control (runtime/overload.py; --overload / JobConfig):
        # on the distributed engine the honest backlog signal is the
        # host-side staging (pending/forecast buffers + SSP-requeued
        # rows), and the action is SOURCE BACKPRESSURE — _drive_kafka
        # pauses this process's data partitions while the backlog is past
        # backlogCritical. None (default) = unarmed, zero-cost.
        from omldm_tpu.runtime.overload import parse_overload_spec

        self.overload_cfg = parse_overload_spec(
            getattr(config, "overload", "") or ""
        )
        # pressure PEAK since the last heartbeat tick: the drive loops pump
        # (drain) right before each tick, so the instantaneous level at
        # tick time would always read OK — the peak over the window is the
        # honest signal the autoscaling supervisor consumes (updated by
        # the row-buffering paths, zero-cost unarmed)
        self._level_window = 0
        # elastic rescale-restore (restore-with-rescale): a snapshot taken
        # with N processes may restore across M != N (fleet rows merged/
        # seeded, shards remapped, source stripe re-agreed). Disabled via
        # --rescaleRestore false, which degrades a count mismatch to a
        # warned fresh start instead of crashing the fleet attempt.
        self.rescale_restore = True
        # cumulative rescale count for Statistics: pinned by the
        # supervisor (--rescaleCount, authoritative across incarnations);
        # an unsupervised manual rescale-restore self-increments instead
        self.rescales_performed = 0
        self._rescale_count_pinned = False
        # self-healing fleet telemetry (runtime/selfheal.py): how many
        # process slots the supervisor has shrunk away from the configured
        # width (--fleetDegraded, authoritative; 0 = full width), and the
        # count of telemetry writes (heartbeat files, black-box ring
        # dumps) the disk refused — survived as a dropped-write counter
        # instead of a dead worker (blackboxWriteErrors)
        self.fleet_degraded = 0
        self.hb_write_errors = 0
        # collective hang watchdog (--collectiveTimeoutMs; None = unarmed,
        # zero objects): a worker stuck in a fabric collective whose peer
        # died dumps its black box and exits HANG_EXIT instead of wedging
        self.watchdog = None
        self._ckpt_seq = 0
        self._reduce_jits: Dict[Tuple[str, int], Any] = {}
        self._loss_mean_jit = None
        # serving-launch wall clock (per collective predict round,
        # including the device wait): recent_p99 rides the heartbeat
        # frame to the autoscaling supervisor — the host-plane latency
        # signal the staging-backlog level alone cannot see
        from omldm_tpu.utils.tracing import StepTimer

        self.serve_timer = StepTimer("dist_serve", cap=8192)
        # flight recorder (runtime/events.py; --events / --blackboxPath):
        # the distributed engine keeps the JOURNAL half of the plane —
        # restore/rescale/backpressure decisions record as typed events,
        # the ring dumps to blackbox-proc<pid>.jsonl at every dirty chunk
        # tick (so a SIGKILLed worker leaves a near-current ring for the
        # supervisor's incident bundle) — while the watchdog rule layer
        # stays host-plane (it reads the in-process metrics registry).
        # None (default) = zero recorder objects.
        from omldm_tpu.runtime.events import EventJournal, parse_events_spec

        self.events = None
        self._ev_clock = 0  # records consumed (the journal's count clock)
        ev_cfg = parse_events_spec(getattr(config, "events", "") or "")
        if ev_cfg is not None:
            self.events = EventJournal(
                cap=ev_cfg.cap,
                pid=self.pid,
                path=(
                    ev_cfg.blackbox_path
                    or getattr(config, "blackbox_path", "")
                ),
                position=lambda: self._ev_clock,
                tail_len=ev_cfg.tail,
            )

    def _warn(self, msg: str) -> None:
        print(f"[distributed p{self.pid}] {msg}", file=sys.stderr)

    def _record_event(self, kind: str, cause: str, **fields) -> None:
        """Flight-recorder hook: one attribute read when unarmed."""
        if self.events is not None:
            self.events.record(kind, cause, **fields)

    # --- hang safety (runtime/selfheal.HangWatchdog) ---

    def hang_guard(self, phase: str):
        """Deadline guard around a collective-bearing region: re-entrant,
        refreshed on every entry. The no-op context when the watchdog is
        unarmed (the default)."""
        if self.watchdog is None:
            from contextlib import nullcontext

            return nullcontext()
        return self.watchdog.guard(phase)

    def arm_hang_watchdog(
        self, timeout_s: float, warmup_s: Optional[float] = None
    ) -> None:
        """Arm the collective watchdog: a guarded region that makes no
        progress for ``timeout_s`` (first entry per phase: ``warmup_s``,
        the cold-compile allowance) dumps this process's black box and
        exits :data:`~omldm_tpu.runtime.selfheal.HANG_EXIT` — the
        reason-coded "my peer is wedged" exit the supervisor blames on
        the SILENT process, not on this honest survivor."""
        from omldm_tpu.runtime.selfheal import HANG_EXIT, HangWatchdog

        def on_expire(phase: str) -> None:
            self._warn(
                f"collective watchdog: no progress in {phase!r} for "
                f"{timeout_s * 1000.0:.0f}ms — a peer is dead or wedged; "
                f"dumping black box and exiting HANG_EXIT({HANG_EXIT}) "
                "instead of blocking forever"
            )
            if self.events is not None:
                from omldm_tpu.runtime.events import HANG

                self.events.record(
                    HANG, "collective_timeout", phase=phase,
                    timeout_ms=timeout_s * 1000.0,
                )
                self.events.incident("hang")
            os._exit(HANG_EXIT)

        self.watchdog = HangWatchdog(
            timeout_s, on_expire, warmup_s=warmup_s
        )

    def note_event_records(self, n: int) -> None:
        """Advance the journal's count clock (records consumed this
        incarnation) — called from the chunk tick."""
        if self.events is not None:
            self._ev_clock += int(n)

    # --- overload control (runtime/overload.py) ---

    def backlog_rows(self) -> int:
        """Host-side staging backlog on THIS process: rows buffered ahead
        of the collective step (pending + forecast buffers) plus rows the
        SSP bound refused and requeued."""
        return int(sum(
            p.pend_n + p.fore_n + getattr(p.trainer, "requeued_rows", 0)
            for p in self.pipelines.values()
        ))

    def overload_level(self) -> int:
        """0 OK / 1 ELEVATED / 2 CRITICAL from the staging backlog (the
        distributed engine's pressure signal); 0 when unarmed."""
        cfg = self.overload_cfg
        if cfg is None:
            return 0
        backlog = self.backlog_rows()
        if backlog >= cfg.backlog_critical:
            return 2
        if backlog >= cfg.backlog_high:
            return 1
        return 0

    def _note_pressure(self) -> None:
        """Track the pressure peak across a pump window (called by the
        row-buffering paths — the moment the staging backlog is honest,
        before pump drains it). One attribute write when unarmed-free."""
        if self.overload_cfg is not None:
            level = self.overload_level()
            if level > self._level_window:
                self._level_window = level

    def overload_level_window(self) -> int:
        """The worst pressure level since the last call (folded with the
        instantaneous level), then reset — the per-tick value the
        heartbeat file carries to the autoscaling supervisor."""
        level = max(self._level_window, self.overload_level())
        self._level_window = 0
        return level

    def heartbeat_frame(self) -> dict:
        """The compact metrics frame this worker's heartbeat file carries
        (supervisor._beat_frame parses it): the window-peak pressure
        level plus the signals the level derivation alone cannot
        express — collective-predict serve p99 ms and the staging
        backlog row count. ``imbalance`` is 0 here: the distributed
        engine fans every record to every pipeline, so per-tenant
        fair-share excess is a host-plane (Spoke) signal — the key stays
        in the frame so one supervisor parser serves both planes."""
        return {
            "level": self.overload_level_window(),
            "serveP99": round(self.serve_timer.recent_p99(), 3),
            "imbalance": 0.0,
            "backlog": int(self.backlog_rows()),
            # flight-recorder high-water id + alert count (0 unarmed; the
            # alert half lives on the host plane, so alerts stays 0 here
            # — the key rides the frame so one supervisor parser serves
            # both planes, like imbalance)
            "events": (
                self.events.high_water if self.events is not None else 0
            ),
            "alerts": (
                self.events.alerts if self.events is not None else 0
            ),
        }

    def _fetch_replicated(self, arr) -> np.ndarray:
        """Host copy of a REPLICATED global array: read the local shard
        (a plain device_get would try to fetch non-addressable shards of
        the multi-process array and fail)."""
        return np.asarray(arr.addressable_shards[0].data)

    # --- fabric primitives ---

    def _collective_reduce(self, values: Sequence[float], op: str) -> np.ndarray:
        """Elementwise sum/max of a small per-process float vector over the
        fabric; returns the reduced vector (identical on every process)."""
        vec = np.asarray(list(values), np.float64)
        if self.nproc == 1:
            return vec
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from omldm_tpu.parallel.multihost import host_local_array

        k = vec.size
        if op == "sum":
            rows = np.broadcast_to(
                vec[None, :] / self.dp_local, (self.dp_local, k)
            ).astype(np.float64)
        else:
            rows = np.broadcast_to(vec[None, :], (self.dp_local, k)).astype(
                np.float64
            )
        arr = host_local_array(rows, self.mesh, P("dp"))
        fn = self._reduce_jits.get((op, k))
        if fn is None:
            rep = NamedSharding(self.mesh, P())
            reduce = (lambda a: a.sum(axis=0)) if op == "sum" else (
                lambda a: a.max(axis=0)
            )
            fn = jax.jit(reduce, out_shardings=rep)
            self._reduce_jits[(op, k)] = fn
        # every completed reduce is fleet progress: the (re-entrant) guard
        # entry refreshes any outer phase's hang deadline
        with self.hang_guard("reduce"):
            return self._fetch_replicated(fn(arr))

    def _agree_rounds(self, local_rounds: int) -> int:
        """All processes take the MAX of their desired round counts over
        the fabric, so every one of them enters the same number of
        collective steps (short partitions contribute masked batches)."""
        return int(self._collective_reduce([float(local_rounds)], "max")[0])

    def barrier(self) -> None:
        """Fabric barrier (a fetched 1-scalar collective): nobody returns
        until every process reached this point."""
        self._collective_reduce([0.0], "max")

    # --- control plane: process-0 broadcast over the fabric ---

    # frame header: 4-byte payload length + 1-byte continuation flag
    _FRAME_HEADER = 5

    def _frame_batches(self, lines: List[str]) -> List[List[str]]:
        """Greedy-pack request lines into frames that fit the fixed
        broadcast buffer (a fleet-scale Create wave — tens of thousands
        of tenants — is far larger than one frame)."""
        cap = CONTROL_CAP - self._FRAME_HEADER
        batches: List[List[str]] = [[]]
        size = 0
        for line in lines:
            n = len(line.encode("utf-8"))
            if n > cap:
                raise ValueError(
                    f"request line too large for the control broadcast "
                    f"({n} bytes > {cap})"
                )
            if batches[-1] and size + 1 + n > cap:
                batches.append([])
                size = 0
            size += n + (1 if len(batches[-1]) else 0)
            batches[-1].append(line)
        return batches

    def _broadcast_lines(self, lines: List[str]) -> List[str]:
        """Every process receives process 0's request lines. Each frame
        travels as a [nproc, CONTROL_CAP] uint8 array assembled from
        per-process rows; a replicated-output jit hands every process row
        0 — i.e. the broadcast IS a collective on the training fabric.
        Payloads larger than one frame stream as multiple frames, paced
        by a continuation flag in the header: every process loops until
        process 0's flag clears, so the collective count stays lockstep
        without anybody knowing the total up front."""
        out: List[str] = []
        batches = self._frame_batches(lines) if self.pid == 0 else [[]]
        i = 0
        while True:
            batch = batches[i] if i < len(batches) else []
            more = self.pid == 0 and i + 1 < len(batches)
            received, more = self._broadcast_frame(batch, more)
            out.extend(received)
            i += 1
            if not more:
                return out

    def _broadcast_frame(
        self, lines: List[str], more: bool
    ) -> Tuple[List[str], bool]:
        """One fixed-size broadcast collective; returns (lines, more) as
        decoded from process 0's row."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from omldm_tpu.parallel.multihost import host_local_array

        payload = "\n".join(lines).encode("utf-8") if self.pid == 0 else b""
        hdr = self._FRAME_HEADER
        if len(payload) > CONTROL_CAP - hdr:
            raise ValueError(
                f"control broadcast overflow ({len(payload)} bytes > "
                f"{CONTROL_CAP - hdr}); split the request batch"
            )
        row = np.zeros((1, CONTROL_CAP), np.uint8)
        row[0, :4] = np.frombuffer(
            np.uint32(len(payload)).tobytes(), np.uint8
        )
        row[0, 4] = 1 if more else 0
        row[0, hdr : hdr + len(payload)] = np.frombuffer(payload, np.uint8)
        if self.nproc == 1:
            rows = row
        else:
            # one row per process on the dp axis; replicated output makes
            # row 0 locally addressable everywhere
            mesh_rows = np.repeat(row, self.dp_local, axis=0)
            arr = host_local_array(mesh_rows, self.mesh, P("dp"))
            take0 = jax.jit(
                lambda a: a[0],
                out_shardings=NamedSharding(self.mesh, P()),
            )
            rows = self._fetch_replicated(take0(arr))[None, :]
        n = int(np.frombuffer(rows[0, :4].tobytes(), np.uint32)[0])
        text = rows[0, hdr : hdr + n].tobytes().decode("utf-8")
        return [l for l in text.split("\n") if l], bool(rows[0, 4])

    def sync_requests(self, lines: Optional[List[str]] = None) -> None:
        """Process 0 passes its pending request lines; every process runs
        the SAME control-plane transitions afterwards (the broadcast makes
        the lines identical, so the collective programs Query/Delete/Create
        trigger stay lockstep). The full request vocabulary is honored:
        Create/Update deploy, Delete tears down, Query answers collectively;
        anything invalid or unsupported is LOGGED and dropped, never
        silently ignored (PipelineMap.scala:34,46 prints and drops)."""
        with self.hang_guard("control"):
            self._sync_requests_guarded(lines)

    def _deploy_beat(self, i: int) -> None:
        if self.beat_hook is not None and i % 256 == 255:
            self.beat_hook()

    def _shared_jit(self, p: "_DistPipeline", name: str, build):
        key = (name, p.sparse, p.dim, p.trainer.program_key)
        fn = self._prog_cache.get(key)
        if fn is None:
            fn = self._prog_cache[key] = build()
        return fn

    def _sync_requests_guarded(
        self, lines: Optional[List[str]] = None
    ) -> None:
        for i, line in enumerate(self._broadcast_lines(list(lines or []))):
            self._deploy_beat(i)
            request = Request.from_json(line)
            if request is None:
                self._warn(f"dropping unparseable request line: {line[:120]!r}")
                continue
            err = self.pipeline_manager.validate(request)
            if err is not None:
                self._warn(
                    f"rejecting {request.request.value} for pipeline "
                    f"{request.id}: {err}"
                )
                continue
            if request.request in (RequestType.CREATE, RequestType.UPDATE):
                self._deploy(request, line)
            elif request.request == RequestType.DELETE:
                self.pipeline_manager.admit(request)
                dropped = self.pipelines.pop(request.id, None)
                if dropped is not None:
                    # predictions already served belong to the output even
                    # though the pipeline is gone (a streaming sink would
                    # have emitted them long ago)
                    self.orphan_predictions.extend(
                        (request.id, v) for v in dropped.predictions
                    )
                self._warn(f"pipeline {request.id} deleted")
            elif request.request == RequestType.QUERY:
                self._answer_query(request)

    def _request_dim(self, request: Request) -> Optional[int]:
        ds = request.learner.data_structure if request.learner else None
        if ds and "nFeatures" in ds:
            return int(ds["nFeatures"]) + int(
                request.training_configuration.extra.get("hashDims", 0)
            )
        return None

    def _deploy(self, request: Request, raw_line: str) -> None:
        """Deploy/replace one pipeline on the shared mesh. The distributed
        runtime hosts MANY concurrent pipelines (the reference's per-subtask
        Map[Int, wrapper], SpokeLogic.scala:28-29); all share the stream, so
        their feature widths must agree with the stream width pinned by the
        first deploy. Anything the collective engine cannot host (sparse
        COO streams, host-side learners, unsupported protocols) is rejected
        WITH a logged reason instead of dropped silently."""
        from omldm_tpu.api.requests import TrainingConfiguration
        from omldm_tpu.parallel.spmd import SPMDTrainer

        ds = (request.learner.data_structure if request.learner else None) or {}
        sparse = bool(ds.get("sparse"))
        if self.stream_mode is not None and (
            (self.stream_mode == "sparse") != sparse
        ):
            self._warn(
                f"rejecting pipeline {request.id}: the stream is "
                f"{self.stream_mode} (pinned by the first deploy) and a "
                f"{'sparse' if sparse else 'dense'} pipeline cannot share "
                "its parse route"
            )
            return
        if sparse:
            # sparse widths are EXACT (hashSpace inside nFeatures); the
            # dense hashDims knob does not apply to the COO path
            dim = int(ds.get("nFeatures", 0)) or None
        else:
            dim = self._request_dim(request)
        if dim is None:
            self._warn(
                f"rejecting pipeline {request.id}: distributed deployment "
                "needs dataStructure.nFeatures on the Create (the stream "
                "width must be known before partitions start)"
            )
            return
        if self.dim is not None and dim != self.dim:
            self._warn(
                f"rejecting pipeline {request.id}: feature width {dim} != "
                f"stream width {self.dim} pinned by the first deploy"
            )
            return
        tc = request.training_configuration or TrainingConfiguration(
            protocol="Synchronous"
        )
        try:
            trainer = SPMDTrainer(
                request.learner,
                request.preprocessors or (),
                dim=dim,
                protocol=tc.protocol,
                mesh=self.mesh,
                training_configuration=tc,
                batch_size=self.config.batch_size,
            )
        except ValueError as exc:
            self._warn(f"rejecting pipeline {request.id}: {exc}")
            return
        hash_dims = 0 if sparse else int(tc.extra.get("hashDims", 0))
        if self.dim is not None and hash_dims != self.hash_dims:
            self._warn(
                f"rejecting pipeline {request.id}: hashDims {hash_dims} != "
                f"stream hashDims {self.hash_dims} pinned by the first deploy"
            )
            return
        max_nnz = int(ds.get("maxNnz", 40)) if sparse else 0
        hash_space = int(ds.get("hashSpace", 0)) if sparse else 0
        if sparse and self.pipelines:
            pinned = next(iter(self.pipelines.values())).max_nnz
            if max_nnz != pinned or hash_space != self.sparse_hash_space:
                self._warn(
                    f"rejecting pipeline {request.id}: COO layout "
                    f"(maxNnz {max_nnz}, hashSpace {hash_space}) differs "
                    "from the stream layout pinned by the first deploy"
                )
                return
        self.pipeline_manager.admit(request)
        self.dim = dim
        self.hash_dims = hash_dims
        self.stream_mode = "sparse" if sparse else "dense"
        if sparse:
            self.sparse_hash_space = hash_space
        if request.id in self.pipelines:
            self._warn(
                f"pipeline {request.id} replaced by "
                f"{request.request.value} (fresh model state)"
            )
        self.pipelines[request.id] = _DistPipeline(
            request, raw_line, dim, trainer,
            self.config.test_set_size,
            self.dp_local * self.config.batch_size,
            sparse=sparse, max_nnz=max_nnz,
        )
        if self.watchdog is not None:
            # a fresh pipeline means fresh XLA compiles in already-warmed
            # phases: re-grant the cold-compile allowance so the hang
            # watchdog does not shoot an honestly-compiling worker
            self.watchdog.rewarm()

    # --- data path: this process's partition only ---

    def handle_partition_rows(self, x: np.ndarray, y: np.ndarray) -> None:
        """Buffer rows from THIS process's ingest partition for EVERY live
        pipeline (each record reaches each pipeline, FlinkSpoke's per-key
        fan-out), holdout-split per pipeline exactly as the in-process
        runtime applies it per worker. Rows are NOT trained here:
        collective steps only run inside :meth:`pump`, where every process
        agrees on the round count first — a process stepping on local
        buffer fullness alone could enter a collective its peers never
        reach (lockstep deadlock)."""
        n = x.shape[0]
        if n == 0:
            return
        for p in self.pipelines.values():
            self._buffer_rows(p, x, y)
        self._note_pressure()

    def _buffer_rows(self, p: _DistPipeline, x: np.ndarray, y: np.ndarray) -> None:
        if self.config.test:
            n = x.shape[0]
            c = (p.holdout_count + np.arange(n)) % 10
            p.holdout_count += n
            test_mask = c >= 8
            keep_idx = np.nonzero(~test_mask)[0]
            t_idx = np.nonzero(test_mask)[0]
            ev_x, ev_y, ev_src = p.test_set.append_many(x[t_idx], y[t_idx])
            if ev_src.size:
                pos = np.concatenate([keep_idx, t_idx[ev_src]])
                order = np.argsort(pos, kind="stable")
                x = np.concatenate([x[keep_idx], ev_x])[order]
                y = np.concatenate([y[keep_idx], ev_y])[order]
            else:
                x, y = x[keep_idx], y[keep_idx]
        else:
            p.holdout_count += x.shape[0]
        if x.shape[0]:
            p.pend_x.append(np.asarray(x, np.float32))
            p.pend_y.append(np.asarray(y, np.float32))
            p.pend_n += x.shape[0]

    def handle_partition_rows_sparse(
        self, idx: np.ndarray, val: np.ndarray, y: np.ndarray
    ) -> None:
        """COO twin of :meth:`handle_partition_rows` (padded (idx, val)
        rows from this partition, holdout-split per pipeline)."""
        if idx.shape[0] == 0:
            return
        for p in self.pipelines.values():
            self._buffer_rows_sparse(p, idx, val, y)
        self._note_pressure()

    def _buffer_rows_sparse(self, p, idx, val, y) -> None:
        if self.config.test:
            n = idx.shape[0]
            c = (p.holdout_count + np.arange(n)) % 10
            p.holdout_count += n
            test_mask = c >= 8
            keep = np.nonzero(~test_mask)[0]
            t_idx = np.nonzero(test_mask)[0]
            ev_i, ev_v, ev_y, ev_src = p.test_set.append_many(
                idx[t_idx], val[t_idx], y[t_idx]
            )
            if ev_src.size:
                pos = np.concatenate([keep, t_idx[ev_src]])
                order = np.argsort(pos, kind="stable")
                idx = np.concatenate([idx[keep], ev_i])[order]
                val = np.concatenate([val[keep], ev_v])[order]
                y = np.concatenate([y[keep], ev_y])[order]
            else:
                idx, val, y = idx[keep], val[keep], y[keep]
        else:
            p.holdout_count += idx.shape[0]
        if idx.shape[0]:
            p.pend_x.append(np.asarray(idx, np.int32))
            p.pend_v.append(np.asarray(val, np.float32))
            p.pend_y.append(np.asarray(y, np.float32))
            p.pend_n += idx.shape[0]

    def handle_forecast_rows_sparse(
        self, idx: np.ndarray, val: np.ndarray
    ) -> None:
        if idx.shape[0] == 0:
            return
        for p in self.pipelines.values():
            p.fore_x.append(np.asarray(idx, np.int32))
            p.fore_v.append(np.asarray(val, np.float32))
            p.fore_n += idx.shape[0]
        self._note_pressure()

    def handle_forecast_rows(self, x: np.ndarray) -> None:
        """Buffer forecast rows from this partition for every pipeline;
        predictions are served collectively at the next :meth:`pump` (the
        model is sharded across processes, so serving is a lockstep
        program like everything else)."""
        if x.shape[0] == 0:
            return
        for p in self.pipelines.values():
            p.fore_x.append(np.asarray(x, np.float32))
            p.fore_n += x.shape[0]
        self._note_pressure()

    def pump(self, final: bool = False) -> None:
        """Run the agreed number of lockstep collective steps per pipeline
        over the buffered rows. Call at synchronized points of the drive
        loop (all processes pump after the same stream chunk; ``final=True``
        drains remainders with zero-masked padding). Pipelines are visited
        in sorted id order so every process issues the same collective
        sequence."""
        with self.hang_guard("pump"):
            for net_id in sorted(self.pipelines):
                p = self.pipelines[net_id]
                self._pump_pipeline(p, final)
                self._pump_forecasts(p)

    def _pump_pipeline(self, p: _DistPipeline, final: bool) -> None:
        cap = p.stage_cap
        want = -(-p.pend_n // cap) if final else p.pend_n // cap
        rounds = self._agree_rounds(int(want))
        if rounds == 0:
            return
        b = self.config.batch_size
        from jax.sharding import PartitionSpec as P

        from omldm_tpu.parallel.multihost import host_local_array

        width = p.max_nnz if p.sparse else p.dim
        buf_x = (
            np.concatenate(p.pend_x)
            if p.pend_x
            else np.zeros(
                (0, width), np.int32 if p.sparse else np.float32
            )
        )
        buf_v = (
            np.concatenate(p.pend_v)
            if p.sparse and p.pend_v
            else np.zeros((0, width), np.float32)
        )
        buf_y = (
            np.concatenate(p.pend_y)
            if p.pend_y
            else np.zeros((0,), np.float32)
        )
        p.pend_x, p.pend_v, p.pend_y = [], [], []
        requeued = []  # row blocks refused by the SSP bound this pump
        done = 0
        staged = 0
        last_loss = None
        for _ in range(rounds):
            rows = min(cap, buf_x.shape[0] - done)
            x = np.zeros(
                (cap, width), np.int32 if p.sparse else np.float32
            )
            v = np.zeros((cap, width), np.float32) if p.sparse else None
            y = np.zeros((cap,), np.float32)
            mask = np.zeros((cap,), np.float32)
            if rows > 0:
                x[:rows] = buf_x[done : done + rows]
                if p.sparse:
                    v[:rows] = buf_v[done : done + rows]
                y[:rows] = buf_y[done : done + rows]
                mask[:rows] = 1.0
            done += max(rows, 0)
            staged += max(rows, 0)
            x_d = host_local_array(
                x.reshape(self.dp_local, b, width), self.mesh, P("dp")
            )
            y_d = host_local_array(
                y.reshape(self.dp_local, b), self.mesh, P("dp")
            )
            m_d = host_local_array(
                mask.reshape(self.dp_local, b), self.mesh, P("dp")
            )
            if p.sparse:
                v_d = host_local_array(
                    v.reshape(self.dp_local, b, width), self.mesh, P("dp")
                )
                batch = (x_d, v_d)
            else:
                batch = x_d
            last_loss = p.trainer.step(
                batch, y_d, m_d, valid_count=max(rows, 0)
            )
            p.steps_run += 1
            if p.trainer.protocol == "SSP":
                self._requeue_refused(
                    p,
                    x.reshape(self.dp_local, b, width),
                    None if v is None else v.reshape(
                        self.dp_local, b, width
                    ),
                    y.reshape(self.dp_local, b),
                    mask.reshape(self.dp_local, b),
                    requeued,
                )
        # the trainer's internal curve holds lazy multi-process arrays the
        # host cannot np.asarray; the distributed curve below replaces it
        p.trainer._curve.clear()
        # rebuild the pending buffer from the un-stepped tail PLUS any
        # SSP-refused rows collected during the loop (overwriting with the
        # tail alone would silently drop the requeued rows)
        p.pend_x = [buf_x[done:]] if done < buf_x.shape[0] else []
        if p.sparse:
            p.pend_v = [buf_v[done:]] if done < buf_x.shape[0] else []
        p.pend_y = [buf_y[done:]] if done < buf_x.shape[0] else []
        p.pend_n = max(buf_x.shape[0] - done, 0)
        requeued_rows = 0
        for blk in requeued:
            p.pend_x.append(blk[0])
            if p.sparse:
                p.pend_v.append(blk[1])
            p.pend_y.append(blk[-1])
            p.pend_n += blk[0].shape[0]
            requeued_rows += blk[0].shape[0]
        # one pump-granularity learning-curve point: global mean loss of
        # the pump's last step + globally-consumed row count (two tiny
        # collectives per pump, not per step)
        if last_loss is not None:
            if self._loss_mean_jit is None:
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P2

                self._loss_mean_jit = jax.jit(
                    lambda l: l.mean(),
                    out_shardings=NamedSharding(self.mesh, P2()),
                )
            loss_val = float(
                self._fetch_replicated(self._loss_mean_jit(last_loss))
            )
            consumed = self._collective_reduce(
                [float(staged - requeued_rows)], "sum"
            )[0]
            p.global_rows += int(consumed)
            p.curve.append((loss_val, p.global_rows))

    def _requeue_refused(self, p: _DistPipeline, xg, vg, yg, mg, requeued) -> None:
        """SSP pacing across processes: the device refuses batches of
        workers past the staleness bound (state untouched, accepted=0);
        each process collects ITS OWN refused rows into ``requeued`` (the
        caller merges them back into the pending buffer after the round
        loop) and corrects the fitted counter — the multi-process form of
        the SPMD bridge's host-driven requeue."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if p._accepted_jit is None:
            from omldm_tpu.parallel.spmd import stacked

            rep = NamedSharding(self.mesh, P())
            p._accepted_jit = self._shared_jit(
                p, "accepted",
                lambda: jax.jit(
                    lambda s: stacked(
                        s["accepted"], self.dp_global, p.trainer.hub
                    )[:, 0] > 0.0,
                    out_shardings=rep,
                ),
            )
        acc = self._fetch_replicated(p._accepted_jit(p.trainer.state))
        lo = self.pid * self.dp_local
        mine = acc[lo : lo + self.dp_local]
        for w in np.nonzero(~mine)[0]:
            rows = mg[w] > 0.0
            k = int(rows.sum())
            if k == 0:
                continue
            p.trainer.note_requeued(k)
            if p.sparse:
                requeued.append((
                    np.asarray(xg[w][rows], np.int32),
                    np.asarray(vg[w][rows], np.float32),
                    np.asarray(yg[w][rows], np.float32),
                ))
            else:
                requeued.append((
                    np.asarray(xg[w][rows], np.float32),
                    np.asarray(yg[w][rows], np.float32),
                ))

    def _pump_forecasts(self, p: _DistPipeline) -> None:
        """Agreed rounds of collective predict over buffered forecast
        rows; every process appends ITS rows' predictions locally."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from omldm_tpu.parallel.multihost import host_local_array

        cap = p.stage_cap
        rounds = self._agree_rounds(-(-p.fore_n // cap))
        if rounds == 0:
            return
        if p._predict_jit is None:
            t = p.trainer
            rep = NamedSharding(self.mesh, P())
            w0 = t.shard0

            if p.sparse:

                def predict_fn(state, i, v):
                    k = i.shape[-1]
                    z = (i.reshape(-1, k), v.reshape(-1, k))
                    return t.learner.predict(w0(state["params"]), z)

            else:

                def predict_fn(state, x):
                    d = x.shape[-1]
                    z = x.reshape(-1, d)
                    for prep, s in zip(t.preps, state["preps"]):
                        z = prep.transform(w0(s), z)
                    return t.learner.predict(w0(state["params"]), z)

            p._predict_jit = self._shared_jit(
                p, "predict",
                lambda: jax.jit(predict_fn, out_shardings=rep),
            )
        width = p.max_nnz if p.sparse else p.dim
        buf = (
            np.concatenate(p.fore_x)
            if p.fore_x
            else np.zeros(
                (0, width), np.int32 if p.sparse else np.float32
            )
        )
        buf_v = (
            np.concatenate(p.fore_v)
            if p.sparse and p.fore_v
            else np.zeros((0, width), np.float32)
        )
        p.fore_x, p.fore_v, p.fore_n = [], [], 0
        done = 0
        for _ in range(rounds):
            rows = min(cap, buf.shape[0] - done)
            x = np.zeros(
                (cap, width), np.int32 if p.sparse else np.float32
            )
            if rows > 0:
                x[:rows] = buf[done : done + rows]
            x_d = host_local_array(
                x.reshape(self.dp_local, -1, width), self.mesh, P("dp")
            )
            if p.sparse:
                v = np.zeros((cap, width), np.float32)
                if rows > 0:
                    v[:rows] = buf_v[done : done + rows]
                v_d = host_local_array(
                    v.reshape(self.dp_local, -1, width), self.mesh, P("dp")
                )
                with self.serve_timer:
                    preds = self._fetch_replicated(p._predict_jit(
                        p.trainer.state, x_d, v_d
                    ))
            else:
                with self.serve_timer:
                    preds = self._fetch_replicated(p._predict_jit(
                        p.trainer.state, x_d
                    ))
            # the replicated output covers every process's rows; this
            # process's slice starts at pid * cap within the global batch
            mine = preds[self.pid * cap : self.pid * cap + max(rows, 0)]
            p.predictions.extend(float(v_) for v_ in mine)
            done += max(rows, 0)

    def flush(self) -> None:
        """Drain every pipeline, including SSP-requeued rows: repeated
        final pumps are guaranteed progress under balanced partitions (the
        bound refuses only workers ahead of the slowest, and every process
        keeps feeding its slowest workers); a livelock guard backstops
        pathological streams."""
        self.pump(final=True)
        with self.hang_guard("flush"):
            for net_id in sorted(self.pipelines):
                p = self.pipelines[net_id]
                guard = 0
                while self._agree_rounds(1 if p.pend_n else 0):
                    before = p.pend_n
                    self._pump_pipeline(p, final=True)
                    progressed = 1 if p.pend_n < before else 0
                    if not self._agree_rounds(progressed):
                        # NOBODY advanced: a dried-up partition pins the
                        # staleness bound (its worker's clock cannot move)
                        # — apply the termination-time release, exactly the
                        # host plane's SSPParameterServer.on_terminate
                        # semantics
                        p.trainer.release_stragglers()
                    guard += 1
                    if guard > 1000:
                        raise RuntimeError(
                            "SSP drain made no progress requeuing refused "
                            "rows"
                        )
                self._pump_forecasts(p)

    # --- queries ---

    def _answer_query(self, request: Request) -> None:
        """Answer a user Query COLLECTIVELY: the union-holdout eval and the
        worker-0 parameter gather are lockstep programs every process runs;
        process 0 assembles the bucketed QueryResponse fragments exactly as
        the SPMD bridge does (FlinkNetwork.scala:196-231 wire format; the
        fleet is one logical model, so the merger expects one fragment
        set)."""
        import jax
        import jax.flatten_util  # noqa: F401  (ravel_pytree inside the jit)
        from jax.sharding import NamedSharding, PartitionSpec as P

        p = self.pipelines.get(request.id)
        if p is None:
            # admitted by the gatekeeper but never deployed here (e.g. a
            # rejected sparse Create): say so instead of dropping
            self._warn(f"query for undeployed pipeline {request.id} dropped")
            return
        self._pump_pipeline(p, final=True)
        loss, score = self._evaluate_global(p)
        if p._gather_params_jit is None:
            rep = NamedSharding(self.mesh, P())

            def gather_fn(state):
                flat, _ = jax.flatten_util.ravel_pytree(
                    p.trainer.shard0(state["params"])
                )
                return flat

            p._gather_params_jit = self._shared_jit(
                p, "gather_params",
                lambda: jax.jit(gather_fn, out_shardings=rep),
            )
        flat = self._fetch_replicated(p._gather_params_jit(p.trainer.state))
        fitted = int(self._collective_reduce(
            [float(p.trainer.fitted)], "sum"
        )[0])
        if self.pid != 0:
            return
        rid = request.request_id if request.request_id is not None else 0
        bucket_cap = self.config.max_param_bucket_size
        chunks = [
            flat[i : i + bucket_cap]
            for i in range(0, max(flat.size, 1), bucket_cap)
        ] or [None]
        req = p.request
        learner_desc = {
            "name": req.learner.name,
            "hyperParameters": dict(req.learner.hyper_parameters or {}),
            "dataStructure": dict(req.learner.data_structure or {}),
        }
        self.response_merger.expect(rid, 1)
        for i, chunk in enumerate(chunks):
            learner = (
                dict(learner_desc) if i == 0 else {"name": learner_desc["name"]}
            )
            if chunk is not None:
                learner["parameters"] = {"bucketValues": chunk.tolist()}
            self.response_merger.add_fragment(
                QueryResponse(
                    response_id=rid,
                    mlp_id=req.id,
                    bucket=i,
                    num_buckets=len(chunks),
                    preprocessors=[
                        {
                            "name": pr.name,
                            "hyperParameters": dict(pr.hyper_parameters or {}),
                        }
                        for pr in (req.preprocessors or [])
                    ] if i == 0 else None,
                    learner=learner,
                    protocol=req.training_configuration.protocol if i == 0 else None,
                    data_fitted=fitted,
                    loss=loss,
                    score=score,
                    source_worker=0,
                )
            )

    # --- reporting ---

    def _evaluate_global(self, p: _DistPipeline) -> Tuple[float, float]:
        """Loss/score of the fleet model on the UNION of every process's
        holdout set, computed as ONE collective program: each process
        contributes its padded holdout as its mesh shard, the worker-0
        model is gathered inside the jit, and the masked means reduce
        globally — every process receives the same replicated scalars."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from omldm_tpu.parallel.multihost import host_local_array

        cap = p.test_set.max_size
        width = p.max_nnz if p.sparse else p.dim
        xs_l = np.zeros(
            (self.dp_local, cap, width), np.int32 if p.sparse else np.float32
        )
        vs_l = (
            np.zeros((self.dp_local, cap, width), np.float32)
            if p.sparse else None
        )
        ys_l = np.zeros((self.dp_local, cap), np.float32)
        m_l = np.zeros((self.dp_local, cap), np.float32)
        n = len(p.test_set)
        if n:
            if p.sparse:
                ti, tv, ty = p.test_set.arrays()
                xs_l[0, :n] = ti
                vs_l[0, :n] = tv
                ys_l[0, :n] = ty
            else:
                xs, ys = p.test_set.arrays()
                xs_l[0, :n] = xs
                ys_l[0, :n] = ys
            m_l[0, :n] = 1.0
        x_d = host_local_array(xs_l, self.mesh, P("dp"))
        y_d = host_local_array(ys_l, self.mesh, P("dp"))
        m_d = host_local_array(m_l, self.mesh, P("dp"))
        v_d = (
            host_local_array(vs_l, self.mesh, P("dp")) if p.sparse else None
        )
        if p._eval_jit is None:
            t = p.trainer
            rep = NamedSharding(self.mesh, P())
            w0 = t.shard0

            if p.sparse:

                def eval_fn(state, i, v, y, mask):
                    k = i.shape[-1]
                    z = (i.reshape(-1, k), v.reshape(-1, k))
                    yv = y.reshape(-1)
                    mv = mask.reshape(-1)
                    params = w0(state["params"])
                    return (
                        t.learner.loss(params, z, yv, mv),
                        t.learner.score(params, z, yv, mv),
                    )

            else:

                def eval_fn(state, x, y, mask):
                    d = x.shape[-1]
                    z = x.reshape(-1, d)
                    yv = y.reshape(-1)
                    mv = mask.reshape(-1)
                    for prep, s in zip(t.preps, state["preps"]):
                        z = prep.transform(w0(s), z)
                    params = w0(state["params"])
                    return (
                        t.learner.loss(params, z, yv, mv),
                        t.learner.score(params, z, yv, mv),
                    )

            p._eval_jit = self._shared_jit(
                p, "eval",
                lambda: jax.jit(eval_fn, out_shardings=(rep, rep)),
            )
        if p.sparse:
            loss, score = p._eval_jit(p.trainer.state, x_d, v_d, y_d, m_d)
        else:
            loss, score = p._eval_jit(p.trainer.state, x_d, y_d, m_d)
        return (
            float(self._fetch_replicated(loss)),
            float(self._fetch_replicated(score)),
        )

    def _global_device_counters(self, p: _DistPipeline) -> Tuple[int, int, int]:
        """(sum of per-worker syncs, worker-0 syncs, worker-0 steps) read
        through a replicated-output jit (the fleet state is sharded across
        processes; direct device_get cannot address remote shards)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if p._counters_jit is None:
            from omldm_tpu.parallel.spmd import stacked

            rep = NamedSharding(self.mesh, P())

            def view(leaf):
                return stacked(leaf, self.dp_global, p.trainer.hub)

            p._counters_jit = self._shared_jit(
                p, "counters",
                lambda: jax.jit(
                    lambda s: (
                        view(s["syncs"])[:, 0].sum(),
                        view(s["syncs"])[0, 0],
                        view(s["step"])[0, 0],
                    ),
                    out_shardings=(rep, rep, rep),
                ),
            )
        a, b, c = p._counters_jit(p.trainer.state)
        return (
            int(self._fetch_replicated(a)),
            int(self._fetch_replicated(b)),
            int(self._fetch_replicated(c)),
        )

    def pipeline_statistics(self, p: _DistPipeline) -> Tuple[Statistics, int]:
        """One pipeline's Statistics (the reference schema,
        FlinkHub.scala:118-153) with fabric-reduced counters, plus the
        global holdout size. COLLECTIVE: every process must call it in the
        same order."""
        loss, score = self._evaluate_global(p)
        syncs_sum, syncs00, steps = self._global_device_counters(p)
        t = p.trainer
        sync_count, total_bytes = t.protocol_traffic_bytes(
            t.protocol, t.dp, t.flat_size, syncs_sum, syncs00, steps
        )
        # same counters priced at the configured transport codec's wire
        # width — the multi-process model-exchange route's bytes-on-wire
        # (the role of the reference's psMessages traffic accounting)
        _, wire_bytes = t.protocol_traffic_bytes(
            t.protocol, t.dp, t.flat_size, syncs_sum, syncs00, steps,
            codec=t.codec_name,
        )
        reduced = self._collective_reduce(
            [float(t.fitted), float(len(p.test_set)), float(p.pend_n)], "sum"
        )
        stats = Statistics(
            pipeline=p.request.id,
            protocol=t.protocol,
            models_shipped=sync_count * t.dp,
            bytes_shipped=int(total_bytes),
            bytes_on_wire=int(wire_bytes),
            num_of_blocks=sync_count,
            fitted=int(round(reduced[0])),
            learning_curve=[l for l, _ in p.curve],
            lcx=[r for _, r in p.curve],
            mean_buffer_size=float(reduced[2]) / self.nproc,
            score=score,
            # elastic-rescale telemetry: how many parallelism changes this
            # state has been carried across, and the CURRENT fleet width
            rescales_performed=self.rescales_performed,
            fleet_processes=self.nproc,
            # self-healing telemetry: slots shrunk away from the
            # configured width (supervisor-pinned gauge) and telemetry
            # writes the disk refused (heartbeats + black-box dumps)
            fleet_degraded=self.fleet_degraded,
            blackbox_write_errors=self.hb_write_errors + (
                self.events.write_errors if self.events is not None else 0
            ),
        )
        return stats, int(round(reduced[1]))

    def merged_report(self) -> Optional[dict]:
        """Global job report in the reference's JobStatistics schema
        (StatisticsOperator.scala:110-127): one Statistics entry per live
        pipeline, counters reduced over the fabric, score evaluated on the
        union holdout. COLLECTIVE — every process calls it; only process 0
        returns the dict (with deployment extras: process count, global
        holdout sizes, local SSP-requeue proof), the others get None."""
        entries = []
        holdout = {}
        requeued_local = 0
        with self.hang_guard("report"):
            for net_id in sorted(self.pipelines):
                p = self.pipelines[net_id]
                stats, hold = self.pipeline_statistics(p)
                entries.append(stats)
                holdout[str(net_id)] = hold
                requeued_local += getattr(p.trainer, "requeued_rows", 0)
        # terminate-time stranded-row accounting (collective: every
        # process contributes its staging backlog) — the SLO evaluator's
        # no-stranded-rows gate reads this instead of trusting the drive
        # loop to have drained
        stranded = self._collective_reduce(
            [float(self.backlog_rows())], "sum"
        )
        if self.pid != 0:
            return None
        report = JobStatistics(
            job_name=self.config.job_name,
            parallelism=self.dp_global,
            duration_ms=(time.time() - self.start_time) * 1000.0,
            statistics=entries,
        ).to_dict()
        report["processes"] = self.nproc
        # deployment-level mirrors of the per-pipeline gauges (operators
        # read the job header without walking statistics rows)
        report["fleetProcesses"] = self.nproc
        report["rescalesPerformed"] = self.rescales_performed
        # self-healing: slots currently shrunk away from the configured
        # width (0 = full width; supervisor-pinned via --fleetDegraded)
        report["fleetDegraded"] = self.fleet_degraded
        report["holdout"] = holdout
        # LOCAL count (process 0's workers): >0 proves the SSP requeue
        # path executed in this run
        report["requeuedLocal"] = requeued_local
        report["terminateAccounting"] = {
            "backlogRows": int(stranded[0]),
        }
        return report

    # --- checkpoint / restore (FlinkSpoke.scala:233-334 semantics) ---

    def save_checkpoint(self, root: str, cursor: Any) -> str:
        """Write a consistent distributed snapshot. Must be called at a
        synchronized pump point by EVERY process with its own ``cursor``
        (source position: row count for file striding, per-partition
        offsets for Kafka). Layout::

            root/ckpt-<k>/manifest.json     (proc 0: request lines, shape)
            root/ckpt-<k>/fleet_<net>.npz   (proc 0: gathered fleet state)
            root/ckpt-<k>/proc<p>.npz|.json (each: buffers + cursor)
            root/LATEST                     (proc 0: pointer, flipped last)

        The pointer flip happens only after a fabric barrier confirms every
        process's files are durable — the atomic-commit role of a Flink
        checkpoint barrier's acknowledgement. Every file's sha256 is
        recorded (fleet files in the manifest, each proc shard in its own
        cursor meta) so restore can verify a generation's INTEGRITY before
        trusting it — a torn/corrupted file fails the digest and the fleet
        falls back to the previous surviving generation."""
        with self.hang_guard("checkpoint"):
            return self._save_checkpoint_guarded(root, cursor)

    def _save_checkpoint_guarded(self, root: str, cursor: Any) -> str:
        import jax

        k = self._ckpt_seq
        self._ckpt_seq += 1
        d = os.path.join(root, f"ckpt-{k}")
        os.makedirs(d, exist_ok=True)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        fleet_digests: Dict[str, str] = {}
        for net_id in sorted(self.pipelines):
            p = self.pipelines[net_id]
            if p._gather_state_jit is None:
                specs = jax.tree_util.tree_map(lambda _: rep, p.trainer.state)
                p._gather_state_jit = self._shared_jit(
                    p, "gather_state",
                    lambda: jax.jit(lambda s: s, out_shardings=specs),
                )
            # the jitted gather is COLLECTIVE (every process dispatches
            # it), but only process 0 pays the host fetch + write — the
            # other processes' replicated copies never leave the device
            gathered = p._gather_state_jit(p.trainer.state)
            if self.pid == 0:
                leaves = [
                    self._fetch_replicated(l)
                    for l in jax.tree_util.tree_leaves(gathered)
                ]
                fleet_digests[f"fleet_{net_id}.npz"] = _atomic_savez(
                    os.path.join(d, f"fleet_{net_id}.npz"),
                    {f"leaf_{i}": l for i, l in enumerate(leaves)},
                )
        arrays: Dict[str, np.ndarray] = {}
        meta: Dict[str, Any] = {
            "cursor": cursor,
            "pipelines": {},
            # already-served outputs survive a restore: the request-topic
            # offsets are checkpointed past an answered Query, so the
            # response (and a deleted pipeline's predictions) would
            # otherwise vanish from the final output files
            "orphan_predictions": [
                [int(n), float(v)] for n, v in self.orphan_predictions
            ],
        }
        if self.pid == 0:
            meta["responses"] = [r.to_dict() for r in self.responses]
        for net_id in sorted(self.pipelines):
            p = self.pipelines[net_id]
            width = p.max_nnz if p.sparse else p.dim
            xdt = np.int32 if p.sparse else np.float32
            pend_x = (
                np.concatenate(p.pend_x)
                if p.pend_x else np.zeros((0, width), xdt)
            )
            pend_y = (
                np.concatenate(p.pend_y)
                if p.pend_y else np.zeros((0,), np.float32)
            )
            fore_x = (
                np.concatenate(p.fore_x)
                if p.fore_x else np.zeros((0, width), xdt)
            )
            arrays[f"n{net_id}_pend_x"] = pend_x
            arrays[f"n{net_id}_pend_y"] = pend_y
            arrays[f"n{net_id}_fore_x"] = fore_x
            if p.sparse:
                arrays[f"n{net_id}_pend_v"] = (
                    np.concatenate(p.pend_v)
                    if p.pend_v else np.zeros((0, width), np.float32)
                )
                arrays[f"n{net_id}_fore_v"] = (
                    np.concatenate(p.fore_v)
                    if p.fore_v else np.zeros((0, width), np.float32)
                )
                if len(p.test_set):
                    ti, tv, ty = p.test_set.arrays()
                else:
                    ti = np.zeros((0, width), np.int32)
                    tv = np.zeros((0, width), np.float32)
                    ty = np.zeros((0,), np.float32)
                arrays[f"n{net_id}_test_x"] = np.asarray(ti, np.int32)
                arrays[f"n{net_id}_test_v"] = np.asarray(tv, np.float32)
                arrays[f"n{net_id}_test_y"] = np.asarray(ty, np.float32)
            else:
                tx, ty = (
                    p.test_set.arrays() if len(p.test_set)
                    else (np.zeros((0, p.dim), np.float32),
                          np.zeros((0,), np.float32))
                )
                arrays[f"n{net_id}_test_x"] = np.asarray(tx, np.float32)
                arrays[f"n{net_id}_test_y"] = np.asarray(ty, np.float32)
            meta["pipelines"][str(net_id)] = {
                "holdout_count": p.holdout_count,
                "fitted": p.trainer.fitted,
                "steps_host": p.trainer._steps_host,
                "requeued": getattr(p.trainer, "requeued_rows", 0),
                "steps_run": p.steps_run,
                "predictions": p.predictions,
                "curve": p.curve,
                "global_rows": p.global_rows,
            }
        # the shard digest rides in the shard's OWN meta (each process
        # writes only its own files; the manifest carries proc 0's)
        meta["sha256"] = _atomic_savez(
            os.path.join(d, f"proc{self.pid}.npz"), arrays
        )
        _atomic_write_json(os.path.join(d, f"proc{self.pid}.json"), meta)
        if self.pid == 0:
            _atomic_write_json(
                os.path.join(d, "manifest.json"),
                {
                    "seq": k,
                    "processes": self.nproc,
                    "dp_global": self.dp_global,
                    "request_lines": [
                        self.pipelines[i].raw_line
                        for i in sorted(self.pipelines)
                    ],
                    # per-file integrity digests (restore verifies before
                    # trusting the generation; proc shards carry theirs
                    # in their own cursor metas)
                    "digests": fleet_digests,
                },
            )
        self.barrier()  # every process's files durable before the flip
        if self.pid == 0:
            _atomic_write_bytes(
                os.path.join(root, "LATEST"), f"ckpt-{k}".encode()
            )
            # retention: prune superseded snapshots (same policy as the
            # single-process CheckpointManager's keep/prune,
            # checkpoint/checkpoint.py) — only LATEST is ever restored,
            # a couple of spares survive a torn write of the newest
            keep = max(getattr(self.config, "checkpoint_keep", 3), 1)
            import shutil

            for name in os.listdir(root):
                if not name.startswith("ckpt-"):
                    continue
                try:
                    seq = int(name.split("-", 1)[1])
                except ValueError:
                    continue
                if seq <= k - keep:
                    shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        self.barrier()  # nobody races ahead of the visible pointer
        return d

    def _checkpoint_candidates(self, root: str) -> List[Tuple[int, str]]:
        """(seq, dir) of every snapshot under ``root``, newest first."""
        try:
            names = os.listdir(root)
        except OSError:
            return []
        out = []
        for name in names:
            if not name.startswith("ckpt-"):
                continue
            try:
                out.append(
                    (int(name.split("-", 1)[1]), os.path.join(root, name))
                )
            except ValueError:
                continue
        return sorted(out, reverse=True)

    def _validate_checkpoint(self, d: str) -> Optional[dict]:
        """Fully load-check every file THIS process needs from snapshot
        ``d`` (manifest, the proc shard pairs the rescale shard map hands
        it, every process's cursor meta, the fleet files); returns the
        manifest, or None — with the reason logged — when any file is
        missing, truncated, or undecodable. Loading every array is
        deliberate: a torn npz can open fine and fail only when its
        members decompress, and restore must never half-load. A snapshot
        from a DIFFERENT process count validates the shards this process
        will merge (``rescale_shard_map``) — unless rescale-restore is
        disabled, in which case only the manifest is checked (restore
        refuses with the actionable knob before touching any shard)."""
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
            net_ids = [
                int(json.loads(line)["id"])
                for line in manifest["request_lines"]
            ]
            old_n = int(manifest.get("processes", self.nproc))
            if old_n != self.nproc and not self.rescale_restore:
                return manifest
            # cursor metas of EVERY old process (the Kafka offset union
            # needs them all; cheap JSON reads) — each carries its own
            # shard's sha256
            shard_digests: Dict[str, Any] = {}
            for q in range(old_n):
                with open(os.path.join(d, f"proc{q}.json")) as f:
                    shard_digests[f"proc{q}.npz"] = json.load(f).get(
                        "sha256"
                    )
            digests = dict(manifest.get("digests") or {})
            digests.update(shard_digests)
            paths = [
                os.path.join(d, f"proc{q}.npz")
                for q in rescale_shard_map(old_n, self.nproc, self.pid)
            ] + [
                os.path.join(d, f"fleet_{net_id}.npz") for net_id in net_ids
            ]
            for path in paths:
                # integrity first: a recorded digest must match the bytes
                # on disk EXACTLY (catches corruptions np.load would
                # happily half-decode); snapshots from before the digest
                # era (no recorded digest) fall through to the load check
                recorded = digests.get(os.path.basename(path))
                if recorded and _file_sha256(path) != recorded:
                    raise ValueError(
                        f"sha256 mismatch on {os.path.basename(path)}"
                    )
                with np.load(path) as z:
                    for key in z.files:
                        _ = z[key]
            return manifest
        except Exception as exc:
            self._warn(
                f"snapshot {os.path.basename(d)} failed validation: "
                f"{type(exc).__name__}: {exc}"
            )
            from omldm_tpu.runtime.events import RESTORE

            # reason-coded restore decision: this generation is untrusted
            # and the fleet will fall back to the previous surviving one
            self._record_event(
                RESTORE, "candidate_rejected",
                snapshot=os.path.basename(d),
                error=f"{type(exc).__name__}: {exc}",
            )
            return None

    def _agree_restore_target(
        self, root: str
    ) -> Tuple[Optional[str], Optional[dict]]:
        """Pick the newest snapshot EVERY process can fully load. Each
        process validates candidates newest-first; the fleet agrees on the
        min of the per-process bests, re-validating until one snapshot is
        good everywhere — a corrupt/truncated/withheld shard on any
        process falls the whole fleet back to the previous complete
        snapshot instead of crashing or half-loading (the role of Flink
        discarding an incomplete checkpoint and restoring the last
        COMPLETED one)."""
        ceiling: Optional[int] = None
        while True:
            local_seq, local_manifest = -1, None
            for seq, d in self._checkpoint_candidates(root):
                if ceiling is not None and seq > ceiling:
                    continue
                manifest = self._validate_checkpoint(d)
                if manifest is not None:
                    local_seq, local_manifest = seq, manifest
                    break
            # fleet minimum of the per-process newest-valid seq
            agreed = int(round(
                -self._collective_reduce([-float(local_seq)], "max")[0]
            ))
            if agreed < 0:
                return None, None
            if agreed != local_seq:
                local_manifest = self._validate_checkpoint(
                    os.path.join(root, f"ckpt-{agreed}")
                )
            ok = 1.0 if local_manifest is not None else 0.0
            all_ok = -self._collective_reduce([-ok], "max")[0]
            if all_ok > 0.5:
                return os.path.join(root, f"ckpt-{agreed}"), local_manifest
            ceiling = agreed - 1

    def restore_checkpoint(self, root: str) -> Optional[Any]:
        """Resume every process from the latest CONSISTENT snapshot;
        returns this process's saved cursor (None when no usable snapshot
        exists). Must be called before any data is consumed, by every
        process (the fleet-state placement — and the agreement on which
        snapshot is loadable everywhere — is collective). A snapshot with
        a corrupt/truncated/missing shard is skipped in favor of the
        previous complete one; the LATEST pointer is repointed and the
        unusable snapshots pruned so later incarnations never retry
        them."""
        with self.hang_guard("restore"):
            return self._restore_checkpoint_guarded(root)

    def _restore_checkpoint_guarded(self, root: str) -> Optional[Any]:
        import jax

        latest = os.path.join(root, "LATEST")
        if not os.path.exists(latest) and not self._checkpoint_candidates(
            root
        ):
            return None
        d, manifest = self._agree_restore_target(root)
        if d is None:
            self._warn(
                "no usable distributed snapshot (every candidate failed "
                "validation on some process); starting fresh"
            )
            from omldm_tpu.runtime.events import RESTORE

            self._record_event(RESTORE, "no_usable_snapshot")
            return None
        pointed = d
        if os.path.exists(latest):
            with open(latest, "rb") as f:
                pointed = os.path.join(root, f.read().decode().strip())
        if os.path.abspath(pointed) != os.path.abspath(d):
            self._warn(
                f"falling back from {os.path.basename(pointed)} to "
                f"{os.path.basename(d)} (newer snapshot incomplete)"
            )
            if self.pid == 0:
                # repoint + prune: the unusable snapshots must not be
                # retried by a later incarnation, and the next save reuses
                # their seq numbers
                import shutil

                chosen_seq = int(os.path.basename(d).split("-", 1)[1])
                for seq, cand in self._checkpoint_candidates(root):
                    if seq > chosen_seq:
                        shutil.rmtree(cand, ignore_errors=True)
                _atomic_write_bytes(
                    latest, os.path.basename(d).encode()
                )
            self.barrier()  # nobody proceeds past a half-pruned root
        old_n = int(manifest["processes"])
        if old_n != self.nproc:
            if not self.rescale_restore:
                # reason-coded refusal, not a fleet crash: the operator
                # pinned the strict count contract, so degrade to the
                # fresh-start path (the caller redeploys the requests
                # file) and name the knob that re-enables elasticity
                self._warn(
                    f"snapshot {os.path.basename(d)} was taken with "
                    f"{old_n} processes but this fleet has {self.nproc}, "
                    "and rescale-restore is disabled (--rescaleRestore "
                    "false) — starting fresh. Relaunch with "
                    "--rescaleRestore true (the default) to redistribute "
                    "the snapshot across the new process count."
                )
                from omldm_tpu.runtime.events import RESTORE

                self._record_event(
                    RESTORE, "rescale_restore_disabled",
                    snapshot_procs=old_n, fleet_procs=self.nproc,
                )
                return None
            if not self._rescale_count_pinned:
                self.rescales_performed += 1
            self._warn(
                f"rescale-restore: redistributing a {old_n}-process "
                f"snapshot across {self.nproc} processes "
                f"(fleet rows {int(manifest['dp_global'])} -> "
                f"{self.dp_global}; source stripe re-agreed)"
            )
            from omldm_tpu.runtime.events import RESTORE

            self._record_event(
                RESTORE, "rescale_redistribution",
                snapshot_procs=old_n, fleet_procs=self.nproc,
                snapshot=os.path.basename(d),
            )
        if old_n == self.nproc and self.events is not None:
            from omldm_tpu.runtime.events import RESTORE

            self._record_event(
                RESTORE, "snapshot", snapshot=os.path.basename(d),
            )
        self._ckpt_seq = int(manifest["seq"]) + 1
        # redeploy the pipeline map from the recorded request lines (no
        # broadcast needed: every process reads the same manifest). A live
        # pipeline whose latest request was an Update redeploys as a Create
        # — the gatekeeper would reject an Update for a pipeline that does
        # not exist yet in this incarnation.
        import dataclasses as _dc

        for i, line in enumerate(manifest["request_lines"]):
            self._deploy_beat(i)
            request = Request.from_json(line)
            assert request is not None, "corrupt manifest request line"
            if request.request == RequestType.UPDATE:
                request = _dc.replace(request, request=RequestType.CREATE)
            self._deploy(request, line)
        from omldm_tpu.parallel.multihost import host_local_array
        from omldm_tpu.parallel.spmd import stacked, stored, stored_spec

        # shards this process merges (exactly [pid] when the count is
        # unchanged; the retiring shards' union on shrink; empty for a
        # grow-seeded new process) + every process's cursor meta (the
        # Kafka offset union needs them all)
        shards = rescale_shard_map(old_n, self.nproc, self.pid)
        all_metas: List[dict] = []
        for q in range(old_n):
            with open(os.path.join(d, f"proc{q}.json")) as f:
                all_metas.append(json.load(f))
        metas = [all_metas[q] for q in shards]
        self.orphan_predictions = [
            (int(n), float(v))
            for m in metas
            for n, v in m.get("orphan_predictions", [])
        ]
        if self.pid == 0:
            # responses live on old process 0's meta; shard 0 always maps
            # to new process 0 (0 % M == 0)
            self.responses.extend(
                QueryResponse.from_dict(r)
                for r in all_metas[0].get("responses", [])
            )
        shard_arrays = [
            np.load(os.path.join(d, f"proc{q}.npz")) for q in shards
        ]
        lo = self.pid * self.dp_local
        for net_id in sorted(self.pipelines):
            p = self.pipelines[net_id]
            fleet = np.load(os.path.join(d, f"fleet_{net_id}.npz"))
            # leaf index -> top-level state key (params/preps/ef/...) so
            # the rescale redistribution can apply per-leaf merge rules;
            # tree_flatten_with_path walks the same order tree_leaves
            # walked at save time (a file from before the state dropped an
            # unread ``est`` has it in between: _saved_leaf_positions)
            paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(
                p.trainer.state
            )
            saved_at = _saved_leaf_positions(
                p.trainer.state, p.trainer.protocol, len(fleet.files),
                os.path.join(d, f"fleet_{net_id}.npz"),
            )
            placed = []
            for i, (path, live) in zip(saved_at, paths_leaves):
                key = str(getattr(path[0], "key", path[0]))
                # saved stored; redistributed by worker row in the
                # [dp, hub, ...] view; placed stored again. The stored
                # leading axis is proportional to the fleet's worker rows.
                saved = stored(fleet[f"leaf_{i}"])
                dp_saved = saved.shape[0] * self.dp_global // live.shape[0]
                full = _rescale_fleet_leaf(
                    stacked(saved, dp_saved, p.trainer.hub),
                    key, self.dp_global,
                )
                local = stored(full[lo : lo + self.dp_local])
                placed.append(
                    host_local_array(local, self.mesh, stored_spec(local))
                )
            p.trainer.state = jax.tree_util.tree_unflatten(treedef, placed)
            pms = [m["pipelines"][str(net_id)] for m in metas]
            # additive per-partition counters SUM across merged shards;
            # lockstep counters (collective step counts) are identical on
            # every process at a synchronized cut, so max == any
            p.holdout_count = sum(int(pm["holdout_count"]) for pm in pms)
            p.trainer._fitted_host = sum(int(pm["fitted"]) for pm in pms)
            p.trainer._steps_host = max(
                (int(pm["steps_host"]) for pm in pms), default=0
            )
            p.trainer.requeued_rows = sum(int(pm["requeued"]) for pm in pms)
            p.steps_run = max((int(pm["steps_run"]) for pm in pms), default=0)
            p.predictions = [float(v) for pm in pms for v in pm["predictions"]]
            # the learning curve is fleet-global (collectively reduced at
            # save time): the first merged shard speaks for everyone, and
            # a grow-seeded process adopts old process 0's copy
            curve_src = pms[0] if pms else all_metas[0]["pipelines"].get(
                str(net_id), {"curve": [], "global_rows": 0}
            )
            p.curve = [(float(l), int(r)) for l, r in curve_src["curve"]]
            p.global_rows = int(curve_src["global_rows"])
            if shard_arrays:
                self._restore_buffers(p, net_id, shard_arrays)
        return _merge_cursors([m["cursor"] for m in all_metas])

    def _restore_buffers(
        self, p: _DistPipeline, net_id: int, shard_arrays: List[Any]
    ) -> None:
        """Merge the staged pending/forecast/holdout buffers of every
        checkpoint shard this process owns (one shard on a same-count
        restore; the retiring stripes' union on shrink — rows interleave
        round-robin so the merged buffers stay a fair stream-order mix,
        the in-process absorb's holdout-interleave semantics)."""
        pend = [a[f"n{net_id}_pend_x"] for a in shard_arrays]
        if sum(b.shape[0] for b in pend):
            perm = _interleave_perm([b.shape[0] for b in pend])
            p.pend_x = [np.concatenate(pend)[perm]]
            if p.sparse:
                p.pend_v = [
                    np.concatenate(
                        [a[f"n{net_id}_pend_v"] for a in shard_arrays]
                    )[perm]
                ]
            p.pend_y = [
                np.concatenate(
                    [a[f"n{net_id}_pend_y"] for a in shard_arrays]
                )[perm]
            ]
            p.pend_n = int(p.pend_x[0].shape[0])
        fore = [a[f"n{net_id}_fore_x"] for a in shard_arrays]
        if sum(b.shape[0] for b in fore):
            perm = _interleave_perm([b.shape[0] for b in fore])
            p.fore_x = [np.concatenate(fore)[perm]]
            if p.sparse:
                p.fore_v = [
                    np.concatenate(
                        [a[f"n{net_id}_fore_v"] for a in shard_arrays]
                    )[perm]
                ]
            p.fore_n = int(p.fore_x[0].shape[0])
        test = [a[f"n{net_id}_test_x"] for a in shard_arrays]
        if sum(b.shape[0] for b in test):
            perm = _interleave_perm([b.shape[0] for b in test])
            tx = np.concatenate(test)[perm]
            ty = np.concatenate(
                [a[f"n{net_id}_test_y"] for a in shard_arrays]
            )[perm]
            # merged holdouts can overflow the ring (shrink folds several
            # full rings into one): evicted rows RE-FEED the training
            # buffer, exactly what the live holdout split does with its
            # evictions (_buffer_rows) — rows conserve across a rescale,
            # none vanish with the retired partitions
            if p.sparse:
                tv = np.concatenate(
                    [a[f"n{net_id}_test_v"] for a in shard_arrays]
                )[perm]
                ev_i, ev_v, ev_y, ev_src = p.test_set.append_many(tx, tv, ty)
                if ev_src.size:
                    p.pend_x.append(np.asarray(ev_i, np.int32))
                    p.pend_v.append(np.asarray(ev_v, np.float32))
                    p.pend_y.append(np.asarray(ev_y, np.float32))
                    p.pend_n += int(ev_src.size)
            else:
                ev_x, ev_y, ev_src = p.test_set.append_many(tx, ty)
                if ev_src.size:
                    p.pend_x.append(np.asarray(ev_x, np.float32))
                    p.pend_y.append(np.asarray(ev_y, np.float32))
                    p.pend_n += int(ev_src.size)


# --- drive loops -----------------------------------------------------------


def _manifest_is_sparse(flags: Dict[str, str]) -> bool:
    """Restores skip the requests file, so the drive-mode choice sniffs
    the snapshot manifests' recorded Create lines. Sparsity is a
    job-level property (the stream mode is pinned by the first deploy and
    recorded in every snapshot), so when the newest manifest is
    unreadable — the corrupt-snapshot case restore itself falls back
    from — ANY readable candidate answers the question."""
    root = flags.get("checkpointDir")
    if not root:
        return False
    candidates = []
    latest = os.path.join(root, "LATEST")
    if os.path.exists(latest):
        with open(latest, "rb") as f:
            candidates.append(os.path.join(root, f.read().decode().strip()))
    try:
        names = [
            n for n in os.listdir(root)
            if n.startswith("ckpt-") and n.split("-", 1)[1].isdigit()
        ]
    except OSError:
        names = []
    names.sort(key=lambda n: -int(n.split("-", 1)[1]))
    candidates += [os.path.join(root, n) for n in names]
    for d in candidates:
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            continue  # unreadable: restore falls back the same way
        for line in manifest.get("request_lines", []):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            ds = (obj.get("learner") or {}).get("dataStructure") or {}
            if ds.get("sparse"):
                return True
        return False  # first READABLE manifest decides
    return False


def _flag_true(flags: Dict[str, str], key: str) -> bool:
    return flags.get(key, "").lower() in ("true", "1", "yes")


def _heartbeat(flags: Dict[str, str], pid: int, frame=0) -> bool:
    """Touch this process's heartbeat file (the supervisor's liveness
    channel). Called at every synchronized pump point, so a process wedged
    in a collective (peer died) stops beating and gets detected. The file
    body is the compact metrics frame
    ``<epoch> <pressure-level> [key=value ...]`` — token 2 is the
    window-peak overload level and the key=value tail carries the
    host-plane signals (``serveP99``/``imbalance``/``backlog``) the
    autoscaling supervisor folds across the fleet
    (supervisor._beat_frame; a bare int ``frame`` writes the legacy
    two-token form). Absent/zero when the overload plane is unarmed.
    Returns False when the disk refused the write (ENOSPC survival: the
    caller counts the dropped beat, the worker keeps running)."""
    d = flags.get("heartbeatDir")
    if not d:
        return True
    if isinstance(frame, dict):
        level = int(frame.get("level", 0))
        tail = "".join(
            f" {k}={frame[k]}"
            for k in ("serveP99", "imbalance", "backlog", "events",
                      "alerts")
            if k in frame
        )
    else:
        level, tail = int(frame), ""
    try:
        os.makedirs(d, exist_ok=True)
        # atomic replace: the supervisor polls this file between writes,
        # and a torn read of a truncate-in-progress beat would feed the
        # autoscaler a phantom level-0 sample mid-burst
        path = os.path.join(d, f"proc{pid}.hb")
        with open(path + ".tmp", "w") as f:
            f.write(f"{time.time()} {level}{tail}")
        os.replace(path + ".tmp", path)
        return True
    except OSError:
        return False  # a full/odd disk must not kill the job over telemetry


def _maybe_rescale_exit(
    job: DistributedStreamJob, flags: Dict[str, str], cursor: Any
) -> None:
    """Honor a standing rescale signal from the autoscaling supervisor:
    process 0 reads the target process count from the signal file, the
    fleet AGREES on it over the fabric (file visibility can race between
    processes — an unagreed exit would wedge the survivors in their next
    collective), snapshots the consistent cut, and every process exits
    with the rescale code so the supervisor relaunches at the new count
    with ``--restore``. No signal dir armed (the default) => zero cost,
    no extra collectives."""
    sig_dir = flags.get("rescaleSignalDir")
    if not sig_dir:
        return
    target = 0
    if job.pid == 0:
        try:
            with open(os.path.join(sig_dir, "RESCALE")) as f:
                target = int(f.read().strip() or 0)
        except (OSError, ValueError):
            target = 0
    agreed = int(job._collective_reduce([float(target)], "max")[0])
    if agreed <= 0 or agreed == job.nproc:
        return
    root = flags.get("checkpointDir")
    if not root:
        # without a checkpoint dir the relaunch would lose all state;
        # refuse loudly (the supervisor refuses to arm autoscale without
        # one, so this is a manually-miswired fleet)
        job._warn(
            "rescale signal ignored: no --checkpointDir to carry state "
            "across the relaunch"
        )
        return
    d = job.save_checkpoint(root, cursor)
    job._warn(
        f"rescale signal honored: snapshot {os.path.basename(d)} taken, "
        f"fleet exiting to relaunch at {agreed} processes"
    )
    if job.events is not None:
        from omldm_tpu.runtime.events import RESCALE

        job.events.record(
            RESCALE, "supervisor_signal_agreed",
            from_procs=job.nproc, to_procs=agreed,
            snapshot=os.path.basename(d),
        )
        # the pre-rescale ring must survive the process exit: this dump
        # is what the supervisor's incident bundle reads
        job.events.incident("rescale")
    from omldm_tpu.runtime.supervisor import RESCALE_EXIT

    raise SystemExit(RESCALE_EXIT)


def _make_injector(job: DistributedStreamJob, flags: Dict[str, str]):
    from omldm_tpu.runtime.supervisor import DistributedFaultInjector

    injector = DistributedFaultInjector(flags, job.pid)
    # launch-refusal fault: fires HERE, before this process's first
    # heartbeat, so the supervisor's classifier sees a worker that died
    # without ever coming up (the LAUNCH class)
    injector.on_launch()
    return injector


def _sync_requests_from_flags(
    job: DistributedStreamJob, flags: Dict[str, str]
) -> None:
    """Deploy the --requests file (process 0 reads, everyone syncs)."""
    lines: List[str] = []
    if job.pid == 0 and flags.get("requests"):
        with open(flags["requests"]) as f:
            lines = [l.strip() for l in f if l.strip()]
    job.sync_requests(lines)


def _load_request_schedule(
    flags: Dict[str, str]
) -> List[Tuple[int, str]]:
    """The count-clocked mid-stream request schedule (--requestSchedule):
    JSONL ``{"atRecord": N, "request": {...}}`` entries, sorted by
    position. EVERY process reads the shared file and computes dueness
    locally from the cursor (identical across processes), so the
    collective sync fires only at pump points where something is due —
    the deterministic, replayable stand-in for the Kafka requests topic's
    wall-clock polling."""
    path = flags.get("requestSchedule")
    if not path:
        return []
    entries: List[Tuple[int, str]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            entries.append(
                (int(obj["atRecord"]), json.dumps(obj["request"]))
            )
    entries.sort(key=lambda e: e[0])
    return entries


def _schedule_start(
    schedule: List[Tuple[int, str]], resume_cursor: int
) -> int:
    """First schedule index NOT yet delivered at ``resume_cursor``:
    entries at/before the checkpoint cut were applied pre-snapshot and
    live in the restored manifest — redelivering them would double-churn
    the topology."""
    i = 0
    while i < len(schedule) and schedule[i][0] <= resume_cursor:
        i += 1
    return i


def _deliver_due_requests(
    job: DistributedStreamJob,
    schedule: List[Tuple[int, str]],
    idx: int,
    cursor: int,
) -> int:
    """Deliver every schedule entry with ``atRecord <= cursor`` (one
    collective sync for the batch); returns the advanced index. Called at
    the synchronized pump point BEFORE the checkpoint cadence, so a
    snapshot at this cut already contains the new topology."""
    if idx >= len(schedule) or schedule[idx][0] > cursor:
        return idx
    due: List[str] = []
    while idx < len(schedule) and schedule[idx][0] <= cursor:
        due.append(schedule[idx][1])
        idx += 1
    job.sync_requests(due if job.pid == 0 else [])
    return idx


def _restore_or_fresh(job: DistributedStreamJob, flags: Dict[str, str]):
    """Restore the latest consistent snapshot; when NO candidate is usable
    (all corrupt/withheld — restore_checkpoint already warned), degrade to
    a fresh run by redeploying the requests file instead of dying with no
    pipelines — Flink's behavior for a job restarted without a completed
    checkpoint. Returns the restored cursor or None."""
    cur = job.restore_checkpoint(flags["checkpointDir"])
    if cur is None and not job.pipelines:
        _sync_requests_from_flags(job, flags)
    return cur


def _chunk_tick(
    job: DistributedStreamJob, flags: Dict[str, str],
    chunk_idx: int, cursor: Any, injector, records: int = 0,
) -> None:
    """One synchronized pump point: heartbeat, checkpoint cadence, fault
    injection. Every process evaluates the same checkpoint condition at
    the same chunk index, so snapshots are collective-consistent; injected
    crashes fire here too, so a kill lands at one well-defined cut (the
    supervisor then relaunches the fleet with --restore, Flink's
    global-restart strategy)."""
    if not _heartbeat(flags, job.pid, job.heartbeat_frame()):
        # dropped-write counter, not a dead worker (ENOSPC survival);
        # surfaces as blackboxWriteErrors in the job report
        job.hb_write_errors += 1
    job.note_event_records(records)
    if job.events is not None and job.events.dirty:
        # dump-on-dirty: decision events are rare on this engine, so the
        # atomic ring rewrite is rare too — and a worker killed between
        # ticks leaves a near-current black box for the bundle
        job.events.dump()
    every = int(flags.get("checkpointEvery", "0"))
    root = flags.get("checkpointDir")
    if every > 0 and root and (chunk_idx + 1) % every == 0:
        d = job.save_checkpoint(root, cursor)
        injector.on_checkpoint(d)
    injector.note_records(records)
    injector.on_chunk(chunk_idx)
    # autoscaling: a supervisor-issued rescale signal checkpoints this
    # consistent cut and exits the fleet for a relaunch at the new count
    _maybe_rescale_exit(job, flags, cursor)


def _sparse_tools(job: DistributedStreamJob):
    """(SparseFastParser, SparseVectorizer) for the job's pinned COO
    layout — shared by the file and Kafka sparse drives."""
    from omldm_tpu.ops.native import SparseFastParser
    from omldm_tpu.runtime.vectorizer import SparseVectorizer

    p0 = next(iter(job.pipelines.values()))
    dense_budget = job.dim - job.sparse_hash_space
    parser = SparseFastParser(
        dense_budget, job.sparse_hash_space, p0.max_nnz
    )
    vec = SparseVectorizer(job.dim, job.sparse_hash_space, p0.max_nnz)
    return parser, vec


def _consume_sparse_block(
    job: DistributedStreamJob, parser, vec, block: bytes,
    line_base: int, nproc: int, pid: int, force_forecast: bool = False,
) -> int:
    """Parse a line-aligned COO block, keep this process's stride (row
    line_base+i belongs to process (line_base+i) % nproc — pass nproc=1
    for Kafka mode, where partition assignment already partitioned the
    stream), and buffer train/forecast rows for every pipeline. Rows the
    C parser defers (valid == 2: escaped categoricals, odd shapes) route
    through the Python codec at their stream position. Returns the number
    of lines consumed."""
    from omldm_tpu.api.data import FORECASTING, DataInstance
    from omldm_tpu.runtime.vectorizer import F32_MAX

    idx, val, y, op, valid = parser.parse(block)
    n = idx.shape[0]
    if n == 0:
        return 0
    gidx = line_base + np.arange(n)
    mine = (gidx % nproc) == pid
    fast = mine & (valid == 1)
    if force_forecast:
        fore = fast
        train = np.zeros_like(fast)
    else:
        train = fast & (op == 0)
        fore = fast & (op != 0)
    # specials interleave with fast rows in stream order (same contract
    # as the single-process COO bridge): split the block at fallback rows
    fb = np.nonzero(mine & (valid == 2))[0]
    if not fb.size:
        if train.any():
            job.handle_partition_rows_sparse(idx[train], val[train], y[train])
        if fore.any():
            job.handle_forecast_rows_sparse(idx[fore], val[fore])
        return n
    lines = block.split(b"\n")
    prev = 0
    for s in list(fb) + [n]:
        s = int(s)
        seg = slice(prev, s)
        seg_train = train[seg]
        seg_fore = fore[seg]
        if seg_train.any():
            job.handle_partition_rows_sparse(
                idx[seg][seg_train], val[seg][seg_train], y[seg][seg_train]
            )
        if seg_fore.any():
            job.handle_forecast_rows_sparse(
                idx[seg][seg_fore], val[seg][seg_fore]
            )
        if s >= n:
            break
        inst = DataInstance.from_json(
            lines[s].decode("utf-8", errors="replace")
        )
        if inst is not None:
            i1, v1 = vec.vectorize(inst)
            if force_forecast or inst.operation == FORECASTING:
                job.handle_forecast_rows_sparse(i1[None], v1[None])
            else:
                yv = (
                    0.0 if inst.target is None
                    else float(min(max(float(inst.target), -F32_MAX), F32_MAX))
                )
                job.handle_partition_rows_sparse(
                    i1[None], v1[None], np.asarray([yv], np.float32)
                )
        prev = s + 1
    return n


def _drive_file_sparse(job: DistributedStreamJob, flags: Dict[str, str]) -> None:
    """Sparse (padded-COO) file drive: line-aligned chunks through the C
    COO parser, row i striped to process i % nproc — the sparse twin of
    the dense strided drive. Checkpoint cursors record the line-aligned
    BYTE offset plus the global line count (both needed: bytes to seek,
    lines to keep the stripe phase)."""
    from omldm_tpu.runtime.spmd_bridge import _line_aligned_chunks

    resume = {"bytes": 0, "lines": 0}
    if _flag_true(flags, "restore") and flags.get("checkpointDir"):
        cur = _restore_or_fresh(job, flags)
        if cur is not None:
            resume = dict(cur)
            job._warn(f"restored; resuming at {resume}")
    assert job.dim is not None, "no pipeline deployed and no snapshot found"
    injector = _make_injector(job, flags)
    parser, vec = _sparse_tools(job)
    chunk_rows = int(flags.get("chunkRows", str(CHUNK_ROWS)))
    # size chunks in bytes from a crude per-line estimate; pump cadence
    # only needs to be IDENTICAL across processes, which byte-chunking is
    chunk_bytes = max(chunk_rows * 256, 1 << 16)
    consumed = int(resume["bytes"])
    line_base = int(resume["lines"])
    chunk_idx = 0
    for buf, stop in _line_aligned_chunks(
        flags["trainingData"], chunk_bytes, start_offset=consumed
    ):
        block = bytes(memoryview(buf)[:stop])
        n = _consume_sparse_block(
            job, parser, vec, block, line_base, job.nproc, job.pid
        )
        line_base += n
        consumed += stop
        job.pump()
        _chunk_tick(
            job, flags, chunk_idx,
            {"bytes": consumed, "lines": line_base},
            injector, records=n,
        )
        chunk_idx += 1
    job.flush()


def _drive_file(job: DistributedStreamJob, flags: Dict[str, str]) -> None:
    """Strided partition of a shared JSON-lines file: row i belongs to
    process i % nproc (the deterministic stand-in for a Kafka partition
    assignment; the whole-file read models the shared offsets). Uses the
    same fused C ingest parser as the single-process CLI."""
    from omldm_tpu.runtime.fast_ingest import iter_file_batches

    resume_cursor = 0
    if _flag_true(flags, "restore") and flags.get("checkpointDir"):
        cur = _restore_or_fresh(job, flags)
        if cur is not None:
            resume_cursor = int(cur)
            job._warn(f"restored; resuming at row {resume_cursor}")
    assert job.dim is not None, "no pipeline deployed and no snapshot found"
    injector = _make_injector(job, flags)
    schedule = _load_request_schedule(flags)
    sched_idx = _schedule_start(schedule, resume_cursor)
    cursor = 0
    chunk_idx = 0
    chunk_rows = int(flags.get("chunkRows", str(CHUNK_ROWS)))
    for bx, by, bop in iter_file_batches(
        flags["trainingData"], job.dim, chunk_rows, job.hash_dims
    ):
        n = bx.shape[0]
        if cursor + n <= resume_cursor:
            cursor += n
            continue
        if cursor < resume_cursor:
            skip = resume_cursor - cursor
            bx, by, bop = bx[skip:], by[skip:], bop[skip:]
            cursor = resume_cursor
            n = bx.shape[0]
        gidx = cursor + np.arange(n)
        mine = (gidx % job.nproc) == job.pid
        cursor += n
        train = mine & (bop == 0)
        if train.any():
            job.handle_partition_rows(bx[train], by[train])
        fore = mine & (bop != 0)
        if fore.any():
            job.handle_forecast_rows(bx[fore])
        # synchronized pump point: every process sees the same chunk
        # sequence. Scheduled requests land BEFORE the checkpoint cadence
        # so a snapshot at this cut carries the new topology (a restore
        # never redelivers them — _schedule_start skips the applied
        # prefix)
        job.pump()
        sched_idx = _deliver_due_requests(job, schedule, sched_idx, cursor)
        _chunk_tick(job, flags, chunk_idx, cursor, injector, records=n)
        chunk_idx += 1
    # entries scheduled past the end of the stream still belong to the
    # storm: deliver them at the final cut instead of dropping silently
    if sched_idx < len(schedule):
        sched_idx = _deliver_due_requests(
            job, schedule, sched_idx, schedule[-1][0]
        )
    job.flush()


def _tp_key(tp) -> str:
    return f"{tp.topic}:{tp.partition}"


def _drive_kafka(job: DistributedStreamJob, flags: Dict[str, str]) -> None:
    """Partitioned Kafka ingest: each process consumes an ASSIGNED set of
    partitions (partition index mod nproc — Flink's static per-subtask
    assignment, KafkaUtils.scala:11-31 / README.md:22-26, rather than
    broker-side group rebalance), tracks per-partition offsets for
    checkpointing, and pumps at synchronized poll windows. Mid-stream
    requests are polled from the requests topic by process 0 and broadcast
    over the fabric each window. Record values are parsed by the fused C
    ingest parser (PackedBatcher), one batcher per topic so forecast-topic
    records are forced to the forecast operation like the single-process
    sources."""
    try:
        from kafka import KafkaConsumer, TopicPartition
    except ImportError as e:
        raise ImportError(
            "Kafka ingest needs the 'kafka-python' package (or an injected "
            "compatible module); use --trainingData file replay otherwise."
        ) from e
    from omldm_tpu.runtime.fast_ingest import PackedBatcher

    brokers = flags["kafkaBrokers"]
    train_topic = flags.get("kafkaTrainTopic", "trainingData")
    fore_topic = flags.get("kafkaForecastTopic", "forecastingData")
    req_topic = flags.get("kafkaRequestTopic", "requests")
    poll_ms = int(flags.get("kafkaPollMs", "300"))

    offsets: Dict[str, int] = {}
    req_offsets: Dict[str, int] = {}
    if _flag_true(flags, "restore") and flags.get("checkpointDir"):
        cur = job.restore_checkpoint(flags["checkpointDir"])
        if cur is not None:
            offsets = dict(cur.get("data", {}))
            req_offsets = dict(cur.get("requests", {}))
            job._warn(f"restored; resuming at offsets {offsets}")

    injector = _make_injector(job, flags)
    consumer = KafkaConsumer(
        bootstrap_servers=brokers, consumer_timeout_ms=poll_ms
    )
    # broker chaos (--kafkaChaos flag / OMLDM_CHAOS_KAFKA env): seeded
    # drop/dup/reorder on the DATA record stream — dropped records'
    # offsets are never committed, so checkpoint/restore replays them:
    # at-least-once, exactly the reference's Kafka source contract. The
    # control (requests) consumer stays clean: duplicated Creates are
    # dropped by the admit gate anyway, but lost ones would change the
    # topology
    from omldm_tpu.runtime.supervisor import maybe_chaos_consumer

    consumer = maybe_chaos_consumer(consumer, flags, name=f"kafka-p{job.pid}")

    def _partitions(client, topic, retries=5):
        # metadata fetch through the shared backoff helper (no hand-rolled
        # sleep loops); [] after the budget keeps the degrade path
        import dataclasses as _dc

        from omldm_tpu.runtime.kafka_io import (
            CONNECT_RETRY,
            _partitions_with_retry,
        )

        policy = _dc.replace(CONNECT_RETRY, attempts=retries)
        return sorted(_partitions_with_retry(client, topic, policy) or [])

    def _seek_or_resume(client, tp, saved_offsets):
        """Seek to the snapshot offset, else to the LOG START — recording
        the broker-reported position (not a literal 0: a retention-trimmed
        partition starts later, and checkpointing 0 would make restore
        seek out of range and silently fall back to 'latest')."""
        saved = saved_offsets.get(_tp_key(tp))
        if saved is not None:
            client.seek(tp, saved)
            return
        # bounded experiment streams consume from the start (the
        # reference's runs pre-load partitioned topics, README.md:22-26)
        client.seek_to_beginning(tp)
        try:
            saved_offsets[_tp_key(tp)] = int(client.position(tp))
        except Exception:
            saved_offsets[_tp_key(tp)] = 0

    # partition -> process assignment: partition p of topic t belongs to
    # process p % nproc (Flink's static per-subtask assignment, PER TOPIC
    # so a topic discovered later never shifts an earlier topic's
    # striping). Process 0's metadata view is AUTHORITATIVE and travels
    # over the fabric: independently-retried partitions_for_topic views
    # can diverge on freshly-created topics, which would silently
    # double-assign or drop partitions if each process striped its own
    # list. Topics still absent (auto-created later — the supported
    # late-start pattern the startup idle bound waits through) are
    # re-probed every window until found, INDEPENDENTLY per topic.
    assigned: List[Any] = []
    undiscovered = [train_topic, fore_topic]
    # rotating stripe base: partition p of the i-th discovered partition
    # group goes to process (p + base) % nproc, base advancing by each
    # group's size — so single-partition topics SPREAD across processes
    # instead of all landing on process 0. Discovery events arrive in
    # broadcast order, so every process advances the base identically.
    stripe_base = [0]

    def _assign_partitions(retries: int) -> None:
        assign_payload: List[str] = []
        if job.pid == 0:
            found = {
                topic: _partitions(consumer, topic, retries)
                for topic in undiscovered
            }
            assign_payload = [json.dumps({"assign": found})]
        [assign_line] = job._broadcast_lines(assign_payload)
        found = json.loads(assign_line)["assign"]
        changed = False
        # iterate in the stable (train, fore) order, not dict order
        for topic in [t for t in (train_topic, fore_topic) if t in found]:
            parts = found[topic]
            if not parts:
                continue
            undiscovered.remove(topic)
            changed = True
            base = stripe_base[0]
            stripe_base[0] += len(parts)
            assigned.extend(
                TopicPartition(topic, p)
                for p in parts if (p + base) % job.nproc == job.pid
            )
        if changed and assigned:
            consumer.assign(assigned)
            for tp in assigned:
                _seek_or_resume(consumer, tp, offsets)

    _assign_partitions(retries=5)
    # process 0 owns the request topic (single-partition control stream);
    # its offsets are checkpointed too — replaying the whole topic on a
    # restore would re-run Updates (wiping the restored model) and
    # re-answer Queries. Like the data topics, a requests topic
    # auto-created after launch is re-probed each window.
    req_consumer = None
    req_assigned = [False]
    if job.pid == 0:
        req_consumer = KafkaConsumer(
            bootstrap_servers=brokers, consumer_timeout_ms=poll_ms
        )

    def _assign_requests(retries: int) -> None:
        # process-0-local (no collective): only it polls the topic
        if req_consumer is None or req_assigned[0]:
            return
        req_tps = [
            TopicPartition(req_topic, p)
            for p in _partitions(req_consumer, req_topic, retries)
        ]
        if req_tps:
            req_assigned[0] = True
            req_consumer.assign(req_tps)
            for tp in req_tps:
                _seek_or_resume(req_consumer, tp, req_offsets)

    _assign_requests(retries=5)

    chunk_rows = int(flags.get("chunkRows", str(CHUNK_ROWS)))
    # batchers are built once the stream width is known (the first Create
    # may arrive on the requests topic mid-run); until then data partitions
    # are simply not polled, so their offsets — and the records — wait in
    # the broker exactly as they would for a slow Flink subtask. A sparse
    # stream swaps in the COO parser (partition assignment already
    # partitioned the stream, so no row striding: nproc=1 in the helper).
    batchers: Dict[str, Any] = {}
    sparse_tools = [None]

    def _ensure_batchers():
        if not batchers and job.dim is not None:
            if job.stream_mode == "sparse":
                sparse_tools[0] = _sparse_tools(job)
                batchers[train_topic] = "sparse"
                batchers[fore_topic] = "sparse"
            else:
                batchers[train_topic] = PackedBatcher(
                    job.dim, chunk_rows, job.hash_dims
                )
                batchers[fore_topic] = PackedBatcher(
                    job.dim, chunk_rows, job.hash_dims
                )
        return bool(batchers)

    def _feed(topic, batches):
        for bx, by, bop in batches:
            if topic == fore_topic:
                job.handle_forecast_rows(bx)
            else:
                train = bop == 0
                if train.any():
                    job.handle_partition_rows(bx[train], by[train])
                if (~train).any():
                    job.handle_forecast_rows(bx[~train])

    def _feed_window(topic, wb):
        """One bulk parse per topic per poll window."""
        if batchers[topic] == "sparse":
            parser, vec = sparse_tools[0]
            _consume_sparse_block(
                job, parser, vec, bytes(wb), 0, 1, 0,
                force_forecast=(topic == fore_topic),
            )
        else:
            _feed(topic, batchers[topic].feed_buffer(wb, 0, len(wb)))

    chunk_idx = 0
    idle_windows = 0
    idle_limit = int(flags.get("idleWindows", "2"))
    startup_limit = int(flags.get("startupIdleWindows", "600"))
    # restores count as deployed: the manifest already rebuilt pipelines
    ever_deployed = bool(job.pipelines)
    # upstream backpressure (runtime/overload.py): while this process's
    # staging backlog is past backlogCritical its DATA partitions pause —
    # records wait in the broker (offsets uncommitted, replayable) while
    # pump() drains the backlog; the requests consumer never pauses (the
    # control plane must keep flowing). State is per process.
    data_paused = [False]
    overload_armed = job.overload_cfg is not None
    while True:
        # 1. control plane: new request lines, broadcast to everyone
        req_lines: List[str] = []
        if req_consumer is not None:
            _assign_requests(retries=1)
            while True:
                try:
                    rec = next(req_consumer)
                except StopIteration:
                    break
                req_offsets[_tp_key(rec)] = rec.offset + 1
                v = rec.value
                req_lines.append(
                    v.decode("utf-8", "replace") if isinstance(v, bytes) else v
                )
        job.sync_requests(req_lines)
        # 1b. late partition discovery: data topics auto-created after
        # launch get assigned once their metadata appears (single attempt
        # per window; the decision to re-try is broadcast-agreed, so every
        # process keeps issuing the same collectives)
        if undiscovered:
            _assign_partitions(retries=1)
            # a re-assign rebuilds the consumer's partition state and
            # silently DROPS any standing pause (kafka-python semantics)
            # — mark the valve open so the block below re-issues the
            # pause immediately while the level is still CRITICAL
            data_paused[0] = False
        # 1c. overload backpressure valve (pause/resume are best-effort:
        # test fakes without the kafka-python API just skip the pause and
        # rely on the chunk_rows poll bound)
        if overload_armed and assigned:
            level = job.overload_level()
            if level >= 2 and not data_paused[0]:
                pause = getattr(consumer, "pause", None)
                if pause is not None:
                    pause(*assigned)
                    data_paused[0] = True
                    job._warn(
                        f"overload CRITICAL (backlog {job.backlog_rows()} "
                        "rows): pausing data consumption"
                    )
                    from omldm_tpu.runtime.events import PAUSE

                    job._record_event(
                        PAUSE, "overload_critical",
                        backlog=job.backlog_rows(),
                    )
            elif level < 2 and data_paused[0]:
                resume = getattr(consumer, "resume", None)
                if resume is not None:
                    resume(*assigned)
                data_paused[0] = False
                job._warn("overload cleared: resuming data consumption")
                from omldm_tpu.runtime.events import PAUSE

                job._record_event(PAUSE, "overload_cleared")
        # 2. data: drain this window's records from the assigned
        # partitions. Record values are ACCUMULATED into one line buffer
        # per topic and parsed with a single bulk C call per topic per
        # window — per-record feed_buffer calls would pay a Python/ctypes
        # round trip per line and forfeit the block parser.
        had_rows = 0
        polled = 0
        win_bufs = {t: bytearray() for t in batchers} if _ensure_batchers() else {}
        while win_bufs and polled < chunk_rows:
            try:
                rec = next(consumer)
            except StopIteration:
                break
            polled += 1
            had_rows = 1
            offsets[_tp_key(rec)] = rec.offset + 1
            wb = win_bufs.get(rec.topic)
            if wb is None:
                continue
            v = rec.value
            wb += v if isinstance(v, bytes) else str(v).encode()
            if not wb.endswith(b"\n"):
                wb += b"\n"
        for topic, wb in win_bufs.items():
            if wb:
                _feed_window(topic, wb)
        for topic, b in batchers.items():
            if b == "sparse":
                continue  # the COO parser consumes whole windows, no tail
            tail = b.flush()
            if tail:
                _feed(topic, [tail])
        # 3. synchronized pump + checkpoint cadence
        job.pump()
        _chunk_tick(
            job, flags, chunk_idx,
            {"data": offsets, "requests": req_offsets},
            injector, records=polled,
        )
        chunk_idx += 1
        # 4. agreed termination: stop after idleWindows globally-idle poll
        # windows (the silence-timer termination of
        # StatisticsOperator.scala:135-142, with the timeout measured in
        # fabric-agreed windows). Before ANY pipeline exists the much
        # larger startup bound applies — a live job must not die in the
        # first second waiting for its Create to reach the requests topic.
        # (job.pipelines is identical on every process: the control plane
        # is broadcast, so this branch needs no extra collective.)
        globally_quiet = job._collective_reduce(
            [float(had_rows + len(req_lines))], "sum"
        )[0] == 0
        if overload_armed:
            # a backpressure PAUSE must not count toward the idle
            # termination bound — the fleet is overloaded, not done.
            # Collective-agreed (every process issues the reduce, armed
            # is config-identical) so the break decision stays lockstep.
            any_paused = job._collective_reduce(
                [float(data_paused[0])], "max"
            )[0] > 0
            if any_paused:
                globally_quiet = False
        ever_deployed = ever_deployed or bool(job.pipelines)
        if globally_quiet:
            idle_windows += 1
            # once ANY pipeline has existed the short bound applies —
            # a Delete of the last pipeline means the job's work is done,
            # not that it should re-enter the startup grace period
            limit = idle_limit if ever_deployed else startup_limit
            if idle_windows >= limit:
                if not ever_deployed:
                    job._warn(
                        "no Create arrived within the startup idle bound; "
                        "terminating with nothing deployed"
                    )
                break
        else:
            idle_windows = 0
    job.flush()
    consumer.close()
    if req_consumer is not None:
        req_consumer.close()


def run_distributed(argv: Optional[List[str]] = None) -> int:
    # --supervise: this process becomes the fleet supervisor instead of a
    # worker — it spawns/monitors the N worker processes and applies the
    # fixed-delay restart policy (it never initializes jax itself)
    from omldm_tpu.__main__ import parse_flags as _parse_flags

    pre_flags = _parse_flags(list(argv or []))
    if _flag_true(pre_flags, "supervise"):
        from omldm_tpu.runtime.supervisor import supervise_from_flags

        return supervise_from_flags(pre_flags)

    flags = pre_flags
    # persistent XLA compile cache: restarted incarnations (and every
    # process after the first on a shared cache) skip recompiling the
    # collective programs — supervised recovery would otherwise pay tens
    # of seconds of compile on each restart
    from omldm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(flags.get("compileCache", "on"))
    if not flags.get("kafkaBrokers"):
        if "trainingData" not in flags:
            raise SystemExit("--trainingData is required in file mode")
        if "requests" not in flags and not _flag_true(flags, "restore"):
            raise SystemExit(
                "--requests is required (or --restore with a checkpoint)"
            )

    config = JobConfig(
        job_name=flags.get("jobName", "OMLDM"),
        batch_size=int(flags.get("batchSize", "256")),
        test_set_size=int(flags.get("testSetSize", "64")),
        # the distributed engine's backpressure/pressure signal
        # (runtime/overload.py backlog thresholds); unset = unarmed
        overload=flags.get("overload", ""),
        # flight recorder: decision-event journal + black-box ring dumps
        # (runtime/events.py; --flightRecorder, matching the in-process
        # CLI where bare --events names the replay file); unset = zero
        # recorder objects
        events=flags.get("flightRecorder", ""),
        blackbox_path=flags.get("blackboxPath", ""),
    )
    nproc_flag = int(flags.get("processes", "0"))
    # --processes 1 with no coordinator is a plain single-process run;
    # jax.distributed requires a coordinator address otherwise
    use_group = flags.get("coordinator") is not None and nproc_flag > 1
    job = DistributedStreamJob(
        config,
        coordinator=flags.get("coordinator") if use_group else None,
        num_processes=nproc_flag if use_group else None,
        process_id=int(flags["processId"]) if use_group else None,
    )
    from omldm_tpu.__main__ import announce_device

    announce_device()
    # elastic-rescale knobs: --rescaleRestore false pins the strict
    # same-count restore contract; --rescaleCount is the supervisor's
    # authoritative cumulative rescale tally for Statistics
    job.rescale_restore = flags.get(
        "rescaleRestore", "true"
    ).lower() not in ("false", "0", "no")
    if "rescaleCount" in flags:
        job.rescales_performed = int(flags["rescaleCount"] or 0)
        job._rescale_count_pinned = True
    # self-healing knobs: the supervisor pins the degraded-width gauge
    # (--fleetDegraded) and --collectiveTimeoutMs arms the hang watchdog
    # (first guard entry per phase gets the --collectiveWarmupMs
    # allowance for cold XLA compiles). Unset = zero watchdog objects,
    # exact pre-PR routes.
    if "fleetDegraded" in flags:
        job.fleet_degraded = int(flags["fleetDegraded"] or 0)
    hang_ms = float(flags.get("collectiveTimeoutMs", "0") or 0)
    if hang_ms > 0:
        job.arm_hang_watchdog(
            hang_ms / 1000.0,
            warmup_s=float(flags.get("collectiveWarmupMs", "120000"))
            / 1000.0,
        )

    def _mid_deploy_beat() -> None:
        if not _heartbeat(flags, job.pid, job.heartbeat_frame()):
            job.hb_write_errors += 1

    job.beat_hook = _mid_deploy_beat
    # process 0 reads the request file; everyone else receives the
    # broadcast (passing lines from a non-0 process is ignored). On a
    # restore the manifest redeploys the pipeline map instead — the
    # requests file was fully consumed before the first snapshot.
    restoring = _flag_true(flags, "restore") and bool(
        flags.get("checkpointDir")
    ) and os.path.exists(os.path.join(flags["checkpointDir"], "LATEST"))
    if not restoring:
        _sync_requests_from_flags(job, flags)
    # --profileDir: jax.profiler trace of this worker's drive loop, one
    # trace directory PER PROCESS (a shared dir would interleave event
    # files) — the distributed twin of the single-process CLI flag
    # (__main__.py). Unset = the no-op context.
    from omldm_tpu.utils.tracing import trace as _profiler_trace

    profile_dir = flags.get("profileDir")
    if profile_dir:
        profile_dir = os.path.join(profile_dir, f"proc{job.pid}")
    with _profiler_trace(profile_dir):
        if flags.get("kafkaBrokers"):
            # a job may start with no pipelines: the Create can arrive on
            # the requests topic mid-run (startupIdleWindows bounds the
            # wait)
            _drive_kafka(job, flags)
        else:
            if not restoring and not job.pipelines:
                raise SystemExit(
                    "no pipeline deployed: the requests file must contain "
                    "at least one valid Create/Update with "
                    f"dataStructure.nFeatures ({flags.get('requests')!r})"
                )
            if job.stream_mode == "sparse" or (
                restoring and _manifest_is_sparse(flags)
            ):
                _drive_file_sparse(job, flags)
            else:
                _drive_file(job, flags)

    # post-training control-plane sync point: a second request file handled
    # after the stream drains (deterministic query-after-training — the
    # pattern the reference exercises by publishing a Query to the requests
    # topic once training data stops flowing, PipelineMap.scala:37-42).
    # Queries here see the fully-trained model; Deletes drop pipelines from
    # the final report.
    if flags.get("requestsFinal"):
        final_lines: List[str] = []
        if job.pid == 0:
            with open(flags["requestsFinal"]) as f:
                final_lines = [l.strip() for l in f if l.strip()]
        job.sync_requests(final_lines)

    # outputs: predictions per process (suffixed — a shared path would be
    # clobbered by the last writer and lose the other partitions' rows),
    # responses + performance from process 0. In Kafka mode, outputs
    # WITHOUT an explicit file sink publish to the reference's output
    # topics (predictions / responses / performance — README.md:21-26,
    # FlinkLearning.scala:137-144) through the shared ProducerSinks; an
    # explicitly-passed file sink keeps precedence over the producer,
    # exactly the single-process CLI's rule (__main__._apply_kafka_sinks).
    sinks = None
    # exactly-once-per-restart output dedupe: a process that already
    # published its topic outputs (then died before exiting cleanly)
    # leaves an EMITTED marker next to the checkpoints; the restored
    # incarnation honors it instead of double-publishing. File sinks need
    # no marker — they truncate-rewrite, so restarts self-dedupe.
    marker = None
    if flags.get("checkpointDir"):
        marker = os.path.join(flags["checkpointDir"], f"EMITTED.p{job.pid}")
        if not restoring:
            try:
                os.unlink(marker)  # stale marker from an earlier job
            except OSError:
                pass
    already_emitted = marker is not None and os.path.exists(marker)
    if flags.get("kafkaBrokers"):
        try:
            from kafka import KafkaProducer

            from omldm_tpu.runtime.kafka_io import (
                CONNECT_RETRY,
                ProducerSinks,
            )
            from omldm_tpu.utils.backoff import with_backoff

            sinks = ProducerSinks(
                with_backoff(
                    lambda: KafkaProducer(
                        bootstrap_servers=flags["kafkaBrokers"]
                    ),
                    retry_on=(Exception,),
                    policy=CONNECT_RETRY,
                )
            )
        except Exception as exc:
            # broker gone at shutdown must not lose the file outputs
            job._warn(f"output-topic producer unavailable: {exc}")
            sinks = None
    if already_emitted and sinks is not None:
        job._warn(
            "outputs already published to the topics by a previous "
            "incarnation; skipping topic publication (exactly-once)"
        )
    want_preds_file = bool(flags.get("predictionsOut"))
    publish_preds = (
        sinks is not None and not want_preds_file and not already_emitted
    )
    if want_preds_file or publish_preds:
        payloads = [
            {"mlpId": net_id, "value": v}
            for net_id, v in job.orphan_predictions
        ] + [
            {"mlpId": net_id, "value": v}
            for net_id in sorted(job.pipelines)
            for v in job.pipelines[net_id].predictions
        ]
        if want_preds_file:
            path = flags["predictionsOut"]
            if job.nproc > 1:
                path = f"{path}.p{job.pid}"
            with open(path, "w") as f:
                for obj in payloads:
                    f.write(json.dumps(obj) + "\n")
        else:
            for obj in payloads:
                sinks.on_prediction(obj)
    report = job.merged_report()
    if report is not None:
        if flags.get("responsesOut"):
            with open(flags["responsesOut"], "w") as f:
                for resp in job.responses:
                    f.write(resp.to_json() + "\n")
        elif sinks is not None and not already_emitted:
            for resp in job.responses:
                sinks.on_response(resp)
        if flags.get("performanceOut"):
            with open(flags["performanceOut"], "w") as f:
                f.write(json.dumps(report) + "\n")
        elif sinks is not None and not already_emitted:
            sinks.on_performance(report)
        print(json.dumps(report))
    if (
        marker is not None
        and sinks is not None
        and not already_emitted
        and not sinks.dropped
    ):
        # published (or deliberately skipped for file sinks): a crash
        # between here and exit must not republish on the next restore.
        # NOT written when the degraded producer dropped sends — those
        # outputs were never delivered, so a restored incarnation against
        # a healed broker must still publish them
        _atomic_write_bytes(marker, b"published\n")
    if sinks is not None:
        sinks.close()
    # final black-box dump: the terminate-time ring is this process's
    # last word in any incident bundle
    if job.events is not None:
        from omldm_tpu.runtime.events import TERMINATE

        job.events.record(TERMINATE, "drive_complete")
        job.events.dump()
    if job.watchdog is not None:
        # the collectives are done: a slow final file write must not be
        # mistaken for a wedged fabric
        job.watchdog.stop()
    return 0


if __name__ == "__main__":
    sys.exit(run_distributed(sys.argv[1:]))
