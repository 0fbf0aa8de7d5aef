"""Spoke: the worker-side runtime hosting pipeline replicas.

Reference counterpart: ``FlinkSpoke`` + ``SpokeLogic``
(FlinkSpoke.scala:28-356, SpokeLogic.scala:20-59): hosts one node per
pipeline in ``state: Map[Int, BufferingWrapper]``, fans every data point out
to all hosted pipelines, runs the 20% holdout sampling (counts 8,9 of each
0-9 cycle into a sliding ``testSet``; evicted points get trained —
FlinkSpoke.scala:94-104), emits a poll marker every 100 training records
(FlinkSpoke.scala:83-89), dispatches control messages, and buffers records/
requests arriving before pipeline creation (caps 100_000 / 10_000,
SpokeLogic.scala:31-35).

TPU redesign: records are vectorized host-side and accumulated into
fixed-shape micro-batches per pipeline; the per-batch fit is the jitted
pipeline step. Forecasting records are answered immediately through a
fixed-width padded predict batch.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from omldm_tpu.api.data import FORECASTING, DataInstance, Prediction
from omldm_tpu.api.requests import Request, RequestType
from omldm_tpu.api.responses import TERMINATION_RESPONSE_ID, QueryResponse
from omldm_tpu.config import JobConfig
from omldm_tpu.guard import guard_config
from omldm_tpu.pipelines import MLPipeline
from omldm_tpu.protocols.base import WorkerNode
from omldm_tpu.protocols.registry import make_worker_node, resolve_protocol
from omldm_tpu.runtime.cohort import CohortEngine
from omldm_tpu.runtime.databuffers import DataSet
from omldm_tpu.runtime.messages import (
    OP_NACK,
    OP_RESYNC,
    ReceiveWindow,
    StreamSequencer,
    channel_chaos_spec,
    channel_window_size,
    reliability_armed,
)
from omldm_tpu.runtime.lifecycle import (
    CANARY,
    REASON_OPERATOR,
    SHADOW,
    LifecycleState,
    build_candidate,
    lifecycle_config,
)
from omldm_tpu.runtime.overload import (
    CRITICAL,
    ELEVATED,
    OverloadController,
    overload_config,
)
from omldm_tpu.runtime.serving import (
    ServeStats,
    ServeQueue,
    ServingPlane,
    _entry_rows,
    serving_config,
)
from omldm_tpu.runtime.telemetry import telemetry_config
from omldm_tpu.runtime.vectorizer import (
    F32_MAX,
    MicroBatcher,
    SparseMicroBatcher,
    SparseVectorizer,
    Vectorizer,
)
from omldm_tpu.utils.tracing import StepTimer

# width of the immediate-serving predict batch (forecasting records are padded
# into this fixed shape so the predict jit never recompiles)
PREDICT_BATCH = 16


def create_pipeline(request: Request, dim: int) -> MLPipeline:
    """THE Create-request pipeline recipe — rng derivation, per-record
    mode, guard arming. SpokeNet construction and the lifecycle plane's
    retained-version rebuild (runtime/lifecycle._version_zero_pipeline)
    both go through here so the two can never drift: a restored version-0
    model must load into exactly the pipeline Create would have built."""
    tc = request.training_configuration
    return MLPipeline(
        request.learner,
        request.preprocessors,
        dim=dim,
        rng=jax.random.PRNGKey(request.id),
        per_record=tc.per_record,
        # model-integrity guard (trainingConfiguration.guard): fused
        # in-program health checks + the LKG rollback ring; None
        # (default) keeps the exact pre-guard programs
        guard=guard_config(tc),
    )


class _PauseBuffer:
    """Bounded ROW-accounted hold buffer: records held while a net is
    paused (cooperative toggle), the spoke's pre-creation packed buffer,
    and the job-level pre-create backlog all share this one trim
    implementation. Beyond the cap the OLDEST rows drop — the same
    keep-newest eviction as every other bounded buffer here
    (SpokeLogic.scala:31-35); packed blocks (entry[0] == "__packed__")
    are accounted and trimmed by their row counts, not as single
    entries; any other entry counts as one row."""

    def __init__(self, cap: int):
        self.cap = cap
        # deque: the trim pops from the FRONT on every over-cap append —
        # a list's pop(0) would make sustained over-cap ingest quadratic
        self._entries: Deque[tuple] = collections.deque()
        self._rows = 0

    def __len__(self) -> int:
        return self._rows

    @property
    def is_empty(self) -> bool:
        return not self._entries

    @staticmethod
    def _entry_rows(entry) -> int:
        if entry[0] == "__packed__":
            return int(entry[1][0].shape[0])
        return 1

    def append(self, entry: tuple) -> None:
        self._entries.append(entry)
        self._rows += self._entry_rows(entry)
        while self._entries and self._rows > self.cap:
            excess = self._rows - self.cap
            head = self._entries[0]
            n = self._entry_rows(head)
            if n <= excess:
                self._entries.popleft()
                self._rows -= n
            else:
                px, py, pop = head[1]
                self._entries[0] = (
                    "__packed__",
                    (px[excess:].copy(), py[excess:].copy(), pop[excess:].copy()),
                    None, None,
                )
                self._rows -= excess

    def peek(self):
        """Oldest held entry, or None."""
        return self._entries[0] if self._entries else None

    def drain(self) -> List[tuple]:
        entries, self._entries = list(self._entries), collections.deque()
        self._rows = 0
        return entries

    def merge(self, others) -> None:
        for other in others:
            for entry in other.drain():
                self.append(entry)


class SpokeNet:
    """Per-(spoke, networkId) state: worker node + batcher + holdout set."""

    def __init__(
        self,
        request: Request,
        worker_id: int,
        n_workers: int,
        dim: int,
        config: JobConfig,
        send,
        timer: Optional[StepTimer] = None,
    ):
        self.request = request
        self.dim = dim
        self._timer = timer
        tc = request.training_configuration
        self.protocol = resolve_protocol(
            tc.protocol, request.learner.name, n_workers
        )
        ds = (request.learner.data_structure or {}) if request.learner else {}
        self.sparse = bool(ds.get("sparse"))
        batch = int(tc.mini_batch_size or config.batch_size)
        if self.sparse:
            # padded-COO featurization: dense slots + hashed categoricals
            # in a wide index space (SparseVector parity,
            # DataPointParser.scala:4,20-47)
            self.max_nnz = int(ds.get("maxNnz", 64))
            hash_space = int(ds.get("hashSpace", 0))
            self.vectorizer = SparseVectorizer(dim, hash_space, self.max_nnz)
            self.batcher = SparseMicroBatcher(self.max_nnz, batch)
        else:
            hash_dims = int(tc.extra.get("hashDims", 0))
            self.vectorizer = Vectorizer(dim, hash_dims)
            self.batcher = MicroBatcher(dim, batch)
        pipeline = create_pipeline(request, dim)
        self.node = make_worker_node(
            self.protocol, pipeline, worker_id, n_workers, tc, send
        )
        # host-plane program-launch accounting (Statistics.programLaunches):
        # the pipeline reports every dispatched program (a shared cohort
        # launch counts once, on its triggering member); the spoke folds
        # the tally into the pipeline's hub statistics at query/terminate
        self.program_launches = 0
        pipeline.on_launch = self._note_launch
        # set on rescale absorb: the batcher then holds rows merged from a
        # retired replica, so its pending fill is no longer a pure suffix
        # of this spoke's stream and shared-ingest grouping must skip it
        self.shared_taint = False
        # adaptive-batching serving plane (trainingConfiguration.serving /
        # JobConfig.serving): when armed, forecasting records queue here
        # and serve in batched predict launches (runtime/serving.py); None
        # (default) keeps the immediate per-record predict path. The plane
        # reference is attached by the hosting Spoke at create time.
        self.serving = serving_config(tc, getattr(config, "serving", ""))
        self.serve_queue = ServeQueue()
        self.serve_stats = ServeStats()
        self._plane: Optional[ServingPlane] = None
        # overload-control plane (trainingConfiguration.overload /
        # JobConfig.overload): when armed, this tenant's admissions run
        # through the spoke's OverloadController (fair-share token
        # bucket, degradation ladder, load shedding; runtime/overload.py);
        # None (default) keeps the exact pre-plane routes. The controller
        # reference is attached by the hosting Spoke at create time.
        self.overload = overload_config(tc, getattr(config, "overload", ""))
        self._octl: Optional[OverloadController] = None
        # telemetry plane (trainingConfiguration.telemetry /
        # JobConfig.telemetry): per-net opt-in/out for SPAN sampling — an
        # explicit false excludes this pipeline's protocol rounds from
        # the job plane's sampled spans (runtime/telemetry.py). The plane
        # itself lives on the job; None here only gates the span hook.
        self.telemetry_cfg = telemetry_config(
            tc, getattr(config, "telemetry", "")
        )
        # flight recorder (trainingConfiguration.events /
        # JobConfig.events): per-net opt-in/out — an explicit false
        # excludes this pipeline from decision-event recording and from
        # the Query event tail even when the JOB plane is armed by
        # another pipeline or the job-wide spec (the telemetry_cfg span
        # rule). The journal itself lives on the job; None here only
        # gates this net's recording sites.
        from omldm_tpu.runtime.events import events_config

        self.events_cfg = events_config(tc, getattr(config, "events", ""))
        # transport-codec seconds already folded into hub statistics
        # (delta-folding: query + terminate must never double-count)
        self._codec_folded = (0.0, 0.0)
        # model-lifecycle plane (trainingConfiguration.lifecycle /
        # JobConfig.lifecycle): when armed, this net owns a per-pipeline
        # model-version registry — Shadow candidates twin-train on the
        # same flushed batches, canary routing splits forecasts at the
        # serve-admission boundary, and the candidate's guard fences the
        # rollback (runtime/lifecycle.py). None (default, and always for
        # sparse nets — the candidate predict/flat paths are dense) keeps
        # the exact pre-plane routes.
        lc_cfg = (
            lifecycle_config(tc, getattr(config, "lifecycle", ""))
            if not self.sparse else None
        )
        self.lifecycle: Optional[LifecycleState] = (
            LifecycleState(lc_cfg) if lc_cfg is not None else None
        )
        # persistent padded predict scratch: the per-record, gang and
        # batched serve paths all pad rows into this reused buffer instead
        # of allocating a fresh pad batch per forecast record
        self._scratch = None
        self._scratch_dirty = 0
        self.scratch_allocs = 0
        # reliable channel (lossy-channel hardening): per-hub outgoing
        # sequence numbers + per-hub receive windows, armed per pipeline.
        # Unarmed (the default), nothing is stamped or windowed and the
        # routes are bit-identical to the pre-reliable runtime.
        self.channel_armed = reliability_armed(tc, channel_chaos_spec(config))
        self.node.channel_armed = self.channel_armed
        self._window_size = channel_window_size(tc)
        self._tx_seq = StreamSequencer() if self.channel_armed else None
        self._rx_windows: Dict[int, ReceiveWindow] = {}
        self._quiesced = False
        self.test_set: DataSet[Tuple[np.ndarray, float]] = DataSet(
            config.test_set_size
        )
        self.holdout_count = 0
        # records arriving while this net is PAUSED (cooperative toggle,
        # FlinkSpoke.scala:127-131) buffer here and drain on resume — the
        # reference's BufferingWrapper holds tuples the same way; beyond
        # the row cap the oldest rows drop (keep-newest eviction)
        self.pause_buffer = _PauseBuffer(config.record_buffer_cap)

    def next_seq(self, hub_id: int) -> Optional[int]:
        if self._tx_seq is None:
            return None
        return self._tx_seq.next(hub_id)

    def rx_window(self, hub_id: int) -> ReceiveWindow:
        window = self._rx_windows.get(hub_id)
        if window is None:
            # post-quiesce windows start in pass-through: the first-ever
            # message from this hub may arrive during termination
            window = self._rx_windows[hub_id] = ReceiveWindow(
                self._window_size, passthrough=self._quiesced
            )
        return window

    @property
    def pipeline(self) -> MLPipeline:
        return self.node.pipeline

    def _note_launch(self) -> None:
        self.program_launches += 1

    def predict_pad(self, n: int):
        """A zeroed padded predict batch with >= ``n`` writable rows, from
        the net's persistent scratch: ``[B', dim]`` (or a sparse
        ``(idx, val)`` pair), ``B'`` the pow2 bucket of ``n`` floored at
        PREDICT_BATCH so the single-record path keeps its pre-plane shape.
        Only the rows dirtied by the previous use are re-zeroed; the
        caller overwrites rows ``[0, n)``. Consumers (predict dispatch,
        Cohort.predict_rows) copy before returning, so reuse across
        forecasts is safe."""
        b = PREDICT_BATCH
        while b < n:
            b <<= 1
        if self.sparse:
            if self._scratch is None or self._scratch[0].shape[0] < b:
                self._scratch = (
                    np.zeros((b, self.max_nnz), np.int32),
                    np.zeros((b, self.max_nnz), np.float32),
                )
                self.scratch_allocs += 1
                self._scratch_dirty = 0
            ib, vb = self._scratch
            if self._scratch_dirty:
                ib[: self._scratch_dirty] = 0
                vb[: self._scratch_dirty] = 0.0
            self._scratch_dirty = n
            return ib[:b], vb[:b]
        if self._scratch is None or self._scratch.shape[0] < b:
            self._scratch = np.zeros((b, self.dim), np.float32)
            self.scratch_allocs += 1
            self._scratch_dirty = 0
        if self._scratch_dirty:
            self._scratch[: self._scratch_dirty] = 0.0
        self._scratch_dirty = n
        return self._scratch[:b]

    def serving_limits(self):
        """The serving config the flush triggers compare against: the
        static config, or — while the spoke's overload controller reports
        pressure — its degraded variant (widened maxBatch/maxDelayMs,
        relaxed staleness: the ladder's serving rung). Overload-unarmed
        nets always get the static config, bit-identically."""
        ctl = self._octl
        if ctl is None or ctl.level == 0:
            return self.serving
        return ctl.degraded_serving(self)

    def gang_predict_ok(self) -> bool:
        """Gang forecast serving bypasses ``node.on_forecast_batch`` with a
        bit-identical batched predict — only valid for attached dense nets
        whose node keeps the base (predict-with-local-model) behavior."""
        return (
            not self.sparse
            and self.pipeline._cohort is not None
            and type(self.node).on_forecast_batch
            is WorkerNode.on_forecast_batch
        )

    def flush_batch(self) -> None:
        if (
            self.serving is not None
            and self.serve_queue.entries
            and len(self.batcher)
        ):
            # this net's model is about to change (the pending rows will
            # stage/dispatch a fit): exact-mode serving drains the queue
            # NOW with the pre-fit params — the bit-identity trigger;
            # relaxed mode counts the chunk (runtime/serving.py)
            self._plane.fence(self)
        if self.pipeline._cohort is not None:
            # a deferred sync point may set `waiting`; settle before the
            # view-vs-copy decision or a blocking node could buffer VIEWS
            self.pipeline.settle_deferred()
        if (
            self.pipeline._cohort is not None
            and self.node.consumes_batch_synchronously
            and not getattr(self.node, "waiting", False)
        ):
            # staged gang dispatch: a non-waiting node consumes the batch
            # synchronously (stage copies it into the cohort's gang
            # buffers), so the batcher can hand out zero-copy views; the
            # launch itself is timed inside Cohort._run_staged
            flushed = self.batcher.flush_views()
            if flushed is not None:
                self.node.on_training_batch(*flushed)
                if (
                    self.lifecycle is not None
                    and self.lifecycle.training_active
                ):
                    # candidate twin-train on the SAME flushed batch; the
                    # views alias batcher buffers that later adds reuse,
                    # so the candidate gets copies (its fit is lazy)
                    x, y, m = flushed
                    self.lifecycle.fit_candidate(x.copy(), y.copy(), m)
            return
        flushed = self.batcher.flush()
        if flushed is not None:
            x, y, mask = flushed
            if self._timer is not None and self.pipeline._cohort is None:
                # per-pipeline dispatch timing; cohort gang launches time
                # themselves inside Cohort._run_staged (same StepTimer)
                with self._timer:
                    self.node.on_training_batch(x, y, mask)
            else:
                self.node.on_training_batch(x, y, mask)
            if self.lifecycle is not None and self.lifecycle.training_active:
                # shadow/canary candidate trains on the same micro-batch
                # (its own solo launch; the active model is untouched)
                self.lifecycle.fit_candidate(x, y, mask)

    def test_arrays(self) -> Optional[Tuple[Any, np.ndarray, np.ndarray]]:
        if self.test_set.is_empty:
            return None
        pts = self.test_set.to_list()
        if self.sparse:
            x = (
                np.stack([p[0][0] for p in pts]),
                np.stack([p[0][1] for p in pts]),
            )
        else:
            x = np.stack([p[0] for p in pts])
        y = np.asarray([p[1] for p in pts], np.float32)
        return x, y, np.ones((len(pts),), np.float32)


class Spoke:
    """One logical worker (a Flink subtask in the reference)."""

    def __init__(
        self,
        worker_id: int,
        config: JobConfig,
        send_to_hub: Callable,   # (network_id, hub_id, worker_id, op, payload, seq)
        emit_prediction: Callable[[Prediction], None],
        emit_response: Callable[[QueryResponse], None],
        on_poll: Callable[[], None],
        # (network_id, hub_id, counter, value) — value is an int for the
        # additive counters, a (p50, p99, p999) triple for serve_latency_ms
        note_wire: Optional[Callable[[int, int, str, Any], None]] = None,
        emit_predictions: Optional[
            Callable[[List[Prediction]], None]
        ] = None,
        # dead-letter hook (stream, payload, reason, detail=, extra=):
        # the overload plane's shed/throttle records quarantine through
        # it with reason codes instead of vanishing
        quarantine: Optional[Callable] = None,
        # opt-in for metadata.tenant record addressing even with the
        # overload plane unarmed (the job sets it when the chaos burst
        # injector is armed — its clones are tenant-addressed); False =
        # metadata-carrying records broadcast exactly as pre-plane
        tenant_routing: bool = False,
        # job-level telemetry plane (runtime/telemetry.TelemetryPlane) or
        # None: gates the span hooks and the phase-attribution hooks —
        # one attribute read on every path when unarmed
        telemetry=None,
        # job-level flight-recorder journal (runtime/events.EventJournal)
        # or None: the decision sites below record typed events through
        # it — one attribute read per site when unarmed
        events=None,
    ):
        self.worker_id = worker_id
        self.config = config
        self.nets: Dict[int, SpokeNet] = {}
        # flush-path step timing: per-launch ms percentiles (StepTimer
        # summary) emittable alongside bytesShipped — covers per-pipeline
        # flush dispatch AND cohort gang launches. Both timers sit on
        # long-lived streaming hot paths, so their sample windows are
        # BOUNDED rings (count stays total; percentiles summarize the
        # most recent window, same policy as ServeStats' latency ring)
        self.step_timer = StepTimer("spoke_flush", cap=65536)
        # serving-launch timing: per-launch ms percentiles for forecast
        # predict dispatches — the immediate per-record path, batched
        # serving-plane flushes, AND cohort gang predicts — reported
        # separately from the fit flush path by StreamJob.launch_timing()
        self.serve_timer = StepTimer("serve_flush", cap=65536)
        # cohort execution engine (JobConfig.cohort): groups same-spec
        # pipelines for gang-scheduled dispatch; None when off — every
        # route below then takes the exact per-pipeline code path
        engine = CohortEngine(
            config, timer=self.step_timer, serve_timer=self.serve_timer
        )
        self.cohorts: Optional[CohortEngine] = (
            engine if engine.enabled else None
        )
        self._send_to_hub = send_to_hub
        self._emit_prediction = emit_prediction
        self._emit_predictions = emit_predictions
        self._emit_response = emit_response
        self._on_poll = on_poll
        # spoke-side reliable-channel events (duplicates dropped, gaps
        # resynced) fold into the pipeline's hub statistics through this
        # job-provided callback: (network_id, hub_id, counter_name, n)
        self._note_wire = note_wire
        # model-integrity guard: True once any hosted net is guard-armed;
        # the per-event guard walk is gated on this one flag so unarmed
        # jobs pay a single attribute read on the data path
        self._any_guard = False
        # model-lifecycle plane: True once any hosted net is lifecycle-
        # armed; gates the per-event candidate tick + the serve-admission
        # canary routing the same way (one attribute read unarmed)
        self._any_lifecycle = False
        # adaptive-batching serving plane (runtime/serving.py): created on
        # the first serving-armed net; the flag gates every hot-path hook
        # so serving-unset jobs pay one attribute read
        self.serving_plane: Optional[ServingPlane] = None
        self._any_serving = False
        # overload controller (runtime/overload.py): created on the first
        # overload-armed net; None (default) = no admission accounting,
        # no ladder, no shedding — one attribute read on the data paths
        self.overload: Optional[OverloadController] = None
        self._quarantine = quarantine
        self.tenant_routing = tenant_routing
        # telemetry plane reference + its phase profile (split so the hot
        # paths read one attribute): set at construction when the job is
        # already armed, or later through attach_telemetry (lazy
        # pipeline-table arming, rescale-grown spokes)
        self.telemetry = telemetry
        self._phases = (
            telemetry.phases if telemetry is not None else None
        )
        self.events = events
        # cached (count, (p50, p99)) per timer name: the terminate probe
        # folds per net, and re-sorting the launch ring per tenant would
        # make a 256-tenant terminate quadratic in ring length
        self._tp_cache: Dict[str, Tuple[int, Tuple[float, float]]] = {}
        # pre-creation buffering (SpokeLogic.scala:31-35)
        self.record_buffer: DataSet[DataInstance] = DataSet(config.record_buffer_cap)
        # packed-row pre-creation buffer: whole (x, y, op) blocks with the
        # same total-row keep-newest cap as the record buffer
        self._packed_buffer = _PauseBuffer(config.record_buffer_cap)
        self._poll_counter = 0

    # --- control path (FlinkSpoke.processElement2) ---

    def handle_request(self, request: Request, dim: int) -> None:
        if request.request == RequestType.CREATE:
            self._create(request, dim)
        elif request.request == RequestType.UPDATE:
            self._delete(request.id)
            self._create(request, dim)
        elif request.request == RequestType.DELETE:
            self._delete(request.id)
        elif request.request == RequestType.QUERY:
            self._query(request)
        elif request.request == RequestType.SHADOW:
            self._lifecycle_shadow(request)
        elif request.request == RequestType.PROMOTE:
            self._lifecycle_promote_request(request)
        elif request.request == RequestType.ROLLBACK:
            self._lifecycle_rollback_request(request)

    def _create(self, request: Request, dim: int) -> None:
        if request.id in self.nets:
            return
        net = SpokeNet(
            request,
            self.worker_id,
            self.config.parallelism,
            dim,
            self.config,
            self._make_send(request.id),
            timer=self.step_timer,
        )
        self.nets[request.id] = net
        net.node.on_start()
        if net.serving is not None:
            net._plane = self._ensure_serving_plane()
        if net.overload is not None:
            if self.overload is None:
                self.overload = OverloadController(self)
            self.overload.arm(net)
            # ladder events are SPOKE-scoped (the controller aggregates
            # across tenants, its events carry no pipeline tag): any
            # events-enabled overload tenant arms them; a spoke whose
            # overload tenants all opted out records nothing
            if self.events is not None and net.events_cfg is not None:
                self.overload.events = self.events
        if net.pipeline.guard is not None:
            self._any_guard = True
            # seed the first last-known-good snapshot at the init params:
            # a trip before the first cadence snapshot must still have a
            # rollback target
            net.pipeline.guard.maybe_snapshot(net.pipeline)
        if net.lifecycle is not None:
            self._any_lifecycle = True
            if self.events is not None and net.events_cfg is not None:
                net.lifecycle.events = self.events
                net.lifecycle.net_id = net.request.id
        if self.cohorts is not None:
            self.cohorts.consider(net.pipeline)
            # pooled pipelines may attach on a LATER create (auto
            # threshold); attached nets are exempt from cooperative
            # toggling, so one caught mid-pause would never be resumed —
            # release it now
            for other in self.nets.values():
                if other.pipeline._cohort is not None and other.node.paused:
                    other.node.paused = False
                    self._drain_pause_buffer(other)
        # drain buffered records (FlinkSpoke.scala:69-80)
        if len(self.record_buffer):
            buffered = self.record_buffer.to_list()
            self.record_buffer.clear()
            for inst in buffered:
                self.handle_data(inst)
        if not self._packed_buffer.is_empty:
            for _op, block, _t, _i in self._packed_buffer.drain():
                self.handle_packed(*block)

    def _ensure_serving_plane(self) -> ServingPlane:
        if self.serving_plane is None:
            self.serving_plane = ServingPlane(
                self._emit_prediction,
                emit_predictions=self._emit_predictions,
                timer=self.serve_timer,
            )
        self._any_serving = True
        return self.serving_plane

    def poll_serving(self) -> None:
        """Serving-plane boundary tick: fill-triggered flushes (aligned so
        same-cohort queues gang) and the maxDelayMs deadline clock. Runs
        after every data event and from the live loop's silence check;
        one flag read when no hosted net is serving-armed."""
        if self._any_serving:
            self.serving_plane.maybe_fill_flush()
            self.serving_plane.poll()

    def _delete(self, network_id: int) -> None:
        net = self.nets.pop(network_id, None)
        if (
            net is not None
            and net.serving is not None
            and net.serve_queue.entries
        ):
            # pending forecasts serve through the departing model first —
            # the per-record path would have answered them already
            self.serving_plane.flush_net(net)
        if net is not None and self.cohorts is not None:
            # cohort churn: the member's slot frees for reuse (compaction),
            # no recompile; survivors keep their slots untouched
            self.cohorts.retire(net.pipeline)
        if net is not None and self.overload is not None:
            # the tenant's accounting (and any deferred rows) go with it,
            # like the net's pause buffer does
            self.overload.retire(network_id)
        # a deleted net can no longer generate the hub RPCs that toggle its
        # siblings: resume + drain any survivor left paused, or it would
        # starve until the terminate probe
        for net in self.nets.values():
            if net.node.paused:
                net.node.paused = False
                self._drain_pause_buffer(net)

    def attach_telemetry(self, plane) -> None:
        """Hand this spoke the job's telemetry plane (lazy arming by the
        first pipeline-level telemetry table, or job-armed construction
        racing rescale-grown spokes)."""
        self.telemetry = plane
        self._phases = plane.phases

    def attach_events(self, journal) -> None:
        """Hand this spoke the job's flight-recorder journal (lazy arming
        by the first pipeline-level events table) and wire the hosted
        planes that record their own transitions."""
        self.events = journal
        if self.overload is not None and any(
            net.overload is not None and net.events_cfg is not None
            for net in self.nets.values()
        ):
            self.overload.events = journal
        for net in self.nets.values():
            if net.lifecycle is not None and net.events_cfg is not None:
                net.lifecycle.events = journal
                net.lifecycle.net_id = net.request.id

    def _timer_percentiles(self, timer: StepTimer) -> Tuple[float, float]:
        """(p50, p99) ms of a StepTimer's retained window, cached by the
        timer's total count so a multi-tenant terminate probe sorts each
        ring once, not once per net."""
        cached = self._tp_cache.get(timer.name)
        if cached is not None and cached[0] == timer.count:
            return cached[1]
        sm = timer.summary()
        out = (sm["p50_ms"], sm["p99_ms"])
        self._tp_cache[timer.name] = (timer.count, out)
        return out

    def _make_send(self, network_id: int):
        def send(op: str, payload: Any, hub_id: int = 0) -> None:
            # reliable channel: stamp the per-(net, worker->hub) sequence
            # number at the true ship boundary (below the codec wrapper,
            # above the possibly-lossy router)
            net = self.nets.get(network_id)
            seq = net.next_seq(hub_id) if net is not None else None
            # sampled round tracing: 1/traceSample sends open a span
            # keyed by the transport stamp; the next hub delivery on this
            # stream closes it with the round-trip latency
            tel = self.telemetry
            if (
                tel is not None
                and tel.spans.active
                and net is not None
                and net.telemetry_cfg is not None
            ):
                tel.spans.maybe_open(
                    network_id, hub_id, self.worker_id, op, seq
                )
            self._send_to_hub(
                network_id, hub_id, self.worker_id, op, payload, seq
            )

        return send

    # --- data path (FlinkSpoke.processElement1 / handleData) ---

    def handle_data(self, inst: DataInstance) -> None:
        if not self.nets:
            self.record_buffer.append(inst)
            return
        nets = self.nets.values()
        meta = inst.metadata
        if isinstance(meta, dict) and (
            self.overload is not None or self.tenant_routing
        ):
            # tenant-ADDRESSED record: ``metadata.tenant`` names a hosted
            # pipeline and the record routes to it ALONE instead of
            # fanning out — the per-tenant traffic shape the overload
            # plane's fairness accounting (and its burst injector)
            # exercises. OPT-IN: only an armed overload controller or the
            # burst injector (job-level ``tenant_routing``) activates the
            # route, so pre-existing streams whose metadata happens to
            # carry a "tenant" key keep the exact pre-plane broadcast
            # fan-out. Non-dict metadata (a string/list the validation
            # boundary admits and the reference ignores) never routes.
            # An unknown tenant falls back to the broadcast fan-out;
            # records without the key are untouched.
            target = self.nets.get(meta.get("tenant"))
            if target is not None:
                nets = (target,)
        ctl = self.overload
        serve_entries: List[Tuple[SpokeNet, Any]] = []
        # False only when EVERY admission this record attempted was shed
        # (a flooded tenant-addressed record): nothing entered a queue,
        # so the boundary's serving poll can wait for the next admitted
        # record — shedding must stay far cheaper than serving
        touched = ctl is None
        for net in nets:
            if (
                ctl is not None
                and net.overload is not None
                and not net.node.paused
            ):
                # fair-share admission: the counter accounts every row;
                # the LEVEL gates what an over-limit verdict does — shed
                # forecasts only at CRITICAL, defer training at ELEVATED+.
                # Runs BEFORE featurization: a shed record must cost as
                # close to nothing as the runtime can manage
                over = ctl.spend(net, 1)
                if over and ctl.level >= ELEVATED:
                    if inst.operation == FORECASTING:
                        if ctl.level >= CRITICAL and net.overload.shed:
                            self._shed_forecast(net, inst)
                            continue
                    else:
                        self._defer_training(
                            net,
                            (
                                inst.operation,
                                net.vectorizer.vectorize(inst),
                                inst.target,
                                None,
                            ),
                            1,
                        )
                        touched = True
                        continue
            ph = self._phases
            if ph is None:
                x = net.vectorizer.vectorize(inst)
            else:
                # per-record featurization is the record path's share of
                # the ``stage`` phase (the packed routes attribute their
                # bulk add_many calls the same way)
                with ph.phase("stage"):
                    x = net.vectorizer.vectorize(inst)
            if net.node.paused:
                # hold, don't drop: the net resumes on the next toggle.
                # Only forecasts need the original instance (for the
                # prediction payload); training rows are fully captured by
                # the vectorized x
                held_inst = inst if inst.operation == FORECASTING else None
                net.pause_buffer.append(
                    (inst.operation, x, inst.target, held_inst)
                )
                touched = True
                continue
            if inst.operation == FORECASTING:
                # collect, then serve below: cohort members answer through
                # ONE gang predict launch; emission keeps the nets order
                serve_entries.append((net, x))
            else:
                self._train(net, x, 0.0 if inst.target is None else inst.target)
                touched = True
        if serve_entries:
            touched = True
            self._serve_many(inst, serve_entries)
        # gang barrier: launch every cohort's staged fits for this record
        self._flush_cohorts()
        # guard: evaluate the health results this record's launches noted
        self._guard_tick_all()
        # lifecycle: candidate guard/score/ramp decisions for this record
        self._lifecycle_tick_all()
        # overload: re-derive the pressure level from the queues this
        # record left behind, shed/drain accordingly (one flag read
        # unarmed) — BEFORE the serving poll so degraded limits apply at
        # this boundary. Fully-shed records skip BOTH boundary walks
        # (their spends already advanced the count clock; the next
        # admitted record's tick sees them): shedding must cost as close
        # to nothing as the runtime can manage, or the flood's processing
        # overhead would itself degrade healthy tenants
        if touched:
            if ctl is not None:
                self._overload_tick()
            # serving plane: fill-aligned flushes + maxDelayMs deadline
            self.poll_serving()
        if inst.operation != FORECASTING:
            # poll marker every 100 training records — once per record, not
            # per hosted pipeline (FlinkSpoke.scala:83-89)
            self._poll_counter += 1
            if self.config.test and self._poll_counter % self.config.poll_every == 0:
                self._on_poll()

    # --- packed data path (bulk ingest; C++ parser -> arrays, no per-record
    # Python objects; semantics identical to handle_data on the same rows) ---

    def handle_packed(self, x: np.ndarray, y: np.ndarray, op: np.ndarray) -> None:
        """Bulk equivalent of handle_data for pre-vectorized rows.

        ``x`` [n, W] float32, ``y`` [n] float32, ``op`` [n] uint8
        (0=training, 1=forecasting). Produces the same per-net state as
        feeding the rows one at a time (same holdout cycle, same batcher
        fill order, same poll markers, forecasts served at their stream
        position); pause (toggle) is honored at block granularity rather
        than per record, and cross-spoke protocol interleaving is likewise
        block-granular (the reference's Flink rebalance gives no per-record
        cross-worker ordering either, FlinkLearning.scala:83-88).
        """
        n = x.shape[0]
        if n == 0:
            return
        if not self.nets:
            # same keep-newest eviction as the per-record DataSet buffer
            # (SpokeLogic.scala:31-35), row-accounted by _PauseBuffer
            self._packed_buffer.append(("__packed__", (x, y, op), None, None))
            return
        f_idx = np.nonzero(op != 0)[0]
        ctl = self.overload
        gang_nets: List[SpokeNet] = []
        for net in self.nets.values():
            if net.node.paused:
                # hold the whole block; drains via _drain_pause_buffer
                net.pause_buffer.append(("__packed__", (x, y, op), None, None))
                continue
            if ctl is not None and net.overload is not None:
                # block-granular admission (like pause): an over-limit
                # tenant under pressure sheds/serves its forecast rows
                # and defers its training rows for this whole block
                over = ctl.spend(net, n)
                if over and ctl.level >= ELEVATED:
                    self._overload_packed(net, x, y, op, f_idx)
                    continue
            if net.pipeline._cohort is not None:
                # cohort members advance in LOCKSTEP below so same-cohort
                # flushes stage into shared gang launches (per-net row
                # order, holdout cycle and flush points are identical to
                # the solo path; they are exempt from cooperative pause —
                # gang scheduling IS the fairness mechanism)
                gang_nets.append(net)
                continue
            self._process_packed_for_net(net, x, y, f_idx)
        if len(gang_nets) == 1:
            self._process_packed_for_net(gang_nets[0], x, y, f_idx)
        elif gang_nets:
            self._process_packed_gang(gang_nets, x, y, f_idx)
        self._flush_cohorts()
        self._guard_tick_all()
        self._lifecycle_tick_all()
        if ctl is not None:
            self._overload_tick()
        self.poll_serving()
        nt = n - int(f_idx.size)
        if nt:
            pc = self._poll_counter
            self._poll_counter += nt
            if self.config.test:
                pe = self.config.poll_every
                for _ in range(self._poll_counter // pe - pc // pe):
                    self._on_poll()

    def buffered_packed_dim(self) -> Optional[int]:
        """Feature width of buffered pre-creation packed rows, if any."""
        head = self._packed_buffer.peek()
        if head is not None:
            return int(head[1][0].shape[1])
        return None

    def _adapt_width(self, rows: np.ndarray, dim: int) -> np.ndarray:
        """Pad/truncate packed rows to a net's feature width (nets created
        with a different dim than the packed stream still train)."""
        w = rows.shape[1]
        if w == dim:
            return rows
        if w > dim:
            return rows[:, :dim]
        out = np.zeros((rows.shape[0], dim), np.float32)
        out[:, :w] = rows
        return out

    @staticmethod
    def _dense_rows_to_coo(rows: np.ndarray, max_nnz: int):
        """Dense packed rows -> per-row padded COO (for sparse nets fed by
        the dense bulk-ingest path; nnz beyond the budget truncates)."""
        n = rows.shape[0]
        idx = np.zeros((n, max_nnz), np.int32)
        val = np.zeros((n, max_nnz), np.float32)
        for i in range(n):
            nz = np.nonzero(rows[i])[0][:max_nnz]
            idx[i, : nz.size] = nz
            val[i, : nz.size] = rows[i, nz]
        return idx, val

    def _holdout_filter(
        self, net: SpokeNet, tx: np.ndarray, ty: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized 8-of-10 holdout split over a packed segment; evicted
        test points re-enter the training flow at the slot of the row that
        evicted them. Identity when test mode is off. Phase-attributed as
        ``holdout`` when the telemetry plane is armed."""
        ph = self._phases
        if ph is None:
            return self._holdout_filter_inner(net, tx, ty)
        with ph.phase("holdout"):
            return self._holdout_filter_inner(net, tx, ty)

    def _holdout_filter_inner(
        self, net: SpokeNet, tx: np.ndarray, ty: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        if not self.config.test:
            return tx, ty
        n = tx.shape[0]
        c = (net.holdout_count + np.arange(n)) % 10
        net.holdout_count += n
        test_mask = c >= 8
        keep_idx = np.nonzero(~test_mask)[0]
        ev_x: List[np.ndarray] = []
        ev_y: List[float] = []
        ev_pos: List[int] = []
        for i in np.nonzero(test_mask)[0]:
            evicted = net.test_set.append((tx[i].copy(), float(ty[i])))
            if evicted is not None:
                ev_x.append(evicted[0])
                ev_y.append(evicted[1])
                ev_pos.append(int(i))
        if ev_pos:
            pos = np.concatenate([keep_idx, np.asarray(ev_pos)])
            order = np.argsort(pos, kind="stable")
            tx = np.concatenate([tx[keep_idx], np.stack(ev_x)])[order]
            ty = np.concatenate(
                [ty[keep_idx], np.asarray(ev_y, np.float32)]
            )[order]
        else:
            tx = tx[keep_idx]
            ty = ty[keep_idx]
        return tx, ty

    def _train_packed(self, net: SpokeNet, tx: np.ndarray, ty: np.ndarray) -> None:
        n = tx.shape[0]
        if n == 0:
            return
        if net.sparse:
            # the packed stream is dense-featured; sparse nets re-sparsify
            # row by row (categorical-rich lines take the per-record path
            # upstream, __main__._packed_training_source)
            sidx, sval = self._dense_rows_to_coo(tx, net.max_nnz)
            for i in range(n):
                self._train(net, (sidx[i], sval[i]), float(ty[i]))
            return
        tx = self._adapt_width(tx, net.dim)
        tx, ty = self._holdout_filter(net, tx, ty)
        i = 0
        total = tx.shape[0]
        while i < total:
            i += self._staged_add(net.batcher, tx, ty, i)
            if net.batcher.full:
                net.flush_batch()

    def _serve_packed(
        self, net: SpokeNet, x: np.ndarray, f_idx: np.ndarray
    ) -> None:
        if net.serving is not None:
            self._queue_packed(net, x, f_idx)
            return
        f_idx = self._route_packed_candidates(net, x, f_idx)
        if f_idx.size == 0:
            return
        self._serve_packed_baseline(net, x, f_idx)

    def _serve_packed_baseline(
        self, net: SpokeNet, x: np.ndarray, f_idx: np.ndarray
    ) -> None:
        """Immediate packed-route serving through the ACTIVE model (the
        canary split, when armed, already happened upstream)."""
        if net.sparse:
            sidx, sval = self._dense_rows_to_coo(x[f_idx], net.max_nnz)
            for j in range(f_idx.size):
                inst = DataInstance(
                    numerical_features=x[int(f_idx[j])].tolist(),
                    operation=FORECASTING,
                )
                self._serve(net, inst, (sidx[j], sval[j]))
            return
        rows = self._adapt_width(x[f_idx], net.dim)
        self._drain_staged_fits(net)
        for s in range(0, f_idx.size, PREDICT_BATCH):
            chunk = rows[s : s + PREDICT_BATCH]
            t0 = time.perf_counter()
            xb = net.predict_pad(chunk.shape[0])
            xb[: chunk.shape[0]] = chunk
            with self.serve_timer:
                preds = net.node.on_forecast_batch(xb)
            for j in range(chunk.shape[0]):
                inst = DataInstance(
                    numerical_features=chunk[j].tolist(),
                    operation=FORECASTING,
                )
                self._emit_prediction(
                    Prediction(net.request.id, inst, float(preds[j]))
                )
            lat = (time.perf_counter() - t0) * 1000.0
            for _ in range(chunk.shape[0]):
                net.serve_stats.note(lat)

    def _queue_packed(
        self, net: SpokeNet, x: np.ndarray, f_idx: np.ndarray
    ) -> None:
        """Admit packed-route forecast rows into the net's serving queue.
        Dense rows defer DataInstance construction to emission; sparse
        rows carry it (the payload features are the pre-COO dense row)."""
        f_idx = self._route_packed_candidates(net, x, f_idx)
        if f_idx.size == 0:
            return
        plane = self.serving_plane
        if net.sparse:
            sidx, sval = self._dense_rows_to_coo(x[f_idx], net.max_nnz)
            for j in range(f_idx.size):
                inst = DataInstance(
                    numerical_features=x[int(f_idx[j])].tolist(),
                    operation=FORECASTING,
                )
                plane.admit(net, inst, (sidx[j], sval[j]))
            return
        rows = self._adapt_width(x[f_idx], net.dim)
        for j in range(rows.shape[0]):
            plane.admit(net, None, rows[j])

    def _train(self, net: SpokeNet, x, y: float) -> None:
        # float32 boundary clamp for the target, matching the packed/C
        # ingest routes (vectorizer.clamp_f32 covers the features): a
        # finite-double target beyond float32 range would otherwise
        # overflow to inf in the batcher and poison the model through a
        # record the validation boundary admitted
        y = min(max(float(y), -F32_MAX), F32_MAX)
        # 20% holdout: counts 8,9 of each 0-9 cycle (FlinkSpoke.scala:94-104)
        c = net.holdout_count % 10
        net.holdout_count += 1
        if self.config.test and c >= 8:
            evicted = net.test_set.append((x, y))
            if evicted is None:
                return
            x, y = evicted
        if net.sparse:
            net.batcher.add(x[0], x[1], y)
        else:
            net.batcher.add(x, y)
        if net.batcher.full:
            net.flush_batch()

    def _serve(self, net: SpokeNet, inst: DataInstance, x) -> None:
        t0 = time.perf_counter()
        if net.sparse:
            ib, vb = net.predict_pad(1)
            ib[0], vb[0] = x
            xb = (ib, vb)
        else:
            xb = net.predict_pad(1)
            xb[0] = x
        self._drain_staged_fits(net)
        with self.serve_timer:
            preds = net.node.on_forecast_batch(xb)
        self._emit_prediction(
            Prediction(net.request.id, inst, float(preds[0]))
        )
        net.serve_stats.note((time.perf_counter() - t0) * 1000.0)

    def _staged_add(self, batcher, tx, ty, i: int) -> int:
        """``batcher.add_many(tx[i:], ty[i:])``, phase-attributed as
        ``stage`` when the telemetry plane is armed (the fit dispatch a
        full batcher triggers times itself into the flush StepTimer —
        the two phases never nest)."""
        ph = self._phases
        if ph is None:
            return batcher.add_many(tx[i:], ty[i:])
        with ph.phase("stage"):
            return batcher.add_many(tx[i:], ty[i:])

    @staticmethod
    def _drain_staged_fits(net: SpokeNet) -> None:
        """Launch a cohort member's staged gang fits BEFORE a serve-timed
        predict: the predict's peek_state would otherwise drain them
        inside the serving timer, double-attributing the fit launch (it
        times itself into the flush timer) to serve_launch percentiles."""
        cohort = net.pipeline._cohort
        if cohort is not None:
            cohort.launch()

    # --- query / termination (FlinkSpoke.scala:136-171) ---

    def _query(self, request: Request) -> None:
        net = self.nets.get(request.id)
        if net is None:
            return
        self.emit_query_response(
            net, request.request_id if request.request_id is not None else 0
        )

    def emit_query_response(self, net: SpokeNet, response_id: int) -> None:
        """Evaluate on the holdout set and emit QueryResponse fragments —
        one per <=max_param_bucket_size model-parameter bucket, the multi-part
        response protocol of FlinkNetwork.sendQueryResponse
        (FlinkNetwork.scala:48-149,151-240). The ResponseMerger re-assembles
        buckets and averages metrics across workers."""
        if net.serving is not None and net.serve_queue.entries:
            # pending forecasts emit BEFORE the response, as the
            # per-record path would have
            self.serving_plane.flush_net(net)
        net.flush_batch()
        self._flush_cohorts()
        # settle any pending guard trip BEFORE evaluating: a query must
        # never report a NaN score off corrupt params the guard was about
        # to roll back
        self._guard_tick_all()
        # ... and any pending lifecycle decision, so the registry view
        # (and its counters) this response carries is settled too
        self._lifecycle_tick_all()
        test = net.test_arrays()
        if test is not None:
            loss, score = net.pipeline.evaluate(*test)
        else:
            loss, score = 0.0, 0.0
        # fold the spoke-side launch tally into the pipeline's hub stats
        # (queries and the terminate probe both pass through here)
        if self._note_wire is not None and net.program_launches:
            self._note_wire(
                net.request.id, 0, "program_launches", net.program_launches
            )
            net.program_launches = 0
        # tenant-mesh width gauge: record the shard count the pipeline's
        # cohort launches actually ran across (max-combined hub-side)
        cohort = net.pipeline._cohort
        if (
            self._note_wire is not None
            and cohort is not None
            and cohort.n_shards > 1
        ):
            self._note_wire(
                net.request.id, 0, "cohort_shards", cohort.n_shards
            )
        # serving telemetry rides the same fold: the served count is a
        # plain counter, the latency percentiles a (p50, p99, p999) triple
        # the job routes to Statistics.note_serve_latency
        if self._note_wire is not None and net.serve_stats.count:
            self._note_wire(
                net.request.id, 0, "forecasts_served", net.serve_stats.count
            )
            self._note_wire(
                net.request.id, 0, "serve_latency_ms",
                net.serve_stats.percentiles(),
            )
            net.serve_stats.reset()
        # overload telemetry: shed/throttle counts fold once (like the
        # launch tally), the pressure level is a peak GAUGE, and the
        # shed-wait p99 rides the same max-combine path as serve latency
        if self._note_wire is not None and self.overload is not None:
            ctl = self.overload
            nid = net.request.id
            shed = ctl.take_shed(nid)
            if shed:
                self._note_wire(nid, 0, "forecasts_shed", shed)
                p99 = ctl.shed_latency_p99(nid)
                if p99:
                    self._note_wire(nid, 0, "shed_latency_ms", p99)
            throttled = ctl.take_throttled(nid)
            if throttled:
                self._note_wire(nid, 0, "records_throttled", throttled)
            if ctl.level_peak:
                self._note_wire(nid, 0, "pressure_level", ctl.level_peak)
        # transport-codec wall time: encode/decode seconds fold as a
        # DELTA since the last fold (query + terminate must never count
        # the same second twice), making codec cost visible in every
        # report instead of only on the codec object
        if self._note_wire is not None and net.node.codec is not None:
            c = net.node.codec
            enc = c.encode_seconds - net._codec_folded[0]
            dec = c.decode_seconds - net._codec_folded[1]
            if enc > 0.0 or dec > 0.0:
                self._note_wire(
                    net.request.id, 0, "codec_seconds", (enc, dec)
                )
                net._codec_folded = (c.encode_seconds, c.decode_seconds)
        # launch-dispatch percentile gauges: the spoke's fit-flush and
        # serving StepTimer windows, max-combined hub-side (cached per
        # timer count so a multi-tenant probe sorts each ring once).
        # Folded ONLY with the telemetry plane armed: these are pure
        # wall-clock values that would otherwise make every unarmed
        # run's statistics report non-reproducible (the bit-identical
        # stats pins across the chaos/codec suites compare full dicts)
        if self._note_wire is not None and self.telemetry is not None:
            if self.step_timer.count:
                self._note_wire(
                    net.request.id, 0, "launch_ms",
                    self._timer_percentiles(self.step_timer),
                )
            if self.serve_timer.count:
                self._note_wire(
                    net.request.id, 0, "serve_launch_ms",
                    self._timer_percentiles(self.serve_timer),
                )
        # model-lifecycle telemetry: shadow/promotion/rollback counter
        # deltas fold once (same once-semantics as the launch tally); the
        # live version id is a max-combined GAUGE like pressureLevel
        if self._note_wire is not None and net.lifecycle is not None:
            for counter, n in net.lifecycle.take_counters().items():
                self._note_wire(net.request.id, 0, counter, n)
            # last-write gauge: always fold the CURRENT live version —
            # including 0 after an operator rollback to the Create model
            self._note_wire(
                net.request.id, 0, "active_version",
                net.lifecycle.active_version,
            )
        desc = net.pipeline.describe()
        qstats = net.node.query_stats()

        # model parameter buckets (termination probes skip the payload:
        # responseId -1 fragments only feed statistics)
        chunks: List[Optional[np.ndarray]] = [None]
        if response_id != TERMINATION_RESPONSE_ID and not net.pipeline.learner.host_side:
            flat, _ = net.pipeline.get_flat_params()
            bucket = self.config.max_param_bucket_size
            chunks = [
                flat[i : i + bucket] for i in range(0, max(flat.size, 1), bucket)
            ] or [None]
        n_buckets = len(chunks)

        for i, chunk in enumerate(chunks):
            learner = dict(desc["learner"]) if i == 0 else {"name": desc["learner"]["name"]}
            if chunk is not None:
                learner["parameters"] = {"bucketValues": chunk.tolist()}
            self._emit_response(
                QueryResponse(
                    response_id=response_id,
                    mlp_id=net.request.id,
                    bucket=i,
                    num_buckets=n_buckets,
                    preprocessors=desc["preprocessors"] if i == 0 else None,
                    learner=learner,
                    protocol=net.protocol if i == 0 else None,
                    data_fitted=qstats["data_fitted"] if i == 0 else 0,
                    loss=loss if i == 0 else None,
                    cumulative_loss=qstats["cumulative_loss"] if i == 0 else None,
                    score=score if i == 0 else None,
                    # the worker's registry view (active version, canary
                    # percentage, per-version shadow scores) rides the
                    # bucket-0 fragment of lifecycle-armed pipelines
                    lifecycle=(
                        net.lifecycle.describe()
                        if i == 0 and net.lifecycle is not None
                        else None
                    ),
                    # the tail of this pipeline's event ring rides the
                    # bucket-0 fragment when the flight recorder is armed
                    # (ResponseMerger keeps the last non-null tail, the
                    # lifecycle merge rule)
                    events=(
                        self.events.tail_for(net.request.id)
                        if i == 0
                        and self.events is not None
                        and net.events_cfg is not None
                        else None
                    ),
                    source_worker=self.worker_id,
                )
            )

    def handle_terminate_probe(self) -> None:
        """Termination probe: flush + evaluate every net, emit responseId -1
        fragments (FlinkSpoke.scala:136-138, FlinkLearning.scala:115-133) and
        let worker nodes push final state. Paused nets resume and drain
        first — quiesce releases cooperative pauses."""
        for net in self.nets.values():
            if net.node.paused:
                net.node.paused = False
            self._drain_pause_buffer(net)
            if self.overload is not None:
                # deferred (throttled) rows train before the final
                # evaluation: deprioritized work is late, never lost
                self._drain_throttled(net)
            net.flush_batch()
            self._flush_cohorts()
            net.node.on_flush()
            self.emit_query_response(net, TERMINATION_RESPONSE_ID)

    def receive_from_hub(
        self,
        network_id: int,
        hub_id: int,
        op: str,
        payload: Any,
        seq: Optional[int] = None,
    ) -> None:
        net = self.nets.get(network_id)
        if net is None:
            return
        if seq is None or not net.channel_armed:
            self._deliver_from_hub(net, network_id, hub_id, op, payload)
            return
        # reliable channel: dedupe/reorder through the per-hub window; a
        # gap past the window NACKs the hub for an authoritative resync
        # and drops the codec's receive bases for this hub's streams (the
        # lost deltas desynced them; the resync/re-anchor realigns)
        window = net.rx_window(hub_id)
        res = window.offer(seq, op, payload)
        if res.duplicates and self._note_wire is not None:
            self._note_wire(
                network_id, hub_id, "duplicates_dropped", res.duplicates
            )
        if res.gap:
            if self._note_wire is not None:
                self._note_wire(network_id, hub_id, "gaps_resynced", 1)
            if self.events is not None and net.events_cfg is not None:
                from omldm_tpu.runtime.events import GAP_RESYNC

                self.events.record(
                    GAP_RESYNC, "window_gap", pipeline=network_id,
                    worker=self.worker_id, stamp=(network_id, seq),
                    side="worker", hub=hub_id,
                    expected=res.gap_from, got=res.gap_to,
                )
            if net.node.codec is not None:
                net.node.codec.reset_rx_stream(f"h{hub_id}>w{self.worker_id}")
                net.node.codec.reset_rx_stream(f"h{hub_id}>*")
            net.node.send(OP_NACK, {"gap": True}, hub_id)
        for d_op, d_payload in res.deliver:
            self._deliver_from_hub(net, network_id, hub_id, d_op, d_payload)

    def _deliver_from_hub(
        self, net: SpokeNet, network_id: int, hub_id: int, op: str, payload: Any
    ) -> None:
        # sampled round tracing: an outstanding span on this stream
        # completes with the hub<->spoke round-trip latency
        tel = self.telemetry
        if tel is not None and tel.spans.active:
            tel.spans.maybe_close(network_id, hub_id, self.worker_id, op)
        if (
            self.events is not None
            and op == OP_RESYNC
            and net.events_cfg is not None
        ):
            # the worker accepted an authoritative re-ship: the recovery
            # half of a NACK/rejection chain, recorded so the bundle shows
            # the catch-up landing (not just being decided hub-side)
            from omldm_tpu.runtime.events import CHANNEL_RESYNC

            self.events.record(
                CHANNEL_RESYNC, "authoritative_reship",
                pipeline=network_id, worker=self.worker_id, hub=hub_id,
            )
        if net.serving is not None and net.serve_queue.entries:
            # a hub payload may replace this net's model wholesale (round
            # release, broadcast, resync): exact-mode serving drains the
            # queue with the pre-replacement params first
            self.serving_plane.fence(net)
        # deliver() is the worker-side decode boundary (transport codec)
        net.node.deliver(op, payload, hub_id)
        # cooperative multi-pipeline fairness: every hub RPC for one net
        # TOGGLES the others (FlinkSpoke.scala:127-131) — alternating
        # pause/resume yields the spoke between hosted pipelines; a net
        # that just resumed drains the records buffered while paused.
        # Cohort-ATTACHED nets are exempt: they advance in gang lockstep,
        # which provides the fairness the toggle approximates (and a
        # toggle storm across a 64-member cohort would thrash every
        # member through pause buffers on each sync reply)
        for other_id, other in self.nets.items():
            if other_id == network_id:
                continue
            if other.pipeline._cohort is not None:
                continue
            other.node.toggle()
            if not other.node.paused:
                self._drain_pause_buffer(other)

    def flush_rx_windows(self) -> None:
        """Stream quiesce: deliver everything the receive windows still
        hold — their gaps will never fill once the stream ended.
        Snapshots both dicts: a delivered release can synchronously drain
        blocked batches, push, and make the hub reply into a window (or
        net) not yet visited."""
        for network_id, net in list(self.nets.items()):
            net._quiesced = True
            for hub_id, window in list(net._rx_windows.items()):
                for op, payload in window.flush():
                    self._deliver_from_hub(net, network_id, hub_id, op, payload)

    def _process_packed_for_net(self, net, x, y, f_idx) -> None:
        """One net's share of a packed block: serve each forecast at its
        stream position (train the rows before it first), matching
        per-record ordering. Serving-armed dense nets take the bulk
        span-admission walker instead of the per-position loop."""
        if self._process_packed_serving_bulk([net], x, y, f_idx):
            return
        n = x.shape[0]
        prev = 0
        for f in f_idx:
            f = int(f)
            if f > prev:
                self._train_packed(net, x[prev:f], y[prev:f])
            self._serve_packed(net, x, np.asarray([f]))
            if self._any_serving:
                self.serving_plane.maybe_fill_flush()
            prev = f + 1
        if prev < n:
            self._train_packed(net, x[prev:], y[prev:])

    # --- overload-control plane (runtime.overload) -----------------------

    def _overload_tick(self) -> None:
        """Pressure re-derivation + the level-transition actions: entering
        CRITICAL sheds over-limit tenants' QUEUED forecasts (they would
        otherwise serve through a saturated plane after sitting out the
        whole episode); recovered tenants (and everyone at OK) drain
        their deferred training rows back into the stream."""
        ctl = self.overload
        old, new = ctl.tick()
        if new >= CRITICAL and old < CRITICAL and self.serving_plane is not None:
            for net in list(self.nets.values()):
                if (
                    net.overload is not None
                    and net.overload.shed
                    and net.serving is not None
                    and net.serve_queue.entries
                    and ctl.is_over(net.request.id)
                ):
                    self._shed_queued(net)
        for nid in ctl.drainable():
            net = self.nets.get(nid)
            if net is not None and not net.node.paused:
                self._drain_throttled(net)

    def _quarantine_shed(self, net: SpokeNet, payload, depth: int) -> None:
        if self._quarantine is not None:
            # an explicit SHED record — reason-coded, carrying the
            # originating tenant and its queue depth — instead of a
            # silent timeout (stream name matches the job's forecasting
            # stream so dead-letter accounting counts it as a record)
            self._quarantine(
                "forecastingData", payload, "shed_overload",
                extra={"tenant": net.request.id, "queueDepth": depth},
            )

    def _shed_forecast(self, net: SpokeNet, inst: DataInstance) -> None:
        """Admission-time shed of one forecasting record (CRITICAL level,
        over-limit tenant): zero wait — the record is refused before it
        queues, so it contributes no shed-latency sample. The quarantine
        payload stays COMPACT (a preformatted row count, not the feature
        vector): shedding must be far cheaper than serving, and overload
        sheds reject volume, not malformed content worth archiving."""
        self.overload.note_shed(net.request.id, 1)
        self._quarantine_shed(
            net, "rows=1 source=admission", net.serve_queue.n_rows
        )

    def _shed_packed(self, net: SpokeNet, f_idx: np.ndarray) -> None:
        """Admission-time shed of a packed block's forecast rows."""
        rows = int(f_idx.size)
        self.overload.note_shed(net.request.id, rows)
        self._quarantine_shed(
            net, {"rows": rows, "source": "packed"}, net.serve_queue.n_rows
        )

    def _shed_queued(self, net: SpokeNet) -> None:
        """CRITICAL-entry shed of a tenant's ALREADY-QUEUED forecasts;
        each entry's enqueue->shed wait feeds the shedLatencyMs
        percentile."""
        depth = net.serve_queue.n_rows
        entries, n_rows = self.serving_plane.take_queue(net)
        if not entries:
            return
        ctl = self.overload
        now = ctl.now()
        for inst, x, t0 in entries:
            k = 1 if inst is not None else _entry_rows(x)
            ctl.note_shed(net.request.id, k, (now - t0) * 1000.0)
        self._quarantine_shed(
            net, {"rows": n_rows, "source": "queue"}, depth
        )

    def _overload_packed(
        self, net: SpokeNet, x, y, op, f_idx: np.ndarray
    ) -> None:
        """An over-limit tenant's share of a packed block under pressure:
        forecasts shed at CRITICAL (served normally at ELEVATED — only
        training deprioritizes there), training rows defer behind healthy
        tenants' work."""
        ctl = self.overload
        if f_idx.size:
            if ctl.level >= CRITICAL and net.overload.shed:
                self._shed_packed(net, f_idx)
            else:
                self._serve_packed(net, x, f_idx)
        t_idx = np.nonzero(op == 0)[0]
        if t_idx.size:
            entry = (
                "__packed__",
                (x[t_idx], y[t_idx], np.zeros((t_idx.size,), np.uint8)),
                None, None,
            )
            self._defer_training(net, entry, int(t_idx.size))

    def _defer_training(self, net: SpokeNet, entry: tuple, rows: int) -> None:
        """Deprioritize an over-limit tenant's training rows into its
        bounded deferral ring (drained when the tenant recovers, pressure
        clears, or the terminate probe fires); ring overflow — the
        oldest rows dropping — is quarantined with reason ``throttled``
        rather than lost silently."""
        ctl = self.overload
        nid = net.request.id
        buf = ctl.deferred.get(nid)
        if buf is None:
            buf = ctl.deferred[nid] = _PauseBuffer(net.overload.defer_cap)
        before = len(buf)
        buf.append(entry)
        ctl.note_throttled(nid, rows)
        evicted = before + rows - len(buf)
        if evicted > 0 and self._quarantine is not None:
            self._quarantine(
                "trainingData", {"rows": evicted}, "throttled",
                extra={"tenant": nid, "queueDepth": len(buf)},
            )

    def _drain_throttled(self, net: SpokeNet) -> None:
        """Re-admit a tenant's deferred training rows (no re-spend: the
        rows were accounted when they arrived)."""
        ctl = self.overload
        if ctl is None:
            return
        buf = ctl.deferred.get(net.request.id)
        if buf is None or buf.is_empty:
            return
        for operation, x, target, _inst in buf.drain():
            if operation == "__packed__":
                px, py, pop = x
                self._process_packed_for_net(
                    net, px, py, np.nonzero(pop != 0)[0]
                )
            else:
                self._train(net, x, 0.0 if target is None else target)

    def queue_depths(self) -> Dict[str, int]:
        """Uniform queue-depth snapshot for this spoke — the accessors the
        overload controller reads as pressure signals, folded into
        ``StreamJob.tenant_topology()`` and the benchmark result rows."""
        return {
            "serving": (
                self.serving_plane.queued()
                if self.serving_plane is not None else 0
            ),
            "batcher": int(
                sum(net.batcher.queued() for net in self.nets.values())
            ),
            "throttled": (
                self.overload.backlog_rows()
                if self.overload is not None else 0
            ),
            "paused": int(
                sum(len(net.pause_buffer) for net in self.nets.values())
            ),
            "pre_create": len(self.record_buffer) + len(self._packed_buffer),
        }

    # --- cohort gang dispatch (runtime.cohort) ---------------------------

    def _flush_cohorts(self) -> None:
        if self.cohorts is not None:
            self.cohorts.flush()

    # --- model-integrity guard (omldm_tpu.guard) -------------------------

    def _guard_tick_all(self) -> None:
        """Evaluate every guarded net's pending in-program health results
        (noted by the fit launches since the last tick) and run the
        recovery ladder for any that tripped. One flag read when no hosted
        net is guard-armed."""
        if not self._any_guard:
            return
        for net in list(self.nets.values()):
            guard = net.pipeline.guard
            if guard is None:
                continue
            reason = guard.check()
            if reason is None:
                guard.maybe_snapshot(net.pipeline)
            else:
                self._guard_trip(net, reason)

    def _guard_trip(self, net: SpokeNet, reason: str) -> None:
        """Divergence detected on one net: contain, roll back, resync.

        - cohort members EVICT to solo execution first (Cohort.detach:
          state materializes out of the stacked tree, the slot frees, no
          recompile, siblings bitwise untouched) so the corrupt state and
          its recovery churn never ride another tenant's gang launch;
        - parameters roll back to the last-known-good snapshot;
        - the worker asks its hub shards for an authoritative resync
          (OP_NACK -> OP_RESYNC), catching up to the fleet model where one
          exists instead of re-converging from the snapshot alone."""
        nid = net.request.id
        journal = self.events if net.events_cfg is not None else None
        if journal is not None:
            # the trip itself is the incident: record the decision chain
            # and dump the ring — the post-mortem must not depend on the
            # stream surviving to terminate
            from omldm_tpu.runtime.events import GUARD_TRIP

            journal.record(
                GUARD_TRIP, reason, pipeline=nid, worker=self.worker_id
            )
        if net.pipeline._cohort is not None and self.cohorts is not None:
            self.cohorts.retire(net.pipeline)
            if self._note_wire is not None:
                self._note_wire(nid, 0, "members_evicted", 1)
            if journal is not None:
                from omldm_tpu.runtime.events import GUARD_EVICT

                journal.record(
                    GUARD_EVICT, reason, pipeline=nid,
                    worker=self.worker_id,
                )
        net.pipeline.guard.rollback(net.pipeline)
        if self._note_wire is not None:
            self._note_wire(nid, 0, "rollbacks_performed", 1)
        if journal is not None:
            from omldm_tpu.runtime.events import GUARD_ROLLBACK

            journal.record(
                GUARD_ROLLBACK, reason, pipeline=nid, worker=self.worker_id
            )
            journal.incident("guard_trip", pipeline=nid)
        if net.serving is not None and net.serve_queue.entries:
            # queued forecasts flush through the ROLLED-BACK (last-known-
            # good) model — never through the params the guard condemned
            self.serving_plane.flush_net(net)
        if net.node.codec is not None:
            # the rollback replaced the model wholesale AND corrupt state
            # may already have shipped: EF residuals and topk tx bases are
            # stale/poisoned on both ends (same treatment as the rescale
            # merge path)
            net.node.codec.reset_streams()
        net.node.request_resync()
        if getattr(net.node, "waiting", False):
            # a blocking worker whose poisoned push was suppressed or
            # rejected may be mid-barrier with nothing in flight — and if
            # the hub holds no authoritative state yet, the resync above
            # ships nothing back. Re-push the now-healthy state so the
            # round can complete (idempotent: barrier entries are
            # worker-keyed — the same repair on_stall performs).
            net.node.resend_state()

    # --- model-lifecycle plane (runtime.lifecycle) -----------------------

    def _lifecycle_shadow(self, request: Request) -> None:
        """Shadow verb: register the request's candidate configuration and
        enter shadow mode — the candidate trains on the same flushed
        micro-batches and holdout-scores on the same test window, while
        serving stays 100% on the active version.

        The candidate must keep the baseline's flat-parameter SIZE (new
        hyper-parameters, same architecture): a promotion swaps the
        protocol node's pipeline, and the hub's model state — which a
        promotion does not rebuild — would crash the next sync round on a
        shape mismatch. A size-changing candidate quarantines instead of
        arming (the operator's primitive for an architecture change
        remains the destructive Update, as in the reference)."""
        net = self.nets.get(request.id)
        if net is None or net.lifecycle is None:
            return
        pipe, spec = build_candidate(
            net, request, net.lifecycle.next_version
        )
        try:
            cand_size = pipe.get_flat_params()[0].size
            base_size = net.pipeline.get_flat_params()[0].size
        except Exception:
            cand_size = base_size = None  # host-side: no flat contract
        if cand_size != base_size:
            if self._quarantine is not None:
                self._quarantine(
                    "requests", request.to_json(), "rejected_request",
                    detail=(
                        "lifecycle candidate changes the parameter shape "
                        f"({cand_size} vs {base_size}); use Update for "
                        "architecture changes"
                    ),
                )
            return
        pipe.on_launch = net._note_launch
        net.lifecycle.arm_shadow(pipe, spec)

    def _lifecycle_promote_request(self, request: Request) -> None:
        """Promote verb: a shadow candidate starts its canary traffic
        ramp; a canarying candidate force-completes (operator override of
        the remaining ramp — the auto-promotion checks are skipped, the
        swap mechanics are identical)."""
        net = self.nets.get(request.id)
        if net is None or net.lifecycle is None:
            return
        entry = net.lifecycle.candidate_entry
        if entry is None:
            return
        if entry.state == SHADOW:
            net.lifecycle.start_canary()
        elif entry.state == CANARY:
            self._lifecycle_promote(net)

    def _lifecycle_rollback_request(self, request: Request) -> None:
        """Rollback verb: demote a live candidate (shadow or canary) —
        routing snaps back to 100% baseline, which never rolled anywhere —
        or, with no candidate in flight, reactivate the retained
        pre-promotion version (undo of a completed promotion)."""
        net = self.nets.get(request.id)
        if net is None or net.lifecycle is None:
            return
        lc = net.lifecycle
        if lc.candidate_entry is not None:
            lc.demote_candidate(REASON_OPERATOR)
            return
        entry = lc.previous
        if entry is None:
            return
        if net.serving is not None and net.serve_queue.entries:
            # queued forecasts drain through the outgoing model first
            self.serving_plane.flush_net(net)
        if net.pipeline._cohort is not None and self.cohorts is not None:
            self.cohorts.retire(net.pipeline)
        net.node.pipeline = lc.reactivate(entry, net)
        self._lifecycle_post_swap(net)

    def _lifecycle_tick_all(self) -> None:
        """Boundary decision pass for every net with a live candidate
        (runs next to the guard tick): candidate guard trips and shadow-
        score regressions roll the candidate back; a completed ramp
        promotes it. One flag read when no hosted net is lifecycle-armed."""
        if not self._any_lifecycle:
            return
        for net in list(self.nets.values()):
            lc = net.lifecycle
            if lc is None or lc.candidate is None:
                continue
            action = lc.tick(net)
            if action is None:
                continue
            if action[0] == "rollback":
                lc.demote_candidate(action[1])
            else:
                self._lifecycle_promote(net)

    def _lifecycle_promote(self, net: SpokeNet) -> None:
        """Runtime half of a promotion: drain the serving queue through
        the outgoing model, detach it from its cohort (its state
        materializes locally so the registry retains a live pipeline for
        operator Rollback), swap the candidate in as the protocol node's
        pipeline, and re-anchor transport/protocol state exactly like the
        rescale model-seed path — the model was replaced wholesale."""
        if net.serving is not None and net.serve_queue.entries:
            self.serving_plane.flush_net(net)
        if net.pipeline._cohort is not None and self.cohorts is not None:
            self.cohorts.retire(net.pipeline)
        net.node.pipeline = net.lifecycle.promote(net)
        self._lifecycle_post_swap(net)

    def _lifecycle_post_swap(self, net: SpokeNet) -> None:
        """Shared tail of promote/reactivate: EF residuals and topk bases
        computed against the replaced model are stale (same treatment as
        the rescale grow-seed), drift baselines re-anchor, and the new
        active model's guard — candidates always carry one — reseeds its
        LKG ring at the promoted params (a rollback must never land on
        the other version's snapshot)."""
        if net.node.codec is not None:
            net.node.codec.reset_streams()
        net.node.on_model_seeded()
        if net.pipeline.guard is not None:
            self._any_guard = True
            net.pipeline.guard.reseed(net.pipeline)

    def _serve_candidate(self, net: SpokeNet, inst, row) -> None:
        """Serve one canary-routed forecast through the candidate model —
        immediately, never queued (the candidate is outside the serving
        plane's exact-staleness contract; its own fit cadence makes the
        padded solo predict trivially exact) — tagging the prediction
        with the candidate version so operators (and the bitwise identity
        gates) can separate candidate output from the active version's."""
        lc = net.lifecycle
        entry = lc.candidate_entry
        t0 = time.perf_counter()
        rows = np.asarray(row, np.float32).reshape(1, -1)
        with self.serve_timer:
            val = float(lc.predict_candidate(rows)[0])
        self._emit_prediction(
            Prediction(net.request.id, inst, val, version=entry.version)
        )
        net.serve_stats.note((time.perf_counter() - t0) * 1000.0)

    def _route_packed_candidates(
        self, net: SpokeNet, x: np.ndarray, f_idx: np.ndarray
    ) -> np.ndarray:
        """Packed-route half of the canary split: walk the block's
        forecast rows through the count-clocked router; candidate-routed
        rows serve immediately through the candidate, the rest return for
        the baseline path. Identity (no clock ticks) without an active
        canary."""
        lc = net.lifecycle
        if lc is None or not lc.canary_active:
            return f_idx
        keep: List[int] = []
        for f in f_idx:
            f = int(f)
            if lc.route_candidate():
                row = self._adapt_width(x[f : f + 1], net.dim)[0]
                self._serve_candidate(
                    net, DataInstance.forecast_payload(row), row
                )
            else:
                keep.append(f)
        return np.asarray(keep, np.int64)

    def _process_packed_gang(self, nets, x, y, f_idx) -> None:
        """Lockstep twin of ``_process_packed_for_net`` over ALL nets:
        segments between forecasts gang-train, forecasts gang-serve at
        their stream position."""
        if self._process_packed_serving_bulk(nets, x, y, f_idx):
            return
        n = x.shape[0]
        prev = 0
        for f in f_idx:
            f = int(f)
            if f > prev:
                self._train_packed_gang(nets, x[prev:f], y[prev:f])
            self._serve_packed_gang(nets, x, f)
            prev = f + 1
        if prev < n:
            self._train_packed_gang(nets, x[prev:], y[prev:])

    def _process_packed_serving_bulk(self, nets, x, y, f_idx) -> bool:
        """Serving-plane fast path for a packed block: when EVERY net is
        dense and serving-armed (equal batch size and fill — lockstep),
        the per-position serve loop collapses into span-wise bulk
        admission between batcher-fill boundaries.

        Exactness argument: a queued forecast's answer only depends on the
        params at its flush, and the fence flushes queues before any fit
        dispatches — so admission order relative to the TRAINING rows
        between two fills is immaterial. The walker feeds training rows in
        fill-sized chunks and, before each chunk, admits every forecast
        positioned before the row that would complete the fill: any fence
        the chunk triggers then flushes exactly the forecasts the
        per-record path would have served pre-fit. (With holdout sampling
        the real fill lands at or after the chunk end — the bound is
        conservative, never early.) Returns False when ineligible; the
        caller falls back to the per-position loop."""
        if f_idx.size == 0 or not nets:
            return False
        b0 = nets[0].batcher.batch_size
        fill0 = len(nets[0].batcher)
        for net in nets:
            if (
                net.serving is None
                or net.sparse
                or net.batcher.batch_size != b0
                or len(net.batcher) != fill0
                # an active canary needs the per-position walk: the
                # count-clocked split is per forecast row, and a span
                # admission would route whole blocks at once
                or (
                    net.lifecycle is not None
                    and net.lifecycle.canary_active
                )
            ):
                return False
        n = x.shape[0]
        plane = self.serving_plane
        t_mask = np.ones((n,), bool)
        t_mask[f_idx] = False
        t_idx = np.nonzero(t_mask)[0]
        rows_cache: Dict[int, np.ndarray] = {}

        def admit(lo: int, hi: int) -> None:
            # one enqueue clock per span (every row of the span becomes
            # servable at this moment), then flush right away if a queue
            # filled — flushing EARLIER than the fence is always
            # exact-safe, and it keeps enqueue->emit latency at span
            # granularity instead of training-chunk granularity
            now = plane._clock()
            for net in nets:
                rows = rows_cache.get(net.dim)
                if rows is None:
                    rows = rows_cache[net.dim] = self._adapt_width(
                        x[f_idx], net.dim
                    )
                plane.admit_rows(net, rows[lo:hi], now)
            plane.maybe_fill_flush()

        fi = 0  # forecasts admitted so far (index into f_idx)
        ti = 0  # training rows fed so far (index into t_idx)
        while ti < t_idx.size:
            room = max(b0 - len(nets[0].batcher), 1)
            chunk = t_idx[ti : ti + room]
            ti += chunk.size
            bound = int(chunk[-1])
            hi = fi + int(np.searchsorted(f_idx[fi:], bound))
            if hi > fi:
                admit(fi, hi)
                fi = hi
            self._train_packed_gang(nets, x[chunk], y[chunk])
        if fi < f_idx.size:
            admit(fi, f_idx.size)
        return True

    def _train_packed_gang(
        self, nets: List[SpokeNet], tx: np.ndarray, ty: np.ndarray
    ) -> None:
        """Feed a training segment to every net in batch-size strides:
        each net's row order, holdout cycle and flush points are identical
        to its solo path — only the flush ORDER across nets interleaves,
        so same-cohort flushes stage into one gang launch (forced by the
        members' own sync points, or at the block's gang barrier)."""
        if tx.shape[0] == 0:
            return
        if not self.config.test:
            # shared-ingest fast path: identical-stream cohort members
            # batch through ONE leader batcher; nets it cannot take stay
            # in the stride loop below
            nets = self._train_packed_shared_groups(nets, tx, ty)
            if not nets:
                return
        feeds = []
        for net in nets:
            if net.sparse:
                # sparse nets keep the row-wise path (no gang kernels)
                self._train_packed(net, tx, ty)
                continue
            ntx = self._adapt_width(tx, net.dim)
            ftx, fty = self._holdout_filter(net, ntx, ty)
            feeds.append([net, ftx, fty, 0])
        pending = True
        while pending:
            pending = False
            for feed in feeds:
                net, ftx, fty, cur = feed
                if cur >= ftx.shape[0]:
                    continue
                cur += self._staged_add(net.batcher, ftx, fty, cur)
                feed[3] = cur
                if net.batcher.full:
                    net.flush_batch()
                if cur < ftx.shape[0]:
                    pending = True

    def _train_packed_shared_groups(
        self, nets: List[SpokeNet], tx: np.ndarray, ty: np.ndarray
    ) -> List[SpokeNet]:
        """Feed identical-stream cohort members through ONE leader batcher
        (same-object flushes let the cohort stage ONE copy and launch the
        shared-input program). Returns the nets the shared path cannot
        take. Eligibility: untainted attached members of the same cohort
        with equal batcher fill — every member then holds the SAME pending
        stream suffix, so the leader's batches are bitwise everyone's."""
        groups: Dict[Any, List[SpokeNet]] = {}
        rest: List[SpokeNet] = []
        for net in nets:
            cohort = net.pipeline._cohort
            if (
                cohort is not None
                and not net.sparse
                and not net.shared_taint
                and net.dim == tx.shape[1]
                and net.node.consumes_batch_synchronously
                # a live shadow/canary candidate twin-trains at this
                # net's OWN flush boundary (SpokeNet.flush_batch); the
                # leader-batcher path bypasses it, so candidate-carrying
                # nets keep the solo stride loop (bitwise identical)
                and not (
                    net.lifecycle is not None
                    and net.lifecycle.training_active
                )
            ):
                groups.setdefault(cohort, []).append(net)
            else:
                rest.append(net)
        for members in groups.values():
            fills = {len(m.batcher) for m in members}
            sizes = {m.batcher.batch_size for m in members}
            if len(members) < 2 or len(fills) != 1 or len(sizes) != 1:
                rest.extend(members)
                continue
            self._train_packed_shared(members, tx, ty)
        return rest

    def _train_packed_shared(
        self, members: List[SpokeNet], tx: np.ndarray, ty: np.ndarray
    ) -> None:
        leader = members[0]
        batcher = leader.batcher
        i = 0
        total = tx.shape[0]
        while i < total:
            i += self._staged_add(batcher, tx, ty, i)
            if batcher.full:
                for net in members:
                    # every member's model is about to change: exact-mode
                    # serving drains each queue first (same fence the
                    # per-member flush_batch applies)
                    if net.serving is not None and net.serve_queue.entries:
                        self.serving_plane.fence(net)
                flushed = batcher.flush_views()
                x, y, m = flushed
                for net in members:
                    # settle deferred sync points BEFORE the view-vs-copy
                    # decision: one may flip this member to waiting
                    net.pipeline.settle_deferred()
                    if getattr(net.node, "waiting", False):
                        # blocked batches must own their arrays; everyone
                        # else consumes (stages a copy) synchronously
                        net.node.on_training_batch(x.copy(), y.copy(), m)
                    else:
                        net.node.on_training_batch(x, y, m)
        for net in members[1:]:
            net.batcher.clone_pending_from(batcher)

    def _gang_predictions(
        self, entries: List[Tuple[SpokeNet, np.ndarray]]
    ) -> Dict[int, float]:
        """One padded predict launch per cohort with >= 2 participants;
        returns {id(net): prediction} for the nets served by a gang."""
        groups: Dict[Any, List[Tuple[SpokeNet, np.ndarray]]] = {}
        for net, xb in entries:
            groups.setdefault(net.pipeline._cohort, []).append((net, xb))
        out: Dict[int, float] = {}
        for cohort, items in groups.items():
            if len(items) < 2:
                continue
            rows = [(net.pipeline._slot, xb) for net, xb in items]
            preds = cohort.predict_rows(rows)
            for (net, _), (slot, _) in zip(items, rows):
                out[id(net)] = float(preds[slot, 0])
        return out

    def _serve_many(self, inst: DataInstance, entries) -> None:
        """Serve one forecast record to many nets, ganging cohort members
        through one predict launch; emission keeps the nets order.
        Serving-armed nets queue instead (runtime/serving.py) and flush at
        the record boundary below when a queue filled."""
        if self._any_lifecycle:
            # canary split at the serve-admission boundary: candidate-
            # routed forecasts serve through the candidate NOW; everything
            # else takes the exact baseline path (queue or immediate)
            kept = []
            for net, x in entries:
                lc = net.lifecycle
                if lc is not None and lc.route_candidate():
                    self._serve_candidate(net, inst, x)
                else:
                    kept.append((net, x))
            entries = kept
        gang_in = []
        t0 = time.perf_counter()
        for net, x in entries:
            if net.serving is not None:
                self.serving_plane.admit(net, inst, x)
            elif net.gang_predict_ok():
                xb = net.predict_pad(1)
                xb[0] = x
                gang_in.append((net, xb))
        ganged = self._gang_predictions(gang_in) if gang_in else {}
        for net, x in entries:
            if net.serving is not None:
                continue
            pred = ganged.get(id(net))
            if pred is None:
                self._serve(net, inst, x)
            else:
                self._emit_prediction(
                    Prediction(net.request.id, inst, pred)
                )
                net.serve_stats.note((time.perf_counter() - t0) * 1000.0)
        if self._any_serving:
            self.serving_plane.maybe_fill_flush()

    def _serve_packed_gang(self, nets: List[SpokeNet], x: np.ndarray, f: int) -> None:
        """Serve packed-row forecast ``f`` to every net at its stream
        position (gang predict for cohort members, the solo path
        otherwise, the serving queue for armed nets)."""
        gang_in = []
        routed: set = set()
        t0 = time.perf_counter()
        for net in nets:
            if net.serving is not None:
                # _queue_packed runs the canary split internally
                self._queue_packed(net, x, np.asarray([f]))
                continue
            lc = net.lifecycle
            if lc is not None and lc.canary_active and lc.route_candidate():
                row = self._adapt_width(x[f : f + 1], net.dim)[0]
                self._serve_candidate(
                    net, DataInstance.forecast_payload(row), row
                )
                routed.add(id(net))
                continue
            if net.gang_predict_ok():
                row = self._adapt_width(x[f : f + 1], net.dim)[0]
                xb = net.predict_pad(1)
                xb[0] = row
                gang_in.append((net, xb))
        ganged = self._gang_predictions(gang_in) if gang_in else {}
        for net in nets:
            if net.serving is not None or id(net) in routed:
                continue
            pred = ganged.get(id(net))
            if pred is None:
                # the split (if armed) already ran above — baseline only
                self._serve_packed_baseline(net, x, np.asarray([f]))
            else:
                row = self._adapt_width(x[f : f + 1], net.dim)[0]
                inst = DataInstance(
                    numerical_features=row.tolist(),
                    operation=FORECASTING,
                )
                self._emit_prediction(
                    Prediction(net.request.id, inst, pred)
                )
                net.serve_stats.note((time.perf_counter() - t0) * 1000.0)
        if self._any_serving:
            self.serving_plane.maybe_fill_flush()

    def _drain_pause_buffer(self, net: SpokeNet) -> None:
        if net.pause_buffer.is_empty:
            return
        for operation, x, target, inst in net.pause_buffer.drain():
            if operation == "__packed__":
                px, py, pop = x
                self._process_packed_for_net(
                    net, px, py, np.nonzero(pop != 0)[0]
                )
            elif operation == FORECASTING:
                if net.lifecycle is not None and net.lifecycle.route_candidate():
                    self._serve_candidate(net, inst, x)
                elif net.serving is not None:
                    self.serving_plane.admit(net, inst, x)
                else:
                    self._serve(net, inst, x)
            else:
                self._train(net, x, 0.0 if target is None else target)
        if self._any_serving:
            self.serving_plane.maybe_fill_flush()

    # --- live rescale (FlinkSpoke.scala:345-348, SpokeLogic.scala:37-50) ---

    def set_parallelism(self, n_workers: int) -> None:
        """Propagate a live parallelism change to every hosted node."""
        for net in self.nets.values():
            net.node.set_parallelism(n_workers)

    def absorb(self, retired: "Spoke") -> None:
        """Merge a retiring spoke's state into this one (shrink rescale):
        model replicas merge via the learner merge hook, pending batcher
        rows re-enter this spoke's batchers, holdout sets interleave, and
        pre-creation buffers concatenate — the mergingDataBuffers +
        wrapper-merge semantics of the reference's rescale path
        (SpokeLogic.scala:37-50, FlinkSpoke.scala:289-330)."""
        # pending forecasts on BOTH sides serve before any model merges:
        # the retiring replicas' models are about to disappear and the
        # survivors' are about to change (a rescale forces a serving
        # flush in every staleness mode)
        if retired.serving_plane is not None:
            retired.serving_plane.flush_all()
        if self.serving_plane is not None:
            self.serving_plane.flush_all()
        if retired.overload is not None:
            # throttled rows train into the retiring replicas BEFORE the
            # model merge (deprioritized work must not vanish with its
            # spoke), and un-folded shed/throttle counters carry over
            for rnet in retired.nets.values():
                retired._drain_throttled(rnet)
            if self.overload is not None:
                rctl, sctl = retired.overload, self.overload
                for nid in list(rctl._shed):
                    sctl._shed[nid] = (
                        sctl._shed.get(nid, 0) + rctl.take_shed(nid)
                    )
                for nid in list(rctl._throttled):
                    sctl._throttled[nid] = (
                        sctl._throttled.get(nid, 0)
                        + rctl.take_throttled(nid)
                    )
                sctl.level_peak = max(sctl.level_peak, rctl.level_peak)
                sctl.total_shed += rctl.total_shed
                sctl.total_throttled += rctl.total_throttled
        # settle gang state on both sides first: the retiring spoke's
        # cohorts dissolve (members get their state back for the merge);
        # survivors keep their cohorts — merge_from edits flow through the
        # member checkout path
        if retired.cohorts is not None:
            retired.cohorts.detach_all()
        self._flush_cohorts()
        for net_id, rnet in retired.nets.items():
            snet = self.nets.get(net_id)
            if snet is None:
                # this spoke never hosted the pipeline (shouldn't happen in
                # a job-managed rescale): adopt the retiring replica whole
                rnet.shared_taint = True
                self.nets[net_id] = rnet
                if rnet.pipeline.guard is not None:
                    self._any_guard = True
                if rnet.lifecycle is not None:
                    self._any_lifecycle = True
                if rnet.serving is not None:
                    # re-home the queue plumbing: the retired spoke's plane
                    # (already flushed above) is gone with its owner
                    rnet._plane = self._ensure_serving_plane()
                if rnet.overload is not None:
                    # re-home the admission accounting the same way
                    if self.overload is None:
                        self.overload = OverloadController(self)
                    self.overload.arm(rnet)
                continue
            snet.shared_taint = True
            # pending rows train into the surviving replica: the batcher's
            # partial fill AND any batches the retiring node buffered while
            # waiting on a protocol sync (SyncingWorker._blocked — dropping
            # them would break the rescale loss-continuity guarantee)
            pending = [rnet.batcher.drain()]
            for bx, by, bm in getattr(rnet.node, "_blocked", []):
                valid = np.asarray(bm) > 0.0
                if rnet.sparse:
                    bi, bv = bx
                    pending.append(((np.asarray(bi)[valid],
                                     np.asarray(bv)[valid]),
                                    np.asarray(by)[valid]))
                else:
                    pending.append((np.asarray(bx)[valid], np.asarray(by)[valid]))
            for entry in pending:
                if entry is None:
                    continue
                px, py = entry
                if rnet.sparse:
                    for i in range(py.shape[0]):
                        snet.batcher.add(px[0][i], px[1][i], float(py[i]))
                        if snet.batcher.full:
                            snet.flush_batch()
                else:
                    i = 0
                    while i < px.shape[0]:
                        i += snet.batcher.add_many(px[i:], py[i:])
                        if snet.batcher.full:
                            snet.flush_batch()
            snet.pipeline.merge_from([rnet.pipeline])
            # the merge replaced the model wholesale: EF residuals and
            # topk bases computed against the pre-merge model are stale
            if snet.node.codec is not None:
                snet.node.codec.reset_streams()
            # ... and so are last-known-good snapshots: a guard rollback
            # must not undo the absorbed replica's contribution
            if snet.pipeline.guard is not None:
                snet.pipeline.guard.reseed(snet.pipeline)
            # lifecycle: the retiring replica's candidate (if any) retires
            # with its spoke — its registry row is released silently, not
            # counted as a rollback — and its un-folded counter deltas
            # carry over to the survivor like the overload counters do
            if rnet.lifecycle is not None:
                rnet.lifecycle.demote_candidate(None)
                if snet.lifecycle is not None:
                    for k, v in rnet.lifecycle.take_counters().items():
                        snet.lifecycle._bump(k, v)
            # holdout windows interleave (keep-newest overflow), the same
            # merge the reference's rescale uses (CommonUtils.scala:36-48)
            snet.test_set.merge([rnet.test_set])
            snet.holdout_count += rnet.holdout_count
            # records held under a cooperative pause carry over too — and
            # drain immediately if the survivor is running (nothing else
            # may trigger a drain before the terminate probe)
            snet.pause_buffer.merge([rnet.pause_buffer])
            if not snet.node.paused:
                self._drain_pause_buffer(snet)
        # pre-creation buffers carry over
        self.record_buffer.merge([retired.record_buffer])
        self._packed_buffer.merge([retired._packed_buffer])
        self._poll_counter += retired._poll_counter

    def mean_buffer_size(self) -> float:
        """getMeanBufferSize analogue (FlinkSpoke.scala:138): mean pending
        (unfitted) records across hosted pipelines."""
        if not self.nets:
            return 0.0
        return float(np.mean([len(net.batcher) for net in self.nets.values()]))
