"""StreamJob: assembles spokes, hubs, control plane, statistics and sinks.

Reference counterpart: ``Job`` + ``FlinkLearning`` (Job.scala:28-171,
FlinkLearning.scala:33-152) — the dataflow graph of SURVEY.md section 1:
training/forecasting sources -> parsers -> workers; requests -> gatekeeper ->
broadcast; worker<->PS protocol traffic (the reference's Kafka ``psMessages``
feedback loop, Job.scala:76-87, replaced by in-process routing / ICI
collectives); predictions, merged query responses, and final job statistics
out.

The job consumes an ordered event iterable (file replay, in-process queues, or
a Kafka consumer adapter) — the deterministic equivalent of the reference's
Kafka sources, with the same termination protocol driven by a silence timer.
"""

from __future__ import annotations

import copy
import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from omldm_tpu.api.data import FORECASTING, TRAINING, DataInstance, Prediction
from omldm_tpu.api.requests import LIFECYCLE_REQUESTS, Request, RequestType
from omldm_tpu.api.responses import TERMINATION_RESPONSE_ID, QueryResponse
from omldm_tpu.api.stats import JobStatistics
from omldm_tpu.config import JobConfig
from omldm_tpu.runtime.control import PipelineManager
from omldm_tpu.runtime.deadletter import DeadLetterSink
from omldm_tpu.runtime.hub import HubManager
from omldm_tpu.runtime.messages import channel_chaos_spec
from omldm_tpu.runtime.responses import ResponseMerger
from omldm_tpu.runtime.spoke import Spoke, _PauseBuffer
from omldm_tpu.runtime.stats import StatisticsCollector
from omldm_tpu.runtime.vectorizer import Vectorizer
from omldm_tpu.utils import tracing

# event stream names (the reference's Kafka topics, README.md:21-26)
TRAINING_STREAM = "trainingData"
FORECASTING_STREAM = "forecastingData"
REQUEST_STREAM = "requests"
# pseudo-stream carrying pre-vectorized (x, y, op) blocks from the C++
# bulk-ingest path (runtime.fast_ingest); replaces per-record JSON events
PACKED_STREAM = "__packed__"

# rows held for pipelines that have not been created yet, before the FIRST
# deploy (the reference's recordBuffer cap, SpokeLogic.scala:31-35)
PRE_CREATE_BACKLOG_CAP = 100_000


class StreamJob:
    def __init__(
        self,
        config: Optional[JobConfig] = None,
        on_prediction: Optional[Callable[[Prediction], None]] = None,
        on_response: Optional[Callable[[QueryResponse], None]] = None,
        on_performance: Optional[Callable[[JobStatistics], None]] = None,
    ):
        self.config = config or JobConfig()
        self.predictions: List[Prediction] = []
        self.responses: List[QueryResponse] = []
        self.performance: List[JobStatistics] = []
        self._on_prediction = on_prediction
        self._on_response = on_response
        self._on_performance = on_performance

        self.pipeline_manager = PipelineManager()
        # fail fast on a malformed job-wide serving default (the
        # per-pipeline trainingConfiguration.serving table is instead
        # validated at the control gate and drops only its own request)
        from omldm_tpu.runtime.serving import parse_serving_spec

        parse_serving_spec(self.config.serving)
        # ... and the same fail-fast for a malformed job-wide overload
        # default (runtime/overload.py)
        from omldm_tpu.runtime.overload import parse_overload_spec

        parse_overload_spec(getattr(self.config, "overload", ""))
        # ... and for a malformed job-wide lifecycle default
        # (runtime/lifecycle.py)
        from omldm_tpu.runtime.lifecycle import parse_lifecycle_spec

        parse_lifecycle_spec(getattr(self.config, "lifecycle", ""))
        # telemetry plane (runtime/telemetry.py): armed by the job-wide
        # JobConfig.telemetry spec here (fail-fast on a malformed one), or
        # lazily by the first pipeline whose trainingConfiguration carries
        # a telemetry table (see _deploy). Unarmed (the default): the
        # attribute stays None, zero telemetry objects exist, and every
        # route below is the exact pre-plane code path.
        from omldm_tpu.runtime.telemetry import parse_telemetry_spec

        self.telemetry = None
        _tel_cfg = parse_telemetry_spec(getattr(self.config, "telemetry", ""))
        # flight recorder (runtime/events.py): armed by the job-wide
        # JobConfig.events spec here (fail-fast on a malformed one), or
        # lazily by the first pipeline whose trainingConfiguration carries
        # an events table (see _deploy). Unarmed (the default): the
        # attribute stays None, zero recorder objects exist, and every
        # decision site below pays one attribute read.
        from omldm_tpu.runtime.events import parse_events_spec

        self.events = None
        _ev_cfg = parse_events_spec(getattr(self.config, "events", ""))
        self.stats = StatisticsCollector(self.config, self._emit_performance)
        # dead-letter quarantine: malformed / validation-rejected records
        # and requests land here with reason codes instead of vanishing
        # (the reference drops them silently, DataPointParser.scala:13-21)
        self.dead_letter = DeadLetterSink(
            path=self.config.dead_letter_path,
            cap=self.config.dead_letter_cap,
            request_stream=REQUEST_STREAM,
        )
        self.response_merger = ResponseMerger(self._emit_response)
        self.hub_manager = HubManager(self.config, self._ship_to_spoke)
        # deterministic chaos channel on the in-process hub<->spoke bridge
        # (JobConfig.chaos / OMLDM_CHAOS): when armed, both directions run
        # through seeded drop/dup/reorder/delay wrappers, and the reliable
        # layer (sequence numbers + receive windows + NACK/resync) arms
        # itself per pipeline to survive it. Unarmed: both attributes stay
        # None and every route is the exact pre-chaos code path.
        self._chaos_up = self._chaos_down = None
        # seeded burst / hot-tenant injector (the overload plane's chaos
        # driver): armed by the burst keys of the same chaos spec; None
        # otherwise
        self._burst = None
        spec_str = channel_chaos_spec(self.config)
        if spec_str:
            from omldm_tpu.runtime.supervisor import (
                BurstInjector,
                ChaosChannel,
                parse_chaos_spec,
            )

            spec = parse_chaos_spec(spec_str)
            self._chaos_up = ChaosChannel.from_spec(
                self.hub_manager.route, spec, "up", name="spoke>hub"
            )
            self._chaos_down = ChaosChannel.from_spec(
                self._reply_to_spoke, spec, "down", name="hub>spoke"
            )
            self._burst = BurstInjector.from_spec(spec)
        self.spokes: List[Spoke] = [
            self._spawn_spoke(i) for i in range(self.config.parallelism)
        ]
        if _tel_cfg is not None:
            self._arm_telemetry(_tel_cfg)
        if _ev_cfg is not None:
            self._arm_events(_ev_cfg)
        # in-memory mirror trim counters (see _trim_emission)
        self.predictions_trimmed = 0
        self.responses_trimmed = 0
        # live parallelism changes this job's state has been carried
        # across (rescale(); mirrored into every pipeline's Statistics at
        # terminate — the in-process half of the rescalesPerformed counter)
        self.rescales_performed = 0
        self._rr = 0  # round-robin data partitioner (the reference rebalances)
        self._pending_creates: List[Request] = []  # awaiting dim inference
        self._dims: dict = {}  # network_id -> feature dim
        # data that arrives before ANY pipeline is deployed is held here and
        # replayed through the normal routing on the first deploy — the
        # job-level equivalent of the reference's pre-creation recordBuffer
        # (FlinkSpoke.scala:69-80, SpokeLogic.scala:31-35, cap 100k). Without
        # it, a stream whose records precede the Create request would never
        # reach an SPMD-engine pipeline (bridges don't exist yet when the
        # rows flow) and would train only on the host plane's spoke buffers.
        # Backed by the spoke's row-accounted keep-newest buffer; entries
        # are ("inst", DataInstance) or ("__packed__", (x, y, op), None,
        # None) so packed blocks trim by row count.
        self._backlog = _PauseBuffer(PRE_CREATE_BACKLOG_CAP)
        # queue_depths() snapshot taken at terminate, after the drain
        # cascade (None until terminate runs) — the load harness' SLO
        # evaluator asserts no stranded rows from it
        self.terminate_accounting: Optional[dict] = None
        # stream position: events consumed so far. Checkpoints record it so
        # a supervisor can resume a replayable source from the exact event
        # the snapshot covers (the role of Flink's source offsets in a
        # checkpoint barrier; runtime.recovery.JobSupervisor)
        self.events_processed = 0
        # external source position (e.g. Kafka (topic, partition) -> next
        # offset, maintained by kafka_io.polling_events' tracker): if a
        # source sets this, checkpoints carry it and recovery seeks the
        # rebuilt source here instead of counting events
        self.source_position: Optional[dict] = None
        # pipelines deployed on the SPMD collective engine instead of the
        # host plane (trainingConfiguration {"engine": "spmd"})
        self.spmd_bridges: Dict[int, Any] = {}
        # phase_table() reports their fused route's spans from here on
        self._spans_since = tracing.RECORDER.mark()
        # opt-in periodic checkpointing (Job.scala:120, Checkpointing.scala)
        self.checkpoint_manager = None
        if self.config.checkpointing:
            from omldm_tpu.checkpoint import CheckpointManager

            self.checkpoint_manager = CheckpointManager(
                self.config.checkpoint_dir,
                keep=getattr(self.config, "checkpoint_keep", 3),
            )

    def _spawn_spoke(self, worker_id: int) -> Spoke:
        """The ONE spoke recipe — construction at job init and spokes
        added by a live :meth:`rescale` grow share it, so every opt-in
        wiring decision (chaos routing, tenant-addressed record routing,
        quarantine, telemetry callbacks) is derived from the same rule on
        both paths. Tenant routing in particular: the job-level flag is
        armed by the burst injector; an armed overload controller arms
        the route per spoke at deploy time (Spoke._create), which a
        rescaled-in spoke re-runs when the live pipelines re-deploy."""
        send_to_hub = (
            self._chaos_up.send if self._chaos_up is not None
            else self.hub_manager.route
        )
        return Spoke(
            worker_id=worker_id,
            config=self.config,
            send_to_hub=send_to_hub,
            emit_prediction=self._emit_prediction,
            emit_response=self._route_response_fragment,
            on_poll=self.stats.mark_activity,
            note_wire=self._note_wire,
            emit_predictions=self._emit_predictions,
            quarantine=self.dead_letter.quarantine,
            tenant_routing=self._burst is not None,
            telemetry=self.telemetry,
            events=(
                self.events.journal if self.events is not None else None
            ),
        )

    # --- sinks ---

    def set_sinks(
        self,
        on_prediction: Optional[Callable[[Prediction], None]] = None,
        on_response: Optional[Callable[[QueryResponse], None]] = None,
        on_performance: Optional[Callable[[JobStatistics], None]] = None,
    ) -> None:
        """Override output sinks after construction; only the callbacks
        passed (non-None) are replaced."""
        if on_prediction is not None:
            self._on_prediction = on_prediction
        if on_response is not None:
            self._on_response = on_response
        if on_performance is not None:
            self._on_performance = on_performance

    def _trim_emission(self, buf: list, counter: str) -> None:
        """Bound the in-memory prediction/response mirrors. With a sink
        callback attached the lists are only mirrors (every entry already
        reached the sink), so beyond ``emission_buffer_cap`` the OLDEST
        entries drop — a stalled/slow sink consumer can no longer grow
        host memory with the stream. Without a sink the list IS the
        job's output and stays unbounded."""
        cap = getattr(self.config, "emission_buffer_cap", 0)
        if cap > 0 and len(buf) > cap:
            drop = len(buf) - cap
            del buf[:drop]
            setattr(self, counter, getattr(self, counter) + drop)

    def _emit_prediction(self, pred: Prediction) -> None:
        self.predictions.append(pred)
        if self._on_prediction:
            self._on_prediction(pred)
            self._trim_emission(self.predictions, "predictions_trimmed")

    def _emit_predictions(self, preds: List[Prediction]) -> None:
        """Bulk twin of :meth:`_emit_prediction` for the serving plane's
        flush emission — one extend per flush instead of one call per
        prediction; sink callbacks still fire per prediction, in order."""
        self.predictions.extend(preds)
        if self._on_prediction:
            for pred in preds:
                self._on_prediction(pred)
            self._trim_emission(self.predictions, "predictions_trimmed")

    def _emit_response(self, resp: QueryResponse) -> None:
        self.responses.append(resp)
        if self._on_response:
            self._on_response(resp)
            self._trim_emission(self.responses, "responses_trimmed")

    def _emit_performance(self, report: JobStatistics) -> None:
        self.performance.append(report)
        if self._on_performance:
            self._on_performance(report)

    def _route_response_fragment(self, frag: QueryResponse) -> None:
        """responseId -1 fragments are termination stats, everything else is
        a user query fragment (FlinkLearning.scala:115-133)."""
        if frag.response_id == TERMINATION_RESPONSE_ID:
            self.stats.add_terminate_fragment(frag)
        else:
            self.response_merger.add_fragment(frag)

    def _ship_to_spoke(
        self,
        network_id: int,
        hub_id: int,
        worker_id: int,
        op: str,
        payload: Any,
        seq=None,
    ) -> None:
        """Hub->spoke ship boundary: through the chaos channel when armed,
        straight to delivery otherwise."""
        if self._chaos_down is not None:
            self._chaos_down.send(
                network_id, hub_id, worker_id, op, payload, seq
            )
        else:
            self._reply_to_spoke(network_id, hub_id, worker_id, op, payload, seq)

    def _reply_to_spoke(
        self,
        network_id: int,
        hub_id: int,
        worker_id: int,
        op: str,
        payload: Any,
        seq=None,
    ) -> None:
        if worker_id >= len(self.spokes):
            return  # addressed to a worker retired by a live rescale
        self.spokes[worker_id].receive_from_hub(
            network_id, hub_id, op, payload, seq
        )

    def _note_wire(
        self, network_id: int, hub_id: int, counter: str, n
    ) -> None:
        """Spoke-side events (reliable-channel repairs, program launches,
        serving telemetry) fold into the pipeline's hub statistics so one
        report carries both sides. Counters are additive ints except
        ``serve_latency_ms``, whose payload is the (p50, p99, p999)
        percentile triple the Statistics plane max-combines."""
        hub = self.hub_manager.hubs.get((network_id, hub_id))
        if hub is None:
            return
        if counter == "serve_latency_ms":
            hub.node.stats.note_serve_latency(*n)
        elif counter == "shed_latency_ms":
            hub.node.stats.note_shed_latency(n)
        elif counter == "codec_seconds":
            hub.node.stats.update_stats(
                codec_encode_seconds=n[0], codec_decode_seconds=n[1]
            )
        elif counter == "launch_ms":
            hub.node.stats.note_launch_ms(*n)
        elif counter == "serve_launch_ms":
            hub.node.stats.note_serve_launch_ms(*n)
        else:
            hub.node.stats.update_stats(**{counter: n})

    # --- telemetry plane (runtime/telemetry.py) --------------------------

    def _arm_telemetry(self, cfg) -> None:
        """Create the job's TelemetryPlane (idempotent) and hand every
        spoke the reference — called from __init__ for the job-wide spec,
        or lazily from _deploy for the first pipeline-armed table."""
        if self.telemetry is not None:
            return
        from omldm_tpu.runtime.telemetry import TelemetryPlane

        plane = TelemetryPlane(cfg)
        # standing probes: existing accounting publishes into the
        # registry WITHOUT double bookkeeping on its hot paths — the
        # registry reads these at snapshot time. serve_launch_p99_ms is
        # also the overload ladder's latency signal once telemetry is
        # armed (runtime/overload.OverloadController.signals).
        plane.registry.probe(
            "serve_launch_p99_ms",
            lambda: max(
                (s.serve_timer.recent_p99() for s in self.spokes),
                default=0.0,
            ),
        )
        plane.registry.probe(
            "flush_launch_p99_ms",
            lambda: max(
                (s.step_timer.recent_p99() for s in self.spokes),
                default=0.0,
            ),
        )
        plane.registry.probe("pressure_level", self.overload_level)
        plane.registry.probe(
            "queued_rows", lambda: float(sum(
                v for k, v in self.queue_depths().items()
                if k not in ("pressure_level",)
            ))
        )
        self.telemetry = plane
        for spoke in self.spokes:
            spoke.attach_telemetry(plane)

    # --- flight recorder (runtime/events.py) -----------------------------

    def _arm_events(self, cfg) -> None:
        """Create the job's FlightRecorder (idempotent) and hand every
        spoke + hub shard the journal — called from __init__ for the
        job-wide spec, or lazily from _deploy for the first pipeline-armed
        table."""
        if self.events is not None:
            return
        from omldm_tpu.runtime.events import FlightRecorder

        rec = FlightRecorder(
            cfg,
            pid=0,
            position=lambda: self.events_processed,
            on_alert=self._emit_alert_record,
            blackbox_default=getattr(self.config, "blackbox_path", ""),
        )
        self.events = rec
        for spoke in self.spokes:
            spoke.attach_events(rec.journal)
        # hub shards created before lazy arming, plus (via the manager's
        # reference) every shard created after it — honoring the same
        # per-pipeline opt-out rule create_hub applies
        from omldm_tpu.runtime.events import events_armed_for

        self.hub_manager.events = rec.journal
        for (nid, _h), hub in self.hub_manager.hubs.items():
            req = self.pipeline_manager.node_map.get(nid)
            if req is not None and events_armed_for(
                req.training_configuration,
                getattr(self.config, "events", ""),
            ):
                hub.node.events = rec.journal
        # dead-letter entries cross-reference the event ring: each
        # quarantine carries the current high-water event id, so a
        # quarantined record points at the bundle that explains it
        self.dead_letter.event_ring = rec.journal

    def _emit_alert_record(self, event: dict) -> None:
        """One watchdog alert onto the performance sink as a
        ``kind="alert"`` record — the live-warning twin of the telemetry
        heartbeat (statistics stay empty: an alert is a pointer into the
        journal, not a stats fold)."""
        start = self.stats.job_start
        now = time.time()
        self._emit_performance(JobStatistics(
            job_name=self.config.job_name,
            parallelism=self.config.parallelism,
            duration_ms=(
                (now - start) * 1000.0 if start is not None else 0.0
            ),
            statistics=[],
            kind="alert",
            seq=event["id"],
            extra={"alert": event},
        ))

    def _watchdog_signals(self) -> dict:
        """The signals dict one watchdog pass evaluates — read from the
        PR 13 metrics registry's probes when telemetry is armed, from the
        same underlying accessors otherwise (peeks, never folds)."""
        rec = self.events
        tel = self.telemetry
        if tel is not None:
            p99 = tel.registry.read_probe("serve_launch_p99_ms")
        else:
            p99 = max(
                (s.serve_timer.recent_p99() for s in self.spokes),
                default=0.0,
            )
        shed = 0
        for spoke in self.spokes:
            ctl = spoke.overload
            if ctl is not None:
                shed += ctl.total_shed + ctl.total_throttled
        loss_points = []
        for hub in self.hub_manager.hubs.values():
            curve = hub.node.stats.learning_curve
            if curve:
                loss_points.append(curve[-1])
        shed += sum(
            h.node.stats.deltas_rejected
            for h in self.hub_manager.hubs.values()
        )
        return {
            "records": rec.records_seen,
            "serve_p99_ms": p99,
            "shed": shed,
            "loss": (
                sum(loss_points) / len(loss_points) if loss_points else None
            ),
            "last_activity": self.stats.last_activity,
        }

    def _watchdog_eval(self, now: Optional[float] = None) -> None:
        rec = self.events
        if rec is None or rec.watchdog is None:
            return
        rec.watchdog.evaluate(self._watchdog_signals(), now)

    def codec_seconds(self) -> Tuple[float, float]:
        """(encode, decode) transport-codec seconds summed across every
        live hub and spoke node — the 'ship' phase of the breakdown
        table, and the live twin of the Statistics codec fields."""
        enc = dec = 0.0
        for hub in self.hub_manager.hubs.values():
            c = getattr(hub.node, "codec", None)
            if c is not None:
                enc += c.encode_seconds
                dec += c.decode_seconds
        for spoke in self.spokes:
            for net in spoke.nets.values():
                c = getattr(net.node, "codec", None)
                if c is not None:
                    enc += c.encode_seconds
                    dec += c.decode_seconds
        return enc, dec

    def phase_table(self, e2e_s: Optional[float] = None) -> dict:
        """Phase-attributed hot-loop breakdown: the telemetry plane's
        measured read/parse/stage/holdout rings plus the phases already
        clocked elsewhere — fit (spoke flush StepTimers), serve (serving
        StepTimers) and ship (transport-codec seconds). With ``e2e_s``,
        each row carries its share of the measured end-to-end wall and
        ``_coverage`` is the attributed fraction. A job with ``engine:
        spmd`` pipelines also reports the spans their fused route has noted
        since the job was built (``utils.tracing.RECORDER`` is always on,
        so that part needs no armed telemetry plane; it is process-wide,
        so jobs running side by side in one process see each other's)."""
        from omldm_tpu.runtime.telemetry import PhaseProfile

        tel = self.telemetry
        profile = tel.phases if tel is not None else None
        if profile is None:
            profile = PhaseProfile()
        fused_route = (
            PhaseProfile(tracing.RECORDER, since=self._spans_since)
            if self.spmd_bridges else None
        )
        enc, dec = self.codec_seconds()
        extra = {
            "fit": sum(s.step_timer.total_ms for s in self.spokes) / 1e3,
            "serve": sum(s.serve_timer.total_ms for s in self.spokes) / 1e3,
            "ship": enc + dec,
        }
        return profile.table(
            e2e_s, extra={k: v for k, v in extra.items() if v > 0.0},
            also=fused_route,
        )

    def heartbeat_statistics(self) -> list:
        """READ-ONLY per-pipeline Statistics snapshots for a heartbeat:
        deep copies of the merged hub stats plus the spoke-side tallies
        that normally fold at query/terminate (launch counts, serving
        telemetry, overload counters) — peeked, never taken, so the
        terminate-time fold still sees every delta exactly once. Scores
        are NOT evaluated (that would dispatch holdout programs into the
        hot loop); the final report carries them. SPMD-engine pipelines
        report at terminate only (their statistics walk is collective)."""
        out = []
        for net_id in self.pipeline_manager.live_pipelines:
            if net_id in self.spmd_bridges:
                continue
            merged = self.hub_manager.network_statistics(net_id)
            s = (
                copy.deepcopy(merged) if merged is not None
                else None
            )
            if s is None:
                from omldm_tpu.api.stats import Statistics

                s = Statistics(pipeline=net_id)
            fitted = 0
            for spoke in self.spokes:
                net = spoke.nets.get(net_id)
                if net is None:
                    continue
                s.update_stats(
                    program_launches=net.program_launches,
                    forecasts_served=net.serve_stats.count,
                )
                if net.serve_stats.count:
                    s.note_serve_latency(*net.serve_stats.percentiles())
                # the HOST-side fitted counter only: query_stats() would
                # also read cumulative_loss, which forces a cohort state
                # checkout (launching staged gang fits EARLY) and breaks
                # the armed-vs-unarmed bit-identity contract
                fitted += int(net.pipeline.fitted)
                ctl = spoke.overload
                if ctl is not None:
                    s.update_stats(
                        forecasts_shed=ctl._shed.get(net_id, 0),
                        records_throttled=ctl._throttled.get(net_id, 0),
                        pressure_level=ctl.level_peak,
                    )
                if net.lifecycle is not None:
                    s.update_stats(
                        active_version=net.lifecycle.active_version
                    )
                c = getattr(net.node, "codec", None)
                if c is not None:
                    # live totals minus what already folded hub-side
                    s.update_stats(
                        codec_encode_seconds=(
                            c.encode_seconds - net._codec_folded[0]
                        ),
                        codec_decode_seconds=(
                            c.decode_seconds - net._codec_folded[1]
                        ),
                    )
            for (nid, _h), hub in self.hub_manager.hubs.items():
                if nid != net_id:
                    continue
                c = getattr(hub.node, "codec", None)
                if c is not None:
                    # hub shards fold only at terminate, so mid-stream
                    # the live totals are the un-folded delta
                    s.update_stats(
                        codec_encode_seconds=c.encode_seconds,
                        codec_decode_seconds=c.decode_seconds,
                    )
            if s.fitted == 0:
                s.fitted = fitted
            nq = self.dead_letter.record_count
            if nq:
                s.update_stats(records_quarantined=nq)
            if self.rescales_performed:
                s.update_stats(rescales_performed=self.rescales_performed)
            if self.events is not None and self.events.journal.total:
                s.update_stats(
                    events_recorded=self.events.journal.total,
                    alerts_raised=self.events.journal.alerts,
                )
            nw = self._blackbox_write_errors()
            if nw:
                s.update_stats(blackbox_write_errors=nw)
            out.append(s)
        return out

    def _blackbox_write_errors(self) -> int:
        """Telemetry/quarantine writes the disk refused (black-box ring
        dumps + dead-letter file appends): survived as a dropped-write
        counter, mirrored job-wide like events_recorded (max-combine, so
        the heartbeat peek + terminate fold cannot double-count)."""
        n = self.dead_letter.write_errors
        if self.events is not None:
            n += self.events.journal.write_errors
        return n

    def _emit_heartbeat(self, now: Optional[float] = None) -> None:
        """One incremental JobStatistics snapshot through the existing
        on_performance sink (the Kafka ``performance`` topic) — the
        continuous form of the terminate-time report. ``kind`` marks it a
        heartbeat so consumers (and JobTerminator semantics) can tell it
        from the final report; the extras carry the registry snapshot,
        queue depths and the phase table."""
        tel = self.telemetry
        seq = tel.mark_beat(now)
        start = self.stats.job_start
        now = time.time() if now is None else now
        report = JobStatistics(
            job_name=self.config.job_name,
            parallelism=self.config.parallelism,
            duration_ms=(
                (now - start) * 1000.0 if start is not None else 0.0
            ),
            statistics=self.heartbeat_statistics(),
            kind="heartbeat",
            seq=seq,
            extra={
                "eventsProcessed": self.events_processed,
                "telemetry": tel.registry.snapshot(),
                "queues": self.queue_depths(),
                "phases": self.phase_table(),
            },
        )
        self._emit_performance(report)

    def heartbeat_frame(self) -> dict:
        """The compact metrics frame a worker heartbeat file carries to
        the autoscaling supervisor (runtime/supervisor._beat_frame):
        pressure level plus the host-plane signals the staging backlog
        alone cannot see — serving launch p99, the hottest tenant's
        fair-share imbalance excess, and the queued-row backlog."""
        p99 = max(
            (s.serve_timer.recent_p99() for s in self.spokes), default=0.0
        )
        imbalance = 0.0
        backlog = 0
        for spoke in self.spokes:
            if spoke.overload is not None:
                imbalance = max(imbalance, spoke.overload._hot)
            depths = spoke.queue_depths()
            backlog += depths["serving"] + depths["batcher"] + depths[
                "throttled"
            ]
        journal = self.events.journal if self.events is not None else None
        return {
            "level": self.overload_level(),
            "serveP99": round(p99, 3),
            "imbalance": round(imbalance, 3),
            "backlog": int(backlog),
            # flight-recorder high-water id + alert count: the supervisor
            # can see a worker's journal advance (and alerts fire) without
            # reading its black box (runtime/events.py)
            "events": journal.high_water if journal is not None else 0,
            "alerts": journal.alerts if journal is not None else 0,
        }

    # --- event handling ---

    def process_event(self, stream: str, payload: Any) -> None:
        if self.stats.terminated:
            return
        gang = self.hub_manager.gang
        if gang is None or not self._any_cohorts():
            # no live cohorts: rounds average inline, the pre-cohort timing
            self._process_event_inner(stream, payload)
        else:
            # cohort gang-averaging window: PS rounds completed while this
            # event processes stage their contribution matrices and average
            # together (one stacked reduction per cohort) at window exit
            with gang.window():
                self._process_event_inner(stream, payload)
        # heartbeat count clock: one tick per event (packed blocks tick
        # row counts inside process_packed_batch); emission happens at
        # the event boundary, after the event's own work settled
        tel = self.telemetry
        if (
            tel is not None
            and stream != PACKED_STREAM
            and tel.note_records(1)
        ):
            self._emit_heartbeat()
        # watchdog count clock: same shape as the heartbeat clock (packed
        # blocks tick row counts inside process_packed_batch)
        rec = self.events
        if (
            rec is not None
            and stream != PACKED_STREAM
            and rec.note_records(1)
        ):
            self._watchdog_eval()

    def _any_cohorts(self) -> bool:
        return any(
            s.cohorts is not None and s.cohorts.cohorts for s in self.spokes
        )

    def _process_event_inner(self, stream: str, payload: Any) -> None:
        self.events_processed += 1
        if stream == REQUEST_STREAM:
            if isinstance(payload, Request):
                request = payload
            else:
                request = Request.from_json(payload)
                if request is None:
                    self.dead_letter.quarantine(
                        stream, payload, "malformed_request"
                    )
            if request is not None:
                self._handle_request(request)
        elif stream in (TRAINING_STREAM, FORECASTING_STREAM):
            if isinstance(payload, DataInstance):
                inst = payload
            else:
                tel = self.telemetry
                if tel is not None and tel.phases is not None:
                    with tel.phases.phase("parse"):
                        inst, reason = DataInstance.parse(payload)
                else:
                    inst, reason = DataInstance.parse(payload)
                if reason is not None:
                    # EOS markers / blank lines return (None, None) and
                    # pass through silently — they are protocol, not poison
                    self.dead_letter.quarantine(stream, payload, reason)
            if inst is not None:
                if stream == FORECASTING_STREAM:
                    inst.operation = FORECASTING
                self._handle_data(inst)
                if self._burst is not None:
                    # seeded burst amplification: extra tenant-addressed
                    # copies of this forecast flood the hot tenant — the
                    # overload plane's deterministic overload driver
                    for clone in self._burst.clones(inst):
                        self._handle_data(clone)
        elif stream == PACKED_STREAM:
            self.process_packed_batch(*payload)

    def _handle_request(self, request: Request) -> None:
        self.stats.mark_activity()
        err = self.pipeline_manager.validate(request)
        if err is not None:
            # the reference println-and-drops (PipelineMap.scala:34,46);
            # here the rejection is quarantined with its validation error
            self.dead_letter.quarantine(
                REQUEST_STREAM, request.to_json(), "rejected_request",
                detail=err,
            )
            return
        self.pipeline_manager.apply(request)
        if request.request in (RequestType.CREATE, RequestType.UPDATE):
            dim = self._request_dim(request)
            if dim is None:
                # an Update reuses the live pipeline's dim
                dim = self._dims.get(request.id)
            if dim is None:
                # a record already buffered in a spoke can pin the dim
                dim = self._infer_dim_from_buffers(request)
            if dim is None:
                self._pending_creates.append(request)
                return
            self._deploy(request, dim)
        elif request.request == RequestType.DELETE:
            for spoke in self.spokes:
                spoke.handle_request(request, 0)
            self.hub_manager.delete_network(request.id)
            self.spmd_bridges.pop(request.id, None)
            self._dims.pop(request.id, None)
            # a pipeline deleted before dim inference must not resurrect
            self._pending_creates = [
                r for r in self._pending_creates if r.id != request.id
            ]
        elif request.request in LIFECYCLE_REQUESTS:
            # model-lifecycle verbs (Shadow / Promote / Rollback): the
            # structural validation already passed the gate above; the
            # ARMING check needs the job-wide default spec, so it lives
            # here — an unarmed (or SPMD-deployed) target quarantines the
            # request instead of silently ignoring it
            from omldm_tpu.runtime.lifecycle import lifecycle_config

            if request.id in self.spmd_bridges:
                self.dead_letter.quarantine(
                    REQUEST_STREAM, request.to_json(), "rejected_request",
                    detail="lifecycle verbs are host-plane only",
                )
                return
            if request.id not in self._dims:
                # admitted but not deployed yet (awaiting dim inference):
                # no worker hosts it — same drop rule as an early Query
                return
            live = self.pipeline_manager.node_map.get(request.id)
            armed = live is not None and lifecycle_config(
                live.training_configuration,
                getattr(self.config, "lifecycle", ""),
            ) is not None
            if armed and live.learner is not None and (
                (live.learner.data_structure or {}).get("sparse")
            ):
                # a job-wide lifecycle default does not arm sparse nets
                # (SpokeNet leaves lifecycle None — the candidate
                # predict/flat paths are dense), so a verb aimed at one
                # must quarantine here, not vanish spoke-side
                armed = False
            if not armed:
                self.dead_letter.quarantine(
                    REQUEST_STREAM, request.to_json(), "rejected_request",
                    detail=(
                        f"lifecycle plane not armed for pipeline "
                        f"{request.id}"
                    ),
                )
                return
            for spoke in self.spokes:
                spoke.handle_request(request, self._dims.get(request.id, 0))
        elif request.request == RequestType.QUERY:
            if request.id not in self._dims:
                # pipeline admitted but not deployed yet (awaiting dim
                # inference): no worker hosts it, so no fragments would ever
                # arrive — drop the query instead of leaking an expectation
                return
            rid = request.request_id if request.request_id is not None else 0
            bridge = self.spmd_bridges.get(request.id)
            if bridge is not None:
                # the fleet is one logical model: a single fragment set
                self.response_merger.expect(rid, 1)
                bridge.emit_query_response(rid)
                return
            targets = self.pipeline_manager.query_targets(
                request, self.config.parallelism
            )
            self.response_merger.expect(rid, len(targets))
            for w in targets:
                self.spokes[w].handle_request(request, self._dims.get(request.id, 0))

    def _infer_dim_from_buffers(self, request: Request) -> Optional[int]:
        hash_dims = int(request.training_configuration.extra.get("hashDims", 0))
        head = self._backlog.peek()  # oldest pre-create entry
        if head is not None:
            if head[0] == "inst":
                return Vectorizer.infer_dim(head[1], hash_dims)
            # packed rows already include any hashed-categorical region
            return int(head[1][0].shape[1])
        for spoke in self.spokes:
            for inst in spoke.record_buffer:
                return Vectorizer.infer_dim(inst, hash_dims)
            packed_dim = spoke.buffered_packed_dim()
            if packed_dim is not None:
                return packed_dim
        return None

    def _replay_backlog(self) -> None:
        for entry in self._backlog.drain():
            if entry[0] == "inst":
                self._handle_data(entry[1])
            else:
                self.process_packed_batch(*entry[1])

    def _request_dim(self, request: Request) -> Optional[int]:
        """Feature dim from the request's dataStructure (nFeatures), else None
        => deferred until the first data record arrives (the reference sizes
        models lazily on first record)."""
        ds = request.learner.data_structure if request.learner else None
        if ds and "nFeatures" in ds:
            if ds.get("sparse"):
                # sparse widths are EXACT: hashSpace lives inside nFeatures
                # and the dense hashDims knob does not apply to the COO path
                return int(ds["nFeatures"])
            return int(ds["nFeatures"]) + int(
                request.training_configuration.extra.get("hashDims", 0)
            )
        return None

    def _deploy(self, request: Request, dim: int) -> None:
        """Create the pipeline on every worker and its hub shard(s) —
        the reference broadcasts a ControlMessage per worker
        (PipelineMap.scala:54-57) and spoke 0 creates each of the
        hubParallelism hubs (FlinkSpoke.scala:220-222). A request whose
        trainingConfiguration sets {"engine": "spmd"} (and a supported
        protocol/learner) deploys on the SPMD collective engine instead.
        The ``deploy`` span times the building (the SPMD trainer's
        ``build_state`` is its descendant) and counts the SPMD trainer's
        ``params``; the backlog it releases is replayed after it."""
        with tracing.span("deploy", key=request.id) as span:
            from omldm_tpu.runtime.spmd_bridge import (
                make_spmd_bridge,
                spmd_engine_requested,
                spmd_engine_supported,
            )

            # lazy telemetry arming: the first pipeline whose
            # trainingConfiguration carries a telemetry table creates the
            # job's plane (the gate already validated the spec; job-wide
            # arming happened at __init__)
            if self.telemetry is None:
                from omldm_tpu.runtime.telemetry import telemetry_config

                try:
                    tel_cfg = telemetry_config(
                        request.training_configuration,
                        getattr(self.config, "telemetry", ""),
                    )
                except (ValueError, TypeError):
                    tel_cfg = None  # gate-validated; belt and braces
                if tel_cfg is not None:
                    self._arm_telemetry(tel_cfg)
            # ... and lazy flight-recorder arming, same rule (the gate already
            # validated the table; job-wide arming happened at __init__)
            if self.events is None:
                from omldm_tpu.runtime.events import events_config

                try:
                    ev_cfg = events_config(
                        request.training_configuration,
                        getattr(self.config, "events", ""),
                    )
                except (ValueError, TypeError):
                    ev_cfg = None  # gate-validated; belt and braces
                if ev_cfg is not None:
                    self._arm_events(ev_cfg)
            use_spmd = spmd_engine_requested(request) and spmd_engine_supported(request)
            # an Update must tear down the previous deployment on EITHER plane
            if request.id in self._dims:
                self.hub_manager.delete_network(request.id)
                self.spmd_bridges.pop(request.id, None)
                if use_spmd:
                    # clear stale host-plane nets when switching planes
                    delete = dataclasses.replace(request, request=RequestType.DELETE)
                    for spoke in self.spokes:
                        spoke.handle_request(delete, 0)
            self._dims[request.id] = dim
            if use_spmd:
                bridge = self.spmd_bridges[request.id] = make_spmd_bridge(
                    request, dim, self.config,
                    self._emit_prediction, self._route_response_fragment,
                )
                span.add(params=bridge.trainer.n_params)
            else:
                for spoke in self.spokes:
                    spoke.handle_request(request, dim)
                for h in range(request.training_configuration.hub_parallelism):
                    self.hub_manager.create_hub(request, h, dim)
        self._replay_backlog()

    def rescale(self, n_new: int) -> None:
        """LIVE parallelism change, mid-stream, no restart — the runtime
        analogue of the reference's elastic rescale (spokeParallelism bump +
        wrapper merge + mergingDataBuffers, FlinkSpoke.scala:345-348,
        SpokeLogic.scala:37-50):

        - grow: new spokes spawn, every live host-plane pipeline deploys on
          them (fresh replicas sync through their protocol's next round);
        - shrink: retiring spokes merge into survivor ``id % n_new`` —
          model replicas via the learner merge hook, pending batcher rows
          re-fed, holdout sets interleaved, pre-creation buffers carried;
        - every surviving node and PS shard learns the new worker count
          (barrier counts, termination countdown, score normalization all
          follow config.parallelism).

        SPMD-engine pipelines keep their device mesh (dp is bound to
        hardware, not to the virtual worker count)."""
        p = len(self.spokes)
        if n_new == p:
            return
        if n_new < 1:
            raise ValueError(f"parallelism must be >= 1, got {n_new}")
        self.rescales_performed += 1
        if self.events is not None:
            # a rescale is an incident-grade decision: record it and dump
            # the ring (the pre-rescale story must survive the transition)
            from omldm_tpu.runtime.events import RESCALE

            self.events.journal.record(
                RESCALE, "live_rescale", from_procs=p, to_procs=n_new
            )
            self.events.journal.incident("rescale")
            # reused worker slots restart their sequence counters at 0:
            # later stamped events belong to a NEW transport epoch so the
            # bundle merge never cross-compares them with pre-rescale seqs
            self.events.journal.bump_epoch()
        if n_new > p:
            for w in range(p, n_new):
                self.spokes.append(self._spawn_spoke(w))
            self.config.parallelism = n_new
            # deploy live host-plane pipelines on the new workers
            for net_id, request in self.pipeline_manager.node_map.items():
                if net_id in self.spmd_bridges:
                    continue
                dim = self._dims.get(net_id)
                if dim is None:
                    continue
                src = self.spokes[0].nets.get(net_id)
                deploy = request
                if src is not None:
                    # pin the RESOLVED protocol: a pipeline created at
                    # parallelism 1 was forced to CentralizedTraining
                    # (FlinkSpoke.scala:213-215); re-resolving the original
                    # request at the new parallelism would hand new workers
                    # a different protocol than the live hub speaks
                    deploy = dataclasses.replace(
                        request,
                        training_configuration=dataclasses.replace(
                            request.training_configuration,
                            protocol=src.protocol,
                        ),
                    )
                for w in range(p, n_new):
                    self.spokes[w].handle_request(deploy, dim)
                    dst = self.spokes[w].nets.get(net_id)
                    if src is None or dst is None:
                        continue
                    # seed the new replica from the fleet's current model:
                    # a fresh-init replica would drag the next averaging
                    # round halfway back toward initialization
                    state = copy.deepcopy(src.pipeline.state)
                    state["fitted"] = dst.pipeline.state["fitted"]
                    state["cum_loss"] = dst.pipeline.state["cum_loss"]
                    dst.pipeline.state = state
                    # drift-monitoring workers re-anchor their baseline at
                    # the seeded model (a stale init-time estimate would
                    # register the seed itself as drift and fire a sync);
                    # transport-codec state (EF residuals, topk bases)
                    # likewise restarts from the replaced model
                    dst.node.on_model_seeded()
                    if dst.node.codec is not None:
                        dst.node.codec.reset_streams()
                    # guard LKG snapshots restart at the seeded model: a
                    # rollback must never land on the stale init params
                    if dst.pipeline.guard is not None:
                        dst.pipeline.guard.reseed(dst.pipeline)
                    # model-lifecycle replication: a live registry with a
                    # candidate (or a promoted active version) replicates
                    # onto the grown spoke through the checkpoint-restore
                    # recipe — otherwise the new spoke would twin-train
                    # nothing and a stream whose training rows happen to
                    # round-robin onto it would stall the canary forever
                    if (
                        src.lifecycle is not None
                        and dst.lifecycle is not None
                        and (
                            src.lifecycle.candidate is not None
                            or src.lifecycle.active_version != 0
                        )
                    ):
                        from omldm_tpu.checkpoint.checkpoint import (
                            _pipeline_snapshot,
                        )

                        fresh_fitted = dst.pipeline.state["fitted"]
                        fresh_loss = dst.pipeline.state["cum_loss"]
                        swapped = dst.lifecycle.restore(
                            dst,
                            src.lifecycle.snapshot(),
                            _pipeline_snapshot(src.pipeline),
                        )
                        # the replica's own statistics start fresh: the
                        # source spoke keeps its un-folded counter deltas
                        # (replicating them would double-count at the
                        # query/terminate fold)
                        for k in dst.lifecycle._pending:
                            dst.lifecycle._pending[k] = 0
                            dst.lifecycle.totals[k] = 0
                        if swapped:
                            # restore installed the PROMOTED-spec pipeline
                            # carrying src's full state: re-apply the
                            # fresh-replica seeding contract to the new
                            # pipeline object (own counters zero, drift
                            # baseline / codec streams / guard ring
                            # re-anchored at the seeded model)
                            state = dst.pipeline.state
                            state["fitted"] = fresh_fitted
                            state["cum_loss"] = fresh_loss
                            dst.node.on_model_seeded()
                            if dst.node.codec is not None:
                                dst.node.codec.reset_streams()
                            if dst.pipeline.guard is not None:
                                dst.pipeline.guard.reseed(dst.pipeline)
        else:
            survivors, retired = self.spokes[:n_new], self.spokes[n_new:]
            self.config.parallelism = n_new
            for r in retired:
                survivors[r.worker_id % n_new].absorb(r)
            self.spokes = survivors
        for spoke in self.spokes:
            spoke.set_parallelism(n_new)
        self.hub_manager.set_parallelism(n_new)

    def _handle_data(self, inst: DataInstance) -> None:
        self.stats.mark_activity()
        # records are the liveness clock: a silent worker that has every
        # survivor blocked on a barrier stops ALL protocol traffic, so the
        # hub-side deadline check must ride the data stream instead. The
        # walk itself is STRIDED inside check_liveness (every N events or
        # on a deadline); unarmed jobs pay one flag read
        self.hub_manager.check_liveness()
        if self._pending_creates:
            pending, self._pending_creates = self._pending_creates, []
            for request in pending:
                hash_dims = int(
                    request.training_configuration.extra.get("hashDims", 0)
                )
                dim = Vectorizer.infer_dim(inst, hash_dims)
                self._deploy(request, dim)
        if not self._dims:
            # nothing deployed yet: hold for replay on the first deploy
            self._backlog.append(("inst", inst))
            return
        spoke = self.spokes[self._rr % len(self.spokes)]
        self._rr += 1
        spoke.handle_data(inst)
        # SPMD-engine pipelines see every record (the bridge spreads them
        # across its mesh worker slots internally)
        for bridge in self.spmd_bridges.values():
            bridge.handle_data(inst)

    def process_packed_batch(
        self, x: "np.ndarray", y: "np.ndarray", op: "np.ndarray"
    ) -> None:
        """Bulk data path: pre-vectorized rows from the C++ ingest parser
        (runtime.fast_ingest.PackedBatcher). Rows are distributed exactly as
        per-record events would be: a strided round-robin share per host
        spoke (continuing the _rr cycle, so packed and per-record events can
        interleave) and every row to every SPMD-engine bridge.

        Callers may invoke this directly (benchmarks, fused ingest), not
        only through ``process_event``, so the cohort gang-averaging window
        opens here too (the window is depth-counted — nesting under a
        process_event window just defers the flush to the outer exit)."""
        gang = self.hub_manager.gang
        if gang is None or not self._any_cohorts():
            self._process_packed_inner(x, y, op)
        else:
            with gang.window():
                self._process_packed_inner(x, y, op)
        # heartbeat count clock: packed blocks tick their ROW count so
        # the cadence is the same pure function of the record sequence
        # whichever ingest route carried the rows
        tel = self.telemetry
        if (
            tel is not None
            and not self.stats.terminated
            and tel.note_records(int(x.shape[0]))
        ):
            self._emit_heartbeat()
        rec = self.events
        if (
            rec is not None
            and not self.stats.terminated
            and rec.note_records(int(x.shape[0]))
        ):
            self._watchdog_eval()

    def _process_packed_inner(
        self, x: "np.ndarray", y: "np.ndarray", op: "np.ndarray"
    ) -> None:
        n = x.shape[0]
        if n == 0 or self.stats.terminated:
            return
        self.stats.mark_activity()
        self.hub_manager.check_liveness()
        if self._pending_creates:
            pending, self._pending_creates = self._pending_creates, []
            for request in pending:
                self._deploy(request, int(x.shape[1]))
        if not self._dims:
            self._backlog.append(("__packed__", (x, y, op), None, None))
            return
        p = len(self.spokes)
        for w in range(p):
            start = (w - self._rr) % p
            if start < n:
                self.spokes[w].handle_packed(
                    x[start::p], y[start::p], op[start::p]
                )
        self._rr += n
        for bridge in self.spmd_bridges.values():
            bridge.handle_batch(x, y, op)

    def launch_timing(self) -> dict:
        """Pooled spoke StepTimer summary — the dispatch-cost
        observability twin of the bytesShipped counters. Top-level keys
        are the FIT flush path's per-launch ms percentiles (p50/p99) +
        launches/sec across every spoke; the ``serve_*`` keys carry the
        SERVING-launch percentiles (immediate per-record predicts,
        serving-plane flush launches, and cohort gang predicts — the
        paths Spoke.serve_timer wraps)."""
        from omldm_tpu.utils.tracing import StepTimer

        pooled = StepTimer("spoke_flush")
        serve = StepTimer("serve_flush")
        for spoke in self.spokes:
            for d in spoke.step_timer._durations_ms:
                pooled.record(d)
            for d in spoke.serve_timer._durations_ms:
                serve.record(d)
        out = pooled.summary()
        ssum = serve.summary()
        # counts report the TRUE totals (StepTimer.cap contract): the
        # spokes' bounded rings only carry the percentile windows
        out["count"] = sum(s.step_timer.count for s in self.spokes)
        out["serve_count"] = sum(s.serve_timer.count for s in self.spokes)
        out["serve_p50_ms"] = ssum["p50_ms"]
        out["serve_p99_ms"] = ssum["p99_ms"]
        return out

    # --- overload control (runtime/overload.py) --------------------------

    def overload_level(self) -> int:
        """The job's pressure level: the MAX over every spoke's overload
        controller (0 = OK when none is armed). The Kafka drive loops
        read this to pause consumption while any spoke is CRITICAL —
        unconsumed offsets stay uncommitted, so paused traffic is
        replayable rather than buffered (Flink's credit-based
        backpressure, moved into the runtime)."""
        level = 0
        for spoke in self.spokes:
            if spoke.overload is not None and spoke.overload.level > level:
                level = spoke.overload.level
        return level

    def overload_idle_tick(self) -> None:
        """Advance every controller's count clock during source idle /
        pause windows: nothing admits while paused, so without idle
        ticks the buckets would never refill and a CRITICAL pause could
        never clear (see OverloadController.idle_tick)."""
        for spoke in self.spokes:
            if spoke.overload is not None:
                spoke.overload.idle_tick()
                # idle capacity also drains deferred rows / sheds settle
                spoke._overload_tick()

    def queue_depths(self) -> dict:
        """Aggregate queue-depth snapshot across every spoke (the uniform
        accessors of runtime/spoke.Spoke.queue_depths) + the job-level
        pre-deploy backlog and the current pressure level — folded into
        tenant_topology() and every protocol_comparison results row."""
        agg: dict = {
            "serving": 0, "batcher": 0, "throttled": 0, "paused": 0,
            "pre_create": 0,
        }
        for spoke in self.spokes:
            for k, v in spoke.queue_depths().items():
                agg[k] += v
        agg["backlog"] = len(self._backlog)
        agg["pressure_level"] = self.overload_level()
        return agg

    def tenant_topology(self) -> dict:
        """Where the co-hosted tenants actually run: the local device
        count, the widest engaged tenant-mesh shard count, and each live
        cohort's per-shard active-member placement — recorded by the
        multi-tenant benchmark sweep so BENCH rounds can attribute
        throughput to mesh width."""
        import jax

        topo = {
            "devices": jax.local_device_count(),
            "cohort_shards": 1,
            "placement": [],
            # live queue depths + pressure level ride the topology report
            # so BENCH rounds see WHERE work is waiting, not just where
            # tenants run
            "queues": self.queue_depths(),
            # model-lifecycle registries (runtime/lifecycle.py): each
            # armed pipeline's active version, canary percentage and
            # per-version shadow scores — the worker-0 replica's view
            # (the canary clocks are per-spoke; worker 0 is the
            # representative, like query routing for single-learner
            # models) so operators can watch a rollout without scraping
            # logs. Empty when the plane is unarmed everywhere.
            "lifecycle": {},
        }
        for spoke in self.spokes:
            for net_id, net in spoke.nets.items():
                if net.lifecycle is not None:
                    topo["lifecycle"].setdefault(
                        net_id, net.lifecycle.describe()
                    )
        for spoke in self.spokes:
            engine = spoke.cohorts
            if engine is None:
                continue
            for cohort in engine.cohorts.values():
                topo["cohort_shards"] = max(
                    topo["cohort_shards"], cohort.n_shards
                )
                topo["placement"].append(cohort.shard_placement())
        return topo

    def ensure_deployed(self, dim: int) -> None:
        """Deploy any Create requests still waiting on a feature width —
        the fused file route knows the width up front (CLI flags / schema)
        instead of from the first data record."""
        if self._pending_creates:
            pending, self._pending_creates = self._pending_creates, []
            for request in pending:
                self._deploy(request, dim)

    def fused_file_bridge(self):
        """The single SPMD bridge qualifying for fused C file ingest, or
        None. Fused ingest bypasses the per-event loop, so it is only taken
        when that loop would have nothing else to do: exactly one deployed
        pipeline, on the SPMD plane, with no host-plane nets and no pending
        work."""
        if self._pending_creates or self._backlog or self.stats.terminated:
            return None
        if len(self.spmd_bridges) != 1:
            return None
        if any(net_id not in self.spmd_bridges for net_id in self._dims):
            return None  # host-plane pipelines also consume the stream
        bridge = next(iter(self.spmd_bridges.values()))
        return bridge if bridge.supports_fused_ingest() else None

    def run_file_fused(self, path: str) -> bool:
        """Consume a JSON-lines training file through the bridge's file
        route (SPMDBridge.ingest_file: C parse, holdout and staging on
        this thread, launches in order on a dispatch thread; an SSP
        pipeline's launches on this thread). Returns False when the job
        does not qualify — callers fall back to the packed event route."""
        bridge = self.fused_file_bridge()
        if bridge is None:
            return False
        bridge.ingest_file(path, on_chunk=self.stats.mark_activity)
        return True

    # --- run loops ---

    def run(
        self,
        events: Iterable[Tuple[str, Any]],
        terminate_on_end: bool = True,
    ) -> Optional[JobStatistics]:
        """Replay an ordered event stream; fires the termination protocol at
        stream end (the deterministic equivalent of the 30 s silence timer)."""
        for stream, payload in events:
            if self.stats.terminated:
                break
            self.process_event(stream, payload)
            if self.checkpoint_manager is not None:
                self.checkpoint_manager.maybe_save(self)
        if terminate_on_end and not self.stats.terminated:
            return self.terminate()
        return self.performance[-1] if self.performance else None

    def check_silence(self, now: Optional[float] = None) -> Optional[JobStatistics]:
        """Live-mode hook: fire the termination probe when the silence
        timeout elapsed (StatisticsOperator.scala:135-142). Also the
        serving plane's idle deadline clock — a queued forecast whose
        maxDelayMs elapses during stream silence must not wait for the
        next record to flush it."""
        for spoke in self.spokes:
            spoke.poll_serving()
        # telemetry idle tick: a stalled/paused stream with activity
        # pending since the last beat still reports (wall-clocked — the
        # count clock cannot advance while nothing flows)
        tel = self.telemetry
        if tel is not None and not self.stats.terminated and tel.idle_due(now):
            self._emit_heartbeat(now)
        # watchdog silence rule: wall-clock poll — the count clock cannot
        # advance while nothing flows, which is when silence matters
        rec = self.events
        if (
            rec is not None
            and rec.watchdog is not None
            and not self.stats.terminated
        ):
            rec.watchdog.poll_silence(self.stats.last_activity, now)
        if self.stats.silence_exceeded(now):
            return self.terminate()
        return None

    def terminate(self) -> Optional[JobStatistics]:
        """The section 3.5 termination protocol: probe every worker, fold hub
        state, count fragments, normalize, emit JobStatistics."""
        if self.stats.terminated:
            return self.performance[-1] if self.performance else None
        # the fault window ends at stream end: chaos channels quiesce
        # (held traffic flushes, later sends pass through — the probe's
        # final pushes must not be eaten) and receive windows hand back
        # whatever a never-filled gap was holding
        for chaos in (self._chaos_up, self._chaos_down):
            if chaos is not None:
                chaos.quiesce()
        if self.hub_manager.gang is not None:
            self.hub_manager.gang.flush()
        for spoke in self.spokes:
            spoke.flush_rx_windows()
        self.hub_manager.flush_windows()
        self.stats.probe_fired = True
        for spoke in self.spokes:
            spoke.handle_terminate_probe()
        # quarantined-record count, mirrored into every pipeline's report
        # (a dropped record would have reached each of them; see the
        # Statistics.records_quarantined field note)
        nq = self.dead_letter.record_count
        nr = self.rescales_performed
        # flight-recorder totals, mirrored the same way (the journal is
        # job-level; Statistics.events_recorded/alerts_raised carry it)
        ne = na = 0
        if self.events is not None:
            from omldm_tpu.runtime.events import TERMINATE

            self.events.journal.record(TERMINATE, "termination_protocol")
            ne = self.events.journal.total
            na = self.events.journal.alerts
        nw = self._blackbox_write_errors()
        for bridge in self.spmd_bridges.values():
            bridge.handle_terminate_probe()
            bridge_stats = bridge.network_statistics()
            if bridge_stats is not None:
                if nq:
                    bridge_stats.update_stats(records_quarantined=nq)
                if nr:
                    bridge_stats.update_stats(rescales_performed=nr)
                if ne:
                    bridge_stats.update_stats(
                        events_recorded=ne, alerts_raised=na
                    )
                if nw:
                    bridge_stats.update_stats(blackbox_write_errors=nw)
            self.stats.add_hub_statistics(bridge.request.id, bridge_stats)
        self.hub_manager.on_terminate()
        for net_id in self.pipeline_manager.live_pipelines:
            merged = self.hub_manager.network_statistics(net_id)
            if merged is not None:
                if nq:
                    merged.update_stats(records_quarantined=nq)
                if nr:
                    # like records_quarantined: a JOB-level count mirrored
                    # into each pipeline's report (rescales touch every
                    # live pipeline's replicas)
                    merged.update_stats(rescales_performed=nr)
                if ne:
                    merged.update_stats(
                        events_recorded=ne, alerts_raised=na
                    )
                if nw:
                    merged.update_stats(blackbox_write_errors=nw)
                merged.normalize(
                    max(
                        len(
                            [
                                k
                                for k in self.hub_manager.hubs
                                if k[0] == net_id
                            ]
                        ),
                        1,
                    )
                )
                self.stats.add_hub_statistics(net_id, merged)
        # terminate-time stranded-row snapshot: after the probe/flush
        # cascade above every queue must be empty — the SLO evaluator's
        # no-stranded-rows gate reads this instead of trusting the drain
        self.terminate_accounting = self.queue_depths()
        report = self.stats.try_finalize(
            len(self.pipeline_manager.live_pipelines)
        )
        # release the dead-letter file handle (supervised restarts open a
        # fresh one per incarnation; a late quarantine reopens on demand)
        self.dead_letter.close()
        # ... and the telemetry span file (the final report above is the
        # terminate-time JobStatistics, bit-identical to the pre-plane
        # schema — heartbeats only ever ADD performance entries)
        if self.telemetry is not None:
            self.telemetry.close()
        # final black-box dump: the terminate-time ring is the incident
        # bundle's last word from this process
        if self.events is not None:
            self.events.journal.dump()
        return report
