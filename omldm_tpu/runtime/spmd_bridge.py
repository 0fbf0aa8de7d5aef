"""SPMDBridge: host one streaming pipeline on the collective SPMD engine.

The streaming runtime's host plane multiplexes pipelines across in-process
spokes (message-passing protocol sync, SURVEY.md §3.3); this bridge is the
second deployment mode: a pipeline whose ``trainingConfiguration`` sets
``{"engine": "spmd"}`` trains on :class:`omldm_tpu.parallel.SPMDTrainer`
instead — every data-parallel worker is a mesh shard and protocol sync is
an XLA collective over ICI, while the pipeline keeps the EXACT streaming
contract of a host-plane pipeline: 8-of-10 holdout sampling, micro-batch
training of evicted/kept records, forecasting predictions, bucketed query
responses, the responseId -1 termination fragments (one per configured
worker so the parallelism x pipelines countdown is preserved,
StatisticsOperator.scala:109), and protocol statistics with
bytesShipped/modelsShipped accounting from the collective call sites.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np

from omldm_tpu.api.data import FORECASTING, DataInstance, Prediction
from omldm_tpu.api.requests import Request
from omldm_tpu.api.responses import TERMINATION_RESPONSE_ID, QueryResponse
from omldm_tpu.api.stats import Statistics
from omldm_tpu.config import JobConfig
from omldm_tpu.parallel.mesh import make_mesh
from omldm_tpu.parallel.spmd import SPMD_PROTOCOLS, SPMDTrainer
from omldm_tpu.runtime.databuffers import ArrayHoldout
from omldm_tpu.runtime.spoke import PREDICT_BATCH
from omldm_tpu.runtime.vectorizer import F32_MAX, Vectorizer
from omldm_tpu.utils import tracing


# flush remainders pad to this sub-batch instead of a full dp*B group
# (a 1-row tail no longer ships half a megabyte of zeros)
TAIL_BATCH = 256


def spmd_engine_requested(request: Request) -> bool:
    return (
        str(request.training_configuration.extra.get("engine", "")).lower()
        == "spmd"
    )


def spmd_engine_supported(request: Request) -> bool:
    """The engine hosts the 6 collective protocols with device learners;
    anything else falls back to the host plane. Sparse (padded-COO)
    pipelines deploy on :class:`SparseSPMDBridge`."""
    protocol = request.training_configuration.protocol
    learner = request.learner.name if request.learner else ""
    return protocol in SPMD_PROTOCOLS and learner not in ("HT",)


def make_spmd_bridge(request: Request, dim, config, emit_prediction,
                     emit_response) -> "SPMDBridge":
    """Bridge factory: padded-COO pipelines get the sparse variant."""
    ds = request.learner.data_structure if request.learner else None
    cls = SparseSPMDBridge if (ds and ds.get("sparse")) else SPMDBridge
    return cls(request, dim, config, emit_prediction, emit_response)


def _line_aligned_chunks(path: str, chunk_bytes: int, start_offset: int = 0):
    """Yield (buf, stop) line-aligned regions of a JSON-lines file from one
    reusable read buffer (readinto + carried partial line; grows when a
    single line exceeds the buffer). Shared by the dense and sparse bulk
    ingest routes so the subtle carry logic exists once. ``start_offset``
    resumes mid-file at a known line-aligned byte position (checkpoint
    cursors record one)."""
    with tracing.span("read"):
        # zero-filled: the first touch of every page of the buffer
        buf = bytearray(chunk_bytes)
        f = open(path, "rb")
    carry = 0
    with f:
        if start_offset:
            f.seek(start_offset)
        while True:
            with tracing.span("read"):
                if carry >= len(buf):  # one line longer than the buffer
                    buf.extend(bytes(len(buf)))
                n = f.readinto(memoryview(buf)[carry:])
                end = carry + n
                cut = buf.rfind(b"\n", 0, end) if n else -1
            if not n:
                break
            if cut < 0:
                carry = end
                continue
            yield buf, cut + 1
            carry = end - (cut + 1)
            if carry:
                buf[:carry] = buf[cut + 1 : end]
        if carry:
            buf[carry : carry + 1] = b"\n"
            yield buf, carry + 1


class _OverlapDispatcher:
    """Bounded producer/consumer scaffolding of the file route: a pool of
    ``depth`` spare stage sets bounds look-ahead memory (the parse thread
    blocks in ``submit`` when the device is behind), a work queue
    dispatches sets strictly in order on one daemon thread, and worker
    exceptions surface to the parse thread — the set returns to the pool
    even when the launch raises, so the producer can never deadlock in
    ``submit`` instead of seeing the error. With ``depth`` 0 there is no
    thread: ``submit`` launches on the calling thread and hands the same
    set back, so ``quiesce`` has nothing to wait for."""

    def __init__(self, make_set, depth: int, train):
        import queue
        import threading

        # what this dispatcher works for (a file's ``ingest_file`` span):
        # the dispatch thread's spans name it as their parent
        cause = tracing.current()
        with tracing.span("dispatcher_open"):
            self.errors: List[BaseException] = []
            self._train = train
            self._thread = None
            if depth <= 0:
                return
            self.pool: "queue.Queue" = queue.Queue()
            for _ in range(depth):
                self.pool.put(make_set())
            self.work: "queue.Queue" = queue.Queue()

            def worker():
                tracing.adopt(cause)
                while True:
                    item = self.work.get()
                    try:
                        if item is None:
                            return
                        stage_set, n = item
                        if not self.errors:
                            self._train(stage_set, n)
                    except BaseException as exc:  # surfaced to the producer
                        self.errors.append(exc)
                    finally:
                        if item is not None:
                            self.pool.put(item[0])
                        self.work.task_done()

            self._thread = threading.Thread(
                target=worker, daemon=True, name="omldm-dispatch"
            )
            self._thread.start()

    def submit(self, stage_set, n: int):
        """Queue a filled set, return a fresh one from the pool. Raises
        any pending worker error instead of queueing more work onto a
        dead pipeline."""
        if self._thread is None:
            self._train(stage_set, n)
            return stage_set
        if self.errors:
            raise self.errors[0]
        self.work.put((stage_set, n))
        with tracing.span("pool_wait"):
            return self.pool.get()

    def quiesce(self) -> None:
        """Drain the queue (producer-side trainer access needs the worker
        idle); re-raise any worker error."""
        with tracing.span("quiesce"):
            if self._thread is not None:
                self.work.join()
        if self.errors:
            raise self.errors[0]

    def close(self) -> None:
        with tracing.span("dispatcher_close"):
            if self._thread is not None:
                self.work.put(None)
                self._thread.join()

    def raise_pending(self) -> None:
        if self.errors:
            raise self.errors[0]


class SPMDBridge:
    """One pipeline, streaming in, trained across the device mesh."""

    def __init__(
        self,
        request: Request,
        dim: int,
        config: JobConfig,
        emit_prediction: Callable[[Prediction], None],
        emit_response: Callable[[QueryResponse], None],
    ):
        self.request = request
        self.config = config
        self._emit_prediction = emit_prediction
        self._emit_response = emit_response
        tc = request.training_configuration
        n_dev = len(jax.devices())
        hub = max(int(tc.hub_parallelism), 1)
        if hub > n_dev:
            hub = 1
        # as many mesh workers as devices allow, capped by the job's
        # configured parallelism (the virtual worker count for statistics)
        dp = max(min(config.parallelism, n_dev // hub), 1)
        self.trainer = SPMDTrainer(
            request.learner,
            request.preprocessors or (),
            dim=dim,
            protocol=tc.protocol,
            mesh=make_mesh(dp=dp, hub=hub),
            training_configuration=tc,
            batch_size=config.batch_size,
        )
        self.dp = dp
        hash_dims = int(tc.extra.get("hashDims", 0))
        self.vectorizer = Vectorizer(dim, hash_dims)
        self.dim = dim
        self.test_set = ArrayHoldout(config.test_set_size, dim)
        self.holdout_count = 0
        # staged rows fill a [chain * dp * B, D] buffer; a full buffer is
        # one chained step_many launch (amortizes the per-launch dispatch
        # cost)
        self.chain = max(int(tc.extra.get("stageChain", 8)), 1)
        b = config.batch_size
        # optional narrow feed dtype: float16 staging halves host->device
        # bytes. This is LOSSY quantization of the inputs, not a transport
        # trick: features/targets round to fp16 (~3 decimal digits,
        # |x| <= 65504) before the on-device f32 cast. Opt in only for
        # streams whose value range tolerates it.
        feed = str(tc.extra.get("feedDtype", "float32"))
        if feed not in ("float32", "float16"):
            raise ValueError(f"feedDtype must be float32|float16, got {feed!r}")
        self.feed_dtype = np.dtype(feed)
        # SSP paces per-worker progress: every launch must surface its
        # accept flags so refused batches can be requeued — no chaining.
        # Asynchronous CONSUMES every offered batch (allowed = has_data),
        # so it keeps the chained bulk path and never checks flags.
        self._paced = tc.protocol == "SSP"
        if self._paced:
            self.chain = 1
        self._stage_cap = self.chain * dp * b
        self._stage_x = np.zeros((self._stage_cap, dim), self.feed_dtype)
        self._stage_y = np.zeros((self._stage_cap,), self.feed_dtype)
        self._stage_n = 0
        # the C driver over the current stage set (made on first use) and,
        # for the length of a file, the dispatcher its launches go through
        self._fused = None
        self._dispatcher: Optional[_OverlapDispatcher] = None

    # --- data path ---

    def handle_data(self, inst: DataInstance) -> None:
        x = self.vectorizer.vectorize(inst)
        if inst.operation == FORECASTING:
            xb = np.zeros((PREDICT_BATCH, self.dim), np.float32)
            xb[0] = x
            preds = self.trainer.predict(xb)
            self._emit_prediction(
                Prediction(self.request.id, inst, float(preds[0]))
            )
            return
        y = (
            0.0 if inst.target is None
            else min(max(float(inst.target), -F32_MAX), F32_MAX)
        )
        # 20% holdout: counts 8,9 of each 0-9 cycle (FlinkSpoke.scala:94-104)
        # — the single-record case of _train_rows
        self._train_rows(x[None, :], np.asarray([y], np.float32))

    def handle_batch(
        self, x: np.ndarray, y: np.ndarray, op: np.ndarray
    ) -> None:
        """Bulk equivalent of handle_data for pre-vectorized rows (the C++
        ingest path): same holdout cycle and staging order as feeding the
        rows one at a time, but vectorized end to end."""
        n = x.shape[0]
        if n == 0:
            return
        if x.shape[1] != self.dim:
            w = min(x.shape[1], self.dim)
            out = np.zeros((n, self.dim), np.float32)
            out[:, :w] = x[:, :w]
            x = out
        f_idx = np.nonzero(op != 0)[0]
        if f_idx.size:
            # serve each forecast at its stream position (train the rows
            # before it first) so packed ordering matches per-record
            prev = 0
            for f in f_idx:
                f = int(f)
                if f > prev:
                    self._train_rows(x[prev:f], y[prev:f])
                xb = np.zeros((PREDICT_BATCH, self.dim), np.float32)
                xb[0] = x[f]
                preds = self.trainer.predict(xb)
                inst = DataInstance(
                    numerical_features=x[f].tolist(),
                    operation=FORECASTING,
                )
                self._emit_prediction(
                    Prediction(self.request.id, inst, float(preds[0]))
                )
                prev = f + 1
            if prev < n:
                self._train_rows(x[prev:], y[prev:])
            return
        self._train_rows(x, y)

    def _train_rows(self, x: np.ndarray, y: np.ndarray) -> None:
        """Holdout-split a run of training rows, then stage them."""
        n = x.shape[0]
        if n == 0:
            return
        if self.config.test:
            c = (self.holdout_count + np.arange(n)) % 10
            self.holdout_count += n
            test_mask = c >= 8
            keep_idx = np.nonzero(~test_mask)[0]
            t_idx = np.nonzero(test_mask)[0]
            ev_x, ev_y, ev_src = self.test_set.append_many(x[t_idx], y[t_idx])
            if ev_src.size:
                # evicted points re-enter training at the evicting row's slot
                pos = np.concatenate([keep_idx, t_idx[ev_src]])
                order = np.argsort(pos, kind="stable")
                x = np.concatenate([x[keep_idx], ev_x])[order]
                y = np.concatenate([y[keep_idx], ev_y])[order]
            else:
                x = x[keep_idx]
                y = y[keep_idx]
        else:
            self.holdout_count += n
        self._stage_rows(x, y)

    def _stage_rows(self, x: np.ndarray, y: np.ndarray) -> None:
        i = 0
        n = x.shape[0]
        while i < n:
            take = min(self._stage_cap - self._stage_n, n - i)
            self._stage_x[self._stage_n : self._stage_n + take] = x[i : i + take]
            self._stage_y[self._stage_n : self._stage_n + take] = y[i : i + take]
            self._stage_n += take
            i += take
            if self._stage_n >= self._stage_cap:
                self._train_staged()

    # --- the stage set (what differs between the bridges' stages) ---

    def _stage_set(self) -> tuple:
        return (self._stage_x, self._stage_y)

    def _adopt_stage_set(self, stage_set: tuple) -> None:
        self._stage_x, self._stage_y = stage_set
        self._fused = None  # the C driver is bound to the arrays

    def _launch_stage_set(self, stage_set: tuple, n: int) -> None:
        self._train_buffer(*stage_set, n)

    def _spare_stage_set(self) -> tuple:
        return tuple(np.zeros_like(a) for a in self._stage_set())

    def _train_staged(self) -> None:
        """Launch the staged rows: while a file is being ingested through
        its dispatcher (which hands back the set to fill next), else on
        the calling thread."""
        n = self._stage_n
        if n == 0:
            return
        self._stage_n = 0
        if self._dispatcher is not None:
            self._adopt_stage_set(
                self._dispatcher.submit(self._stage_set(), n)
            )
        else:
            self._launch_stage_set(self._stage_set(), n)

    def _train_buffer(
        self, buf_x: np.ndarray, buf_y: np.ndarray, n: int
    ) -> None:
        """Launch ``n`` staged rows from an EXPLICIT buffer pair (the
        file route's dispatcher owns several): a full stage is one chained
        mask-free step_many_dense launch of ``chain`` [dp, B, D] steps (the
        stage buffer is exactly chain*dp*B rows, so every row is valid and
        no mask ships); a partial stage (flush) runs whole [dp, B] groups
        as single steps and the remainder through a small [dp, TAIL_B]
        padded step instead of padding a whole dp*B group for a handful of
        rows."""
        # COPY before handing rows to the device: dispatch is async and
        # jax may alias numpy argument buffers zero-copy (observed on the
        # CPU backend — reusing the stage buffer mid-read corrupted rows
        # nondeterministically), and under SSP refused batches re-enter
        # the reused stage anyway. The memcpy is small next to the parse.
        b = self.config.batch_size
        group = self.dp * b
        if n == self._stage_cap and not self._paced:
            with tracing.span("copy_stage"):
                xs = np.array(buf_x, copy=True).reshape(
                    self.chain, self.dp, b, self.dim
                )
                ys = np.array(buf_y, copy=True).reshape(
                    self.chain, self.dp, b
                )
            with self._fit_span(n, n, tail=False, tokens=n * self.dim):
                self.trainer.step_many_dense(xs, ys)
            return
        stage_x = buf_x[:n].copy()
        stage_y = buf_y[:n].copy()
        done = 0
        while n - done >= group:
            xg = stage_x[done : done + group].reshape(self.dp, b, self.dim)
            yg = stage_y[done : done + group].reshape(self.dp, b)
            with self._fit_span(
                group, group, tail=False, tokens=group * self.dim
            ):
                self.trainer.step(
                    xg.astype(np.float32, copy=False),
                    yg.astype(np.float32, copy=False),
                    np.ones((self.dp, b), np.float32),
                    valid_count=group,
                )
            self._requeue_refused(xg, yg, None)
            done += group
        tail_b = min(b, TAIL_BATCH)
        tail_group = self.dp * tail_b
        while n - done > 0:
            rem = min(n - done, tail_group)
            x = np.zeros((tail_group, self.dim), np.float32)
            y = np.zeros((tail_group,), np.float32)
            mask = np.zeros((tail_group,), np.float32)
            x[:rem] = stage_x[done : done + rem]
            y[:rem] = stage_y[done : done + rem]
            mask[:rem] = 1.0
            # stripe rows across workers (row i -> slot i % dp); under SSP
            # pacing, slots map SLOWEST-CLOCK-FIRST onto workers — the
            # slowest worker always satisfies the bound, so every tail pass
            # is guaranteed progress and short tails feed the laggards that
            # gate min_clock instead of starving them
            xg = np.ascontiguousarray(
                x.reshape(tail_b, self.dp, self.dim).transpose(1, 0, 2)
            )
            yg = np.ascontiguousarray(y.reshape(tail_b, self.dp).T)
            mg = np.ascontiguousarray(mask.reshape(tail_b, self.dp).T)
            if self._paced:
                order = np.argsort(self.trainer.worker_clocks(), kind="stable")
                inv = np.empty_like(order)
                inv[order] = np.arange(self.dp)
                xg, yg, mg = xg[inv], yg[inv], mg[inv]
            with self._fit_span(
                rem, tail_group, tail=True, tokens=rem * self.dim
            ):
                self.trainer.step(xg, yg, mg, valid_count=rem)
            self._requeue_refused(xg, yg, mg)
            done += rem

    def _fit_span(self, rows: int, rows_padded: int, tail: bool, **counts):
        """The ``fit`` span of one program dispatch, keyed by the trainer's
        step ordinal before it (the k-th ``fit`` is the k-th execution of
        the step program on the device; a chained launch is one program of
        ``chain`` steps). ``rows_padded`` is the batch the program runs
        over, padding included; the dense route adds ``tokens``, the
        feature values of the rows it fits (a language model's tokens)."""
        span = tracing.span(
            "fit", key=self.trainer._steps_host, rows=rows,
            rows_padded=rows_padded, **counts,
        )
        span.set(tail=tail)
        return span

    def _requeue_refused(self, xg, yg, mg) -> None:
        """SSP pacing: re-stage the rows of workers whose batch the device
        refused (staleness bound) and correct the fitted counter."""
        if not self._paced:
            return
        acc = self.trainer.last_accepted()
        if acc.all():
            return
        for w in np.nonzero(~acc)[0]:
            rows = (
                np.ones(yg.shape[1], bool) if mg is None else mg[w] > 0.0
            )
            k = int(rows.sum())
            if k == 0:
                continue
            self.trainer.note_requeued(k)
            self._stage_rows(
                np.asarray(xg[w][rows], np.float32),
                np.asarray(yg[w][rows], np.float32),
            )

    def flush(self) -> None:
        """Drain the stage. Under SSP pacing, refused rows re-enter the
        stage; repeated passes are guaranteed progress (tail slots map
        slowest-first, and the slowest worker always satisfies the bound),
        so the drain terminates — the quiesce analogue of the host plane's
        SSPParameterServer.on_terminate release."""
        self._train_staged()
        while self._paced and self._stage_n:
            before = self._stage_n
            self._train_staged()
            if self._stage_n >= before:
                raise RuntimeError(
                    "SSP flush made no progress draining refused rows"
                )

    # --- checkpoint buffer snapshot (polymorphic: sparse overrides) ---

    def snapshot_buffers(self) -> dict:
        """Holdout + staged rows for a job checkpoint."""
        test_x, test_y = self.test_set.arrays()
        return {
            "test_x": test_x.copy(),
            "test_y": test_y.copy(),
            "stage_x": np.asarray(
                self._stage_x[: self._stage_n], np.float32
            ).copy(),
            "stage_y": np.asarray(
                self._stage_y[: self._stage_n], np.float32
            ).copy(),
        }

    def restore_buffers(self, bd: dict) -> None:
        if bd["test_x"].shape[0]:
            self.test_set.append_many(bd["test_x"], bd["test_y"])
        if bd["stage_x"].shape[0]:
            self._stage_rows(bd["stage_x"], bd["stage_y"])

    # --- the file route (C parse -> holdout -> stage, launches in order) ---

    # bytes of a file read and parsed at a time
    CHUNK_BYTES = 1 << 22

    def supports_fused_ingest(self) -> bool:
        """The fused C loop writes float32 rows straight into the staging
        buffers; fp16 feeds and missing-toolchain hosts use the packed
        numpy route instead."""
        from omldm_tpu.ops.native import fast_parser_available

        return self.feed_dtype == np.float32 and fast_parser_available()

    def supports_overlapped_ingest(self) -> bool:
        """Whether the file route's launches run on a dispatch thread
        beside the parse. SSP's launches re-enter refused rows into the
        stage, so they run on the calling thread."""
        return self.supports_fused_ingest() and not self._paced

    def _fused_stage(self):
        """The C driver over the current stage set and the holdout ring."""
        from omldm_tpu.ops.native import FusedStage

        if self._fused is None:
            hash_dims = int(
                self.request.training_configuration.extra.get("hashDims", 0)
            )
            self._fused = FusedStage(
                self._stage_x,
                self._stage_y,
                self.test_set._x,
                self.test_set._y,
                n_features=self.dim - hash_dims,
                test_enabled=bool(self.config.test),
            )
        return self._fused

    @contextlib.contextmanager
    def _c_driver(self):
        """The C driver over the CURRENT stage set (a launch swaps the set,
        and the driver follows it) with the mutable cursors synced in
        (Python code between two C calls, and SSP requeue inside a launch,
        may have moved them) and synced out after the call."""
        fs = self._fused_stage()
        ctx = fs.ctx
        ctx.stage_n = self._stage_n
        ctx.hold_n = self.test_set._n
        ctx.hold_head = self.test_set._head
        ctx.holdout_count = self.holdout_count
        try:
            yield fs
        finally:
            self._stage_n = int(ctx.stage_n)
            self.test_set._n = int(ctx.hold_n)
            self.test_set._head = int(ctx.hold_head)
            self.holdout_count = int(ctx.holdout_count)

    def ingest_file(
        self, path: str, chunk_bytes: Optional[int] = None, on_chunk=None,
        depth: int = 2,
    ) -> None:
        """Stream a JSON-lines file into the trainer: the calling thread
        reads line-aligned chunks and parses, holdout-splits and stages
        them in C (which releases the GIL) while a dispatch thread launches
        every filled stage set, so a file costs max(parse, device) instead
        of their sum. ``depth`` spare stage sets bound the look-ahead (the
        parse blocks when every set is queued or in flight, so memory
        stays fixed); with ``depth`` 0, which is what an SSP pipeline
        always gets, the launches run on the calling thread.

        Sets are launched strictly IN ORDER and the file's last, partial
        set goes through the same queue, so the result is that of feeding
        the lines one by one through :meth:`handle_data` (pinned by
        tests/test_overlap.py, tests/test_fused_ingest.py). Fallback lines
        and forecasts quiesce the queue first, then run on the calling
        thread. The ``ingest_file`` span counts the training rows (fitted
        or held out) the file brought.

        Reference counterpart: the pipelined whole-job hot path
        Job.scala:42-70 -> FlinkSpoke.scala:92-107 (Flink's operator
        chain keeps source/parse and the learner's fit concurrent across
        its task threads; this is the TPU-native two-thread form)."""
        rows = self.holdout_count
        with tracing.span("ingest_file") as span:
            try:
                consume = self._block_consumer()
                disp = self._dispatcher = _OverlapDispatcher(
                    self._spare_stage_set,
                    0 if self._paced else depth,
                    self._launch_stage_set,
                )
                try:
                    for buf, stop in _line_aligned_chunks(
                        path, chunk_bytes or self.CHUNK_BYTES
                    ):
                        # surface a dispatch-thread error at the next chunk
                        # boundary instead of parsing the rest of the file
                        disp.raise_pending()
                        consume(buf, stop)
                        if on_chunk is not None:
                            on_chunk()
                    # the final partial stage drains through the same queue
                    self._train_staged()
                finally:
                    self._dispatcher = None
                    disp.close()
                disp.raise_pending()
            finally:
                span.add(rows=self.holdout_count - rows)

    def _block_consumer(self):
        """What a file's chunks go through: ``consume(buf, stop)`` takes
        the whole lines ``buf[:stop]``."""
        return self._fused_consume

    def _fused_consume(self, buf: bytearray, stop: int) -> None:
        """Drive the C line loop over ``buf[:stop]`` (whole lines), handing
        stage launches / fallback lines / forecasts back to Python. The
        dispatcher is quiesced before any branch that touches the trainer
        from this thread (fallback/forecast), so those never race the
        dispatch thread."""
        off = 0
        while off < stop:
            # the C loop parses a line into its stage slot: one span
            before = self.holdout_count
            with tracing.span("parse_stage") as span:
                with self._c_driver() as fs:
                    rc, consumed, soff, slen = fs.parse_stage(buf, off, stop)
                span.add(rows=self.holdout_count - before)
            base = off
            off += consumed
            if rc == fs.RC_DONE:
                return
            if rc == fs.RC_STAGE_FULL:
                self._train_staged()
                continue
            self._dispatcher.quiesce()
            if rc == fs.RC_FALLBACK:
                line = bytes(buf[base + soff : base + soff + slen]).decode(
                    "utf-8", errors="replace"
                )
                inst = DataInstance.from_json(line)
                if inst is not None:
                    self.handle_data(inst)
            elif rc == fs.RC_FORECAST:
                x, _ = fs.forecast_row()
                xb = np.zeros((PREDICT_BATCH, self.dim), np.float32)
                xb[0] = x
                preds = self.trainer.predict(xb)
                inst = DataInstance(
                    numerical_features=x.tolist(), operation=FORECASTING
                )
                self._emit_prediction(
                    Prediction(self.request.id, inst, float(preds[0]))
                )

    # --- query / termination path ---

    def _evaluate(self) -> Tuple[float, float]:
        if self.test_set.is_empty:
            return 0.0, 0.0
        xs, ys = self.test_set.arrays()
        # pad to the holdout capacity so the jitted eval program compiles
        # once, not once per fill level while the holdout warms up
        cap = self.test_set.max_size
        n = len(ys)
        if n < cap:
            pad = cap - n
            xs = np.concatenate([xs, np.zeros((pad, xs.shape[1]), xs.dtype)])
            ys = np.concatenate([ys, np.zeros((pad,), ys.dtype)])
        mask = np.zeros((cap,), np.float32)
        mask[:n] = 1.0
        return self.trainer.evaluate(xs, ys, mask)

    def emit_query_response(self, response_id: int) -> None:
        """Bucketed QueryResponse (FlinkNetwork.scala:48-149,151-240); the
        fleet model is one logical model, so user queries get a single
        worker's fragment set (the merger expects 1)."""
        self.flush()
        loss, score = self._evaluate()
        flat = self.trainer.global_flat_params()
        chunks: List[Optional[np.ndarray]] = [None]
        if response_id != TERMINATION_RESPONSE_ID:
            bucket = self.config.max_param_bucket_size
            chunks = [
                flat[i : i + bucket]
                for i in range(0, max(flat.size, 1), bucket)
            ] or [None]
        tc = self.request.training_configuration
        learner_desc = {
            "name": self.request.learner.name,
            "hyperParameters": dict(self.request.learner.hyper_parameters or {}),
            "dataStructure": dict(self.request.learner.data_structure or {}),
        }
        n_workers = (
            self.config.parallelism
            if response_id == TERMINATION_RESPONSE_ID
            else 1
        )
        fitted = self.trainer.fitted
        for w in range(n_workers):
            for i, chunk in enumerate(chunks):
                learner = (
                    dict(learner_desc) if i == 0
                    else {"name": learner_desc["name"]}
                )
                if chunk is not None:
                    learner["parameters"] = {"bucketValues": chunk.tolist()}
                self._emit_response(
                    QueryResponse(
                        response_id=response_id,
                        mlp_id=self.request.id,
                        bucket=i,
                        num_buckets=len(chunks),
                        preprocessors=[
                            {"name": p.name, "hyperParameters": dict(p.hyper_parameters or {})}
                            for p in (self.request.preprocessors or [])
                        ] if i == 0 else None,
                        learner=learner,
                        protocol=tc.protocol if i == 0 else None,
                        # fitted counts once across the fleet's fragments
                        data_fitted=fitted if (i == 0 and w == 0) else 0,
                        loss=loss if i == 0 else None,
                        cumulative_loss=None,
                        score=score if i == 0 else None,
                        source_worker=w,
                    )
                )

    def handle_terminate_probe(self) -> None:
        self.emit_query_response(TERMINATION_RESPONSE_ID)

    def network_statistics(self) -> Statistics:
        """Protocol statistics with the collective-call-site accounting
        (bytesShipped parity, FlinkHub.scala:118-127)."""
        curve = self.trainer.curve_slice()
        # the launches' device counters, read here because the curve's
        # losses just waited for the same launches
        tracing.RECORDER.add_counts("fit", **self.trainer.plan_counts())
        _, score = self._evaluate()
        return Statistics(
            pipeline=self.request.id,
            protocol=self.request.training_configuration.protocol,
            models_shipped=self.trainer.sync_count() * self.dp,
            bytes_shipped=self.trainer.bytes_shipped(),
            bytes_on_wire=self.trainer.bytes_on_wire(),
            num_of_blocks=self.trainer.sync_count(),
            fitted=self.trainer.fitted,
            learning_curve=[l for l, _ in curve],
            lcx=[f for _, f in curve],
            mean_buffer_size=float(self._stage_n),
            score=score,
        )


class SparseSPMDBridge(SPMDBridge):
    """Padded-COO pipeline on the collective engine: the model vector stays
    dense and hub-sharded on the mesh, each record ships only its K active
    features ((idx[K], val[K]) — the SparseVector input type of the
    reference's parse path, DataPointParser.scala:4,20-47), and protocol
    sync is the same XLA collective as the dense bridge. Streaming contract
    identical: 8-of-10 holdout, forecasts at stream position, bucketed
    query responses, termination fragments, byte-accounted statistics."""

    # sparse chunks default to 8 MB (vs the dense 4 MB): the MT parse
    # amortizes its newline-index pass and thread handoff over longer
    # line runs — measured ~+8% host throughput on the Criteo stream
    CHUNK_BYTES = 1 << 23

    def __init__(self, request, dim, config, emit_prediction, emit_response):
        super().__init__(request, dim, config, emit_prediction, emit_response)
        from omldm_tpu.runtime.databuffers import SparseHoldout
        from omldm_tpu.runtime.vectorizer import SparseVectorizer

        ds = request.learner.data_structure or {}
        self.max_nnz = int(ds.get("maxNnz", 64))
        hash_space = int(ds.get("hashSpace", 0))
        self.vectorizer = SparseVectorizer(dim, hash_space, self.max_nnz)
        self.test_set = SparseHoldout(config.test_set_size, self.max_nnz)
        # COO staging: one [dp, B] group per launch (no dense chaining)
        self.chain = 1
        self._stage_cap = self.dp * config.batch_size
        self._stage_i = np.zeros((self._stage_cap, self.max_nnz), np.int32)
        self._stage_v = np.zeros((self._stage_cap, self.max_nnz), np.float32)
        self._stage_y = np.zeros((self._stage_cap,), np.float32)
        del self._stage_x  # the dense bridge's
        self._stage_n = 0

    def supports_fused_ingest(self) -> bool:
        """The sparse file route: the C block parser (categorical hashing
        in C) and the C stager."""
        from omldm_tpu.ops.native import fast_parser_available

        return fast_parser_available()

    def _stage_set(self) -> tuple:
        return (self._stage_i, self._stage_v, self._stage_y)

    def _adopt_stage_set(self, stage_set: tuple) -> None:
        self._stage_i, self._stage_v, self._stage_y = stage_set
        self._fused = None  # the C stager is bound to the arrays

    def _launch_stage_set(self, stage_set: tuple, n: int) -> None:
        self._launch_coo(*stage_set, n)

    def _fused_stage(self):
        """The C stager over the current stage set and the holdout ring."""
        from omldm_tpu.ops.native import SparseFusedStage

        if self._fused is None:
            self._fused = SparseFusedStage(
                self._stage_i, self._stage_v, self._stage_y,
                self.test_set._idx, self.test_set._val, self.test_set._y,
                test_enabled=bool(self.config.test),
            )
        return self._fused

    def _make_coo_parser(self):
        from omldm_tpu.ops.native import SparseFastParser

        # parserThreads: 0 = auto (min(cores, 8), FastParser's rule) —
        # multi-core hosts parse disjoint line ranges on C threads.
        # reuse_buffers: the file route consumes every returned array
        # within the chunk (the C stager copies the rows), so the parser
        # may hand out scratch views instead of fresh allocations
        return SparseFastParser(
            self.vectorizer.dim - self.vectorizer.hash_space,
            self.vectorizer.hash_space,
            self.max_nnz,
            n_threads=int(
                self.request.training_configuration.extra.get(
                    "parserThreads", 0
                )
            ),
            reuse_buffers=True,
        )

    def _block_consumer(self):
        return functools.partial(
            self._consume_coo_block, self._make_coo_parser()
        )

    # --- data path ---

    def handle_data(self, inst: DataInstance) -> None:
        if inst.operation == FORECASTING:
            with tracing.span("decode"):
                idx, val = self.vectorizer.vectorize(inst)
            self._emit_forecast(idx, val, inst)
            return
        idx, val = self.vectorizer.vectorize(inst)
        y = (
            0.0 if inst.target is None
            else min(max(float(inst.target), -F32_MAX), F32_MAX)
        )
        self._holdout_then_stage(
            idx[None, :], val[None, :], np.asarray([y], np.float32)
        )

    def _emit_forecast(self, idx, val, inst: DataInstance) -> None:
        bi = np.zeros((PREDICT_BATCH, self.max_nnz), np.int32)
        bv = np.zeros((PREDICT_BATCH, self.max_nnz), np.float32)
        bi[0] = idx
        bv[0] = val
        # the padded batch up, the wait behind every step still queued on
        # the device, the predict program, the answer down
        with tracing.span("serve"):
            preds = self.trainer.predict((bi, bv))
        with tracing.span("emit"):
            self._emit_prediction(
                Prediction(self.request.id, inst, float(preds[0]))
            )

    def handle_batch(self, x, y, op) -> None:
        """Dense packed rows (the C ingest path) re-enter as COO — rare for
        sparse jobs (the CLI routes sparse streams per-record), but a mixed
        feed must behave identically to per-record delivery."""
        from omldm_tpu.runtime.spoke import Spoke

        n = x.shape[0]
        if n == 0:
            return
        f_idx = np.nonzero(op != 0)[0]
        prev = 0
        for f in f_idx:
            f = int(f)
            if f > prev:
                si, sv = Spoke._dense_rows_to_coo(x[prev:f], self.max_nnz)
                self._train_sparse_rows(si, sv, y[prev:f])
            si, sv = Spoke._dense_rows_to_coo(x[f : f + 1], self.max_nnz)
            inst = DataInstance(
                numerical_features=x[f].tolist(), operation=FORECASTING
            )
            self._emit_forecast(si[0], sv[0], inst)
            prev = f + 1
        if prev < n:
            si, sv = Spoke._dense_rows_to_coo(x[prev:], self.max_nnz)
            self._train_sparse_rows(si, sv, y[prev:])

    def _train_sparse_rows(self, idx, val, y) -> None:
        y = np.clip(np.asarray(y, np.float64), -F32_MAX, F32_MAX).astype(
            np.float32
        )
        self._holdout_then_stage(idx, val, y)

    def _holdout_then_stage(self, idx, val, y) -> None:
        """8-of-10 holdout cycle with evicted rows re-entering at the
        evicting row's stream position (exact dense-bridge semantics)."""
        n = idx.shape[0]
        if n == 0:
            return
        if self.config.test:
            c = (self.holdout_count + np.arange(n)) % 10
            self.holdout_count += n
            test_mask = c >= 8
            keep = np.nonzero(~test_mask)[0]
            t_idx = np.nonzero(test_mask)[0]
            ev_i, ev_v, ev_y, ev_src = self.test_set.append_many(
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
            self.holdout_count += n
        self._stage_coo(idx, val, y)

    def _stage_coo(self, idx, val, y) -> None:
        """Fill the COO stage (the sparse twin of _stage_rows); a full
        stage launches one [dp, B] collective step and the fill resumes —
        overflow beyond the stage capacity trains rather than truncating
        (restore under a smaller mesh relies on this)."""
        i = 0
        n = idx.shape[0]
        while i < n:
            take = min(self._stage_cap - self._stage_n, n - i)
            s = self._stage_n
            self._stage_i[s : s + take] = idx[i : i + take]
            self._stage_v[s : s + take] = val[i : i + take]
            self._stage_y[s : s + take] = y[i : i + take]
            self._stage_n += take
            i += take
            if self._stage_n >= self._stage_cap:
                self._train_staged()

    def _launch_coo(self, si, sv, sy, n) -> None:
        """Launch ``n`` staged COO rows (explicit arrays, so the file
        route's dispatch thread can drive it on pooled sets). Rows are
        COPIED before device handoff: dispatch is async and jax may alias
        numpy argument buffers zero-copy (observed on CPU), while the
        stage set is reused as soon as this returns; SSP requeue also
        re-enters these buffers."""
        with tracing.span("launch"):
            with tracing.span("copy_stage"):
                si = si[:n].copy()
                sv = sv[:n].copy()
                sy = sy[:n].copy()
            b = self.config.batch_size
            group = self.dp * b
            done = 0
            while n - done >= group:
                ig = si[done : done + group].reshape(self.dp, b, self.max_nnz)
                vg = sv[done : done + group].reshape(self.dp, b, self.max_nnz)
                yg = sy[done : done + group].reshape(self.dp, b)
                mg = np.ones((self.dp, b), np.float32)
                with self._fit_span(group, group, tail=False):
                    self.trainer.step((ig, vg), yg, mg, valid_count=group)
                self._requeue_refused_sparse(ig, vg, yg, mg)
                done += group
            tail_b = min(b, TAIL_BATCH)
            tail_group = self.dp * tail_b
            while n - done > 0:
                rem = min(n - done, tail_group)
                ti = np.zeros((tail_group, self.max_nnz), np.int32)
                tv = np.zeros((tail_group, self.max_nnz), np.float32)
                ty = np.zeros((tail_group,), np.float32)
                tm = np.zeros((tail_group,), np.float32)
                ti[:rem] = si[done : done + rem]
                tv[:rem] = sv[done : done + rem]
                ty[:rem] = sy[done : done + rem]
                tm[:rem] = 1.0
                # stripe rows across workers; SSP maps slots slowest-first so
                # every tail pass is guaranteed progress (dense-bridge rule)
                ig = np.ascontiguousarray(
                    ti.reshape(tail_b, self.dp, self.max_nnz).transpose(1, 0, 2)
                )
                vg = np.ascontiguousarray(
                    tv.reshape(tail_b, self.dp, self.max_nnz).transpose(1, 0, 2)
                )
                yg = np.ascontiguousarray(ty.reshape(tail_b, self.dp).T)
                mg = np.ascontiguousarray(tm.reshape(tail_b, self.dp).T)
                if self._paced:
                    order = np.argsort(self.trainer.worker_clocks(), kind="stable")
                    inv = np.empty_like(order)
                    inv[order] = np.arange(self.dp)
                    ig, vg, yg, mg = ig[inv], vg[inv], yg[inv], mg[inv]
                with self._fit_span(rem, tail_group, tail=True):
                    self.trainer.step((ig, vg), yg, mg, valid_count=rem)
                self._requeue_refused_sparse(ig, vg, yg, mg)
                done += rem

    def _requeue_refused_sparse(self, ig, vg, yg, mg) -> None:
        if not self._paced:
            return
        acc = self.trainer.last_accepted()
        if acc.all():
            return
        for w in np.nonzero(~acc)[0]:
            rows = mg[w] > 0.0
            k = int(rows.sum())
            if k == 0:
                continue
            self.trainer.note_requeued(k)
            # refused rows re-enter the stage directly (they already went
            # through the holdout cycle)
            self._stage_coo(ig[w][rows], vg[w][rows], yg[w][rows])

    # --- evaluation / checkpoint buffers ---

    def _evaluate(self):
        if self.test_set.is_empty:
            return 0.0, 0.0
        ti, tv, ty = self.test_set.arrays()
        cap = self.test_set.max_size
        n = len(ty)
        if n < cap:
            pad = cap - n
            ti = np.concatenate(
                [ti, np.zeros((pad, self.max_nnz), np.int32)]
            )
            tv = np.concatenate(
                [tv, np.zeros((pad, self.max_nnz), np.float32)]
            )
            ty = np.concatenate([ty, np.zeros((pad,), np.float32)])
        mask = np.zeros((cap,), np.float32)
        mask[:n] = 1.0
        return self.trainer.evaluate((ti, tv), ty, mask)

    def snapshot_buffers(self) -> dict:
        ti, tv, ty = self.test_set.arrays()
        return {
            "sparse": True,
            "test_i": ti.copy(),
            "test_v": tv.copy(),
            "test_yv": ty.copy(),
            "stage_i": self._stage_i[: self._stage_n].copy(),
            "stage_v": self._stage_v[: self._stage_n].copy(),
            "stage_yv": self._stage_y[: self._stage_n].copy(),
            # dense-keyed empties keep old readers from crashing
            "test_x": np.zeros((0, 1), np.float32),
            "test_y": np.zeros((0,), np.float32),
            "stage_x": np.zeros((0, 1), np.float32),
            "stage_y": np.zeros((0,), np.float32),
        }

    def restore_buffers(self, bd: dict) -> None:
        if bd.get("test_i") is not None and bd["test_i"].shape[0]:
            self.test_set.append_many(
                bd["test_i"], bd["test_v"], bd["test_yv"]
            )
        if bd.get("stage_i") is not None and bd["stage_i"].shape[0]:
            # through the stage filler: a snapshot taken on a larger mesh
            # may carry more staged rows than this bridge's capacity, and
            # the overflow must train, not crash or truncate
            self._stage_coo(bd["stage_i"], bd["stage_v"], bd["stage_yv"])

    # --- the file route's blocks: C block parser, then the C stager ---

    def _consume_coo_block(self, parser, buf: bytearray, stop: int) -> None:
        """Block parse of ``buf[:stop]`` on the parser's C threads
        (zero-copy out of the reusable read buffer: zlib-CRC32 categorical
        hashing in C, parity fuzz-pinned by tests/test_sparse_parser.py),
        then holdout + staging of the parsed runs in C. Fallback lines,
        forecasts and drops re-route through the per-record codec at
        their stream position."""
        with tracing.span("parse") as parse_span:
            idx, val, y, op, valid = parser.parse_range(buf, 0, stop)
            n = idx.shape[0]
            parse_span.add(rows=n)
        if n == 0:
            return
        # specials (codec fallbacks, forecasts, drops) break the bulk run
        # so ordering matches per-record delivery exactly
        special = np.nonzero((valid != 1) | (op != 0))[0]
        lines = None
        if special.size:
            # one special line costs a copy and a split of the whole block
            with tracing.span("split_lines"):
                lines = bytes(memoryview(buf)[:stop]).split(b"\n")
        prev = 0
        for s in special:
            s = int(s)
            if s > prev:
                with tracing.span("stage"):
                    self._stage_parsed_rows(idx[prev:s], val[prev:s], y[prev:s])
            # a line the C parser read as a forecast, or one it left to
            # the Python codec (which may still find a forecast in it)
            is_forecast = valid[s] == 1 and op[s] != 0
            with tracing.span("forecast" if is_forecast else "fallback") as sp:
                with tracing.span("decode"):
                    inst = DataInstance.from_json(
                        lines[s].decode("utf-8", errors="replace")
                    )
                if inst is not None:
                    sp.key = inst.id
                    # specials may touch the trainer from this (producer)
                    # thread (forecasts serve a prediction): drain queued
                    # collective steps first — including any enqueued by
                    # the staging right above — so two threads never race
                    # on trainer state
                    self._dispatcher.quiesce()
                    self.handle_data(inst)
            prev = s + 1
        if prev < n:
            with tracing.span("stage"):
                self._stage_parsed_rows(idx[prev:], val[prev:], y[prev:])

    def _stage_parsed_rows(self, idx, val, y) -> None:
        """Holdout + stage a run of C-PARSED COO rows through the C stager
        (omldm_stage_coo_rows): bit-identical to
        :meth:`_holdout_then_stage` + :meth:`_stage_coo` (the per-record
        path's, and the tests' reference) but with the holdout cycle, ring
        swap and stage fill in one C pass instead of mask/argsort/
        concatenate numpy per block. Pauses at stage-full for the launch."""
        n = idx.shape[0]
        i = 0
        while i < n:
            with self._c_driver() as fs:
                i += fs.stage_rows(idx, val, y, i)
            if self._stage_n >= self._stage_cap:
                self._train_staged()
