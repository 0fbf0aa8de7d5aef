"""Job checkpointing with rescale-merge restore.

Reference counterpart: Flink-native checkpointing (opt-in flag Job.scala:120,
FsStateBackend + 5 s interval, Checkpointing.scala:9-25). The spoke snapshots
live node wrappers (model state included), the holdout test set, the record
buffer and the request buffer into operator ListState
(FlinkSpoke.scala:233-251); on restore parallel copies are merged and
overflow re-trained (FlinkSpoke.scala:261-334).

NOTE the reference's restore path is latently broken — the merged
``new_state`` is never assigned back into ``state`` (FlinkSpoke.scala:291-305,
SURVEY.md section 5); this implementation performs the assignment the
reference forgot: merged learner/preprocessor state really lands in the
restored workers.

Rescale semantics (elasticity, FlinkSpoke.scala:345-348): restoring to a
different ``parallelism`` merges every worker replica of a pipeline
(learner-specific ``merge`` — parameter average, sufficient-statistics sum,
count-weighted centroids, biggest-tree), redistributes holdout test sets
round-robin (capacity overflow is queued for re-training, like the
reference's evicted-holdout rule), and redeploys onto the new worker count.

Format: one pickle file per snapshot (host pytrees with numpy leaves; HT
trees pickle as host objects) + a ``latest`` pointer. Checkpoints are
internal state, not an interchange format.
"""

from __future__ import annotations

import copy
import dataclasses
import os
import pickle
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from omldm_tpu.api.requests import Request
from omldm_tpu.config import JobConfig


def _fresh_copy(leaf):
    """Independent buffer per worker (host structures pass through)."""
    if hasattr(leaf, "shape"):
        import jax.numpy as jnp

        return jnp.array(leaf)
    return leaf


def _to_host(tree):
    return jax.tree_util.tree_map(
        lambda l: np.asarray(jax.device_get(l))
        if hasattr(l, "shape") or isinstance(l, (int, float))
        else l,
        tree,
    )


# node attributes that are wiring (callables/config) or restored separately
# (the pipeline), not protocol state. The flight-recorder journal
# ("events", re-wired by the runtime like the other callbacks) and its
# transient receive stamp are wiring too — the journal holds clock
# closures that must never reach pickle.
_NODE_SKIP = frozenset({
    "pipeline", "config", "send", "reply", "broadcast", "events",
    "_rx_stamp",
})


def _node_state(node) -> dict:
    """Snapshot a protocol node's round state (sync barriers, clocks,
    partial rounds, blocked-batch buffers, statistics counters) — the state
    the reference keeps in its wrapper/PS objects inside Flink operator
    state (FlinkSpoke.scala:233-251). Wiring attributes are excluded and
    re-established by the runtime on restore."""
    return {
        k: copy.deepcopy(v)
        for k, v in vars(node).items()
        if k not in _NODE_SKIP and not callable(v)
    }


def _restore_node(node, state: Optional[dict]) -> None:
    if state:
        vars(node).update(copy.deepcopy(state))


def _pipeline_snapshot(pipe) -> dict:
    """The one pipeline-state schema: spoke nets and the SingleLearner hub
    model both save/load through this pair so the field set cannot drift."""
    return {
        "params": _to_host(pipe.state["params"]),
        "preps": [_to_host(s) for s in pipe.state["preps"]],
        "fitted": pipe.fitted,
        "cum_loss": pipe.cumulative_loss,
    }


def _pipeline_load(pipe, sv: dict) -> None:
    pipe.state["params"] = sv["params"]
    pipe.state["preps"] = list(sv["preps"])
    pipe.state["cum_loss"] = jnp.asarray(sv["cum_loss"], jnp.float32)
    pipe._fitted_host = sv["fitted"]


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self._last_save = 0.0
        # seed the sequence past any snapshots already in the directory: a
        # manager built mid-recovery (restore() constructs a fresh
        # StreamJob) must not reuse a live sequence number — a
        # same-millisecond collision would overwrite (or name-sort before)
        # the newest snapshot and let _prune delete what `latest` points at
        self._seq = 0
        for name in os.listdir(directory):
            if name.startswith("ckpt_") and name.endswith(".pkl"):
                parts = name[:-4].split("_")
                if len(parts) == 3 and parts[2].isdigit():
                    self._seq = max(self._seq, int(parts[2]))
        # snapshots retained on disk; <= 0 keeps everything
        self.keep = keep

    # --- save ---

    def save(self, job) -> str:
        """Snapshot a StreamJob; returns the checkpoint path."""
        spokes = []
        for spoke in job.spokes:
            nets: Dict[int, dict] = {}
            for net_id, net in spoke.nets.items():
                pipe = net.pipeline
                nets[net_id] = {
                    **_pipeline_snapshot(pipe),
                    "holdout_count": net.holdout_count,
                    "test_set": net.test_set.to_list(),
                    "pending": self._batcher_contents(net.batcher),
                    "node": _node_state(net.node),
                }
                # the guard's LKG rollback ring survives a restart (a
                # reseed at the restored params could make a corruption
                # that slipped into the snapshot its own rollback target)
                if pipe.guard is not None:
                    nets[net_id]["guard"] = pipe.guard.snapshot()
                # the model-lifecycle registry (versions, candidate
                # pipeline state, canary clocks) — a supervised restart
                # resumes MID-CANARY instead of silently reverting to a
                # single unversioned model
                if net.lifecycle is not None:
                    nets[net_id]["lifecycle"] = net.lifecycle.snapshot()
            spokes.append(nets)
        hub_nodes = {}
        for (net_id, hub_id), hub in job.hub_manager.hubs.items():
            entry: Dict[str, Any] = {"node": _node_state(hub.node)}
            central = getattr(hub.node, "pipeline", None)
            if central is not None:
                # SingleLearner: THE model lives on the hub (FlinkHub.scala:
                # 128-153) — snapshot it like a spoke pipeline
                entry["pipeline"] = _pipeline_snapshot(central)
            hub_nodes[(net_id, hub_id)] = entry
        hub_stats = {}
        for net_id in job.pipeline_manager.live_pipelines:
            merged = job.hub_manager.network_statistics(net_id)
            if merged is not None:
                hub_stats[net_id] = merged.to_dict()
        bridges = {}
        for net_id, bridge in job.spmd_bridges.items():
            t = bridge.trainer
            bridges[net_id] = {
                "mesh": (t.dp, t.hub),
                "fleet": _to_host(t.state),
                "fitted": t.fitted,
                "steps": t._steps_host,
                "holdout_count": bridge.holdout_count,
                # holdout + staged rows come from the bridge so the sparse
                # variant can snapshot its COO buffers
                **bridge.snapshot_buffers(),
            }
        snapshot = {
            "config": dataclasses.asdict(job.config),
            "requests": [
                r.to_dict() for r in job.pipeline_manager.node_map.values()
            ],
            "dims": dict(job._dims),
            "spokes": spokes,
            "hub_stats": hub_stats,
            "hub_nodes": hub_nodes,
            "bridges": bridges,
            # stream position + routing state: a supervisor resumes a
            # replayable source at ``offset`` and the restored job routes
            # subsequent records exactly as the original would have (the
            # role of source offsets in a Flink checkpoint barrier)
            "offset": job.events_processed,
            "source_position": copy.deepcopy(job.source_position),
            "rr": job._rr,
            "rescales": job.rescales_performed,
            "backlog": list(job._backlog._entries),
            "pending_creates": [r.to_dict() for r in job._pending_creates],
            "time": time.time(),
        }
        # ms timestamp + monotonic sequence: unique, name-sortable names
        # even when saves land inside the same millisecond
        self._seq += 1
        path = os.path.join(
            self.directory,
            f"ckpt_{int(time.time()*1000):013d}_{self._seq:06d}.pkl",
        )
        # atomic writes (temp + os.replace): a crash mid-write must never
        # leave a truncated snapshot or an empty 'latest' pointer — the
        # supervised-recovery path reads both
        with open(path + ".tmp", "wb") as f:
            pickle.dump(snapshot, f)
        os.replace(path + ".tmp", path)
        pointer = os.path.join(self.directory, "latest")
        with open(pointer + ".tmp", "w") as f:
            f.write(os.path.basename(path))
        os.replace(pointer + ".tmp", pointer)
        self._last_save = time.time()
        self._prune()
        return path

    def _prune(self) -> None:
        """Retain the newest ``keep`` snapshots (file names sort
        chronologically); <= 0 keeps everything."""
        if self.keep <= 0:
            return
        snaps = sorted(
            f for f in os.listdir(self.directory)
            if f.startswith("ckpt_") and f.endswith(".pkl")
        )
        for stale in snaps[: -self.keep]:
            try:
                os.remove(os.path.join(self.directory, stale))
            except OSError:
                pass

    @staticmethod
    def _batcher_contents(batcher) -> List[tuple]:
        if hasattr(batcher, "_idx"):  # SparseMicroBatcher: padded-COO rows
            return [
                (
                    batcher._idx[i].copy(),
                    batcher._val[i].copy(),
                    float(batcher._y[i]),
                )
                for i in range(len(batcher))
            ]
        return [
            (batcher._x[i].copy(), float(batcher._y[i])) for i in range(len(batcher))
        ]

    @staticmethod
    def _refeed_pending(net, pending) -> None:
        """Re-add snapshotted pending rows to a net's batcher. Shapes:
        (idx, val, y) sparse batcher rows; ((idx, val), y) sparse
        holdout-evicted points; (x, y) dense."""
        for row in pending:
            if len(row) == 3:
                net.batcher.add(
                    np.asarray(row[0], np.int32),
                    np.asarray(row[1], np.float32),
                    float(row[2]),
                )
            elif isinstance(row[0], tuple):
                (idx, val), y = row
                net.batcher.add(
                    np.asarray(idx, np.int32),
                    np.asarray(val, np.float32),
                    float(y),
                )
            else:
                net.batcher.add(np.asarray(row[0], np.float32), float(row[1]))
            if net.batcher.full:
                net.flush_batch()

    def maybe_save(self, job, now: Optional[float] = None) -> Optional[str]:
        """Periodic checkpointing at ``check_interval_ms`` (the reference's
        5 s default, Checkpointing.scala:21)."""
        if not job.config.checkpointing:
            return None
        now = time.time() if now is None else now
        if (now - self._last_save) * 1000.0 >= job.config.check_interval_ms:
            return self.save(job)
        return None

    # --- restore ---

    def candidate_paths(self) -> List[str]:
        """Every snapshot in the directory, NEWEST first (file names sort
        chronologically). The recovery path walks this list when the
        newest generation fails to load — a torn/corrupted pickle falls
        back to the previous surviving generation instead of being the
        only snapshot ever tried (``recover_job``)."""
        try:
            names = sorted(
                (
                    f
                    for f in os.listdir(self.directory)
                    if f.startswith("ckpt_") and f.endswith(".pkl")
                ),
                reverse=True,
            )
        except OSError:
            return []
        return [os.path.join(self.directory, f) for f in names]

    def latest_path(self) -> Optional[str]:
        pointer = os.path.join(self.directory, "latest")
        if not os.path.exists(pointer):
            return None
        with open(pointer) as f:
            name = f.read().strip()
        if not name:  # empty/corrupt pointer = no checkpoint, not a crash
            return None
        path = os.path.join(self.directory, name)
        return path if os.path.exists(path) else None

    def restore(self, parallelism: Optional[int] = None, path: Optional[str] = None):
        """Rebuild a StreamJob from a snapshot; ``parallelism`` overrides the
        saved worker count (rescale-merge)."""
        from omldm_tpu.runtime.job import StreamJob

        path = path or self.latest_path()
        if path is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        with open(path, "rb") as f:
            snapshot = pickle.load(f)

        config = JobConfig(**snapshot["config"])
        if parallelism is not None:
            config.parallelism = parallelism
        job = StreamJob(config)

        # re-admit and redeploy the live pipelines
        for req_dict in snapshot["requests"]:
            request = Request.from_dict(req_dict)
            if job.pipeline_manager.admit(request):
                dim = snapshot["dims"].get(request.id)
                if dim is not None:
                    job._deploy(request, dim)

        for net_id_key in {k for nets in snapshot["spokes"] for k in nets}:
            self._restore_network(job, snapshot, net_id_key)

        for net_id, bd in snapshot.get("bridges", {}).items():
            self._restore_bridge(job, int(net_id), bd)

        # stream position + routing continuity (resume-from-offset replay)
        job.events_processed = snapshot.get("offset", 0)
        job.source_position = snapshot.get("source_position")
        job._rr = snapshot.get("rr", 0)
        job.rescales_performed = snapshot.get("rescales", 0)
        saved_par = snapshot["config"].get("parallelism")
        if parallelism is not None and parallelism != saved_par:
            # a restore-with-rescale counts like a live rescale (the
            # override redistributes every replica across the new count)
            job.rescales_performed += 1
        for entry in snapshot.get("backlog", ()):
            job._backlog.append(entry)
        job._pending_creates = [
            Request.from_dict(d) for d in snapshot.get("pending_creates", ())
        ]

        # protocol statistics continuity (counters keep accumulating)
        for net_id, sd in snapshot["hub_stats"].items():
            hub = job.hub_manager.hubs.get((int(net_id), 0))
            if hub is not None:
                s = hub.node.stats
                s.models_shipped = sd["modelsShipped"]
                s.bytes_shipped = sd["bytesShipped"]
                s.num_of_blocks = sd["numOfBlocks"]
                s.fitted = sd["fitted"]
                s.learning_curve = list(sd["learningCurve"])
                s.lcx = list(sd["LCX"])

        # protocol ROUND state (sync barriers, partial rounds, clocks,
        # blocked batches, per-worker watermarks): exact continuity is only
        # well-defined 1:1 — under a rescale the fresh nodes start a clean
        # round over the merged model instead
        same_parallelism = len(snapshot["spokes"]) == len(job.spokes)
        if same_parallelism:
            for spoke, nets in zip(job.spokes, snapshot["spokes"]):
                for net_id, sv in nets.items():
                    net = spoke.nets.get(net_id)
                    if net is not None:
                        _restore_node(net.node, sv.get("node"))
        for key, entry in snapshot.get("hub_nodes", {}).items():
            hub = job.hub_manager.hubs.get(key)
            if hub is None:
                continue
            if same_parallelism:
                _restore_node(hub.node, entry.get("node"))
            # the SingleLearner central model does NOT depend on the spoke
            # count — THE model lives on the hub and must survive a rescale
            # restore too (only round state resets across a rescale)
            central = getattr(hub.node, "pipeline", None)
            if central is not None and "pipeline" in entry:
                _pipeline_load(central, entry["pipeline"])
        return job

    def _restore_bridge(self, job, net_id: int, bd: dict) -> None:
        """Restore an SPMD-engine pipeline: fleet state back onto the mesh.

        Same mesh shape: exact shard-by-shard re-placement. Different shape
        (restore under a different parallelism/device count): every worker
        replica seeds from the MEAN of the saved dp replicas — checkpoints
        are taken between events, not at sync barriers, so under
        Asynchronous/SSP/EASGD the replicas diverge mid-round and the mean
        preserves every worker's progress (mirroring the host-plane rescale
        merge in _restore_network); progress counters carry worker-0's
        values and staleness clocks restart coherently at zero."""
        bridge = job.spmd_bridges.get(net_id)
        if bridge is None:
            return
        from omldm_tpu.parallel.ckpt import place_tree
        from omldm_tpu.parallel.spmd import drop_unread_leaves, stacked, stored

        t = bridge.trainer
        # the saved leaves in their [dp, hub, ...] view (a snapshot from
        # before vector leaves were stored flat already is), without an
        # ``est`` or a ``center`` that this protocol's state has none of
        fleet = jax.tree_util.tree_map(
            lambda l: stacked(np.asarray(l), *bd["mesh"]),
            drop_unread_leaves(bd["fleet"], t.protocol),
        )
        if (t.dp, t.hub) == tuple(bd["mesh"]):
            new_state = fleet
        else:

            def tile(leaf):
                l = leaf[0, 0]
                return np.broadcast_to(
                    l, (t.dp, t.hub) + l.shape
                ).copy()

            def merge_tile(leaf):
                # model-bearing leaves: mean over the dp replicas (hub
                # shard 0 — hub replicas agree by construction) so
                # mid-round divergence is merged, not discarded
                m = leaf[:, 0].mean(axis=0).astype(leaf.dtype)
                return np.broadcast_to(m, (t.dp, t.hub) + m.shape).copy()

            new_state = {
                "params": jax.tree_util.tree_map(merge_tile, fleet["params"]),
                "preps": [
                    jax.tree_util.tree_map(merge_tile, p)
                    for p in fleet["preps"]
                ],
                "step": tile(fleet["step"]),
                "syncs": tile(fleet["syncs"]),
                "cum_loss": tile(fleet["cum_loss"]),
                "clock": np.zeros_like(tile(fleet["clock"])),
                "accepted": np.ones_like(tile(fleet["accepted"])),
            }
            for key in ("est", "center"):  # only under the protocols that read them
                if key in fleet:
                    new_state[key] = merge_tile(fleet[key])
            # call-site byte counters and any protocol-specific extras
            # carry over worker-0's values so accounting stays monotonic
            for key, val in fleet.items():
                if key not in new_state:
                    new_state[key] = tile(val)
        t.state = place_tree(
            jax.tree_util.tree_map(stored, new_state), t._state_specs, t.mesh
        )
        t._fitted_host = bd["fitted"]
        t._steps_host = bd["steps"]
        bridge.holdout_count = bd["holdout_count"]
        bridge.restore_buffers(bd)

    def _restore_network(self, job, snapshot, net_id: int):
        saved = [
            nets[net_id] for nets in snapshot["spokes"] if net_id in nets
        ]
        if not saved:
            return
        new_spokes = [s for s in job.spokes if net_id in s.nets]
        if not new_spokes:
            return
        pipes = [s.nets[net_id].pipeline for s in new_spokes]
        learner = pipes[0].learner

        if len(saved) == len(new_spokes):
            # same parallelism: 1:1 state reload
            for spoke, sv in zip(new_spokes, saved):
                self._load_net_state(spoke.nets[net_id], sv)
            return

        # rescale: merge all old replicas into one canonical state...
        merged_params = learner.merge([sv["params"] for sv in saved])
        merged_preps = []
        for i, prep in enumerate(pipes[0].preps):
            merged_preps.append(prep.merge([sv["preps"][i] for sv in saved]))
        total_fitted = sum(sv["fitted"] for sv in saved)
        total_cum_loss = sum(sv["cum_loss"] for sv in saved)

        # ...replicate it onto every new worker (the assignment the reference
        # forgot, FlinkSpoke.scala:291-305). Each worker gets its OWN buffer
        # copy: the fused fit step donates its state, so sharing one pytree
        # across workers would delete buffers out from under the others.
        for spoke in new_spokes:
            net = spoke.nets[net_id]
            pipe = net.pipeline
            pipe.state["params"] = jax.tree_util.tree_map(
                _fresh_copy, merged_params
            )
            for i in range(len(pipe.preps)):
                pipe.state["preps"][i] = jax.tree_util.tree_map(
                    _fresh_copy, merged_preps[i]
                )
            pipe._fitted_host = total_fitted // len(new_spokes)
            # distribute the summed cumulative loss evenly so the job-wide
            # sum (and hence termination-stats totals) carries across rescale
            pipe.state["cum_loss"] = jnp.asarray(
                total_cum_loss / len(new_spokes), jnp.float32
            )
            # the guard's LKG ring restarts at the MERGED model (the saved
            # per-replica rings describe states no restored worker holds —
            # a rollback onto one would undo the merge); the lifecycle
            # registry likewise restarts clean: candidate/canary clocks
            # are per-replica and only well-defined 1:1
            if pipe.guard is not None:
                pipe.guard.reseed(pipe)
            net.holdout_count = max(sv["holdout_count"] for sv in saved)

        # ...and redistribute holdout points + pending records round-robin;
        # test-set overflow queues for training (the evicted-holdout rule)
        all_test = [p for sv in saved for p in sv["test_set"]]
        all_pending = [p for sv in saved for p in sv["pending"]]
        for i, (x, y) in enumerate(all_test):
            net = new_spokes[i % len(new_spokes)].nets[net_id]
            evicted = net.test_set.append((x, y))
            if evicted is not None:
                all_pending.append(evicted)
        for i, row in enumerate(all_pending):
            net = new_spokes[i % len(new_spokes)].nets[net_id]
            self._refeed_pending(net, [row])

    @classmethod
    def _load_net_state(cls, net, sv: dict) -> None:
        # lifecycle registry first: when the snapshot's ACTIVE version is
        # a promoted candidate, restore() rebuilds that pipeline from its
        # spec, loads this snapshot's pipeline fields into it, and
        # installs it — the default load below would otherwise push
        # promoted-spec params into the Create-spec pipeline
        swapped = False
        if net.lifecycle is not None and sv.get("lifecycle") is not None:
            swapped = net.lifecycle.restore(net, sv["lifecycle"], sv)
        if not swapped:
            _pipeline_load(net.pipeline, sv)
        if net.pipeline.guard is not None and sv.get("guard") is not None:
            net.pipeline.guard.restore(sv["guard"])
        net.holdout_count = sv["holdout_count"]
        for p in sv["test_set"]:
            net.test_set.append(p)
        cls._refeed_pending(net, sv["pending"])
