"""SPMD protocol engine: distributed online learning as XLA collectives.

This is the TPU performance path. Where the host-multiplexed runtime
(omldm_tpu.runtime + omldm_tpu.protocols) exchanges parameter messages
through an in-process router — semantically mirroring the reference's
spoke -> hub -> Kafka -> spoke loop (Job.scala:76-87) — the SPMD engine
compiles the WHOLE fleet into one program: every data-parallel worker replica
is a mesh shard, one jitted step trains all replicas simultaneously, and
protocol synchronization is an XLA collective over the ``"dp"`` axis riding
ICI. The ``"hub"`` axis shards the parameter-server state: the protocol
allreduce is decomposed into per-hub-shard ``pmean`` (reduce-scatter role) +
``all_gather`` — the mesh-native form of the reference's bucketed
HubParallelism PS (FlinkSpoke.scala:181-195, FlinkNetwork.scala:48-149).

Protocol mapping (SURVEY.md section 7 step 5):

- ``Synchronous``   — every ``syncEvery`` batches: params <- psmean over dp.
- ``EASGD``         — elastic interaction with a center variable kept in
                      state: x_i -= a(x_i - c); c += a*mean(x_i - c).
- ``GM``            — local drift check; a 1-scalar psum votes on violation;
                      the expensive parameter collective runs under
                      ``lax.cond`` only when some worker left the sphere —
                      communication skipping preserved on real hardware.
- ``FGM``           — safe-zone sum psi = psum(phi_i) decides; same
                      conditional collective. (The increment-counting phase
                      exists to avoid coordinator chatter on a network; on an
                      ICI mesh the 1-scalar psum IS cheaper than any counter
                      machinery, so the safe-zone semantics are kept and the
                      counters retired — see the host-plane FGM for the
                      faithful two-phase variant.)
- ``Asynchronous``  — event-driven PS pushes: each worker advances its own
                      CLOCK only on ticks where it has data (an all-zero
                      mask means "no batch arrived at this worker"), and
                      folds its delta into the shared global at its own
                      clock cadence — uncoordinated progress expressed in
                      one SPMD program.
- ``SSP``           — same event-driven progress, but the staleness bound
                      BINDS: a worker whose clock is ``staleness`` ahead of
                      the slowest worker's (``lax.pmin`` over dp) is
                      REFUSED its batch — the step leaves its state
                      untouched and flags it not-accepted, and the host
                      requeues the batch (host-driven pacing; the device
                      enforces fastest − slowest ≤ s exactly like the host
                      plane's clock-tracked SSP, protocols/sync.py).
                      Per-worker clocks and accept flags live in the fleet
                      state (``worker_clocks()`` / ``last_accepted()``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from omldm_tpu.api.requests import LearnerSpec, PreprocessorSpec, TrainingConfiguration
from omldm_tpu.learners.registry import make_learner
from omldm_tpu.ops.codec import BYTES_PER_ELEMENT, LEAF_META_BYTES, make_qdq
from omldm_tpu.preprocessors.registry import make_preprocessor
from omldm_tpu.parallel.mesh import make_mesh
from omldm_tpu.runtime.codec import comm_codec_name
from omldm_tpu.utils import batch_valid_counts, tracing


SPMD_PROTOCOLS = (
    "Synchronous",
    "EASGD",
    "GM",
    "FGM",
    "Asynchronous",
    "SSP",
)

# The protocols whose step reads the state leaf ``est``, the worker's model
# at its last sync: GM and FGM measure their drift from it, Asynchronous and
# SSP push their delta against it. The fleet state holds the leaf under
# these alone (as it holds ``ef`` only under a transport codec): under
# Synchronous and EASGD it would be a model-sized copy that nothing reads.
# A snapshot from before that rule holds it under every protocol; the
# restore paths drop it there (:func:`drop_unread_leaves`).
EST_PROTOCOLS = frozenset({"GM", "FGM", "Asynchronous", "SSP"})

# Likewise ``center``, the EASGD center variable and the shared global that
# Asynchronous and SSP fold their deltas into: a model-sized leaf that
# Synchronous, GM and FGM would carry through their sync untouched.
CENTER_PROTOCOLS = frozenset({"EASGD", "Asynchronous", "SSP"})

# leaf -> the protocols that hold it, in the order the state let the unread
# ones go (``est`` first): a snapshot from before holds them still, and the
# restore paths drop them (:func:`drop_unread_leaves`, :func:`unread_leaves`).
READ_UNDER = {"est": EST_PROTOCOLS, "center": CENTER_PROTOCOLS}


def unread_leaves(protocol: str) -> List[str]:
    """The model-sized leaves the state of ``protocol`` does not hold."""
    return [k for k, held in READ_UNDER.items() if protocol not in held]


# Compiled programs shared across same-config trainers. A fleet hosts
# tens of thousands of pipelines whose step/serve/scan programs are
# IDENTICAL up to the state flowing through them; one jax.jit closure
# per trainer would compile (and keep the JIT code pages of) one
# executable each, which exhausts the process mmap budget
# (vm.max_map_count, 65530 by default) around ~10k pipelines. The cache
# key is the trainer's full static signature, and every cached callable
# takes the state explicitly, so sharing is semantics-free.
_PROGRAM_CACHE: Dict[tuple, Any] = {}


def _program(key: tuple, build):
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        fn = _PROGRAM_CACHE[key] = build()
    return fn


# --- the stored form of a state leaf, and the one view of it ---
#
# Every mesh shard (w, h) holds one value of every state leaf. A leaf whose
# per-shard value is a VECTOR (a rank-1 ``params`` leaf, ``est``, ``center``,
# ``ef``) is stored flat, ``[dp * hub * n]`` sharded over ``("dp", "hub")``
# together, so the block a shard holds IS the vector the step computes on:
# entering the step, leaving it and serving from it move nothing, and the
# donated buffer is updated in place. (Stored ``[dp, hub, n]``, the block is
# ``f32[1, 1, n]``, which the TPU tiles (1, 128) where ``f32[n]`` is tiled
# (1024): stripping and restoring the two 1s then copies the whole vector,
# three passes over ``n`` a step.) Every other leaf (scalars, matrices) is
# stored stacked ``[dp, hub, ...]``: its tiling survives the leading 1s.
# The stored form tells which: rank 1 is a vector leaf. Nothing outside
# the functions below knows it.


def shard_value(leaf, dp=1, hub=1):
    """The value shard ``(0, 0)`` of a ``(dp, hub)`` mesh holds of a stored
    leaf. With the defaults the array at hand is one shard's own block (the
    step, inside ``shard_map``), and a vector leaf passes through untouched
    (a slice that covers the whole emits nothing)."""
    if leaf.ndim != 1:
        return leaf[0, 0]
    return leaf[: leaf.shape[0] // (dp * hub)]


def shard_block(value):
    """Inverse of :func:`shard_value` at one shard: the block a shard stores
    for ``value``."""
    return value if value.ndim == 1 else value[None, None]


def stacked(leaf, dp, hub):
    """Whole stored leaf -> ``[dp, hub, ...]`` (a reshape: no copy on the
    host)."""
    return leaf.reshape(dp, hub, -1) if leaf.ndim == 1 else leaf


def stored(leaf):
    """Inverse of :func:`stacked`: ``[dp, hub, ...]`` -> the stored form."""
    return leaf.reshape(-1) if leaf.ndim == 3 else leaf


def stored_spec(leaf) -> P:
    """PartitionSpec of a stored leaf: one block per mesh shard."""
    return P(("dp", "hub")) if leaf.ndim == 1 else P("dp", "hub")


def drop_unread_leaves(saved: dict, protocol: str) -> dict:
    """A saved fleet state as a trainer under ``protocol`` holds it: without
    the ``est`` and the ``center`` that a snapshot from before
    ``READ_UNDER`` carries under the protocols that read neither (model-sized
    copies no code read). Nothing else is dropped: any other mismatch with
    the live tree still fails where the state is placed."""
    unread = [k for k in unread_leaves(protocol) if k in saved]
    if not unread:
        return saved
    return {k: v for k, v in saved.items() if k not in unread}


class SPMDTrainer:
    """One pipeline trained data-parallel across a ("dp", "hub") mesh.

    Every mesh shard holds one value of every state leaf, in the stored form
    :func:`stored` gives (vector leaves flat ``[dp * hub * n]``, the others
    stacked ``[dp, hub, ...]``); readers go through :func:`shard_value` and
    :func:`stacked`. Micro-batches arrive stacked ``[dp, B, D]`` (one batch
    per worker). ``step`` runs one jitted, donated training step for the
    whole fleet."""

    def __init__(
        self,
        learner_spec: LearnerSpec,
        preprocessor_specs: Sequence[PreprocessorSpec] = (),
        dim: int = 0,
        protocol: str = "Synchronous",
        mesh=None,
        training_configuration: Optional[TrainingConfiguration] = None,
        batch_size: int = 256,
        seed: int = 0,
    ):
        if protocol not in SPMD_PROTOCOLS:
            raise ValueError(
                f"SPMD engine supports {SPMD_PROTOCOLS}, got {protocol!r}; "
                "host-side models (HT) and SingleLearner/CentralizedTraining "
                "run in the host-multiplexed runtime"
            )
        self.mesh = mesh if mesh is not None else make_mesh()
        self.dp = self.mesh.shape["dp"]
        self.hub = self.mesh.shape["hub"]
        self.protocol = protocol
        self.tc = training_configuration or TrainingConfiguration(protocol=protocol)
        self.learner = make_learner(learner_spec)
        if self.learner.host_side:
            raise ValueError("host-side learners cannot run in the SPMD engine")
        self.preps = [make_preprocessor(p) for p in preprocessor_specs]
        if getattr(self.learner, "sparse", False) and self.preps:
            raise ValueError(
                "sparse learners take padded-COO batches; streaming "
                "preprocessors are a dense-feature concept"
            )
        self.dim = dim
        self.batch_size = batch_size
        self.sync_every = int(self.tc.extra.get("syncEvery", 4))
        self.threshold = float(self.tc.extra.get("threshold", 0.5))
        # SSP staleness bound s: fastest - slowest worker clock <= s
        # (ref: the SSPWorker/SSPParameterServer pair, MLNodeGenerator.scala)
        self.staleness = int(self.tc.extra.get("staleness", 3))
        if protocol == "SSP" and self.staleness < 1:
            # s=0 would refuse every batch at decision time (gap >= 0 is
            # never < 0) and livelock the host's requeue loop; lockstep
            # semantics are what Synchronous is for
            raise ValueError(
                f"SSP staleness must be >= 1, got {self.staleness}"
            )
        default_alpha = 0.5 / max(self.dp, 1)
        self.alpha = float(self.tc.extra.get("alpha", default_alpha))
        # transport codec (trainingConfiguration.comm.codec): the SPMD twin
        # of the host plane's runtime.codec — quantize-dequantize at the
        # collective ship boundary with an error-feedback state leaf, so
        # every value crossing the (emulated) wire is codec-representable.
        # ``topk`` is host-plane only (make_qdq raises: the allreduce needs
        # dense operands); ``none`` compiles the exact pre-codec step.
        self.codec_name = comm_codec_name(self.tc)
        self._qdq = make_qdq(self.codec_name)

        # feature dims through the prep chain
        d = dim
        prep_dims = [d]
        for p in self.preps:
            d = p.out_dim(d)
            prep_dims.append(d)
        self.learner_dim = d

        with tracing.span("build_state"):
            # the parameters' shapes -> flat layout shared by every replica
            # (shapes alone: a model is not built here to be counted)
            self._template = jax.eval_shape(
                lambda: self.learner.init(d, jax.random.PRNGKey(seed))
            )
            self.n_params = sum(
                int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(self._template)
            )
            self.pad = (-self.n_params) % self.hub
            self.flat_size = self.n_params + self.pad
            self.shard_size = self.flat_size // self.hub

            with tracing.span("init_state_host"):
                state_host = self._init_state(seed, prep_dims)
            self._state_specs = jax.tree_util.tree_map(stored_spec, state_host)
            # ends when device_put returns: the copies may still be in flight
            with tracing.span("place_state"):
                self.state = jax.tree_util.tree_map(
                    lambda leaf, spec: jax.device_put(
                        jnp.asarray(leaf), NamedSharding(self.mesh, spec)
                    ),
                    state_host, self._state_specs,
                )

        # the static signature every compiled program of this trainer is
        # a pure function of: trainers agreeing on it share executables
        # through _PROGRAM_CACHE (their step closures are interchangeable
        # — self._flat / self._ps_allreduce depend only on these fields)
        self.program_key = (
            id(self.mesh),
            repr(learner_spec),
            tuple(repr(p) for p in preprocessor_specs),
            dim, protocol, batch_size, self.sync_every, self.threshold,
            self.staleness, self.alpha, self.codec_name,
            bool(self.tc.per_record),
        )
        step_impl = self._build_step()
        self._step_fn = step_impl
        self._step_many = None  # built lazily on first step_many call
        self._step_many_dense = None  # lazily too (mask-free bulk variant)
        batch_spec = P("dp")
        self._step = _program(
            ("step",) + self.program_key,
            lambda: jax.jit(
                jax.shard_map(
                    step_impl,
                    mesh=self.mesh,
                    in_specs=(
                        self._state_specs, batch_spec, batch_spec,
                        batch_spec,
                    ),
                    out_specs=(self._state_specs, P("dp", "hub")),
                ),
                donate_argnums=0,
            ),
        )
        self._fitted_host = 0
        self._steps_host = 0
        self._curve: List[Tuple[Any, int]] = []
        # lazy [.., dp, hub, 4] counters of the launches whose update
        # counted (see step_fn), until plan_counts() reads them
        self._counted: List[Any] = []

    # --- state construction ---

    def _init_state(self, seed: int, prep_dims):
        keys = jax.random.split(jax.random.PRNGKey(seed), self.dp)
        params_dp = jax.vmap(lambda k: self.learner.init(self.learner_dim, k))(keys)

        def stack(leaf):  # [dp, ...] -> [dp, hub, ...]
            return np.repeat(np.asarray(leaf)[:, None], self.hub, axis=1)

        params = jax.tree_util.tree_map(stack, params_dp)
        preps = [
            jax.tree_util.tree_map(
                lambda l: stack(np.broadcast_to(np.asarray(l), (self.dp,) + np.shape(l))),
                p.init(di),
            )
            for p, di in zip(self.preps, prep_dims)
        ]
        zero = stack(np.zeros((self.dp,), np.float32))
        izero = stack(np.zeros((self.dp,), np.int32))
        state = {
            "params": params,
            "preps": preps,
            "step": izero.copy(),
            "syncs": izero.copy(),
            "cum_loss": zero.copy(),
            # per-worker PROGRESS clock (ticks with data actually consumed)
            # and the accept flag of the latest step — the SSP bound reads
            # and the host's pacing/requeue decisions are driven by these
            "clock": izero.copy(),
            "accepted": stack(np.ones((self.dp,), np.float32)),
            # steps on which the gated Async/SSP fold allreduce actually
            # executed (physical collective rounds; 0 for other protocols)
            "fold_rounds": izero.copy(),
        }
        if self.protocol in EST_PROTOCOLS | CENTER_PROTOCOLS:
            # drift estimates seed from each worker's OWN init (the
            # host-plane nodes do the same in on_start): a shared template
            # seed would make randomly-initialized learners (NN) register
            # spurious drift and fire a violation sync before any training
            # happened
            # (raveled on the host, in ``ravel_pytree``'s leaf order:
            # raveling the host copy with jax would put the whole model on
            # the device twice more, the memory peak of a process with a
            # 1 GiB model)
            per_worker_flat = np.zeros((self.dp, self.flat_size), np.float32)
            leaves_dp = [
                np.asarray(l) for l in jax.tree_util.tree_leaves(params_dp)
            ]
            for w in range(self.dp):
                k = 0
                for l in leaves_dp:
                    per_worker_flat[w, k : k + l[w].size] = np.ravel(l[w])
                    k += l[w].size
        if self.protocol in EST_PROTOCOLS:
            # estimate at last sync (GM/FGM drift base, async/SSP delta
            # base): only where the step reads it
            state["est"] = stack(per_worker_flat)
        if self.protocol in CENTER_PROTOCOLS:
            # the center (EASGD center variable / async-SSP shared global)
            # is PS state: it must start IDENTICAL on every worker — its
            # updates are pure collectives, so replicas only stay in
            # agreement if they agree at step 0. Seed it with the
            # fleet-mean init. Only where the step reads it.
            state["center"] = stack(np.broadcast_to(
                per_worker_flat.mean(axis=0, keepdims=True),
                per_worker_flat.shape,
            ))
        if self._qdq is not None:
            # per-worker error-feedback residual for the transport codec:
            # the quantization error of each shipped vector, added back to
            # the next one shipped (1-bit-SGD-style EF). Only present when
            # a codec is configured, so codec-none state trees — and their
            # checkpoints — are unchanged.
            state["ef"] = stack(np.zeros((self.dp, self.flat_size), np.float32))
        return jax.tree_util.tree_map(stored, state)

    # --- the per-shard step ---

    def _flat(self, params):
        flat, _ = jax.flatten_util.ravel_pytree(params)
        if self.pad:
            flat = jnp.concatenate([flat, jnp.zeros((self.pad,), flat.dtype)])
        return flat

    def _unflat(self, flat):
        """Inverse of :meth:`_flat`. Where the model is one vector the flat
        form IS that vector: slices and reshapes that cover the whole emit
        nothing (``ravel_pytree``'s own unravel emits a ``split``)."""
        leaves, treedef = jax.tree_util.tree_flatten(self._template)
        out, k = [], 0
        for leaf in leaves:
            out.append(
                flat[k : k + leaf.size].reshape(leaf.shape).astype(leaf.dtype)
            )
            k += leaf.size
        return jax.tree_util.tree_unflatten(treedef, out)

    def _ps_allreduce(self, flat):
        """pmean over workers, decomposed through the hub-sharded PS:
        each hub shard reduces its param bucket (reduce-scatter role), then
        the buckets are re-assembled with an all_gather."""
        i = jax.lax.axis_index("hub")
        my = jax.lax.dynamic_slice(flat, (i * self.shard_size,), (self.shard_size,))
        avg = jax.lax.pmean(my, "dp")
        full = jax.lax.all_gather(avg, "hub", tiled=True)
        return jax.lax.pcast(full, "dp", to="varying")

    def _build_step(self):
        learner = self.learner
        preps = self.preps
        per_record = self.tc.per_record
        protocol = self.protocol
        sync_every = max(self.sync_every, 1)
        threshold = self.threshold
        alpha = self.alpha
        n_workers = self.dp

        staleness = self.staleness

        sparse = getattr(learner, "sparse", False)

        qdq = self._qdq  # transport codec QDQ kernel (None = raw fp32)
        keeps_est = protocol in EST_PROTOCOLS
        keeps_center = protocol in CENTER_PROTOCOLS
        # the flat form of the parameters is what the collectives and the
        # protocols' drift and center arithmetic read. On one shard under
        # Synchronous with no codec nothing does (the mean over one worker
        # is that worker's own model, bit for bit): the learner's new
        # parameters are the state's, and the model is neither concatenated
        # nor split. Read from the mesh and the protocol, never the learner.
        reads_flat = not (
            protocol == "Synchronous" and self.dp * self.hub == 1
            and qdq is None
        )

        def step_fn(state, x, y, mask):
            # per-shard views: state leaves as one shard stores them
            # (shard_value); batch [1,B,D] dense
            # or ([1,B,K] idx, [1,B,K] val) padded-COO. Inputs may arrive
            # in a narrow feed dtype (float16 staging halves host->device
            # bytes); compute is always f32.
            f32 = jnp.float32
            if sparse:
                idx, val = x
                x = (
                    jax.lax.pcast(idx[0], "hub", to="varying"),
                    jax.lax.pcast(val[0].astype(f32), "hub", to="varying"),
                )
            else:
                x = jax.lax.pcast(x[0].astype(f32), "hub", to="varying")
            y = jax.lax.pcast(y[0].astype(f32), "hub", to="varying")
            mask = jax.lax.pcast(mask[0].astype(f32), "hub", to="varying")
            params = jax.tree_util.tree_map(shard_value, state["params"])
            prep_states = [
                jax.tree_util.tree_map(shard_value, s) for s in state["preps"]
            ]
            est = shard_value(state["est"]) if keeps_est else None
            center = shard_value(state["center"]) if keeps_center else None
            step_i = shard_value(state["step"])
            syncs = shard_value(state["syncs"])
            cum_loss = shard_value(state["cum_loss"])
            clock = shard_value(state["clock"])
            fold_rounds = shard_value(state["fold_rounds"])
            ef = shard_value(state["ef"]) if qdq is not None else None

            old_params = params
            old_preps = prep_states

            # preprocessors: online stats update + transform
            new_preps = []
            z = x
            for prep, s in zip(preps, prep_states):
                s = prep.update(s, z, mask)
                new_preps.append(s)
                z = prep.transform(s, z)

            # a learner that counts what its update did (the sparse index
            # plan: ops.sparse.sparse_update) hands the launch's counters
            # out beside the loss: one more small output, read where the
            # losses are (plan_counts)
            counting = None if per_record else getattr(
                learner, "update_counting", None
            )
            counters = None
            if counting is not None:
                params, loss, counters = counting(params, z, y, mask)
            else:
                update = (
                    learner.update_per_record if per_record else learner.update
                )
                params, loss = update(params, z, y, mask)

            flat = self._flat(params) if reads_flat else None
            step_i = step_i + 1
            at_cadence = (step_i % sync_every) == 0
            has_data = jnp.sum(mask) > 0.0
            # derived from mask so it carries the (dp, hub)-varying type
            accepted = jnp.sum(mask) * 0.0 + 1.0

            def reduced(f, r):
                """The fleet mean of ``f`` as it crosses the wire, and the
                error-feedback residual left behind. Codec ship boundary:
                the worker's contribution is quantized (with error
                feedback) before entering the collective, and the
                reassembled global is quantized again for the downlink —
                both wire legs carry only codec-representable values."""
                if qdq is None:
                    return self._ps_allreduce(f), r
                snd = f + r
                t = qdq(snd)
                return qdq(self._ps_allreduce(t)), snd - t

            # the sync's ``lax.cond`` carries the leaves the state holds:
            # ``est``, ``center`` and ``ef`` are None (no leaf) where the
            # state has none
            def keep(*carry):
                return carry

            if not reads_flat:
                syncs = syncs + at_cadence.astype(syncs.dtype)
            elif protocol == "Synchronous":
                def do_sync(f, s, r):
                    g, r = reduced(f, r)
                    return g, s + 1, r

                flat, syncs, ef = jax.lax.cond(
                    at_cadence, do_sync, keep, flat, syncs, ef,
                )
            elif protocol == "EASGD":
                def do_sync(f, c, s, r):
                    mean_x, r = reduced(f, r)
                    new_c = c + alpha * n_workers * (mean_x - c)
                    new_f = f - alpha * (f - c)
                    return new_f, new_c, s + 1, r

                flat, center, syncs, ef = jax.lax.cond(
                    at_cadence, do_sync, keep, flat, center, syncs, ef,
                )
            elif protocol in ("GM", "FGM"):
                drift2 = jnp.sum((flat - est) ** 2)
                if protocol == "GM":
                    # any worker outside the sphere => global violation
                    violations = jax.lax.psum(
                        (drift2 > threshold**2).astype(jnp.float32), "dp"
                    )
                    fire = violations > 0
                else:
                    # FGM safe zone: psi = sum_i (drift_i^2 - T^2) >= 0
                    psi = jax.lax.psum(drift2 - threshold**2, "dp")
                    fire = psi >= 0.0

                def do_sync(f, e, s, r):
                    g, r = reduced(f, r)
                    return g, g, s + 1, r

                flat, est, syncs, ef = jax.lax.cond(
                    jnp.logical_and(at_cadence, fire), do_sync, keep,
                    flat, est, syncs, ef,
                )
            else:  # Asynchronous / SSP: event-driven progress + PS folds
                # progress is per-worker: a worker only advances its clock
                # on ticks where it has data; under SSP a worker whose
                # clock is `staleness` ahead of the slowest is REFUSED the
                # batch (state untouched, accepted=0) and the host requeues
                # it — the bound binds across device steps, not just
                # within a lockstep round
                min_clock = jax.lax.pmin(clock, "dp")
                if protocol == "SSP":
                    allowed = jnp.logical_and(
                        has_data, (clock - min_clock) < staleness
                    )
                else:
                    allowed = has_data
                accepted = allowed.astype(jnp.float32)
                clock = clock + allowed.astype(jnp.int32)
                # refused/idle workers keep their exact previous state
                flat0 = self._flat(old_params)
                flat = jnp.where(allowed, flat, flat0)
                new_preps = [
                    jax.tree_util.tree_map(
                        lambda new, old: jnp.where(allowed, new, old), s, s0
                    )
                    for s, s0 in zip(new_preps, old_preps)
                ]
                loss = jnp.where(allowed, loss, 0.0)
                # PS push at the worker's own clock cadence. The param-sized
                # fold allreduce is GATED the way GM/FGM gate their sync: a
                # 1-scalar psum vote ("does anyone fold this step?") and the
                # collective under lax.cond — steps where no worker folds
                # ship only the scalar vote over ICI, so physical bytes
                # track logical folds (~syncEvery x fewer param collectives)
                # instead of paying lockstep traffic for async semantics
                my_turn = jnp.logical_and(
                    allowed, (clock % sync_every) == 0
                )
                any_fold = (
                    jax.lax.psum(my_turn.astype(jnp.float32), "dp") > 0.0
                )
                contrib = jnp.where(my_turn, flat - est, jnp.zeros_like(flat))

                if qdq is None:
                    def do_fold(c, fr):
                        # shared global accumulates mean deltas (PS fold),
                        # routed through the hub shards like every collective
                        return c + self._ps_allreduce(contrib), fr + 1

                    center, fold_rounds = jax.lax.cond(
                        any_fold, do_fold, lambda c, fr: (c, fr),
                        center, fold_rounds,
                    )
                else:
                    def do_fold(c, fr, r):
                        # only folding workers ship (and spend) their EF
                        # residual; bystanders contribute exact zeros and
                        # keep their residual for their own next fold
                        s = jnp.where(
                            my_turn, contrib + r, jnp.zeros_like(contrib)
                        )
                        t = qdq(s)
                        new_c = c + qdq(self._ps_allreduce(t))
                        return new_c, fr + 1, jnp.where(my_turn, s - t, r)

                    center, fold_rounds, ef = jax.lax.cond(
                        any_fold, do_fold, lambda c, fr, r: (c, fr, r),
                        center, fold_rounds, ef,
                    )
                flat = jnp.where(my_turn, center, flat)
                est = jnp.where(my_turn, center, est)
                syncs = syncs + my_turn.astype(jnp.int32)

            if protocol not in ("Asynchronous", "SSP"):
                clock = clock + has_data.astype(jnp.int32)

            if reads_flat:
                params = self._unflat(flat)
            n = jnp.sum(mask) * accepted
            cum_loss = cum_loss + loss * n

            new_state = {
                "params": jax.tree_util.tree_map(shard_block, params),
                "preps": [
                    jax.tree_util.tree_map(shard_block, s) for s in new_preps
                ],
                "step": shard_block(step_i),
                "syncs": shard_block(syncs),
                "cum_loss": shard_block(cum_loss),
                "clock": shard_block(clock),
                "accepted": shard_block(accepted),
                "fold_rounds": shard_block(fold_rounds),
            }
            if keeps_est:
                new_state["est"] = shard_block(est)
            if keeps_center:
                new_state["center"] = shard_block(center)
            if qdq is not None:
                new_state["ef"] = shard_block(ef)
            counted = () if counters is None else (counters[None, None],)
            return new_state, (shard_block(loss), counted)

        return step_fn

    # --- public API ---

    def step(self, x, y, mask, valid_count=None):
        """One fleet step. x: [dp, B, D]; y, mask: [dp, B].
        Returns the lazy [dp, hub] loss array. Pass ``valid_count`` (total
        valid rows) when ``mask`` is device-resident — otherwise the
        counting ``np.asarray(mask)`` forces a device->host copy."""
        n = int(valid_count) if valid_count is not None else int(np.asarray(mask).sum())
        self.state, (loss, counted) = self._step(self.state, x, y, mask)
        self._counted += counted
        self._fitted_host += n
        self._steps_host += 1
        self._curve.append((loss, self._fitted_host))
        return loss

    def step_many(self, xs, ys, masks, valid_counts=None):
        """T chained fleet steps in ONE program launch (lax.scan over staged
        batches inside the sharded step). xs: [T, dp, B, D]; ys/masks:
        [T, dp, B]. Returns the lazy [T, dp, hub] losses."""
        if self._step_many is None:
            batch_spec = P(None, "dp")

            def many_impl(state, xs, ys, masks):
                def body(st, b):
                    x, y, m = b
                    return self._step_fn(st, x, y, m)

                return jax.lax.scan(body, state, (xs, ys, masks))

            self._step_many = _program(
                ("step_many",) + self.program_key,
                lambda: jax.jit(
                    jax.shard_map(
                        many_impl,
                        mesh=self.mesh,
                        in_specs=(
                            self._state_specs, batch_spec, batch_spec,
                            batch_spec,
                        ),
                        out_specs=(self._state_specs, P(None, "dp", "hub")),
                    ),
                    donate_argnums=0,
                ),
            )
        counts = batch_valid_counts(masks, valid_counts)
        self.state, (losses, counted) = self._step_many(
            self.state, xs, ys, masks
        )
        self._counted += counted
        fitted_after = []
        for c in counts:
            self._fitted_host += c
            fitted_after.append(self._fitted_host)
        self._steps_host += len(counts)
        self._curve.append((losses, fitted_after))
        return losses

    def step_many_dense(self, xs, ys):
        """T chained fleet steps where EVERY row is valid: the mask is
        synthesized on device, so the host ships only xs/ys (in their feed
        dtype — float16 staging halves the bytes again). This is the bulk
        streaming path: a full stage buffer has no padding by construction
        (runtime.spmd_bridge stages exactly chain*dp*B rows)."""
        if getattr(self, "_step_many_dense", None) is None:
            batch_spec = P(None, "dp")

            def many_dense_impl(state, xs, ys):
                def body(st, b):
                    x, y = b
                    # ones derived from y so the mask carries its
                    # (dp, hub)-varying type
                    ones = y.astype(jnp.float32) * 0.0 + 1.0
                    return self._step_fn(st, x, y, ones)

                return jax.lax.scan(body, state, (xs, ys))

            self._step_many_dense = _program(
                ("step_many_dense",) + self.program_key,
                lambda: jax.jit(
                    jax.shard_map(
                        many_dense_impl,
                        mesh=self.mesh,
                        in_specs=(self._state_specs, batch_spec, batch_spec),
                        out_specs=(self._state_specs, P(None, "dp", "hub")),
                    ),
                    donate_argnums=0,
                ),
            )
        t, dp, b = xs.shape[0], xs.shape[1], xs.shape[2]
        self.state, (losses, counted) = self._step_many_dense(
            self.state, xs, ys
        )
        self._counted += counted
        fitted_after = []
        for _ in range(t):
            self._fitted_host += dp * b
            fitted_after.append(self._fitted_host)
        self._steps_host += t
        self._curve.append((losses, fitted_after))
        return losses

    @property
    def fitted(self) -> int:
        return self._fitted_host

    def shard0(self, tree):
        """Shard (0, 0)'s value of every leaf of a stored state (sub)tree:
        the model that queries and evaluations read (post-sync replicas
        agree). Works on host copies and under ``jit`` alike."""
        return jax.tree_util.tree_map(
            lambda l: shard_value(l, self.dp, self.hub), tree
        )

    def host_stacked(self, leaf) -> np.ndarray:
        """Host copy of a state leaf, viewed ``[dp, hub, ...]``."""
        return stacked(np.asarray(jax.device_get(leaf)), self.dp, self.hub)

    def worker_clocks(self) -> np.ndarray:
        """Per-worker progress clocks [dp] (ticks with data consumed)."""
        return self.host_stacked(self.state["clock"])[:, 0]

    def last_accepted(self) -> np.ndarray:
        """Bool [dp]: whether each worker CONSUMED its batch on the latest
        step. Under SSP a worker at the staleness bound refuses its batch;
        the host must requeue it (and call :meth:`note_requeued` so fitted
        counts only consumed rows)."""
        return self.host_stacked(self.state["accepted"])[:, 0] > 0.0

    def release_stragglers(self) -> None:
        """Termination-time SSP release — the collective analogue of the
        host plane's SSPParameterServer.on_terminate: lift every worker's
        clock to the fleet max so the staleness bound stops refusing final
        drains. Needed when a worker's data partition runs dry (its clock
        can never advance on zero-mask batches, which would pin the bound
        and livelock peers' drains — possible in the multi-process
        deployment where rows cannot be re-striped across processes)."""
        new_clock = _program(
            ("release_clock", id(self.mesh)),
            lambda: jax.jit(
                lambda c: jnp.full_like(c, c.max()),
                out_shardings=NamedSharding(
                    self.mesh, self._state_specs["clock"]
                ),
            ),
        )(self.state["clock"])
        self.state = {**self.state, "clock": new_clock}

    def note_requeued(self, n_rows: int) -> None:
        """Correct the fitted counter for rows a step refused (the host
        counted them optimistically when it issued the step)."""
        self._fitted_host -= int(n_rows)
        self.requeued_rows = getattr(self, "requeued_rows", 0) + int(n_rows)

    def curve_slice(self) -> List[Tuple[float, int]]:
        fresh = self._curve
        self._curve = []
        out: List[Tuple[float, int]] = []
        for losses, fitted in fresh:
            if isinstance(fitted, list):  # step_many entry: [T, dp, hub]
                arr = np.asarray(losses)
                arr = arr.reshape(arr.shape[0], -1).mean(axis=1)
                out.extend((float(l), int(f)) for l, f in zip(arr, fitted))
            else:
                out.append((float(np.asarray(losses).mean()), int(fitted)))
        return out

    def plan_counts(self) -> Dict[str, int]:
        """What the launches since the last call report of their sparse
        index plan (``ops.sparse.sparse_update``), summed over launches and
        workers: ``slots``, the ``slots_distinct`` addresses among them, the
        ``slots_tiles`` whole tiles of 1,024 weights those touch and the
        ``overflow_launches`` that held more distinct addresses than
        the plan's capacity and ran the plain pair. Empty where no launch ran the
        plan. Waits for the device, like :meth:`curve_slice`."""
        fresh, self._counted = self._counted, []
        if not fresh:
            return {}
        # hub replicas of a worker agree: take hub shard 0
        per = np.concatenate(
            [np.asarray(c).reshape(-1, self.hub, 4)[:, 0] for c in fresh]
        ).astype(np.int64)
        return {
            "slots": int(per[:, 2].sum()),
            "slots_distinct": int(per[:, 0].sum()),
            "slots_tiles": int(per[:, 3].sum()),
            "overflow_launches": int(per[:, 1].sum()),
        }

    def sync_count(self) -> int:
        """Total parameter synchronizations executed (summed over workers for
        staggered protocols; rounds for the others)."""
        syncs = self.host_stacked(self.state["syncs"])
        if self.protocol in ("Asynchronous", "SSP"):
            return int(syncs[:, 0].sum())
        return int(syncs[0, 0])

    @staticmethod
    def protocol_traffic_bytes(
        protocol: str, dp: int, flat_size: int,
        syncs_sum: int, syncs00: int, steps: int,
        codec: str = "none",
    ) -> Tuple[int, int]:
        """(sync_count, bytesShipped) from raw counters — the ONE payload
        formula, shared with the distributed job's merged report so the
        two accountings can never diverge. ``codec`` prices each param
        sync at the transport codec's wire width (ops.codec): pass
        ``"none"`` (the default) for the LOGICAL fp32 accounting, the
        pipeline's configured codec for bytes-on-wire. Scalar control
        channels (votes, clocks) are never compressed."""
        per_el = BYTES_PER_ELEMENT[codec]
        meta = LEAF_META_BYTES[codec]
        param_bytes = 2 * (int(flat_size * per_el) + meta)
        if protocol in ("Asynchronous", "SSP"):
            sync_count = syncs_sum
            total = syncs_sum * param_bytes
            channels = 2 if protocol == "SSP" else 1
            total += steps * dp * channels * 2 * 4
        else:
            sync_count = syncs00
            total = syncs00 * dp * param_bytes
        if protocol in ("GM", "FGM"):
            total += steps * dp * 2 * 4
        return sync_count, total

    def bytes_shipped(self) -> int:
        """bytesShipped (FlinkHub.scala:118-127) from CALL-SITE counters,
        not a closed-form guess: every collective site in the compiled step
        increments a device-state counter when it executes, and each site's
        per-execution payload is exact from its traced shapes:

        - param sync (``_ps_allreduce`` under the protocol's condition):
          counted per executing worker in ``syncs``; one execution moves
          that worker's params up and the global back down
          (2 * flat * 4B). For Sync/EASGD/GM/FGM the round counter covers
          all dp workers; Async/SSP count per-worker folds directly.
        - GM/FGM violation/safe-zone vote and the Async/SSP fold vote
          (+ SSP's min-clock pmin): a 1-scalar collective EVERY step per
          worker (the protocols' cheap control channel) — 2 * 4B per
          worker-step per channel, read from the device ``step`` counter.
          This is the traffic the communication-skipping protocols pay
          even in silent rounds.
        """
        syncs = self.host_stacked(self.state["syncs"])
        steps = int(self.host_stacked(self.state["step"])[0, 0])
        _, total = self.protocol_traffic_bytes(
            self.protocol, self.dp, self.flat_size,
            int(syncs[:, 0].sum()), int(syncs[0, 0]), steps,
        )
        return total

    def bytes_on_wire(self) -> int:
        """bytesShipped priced at the configured transport codec's wire
        width — what the sync traffic would cost a deployment whose
        inter-host links carry the quantized representation (the values
        crossing the collective are already codec-representable via the
        in-step QDQ). Equal to :meth:`bytes_shipped` with codec ``none``."""
        syncs = self.host_stacked(self.state["syncs"])
        steps = int(self.host_stacked(self.state["step"])[0, 0])
        _, total = self.protocol_traffic_bytes(
            self.protocol, self.dp, self.flat_size,
            int(syncs[:, 0].sum()), int(syncs[0, 0]), steps,
            codec=self.codec_name,
        )
        return total

    def collective_bytes_physical(self) -> int:
        """Bytes the HARDWARE moved, as opposed to the application-payload
        accounting above. The Async/SSP fold allreduce is gated on a
        1-scalar vote (see _build_step), so its physical traffic is
        per-EXECUTED-round (the device ``fold_rounds`` counter), not
        per-step — plus the per-step scalar vote channel(s). When folds
        line up across workers the physical figure approaches
        bytes_shipped / dp-concurrency; it can exceed bytes_shipped only
        by the scalar control traffic."""
        param_bytes = 2 * self.flat_size * 4
        if self.protocol in ("Asynchronous", "SSP"):
            steps = int(self.host_stacked(self.state["step"])[0, 0])
            rounds = int(self.host_stacked(self.state["fold_rounds"])[0, 0])
            channels = 2 if self.protocol == "SSP" else 1
            return (
                rounds * self.dp * param_bytes
                + steps * self.dp * channels * 2 * 4
            )
        return self.bytes_shipped()

    def global_flat_params(self) -> np.ndarray:
        """Model of worker 0 / hub 0 (post-sync replicas agree), flattened
        on the host in ``ravel_pytree``'s leaf order (raveling the host copy
        with jax would put the whole model on the device twice more)."""
        leaves = jax.tree_util.tree_leaves(
            self.shard0(jax.device_get(self.state["params"]))
        )
        return np.concatenate([np.ravel(l) for l in leaves])

    def shard_params(self):
        """Per-worker params pytree list (host copies)."""
        host = jax.tree_util.tree_map(self.host_stacked, self.state["params"])
        return [
            jax.tree_util.tree_map(lambda l: l[w, 0], host)
            for w in range(self.dp)
        ]

    def save(self, directory: str) -> None:
        """Orbax snapshot of the full fleet state (SURVEY.md section 7 step 8)."""
        from omldm_tpu.parallel.ckpt import save_tree

        save_tree(directory, self.state)

    def load(self, directory: str) -> None:
        """Restore fleet state saved by :meth:`save` (same mesh shape). A
        snapshot whose vector leaves were saved ``[dp, hub, n]`` loads too:
        :func:`stored` brings either form to the stored one. So does one
        that holds an ``est`` or a ``center`` this protocol's state has none
        of (:func:`drop_unread_leaves`)."""
        from omldm_tpu.parallel.ckpt import load_tree, place_tree

        host_state = jax.tree_util.tree_map(
            stored, drop_unread_leaves(load_tree(directory), self.protocol)
        )
        self.state = place_tree(host_state, self._state_specs, self.mesh)

    def _serve_fns(self):
        """Jitted worker-0 serving programs, compiled once and cached: the
        whole (shard (0, 0)'s value of each leaf -> preprocess ->
        predict/eval) chain runs on device — the previous implementation
        device_get the ENTIRE model pytree per call, which put a full
        fleet-state transfer on the per-forecast serving hot path. On one
        chip the value of a vector leaf is the stored leaf itself."""
        if getattr(self, "_serve_cache", None) is None:

            def transform(state, z):
                for prep, s in zip(self.preps, state["preps"]):
                    z = prep.transform(self.shard0(s), z)
                return z

            def predict_fn(state, x):
                z = transform(state, x)
                return self.learner.predict(self.shard0(state["params"]), z)

            def eval_fn(state, x, y, mask):
                z = transform(state, x)
                params = self.shard0(state["params"])
                return (
                    self.learner.loss(params, z, y, mask),
                    self.learner.score(params, z, y, mask),
                )

            self._serve_cache = _program(
                ("serve",) + self.program_key,
                lambda: (jax.jit(predict_fn), jax.jit(eval_fn)),
            )
        return self._serve_cache

    @staticmethod
    def _as_device(x):
        """Dense [B, D] arrays and padded-COO (idx, val) tuples both pass
        through the serve programs."""
        if isinstance(x, tuple):
            return tuple(jnp.asarray(a) for a in x)
        return jnp.asarray(x)

    def predict(self, x) -> np.ndarray:
        """Serve with the worker-0 model (post-sync replicas agree):
        transform through its preprocessor state, then learner.predict."""
        predict_fn, _ = self._serve_fns()
        return np.asarray(predict_fn(self.state, self._as_device(x)))

    def evaluate(self, x, y, mask) -> Tuple[float, float]:
        """Loss/score of the worker-0 model on a host-side holdout set."""
        _, eval_fn = self._serve_fns()
        loss, score = eval_fn(
            self.state, self._as_device(x), jnp.asarray(y), jnp.asarray(mask)
        )
        return float(loss), float(score)
