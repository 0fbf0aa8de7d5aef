"""Cohort execution engine: multi-pipeline co-hosting with gang dispatch.

The host plane hosts one ``MLPipeline`` per (spoke, networkId). PRs 2-5 made
the *single*-pipeline path fast, but with M live pipelines the spoke still
pays M separate tiny XLA program launches per micro-batch cycle:
``_JIT_CACHE`` (pipelines/pipeline.py) shares *compilation* across same-spec
pipelines while *dispatch* stays per-pipeline, so multi-tenant throughput
collapses roughly linearly with pipeline count.

This module groups live pipelines into **cohorts** keyed by the same
``_JIT_CACHE`` key (learner spec, prep chain, dim, per_record), stacks their
state pytrees along a leading pipeline axis, and runs fit / predict /
flat-params for the whole cohort as ONE jitted, donated program launch:

- **Staged gang fit** — ``MLPipeline.fit`` on an attached pipeline *stages*
  its micro-batch instead of dispatching; the spoke's gang barrier (end of a
  record / packed block) launches every staged batch of the cohort as one
  program over ``[capacity, T, B, ...]`` inputs. Capacity and the staging
  depth T are bucketed to powers of two so Create/Update/Delete/rescale
  churn compacts slots (free-list reuse) instead of recompiling; inactive
  slots ride along with zero masks and bit-identically keep their state.
- **Gang member iteration** — the per-member program is ``lax.scan`` of the
  SAME ``fit_impl`` the per-pipeline path jits, iterated over members with
  ``lax.map`` (default on CPU): one launch, and the math per member is
  bit-identical to per-pipeline execution (pinned by tests/test_cohort.py).
  ``cohort_impl="vmap"`` swaps in ``jax.vmap`` — faster on batch-parallel
  backends but subject to batched-reduction rounding (~1e-9 relative), so it
  is only the default off-CPU.
- **Gang flat params** — protocol sync points read/write flat parameter
  vectors (``get_flat_params``/``set_flat_params``). A cohort computes the
  whole ``[capacity, P]`` flat matrix in one launch (cached, row-invalidated
  on writes) and scatters written rows back in one batched unravel+scatter,
  so M same-spec sync points cost O(1) launches instead of O(M) ravels.
- **Deferred protocol actions** — ``WorkerNode`` sync points that would
  force a mid-gang launch (get_flat after the round's fit) register through
  ``MLPipeline.defer_after_launch`` and run right after the gang launch, so
  a sync round stays ONE launch for the whole cohort.
- **Gang hub averaging** — :class:`GangAverager` lets same-protocol cohort
  members' parameter-server shards stage their completed round matrices and
  average them in one stacked ``[M, W, P]`` numpy reduction at the job's
  event barrier (wired to ``SynchronousParameterServer``).

The engine is armed by ``JobConfig.cohort``: ``"off"`` (every route is the
exact pre-cohort code path), ``"auto"`` (cohorts form once
``cohort_min`` homogeneous pipelines are live on a spoke — the default), or
``"on"`` (every eligible pipeline cohorts immediately, capacity 1 up).

**Device sharding** (``JobConfig.cohort_shards``): the tenant axis is
embarrassingly parallel, so with S > 1 shards the cohort lays its leading
pipeline axis across the first S local devices as a ``"tenants"`` mesh axis
(``jax.shard_map``) and every gang program — fit, shared-input
fit, gang predict (forecast serving flushes), flat params, and the guard's
fused health vector — runs as ONE sharded launch with the per-shard member
iteration unchanged (``lax.map``/``vmap`` over the shard's local block).
Because members are independent, the per-member math is the SAME program
the single-device cohort runs: shard count 1 is the exact pre-sharding
code path, and sharded execution is bit-identical to it on CPU (pinned by
tests/test_cohort_sharded.py). Slots map to shards in contiguous blocks
(slot s lives on shard ``s // (capacity // S)``), capacity stays a
multiple of S (initial capacity S, doubling growth), Create/Update/Delete
churn compacts into the least-loaded shard's lowest free slot (no shape
change => no recompile, and tenants stay balanced across the mesh), and
the staging buffers transfer per-shard — each device receives its own
contiguous block slice instead of the whole gang input funneling through
one device.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from omldm_tpu.guard import gang_health_values
from omldm_tpu.pipelines.pipeline import (
    _LRU_CAP,
    _LRUCache,
    _build_impls,
    _param_health,
)

# staged batches per member before a launch is forced: bounds the gang input
# tensor [capacity, T, B, D] when a pipeline has no sync point for a while
MAX_STAGE_DEPTH = 32

# gang program cache: (pipeline cache key, use_vmap, n_shards) -> jitted
# callables. Shape specialization inside jit handles the (capacity, T)
# buckets; this cache only bounds the number of traced python callables,
# like _JIT_CACHE.
_GANG_CACHE: _LRUCache = _LRUCache(_LRU_CAP)

# one Mesh per shard count, shared by every cohort at that width (the
# cached gang programs close over it, so cohorts built later must see the
# SAME mesh object their cached programs were traced against)
_MESHES: Dict[int, Any] = {}


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def resolve_cohort_shards(config) -> int:
    """The effective tenant-axis shard count for ``config.cohort_shards``:
    ``off``/empty/<=1 -> 1 (single-device gang launches, the exact
    pre-sharding path), ``auto`` -> the largest power of two <= the local
    device count, an integer -> clamped to the local device count and
    floored to a power of two (capacity buckets double from S, so a pow2
    S keeps them pow2)."""
    spec = str(getattr(config, "cohort_shards", "off") or "off").strip().lower()
    if spec in ("off", "none", "false", "0", "1", ""):
        return 1
    n_dev = len(jax.local_devices())
    if spec == "auto":
        want = n_dev
    else:
        try:
            want = int(spec)
        except ValueError:
            # unrecognized spelling: degrade to single-device like the
            # sibling cohort/cohort_impl knobs, never kill the job
            return 1
    want = min(max(want, 1), n_dev)
    n = 1
    while n * 2 <= want:
        n *= 2
    return n


def _mesh_for(n_shards: int):
    mesh = _MESHES.get(n_shards)
    if mesh is None:
        devices = np.array(jax.local_devices()[:n_shards])
        mesh = jax.sharding.Mesh(devices, ("tenants",))
        _MESHES[n_shards] = mesh
    return mesh


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _build_gang_programs(
    learner, preps, per_record: bool, use_vmap: bool, guarded: bool = False,
    mesh=None,
):
    """The (fit, shared-input fit, predict, flat) jitted programs for a
    cohort spec.

    The member computation is the SAME ``fit_impl`` the per-pipeline path
    jits; only the iteration over members differs (lax.map or vmap).
    ``guarded`` cohorts additionally reduce each member's post-scan
    parameter health (isfinite + squared norm) inside the SAME launch —
    the per-member half of the model-integrity guard, detecting one
    diverging member without extra dispatches or perturbing siblings.

    With ``mesh`` set (device-sharded cohorts), every program wraps in
    ``shard_map`` over the ``tenants`` axis before jit: each shard runs
    the per-member iteration over ITS contiguous block of the leading
    pipeline axis — members are independent, so no collective is needed
    and the per-member math is bitwise the single-device program's. The
    shared-input twin keeps its batches replicated (shipped once) and
    broadcasts per shard; everything else shards on the leading axis."""
    fit_impl, predict_impl, _eval_impl, _ = _build_impls(
        learner, preps, per_record
    )

    def member_fit(st, xs_m, ys_m, ms_m):
        def step(st, batch):
            x, y, m = batch
            new_st, loss = fit_impl(st, x, y, m)
            # zero-mask steps (T padding, inactive slots) keep their state
            # BITWISE: the computed branch is discarded by the select, so
            # even a NaN from an all-masked update cannot leak
            keep = jnp.sum(m) > 0
            new_st = _tree_map(
                lambda a, b: jnp.where(keep, a, b), new_st, st
            )
            return new_st, loss

        st2, losses = jax.lax.scan(step, st, (xs_m, ys_m, ms_m))
        if guarded:
            return st2, (losses, _param_health(st2["params"]))
        return st2, losses

    def _ravel(p):
        return jax.flatten_util.ravel_pytree(p)[0]

    if use_vmap:
        gang_fit = jax.vmap(member_fit)
        gang_predict = jax.vmap(predict_impl)
        gang_flat = jax.vmap(_ravel)
    else:
        def gang_fit(state, xs, ys, ms):
            return jax.lax.map(
                lambda z: member_fit(*z), (state, xs, ys, ms)
            )

        def gang_predict(state, xs):
            return jax.lax.map(lambda z: predict_impl(*z), (state, xs))

        def gang_flat(params):
            return jax.lax.map(_ravel, params)

    def gang_fit_shared(state, active, xs, ys, ms):
        # SHARED-input twin: every member trains the same [T, B, ...]
        # batches, shipped ONCE and broadcast in-program (XLA folds the
        # broadcast into the per-member slices, so the host->device
        # conversion stops scaling with the member count). The member
        # computation is gang_fit's own — inactive slots just see zero
        # masks, the same bitwise state-preserving select as T padding.
        cap = jax.tree_util.tree_leaves(state)[0].shape[0]
        xs_b = jnp.broadcast_to(xs, (cap,) + xs.shape)
        ys_b = jnp.broadcast_to(ys, (cap,) + ys.shape)
        act = active.reshape((cap,) + (1,) * ms.ndim)
        ms_b = jnp.where(
            act, jnp.broadcast_to(ms, (cap,) + ms.shape), 0.0
        )
        return gang_fit(state, xs_b, ys_b, ms_b)

    if mesh is not None:
        # device-sharded gang: one launch, the tenants axis laid across
        # the mesh, per-shard member iteration. in/out specs are pytree
        # PREFIXES — P("tenants") shards every leaf's leading (pipeline)
        # axis; P() replicates the shared-input batches so they ship once
        # and broadcast in-program on each shard. The wraps bind NEW
        # names: gang_fit_shared calls gang_fit late-bound, and wrapping
        # it in place would nest shard_maps.
        P = jax.sharding.PartitionSpec
        sh, rep = P("tenants"), P()
        sharded_fit = jax.shard_map(
            gang_fit, mesh=mesh, in_specs=(sh, sh, sh, sh), out_specs=sh,
            check_vma=False,
        )
        sharded_shared = jax.shard_map(
            gang_fit_shared, mesh=mesh, in_specs=(sh, sh, rep, rep, rep),
            out_specs=sh, check_vma=False,
        )
        sharded_predict = jax.shard_map(
            gang_predict, mesh=mesh, in_specs=(sh, sh), out_specs=sh,
            check_vma=False,
        )
        sharded_flat = jax.shard_map(
            gang_flat, mesh=mesh, in_specs=sh, out_specs=sh,
            check_vma=False,
        )
        return (
            jax.jit(sharded_fit, donate_argnums=0),
            jax.jit(sharded_shared, donate_argnums=0),
            jax.jit(sharded_predict),
            jax.jit(sharded_flat),
        )

    return (
        jax.jit(gang_fit, donate_argnums=0),
        jax.jit(gang_fit_shared, donate_argnums=0),
        jax.jit(gang_predict),
        jax.jit(gang_flat),
    )


class _LaunchResult:
    """Shared holder for one gang launch's ``[C, T]`` loss matrix. Created
    when staging opens a launch group, fulfilled (lazily) at launch, and
    materialized to numpy at most once — forcing the launch first if a
    learning-curve poll somehow reads it early."""

    __slots__ = ("_cohort", "_lazy", "_np")

    def __init__(self, cohort: "Cohort"):
        self._cohort: Optional[Cohort] = cohort
        self._lazy = None
        self._np: Optional[np.ndarray] = None

    def fulfill(self, losses) -> None:
        self._lazy = losses
        self._cohort = None

    def values(self) -> np.ndarray:
        if self._np is None:
            if self._lazy is None:
                cohort, self._cohort = self._cohort, None
                if cohort is not None:
                    cohort.launch()
            self._np = np.asarray(self._lazy)
            self._lazy = None
        return self._np


class _StagedLoss:
    """Lazy loss of a staged fit: floats (or arrays, for fit_many chains)
    exactly like the lazy device scalars the un-cohorted path returns."""

    __slots__ = ("_res", "_slot", "_t0", "_t1")

    def __init__(self, res: _LaunchResult, slot: int, t0: int,
                 t1: Optional[int] = None):
        self._res = res
        self._slot = slot
        self._t0 = t0
        self._t1 = t1

    def _resolve(self):
        vals = self._res.values()
        if self._t1 is None:
            return vals[self._slot, self._t0]
        return vals[self._slot, self._t0:self._t1]

    def __float__(self) -> float:
        return float(self._resolve())

    def __array__(self, dtype=None):
        return np.asarray(self._resolve(), dtype)


class Cohort:
    """Same-spec pipelines sharing one stacked state tree + gang programs.

    Slots: ``members[slot]`` is the attached pipeline or None; capacity is
    a power of two; churn reuses freed slots (compaction) and only a full
    cohort doubles capacity (a shape change XLA re-specializes once)."""

    def __init__(self, pipeline, use_vmap: bool, timer=None, n_shards: int = 1,
                 serve_timer=None):
        self.key = pipeline.cache_key
        self.use_vmap = use_vmap
        self.timer = timer
        # serving-launch timing (gang predict flushes) is accounted apart
        # from the fit flush path so launch_timing() can report both
        self.serve_timer = serve_timer
        # tenant-axis device sharding: with n_shards > 1 the stacked state
        # and every gang launch lay the leading pipeline axis across the
        # first n_shards local devices (mesh axis "tenants"); 1 = the
        # exact single-device pre-sharding path
        self.n_shards = max(int(n_shards), 1)
        self._mesh = _mesh_for(self.n_shards) if self.n_shards > 1 else None
        self._sharding = (
            jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec("tenants")
            )
            if self._mesh is not None
            else None
        )
        # guarded pipelines gang with guarded programs (the guard flag is
        # part of cache_key, so a cohort is uniformly guarded or not)
        self.guarded = pipeline.guard is not None
        programs = _GANG_CACHE.get((self.key, use_vmap, self.n_shards))
        if programs is None:
            programs = _build_gang_programs(
                pipeline.learner, pipeline.preps, pipeline.per_record,
                use_vmap, guarded=self.guarded, mesh=self._mesh,
            )
            _GANG_CACHE.put((self.key, use_vmap, self.n_shards), programs)
        self._gfit, self._gfit_shared, self._gpred, self._gflat = programs
        flat0, self._unravel = jax.flatten_util.ravel_pytree(
            pipeline._state["params"]
        )
        self._flat_size = int(flat0.size)
        self._junflat = jax.jit(
            lambda mat: jax.lax.map(self._unravel, mat)
        )
        self.capacity = 0
        self.members: List[Optional[Any]] = []
        self.n_active = 0
        self._free: List[int] = []
        self.stacked = None
        # host-side authoritative overrides, scattered before every launch
        self._host_state: Dict[int, dict] = {}
        self._pending_flat: Dict[int, np.ndarray] = {}
        # staging: persistent [capacity, T, B, ...] numpy buffers written
        # in place at stage time (no per-launch allocation or entry
        # lists); `_counts` tracks the staged depth per slot, and only
        # the staged mask region is re-zeroed after a launch — stale
        # x/y garbage under a zero mask is discarded bitwise in-program
        self._counts: Dict[int, int] = {}
        self._buf_x: Optional[np.ndarray] = None
        self._buf_y: Optional[np.ndarray] = None
        self._buf_m: Optional[np.ndarray] = None
        # shared-input detection: when every member's staged batch at each
        # depth is the SAME array object (the spoke's shared-ingest path
        # flushes one batcher to all members of an identical-stream
        # cohort), the launch runs the shared program over ONE [T, B, ...]
        # input instead of a [capacity, T, B, ...] stack — collapsing the
        # dominant host->device conversion by the member count
        self._share_first: Optional[int] = None
        self._share_rows: List[Tuple[Any, Any, Any]] = []
        self._all_shared = False
        self._next_result: Optional[_LaunchResult] = None
        # deferred protocol actions (sync points) run right after a launch
        self._post: List[Tuple[int, Callable[[], None]]] = []
        self._post_slots: set = set()
        self._flat_cache: Optional[np.ndarray] = None
        self._in_launch = False
        # persistent gang-predict staging pads, keyed by per-slot batch
        # shape (the serving plane's pow2 row buckets keep this small);
        # _pred_dirty tracks which slots each pad last wrote
        self._pred_scratch: Dict[tuple, np.ndarray] = {}
        self._pred_dirty: Dict[tuple, List[int]] = {}
        self.attach(pipeline)

    # --- tenant-axis sharding helpers ------------------------------------

    def _pin(self, tree):
        """Constrain a stacked pytree to the tenants sharding. Host writes
        and growth run as plain jnp ops whose output placement GSPMD
        chooses; this re-lays every leaf's leading axis across the mesh
        (a no-op copy when already correctly sharded). Identity when
        unsharded."""
        if self._sharding is None:
            return tree
        return jax.device_put(tree, self._sharding)

    def _stage_dev(self, host_view: np.ndarray):
        """Ship one staged gang input to the device(s). Unsharded: hand
        the numpy view to the dispatch (which copies to the one device).
        Sharded: transfer per shard — slots are laid out in contiguous
        shard blocks, so each device receives its own slice of the host
        buffer and the transfer fans out across the mesh instead of
        funneling the whole ``[C, T, B, ...]`` tensor through one
        device."""
        if self._sharding is None:
            return host_view
        return jax.device_put(host_view, self._sharding)

    def _member_pull(self, slot: int) -> dict:
        """One member's state slice out of the stacked tree. Sharded
        cohorts materialize it to HOST leaves: a slice stays committed to
        its owning mesh device, and downstream per-member ops (solo
        re-dispatch after detach, merge_from, checkpoint restore) would
        trip multi-device colocation checks mixing it with default-device
        arrays. Values are bitwise the device slice either way."""
        st = _tree_map(lambda l: l[slot], self.stacked)
        if self._sharding is not None:
            st = _tree_map(lambda l: np.asarray(l), st)
        return st

    def _host_state_leaves(self, state):
        """Scatter-side twin of :meth:`_member_pull`: writes into a
        sharded stack go in as host numpy leaves (uncommitted), never as
        arrays pinned to some other device."""
        if self._sharding is None:
            return state
        return _tree_map(lambda v: np.asarray(v), state)

    def _shard_of(self, slot: int) -> int:
        per = max(self.capacity // self.n_shards, 1)
        return min(slot // per, self.n_shards - 1)

    def shard_placement(self) -> List[int]:
        """Active member count per shard (length ``n_shards``) — the
        tenant placement the multi-tenant sweep records per mesh width."""
        counts = [0] * self.n_shards
        for slot, member in enumerate(self.members):
            if member is not None:
                counts[self._shard_of(slot)] += 1
        return counts

    def _pick_slot(self) -> int:
        """Claim a free slot. Single-shard: the lowest free slot (churn
        compaction). Sharded: the lowest free slot on the least-loaded
        shard — churn still compacts (within a shard, so no shape change
        and no recompile) while members stay balanced across the mesh."""
        if self.n_shards == 1:
            return self._free.pop()
        counts = self.shard_placement()
        slot = min(
            self._free, key=lambda s: (counts[self._shard_of(s)], s)
        )
        self._free.remove(slot)
        return slot

    # --- membership ------------------------------------------------------

    def attach(self, pipeline) -> int:
        """Adopt a pipeline: its local state seeds a (reused or new) slot
        and the pipeline's hot-path methods route through the cohort."""
        self.launch()
        if self.stacked is None:
            # first member: the smallest stack seeded from its state —
            # capacity 1 unsharded, one slot per shard when sharded (the
            # leading axis must cover the mesh; the duplicate rows are
            # inert until attach seeds them)
            cap = self.n_shards
            self.capacity = cap
            self.members = [pipeline] + [None] * (cap - 1)
            self.n_active = 1
            self._free = list(range(cap - 1, 0, -1))
            if cap == 1:
                self.stacked = _tree_map(
                    lambda l: jnp.asarray(l)[None], pipeline._state
                )
            else:
                self.stacked = self._pin(_tree_map(
                    lambda l: jnp.broadcast_to(
                        jnp.asarray(l)[None],
                        (cap,) + jnp.asarray(l).shape,
                    ),
                    pipeline._state,
                ))
            pipeline._cohort = self
            pipeline._slot = 0
            pipeline._state = None
            self._flat_cache = None
            return 0
        if not self._free:
            self._grow()
        slot = self._pick_slot()
        state = self._host_state_leaves(pipeline._state)
        self.stacked = self._pin(_tree_map(
            lambda leaf, v: leaf.at[slot].set(
                v if isinstance(v, np.ndarray) else jnp.asarray(v)
            ),
            self.stacked, state,
        ))
        self.members[slot] = pipeline
        self.n_active += 1
        pipeline._cohort = self
        pipeline._slot = slot
        pipeline._state = None
        self._flat_cache = None
        return slot

    def detach(self, pipeline) -> None:
        """Release a member: its slot's state materializes back into the
        pipeline and the slot returns to the free list for churn reuse."""
        self.launch()
        slot = pipeline._slot
        pipeline._state = self._member_pull(slot)
        pipeline._cohort = None
        pipeline._slot = -1
        self.members[slot] = None
        self.n_active -= 1
        self._host_state.pop(slot, None)
        self._pending_flat.pop(slot, None)
        self._free.append(slot)
        self._free.sort(reverse=True)  # reuse the lowest slot first

    def _grow(self) -> None:
        """Double capacity (power-of-two buckets): the new region is filled
        with duplicated rows — inert until a slot is seeded by attach.

        Sharded cohorts double EACH SHARD'S contiguous block in place
        (slot ``i*per + j`` remaps to ``i*2*per + j``): every member stays
        on its shard across growth, so placement balance survives and the
        one-time data movement is shard-local. Growth only happens from
        :meth:`attach`, right after a launch barrier — staging counts,
        launch groups and deferred actions are all empty, so only the
        membership maps and pending host writes carry slot keys."""
        old = self.capacity
        if self.n_shards == 1:
            self.stacked = _tree_map(
                lambda l: jnp.concatenate([l, l], axis=0), self.stacked
            )
            self.members.extend([None] * old)
            self._free.extend(range(old * 2 - 1, old - 1, -1))
            self._free.sort(reverse=True)
            self.capacity = old * 2
            return
        per = old // self.n_shards

        def dbl(l):
            blocks = l.reshape((self.n_shards, per) + l.shape[1:])
            blocks = jnp.concatenate([blocks, blocks], axis=1)
            return blocks.reshape((old * 2,) + l.shape[1:])

        self.stacked = self._pin(_tree_map(dbl, self.stacked))
        remap = {
            s: (s // per) * 2 * per + (s % per) for s in range(old)
        }
        new_members: List[Optional[Any]] = [None] * (old * 2)
        for s, member in enumerate(self.members):
            if member is not None:
                new_members[remap[s]] = member
                member._slot = remap[s]
        self.members = new_members
        self._host_state = {
            remap[s]: v for s, v in self._host_state.items()
        }
        self._pending_flat = {
            remap[s]: v for s, v in self._pending_flat.items()
        }
        self.capacity = old * 2
        self._free = sorted(
            (s for s in range(old * 2) if new_members[s] is None),
            reverse=True,
        )
        self._flat_cache = None

    # --- staging ----------------------------------------------------------

    def has_staged(self, slot: int) -> bool:
        return slot in self._counts

    def has_deferred(self, slot: int) -> bool:
        return slot in self._post_slots

    def after_launch(self, slot: int, cb: Callable[[], None]) -> None:
        self._post.append((slot, cb))
        self._post_slots.add(slot)

    def _open_group(self) -> _LaunchResult:
        if self._next_result is None:
            self._next_result = _LaunchResult(self)
        return self._next_result

    def _stage_room(self, slot: int, x: np.ndarray, y: np.ndarray,
                    m: np.ndarray, need: int) -> int:
        """Make room for ``need`` more staged steps on ``slot``; returns
        the slot's current depth (post any forced launch/realloc)."""
        if slot in self._post_slots:
            # a deferred sync point is pending for this member: it must run
            # (on the post-launch model) before the member's next fit
            self.launch()
        n = self._counts.get(slot, 0)
        if n + need > MAX_STAGE_DEPTH:
            self.launch()
            n = 0
        buf = self._buf_x
        if (
            buf is None
            or buf.shape[0] != self.capacity
            or buf.shape[2:] != x.shape
            or buf.shape[1] < n + need
        ):
            self._realloc_buffers(x, y, m, n + need)
            n = self._counts.get(slot, 0)  # a shape-mismatch realloc launches
        return n

    def _realloc_buffers(self, x, y, m, depth: int) -> None:
        t_alloc = _pow2(max(depth, 4))
        new_x = np.zeros((self.capacity, t_alloc) + x.shape, np.float32)
        new_y = np.zeros((self.capacity, t_alloc) + y.shape, np.float32)
        new_m = np.zeros((self.capacity, t_alloc) + m.shape, np.float32)
        if self._counts and self._buf_x is not None:
            if self._buf_x.shape[2:] != x.shape:
                # same-cohort batches always share a shape; a mismatch can
                # only arrive across a settle point
                self.launch()
                self._counts = {}
            else:
                c = min(self._buf_x.shape[0], self.capacity)
                t = min(self._buf_x.shape[1], t_alloc)
                new_x[:c, :t] = self._buf_x[:c, :t]
                new_y[:c, :t] = self._buf_y[:c, :t]
                new_m[:c, :t] = self._buf_m[:c, :t]
        self._buf_x, self._buf_y, self._buf_m = new_x, new_y, new_m

    def _materialize_shared(self) -> None:
        """Backfill the per-slot buffers of members that skipped their
        copies under shared detection; per-slot launching is valid after."""
        if not self._all_shared:
            return
        self._all_shared = False
        lead = self._share_first
        for slot, n in self._counts.items():
            if slot == lead:
                continue
            self._buf_x[slot, :n] = self._buf_x[lead, :n]
            self._buf_y[slot, :n] = self._buf_y[lead, :n]
            self._buf_m[slot, :n] = self._buf_m[lead, :n]
        self._share_rows = []

    def stage_fit(self, slot: int, x, y, mask) -> _StagedLoss:
        x = np.asarray(x)
        y = np.asarray(y)
        m = np.asarray(mask)
        n = self._stage_room(slot, x, y, m, 1)
        res = self._open_group()
        if not self._counts:
            # first stage of a launch group: it leads shared detection
            self._share_first = slot
            self._share_rows = [(x, y, m)]
            self._all_shared = True
        elif self._all_shared:
            if slot == self._share_first and n == len(self._share_rows):
                self._share_rows.append((x, y, m))
            elif (
                slot != self._share_first
                and n < len(self._share_rows)
                and x is self._share_rows[n][0]
                and y is self._share_rows[n][1]
                and m is self._share_rows[n][2]
            ):
                # identical objects: the leader's buffer row IS this
                # member's batch — no copy
                self._counts[slot] = n + 1
                return _StagedLoss(res, slot, n)
            else:
                self._materialize_shared()
        self._buf_x[slot, n] = x
        self._buf_y[slot, n] = y
        self._buf_m[slot, n] = m
        self._counts[slot] = n + 1
        return _StagedLoss(res, slot, n)

    def stage_fit_many(self, slot: int, xs, ys, masks) -> _StagedLoss:
        xs = np.asarray(xs)
        ys = np.asarray(ys)
        ms = np.asarray(masks)
        depth = int(xs.shape[0])
        n = self._stage_room(slot, xs[0], ys[0], ms[0], depth)
        self._materialize_shared()  # chained drains never share objects
        res = self._open_group()
        self._buf_x[slot, n : n + depth] = xs
        self._buf_y[slot, n : n + depth] = ys
        self._buf_m[slot, n : n + depth] = ms
        self._counts[slot] = n + depth
        return _StagedLoss(res, slot, n, n + depth)

    # --- launching --------------------------------------------------------

    def launch(self) -> None:
        """Gang barrier: execute every staged fit, then run the deferred
        protocol actions (which may stage/launch more — e.g. a sync push
        whose round release drains blocked batches)."""
        if self._in_launch:
            self._run_staged()
            return
        self._in_launch = True
        try:
            while True:
                self._run_staged()
                if not self._post:
                    break
                post, self._post = self._post, []
                self._post_slots = set()
                for _slot, cb in post:
                    cb()
        finally:
            self._in_launch = False

    def _note_launch(self, slot: int) -> None:
        member = self.members[slot] if 0 <= slot < self.capacity else None
        if member is not None and member.on_launch is not None:
            member.on_launch()

    def _timed(self):
        return self.timer if self.timer is not None else contextlib.nullcontext()

    def _timed_serve(self):
        """Gang predict launches (forecast serving flushes) time into the
        serve timer, not the fit flush timer, so launch_timing() reports
        the serving plane's launch percentiles separately."""
        if self.serve_timer is not None:
            return self.serve_timer
        return self._timed()

    def _run_staged(self) -> None:
        self._apply_host_writes()
        if not self._counts:
            return
        shared = (
            self._all_shared
            and len(self._counts) > 1
            and len(set(self._counts.values())) == 1
        )
        if not shared:
            self._materialize_shared()
        lead = self._share_first
        self._share_first = None
        self._share_rows = []
        self._all_shared = False
        counts, self._counts = self._counts, {}
        result, self._next_result = self._next_result, None
        t_pad = _pow2(max(counts.values()))
        self._note_launch(min(counts))
        # the staged rows are COPIED off the staging buffers before the
        # dispatch: it is async and jax may alias a numpy argument
        # zero-copy (the CPU backend does where the view happens to be
        # aligned), while the buffers' masks are re-zeroed right below and
        # the rows refilled by the next stage_fit — without the copy a
        # launch could read zeros for rows it was given (seen as holes of
        # 0.0 in a learning curve and untrained batches, on some runs)
        if shared:
            # one [T, B, ...] input for the whole cohort: the conversion
            # cost stops scaling with the member count
            xs, ys, ms = (
                buf[lead, :t_pad].copy()
                for buf in (self._buf_x, self._buf_y, self._buf_m)
            )
            active = np.zeros((self.capacity,), np.bool_)
            active[list(counts)] = True
            with self._timed():
                self.stacked, losses = self._gfit_shared(
                    self.stacked, active, xs, ys, ms
                )
            self._buf_m[lead, :t_pad] = 0.0
            if self.guarded:
                losses = self._note_health(losses, counts)
        else:
            # sharded cohorts ship each device its own contiguous block of
            # the slot-major staging buffers (_stage_dev); unsharded, the
            # arrays go straight to the dispatch
            xs, ys, ms = (
                self._stage_dev(buf[:, :t_pad].copy())
                for buf in (self._buf_x, self._buf_y, self._buf_m)
            )
            with self._timed():
                self.stacked, losses = self._gfit(self.stacked, xs, ys, ms)
            # re-zero ONLY the staged mask region: everything else is
            # already zero, and stale x/y rows under a zero mask are inert
            for slot, n in counts.items():
                self._buf_m[slot, :n] = 0.0
            if self.guarded:
                losses = self._note_health(losses, counts)
        if result is not None:
            result.fulfill(losses)
        self._flat_cache = None

    def _note_health(self, gang_out, counts):
        """Split a guarded gang launch's ``(losses, sq_norm[C])`` output:
        hand each launched member its health scalar and return the plain
        loss matrix for the launch result. The [C] health vector is
        materialized ONCE here (the launch just ran, so this is one small
        transfer) — per-slot lazy device slices would cost every member
        its own blocking transfer at the next guard tick, C tiny syncs in
        exactly the dispatch-overhead regime cohorts exist to collapse.
        Sharded cohorts gather the vector per shard in one parallel
        device_get (guard.gang_health_values)."""
        losses, sq_norm = gang_out
        vals = gang_health_values(sq_norm)
        for slot, n in counts.items():
            member = self.members[slot]
            if member is not None and member.guard is not None:
                member.guard.note(float(vals[slot]), fits=n)
        return losses

    def _apply_host_writes(self) -> None:
        """Scatter host-side authoritative state (checkouts, written flat
        rows) back into the stacked tree before the next program runs."""
        if self._host_state:
            for slot, st in self._host_state.items():
                st = self._host_state_leaves(st)
                self.stacked = _tree_map(
                    lambda leaf, v: leaf.at[slot].set(
                        v if isinstance(v, np.ndarray) else jnp.asarray(v)
                    ),
                    self.stacked, st,
                )
            self.stacked = self._pin(self.stacked)
            self._host_state.clear()
            self._flat_cache = None
        if self._pending_flat:
            slots = sorted(self._pending_flat)
            k = _pow2(len(slots))
            mat = np.zeros((k, self._flat_size), np.float32)
            for i, s in enumerate(slots):
                mat[i] = self._pending_flat[s]
            # pad with duplicates of the first row/index: a duplicate
            # scatter index writes the same value, so the pow2 bucket is
            # free of shape churn without perturbing any other slot
            mat[len(slots):] = mat[0]
            idx = np.asarray(
                slots + [slots[0]] * (k - len(slots)), np.int32
            )
            new_params = self._junflat(jnp.asarray(mat))
            if self._sharding is not None:
                # host-leaf updates + numpy indices: the scatter operands
                # must not be committed to one device while the target is
                # mesh-sharded
                new_params = _tree_map(lambda l: np.asarray(l), new_params)
                self.stacked["params"] = self._pin(_tree_map(
                    lambda leaf, u: leaf.at[idx].set(u),
                    self.stacked["params"], new_params,
                ))
            else:
                jidx = jnp.asarray(idx)
                self.stacked["params"] = _tree_map(
                    lambda leaf, u: leaf.at[jidx].set(u),
                    self.stacked["params"], new_params,
                )
            self._pending_flat.clear()

    # --- member state access ---------------------------------------------

    def checkout(self, slot: int) -> dict:
        """Authoritative (host-cached) state dict for one member. The SAME
        dict is returned until the next launch scatters it back, so callers
        that mutate entries in place (checkpoint restore, merge_from) see
        their writes land in the stacked tree."""
        st = self._host_state.get(slot)
        if st is None:
            self.launch()
            st = self._member_pull(slot)
            pend = self._pending_flat.pop(slot, None)
            if pend is not None:
                st["params"] = self._unravel(jnp.asarray(pend))
            self._host_state[slot] = st
            self._flat_cache = None  # caller may mutate params
        return st

    def set_member_state(self, slot: int, value: dict) -> None:
        self.launch()
        self._pending_flat.pop(slot, None)
        self._host_state[slot] = value
        self._flat_cache = None

    def peek_state(self, slot: int) -> dict:
        """Read-only member state snapshot (predict/evaluate)."""
        st = self._host_state.get(slot)
        if st is not None:
            return st
        self.launch()
        return self._member_pull(slot)

    def member_flat(self, slot: int):
        """(flat params row copy, unravel) — the gang get_flat: the [C, P]
        flat matrix is computed in ONE launch and cached; row writes keep
        the cache warm instead of invalidating it."""
        st = self._host_state.get(slot)
        if st is not None:
            flat, _ = jax.flatten_util.ravel_pytree(st["params"])
            return np.array(flat), self._unravel
        self.launch()
        if self._flat_cache is None:
            self._note_launch(slot)
            with self._timed():
                # writable copy: row writes keep the cache warm
                self._flat_cache = np.array(
                    self._gflat(self.stacked["params"])
                )
        return self._flat_cache[slot].copy(), self._unravel

    def set_member_flat(self, slot: int, flat: np.ndarray) -> None:
        if slot in self._host_state:
            self._host_state[slot]["params"] = self._unravel(
                jnp.asarray(flat)
            )
            return
        row = np.array(flat, np.float32, copy=True)
        self._pending_flat[slot] = row
        if self._flat_cache is not None:
            self._flat_cache[slot] = row

    def member_cum_loss(self, slot: int) -> float:
        st = self._host_state.get(slot)
        if st is not None:
            return float(st["cum_loss"])
        self.launch()
        return float(self.stacked["cum_loss"][slot])

    def predict_rows(self, entries: List[Tuple[int, np.ndarray]]) -> np.ndarray:
        """Gang forecast serving: one padded predict launch over the whole
        cohort. ``entries`` are ``(slot, padded [B, ...] batch)`` pairs —
        every batch the same shape, any number of rows (the per-record
        path passes one PREDICT_BATCH pad per slot; the serving plane
        passes multi-row queues, batching across stream positions AND
        tenants). The result indexes ``[slot, row]`` per participant.

        The ``[capacity, B, ...]`` staging pad is a persistent per-shape
        scratch (the dispatch copies host buffers to device before
        returning, so reuse is safe — same contract as the fit staging
        buffers); only previously-written slots re-zero."""
        self.launch()
        x0 = entries[0][1]
        shape = (self.capacity,) + x0.shape
        xs = self._pred_scratch.get(shape[1:])
        if xs is None or xs.shape != shape:
            xs = np.zeros(shape, np.float32)
            self._pred_scratch[shape[1:]] = xs
            self._pred_dirty.pop(shape[1:], None)
        else:
            for slot in self._pred_dirty.get(shape[1:], ()):
                xs[slot] = 0.0
        for slot, xb in entries:
            xs[slot] = xb
        self._pred_dirty[shape[1:]] = [slot for slot, _ in entries]
        self._note_launch(entries[0][0])
        with self._timed_serve():
            out = self._gpred(self.stacked, self._stage_dev(xs))
        return np.asarray(out)


class CohortEngine:
    """Per-spoke cohort manager: groups eligible pipelines by jit-cache key
    and forms cohorts per the configured mode/threshold."""

    def __init__(self, config, timer=None, serve_timer=None):
        mode = str(getattr(config, "cohort", "off")).lower()
        self.mode = mode if mode in ("auto", "on") else "off"
        self.min_members = (
            1 if self.mode == "on"
            else max(int(getattr(config, "cohort_min", 8)), 1)
        )
        impl = str(getattr(config, "cohort_impl", "auto")).lower()
        if impl == "auto":
            self.use_vmap = jax.default_backend() != "cpu"
        else:
            self.use_vmap = impl == "vmap"
        # tenant-axis device sharding (JobConfig.cohort_shards): resolved
        # once per engine; every cohort this engine forms shares the width
        self.n_shards = resolve_cohort_shards(config)
        self.timer = timer
        self.serve_timer = serve_timer
        self.cohorts: Dict[Any, Cohort] = {}
        self._pool: Dict[Any, List[Any]] = {}

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    @staticmethod
    def eligible(pipeline) -> bool:
        """Dense, device-side pipelines with float32 flat params gang;
        host-side (HT), SingleLearner-forced (the model lives on the hub,
        spoke replicas only serve) and sparse-COO learners keep the
        per-pipeline path."""
        from omldm_tpu.learners.registry import SINGLE_LEARNER_ONLY

        if pipeline.cache_key is None or pipeline.learner.host_side:
            return False
        if pipeline.learner.name in SINGLE_LEARNER_ONLY:
            return False
        if getattr(pipeline.learner, "sparse", False):
            return False
        if pipeline._cohort is not None:
            return False
        flat, _ = jax.flatten_util.ravel_pytree(pipeline._state["params"])
        return flat.dtype == jnp.float32

    def consider(self, pipeline) -> None:
        """Offer a (new) pipeline: joins its key's cohort, or pools until
        the auto threshold forms one."""
        if self.mode == "off" or not self.eligible(pipeline):
            return
        key = pipeline.cache_key
        cohort = self.cohorts.get(key)
        if cohort is not None:
            cohort.attach(pipeline)
            return
        pool = self._pool.setdefault(key, [])
        pool.append(pipeline)
        if len(pool) >= self.min_members:
            cohort = Cohort(
                pool[0], self.use_vmap, timer=self.timer,
                n_shards=self.n_shards, serve_timer=self.serve_timer,
            )
            for p in pool[1:]:
                cohort.attach(p)
            self.cohorts[key] = cohort
            del self._pool[key]

    def retire(self, pipeline) -> None:
        cohort = pipeline._cohort
        if cohort is not None:
            cohort.detach(pipeline)
            if cohort.n_active == 0:
                self.cohorts.pop(cohort.key, None)
            return
        pool = self._pool.get(getattr(pipeline, "cache_key", None))
        if pool and pipeline in pool:
            pool.remove(pipeline)

    def flush(self) -> None:
        """Gang barrier: launch every cohort's staged work."""
        for cohort in self.cohorts.values():
            cohort.launch()

    def detach_all(self) -> None:
        """Dissolve every cohort (rescale absorb, shutdown): members get
        their state back and run per-pipeline until re-considered."""
        for cohort in list(self.cohorts.values()):
            for member in list(cohort.members):
                if member is not None:
                    cohort.detach(member)
        self.cohorts.clear()
        self._pool.clear()


class GangAverager:
    """Deferred, vectorized model averaging for same-protocol cohort
    members' parameter-server shards.

    A hub whose round completes inside an active window stages its stacked
    ``[W, P]`` contribution matrix; at the window's exit every same-shape
    group averages in ONE ``[M, W, P]`` numpy reduction (bit-identical to
    the per-hub ``mean(axis=0)``) and the hubs broadcast their releases.
    Outside a window ``active`` is False and hubs average immediately — the
    exact pre-cohort behavior."""

    def __init__(self):
        self._depth = 0
        self._staged: List[Tuple[Any, np.ndarray]] = []

    @property
    def active(self) -> bool:
        return self._depth > 0

    @contextlib.contextmanager
    def window(self):
        self._depth += 1
        try:
            yield
        finally:
            self._depth -= 1
            if self._depth == 0:
                self.flush()

    def stage(self, hub_node, stacked: np.ndarray) -> None:
        self._staged.append((hub_node, stacked))

    def flush(self) -> None:
        # releases can complete further rounds synchronously (a released
        # worker drains, pushes, and closes the next round): loop until dry
        while self._staged:
            staged, self._staged = self._staged, []
            groups: Dict[Tuple[int, ...], List[Tuple[Any, np.ndarray]]] = {}
            for node, mat in staged:
                groups.setdefault(mat.shape, []).append((node, mat))
            for items in groups.values():
                if len(items) == 1:
                    node, mat = items[0]
                    node._finish_round(mat.mean(axis=0))
                    continue
                means = np.stack([m for _, m in items]).mean(axis=1)
                for (node, _), avg in zip(items, means):
                    node._finish_round(avg)
