"""Multi-host (DCN + ICI) distributed backend.

The reference's distributed fabric is Flink network shuffles + a Kafka
feedback edge (SURVEY.md section 5 "distributed communication backend").
The TPU-native equivalent is jax.distributed + XLA collectives: one Python
process per host joins a coordinator, `jax.devices()` becomes the GLOBAL
device list, and collectives ride ICI within a pod slice and DCN across
slices. This module packages the three pieces every multi-host deployment
needs:

- :func:`initialize_multihost` — join/initialize the process group
  (env-driven on Cloud TPU; explicit coordinator for manual clusters).
- :func:`make_multihost_mesh` — a DCN-aware mesh: the data-parallel axis
  spans hosts over DCN (protocols tolerate its latency — syncs are
  periodic), while sp/tp/hub axes stay inside a host's ICI domain where
  per-block collectives are cheap. Uses
  ``mesh_utils.create_hybrid_device_mesh`` when more than one ICI domain
  is present.
- :func:`host_local_array` — build a globally-sharded array from each
  host's LOCAL ingest partition (``jax.make_array_from_process_local_data``),
  the multi-host form of the reference's per-subtask Kafka partitions.

Single-process (tests, one chip) every function degrades to the local
behavior, so the same training script runs anywhere.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    connect_attempts: int = 3,
    connect_timeout_s: Optional[float] = None,
) -> Tuple[int, int]:
    """Join the jax.distributed process group; returns (process_id,
    process_count). Call FIRST, before anything that initializes the XLA
    backend (device queries, array ops) — jax.distributed.initialize
    requires it.

    With explicit args the process group is joined directly (manual
    clusters); with no args JAX's own auto-detection runs (Cloud TPU
    metadata, Slurm, Open MPI) and a failed detection falls back to
    single-process (0, 1) — so the same call is safe on a laptop.

    The explicit join retries under the shared backoff helper
    (``connect_attempts`` tries; ``connect_timeout_s`` bounds the whole
    join) — a worker relaunched by the supervisor a beat before its peers
    must not die just because the coordinator port is not up yet."""
    if coordinator_address is not None or num_processes is not None:
        from omldm_tpu.utils.backoff import with_backoff

        kwargs = {}
        if connect_timeout_s is not None:
            # the overall deadline bounds the whole join; each ATTEMPT gets
            # its share, else the first attempt eats the budget and the
            # advertised retries can never run
            kwargs["initialization_timeout"] = max(
                int(connect_timeout_s / max(connect_attempts, 1)), 1
            )
        with_backoff(
            lambda: jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **kwargs,
            ),
            attempts=connect_attempts,
            base_delay=0.5,
            growth=2.0,
            jitter=0.25,
            timeout=connect_timeout_s,
            retry_on=(RuntimeError,),
        )
        return jax.process_index(), jax.process_count()
    try:
        jax.distributed.initialize()  # cluster auto-detection
    except (ValueError, RuntimeError, OSError):
        # no cluster found (ValueError); the backend was already
        # initialized, e.g. a single-host run that did jax work first
        # (RuntimeError); or a TPU host whose metadata server cannot be
        # reached, so the Cloud TPU detection's request fails (OSError,
        # seen on a sealed one-host v5e machine): report what exists
        pass
    return jax.process_index(), jax.process_count()


def _num_slices(devices) -> int:
    """Number of ICI domains (pod slices) among ``devices`` — the DCN
    granule create_hybrid_device_mesh partitions by. A slice may span
    several hosts (e.g. a v4-32 is 4 processes but ONE ICI domain)."""
    return len({getattr(d, "slice_index", 0) for d in devices})


def make_multihost_mesh(
    axis_names: Sequence[str] = ("dp", "sp", "tp"),
    ici_shape: Optional[Sequence[int]] = None,
    dcn_axis: str = "dp",
    devices=None,
) -> Mesh:
    """DCN-aware mesh over all global devices.

    ``ici_shape`` gives the per-ICI-domain (per pod slice) extent of each
    axis; the ``dcn_axis`` is additionally multiplied across the slice
    count. Within one slice (however many hosts it spans) this is an
    ordinary contiguous mesh of shape ici_shape over all its devices.

    Example on 4 slices x 8 chips, axis_names=("dp","sp","tp"),
    ici_shape=(1, 4, 2): global mesh (4, 4, 2) — dp spans slices over DCN
    (periodic protocol syncs tolerate its latency), sp/tp stay inside each
    slice's ICI domain where per-block collectives are cheap."""
    devices = list(devices if devices is not None else jax.devices())
    n_slices = _num_slices(devices)
    per_slice = len(devices) // n_slices
    if ici_shape is None:
        # default: everything on the dcn/data axis within the slice too
        ici_shape = [1] * len(axis_names)
        ici_shape[list(axis_names).index(dcn_axis)] = per_slice
    ici_shape = list(ici_shape)
    if int(np.prod(ici_shape)) != per_slice:
        raise ValueError(
            f"ici_shape {tuple(ici_shape)} must multiply to the per-slice "
            f"device count {per_slice}"
        )
    if n_slices == 1:
        grid = np.asarray(devices).reshape(ici_shape)
        return Mesh(grid, tuple(axis_names))
    dcn_shape = [1] * len(axis_names)
    dcn_shape[list(axis_names).index(dcn_axis)] = n_slices
    grid = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=devices
    )
    return Mesh(grid, tuple(axis_names))


def host_local_array(
    local_data: np.ndarray,
    mesh: Mesh,
    spec: P,
) -> jax.Array:
    """Assemble a globally-sharded array from this host's local partition.

    Each process passes only ITS slice of the global batch (its ingest
    partition); the result is one logical array sharded per ``spec`` whose
    global leading dim is the concatenation over processes. Single-process
    this is just ``device_put`` with the sharding."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(local_data, sharding)
    return jax.make_array_from_process_local_data(sharding, local_data)
