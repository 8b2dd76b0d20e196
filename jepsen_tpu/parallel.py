"""Device-mesh and multi-host helpers: the distributed data plane.

The reference's distributed backends are SSH (control plane,
jepsen/src/jepsen/control.clj) plus JVM threads (workers,
core.clj:219-265). This rebuild keeps the SSH control plane
(jepsen_tpu.control) and adds a second, accelerator-native axis the
reference never had: histories bit-packed to integer columns and
checked as ONE tensor program split over a `jax.sharding.Mesh`
(checker/tpu.py::check_keyed_tpu).

P-compositional checking is embarrassingly data-parallel over the
independent-key axis, and the program says so: each crash-width cohort
of keys is split over the mesh's ``keys`` axis as evenly as it goes,
each device's share padded with trivially complete rows to a multiple
of 4 keys (so cohorts fall on few executable shapes), and placed with
`NamedSharding(mesh, P("keys"))`, and the vmapped search runs under
`jax.shard_map`, so each device runs its own while-loop over its own
keys and stops at its own slowest key. No collective runs inside a
level; the per-key verdict vectors come back to the host once a launch
ends (gathered across processes on a multi-host mesh). Multi-host:
every process contributes its local devices via
`jax.distributed.initialize`; the same program runs SPMD on each host
(certified by the two-process DCN dryrun, __graft_entry__.dryrun_dcn).

A second, orthogonal axis exists for single searches: pool sharding
(`checker.tpu.check_packed_sharded`) partitions ONE search's frontier
pool over the mesh so the devices cooperate on one history — the
sequence-parallel analog, for ultra-wide histories whose per-level
expansion dwarfs one chip.

Deliberately dependency-light: importing this module does not import
jax; every function resolves it lazily so the pure-CPU paths (native
engine, Python checkers, suites) never pay for it.
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence

#: The canonical mesh axis for independent-key data parallelism.
KEYS_AXIS = "keys"


def device_count() -> int:
    import jax
    return len(jax.devices())


def make_mesh(n_devices: Optional[int] = None, axis: str = KEYS_AXIS,
              devices: Optional[Sequence[Any]] = None):
    """A 1-D mesh over ``n_devices`` (default: all) devices.

    The single ``keys`` axis is the right topology for checking:
    per-key searches never communicate, so any higher-dimensional
    arrangement only constrains XLA for no benefit."""
    import jax
    import numpy as np
    devs = list(devices) if devices is not None else list(jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"asked for {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.array(devs), (axis,))


def keyed_sharding(mesh, axis: str = KEYS_AXIS):
    """NamedSharding placing the leading (key-batch) dim across the
    mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> bool:
    """Join this process into a multi-host JAX cluster
    (jax.distributed.initialize) so `jax.devices()` spans every host and
    meshes built here shard over DCN+ICI.

    All-None arguments use JAX's environment autodetection (TPU pods
    populate it from the metadata server). Returns True when
    initialization happened, False when it was skipped (already
    initialized, or single-process with no coordinator configured) —
    callers treat False as 'single host, proceed locally'."""
    import jax
    if getattr(initialize_multihost, "_done", False):
        return False
    auto = coordinator_address is None
    if auto and "JAX_COORDINATOR_ADDRESS" not in os.environ:
        # Note TPU_WORKER_HOSTNAMES alone is NOT enough: single-host TPU
        # attachments set it too, and initialize() would then demand a
        # coordinator. Only an explicit coordinator opts in.
        return False
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    try:
        jax.distributed.initialize(**kwargs)
    except (RuntimeError, ValueError):
        if not auto:
            raise
        return False  # mis-set env in a single-process run: proceed local
    initialize_multihost._done = True
    return True


def check_keyed_distributed(keyed, model, n_devices: Optional[int] = None,
                            **kwargs):
    """Keyed device checking over an automatically built mesh — the
    one-call distributed entry point: initialize multi-host if the
    environment is configured for it, build the keys mesh over every
    visible device, fan the batch out.

    kwargs pass through to checker.tpu.check_keyed_tpu."""
    from jepsen_tpu.checker.tpu import check_keyed_tpu
    initialize_multihost()
    mesh = make_mesh(n_devices)
    return check_keyed_tpu(keyed, model, mesh=mesh, **kwargs)
