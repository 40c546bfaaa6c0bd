"""Multi-host distributed initialization for the crypto batch plane.

Reference behavior being replaced: the NCCL/MPI-style scale-out story — the
reference's pool spans hosts via per-node ZMQ processes; here the DEVICE
side additionally spans hosts via JAX's distributed runtime: every host
runs the same SPMD crypto-plane program over one global mesh, with XLA
placing the collectives (all_gather of Merkle subtree roots, psum of
verdict counts) on ICI within a slice and DCN across slices (the
scaling-book recipe: pick a mesh, annotate shardings, let XLA insert the
collectives).

Usage (one call per host process, before any other JAX API):

    from plenum_tpu.parallel.multihost import init_multihost, global_mesh
    init_multihost(coordinator="10.0.0.1:8476",
                   num_processes=4, process_id=HOST_RANK)
    mesh = global_mesh()                  # spans ALL hosts' devices
    plane = ShardedCryptoPlane(mesh)      # same code as single-host

Host-side inputs must be globally sharded arrays
(jax.make_array_from_process_local_data) — helpers below wrap that. The
suite exercises this end-to-end with TWO real OS processes joining one
distributed job over a localhost coordinator (gloo collectives on the
CPU backend, 4 virtual devices per process -> one 8-device global mesh):
tests/test_multihost.py.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import mesh_shape_for

_initialized = False


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> None:
    """Join (or bootstrap) the distributed runtime. Idempotent. With no
    arguments on a single host this is a no-op that marks the process
    initialized (jax.distributed requires no setup for one process)."""
    global _initialized
    if _initialized:
        return
    if coordinator is not None:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _initialized = True

# NOTE on lanes vs the global mesh: the multi-device pipeline's lanes
# are per-chip dispatch streams and must be able to device_put from
# this process, so a multi-host job runs one N-lane pipeline PER HOST
# over `parallel.mesh.lane_roster()` (local devices only), while the
# SPMD plane (ShardedCryptoPlane over `global_mesh()`) remains the
# one-program-spans-all-hosts story. The THIRD cross-host shape is the
# federated pipeline (parallel/federation.py): remote crypto-service
# hosts rostered below join THIS host's ring as extra lanes — rented
# verification capacity over the service wire rather than one SPMD
# program — with work-stealing between backlogged lanes.


def crypto_host_roster(config=None,
                       hosts: Optional[str] = None) -> list[str]:
    """Remote crypto-host roster for the federated pipeline: the
    comma-separated crypto_service socket paths of rostered hosts
    (config.PIPELINE_REMOTE_HOSTS, or an explicit override string).
    Empty roster -> empty list -> the single-host classes construct
    exactly (the federation gate in pipeline.make_crypto_pipeline)."""
    raw = hosts if hosts is not None else str(
        getattr(config, "PIPELINE_REMOTE_HOSTS", "") or "")
    return [h.strip() for h in raw.split(",") if h.strip()]


def global_mesh(n_devices: Optional[int] = None) -> Mesh:
    """("inst", "sig") mesh over EVERY device in the job (all hosts)."""
    devs = jax.devices()                    # global list under jax.distributed
    if n_devices is not None:
        devs = devs[:n_devices]
    inst, sig = mesh_shape_for(len(devs))
    return Mesh(np.array(devs).reshape(inst, sig), ("inst", "sig"))


def shard_host_batch(mesh: Mesh, arr: np.ndarray,
                     spec: P) -> jax.Array:
    """Build a GLOBAL device array from this host's local slice of the
    batch. On one host this is a plain device put with the sharding; on
    many hosts each process contributes its devices' shards."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, arr)
