"""Device mesh construction and sharding helpers.

Replaces the reference's entire network layer
(/root/reference/src/network/: Linkers socket/MPI mesh construction,
BruckMap/RecursiveHalvingMap topologies, network.cpp collectives): on TPU
there is no linker handshake — the mesh IS the topology, and XLA emits
the collectives (SURVEY.md §2.6 TPU mapping). Multi-host is reached via
``jax.distributed.initialize`` + the same mesh spanning all processes.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "shard_rows", "replicate", "DATA_AXIS",
           "pad_rows"]

DATA_AXIS = "data"


def make_mesh(num_devices: int = 0, axis_name: str = DATA_AXIS,
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D data-parallel mesh over available devices.

    The reference analog is Network::Init (rank/num_machines from the
    socket or MPI world); here the 'world' is jax.devices() — spanning
    hosts automatically under jax.distributed.
    """
    if devices is None:
        devices = jax.devices()
        if jax.process_count() > 1 and jax.default_backend() == "cpu":
            # CPU-test plumbing: in a multi-process CPU world
            # (parallel/hostsync.py picks the kv transport there) every
            # process runs the identical replicated program over its
            # OWN local devices, and the cross-rank surface is exactly
            # the host-level sync points. This dates from jaxlib 0.4.x,
            # whose CPU backend refused multiprocess computations; the
            # installed 0.9.0 runs them over gloo (a 2-process jitted
            # sum was checked), so the branch is a choice the CPU tests
            # were written against, no longer a backend limit.
            devices = jax.local_devices()
    if num_devices and num_devices > 0:
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def pad_rows(n: int, num_devices: int) -> int:
    """Rows of padding needed so every device holds an equal shard."""
    return (-n) % num_devices


def shard_rows(mesh: Mesh, arr, row_axis: int = 0):
    """Place an array with rows sharded over the mesh's data axis."""
    spec = [None] * arr.ndim
    spec[row_axis] = mesh.axis_names[0]
    sharding = NamedSharding(mesh, P(*spec))
    return jax.device_put(arr, sharding)


def replicate(mesh: Mesh, arr):
    return jax.device_put(arr, NamedSharding(mesh, P()))
