"""Device mesh construction and sharding helpers.

The communication tier of the framework.  The reference has two transport
stacks — Spark TCP broadcast/reduce between nodes (reference:
src/main/scala/apps/ImageNetApp.scala:102,178) and a CUDA P2P tree within a
node (reference: caffe/src/caffe/parallel.cpp:271-360) — and no
NCCL/MPI/Gloo anywhere (SURVEY.md §2.5).  Here both collapse into XLA
collectives over a ``jax.sharding.Mesh``: ``psum``/``pmean`` ride ICI within
a slice and DCN across slices, chosen by the compiler from the mesh
topology.  Multi-host extends the same mesh via the JAX distributed runtime
(``sparknet_tpu.parallel.cluster.init_cluster``).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
MODEL_AXIS = "model"
HOST_AXIS = "host"   # the slow tier: DCN / cross-host (τ-averaging)
CHIP_AXIS = "chip"   # the fast tier: ICI within a host (per-step psum)


def make_mesh(n_devices: int | None = None, *, model_parallel: int = 1,
              devices=None) -> Mesh:
    """A (data, model) mesh over the available devices.

    ``model_parallel=1`` (the parity default — the reference has no model
    parallelism, SURVEY.md §2.4) yields a pure data-parallel mesh; larger
    values carve an inner model axis for tensor-parallel shardings laid out
    on adjacent devices so its collectives ride the fastest ICI links.
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"mesh of {n_devices} devices asked for, have {len(devs)}")
        devs = devs[:n_devices]
    n = len(devs)
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by mp={model_parallel}")
    arr = np.asarray(devs).reshape(n // model_parallel, model_parallel)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def make_pod_mesh(n_hosts: int | None = None, chips_per_host: int | None = None,
                  *, devices=None) -> Mesh:
    """A (host, chip) mesh — the deployment topology SparkNet's two DP
    tiers compose onto: per-step gradient psum over the ``chip`` axis
    (ICI within a host — the reference's intra-node P2PSync,
    caffe/src/caffe/parallel.cpp:271-360) × τ-step weight averaging over
    the ``host`` axis (DCN across hosts — the reference's Spark
    driver rounds, ImageNetApp.scala:100-182).  Device order follows
    ``jax.devices()``, which groups each process's local devices
    contiguously — so on a real multi-host pod rows of the mesh ARE
    hosts and the chip-axis collectives ride ICI."""
    devs = list(devices if devices is not None else jax.devices())
    if n_hosts is None:
        n_hosts = max(jax.process_count(), 1)
    if n_hosts < 1:
        raise ValueError(f"pod mesh needs n_hosts >= 1, got {n_hosts}")
    if chips_per_host is None:
        chips_per_host = len(devs) // n_hosts
    if chips_per_host < 1:
        raise ValueError(
            f"pod mesh needs chips_per_host >= 1, got {chips_per_host}")
    need = n_hosts * chips_per_host
    if need > len(devs):
        raise ValueError(
            f"pod mesh {n_hosts}x{chips_per_host} needs {need} devices, "
            f"have {len(devs)}")
    arr = np.asarray(devs[:need]).reshape(n_hosts, chips_per_host)
    return Mesh(arr, (HOST_AXIS, CHIP_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharded(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading (batch) axis across the data axis."""
    return NamedSharding(mesh, PartitionSpec(DATA_AXIS, *([None] * (ndim - 1))))


def put_global(value, sharding: NamedSharding) -> jax.Array:
    """Build a global array from a host value every process holds in full
    (weights, solver state).  Works on single-host meshes AND multi-host
    meshes with non-addressable devices — the replacement for the
    reference's ship-the-model-by-classloader replication (reference:
    CifarApp.scala:23-29; SURVEY.md §7.3 'per-host model replication must
    be explicit')."""
    value = np.asarray(value)
    return jax.make_array_from_callback(
        value.shape, sharding, lambda idx: value[idx])


def put_global_tree(tree, sharding):
    """Place a host pytree on the mesh.  ``sharding`` is either a single
    NamedSharding applied to every leaf (the replicated classic) or a
    matching pytree of NamedShardings — the hybrid-sharding path, where
    each parameter leaf carries its own placement from the partition
    rule table (``parallel/partition.py``)."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(lambda x: put_global(x, sharding), tree)
    return jax.tree_util.tree_map(put_global, tree, sharding)


def stage_local(local_value, sharding: NamedSharding) -> jax.Array:
    """Assemble a global array from *per-process* local rows — the data
    path: each host contributes only its own partition slice of the batch
    (the zipPartitions placement of the reference, ImageNetApp.scala:145),
    and no host ever materializes the global batch."""
    if jax.process_count() == 1:
        return jax.device_put(local_value, sharding)
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_value))
