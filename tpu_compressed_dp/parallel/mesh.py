"""Device mesh construction and multi-host rendezvous.

TPU-native replacement for the reference's process-group machinery:
``dist.init_process_group('gloo'|'nccl', ...)`` (`CIFAR10/core.py:334`,
`IMAGENET/training/train_imagenet_nv.py:161-162`) and the NCCL ring-order
tuning strings (`IMAGENET/train.py:159-203`).  On TPU there is no user-level
ring configuration: we build a `jax.sharding.Mesh` and let XLA route
collectives over ICI/DCN; the mesh axis layout *is* the tuning surface.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_compressed_dp.obs.trace import install_host_events

__all__ = [
    "distributed_init",
    "setup_compile_cache",
    "make_data_mesh",
    "make_mesh",
    "data_sharding",
    "replicated_sharding",
    "world_size",
    "force_host_devices",
    "make_global_batch",
]

DATA_AXIS = "data"


def distributed_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host rendezvous.

    Equivalent of the reference's ``env://`` NCCL rendezvous driven by
    ``MASTER_ADDR``/``RANK``/``WORLD_SIZE`` (`train_imagenet_nv.py:64-66`,
    `dist_utils.py:27-28`).  On Cloud TPU the arguments are auto-detected; on
    other platforms they map 1:1 onto the reference's flags
    (``--master_address``, ``--world_size``, ``--rank``, `dawn.py:11-13`).
    No-ops when running single-process.
    """
    if num_processes is not None and num_processes <= 1:
        return
    if coordinator_address is None and num_processes is None and "COORDINATOR_ADDRESS" not in os.environ:
        # Single-process (possibly multi-chip) run: nothing to rendezvous.
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def force_host_devices(n: int, env: Optional[dict] = None) -> dict:
    """Emulate an ``n``-chip mesh on CPU (the JAX-native multi-device fake).

    Must run before the first JAX backend initialisation.  This is the test
    fixture the reference lacked (SURVEY.md §4): its closest analog was N
    Gloo processes on one machine.  Replaces (never appends alongside) any
    inherited device-count flag — duplicated XLA flags are an error.
    Mutates and returns ``env`` (default ``os.environ``) so spawn sites can
    use it on a copied environment.
    """
    if env is None:
        env = os.environ
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; every entry point calls
    this first, before anything compiles.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    cache option is set in code.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``, derived from the package location: the
    directory is part of the cache key, so it is never a temporary, pid- or
    time-derived name.  Returns the directory in use.

    Being first, it is also where the host events are switched on
    (``obs.trace.install_host_events``): every trace, lowering, compile and
    cache answer from here on, and every collector pass, is on the step
    timeline's clock with no edit of an entry point.
    """
    install_host_events()
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_data_mesh(num_devices: Optional[int] = None, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D ``('data',)`` mesh — the data-parallel world.

    The reference's world is the flat rank set of the process group; here it is
    a named mesh axis so the compression layer can later compose with model
    axes (tensor/pipeline/sequence) without rework (SURVEY.md §2.2).
    """
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested a {num_devices}-device mesh but only "
                f"{len(devices)} devices are available"
            )
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def make_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """General N-D mesh for composed parallelism (dp x tp x pp x sp ...)."""
    n = int(np.prod(axis_sizes))
    available = jax.devices() if devices is None else list(devices)
    if n > len(available):
        raise ValueError(
            f"mesh {tuple(axis_sizes)} needs {n} devices but only "
            f"{len(available)} are available"
        )
    devices = np.asarray(available[:n]).reshape(tuple(axis_sizes))
    return Mesh(devices, tuple(axis_names))


def world_size(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    return mesh.shape[axis]


def data_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Batch-dimension sharding over the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def make_global_batch(batch: dict, mesh: Mesh, axis: str = DATA_AXIS) -> dict:
    """Assemble per-process local batches into global sharded arrays.

    The multi-host equivalent of the reference's ``DistributedSampler``
    hand-off (`dataloader.py:33`): each process holds its own slice of the
    global batch; under SPMD the jitted step wants one global ``jax.Array``
    whose shards live where the local data already is.  Identity when
    single-process (the local batch *is* the global batch).
    """
    if jax.process_count() == 1:
        return batch
    sharding = NamedSharding(mesh, P(axis))
    out = {}
    for k, v in batch.items():
        v = np.asarray(v)
        global_shape = (v.shape[0] * jax.process_count(),) + v.shape[1:]
        out[k] = jax.make_array_from_process_local_data(sharding, v, global_shape)
    return out
