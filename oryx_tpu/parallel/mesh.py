"""Device mesh helpers.

The reference scales out through Spark executors on YARN
(framework/oryx-lambda/.../AbstractSparkLayer.java:137-168 builds the
streaming context whose tasks fan out over the cluster).  The TPU-native
analog is a jax.sharding.Mesh over the chips of a slice: data-parallel
rows of the factor matrices ride the "d" axis, and cross-device
communication is XLA collectives over ICI instead of Spark shuffle.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["build_mesh", "local_mesh", "initialize_multihost"]


def initialize_multihost(config=None) -> bool:
    """Join this process to a multi-host JAX cluster, if configured.

    The reference scales across machines with Spark executors over YARN
    plus NCCL-free shuffle; the TPU-native equivalent is
    ``jax.distributed`` — after initialization ``jax.devices()`` spans
    every host's chips (ICI within a slice, DCN across slices), and the
    SAME 1-D mesh + shard_map training code runs unchanged at multi-host
    scale because it only ever names mesh axes, never hosts.

    Config keys (all optional — on Cloud TPU the runtime supplies them
    and a bare ``jax.distributed.initialize()`` suffices):
      oryx.distributed.coordinator-address   host:port of process 0
      oryx.distributed.num-processes
      oryx.distributed.process-id

    Returns True when distributed mode was initialized.  Safe to call
    when unconfigured (no-op) or already initialized.
    """
    coord = num = pid = None
    if config is not None:
        coord = config.get_optional_string(
            "oryx.distributed.coordinator-address")
        if config.has_path("oryx.distributed.num-processes"):
            num = config.get_int("oryx.distributed.num-processes")
        if config.has_path("oryx.distributed.process-id"):
            pid = config.get_int("oryx.distributed.process-id")
    if coord is None and num is None and pid is None:
        return False
    if jax.distributed.is_initialized():  # already joined — idempotent
        return True
    # a genuine join failure (unreachable coordinator, bad params) must
    # propagate: silently training single-host when multi-host was
    # configured would be the worst failure mode
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=num, process_id=pid)
    return True


def build_mesh(n_devices: int | None = None, axis: str = "d") -> Mesh:
    """A 1-D mesh over the first ``n_devices`` visible devices (all, if
    None).  One axis is the right shape for ALS: both factor matrices are
    row-sharded over it and the opposite factor is all-gathered per
    half-sweep, so a single axis carries all collective traffic."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices but only {len(devs)} visible")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def local_mesh(axis: str = "d") -> Mesh:
    """Mesh over every device JAX can see (single-host: all local chips)."""
    return build_mesh(None, axis)


def mesh_from_config(config, axis: str = "d") -> Mesh | None:
    """The batch layer's training mesh, or None for single-device.

    ``oryx.batch.streaming.num-executors x executor-cores`` is the
    requested total device count (the reference's executor sizing,
    reference.conf:146-150 / oryx-run.sh:160-231, re-read as chips);
    the mesh shrinks to the devices actually present.
    """
    master = config.get_string("oryx.batch.streaming.master")
    if master == "cpu":
        return None
    # multi-host: join the cluster BEFORE the first jax.devices() call
    # so the mesh spans every host's chips
    initialize_multihost(config)
    if jax.default_backend() == "cpu" and master != "mesh":
        # "auto" on a CPU backend: virtual host devices exist only for
        # sharding tests; single-device XLA is faster for real work.
        # master = "mesh" forces a mesh over them (tests, dry runs).
        return None
    if jax.process_count() > 1:
        # multi-host: every process's local devices MUST be in the mesh
        # (a truncated mesh would exclude some hosts' chips and deadlock
        # their shard_map dispatches at the first collective), so the
        # executor sizing is advisory only here
        return build_mesh(None, axis)
    requested = (config.get_int("oryx.batch.streaming.num-executors")
                 * config.get_int("oryx.batch.streaming.executor-cores"))
    n = min(requested, len(jax.devices()))
    if n <= 1:
        return None
    return build_mesh(n, axis)
