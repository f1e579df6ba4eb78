"""Multi-host (DCN) support for the batched simulation.

The cluster batch shards over a mesh with no collectives inside the step
(batched/engine.py), so scaling past one host is purely a placement problem:
build the same compiled trace on every process, materialize each process's
addressable shards of the global arrays, and gather metric reductions across
processes at readout. The step program itself is unchanged — XLA runs it
SPMD per host, and the only DCN traffic is trace upload and metric readout
(the scalar analog of this "network" is the in-process event queue,
reference: src/config.rs:28-36; SURVEY.md §5.8).

Single-process meshes take the plain device_put path; these helpers are the
cross-process generalization (jax.make_array_from_callback for placement,
multihost_utils.process_allgather for readout) and degrade to the local
behavior when jax.process_count() == 1, which is how the test suite
exercises them.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from kubernetriks_tpu.sanitize import assert_sync_allowed


def initialize_from_env(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """jax.distributed.initialize with explicit args or the JAX_* /
    cloud-TPU environment autodetection; call once per process before any
    device op. Returns True if a multi-process runtime was initialized.
    Safe to call unconditionally: when no coordinator is configured or
    detectable (a plain single-process run), this is a no-op returning
    False, and a repeated call after the runtime (or backend) already
    started returns whether a multi-process runtime is active instead of
    surfacing jax's RuntimeError."""
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except ValueError:
        # jax raises when cluster autodetection finds no coordinator; that
        # IS the single-process case this helper promises to tolerate.
        return False
    except RuntimeError as e:
        # Tolerate ONLY the late-init case (XLA backend already started —
        # too late to go distributed, i.e. a plain single-process run).
        # Genuine distributed-init failures (coordinator unreachable, ...)
        # also surface as RuntimeError subclasses and must stay loud.
        if "must be called before" in str(e) or "already initialized" in str(e):
            return False
        raise
    return True


def global_mesh(axis_name: str = "clusters") -> Mesh:
    """1-D mesh over every device of every process (DP over the cluster
    batch; pass to BatchedSimulation(mesh=...))."""
    return Mesh(np.array(jax.devices()), (axis_name,))


def is_cross_process(mesh: Mesh) -> bool:
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def put_global(tree, shardings):
    """Place a host-built pytree onto (possibly cross-process) shardings.

    Every process holds the full host copy (the compiled trace is
    deterministic, so all processes build identical arrays) and contributes
    the shards it can address; jax.make_array_from_callback assembles the
    global jax.Arrays. Equivalent to jax.device_put on a single process."""

    def put(leaf, sharding):
        host = np.asarray(leaf)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx]
        )

    return jax.tree.map(put, tree, shardings)


def to_host(x) -> np.ndarray:
    """Global host copy of a (possibly cross-process sharded) array: plain
    np.asarray when this process addresses all shards, otherwise an
    allgather over DCN.

    THE framework's device-to-host choke point: under KTPU_SANITIZE an
    unwaived call inside the sanitized dispatch region raises (jax's
    transfer guard never fires on the CPU backend, so the sanitizer
    carries its own net here)."""
    assert_sync_allowed("to_host")
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))
