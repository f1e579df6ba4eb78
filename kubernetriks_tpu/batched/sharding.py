"""The one boundary at which a mesh build is sharded.

Clusters are independent, so a build over `Mesh(devices, ("clusters",))` is
pure data parallelism: under a mesh every window program is wrapped ONCE, at
its jit entry, in `jax.shard_map` over the cluster axis (`over_clusters`).
Inside, each device traces exactly the program a one-chip build of its shard
traces — `C` is the local width, `arange(C)` is local, the Pallas kernels are
called directly, the hot node leaves go lane-major by the same tristate — and
GSPMD never partitions the window body (it does not see that
`x.at[arange(C)[:, None], idx]` touches the shard's own rows only, and
all-gathers the operands).

In and out specs come from the state's own rule (`cluster_specs`, which also
places the state, slab, stages and statics at build): a leaf leads with the
cluster axis, a scalar is replicated. The few values that really are global
(the slide's shift, the superspan's capacity read, the fast-forward's next
due window) get a collective by name through `all_min`; the cluster index a
PRNG draw keys on comes from `cluster_ids`. The `.any()` gates of the body's
`lax.cond`s stay local: each is conservative, so a shard that skips what
another runs changes no leaf, and no collective sits inside such a branch.

Without a mesh (`shards=None`) nothing here is applied: the entry calls its
body, the helpers reduce plainly, and a one-chip build traces the jaxpr it
always traced.
"""

from __future__ import annotations

import functools
import inspect
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec


class ClusterShards(NamedTuple):
    """A build's mesh and the name of the axis its clusters are split over
    (hashable: a jit static of every window-program entry)."""

    mesh: Mesh
    axis: str


def shard_axis_of(shards: Optional[ClusterShards]) -> Optional[str]:
    """The axis name the in-program collectives reduce over; None = no mesh."""
    return None if shards is None else shards.axis


def cluster_specs(tree, axis: str):
    """The sharding rule of every per-cluster pytree (state, slab, stage,
    statics, constants): a leaf leads with the cluster axis, a scalar is
    replicated."""
    return jax.tree.map(
        lambda leaf: PartitionSpec() if jnp.ndim(leaf) == 0 else PartitionSpec(axis),
        tree,
    )


def all_min(x, shard_axis: Optional[str]):
    """min over every cluster of the build: the plain reduction on one
    device, the shard's min `pmin`ned over the mesh inside a window
    program's shard_map. Call it only where every shard takes the same
    branch."""
    m = jnp.min(x)
    return m if shard_axis is None else jax.lax.pmin(m, shard_axis)


def cluster_ids(n_local: int, shard_axis: Optional[str]):
    """(n_local,) int32 GLOBAL cluster indices of this shard's rows (the
    local arange without a mesh). Row gathers and scatters index locally;
    only a value that must not depend on the sharding (a PRNG key) reads
    this."""
    ids = jnp.arange(n_local, dtype=jnp.int32)
    if shard_axis is None:
        return ids
    return ids + jax.lax.axis_index(shard_axis).astype(jnp.int32) * jnp.int32(n_local)


def over_clusters(
    statics: Sequence[str],
    out_specs: Callable,
    replicated: Sequence[str] = (),
):
    """Decorator of a window program's jit body. With `shards=None` the body
    runs as it is; with a ClusterShards it runs under one shard_map over the
    cluster axis: arguments named in `statics` are closed over, those in
    `replicated` (window indices, the progress vector) enter whole, every
    other leaf by `cluster_specs`. `out_specs(axis, statics_dict)` gives
    the outputs' specs (a prefix tree)."""

    def wrap(impl):
        sig = inspect.signature(impl)

        @functools.wraps(impl)
        def entry(*args, **kwargs):
            bound = sig.bind(*args, **kwargs)
            bound.apply_defaults()
            shards = bound.arguments["shards"]
            if shards is None:
                return impl(*args, **kwargs)
            static = {k: v for k, v in bound.arguments.items() if k in statics}
            dynamic = {k: v for k, v in bound.arguments.items() if k not in statics}
            in_specs = {
                k: PartitionSpec() if k in replicated else cluster_specs(v, shards.axis)
                for k, v in dynamic.items()
            }
            return jax.shard_map(
                lambda dyn: impl(**dyn, **static),
                mesh=shards.mesh,
                in_specs=(in_specs,),
                out_specs=out_specs(shards.axis, static),
                check_vma=False,
            )(dynamic)

        return entry

    return wrap
