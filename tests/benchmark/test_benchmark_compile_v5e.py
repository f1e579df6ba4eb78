"""Compile-only guards for the described `v5e:2x2` topology: the kernels of the
benchmark's cells at their real widths, compiled by the TPU's compiler for a
chip that is described and not attached. Nothing runs; a pass is not a chip run.

The topology is described inside a fixture (never at import), and all such
tests live in this one file: only one worker may load the TPU's library.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in specs]


def _compile(fn, args, **static):
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "the kernel is not in the compiled program"
    return compiled


# (clusters, nodes, pod slots, K) as the cells' `setup` lines report them on
# the chip (PR 25): sched1k.montecarlo's Poisson traces pad to 2,176 slots; the
# autoscaled cells carry 100 base nodes + 200 CA slots, and a pod axis of the
# 2,048-slot window (stream) or a query's whole 450 s trace (whatif) plus the
# HPA group's resident slots.
@pytest.mark.parametrize(
    "clusters,nodes,pods,k",
    [(1250, 1000, 2176, 64), (256, 300, 2456, 256), (128, 300, 2688, 256)],
    ids=["sched1k.montecarlo", "autoscaled.stream", "autoscaled.whatif"],
)
def test_megakernel_compiles_at_cell_width(one_chip, clusters, nodes, pods, k):
    from kubernetriks_tpu.ops.scheduler_kernel import (
        fused_select_cycle_commit,
        select_commit_kernel_fits,
    )

    assert select_commit_kernel_fits(nodes, pods, k)
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    node = ((nodes, clusters), i32)  # lane-major, as one chip carries them
    pod_i, pod_f = ((clusters, pods), i32), ((clusters, pods), f32)
    cand = ((clusters, k), f32)
    args = _shapes(
        one_chip,
        ((nodes, clusters), b), node, node,
        ((clusters, pods), b), pod_i, pod_f, pod_i, pod_i, pod_i, pod_f, pod_i, pod_i,
        cand, cand, cand,
    )
    _compile(fused_select_cycle_commit, args, k_pods=k, nodes_lane_major=True)


@pytest.mark.parametrize("clusters", [256, 128], ids=["autoscaled.stream", "autoscaled.whatif"])
def test_ca_kernels_compile_at_autoscaled_width(one_chip, clusters):
    from kubernetriks_tpu.ops.autoscale_kernel import (
        ca_down_kernel_fits,
        ca_up_kernel_fits,
        fused_ca_scale_down,
        fused_ca_scale_up,
    )

    nodes, slots, groups, k_sd, k_up = 300, 200, 1, 8, 64
    assert ca_down_kernel_fits(nodes, slots, k_sd) and ca_up_kernel_fits(slots, groups, k_up)
    i32, f32 = jnp.int32, jnp.float32
    cn, cs, csk = ((clusters, nodes), i32), ((clusters, slots), i32), ((clusters, slots * k_sd), i32)
    down = _shapes(
        one_chip, ((clusters, 1), i32), ((clusters, 1), f32),
        cn, cn, cn, cn, cn, cn, cn, cs, cs, cs, csk, csk, csk,
    )
    _compile(fused_ca_scale_down, down, k_sd=k_sd)
    cg, ck = ((clusters, groups), i32), ((clusters, k_up), i32)
    up = _shapes(one_chip, ((clusters, 1), i32), cg, cg, cg, cg, cg, cg, cg, ck, ck, ck)
    _compile(fused_ca_scale_up, up, n_slots=slots)
