"""Compile-only guard for `sched1k-kubescore.montecarlo`'s megakernel on the
described `v5e:2x2` topology, as `test_benchmark_compile_v5e.py` guards the
accepted cells' kernels (that file is the yardstick's and not a `model_config`
PR's to edit, so this launch has a file of its own). Nothing runs; a pass is
not a chip run. The topology is described inside a fixture, never at import.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_megakernel_with_the_integer_scorers_compiles_at_cell_width(one_chip):
    """`sched1k-kubescore.montecarlo`'s launch (PR 50): the label filters'
    planes, the two capacity planes and the soft planes, the integer chain
    (shifts, wrapping multiplies, one-digit quotients, reductions over the
    node axis) compiled by Mosaic at 1,250 x 1000 x 2,176, K 64."""
    from kubernetriks_tpu.batched.pipeline import compile_profile
    from kubernetriks_tpu.ops.scheduler_kernel import fused_select_cycle_commit, select_commit_kernel_fits

    clusters, nodes, pods, k = 1250, 1000, 2176, 64
    assert select_commit_kernel_fits(nodes, pods, k, None, 1, 2)
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    node = ((nodes, clusters), i32)  # lane-major, as one chip carries them
    pod_i, pod_f = ((clusters, pods), i32), ((clusters, pods), f32)
    cand = ((clusters, k), f32)
    specs = (
        ((nodes, clusters), b), node, node,
        ((clusters, pods), b), pod_i, pod_f, pod_i, pod_i, pod_i, pod_f, pod_i, pod_i,
        cand, cand, cand,
        node, pod_i, pod_i,  # the label filters': node bits, one term plane, the untolerated taints
        node, node, pod_i, pod_i, pod_i, pod_i,  # capacities, two preferred-term planes, weights, soft taints
    )
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in specs]
    profile = compile_profile("kube_default")._replace(units=(500, 1024), soft_taints=1)
    compiled = jax.jit(
        lambda *a: fused_select_cycle_commit(
            *a[:15], k_pods=k, nodes_lane_major=True, profile=profile, affinity=a[15:18], kube=a[18:]
        )
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "the kernel is not in the compiled program"
