"""Flagship composition: every scale feature at once.

Round-2 verdict gap: the sliding pod window, HPA pod groups, the cluster
autoscaler, the device mesh, and the Pallas cycle kernel each worked but were
mutually exclusive. These tests pin the composed behavior:

- the window slides over PLAIN trace pods while HPA ring slots stay
  device-resident (trace_compile.segment_pod_slots segmented layout),
- the composition runs under a C-sharded mesh (the window shift is a
  shard-preserving concatenation),
- the Pallas kernel runs per-shard through shard_map,

and every variant reproduces the full-resident unsharded run exactly
(scalar-oracle anchored by the goldens the components already pass:
reference src/main.rs:57-102 one-config end-to-end run).
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.state import compare_states
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generator import PoissonWorkloadTrace
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

from tests.test_hpa_ca_combined import (
    CLUSTER_TRACE as HPA_CA_CLUSTER,
    CONFIG_SUFFIX as HPA_CA_SUFFIX,
    WORKLOAD_TRACE as HPA_CA_WORKLOAD,
)

N_CLUSTERS = 8
HORIZON = 1500.0


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) == 8
    return Mesh(np.array(devices), ("clusters",))


@pytest.fixture(scope="module")
def mixed_traces():
    """Plain finite Poisson pods (the window slides over these) interleaved
    with the HPA+CA pod group burst workload (resident ring slots)."""
    plain = PoissonWorkloadTrace(
        rate_per_second=0.25,
        horizon=1200.0,
        seed=13,
        cpu=1200,
        ram=2 * 1024**3,
        duration_range=(15.0, 70.0),
    ).convert_to_simulator_events()
    group = GenericWorkloadTrace.from_yaml(
        HPA_CA_WORKLOAD
    ).convert_to_simulator_events()
    workload = sorted(plain + group, key=lambda e: e[0])
    cluster = GenericClusterTrace.from_yaml(HPA_CA_CLUSTER).convert_to_simulator_events()
    return cluster, workload


def _build(mixed_traces, **kwargs):
    cluster, workload = mixed_traces
    config = default_test_simulation_config(HPA_CA_SUFFIX)
    return build_batched_from_traces(
        config,
        list(cluster),
        list(workload),
        n_clusters=N_CLUSTERS,
        max_pods_per_cycle=16,
        # This scenario churns 22 CA node opens (measured) past the default
        # 2 x 10 reserve; the wider reserve keeps the composed run
        # reference-faithful under the strict reserve check.
        ca_slot_multiplier=4,
        **kwargs,
    )


@pytest.fixture(scope="module")
def full_run(mixed_traces):
    sim = _build(mixed_traces)
    sim.step_until_time(HORIZON)
    return sim


def _assert_matches_full(sim, full):
    sm, fm = sim.metrics_summary(), full.metrics_summary()
    assert sm == fm
    assert sim.hpa_replicas(0) == full.hpa_replicas(0)
    np.testing.assert_array_equal(
        np.asarray(sim.ca_node_counts(0)), np.asarray(full.ca_node_counts(0))
    )
    pv, fv = sim.pod_view(0), full.pod_view(0)
    for name in pv:
        assert pv[name] == fv[name], name


def test_window_slides_over_plain_pods_with_hpa_and_ca(mixed_traces, full_run):
    """Sliding pod window + HPA pod groups + CA, unsharded: identical
    terminal metrics, replica trajectory, CA node counts and pod states."""
    sim = _build(mixed_traces, pod_window=64)
    T = int(sim.consts.trace_pod_bound)
    assert sim.pod_window == 64 < T, "window must be smaller than plain pods"
    assert sim.n_pods > 64, "resident HPA ring slots must extend the window"
    sim.step_until_time(HORIZON)
    assert sim._pod_base > 0, "the window never slid"
    # Autoscalers actually did something in this scenario.
    counters = sim.metrics_summary()["counters"]
    assert counters["total_scaled_up_pods"] > 0
    assert counters["total_scaled_up_nodes"] > 0
    assert counters["total_scaled_down_nodes"] > 0
    _assert_matches_full(sim, full_run)


@pytest.mark.slow
def test_flagship_composition_on_mesh(mixed_traces, full_run, mesh):
    """The full composition — sliding window + HPA + CA + 8-device mesh +
    per-shard Pallas kernel (interpret mode on the CPU platform) — matches
    the full-resident unsharded scan run.

    `slow`: the heavy sliding+mesh+interpret combination (~20 s) runs in
    the slow suite while test_pallas_shard_map_matches_scan_on_mesh (~3x
    cheaper) keeps per-shard kernel mesh coverage in tier-1."""
    sim = _build(
        mixed_traces,
        pod_window=64,
        mesh=mesh,
        use_pallas=True,
        pallas_interpret=True,
    )
    assert len(sim.state.pods.phase.devices()) == 8
    sim.step_until_time(HORIZON)
    assert sim._pod_base > 0, "the window never slid under the mesh"
    assert len(sim.state.pods.phase.devices()) == 8, (
        "the window shift dropped the mesh sharding"
    )
    _assert_matches_full(sim, full_run)


def test_pallas_shard_map_matches_scan_on_mesh(mixed_traces, full_run, mesh):
    """Pallas kernel under shard_map on the full-resident mesh run: the whole
    final state pytree matches the unsharded scan path bit for bit (metric
    accumulators to the documented f32 tolerance)."""
    sim = _build(mixed_traces, mesh=mesh, use_pallas=True, pallas_interpret=True)
    sim.step_until_time(HORIZON)
    bad = compare_states(full_run.state, sim.state)
    assert not bad, bad


def test_checkpoint_resume_through_flagship_composition(tmp_path, mixed_traces, full_run):
    """save/load_checkpoint mid-run through the COMPOSED configuration
    (sliding pod window + segmented HPA rings + CA): the restored sim must
    resume with the correct window base and finish identical to the
    uninterrupted run."""
    half = _build(mixed_traces, pod_window=64)
    half.step_until_time(800.0)
    assert half._pod_base > 0, "checkpoint should capture a shifted window"
    half.save_checkpoint(str(tmp_path / "flagship_ckpt"))

    resumed = _build(mixed_traces, pod_window=64)
    resumed.load_checkpoint(str(tmp_path / "flagship_ckpt"))
    assert resumed._pod_base == half._pod_base
    assert resumed.next_window == half.next_window
    resumed.step_until_time(HORIZON)
    _assert_matches_full(resumed, full_run)


def test_heterogeneous_batch_segmented_layout():
    """A batch mixing DIFFERENT traces — one with an HPA pod group, one with
    plain pods only, one with nodes only (zero pods) — through the segmented
    layout and the sliding window: each cluster must behave exactly like its
    own single-cluster full-resident run."""
    config = default_test_simulation_config(HPA_CA_SUFFIX)
    from kubernetriks_tpu.batched.engine import BatchedSimulation
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace

    cluster = GenericClusterTrace.from_yaml(HPA_CA_CLUSTER).convert_to_simulator_events()
    plain = PoissonWorkloadTrace(
        rate_per_second=0.2,
        horizon=900.0,
        seed=29,
        cpu=1000,
        ram=2 * 1024**3,
        duration_range=(15.0, 60.0),
    ).convert_to_simulator_events()
    group = GenericWorkloadTrace.from_yaml(HPA_CA_WORKLOAD).convert_to_simulator_events()

    mixed = compile_cluster_trace(
        cluster, sorted(plain + group, key=lambda e: e[0]), config
    )
    plain_only = compile_cluster_trace(cluster, list(plain), config)
    nodes_only = compile_cluster_trace(cluster, [], config)
    batch = [mixed, plain_only, nodes_only]

    hetero = BatchedSimulation(
        config, batch, max_pods_per_cycle=16, pod_window=48
    )
    assert hetero._resident_shift > 0, "segmented layout must be active"
    hetero.step_until_time(1200.0)
    assert hetero._pod_base > 0

    for i, compiled in enumerate(batch):
        solo = BatchedSimulation(config, [compiled], max_pods_per_cycle=16)
        solo.step_until_time(1200.0)
        assert hetero.cluster_metrics(i) == solo.cluster_metrics(0), i
        pv_h, pv_s = hetero.pod_view(i), solo.pod_view(0)
        for name in pv_h:
            assert pv_h[name] == pv_s[name], (i, name)
        if i == 0:
            # The group cluster's replica trajectory is its own.
            assert hetero.hpa_replicas(0) == solo.hpa_replicas(0)


def test_autoscaled_build_sharded_matches_unsharded_every_leaf():
    """The small autoscaled build (HPA + CA, full-resident) on a mesh of 4
    over clusters with workloads of their own: the autoscale passes' conds
    gate on the SHARD's clusters, and a shard that skips what another runs
    changes no leaf. Every leaf equals the unsharded run's."""
    from kubernetriks_tpu.test_util import leaves_differing
    from tests.sharded_builds import autoscaled_batch, mesh_of

    def run(**kwargs):
        sim = autoscaled_batch(8, **kwargs)
        sim.step_until_time(HORIZON)
        return sim

    unsharded, sharded = run(), run(mesh=mesh_of(4))
    counters = sharded.metrics_summary()["counters"]
    for key in ("total_scaled_up_pods", "total_scaled_up_nodes", "total_scaled_down_nodes"):
        assert counters[key] > 0, (key, counters)
    per_cluster = np.asarray(sharded.state.metrics.scaled_up_nodes)
    assert len(set(per_cluster.tolist())) > 1, "the clusters do not differ"
    assert leaves_differing(unsharded.state, sharded.state) == []
