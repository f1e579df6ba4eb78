"""Automatic pod-window growth: when a dense stretch of the trace outgrows
the sliding window (no leading pod is terminal, so no slide is possible),
the engine doubles the window IN PLACE instead of failing — and the result
stays bit-identical to a full-resident run (same counters, same terminal
state). Covers plain pods, the HPA resident-ring re-positioning, and
checkpoint/resume across a growth."""

import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generator import UniformClusterTrace
from kubernetriks_tpu.trace.generic import GenericWorkloadTrace

N_CLUSTERS = 3


def _long_running_workload(n_pods=200, duration=600.0):
    """1 pod/s arrivals, each running long enough that the live span grows
    to ~n_pods before the first pod ever finishes: a window smaller than
    n_pods MUST grow (no slide is possible while the head pod runs)."""
    return GenericWorkloadTrace.from_yaml(
        "events:"
        + "".join(
            f"""
- timestamp: {1 + i}
  event_type:
    !CreatePod
      pod:
        metadata:
          name: pod_{i:04d}
        spec:
          resources:
            requests: {{cpu: 10, ram: 10485760}}
            limits: {{cpu: 10, ram: 10485760}}
          running_duration: {duration}
"""
            for i in range(n_pods)
        )
    ).convert_to_simulator_events()


def _build(workload, n_clusters=N_CLUSTERS, hpa=False, **kwargs):
    config = default_test_simulation_config()
    config.horizontal_pod_autoscaler.enabled = hpa
    cluster = UniformClusterTrace(4, cpu=16000, ram=32 * 1024**3)
    return build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload,
        n_clusters=n_clusters,
        max_pods_per_cycle=16,
        **kwargs,
    )


def test_window_grows_and_matches_resident():
    workload = _long_running_workload()
    ref = _build(workload)
    ref.step_until_time(1200.0)

    sim = _build(workload, pod_window=64)
    assert sim.pod_window == 64
    sim.step_until_time(1200.0)
    # 200 concurrent long-running pods forced growth past 64 (64 -> 128 ->
    # 200 == the whole plain segment, where it caps).
    assert sim.pod_window == 200
    assert sim.metrics_summary()["counters"] == ref.metrics_summary()["counters"]
    assert (
        sim.metrics_summary()["counters"]["pods_succeeded"] == 200 * N_CLUSTERS
    )
    # Fully grown (window == whole plain segment): same terminal phases on
    # the real slots (the resident build's device axis is 128-align padded
    # with EMPTY slots beyond them).
    P_real = np.asarray(sim.state.pods.phase).shape[1]
    assert np.array_equal(
        np.asarray(ref.state.pods.phase)[:, :P_real],
        np.asarray(sim.state.pods.phase),
    )


@pytest.mark.slow
def test_window_growth_repositions_hpa_ring():
    """Growth moves the resident pod-group ring right; HPA replica
    accounting must survive it (same counters as the resident run).
    Slow lane (tier-1 wall-clock budget): tier-1 keeps plain growth
    parity (test_window_grows_and_matches_resident) and growth x
    checkpoint (test_checkpoint_resume_across_growth); the HPA-ring
    reposition composition runs here in the slow lane."""
    group = GenericWorkloadTrace.from_yaml(
        """
events:
- timestamp: 5.5
  event_type:
    !CreatePodGroup
      pod_group:
        name: grp
        initial_pod_count: 2
        max_pod_count: 6
        pod_template:
          metadata: {name: grp}
          spec:
            resources:
              requests: {cpu: 100, ram: 104857600}
              limits: {cpu: 100, ram: 104857600}
        target_resources_usage: {cpu_utilization: 0.5}
        resources_usage_model_config:
          cpu_config:
            model_name: pod_group
            config: |
              - duration: 200.0
                total_load: 0.6
              - duration: 200.0
                total_load: 2.5
              - duration: 300.0
                total_load: 0.4
"""
    ).convert_to_simulator_events()
    workload = sorted(
        _long_running_workload(n_pods=150, duration=500.0) + group,
        key=lambda e: e[0],
    )

    ref = _build(workload, hpa=True)
    ref.step_until_time(1000.0)
    sim = _build(workload, hpa=True, pod_window=64)
    sim.step_until_time(1000.0)
    assert sim.pod_window > 64, "the window never grew"
    rc, sc = ref.metrics_summary()["counters"], sim.metrics_summary()["counters"]
    assert rc == sc
    assert sc["total_scaled_up_pods"] > 0


def test_checkpoint_resume_across_growth(tmp_path):
    """A checkpoint taken AFTER growth restores into a freshly built engine
    (which grows to match before loading) and finishes identically."""
    workload = _long_running_workload(n_pods=120, duration=400.0)
    ref = _build(workload)
    ref.step_until_time(900.0)

    sim = _build(workload, pod_window=32)
    sim.step_until_time(500.0)
    assert sim.pod_window > 32
    path = str(tmp_path / "ckpt")
    sim.save_checkpoint(path)

    fresh = _build(workload, pod_window=32)
    fresh.load_checkpoint(path)
    assert fresh.pod_window == sim.pod_window
    fresh.step_until_time(900.0)
    assert fresh.metrics_summary()["counters"] == ref.metrics_summary()["counters"]


def test_host_slide_fallback_matches_resident():
    """The host slide path (used when the device payload exceeds its memory
    budget) stays bit-identical: force it by dropping the device payload."""
    # Short durations: leading pods terminate well before the window fills,
    # so the engine SLIDES (growth never triggers and pod_base advances).
    workload = _long_running_workload(n_pods=120, duration=30.0)
    ref = _build(workload)
    ref.step_until_time(700.0)

    sim = _build(workload, pod_window=64)
    sim._device_slide = None  # force the host fallback
    sim.step_until_time(700.0)
    assert sim.pod_window == 64, "expected slides, not growth"
    assert sim._pod_base > 0, "window never slid"
    assert sim.metrics_summary()["counters"] == ref.metrics_summary()["counters"]


@pytest.mark.slow
def test_window_growth_under_mesh():
    """Growth on a C-sharded mesh: the inserted slots and the moved
    autoscale statics (HPA ring) stay shard-local on the 'clusters' axis,
    and the grown run equals the unsharded resident run. Slow lane
    (tier-1 wall-clock budget): tier-1 keeps growth coverage
    (test_window_grows_and_matches_resident, the HPA-ring reposition and
    checkpoint-resume growth cases) AND mesh parity
    (test_batched_sharding.test_sharded_run_matches_unsharded,
    test_flagship_compose.test_pallas_shard_map_matches_scan_on_mesh);
    this is the growthxmesh composition double-check."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < 4:
        import pytest

        pytest.skip("needs >= 4 virtual devices")
    mesh = Mesh(np.array(devices[:4]), ("clusters",))

    group = GenericWorkloadTrace.from_yaml(
        """
events:
- timestamp: 5.5
  event_type:
    !CreatePodGroup
      pod_group:
        name: grp
        initial_pod_count: 2
        max_pod_count: 4
        pod_template:
          metadata: {name: grp}
          spec:
            resources:
              requests: {cpu: 100, ram: 104857600}
              limits: {cpu: 100, ram: 104857600}
        target_resources_usage: {cpu_utilization: 0.5}
        resources_usage_model_config:
          cpu_config:
            model_name: pod_group
            config: |
              - duration: 300.0
                total_load: 1.8
              - duration: 300.0
                total_load: 0.4
"""
    ).convert_to_simulator_events()
    workload = sorted(
        _long_running_workload(n_pods=120, duration=400.0) + group,
        key=lambda e: e[0],
    )

    ref = _build(workload, n_clusters=4, hpa=True)
    ref.step_until_time(900.0)
    sim = _build(workload, n_clusters=4, hpa=True, pod_window=32, mesh=mesh)
    sim.step_until_time(900.0)
    assert sim.pod_window > 32, "the window never grew"
    # Still C-sharded (not merely present on 4 devices as replicas).
    for arr in (sim.state.pods.phase, sim.autoscale_statics.pod_group_id):
        assert arr.sharding.spec[0] == "clusters", arr.sharding
    rc, sc = ref.metrics_summary()["counters"], sim.metrics_summary()["counters"]
    assert rc == sc
    assert sc["total_scaled_up_pods"] > 0, "the HPA ring never activated"


def test_fleet_reset_after_growth_rewinds_to_the_grown_build():
    """A scenario build whose window grew inside a job resets: the pristine
    snapshot grows with the window, so the next job starts from the state a
    build at the grown width starts from and repeats the first exactly."""
    from kubernetriks_tpu.batched.fleet import scenario_vectors
    from kubernetriks_tpu.batched.state import compare_states

    workload = _long_running_workload()
    config = default_test_simulation_config()
    scenario = dict(scenario_vectors(config, N_CLUSTERS))

    sim = _build(workload, pod_window=64, scenario=scenario)
    sim.step_until_time(1200.0)
    assert sim.pod_window == 200, "the window never grew"
    first = sim.metrics_summary()["counters"]
    sim.fleet_reset()
    assert sim.pod_window == 200 and sim.next_window_idx == 0

    wide = _build(workload, pod_window=200, scenario=scenario)
    mismatches = compare_states(sim.state, wide.state)
    assert not mismatches, mismatches

    sim.step_until_time(1200.0)
    wide.step_until_time(1200.0)
    assert sim.metrics_summary()["counters"] == first
    mismatches = compare_states(sim.state, wide.state)
    assert not mismatches, mismatches
