"""Batched-vs-scalar equivalence: the vectorized JAX path at batch=1 must
reproduce the scalar oracle's scheduling decisions, terminal counts and timing
stats on the same traces (SURVEY.md §7 'Scalar reference path').

Integer facts (assignments, phase counts, terminal counters) must match
exactly; float timing stats match to float32 tolerance (the scalar path runs
in Python f64, the batched state in f32).
"""

import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.state import (
    PHASE_RUNNING,
    PHASE_SUCCEEDED,
    PHASE_UNSCHEDULABLE,
)
from kubernetriks_tpu.core.types import PodConditionType
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

# Node/pod names sort in creation order so the scalar path's sorted-name
# iteration equals the batched path's slot order (tie-breaks align).
CLUSTER_YAML = """
events:
- timestamp: 5
  event_type:
    !CreateNode
      node:
        metadata: {name: node_00}
        status: {capacity: {cpu: 8000, ram: 17179869184}}
- timestamp: 5
  event_type:
    !CreateNode
      node:
        metadata: {name: node_01}
        status: {capacity: {cpu: 4000, ram: 8589934592}}
- timestamp: 200
  event_type:
    !CreateNode
      node:
        metadata: {name: node_02}
        status: {capacity: {cpu: 16000, ram: 34359738368}}
"""


def pod_yaml(name, cpu, ram, duration, ts):
    duration_line = (
        f"running_duration: {duration}" if duration is not None else ""
    )
    return f"""
- timestamp: {ts}
  event_type:
    !CreatePod
      pod:
        metadata: {{name: {name}}}
        spec:
          resources:
            requests: {{cpu: {cpu}, ram: {ram}}}
            limits: {{cpu: {cpu}, ram: {ram}}}
          {duration_line}
"""


GiB = 1024**3


def make_workload():
    events = ""
    # A mix that exercises: parallel fit, serialization, unschedulable-then-
    # freed, late big node. All ram values MiB-aligned so quantization is exact.
    specs = [
        ("pod_00", 2000, 4 * GiB, 50.0, 10),
        ("pod_01", 2000, 4 * GiB, 80.0, 11),
        ("pod_02", 4000, 8 * GiB, 40.0, 12),
        ("pod_03", 4000, 8 * GiB, 30.0, 13),
        ("pod_04", 12000, 24 * GiB, 60.0, 20),  # waits for node_02 at t=200
        ("pod_05", 1000, 2 * GiB, 25.0, 95),
        ("pod_06", 8000, 16 * GiB, 45.0, 210),
    ]
    for spec in specs:
        events += pod_yaml(*spec)
    return "events:" + events, [s[0] for s in specs]


def run_scalar(config, cluster_yaml, workload_yaml, until):
    sim = KubernetriksSimulation(config)
    sim.initialize(
        GenericClusterTrace.from_yaml(cluster_yaml),
        GenericWorkloadTrace.from_yaml(workload_yaml),
    )
    sim.step_until_time(until)
    return sim


def run_batched(config, cluster_yaml, workload_yaml, until, n_clusters=1):
    batched = build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(cluster_yaml).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events(),
        n_clusters=n_clusters,
    )
    batched.step_until_time(until)
    return batched


@pytest.mark.parametrize("delays", ["zero", "reference"])
def test_batch_of_one_matches_scalar(delays):
    suffix = ""
    if delays == "zero":
        suffix = "\n".join(
            f"{k}: 0.0"
            for k in (
                "as_to_ps_network_delay",
                "ps_to_sched_network_delay",
                "sched_to_as_network_delay",
                "as_to_node_network_delay",
            )
        )
    config = default_test_simulation_config(suffix)
    workload_yaml, pod_names = make_workload()

    scalar = run_scalar(config, CLUSTER_YAML, workload_yaml, 2000.0)
    batched = run_batched(config, CLUSTER_YAML, workload_yaml, 2000.0)

    # Every pod: same terminal state, same assigned node, close start time.
    view = batched.pod_view(0)
    for name in pod_names:
        scalar_pod = scalar.persistent_storage.succeeded_pods.get(name)
        assert scalar_pod is not None, f"{name} did not succeed in scalar run"
        b = view[name]
        assert b["phase"] == PHASE_SUCCEEDED, f"{name}: batched phase {b['phase']}"
        assert b["node"] == scalar_pod.status.assigned_node, name
        scalar_start = scalar_pod.get_condition(
            PodConditionType.POD_RUNNING
        ).last_transition_time
        assert b["start_time"] == pytest.approx(scalar_start, abs=1e-2), name

    # Metrics: counts exact, timing stats to f32 tolerance.
    sm = scalar.metrics_collector.accumulated_metrics
    bm = batched.metrics_summary()
    assert bm["counters"]["pods_succeeded"] == sm.pods_succeeded
    assert bm["counters"]["terminated_pods"] == sm.internal.terminated_pods
    for key, scalar_est in [
        ("pod_duration", sm.pod_duration_stats),
        ("pod_queue_time", sm.pod_queue_time_stats),
        ("pod_schedule_time", sm.pod_scheduling_algorithm_latency_stats),
    ]:
        best = bm["timings"][key]
        assert best["min"] == pytest.approx(scalar_est.min(), rel=1e-4, abs=1e-3), key
        assert best["max"] == pytest.approx(scalar_est.max(), rel=1e-4, abs=1e-3), key
        assert best["mean"] == pytest.approx(scalar_est.mean(), rel=1e-4, abs=1e-3), key


def test_node_removal_reschedules_like_scalar():
    config = default_test_simulation_config()
    cluster = (
        CLUSTER_YAML
        + """
- timestamp: 60
  event_type:
    !RemoveNode
      node_name: node_00
"""
    )
    workload = "events:" + pod_yaml("pod_00", 6000, 12 * GiB, 100.0, 10)
    scalar = run_scalar(config, cluster, workload, 3000.0)
    batched = run_batched(config, cluster, workload, 3000.0)

    scalar_pod = scalar.persistent_storage.succeeded_pods["pod_00"]
    b = batched.pod_view(0)["pod_00"]
    assert b["phase"] == PHASE_SUCCEEDED
    # Rescheduled onto node_02 (arrives t=200) in both paths.
    assert b["node"] == scalar_pod.status.assigned_node == "node_02"
    scalar_start = scalar_pod.get_condition(
        PodConditionType.POD_RUNNING
    ).last_transition_time
    assert b["start_time"] == pytest.approx(scalar_start, abs=1e-2)


def test_unschedulable_pod_stays_parked_in_both():
    config = default_test_simulation_config()
    workload = "events:" + pod_yaml("pod_00", 99000, 99 * GiB, 10.0, 10)
    scalar = run_scalar(config, CLUSTER_YAML, workload, 500.0)
    batched = run_batched(config, CLUSTER_YAML, workload, 500.0)

    assert "pod_00" in scalar.persistent_storage.unscheduled_pods_cache
    assert batched.pod_view(0)["pod_00"]["phase"] == PHASE_UNSCHEDULABLE
    assert batched.metrics_summary()["counters"]["pods_succeeded"] == 0


def test_pod_removal_while_running_matches():
    config = default_test_simulation_config()
    workload = (
        "events:"
        + pod_yaml("pod_00", 2000, 4 * GiB, 500.0, 10)
        + """
- timestamp: 100
  event_type:
    !RemovePod
      pod_name: pod_00
"""
    )
    scalar = run_scalar(config, CLUSTER_YAML, workload, 1000.0)
    batched = run_batched(config, CLUSTER_YAML, workload, 1000.0)

    assert scalar.metrics_collector.accumulated_metrics.pods_removed == 1
    bm = batched.metrics_summary()
    assert bm["counters"]["pods_removed"] == 1
    assert bm["counters"]["pods_succeeded"] == 0


def test_node_removed_same_tick_as_assignment_matches():
    """Same-tick race: node removal coincides with the scheduling cycle's
    assignment; the pending-removal guard drops the assignment in the scalar
    path (reference: tests/test_pods.rs:366-398, api_server.rs:163-193) and
    the batched removal-time resolution must agree — nothing ever runs."""
    config = default_test_simulation_config()
    cluster = (
        CLUSTER_YAML
        + """
- timestamp: 50
  event_type:
    !RemoveNode
      node_name: node_00
- timestamp: 50
  event_type:
    !RemoveNode
      node_name: node_01
- timestamp: 250
  event_type:
    !RemoveNode
      node_name: node_02
"""
    )
    # Queued at t=49.x, assigned in the t=50 cycle — the same tick the first
    # removals land; the late node_02 (created t=200) is removed at t=250,
    # racing the rescheduled assignment the same way.
    workload = "events:" + pod_yaml("pod_00", 2000, 4 * GiB, 100.0, 49)
    scalar = run_scalar(config, cluster, workload, 1000.0)
    batched = run_batched(config, cluster, workload, 1000.0)

    assert scalar.metrics_collector.accumulated_metrics.pods_succeeded == 0
    bm = batched.metrics_summary()["counters"]
    assert bm["pods_succeeded"] == 0
    # The pod survives, parked/queued with no nodes, in both paths.
    assert scalar.persistent_storage.get_pod("pod_00") is not None
    assert batched.pod_view(0)["pod_00"]["phase"] != PHASE_SUCCEEDED
    assert scalar.api_server.node_count() == 0


def test_pod_removed_before_scheduling_matches():
    """RemovePod while the pod is still parked: dropped from queues, never
    counted as a node-side removal, and the CA's unscheduled cache forgets
    it (reference: tests/test_pods.rs:401-449)."""
    config = default_test_simulation_config()
    # Too big for every node: parks unschedulable, then removed at t=50.
    workload = (
        "events:"
        + pod_yaml("pod_00", 99000, 99 * GiB, 500.0, 10)
        + """
- timestamp: 50
  event_type:
    !RemovePod
      pod_name: pod_00
"""
    )
    scalar = run_scalar(config, CLUSTER_YAML, workload, 1000.0)
    batched = run_batched(config, CLUSTER_YAML, workload, 1000.0)

    assert scalar.persistent_storage.get_pod("pod_00") is None
    assert "pod_00" not in scalar.persistent_storage.unscheduled_pods_cache
    assert scalar.metrics_collector.accumulated_metrics.pods_removed == 0
    bm = batched.metrics_summary()["counters"]
    assert bm["pods_removed"] == 0
    assert bm["pods_succeeded"] == 0
    from kubernetriks_tpu.batched.state import PHASE_REMOVED

    assert batched.pod_view(0)["pod_00"]["phase"] == PHASE_REMOVED


def test_pod_removed_after_finish_matches():
    """RemovePod landing after the pod already finished: tolerated, counted
    as succeeded not removed, in both paths (reference:
    tests/test_pods.rs:597-637, node_component.rs:298-332)."""
    config = default_test_simulation_config()
    workload = (
        "events:"
        + pod_yaml("pod_00", 2000, 4 * GiB, 50.0, 10)
        + """
- timestamp: 500
  event_type:
    !RemovePod
      pod_name: pod_00
"""
    )
    scalar = run_scalar(config, CLUSTER_YAML, workload, 1000.0)
    batched = run_batched(config, CLUSTER_YAML, workload, 1000.0)

    s = scalar.metrics_collector.accumulated_metrics
    assert (s.pods_removed, s.pods_succeeded) == (0, 1)
    bm = batched.metrics_summary()["counters"]
    assert (bm["pods_removed"], bm["pods_succeeded"]) == (0, 1)
    assert batched.pod_view(0)["pod_00"]["phase"] == PHASE_SUCCEEDED


def test_large_timestamp_equivalence_f64():
    """Fidelity at Alibaba-scale timestamps: the same scenario shifted to
    t ~ 1e6 s must still match the scalar f64 oracle with the reference's
    sub-0.1 s network delays (f32 sim time has ~0.06 s resolution there, which
    would swallow the delays; reference delay values: src/config.yaml:73-78)."""
    T0 = 1_000_000.0  # multiple of the 10 s cycle interval
    config = default_test_simulation_config(
        "\n".join(
            [
                "as_to_ps_network_delay: 0.050",
                "ps_to_sched_network_delay: 0.089",
                "sched_to_as_network_delay: 0.023",
                "as_to_node_network_delay: 0.152",
            ]
        )
    )

    cluster_yaml = CLUSTER_YAML.replace("timestamp: 5", f"timestamp: {5 + T0}").replace(
        "timestamp: 200", f"timestamp: {200 + T0}"
    )
    events = ""
    specs = [
        ("pod_00", 2000, 4 * GiB, 50.0, 10 + T0),
        ("pod_01", 2000, 4 * GiB, 80.0, 11 + T0),
        ("pod_02", 4000, 8 * GiB, 40.0, 12 + T0),
        ("pod_03", 4000, 8 * GiB, 30.0, 13 + T0),
        ("pod_04", 12000, 24 * GiB, 60.0, 20 + T0),  # waits for node_02
        ("pod_05", 1000, 2 * GiB, 25.0, 95 + T0),
    ]
    for spec in specs:
        events += pod_yaml(*spec)
    workload_yaml = "events:" + events

    scalar = run_scalar(config, cluster_yaml, workload_yaml, T0 + 2000.0)

    batched = build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(cluster_yaml).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events(),
        n_clusters=1,
    )
    # Windows before T0 are no-ops (no events, empty queues); skip them.
    batched.next_window = T0
    batched.step_until_time(T0 + 2000.0)

    view = batched.pod_view(0)
    for name, *_ in specs:
        scalar_pod = scalar.persistent_storage.succeeded_pods.get(name)
        assert scalar_pod is not None, f"{name} did not succeed in scalar run"
        b = view[name]
        assert b["phase"] == PHASE_SUCCEEDED, name
        assert b["node"] == scalar_pod.status.assigned_node, name
        scalar_start = scalar_pod.get_condition(
            PodConditionType.POD_RUNNING
        ).last_transition_time
        # f64 resolution at t=1e6 is ~1e-10 s; the delays must survive exactly.
        assert b["start_time"] == pytest.approx(scalar_start, abs=1e-6), name

    sm = scalar.metrics_collector.accumulated_metrics
    bm = batched.metrics_summary()
    assert bm["counters"]["pods_succeeded"] == sm.pods_succeeded
    assert bm["counters"]["terminated_pods"] == sm.internal.terminated_pods


def test_conditional_move_matches_scalar():
    """enable_unscheduled_pods_conditional_move on the batched path: both
    resource-aware wake scans must mirror the scalar oracle
    (reference: src/core/scheduler/scheduler.rs:391-409 node-add scan with its
    inverted fits-stay sense, :366-380 freed-budget first-fit)."""
    config = default_test_simulation_config(
        "enable_unscheduled_pods_conditional_move: true"
    )
    assert config.enable_unscheduled_pods_conditional_move

    cluster = """
events:
- timestamp: 5
  event_type:
    !CreateNode
      node:
        metadata: {name: node_00}
        status: {capacity: {cpu: 4000, ram: 8589934592}}
- timestamp: 60
  event_type:
    !CreateNode
      node:
        metadata: {name: node_01}
        status: {capacity: {cpu: 2500, ram: 5368709120}}
"""
    # pod_00 fills node_00; pod_01 + pod_02 park unschedulable.
    # t=60 node_01 arrives: node scan walks (pod_01, pod_02) in park order —
    # pod_01 (3000 > 2500) does NOT fit => woken (and parks again);
    # pod_02 (2000 <= 2500) fits => STAYS parked (the reference's inverted
    # sense) even though node_01 could run it.
    # t~120 pod_00 finishes: freed scan order is (pod_02 ts~20, pod_01 ts~70);
    # pod_02 fits the freed (3000, 6 GiB) => woken and scheduled; pod_01 does
    # not fit the remaining (1000, 2 GiB) => stays until the 300 s stale flush.
    workload = (
        "events:"
        + pod_yaml("pod_00", 3000, 6 * GiB, 100.0, 10)
        + pod_yaml("pod_01", 3000, 6 * GiB, 40.0, 15)
        + pod_yaml("pod_02", 2000, 4 * GiB, 40.0, 16)
    )

    scalar = run_scalar(config, cluster, workload, 600.0)
    batched = run_batched(config, cluster, workload, 600.0)

    view = batched.pod_view(0)
    for name in ("pod_00", "pod_01", "pod_02"):
        scalar_pod = scalar.persistent_storage.succeeded_pods.get(name)
        assert scalar_pod is not None, f"{name} did not succeed in scalar run"
        b = view[name]
        assert b["phase"] == PHASE_SUCCEEDED, name
        assert b["node"] == scalar_pod.status.assigned_node, name
        scalar_start = scalar_pod.get_condition(
            PodConditionType.POD_RUNNING
        ).last_transition_time
        assert b["start_time"] == pytest.approx(scalar_start, abs=1e-6), name

    # The stale flush (not the wake scans) is what released pod_01: it parked
    # again after the node-add wake, then waited out the 300 s stay.
    scalar_p1_start = (
        scalar.persistent_storage.succeeded_pods["pod_01"]
        .get_condition(PodConditionType.POD_RUNNING)
        .last_transition_time
    )
    assert scalar_p1_start > 370.0

    sm = scalar.metrics_collector.accumulated_metrics
    bm = batched.metrics_summary()
    assert bm["counters"]["pods_succeeded"] == sm.pods_succeeded == 3


def test_conditional_move_fitting_pod_stays_parked():
    """Pinned reference quirk: after a node-add wake, a pod that FITS the new
    node stays in the unschedulable queue (scheduler.rs:391-409 returns false
    => not moved) — on both paths."""
    config = default_test_simulation_config(
        "enable_unscheduled_pods_conditional_move: true"
    )
    cluster = """
events:
- timestamp: 5
  event_type:
    !CreateNode
      node:
        metadata: {name: node_00}
        status: {capacity: {cpu: 1000, ram: 2147483648}}
- timestamp: 40
  event_type:
    !CreateNode
      node:
        metadata: {name: node_01}
        status: {capacity: {cpu: 8000, ram: 17179869184}}
"""
    workload = "events:" + pod_yaml("pod_00", 4000, 8 * GiB, 50.0, 10)

    # Stop before the 300 s stale flush would release it.
    scalar = run_scalar(config, cluster, workload, 200.0)
    batched = run_batched(config, cluster, workload, 200.0)

    assert "pod_00" in scalar.persistent_storage.unscheduled_pods_cache
    assert len(scalar.scheduler.unschedulable_pods) == 1
    assert batched.pod_view(0)["pod_00"]["phase"] == PHASE_UNSCHEDULABLE
    # Flush-all would have scheduled it: rerun without conditional move.
    config2 = default_test_simulation_config()
    scalar2 = run_scalar(config2, cluster, workload, 200.0)
    batched2 = run_batched(config2, cluster, workload, 200.0)
    assert "pod_00" in scalar2.persistent_storage.succeeded_pods
    assert batched2.pod_view(0)["pod_00"]["phase"] == PHASE_SUCCEEDED


def test_multi_chunk_event_drain_matches_single_chunk():
    """Event application drains a window's due events in chunks of
    max_events_per_window inside a while_loop; a burst window (more events
    than the chunk size) must produce bit-identical state to a single big
    chunk — covers the cross-chunk cursor / n_creates / queue-seq carry."""
    import jax

    config = default_test_simulation_config()
    workload_yaml, pod_names = make_workload()

    big = run_batched(config, CLUSTER_YAML, workload_yaml, 2000.0)

    tiny = build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(CLUSTER_YAML).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events(),
        n_clusters=1,
        max_events_per_window=2,  # forces multi-iteration drains
    )
    tiny.step_until_time(2000.0)

    flat_a, _ = jax.tree_util.tree_flatten_with_path(big.state)
    flat_b, _ = jax.tree_util.tree_flatten_with_path(tiny.state)
    for (path, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=jax.tree_util.keystr(path),
        )


def _burst_traces():
    """A burst window (40 CreateNodes at t = 0, beside the first pods) and a
    window of more than 64 due events (70 pods created inside [20, 30) s),
    then a thin tail; names sort in creation order."""
    cluster = "events:" + "".join(
        f"""
- timestamp: 0
  event_type:
    !CreateNode
      node:
        metadata: {{name: node_{i:03d}}}
        status: {{capacity: {{cpu: {8000 + 1000 * (i % 5)}, ram: {(16 + 2 * (i % 5)) * GiB}}}}}
"""
        for i in range(40)
    )
    stamps = [1.0, 2.5, 4.0] + [20.0 + 0.14 * k for k in range(70)] + [41.0 + 7.0 * k for k in range(12)]
    specs = [
        (f"pod_{k:03d}", 1000 + 500 * (k % 3), (2 + k % 3) * GiB, 30.0 + 5.0 * (k % 7), ts)
        for k, ts in enumerate(stamps)
    ]
    return cluster, "events:" + "".join(pod_yaml(*spec) for spec in specs), [spec[0] for spec in specs]


def _burst_run(chunk):
    cluster, workload, _ = _burst_traces()
    sim = build_batched_from_traces(
        default_test_simulation_config(),
        GenericClusterTrace.from_yaml(cluster).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload).convert_to_simulator_events(),
        n_clusters=2,
        max_events_per_window=chunk,
    )
    sim.step_until_time(400.0)
    return sim


@pytest.fixture(scope="module")
def burst_references():
    """The same traces through one whole-window chunk and through the scalar path."""
    cluster, workload, names = _burst_traces()
    scalar = run_scalar(default_test_simulation_config(), cluster, workload, 400.0)
    return _burst_run(1024), scalar, names


@pytest.mark.parametrize("chunk", [8, 32, 64, 96])
def test_every_chunk_size_gives_the_same_final_state(chunk, burst_references):
    """The event loop is exact at any chunk: a chunk smaller than a slab
    block, one block, and the two- and three-block chunks the engine's rule
    picks (event_chunk_size), over a window that takes several passes at
    each of them and a window of 73 due events that takes one pass only at
    96. Every leaf of the final state equals the single-chunk build's, so
    the four equal each other, and every pod sits where the scalar path put
    it."""
    import jax

    whole, scalar, names = burst_references
    sim = _burst_run(chunk)
    assert sim.max_events_per_window == chunk
    due = (sim._ev_time_np[0][:, None] < np.asarray([10.0, 30.0])).sum(axis=0)
    assert due[0] >= 43 and due[1] - due[0] > 64  # the burst, then the busy window
    flat_a, _ = jax.tree_util.tree_flatten_with_path(whole.state)
    flat_b, _ = jax.tree_util.tree_flatten_with_path(sim.state)
    for (path, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))
    view = sim.pod_view(0)
    assert sim.pod_view(1) == view
    for name in names:
        scalar_pod = scalar.persistent_storage.succeeded_pods.get(name)
        assert scalar_pod is not None, f"{name} did not succeed in the scalar run"
        assert view[name]["phase"] == PHASE_SUCCEEDED, name
        assert view[name]["node"] == scalar_pod.status.assigned_node, name
        start = scalar_pod.get_condition(PodConditionType.POD_RUNNING).last_transition_time
        assert view[name]["start_time"] == pytest.approx(start, abs=1e-2), name


def test_larger_batch_replicates_cluster_zero():
    """Every cluster in a homogeneous batch produces identical results."""
    config = default_test_simulation_config()
    workload_yaml, pod_names = make_workload()
    batched = run_batched(config, CLUSTER_YAML, workload_yaml, 2000.0, n_clusters=8)
    base = batched.cluster_metrics(0)
    for c in range(1, 8):
        assert batched.cluster_metrics(c) == base
    assert base["pods_succeeded"] == len(pod_names)


def test_checkpoint_resume_bit_identical(tmp_path):
    """save_checkpoint mid-run + load_checkpoint into a fresh build resumes
    bit-identically: the full state is one pytree (SURVEY §5.4)."""
    import jax

    config = default_test_simulation_config()
    workload_yaml, _ = make_workload()

    straight = run_batched(config, CLUSTER_YAML, workload_yaml, 2000.0)

    half = run_batched(config, CLUSTER_YAML, workload_yaml, 990.0)
    half.save_checkpoint(str(tmp_path / "ckpt"))

    resumed = build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(CLUSTER_YAML).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events(),
        n_clusters=1,
    )
    resumed.load_checkpoint(str(tmp_path / "ckpt"))
    assert resumed.next_window == 1000.0
    resumed.step_until_time(2000.0)

    flat_a, _ = jax.tree_util.tree_flatten_with_path(straight.state)
    flat_b, _ = jax.tree_util.tree_flatten_with_path(resumed.state)
    for (path, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )


def test_checkpoint_preserves_gauge_series(tmp_path):
    config = default_test_simulation_config()
    workload_yaml, _ = make_workload()
    sim = build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(CLUSTER_YAML).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events(),
        n_clusters=1,
    )
    sim.collect_gauges = True
    sim.step_until_time(490.0)
    sim.save_checkpoint(str(tmp_path / "g_ckpt"))

    resumed = build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(CLUSTER_YAML).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events(),
        n_clusters=1,
    )
    resumed.collect_gauges = True
    resumed.load_checkpoint(str(tmp_path / "g_ckpt"))
    resumed.step_until_time(700.0)
    times, samples = resumed.gauge_series()
    assert times[0] == 0.0 and times[-1] == 700.0  # no pre-checkpoint hole
    assert samples.shape[0] == len(times) == 71
