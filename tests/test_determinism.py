"""THE north-star determinism test (port of reference tests/test_determinism.rs):
random cluster + workload traces generated from the sim's own seeded RNG, run
repeatedly; pods_succeeded and all three timing estimators must be
bit-identical across runs.

Tier-1 runs the FAST scales by default (150/1500 x 3: the
reference-scale run alone dominated the old ~36-min default suite). The
reference's own scale (~<=1000 node / ~<=10000 pod events, 1 + 10 repeat
runs, reference: tests/test_determinism.rs:70-126) lives in
test_simulation_determinism_reference_scale behind `-m slow`.
"""

from kubernetriks_tpu.metrics.collector import MetricsCollector
from kubernetriks_tpu.sim.callbacks import RunUntilAllPodsAreFinishedCallbacks
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

MAX_NODE_EVENTS = 150
MAX_POD_EVENTS = 1500
REPEAT_RUNS = 3


def generate_cluster_trace(sim: KubernetriksSimulation) -> GenericClusterTrace:
    """reference: tests/test_determinism.rs:14-47 (event mix: ~1/3 removals)."""
    import math

    kernel = sim.sim
    events = math.ceil(kernel.rand() * MAX_NODE_EVENTS)
    created_nodes = {}
    trace_events = []
    for _ in range(events):
        if math.ceil(kernel.rand() * 10.0) % 3.0 == 0.0 and created_nodes:
            next_node_name = sorted(created_nodes)[0]
            creation_ts = created_nodes.pop(next_node_name)
            trace_events.append(
                {
                    "timestamp": creation_ts + kernel.rand() * 10000.0,
                    "event_type": {"__tag__": "RemoveNode", "node_name": next_node_name},
                }
            )
        else:
            name = kernel.random_string(5)
            creation_ts = kernel.rand() * 1000.0
            cpu = math.ceil(kernel.rand() * 10000.0)
            ram = int(kernel.rand() * 100000000000.0)
            created_nodes[name] = creation_ts
            trace_events.append(
                {
                    "timestamp": creation_ts,
                    "event_type": {
                        "__tag__": "CreateNode",
                        "node": {
                            "metadata": {"name": name, "creation_timestamp": creation_ts},
                            "status": {"capacity": {"cpu": cpu, "ram": ram}},
                        },
                    },
                }
            )
    # Guarantee termination: one large always-alive node so every pod
    # eventually schedules (the reference relies on its seed for this).
    trace_events.append(
        {
            "timestamp": 0.0,
            "event_type": {
                "__tag__": "CreateNode",
                "node": {
                    "metadata": {"name": "anchor_node"},
                    "status": {
                        "capacity": {"cpu": 100000, "ram": 1000000000000}
                    },
                },
            },
        }
    )
    return GenericClusterTrace(events=trace_events)


def generate_workload_trace(sim: KubernetriksSimulation) -> GenericWorkloadTrace:
    """reference: tests/test_determinism.rs:49-68."""
    import math

    kernel = sim.sim
    events = math.ceil(kernel.rand() * MAX_POD_EVENTS)
    trace_events = []
    for _ in range(events):
        trace_events.append(
            {
                "timestamp": kernel.rand() * 100000.0,
                "event_type": {
                    "__tag__": "CreatePod",
                    "pod": {
                        "metadata": {"name": kernel.random_string(8)},
                        "spec": {
                            "resources": {
                                "requests": {
                                    "cpu": math.ceil(kernel.rand() * 1000.0),
                                    "ram": int(kernel.rand() * 10000000000.0),
                                },
                                "limits": {"cpu": 1000, "ram": 10000000000},
                            },
                            "running_duration": kernel.rand() * 1000.0,
                        },
                    },
                },
            }
        )
    return GenericWorkloadTrace(events=trace_events)


def run_simulation() -> MetricsCollector:
    config = default_test_simulation_config()
    config.seed = 46
    sim = KubernetriksSimulation(config)
    cluster_trace = generate_cluster_trace(sim)
    workload_trace = generate_workload_trace(sim)
    sim.initialize(cluster_trace, workload_trace)
    sim.run_with_callbacks(RunUntilAllPodsAreFinishedCallbacks())
    return sim.metrics_collector


import pytest


@pytest.mark.slow
def test_simulation_determinism_reference_scale():
    """The reference-scale run (tests/test_determinism.rs:70-126): the
    north-star determinism fact at full size. Minutes-long scalar-python
    repeats — behind -m slow so tier-1 iteration isn't gated on it."""
    global MAX_NODE_EVENTS, MAX_POD_EVENTS, REPEAT_RUNS
    saved = (MAX_NODE_EVENTS, MAX_POD_EVENTS, REPEAT_RUNS)
    MAX_NODE_EVENTS, MAX_POD_EVENTS, REPEAT_RUNS = 1000, 10000, 10
    try:
        test_simulation_determinism()
    finally:
        MAX_NODE_EVENTS, MAX_POD_EVENTS, REPEAT_RUNS = saved


def test_simulation_determinism():
    first = run_simulation()
    assert first.accumulated_metrics.pods_succeeded > 0
    for _ in range(REPEAT_RUNS):
        current = run_simulation()
        assert (
            first.accumulated_metrics.pods_succeeded
            == current.accumulated_metrics.pods_succeeded
        )
        assert (
            first.accumulated_metrics.pod_queue_time_stats
            == current.accumulated_metrics.pod_queue_time_stats
        )
        assert (
            first.accumulated_metrics.pod_scheduling_algorithm_latency_stats
            == current.accumulated_metrics.pod_scheduling_algorithm_latency_stats
        )
        assert (
            first.accumulated_metrics.pod_duration_stats
            == current.accumulated_metrics.pod_duration_stats
        )


def test_oracle_golden_values():
    """Pin the scalar oracle's EXACT metric values for seed 46 at the fast
    scale (VERDICT r1: determinism was asserted run-to-run but nothing
    guarded the oracle itself against silent regressions). Any change to
    event ordering, delay composition, tie-breaks, or the RNG shifts these
    numbers and must be a conscious decision."""
    global MAX_NODE_EVENTS, MAX_POD_EVENTS
    saved = (MAX_NODE_EVENTS, MAX_POD_EVENTS)
    MAX_NODE_EVENTS, MAX_POD_EVENTS = 150, 1500
    try:
        mc = run_simulation()
    finally:
        MAX_NODE_EVENTS, MAX_POD_EVENTS = saved
    m = mc.accumulated_metrics
    assert m.pods_succeeded == 274
    assert m.pod_queue_time_stats.min() == 0.004830714602652006
    assert m.pod_queue_time_stats.max() == 9.917483625002205
    assert m.pod_queue_time_stats.mean() == 4.985349303244703
    assert m.pod_duration_stats.min() == 1.8261357929489908
    assert m.pod_duration_stats.max() == 997.4819772974708
    assert m.pod_duration_stats.mean() == 505.97398806872496
