"""The pending-free channel (batched/step.py `_apply_window_events_work`,
docs/PARITY.md "Freed-resource visibility at cycle boundaries"): a pod's
requests return to the SCHEDULER's allocatable, and wake its unschedulable
queue, one notification chain after the pod left its node. Every case here
holds the batched path to the scalar discrete-event path pod for pod (phase,
node, start time within 5e-6 s) and on the terminal counters, at the
reference's own control-plane delays (src/config.yaml:73-78) and at the test
delays.

On identical nodes every empty node ties, a freed node re-enters the tie and
the tie-break picks by slot, so a free seen a cycle early moves that pod and
every later one: the first cases fail on a build without the channel by a
third to a half of their pods.
"""

import dataclasses

import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.state import (
    PHASE_FAILED,
    PHASE_QUEUED,
    PHASE_REMOVED,
    PHASE_RUNNING,
    PHASE_SUCCEEDED,
    PHASE_UNSCHEDULABLE,
    compare_states,
    held_frees,
)
from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.core.types import PodConditionType
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.telemetry import recorder
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

GiB = 1024**3

DELAYS = {
    # reference src/config.yaml:73-78
    "reference": (0.050, 0.089, 0.023, 0.152, 0.67, 0.50),
    # kubernetriks_tpu/test_util.py DEFAULT_TEST_CONFIG_YAML
    "test": (0.050, 0.010, 0.020, 0.150, 0.30, 0.40),
    "zero": (0.0,) * 6,
}


def config_with(delays: str, suffix: str = "") -> SimulationConfig:
    as_ps, ps_sched, sched_as, as_node, as_ca, as_hpa = DELAYS[delays]
    return SimulationConfig.from_yaml(
        f"""
sim_name: "pending_free"
seed: 123
scheduling_cycle_interval: 10.0
as_to_ps_network_delay: {as_ps}
ps_to_sched_network_delay: {ps_sched}
sched_to_as_network_delay: {sched_as}
as_to_node_network_delay: {as_node}
as_to_ca_network_delay: {as_ca}
as_to_hpa_network_delay: {as_hpa}
"""
        + suffix
    )


def chain(config) -> float:
    """Node -> api server -> storage -> scheduler."""
    return (
        config.as_to_node_network_delay
        + config.as_to_ps_network_delay
        + config.ps_to_sched_network_delay
    )


def bind_delay(config) -> float:
    """Cycle's assignment -> the pod starts on its node."""
    return (
        config.sched_to_as_network_delay
        + 2.0 * config.as_to_ps_network_delay
        + config.as_to_node_network_delay
    )


def node_event(t, name, cpu=64000, ram_gib=128):
    return {
        "timestamp": float(t),
        "event_type": {
            "__tag__": "CreateNode",
            "node": {
                "metadata": {"name": name},
                "status": {"capacity": {"cpu": cpu, "ram": ram_gib * GiB}},
            },
        },
    }


def remove_node_event(t, name):
    return {"timestamp": float(t), "event_type": {"__tag__": "RemoveNode", "node_name": name}}


def pod_event(t, name, duration, cpu=4000, ram_gib=8):
    resources = {"cpu": cpu, "ram": ram_gib * GiB}
    spec = {"resources": {"requests": resources, "limits": resources}}
    if duration is not None:
        spec["running_duration"] = float(duration)
    return {
        "timestamp": float(t),
        "event_type": {"__tag__": "CreatePod", "pod": {"metadata": {"name": name}, "spec": spec}},
    }


def remove_pod_event(t, name):
    return {"timestamp": float(t), "event_type": {"__tag__": "RemovePod", "pod_name": name}}


def montecarlo_events(seed, n_nodes, n_pods, rate=2.0, cpu=4000, ram_gib=8, node_cpu=64000):
    """benchmark/traffic/montecarlo.json's pod shape on identical nodes with
    zero-padded names: Poisson arrivals, 30-120 s durations."""
    rng = np.random.default_rng(seed)
    cluster = [node_event(0.0, f"node_{i:04d}", cpu=node_cpu) for i in range(n_nodes)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_pods))
    workload = [
        pod_event(np.round(t, 3), f"pod_{i:05d}", np.round(rng.uniform(30.0, 120.0), 3), cpu, ram_gib)
        for i, t in enumerate(arrivals)
    ]
    return cluster, workload


def run_scalar(config, cluster, workload, end):
    sim = KubernetriksSimulation(config)
    sim.initialize(GenericClusterTrace(events=list(cluster)), GenericWorkloadTrace(events=list(workload)))
    sim.step_until_time(end)
    return sim


def run_batched(config, cluster, workload, end, n_clusters=1, **kwargs):
    sim = build_batched_from_traces(
        config,
        GenericClusterTrace(events=list(cluster)).convert_to_simulator_events(),
        GenericWorkloadTrace(events=list(workload)).convert_to_simulator_events(),
        n_clusters=n_clusters,
        **kwargs,
    )
    sim.step_until_time(end)
    return sim


def assert_equal_to_scalar(scalar, batched, cluster_idx=0, want_succeeded=1):
    """Every pod's phase, node and start time, and the terminal counters."""
    sm = scalar.metrics_collector.accumulated_metrics
    counters = batched.cluster_metrics(cluster_idx)
    assert counters["pods_succeeded"] == sm.pods_succeeded
    assert counters["pods_removed"] == sm.pods_removed
    assert counters["terminated_pods"] == sm.internal.terminated_pods
    assert sm.pods_succeeded >= want_succeeded
    storage = scalar.persistent_storage
    other_node, other_start = [], []
    for name, pod in batched.pod_view(cluster_idx).items():
        if pod["phase"] == PHASE_SUCCEEDED:
            ref = storage.succeeded_pods.get(name)
            assert ref is not None, f"{name}: succeeded on the batched path alone"
        elif pod["phase"] == PHASE_RUNNING:
            ref = storage.storage_data.pods.get(name)
            assert ref is not None and ref.status.assigned_node, f"{name}: running on the batched path alone"
        elif pod["phase"] == PHASE_UNSCHEDULABLE:
            assert name in storage.unscheduled_pods_cache, name
            continue
        elif pod["phase"] == PHASE_REMOVED:
            assert name not in storage.succeeded_pods and name not in storage.storage_data.pods, name
            continue
        elif pod["phase"] == PHASE_FAILED:
            assert name in storage.failed_pods, name
            continue
        else:
            assert pod["phase"] == PHASE_QUEUED, (name, pod)
            continue
        if ref.status.assigned_node != pod["node"]:
            other_node.append(name)
        started = ref.get_condition(PodConditionType.POD_RUNNING)
        if started is not None and abs(started.last_transition_time - pod["start_time"]) > 5e-6:
            other_start.append(name)
    assert not other_node, f"{len(other_node)} pods on another node than the scalar path's: {other_node[:5]}"
    assert not other_start, f"{len(other_start)} pods started at another time: {other_start[:5]}"


def start_times(batched, cluster_idx=0):
    return {name: pod["start_time"] for name, pod in batched.pod_view(cluster_idx).items()}


# --- identical nodes: the tie that the parent loses --------------------------


@pytest.mark.parametrize(
    "delays,seed,n_nodes,n_pods",
    [
        ("reference", 1, 8, 300),
        ("reference", 2, 32, 400),
        ("reference", 3, 100, 600),
        ("test", 4, 8, 300),
        ("test", 5, 32, 400),
    ],
)
def test_identical_nodes_follow_the_scalar_tie_break(delays, seed, n_nodes, n_pods):
    config = config_with(delays)
    cluster, workload = montecarlo_events(seed, n_nodes, n_pods)
    end = n_pods / 2.0 + 200.0
    scalar = run_scalar(config, cluster, workload, end)
    batched = run_batched(config, cluster, workload, end)
    assert_equal_to_scalar(scalar, batched, want_succeeded=n_pods)
    batched.metrics_summary()
    counters = recorder().counters
    assert counters["frees_total"] == n_pods
    # Finishes spread evenly over a 10 s cycle miss it with chance chain / 10.
    expect = n_pods * chain(config) / 10.0
    assert 0 < counters["frees_deferred"] < 3.0 * expect + 5


# --- contention: the wake half decides ---------------------------------------


@pytest.mark.parametrize("delays", ["reference", "test"])
@pytest.mark.parametrize("conditional_move", [False, True])
def test_contended_cluster_wakes_with_the_scalar_queue(delays, conditional_move):
    """Six identical nodes that hold one pod each and three times the load
    they can carry: every pod after the sixth is parked, and runs only in the
    cycle after a finish has reached the scheduler (the wake), or after the
    30 s flush found it stale. Pods of 4 and of 2 cores, so that the
    conditional move's budget scan moves some and not others."""
    suffix = "enable_unscheduled_pods_conditional_move: true" if conditional_move else ""
    config = config_with(delays, suffix)
    rng = np.random.default_rng(77)
    cluster = [node_event(0.0, f"node_{i:03d}", cpu=4000, ram_gib=8) for i in range(6)]
    arrivals = np.cumsum(rng.exponential(4.0, 120))
    workload = [
        pod_event(
            np.round(t, 3), f"pod_{i:04d}", np.round(rng.uniform(20.0, 60.0), 3),
            cpu=int(rng.choice([4000, 2000])), ram_gib=4,
        )
        for i, t in enumerate(arrivals)
    ]
    end = 3000.0
    scalar = run_scalar(config, cluster, workload, end)
    batched = run_batched(config, cluster, workload, end)
    assert_equal_to_scalar(scalar, batched, want_succeeded=120)
    waits = [
        pod["start_time"] - workload[i]["timestamp"]
        for i, pod in enumerate(batched.pod_view(0).values())
    ]
    assert max(waits) > 100.0, "the cluster was not contended"


# --- a finish placed by construction -----------------------------------------

CYCLE_K = 50.0  # the cycle instant the free is placed against


def two_pods_one_slot(config, visible_at: float, second_created_at: float):
    """One node that holds one pod. `first` is bound in the cycle at 10 s and
    its duration is set so that its free reaches the scheduler at
    `visible_at`; `second` wants the same room."""
    started = 10.0 + 1e-6 + bind_delay(config)  # one node: 1 us of scheduling
    duration = visible_at - chain(config) - started
    cluster = [node_event(0.0, "node_0", cpu=4000, ram_gib=8)]
    workload = [
        pod_event(0.5, "first", duration),
        pod_event(second_created_at, "second", 25.0),
    ]
    return cluster, workload


@pytest.mark.parametrize("delays", ["reference", "test"])
@pytest.mark.parametrize(
    "offset,next_cycle",
    [
        (-0.2, False),  # well before the cycle: this cycle sees the free
        (-2e-6, False),  # two float32 steps (at a 10 s offset) before it
        (2e-6, True),  # two steps after: the next cycle's
        (0.1, True),  # finished on the node before the cycle, heard of after
        (0.29, True),  # still inside the gap under the reference's chain
        (0.6, True),  # finished after the cycle: no gap to be in
    ],
)
@pytest.mark.parametrize("parked", [True, False])
def test_a_finish_round_the_cycle_instant(delays, offset, next_cycle, parked):
    """`parked`: the second pod arrived long before, was parked, and runs
    when the WAKE moves it (then the cycle must also see the room). Not
    parked: it arrives for this very cycle and only the ALLOCATABLE decides
    whether it binds now or is parked for the next."""
    config = config_with(delays)
    cluster, workload = two_pods_one_slot(config, CYCLE_K + offset, 1.0 if parked else CYCLE_K - 5.0)
    scalar = run_scalar(config, cluster, workload, 200.0)
    batched = run_batched(config, cluster, workload, 200.0)
    assert_equal_to_scalar(scalar, batched, want_succeeded=2)
    cycle = CYCLE_K + (10.0 if next_cycle else 0.0)
    assert start_times(batched)["second"] == pytest.approx(cycle + 1e-6 + bind_delay(config), abs=5e-6)


def test_a_free_at_the_cycle_instant_is_the_next_cycles():
    """The equality rule, on the batched path alone (the scalar path's sum of
    float64 delays does not land on the instant): with delays that are sums
    of powers of two the free's visibility is exactly 50 s in pair time, and
    the cycle at 50 s does not see it."""
    config = dataclasses.replace(
        config_with("zero"),
        as_to_ps_network_delay=0.25,
        ps_to_sched_network_delay=0.125,
        sched_to_as_network_delay=0.0,
        as_to_node_network_delay=0.5,
    )
    assert chain(config) == 0.875 and bind_delay(config) == 1.0
    # started = 10 + 1e-6 + 1.0; the 1e-6 is taken back from the duration,
    # in float32 as the engine adds it.
    started_off = np.float32(np.float32(1e-6) + np.float32(1.0))
    for steps, next_cycle in ((0, True), (-1, False)):
        vis_off = np.nextafter(np.float32(10.0), np.float32(0.0)) if steps else np.float32(10.0)
        duration = float(np.float32(vis_off - np.float32(0.875)) - started_off) + 30.0
        cluster = [node_event(0.0, "node_0", cpu=4000, ram_gib=8)]
        workload = [pod_event(0.5, "first", duration), pod_event(1.0, "second", 25.0)]
        batched = run_batched(config, cluster, workload, 200.0)
        cycle = 50.0 + (10.0 if next_cycle else 0.0)
        assert start_times(batched)["second"] == pytest.approx(cycle + 1e-6 + 1.0, abs=5e-6), steps


@pytest.mark.parametrize("delays", ["reference", "test"])
def test_a_chain_longer_than_a_window_is_carried_across_windows(delays):
    """A 1 s cycle under the same delays with the storage-to-scheduler hop
    stretched to 2.5 s: a free crosses two or three cycles on the channel."""
    config = dataclasses.replace(
        config_with(delays), scheduling_cycle_interval=1.0, ps_to_sched_network_delay=2.5
    )
    cluster, workload = montecarlo_events(11, 8, 150, rate=1.0)
    scalar = run_scalar(config, cluster, workload, 400.0)
    batched = run_batched(config, cluster, workload, 400.0)
    assert_equal_to_scalar(scalar, batched, want_succeeded=150)
    batched.metrics_summary()
    assert recorder().counters["frees_deferred"] == 150  # every free misses a cycle


@pytest.mark.parametrize("delays", ["reference", "test"])
def test_remove_pod_of_a_running_pod_inside_the_gap(delays):
    """A service is removed while it runs: the storage drops it, the node
    cancels it one hop later, and the scheduler hears of it a chain after
    that. The removal is placed so that the node's cancel falls before the
    cycle at 50 s and the scheduler's news after it: the parked pod runs in
    the cycle at 60 s."""
    config = config_with(delays)
    to_node = config.as_to_ps_network_delay + config.as_to_node_network_delay
    # storage's drop at t + as_to_ps; the node's cancel `to_node` later
    removed_at = CYCLE_K - 0.05 - to_node - config.as_to_ps_network_delay
    cluster = [node_event(0.0, "node_0", cpu=4000, ram_gib=8)]
    workload = [
        pod_event(0.5, "service", None),
        pod_event(1.0, "second", 25.0),
        remove_pod_event(removed_at, "service"),
    ]
    scalar = run_scalar(config, cluster, workload, 200.0)
    batched = run_batched(config, cluster, workload, 200.0)
    assert_equal_to_scalar(scalar, batched)
    assert batched.pod_view(0)["service"]["phase"] == PHASE_REMOVED
    assert start_times(batched)["second"] == pytest.approx(60.0 + 1e-6 + bind_delay(config), abs=5e-6)


@pytest.mark.parametrize("delays", ["reference", "test"])
def test_node_removal_inside_the_gap(delays):
    """`first` finishes on node_1 a quarter second before the cycle at 50 s,
    node_1 itself goes down between that finish and its news: the free is
    owed to a dead node, and still wakes the parked pod, which finds no room
    until node_2 comes."""
    config = config_with(delays)
    started = 10.0 + 2e-6 + bind_delay(config)
    cluster = [
        node_event(0.0, "node_0", cpu=4000, ram_gib=8),
        node_event(0.0, "node_1", cpu=4000, ram_gib=8),
        # the node goes down at t + 2 as_to_ps + as_to_node
        remove_node_event(
            CYCLE_K - 0.05 - 2 * config.as_to_ps_network_delay - config.as_to_node_network_delay,
            "node_1",
        ),
        node_event(100.0, "node_2", cpu=4000, ram_gib=8),
    ]
    workload = [
        # first in the queue takes node_1: a tie goes to the last name
        pod_event(0.4, "first", CYCLE_K - 0.25 - started),
        pod_event(0.5, "service", None),
        pod_event(1.0, "second", 25.0),
    ]
    scalar = run_scalar(config, cluster, workload, 300.0)
    batched = run_batched(config, cluster, workload, 300.0)
    assert_equal_to_scalar(scalar, batched, want_succeeded=2)
    view = batched.pod_view(0)
    assert view["second"]["node"] == "node_2" and view["first"]["phase"] == PHASE_SUCCEEDED


# --- the same channel under the other executors ------------------------------


def _lane_leaves(state, lane):
    """The leaves a lane of a fleet shares with a standalone run of its
    query: its pods, its nodes' allocatable and its counters (its clock is
    the fleet's)."""
    pods, metrics = state.pods, state.metrics
    leaves = {
        "phase": pods.phase, "node": pods.node,
        "start.win": pods.start_time.win, "start.off": pods.start_time.off,
        "finish.win": pods.finish_time.win, "finish.off": pods.finish_time.off,
        "queue_ts.off": pods.queue_ts.off, "attempts": pods.attempts,
        "alloc_cpu": state.nodes.alloc_cpu, "alloc_ram": state.nodes.alloc_ram,
        "alive": state.nodes.alive,
    }
    for name in ("pods_succeeded", "scheduling_decisions", "frees_total", "frees_deferred"):
        leaves[name] = getattr(metrics, name)
    return {name: np.asarray(leaf)[lane] for name, leaf in leaves.items()}


@pytest.mark.parametrize("delays", ["reference", "test"])
def test_sliding_window_and_superspan_equal_the_ladder(delays):
    """A pod window a third of the trace, slid by the ladder and by the
    superspan executor: a pod on the channel is never slid out with its
    free owed (state.slide_phase), so the two leave the same state leaf for
    leaf, which is the scalar path's pod for pod and the resident build's
    on every leaf they share."""
    config = config_with(delays)
    cluster, workload = montecarlo_events(21, 16, 700)
    end = 560.0
    scalar = run_scalar(config, cluster, workload, end)
    resident = run_batched(config, cluster, workload, end, n_clusters=2)
    assert_equal_to_scalar(scalar, resident, cluster_idx=1, want_succeeded=600)
    ladder = run_batched(
        config, cluster, workload, end, n_clusters=2, pod_window=256, superspan=False
    )
    superspan = run_batched(
        config, cluster, workload, end, n_clusters=2, pod_window=256,
        superspan=True, superspan_k=4, superspan_chunk=4,
    )
    assert superspan.dispatch_stats["superspans"] > 0
    assert compare_states(ladder.state, superspan.state) == []
    for slid in (ladder, superspan):
        assert slid._pod_base > 0, "the window never slid"
        assert_equal_to_scalar(scalar, slid, want_succeeded=600)
        for name in ("alloc_cpu", "alloc_ram"):
            np.testing.assert_array_equal(
                np.asarray(getattr(resident.state.nodes, name)),
                np.asarray(getattr(slid.state.nodes, name)),
            )
        for name in ("pods_succeeded", "scheduling_decisions", "frees_total", "frees_deferred"):
            np.testing.assert_array_equal(
                np.asarray(getattr(resident.state.metrics, name)),
                np.asarray(getattr(slid.state.metrics, name)),
            )


@pytest.mark.parametrize("delays", ["reference", "test"])
def test_a_fleet_lane_equals_the_standalone_run(delays):
    """Three queries over two lane-async lanes, so one lane is re-seeded in
    place (frozen at 150 s with frees on the channel until then) and both
    end frozen at their last query's horizon, one of them with a free still
    on the channel: each lane's pods, allocatable and counters equal a
    standalone ladder run to that horizon."""
    from kubernetriks_tpu.batched.fleet import Scenario, ScenarioFleet

    config = config_with(delays)
    cluster, workload = montecarlo_events(41, 8, 500)
    as_events = lambda: (  # noqa: E731
        GenericClusterTrace(events=list(cluster)).convert_to_simulator_events(),
        GenericWorkloadTrace(events=list(workload)).convert_to_simulator_events(),
    )
    fleet = ScenarioFleet(
        config, *as_events(), n_lanes=2, horizon=400.0, use_pallas=False, lane_async=True
    )
    horizons = [400.0, 150.0, 260.0]  # at 150 s and at 260 s frees are on the channel
    qids = [fleet.submit(Scenario(), h) for h in horizons]
    fleet.run_async()
    last_on_lane = {}
    for qid, horizon in zip(qids, horizons):
        assert fleet.results[qid].ok
        last_on_lane[fleet.results[qid].lane] = horizon  # in submission order
    assert sorted(last_on_lane) == [0, 1]
    assert sorted(last_on_lane.values()) == [260.0, 400.0]
    for lane, horizon in last_on_lane.items():
        alone = run_batched(config, cluster, workload, horizon, use_pallas=False)
        got, want = _lane_leaves(fleet.engine.state, lane), _lane_leaves(alone.state, 0)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name], err_msg=f"lane {lane}: {name}")
        assert bool(np.asarray(held_frees(alone.state.pods)).any()) == (horizon == 260.0)
    fleet.close()


POD_FAULTS = """
fault_injection:
  enabled: true
  pod:
    fail_prob: 0.2
    backoff_base: {backoff}
    backoff_cap: 300.0
    restart_limit: 2
"""


@pytest.mark.parametrize("delays", ["reference", "test"])
@pytest.mark.parametrize("backoff", [10.0, 0.05])
def test_failed_attempts_free_through_the_channel(delays, backoff):
    """Chaos: an attempt that fails on its node frees like a finish, one
    chain later, and its retry enters the queue no earlier (a backoff
    shorter than the chain is floored at it). On identical nodes, where the
    freed node re-enters the tie."""
    config = config_with(delays, POD_FAULTS.format(backoff=backoff))
    cluster, workload = montecarlo_events(51, 12, 400)
    end = 900.0
    scalar = run_scalar(config, cluster, workload, end)
    batched = run_batched(config, cluster, workload, end)
    assert_equal_to_scalar(scalar, batched, want_succeeded=300)
    sm = scalar.metrics_collector.accumulated_metrics
    counters = batched.metrics_summary()["counters"]
    assert counters["pod_restarts"] == sm.pod_restarts > 20
    assert counters["pods_failed"] == sm.pods_failed > 0
    failed = scalar.persistent_storage.failed_pods
    for name, pod in batched.pod_view(0).items():
        assert (pod["phase"] == PHASE_FAILED) == (name in failed), name
    # every attempt frees once: the finishes, the retried and the failed
    assert recorder().counters["frees_total"] == (
        counters["pods_succeeded"] + counters["pod_restarts"] + counters["pods_failed"]
    )
    assert recorder().counters["frees_deferred"] > 0


@pytest.mark.parametrize("lane_major", [False, True])
def test_the_free_kernel_carries_the_channel(lane_major):
    """The dense kernel set, interpreted: the free kernel visits the visible
    frees and the window's finishes in one launch, a deferred finish naming
    no node. Every leaf equals the scan engine's, the duration estimator
    to the metric tolerance."""
    config = config_with("reference")
    cluster, workload = montecarlo_events(61, 16, 260)

    def build(pallas):
        sim = build_batched_from_traces(
            config,
            GenericClusterTrace(events=list(cluster)).convert_to_simulator_events(),
            GenericWorkloadTrace(events=list(workload)).convert_to_simulator_events(),
            n_clusters=3,
            max_pods_per_cycle=32,
            use_pallas=pallas,
            pallas_interpret=pallas,
            lane_major=lane_major and pallas,
        )
        if pallas:  # the build-time gates want 128 clusters
            sim.use_pallas_select = True
            sim.use_megakernel = True
        sim.step_until_time(330.0)
        return sim

    plain, kernels = build(False), build(True)
    assert kernels.kernel_formulation()["cycle"] == "megakernel"
    assert compare_states(plain.state, kernels.state) == []
    assert int(np.asarray(plain.state.metrics.frees_deferred).sum()) > 0


def test_the_ring_column_sums_to_the_counter():
    """Ring on: the column `frees_deferred` beside `event_chunks` holds each
    window's growth of the counter, a cluster."""
    config = config_with("reference")
    cluster, workload = montecarlo_events(71, 8, 240)
    sim = run_batched(config, cluster, workload, 330.0, n_clusters=2, telemetry=True)
    deferred = int(np.asarray(sim.state.metrics.frees_deferred).sum())
    ring = sim.telemetry_report()["ring"]
    assert "frees_deferred" in ring["columns"]
    assert ring["totals"]["frees_deferred"] == deferred > 0
    plain = run_batched(config, cluster, workload, 330.0, n_clusters=2)
    assert compare_states(plain.state, sim.state._replace(telemetry=None)) == []


def test_zero_delays_build_no_channel():
    """With the three delays of the chain at zero the constants carry None,
    no free is ever held past its window, and the window program has the
    channel in neither its arguments nor its body."""
    config = config_with("zero")
    cluster, workload = montecarlo_events(31, 8, 200)
    sim = run_batched(config, cluster, workload, 55.0)
    assert sim.consts.delta_free_visible is None and sim.consts.delta_free_unbind is None
    assert not bool(np.asarray(held_frees(sim.state.pods)).any())
    sim.step_until_time(400.0)
    sim.metrics_summary()
    assert recorder().counters["frees_deferred"] == 0
    assert recorder().counters["frees_total"] == 200
    delayed = run_batched(config_with("reference"), cluster, workload, 55.0)
    assert delayed.consts.delta_free_visible == pytest.approx(0.291)
    assert delayed.consts.delta_free_unbind == pytest.approx(0.202)
