"""The scheduling cycle drains its queue (step._run_scheduling_cycle): every
pod eligible at a cycle's start is assigned or parked in it, as the scalar
scheduler's `while pop` does, whatever max_pods_per_cycle, the size of one
pass, is. (a) the batched path against the scalar path pod for pod under
bursts several passes deep, in all four formulations, with the reference's
control-plane delays, with parks (a cluster too small for its burst: the
30 s flush, the conditional move) and with the spread filter; (b) the state
after a run does not depend on the pass size; (c) a cycle whose simulated
duration reaches the interval is counted; (d) a mesh of four CPU devices
gives the one-device state.

Semantics: docs/PARITY.md "The scheduling cycle drains".
"""

import jax
import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import BatchedSimulation, build_batched_from_traces
from kubernetriks_tpu.batched.state import PHASE_SUCCEEDED, PHASE_UNSCHEDULABLE
from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
from kubernetriks_tpu.core.types import PodConditionType
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from spread_traces import ZONE_KEY
from test_pending_free import config_with, node_event, pod_event
from tests.sharded_builds import mesh_of

END = 700.0
PASS = 8
FORMULATIONS = ["scan", "candidate", "select", "megakernel"]
# Leaves that count by the pass size: the four that depend on it by
# definition (MetricArrays.cycle_passes, .cycle_late_decisions, and
# .cycle_deep / .cycle_compacted, the cycles deeper than one pass).
PASS_COUNTERS = ("cycle_passes", "cycle_late_decisions", "cycle_deep", "cycle_compacted")


def burst_events(seed, n_nodes=12, bursts=(24, 56, 80), rate=0.4, node_cpu=16000, labelled=False):
    """`n_nodes` identical nodes (four 4-core pods each), a Poisson background
    and bursts of pods created at one instant, a burst a list entry: 3 to 10
    passes of PASS. With 12 nodes the cluster holds 48 pods, so the larger
    bursts park what does not fit. Names zero-padded: the scalar scheduler's
    sorted-name tie-break is then the batched path's slot order. `labelled`:
    nodes over three zones and two thirds of the pods in two zone-spread
    workloads (maxSkew 1, selector = own label)."""
    rng = np.random.default_rng(seed)
    cluster = [node_event(0.0, f"node_{i:03d}", cpu=node_cpu, ram_gib=32) for i in range(n_nodes)]
    if labelled:
        for i, event in enumerate(cluster):
            event["event_type"]["node"]["metadata"]["labels"] = {ZONE_KEY: f"zone-{i % 3}"}
    times = list(np.round(np.cumsum(rng.exponential(1.0 / rate, int(rate * 500))), 3))
    for size in bursts:
        times += [float(np.round(rng.uniform(40.0, 450.0), 3))] * size
    workload = []
    for i, t in enumerate(sorted(times)):
        event = pod_event(t, f"pod_{i:05d}", np.round(rng.uniform(20.0, 90.0), 3), 4000, 8)
        w = int(rng.integers(3))
        if labelled and w < 2:
            pod = event["event_type"]["pod"]
            pod["metadata"]["labels"] = {"color": f"c{w}"}
            pod["spec"]["topology_spread_constraints"] = [
                {
                    "max_skew": 1,
                    "topology_key": ZONE_KEY,
                    "when_unsatisfiable": "DoNotSchedule",
                    "label_selector": {"match_labels": {"color": f"c{w}"}},
                }
            ]
        workload.append(event)
    return cluster, workload


def traces(cluster, workload):
    return GenericClusterTrace(events=list(cluster)), GenericWorkloadTrace(events=list(workload))


def scalar_run(config, cluster, workload, end=END):
    sim = KubernetriksSimulation(config)
    sim.initialize(*traces(cluster, workload))
    sim.step_until_time(end)
    return sim


def batched_run(config, cluster, workload, formulation, end=END, n_clusters=2, **kwargs):
    if formulation != "scan":
        kwargs.update(use_pallas=True, pallas_interpret=True)
    kwargs.setdefault("max_pods_per_cycle", PASS)
    c, w = traces(cluster, workload)
    sim = build_batched_from_traces(
        config, c.convert_to_simulator_events(), w.convert_to_simulator_events(),
        n_clusters=n_clusters, **kwargs,
    )
    if formulation in ("select", "megakernel"):
        # Below 128 clusters the gates pick the candidate kernel; forcing the
        # dense set needs both (.claude/skills/verify, Gotchas).
        sim.use_pallas_select = True
        sim.use_megakernel = formulation == "megakernel"
    assert sim.kernel_formulation()["cycle"] == formulation
    sim.step_until_time(end)
    return sim


def assert_pod_for_pod(scalar, batched, cluster=1, start_tol=5e-6):
    """Phase, node and start time of every pod, the terminal counters, and
    the queue-time estimator: its count exactly, its extremes and mean to
    float32. Returns the pods the run left parked."""
    storage = scalar.persistent_storage
    parked = 0
    for name, row in batched.pod_view(cluster).items():
        if row["phase"] == PHASE_UNSCHEDULABLE:
            assert name in storage.unscheduled_pods_cache, name
            parked += 1
            continue
        ref = storage.succeeded_pods.get(name) or storage.storage_data.pods.get(name)
        assert ref is not None, name
        assert (row["phase"] == PHASE_SUCCEEDED) == (name in storage.succeeded_pods), name
        if ref.status.assigned_node or row["node"]:
            assert row["node"] == ref.status.assigned_node, name
            start = ref.get_condition(PodConditionType.POD_RUNNING)
            if start is not None and row["start_time"] is not None:
                assert row["start_time"] == pytest.approx(start.last_transition_time, abs=start_tol), name
    sm = scalar.metrics_collector.accumulated_metrics
    counters = batched.cluster_metrics(cluster)
    assert counters["pods_succeeded"] == sm.pods_succeeded
    assert counters["terminated_pods"] == sm.internal.terminated_pods
    assert counters["scheduling_decisions"] == sm.pod_queue_time_stats.count()
    est = jax.tree.map(lambda x: np.asarray(x)[cluster], batched.state.metrics.queue_time)
    assert int(est.count) == sm.pod_queue_time_stats.count()
    assert float(est.minimum) == pytest.approx(sm.pod_queue_time_stats.min(), abs=1e-4)
    assert float(est.maximum) == pytest.approx(sm.pod_queue_time_stats.max(), abs=1e-4)
    assert float(est.total) / int(est.count) == pytest.approx(sm.pod_queue_time_stats.mean(), rel=1e-5)
    return parked


def drain_counters(sim):
    sim.metrics_summary()
    report = sim.telemetry_report()["counters"]
    return {k: v for k, v in report.items() if k.startswith("cycle_")}


# --- (a) batched against scalar ------------------------------------------------

CASES = {
    # name: (delays, config suffix, trace kwargs, pods the run must have parked at some time)
    "bursts": ("zero", "", dict(seed=3, n_nodes=40, bursts=(24, 56, 80)), False),
    "bursts-delays": ("reference", "", dict(seed=4, n_nodes=40, bursts=(24, 56, 80)), False),
    "parks-flush": ("zero", "", dict(seed=5, bursts=(40, 72)), True),
    "parks-flush-delays": ("reference", "", dict(seed=6, bursts=(40, 72)), True),
    "parks-conditional-move": (
        "zero", "enable_unscheduled_pods_conditional_move: true\n", dict(seed=7, bursts=(40, 72)), True,
    ),
    "spread": ("zero", "scheduler_profile: topology_spread\n", dict(seed=8, n_nodes=18, bursts=(24, 64), labelled=True), True),
}


@pytest.mark.parametrize("formulation", FORMULATIONS)
@pytest.mark.parametrize("case", list(CASES))
def test_drained_cycle_equals_scalar_pod_for_pod(case, formulation):
    delays, suffix, trace_kwargs, parks = CASES[case]
    config = config_with(delays, suffix)
    cluster, workload = burst_events(**trace_kwargs)
    scalar = scalar_run(config, cluster, workload)
    batched = batched_run(config, cluster, workload, formulation)
    assert_pod_for_pod(scalar, batched)
    assert (batched.state.spread is not None) == (case == "spread")
    counted = drain_counters(batched)
    # Both clusters of the batch drained their deepest burst in one cycle, in
    # 3 to 10 passes, and most assignments came after a cycle's first pass.
    deepest = max(trace_kwargs["bursts"])
    assert counted["cycle_deepest"] >= deepest
    assert counted["cycle_passes"] > counted["cycle_count"]
    assert counted["cycle_late_decisions"] > 0
    assert counted["cycle_overruns"] == 0
    # A cluster too small for its burst parked what did not fit, in the same
    # cycle, and the scalar path took the same pods back on a flush or a move.
    parked_ever = int(np.asarray(batched.state.pods.attempts).max()) > 1
    assert parked_ever == parks


def test_capped_engine_would_have_differed():
    """The control of (a): what the drain is for. A pod of a burst deeper
    than the pass starts in the cycle it was eligible in, one algorithm
    latency after the pod before it; bounded at the pass size it would have
    started whole intervals later."""
    config = config_with("zero")
    cluster, workload = burst_events(seed=3, n_nodes=40, bursts=(80,), rate=0.05)
    batched = batched_run(config, cluster, workload, "scan", n_clusters=1)
    starts = sorted(
        row["start_time"] for row in batched.pod_view(0).values() if row["start_time"] is not None
    )
    burst = max(np.bincount(np.floor(np.asarray(starts) / 10.0).astype(int)))
    assert burst >= 80  # one cycle placed the whole burst


# --- (b) the pass size changes no result ----------------------------------------


def leaves_differing(a, b, skip=PASS_COUNTERS):
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(flat_a) == len(flat_b)
    return [
        jax.tree_util.keystr(path)
        for (path, x), (_, y) in zip(flat_a, flat_b)
        if not any(name in jax.tree_util.keystr(path) for name in skip)
        and not np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
    ]


@pytest.mark.parametrize("formulation", FORMULATIONS)
@pytest.mark.parametrize("case", ["parks-flush-delays", "spread"])
def test_state_is_bit_identical_for_any_pass_size(case, formulation):
    delays, suffix, trace_kwargs, _ = CASES[case]
    config = config_with(delays, suffix)
    cluster, workload = burst_events(**trace_kwargs)
    runs = {
        k: batched_run(config, cluster, workload, formulation, max_pods_per_cycle=k)
        for k in (4, 16, 64, None)
    }
    whole = runs.pop(None)
    assert whole.max_pods_per_cycle == whole.n_pods
    for k, sim in runs.items():
        assert leaves_differing(whole.state, sim.state) == [], k
    # The pass counters are the only leaves that know the pass size.
    passes = {k: drain_counters(sim)["cycle_passes"] for k, sim in runs.items()}
    assert passes[4] > passes[16] > passes[64] >= drain_counters(whole)["cycle_passes"]
    assert drain_counters(whole)["cycle_late_decisions"] == 0


# --- (c) a cycle as long as the interval is counted ------------------------------


def overrun_events(n_pods):
    """Eight roomy nodes and one burst. With the scheduler's latency model
    raised to 0.125 s a node, a pod's algorithm latency is 1 s, so ten pods
    make a cycle as long as the 10 s interval: interval / (time a node x
    nodes) pods, 10,000 on the benchmark's 1000 nodes at the reference's
    1 us."""
    cluster = [node_event(0.0, f"node_{i:03d}", cpu=64000) for i in range(8)]
    workload = [pod_event(25.0, f"pod_{i:05d}", 40.0, 1000, 1) for i in range(n_pods)]
    return cluster, workload


@pytest.mark.parametrize("formulation", ["scan", "megakernel"])
@pytest.mark.parametrize("n_pods,overruns", [(9, 0), (10, 1), (23, 1)])
def test_overrun_counter(n_pods, overruns, formulation):
    kwargs = {} if formulation == "scan" else dict(use_pallas=True, pallas_interpret=True)
    c, w = traces(*overrun_events(n_pods))
    sim = build_batched_from_traces(
        config_with("zero"), c.convert_to_simulator_events(), w.convert_to_simulator_events(),
        n_clusters=2, max_pods_per_cycle=4, **kwargs,
    )
    if formulation == "megakernel":
        sim.use_pallas_select = sim.use_megakernel = True
    sim.consts = sim.consts._replace(time_per_node=0.125)
    sim.step_until_time(200.0)
    counted = drain_counters(sim)
    assert counted["cycle_deepest"] == n_pods
    assert counted["cycle_overruns"] == 2 * overruns  # summed over the two clusters
    assert counted["cycle_decisions"] == 2 * n_pods


# --- (d) the mesh ---------------------------------------------------------------


@pytest.mark.parametrize("formulation", ["scan", "megakernel"])
def test_mesh_of_four_gives_the_one_device_state(formulation):
    """Every cluster a workload of its own (bursts at other instants, of
    other depths), so the shards' pass loops run other trip counts: each
    shard runs its own loop, and no leaf moves."""
    config = config_with("zero")
    compiled = []
    for i in range(8):
        cluster, workload = burst_events(seed=20 + i, bursts=(24 + 8 * (i % 4), 40 + 4 * i))
        c, w = traces(cluster, workload)
        compiled.append(
            compile_cluster_trace(c.convert_to_simulator_events(), w.convert_to_simulator_events(), config)
        )

    def run(**kwargs):
        if formulation != "scan":
            kwargs.update(use_pallas=True, pallas_interpret=True)
        sim = BatchedSimulation(config, compiled, max_pods_per_cycle=PASS, **kwargs)
        if formulation == "megakernel":
            sim.use_pallas_select = sim.use_megakernel = True
        assert sim.kernel_formulation()["cycle"] == formulation
        sim.step_until_time(END)
        return sim

    one, four = run(), run(mesh=mesh_of(4))
    assert four.kernel_formulation()["sharding"] is not None
    assert leaves_differing(one.state, four.state, skip=()) == []
    assert drain_counters(four)["cycle_deepest"] >= 68
