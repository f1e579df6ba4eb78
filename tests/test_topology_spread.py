"""PodTopologySpread (DoNotSchedule) on both paths: the scalar plugin's
arithmetic (core/scheduler/plugins.py), the batched lowering held to it pod
for pod in all four cycle formulations (batched/pipeline.py,
ops/scheduler_kernel.py), the control (the same traces with the filter off
land elsewhere), every refusal by name, and the structural promise that a
build without constraints compiles the programs it compiled before.

Semantics: docs/PARITY.md "Topology spread".
"""

import dataclasses
import json

import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.pipeline import UnsupportedProfileError
from kubernetriks_tpu.batched.state import (
    PHASE_REMOVED,
    PHASE_SUCCEEDED,
    PHASE_UNSCHEDULABLE,
)
from kubernetriks_tpu.core.scheduler.interface import SchedulingFailure
from kubernetriks_tpu.core.scheduler.kube_scheduler import (
    KubeScheduler,
    kube_scheduler_config_from_spec,
)
from kubernetriks_tpu.core.scheduler.plugins import (
    SchedulerCache,
    UnsupportedSpreadConstraint,
)
from kubernetriks_tpu.core.types import Node, Pod, PodConditionType, TopologySpreadConstraint
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from spread_traces import GiB, ZONE_KEY, spread_traces
from test_pending_free import config_with

END = 3000.0


# --- (a) the scalar plugin ----------------------------------------------------


def _node(name, zone=None, cpu=4000):
    node = Node.new(name, cpu, 8 * GiB)
    if zone is not None:
        node.metadata.labels[ZONE_KEY] = zone
    return node


def _pod(name, color=None, constrained=True, skew=1, selector=None, cpu=1000):
    pod = Pod.new(name, cpu, GiB, 10.0)
    if color is not None:
        pod.metadata.labels["color"] = color
    if constrained:
        pod.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=skew,
                topology_key=ZONE_KEY,
                match_labels={"color": color} if selector is None else selector,
            )
        ]
    return pod


class _Cache:
    """A scheduler's cache by hand: nodes, and pods placed on them."""

    def __init__(self, nodes):
        self.view = SchedulerCache(nodes={n.metadata.name: n for n in nodes})
        self.algorithm = KubeScheduler(kube_scheduler_config_from_spec("topology_spread"))

    def place(self, pod, node_name):
        self.view.pods[pod.metadata.name] = pod
        self.view.assignments.setdefault(node_name, set()).add(pod.metadata.name)
        requests = pod.spec.resources.requests
        self.view.nodes[node_name].status.allocatable.cpu -= requests.cpu

    def schedule(self, pod):
        return self.algorithm.schedule_one(pod, self.view.nodes, self.view)

    def admitted(self, pod):
        """The nodes the whole filter chain leaves, by trying each alone."""
        from kubernetriks_tpu.core.scheduler.plugins import PLUGIN_REGISTRY, TOPOLOGY_SPREAD

        nodes = [self.view.nodes[k] for k in sorted(self.view.nodes)]
        return [
            n.metadata.name for n in PLUGIN_REGISTRY[TOPOLOGY_SPREAD].filter(pod, nodes, self.view)
        ]


def test_skew_arithmetic_opens_only_the_least_loaded_domains():
    cache = _Cache([_node("a1", "a"), _node("a2", "a"), _node("b1", "b"), _node("c1", "c")])
    cache.place(_pod("p1", "blue"), "a1")
    cache.place(_pod("p2", "blue"), "a2")
    cache.place(_pod("p3", "blue"), "b1")
    # match = a:2, b:1, c:0; self = 1; minMatch = 0. maxSkew 1 admits c only,
    # 2 admits b and c, 3 everything.
    assert cache.admitted(_pod("q", "blue", skew=1)) == ["c1"]
    assert cache.admitted(_pod("q", "blue", skew=2)) == ["b1", "c1"]
    assert cache.admitted(_pod("q", "blue", skew=3)) == ["a1", "a2", "b1", "c1"]


def test_self_counts_only_where_the_pod_matches_its_own_selector():
    cache = _Cache([_node("a1", "a"), _node("b1", "b")])
    cache.place(_pod("p1", "blue"), "a1")
    # match = a:1, b:0. A red pod whose selector asks for blue adds no self:
    # 1 + 0 - 0 <= 1 admits a; a blue pod adds itself and a closes.
    assert cache.admitted(_pod("q", "red", selector={"color": "blue"})) == ["a1", "b1"]
    assert cache.admitted(_pod("q", "blue")) == ["b1"]


def test_node_without_the_key_fails_and_is_in_no_domain():
    cache = _Cache([_node("a1", "a"), _node("n1", None), _node("b1", "b")])
    # A matching pod on the keyless node counts nowhere.
    cache.place(_pod("p1", "blue"), "n1")
    assert cache.admitted(_pod("q", "blue")) == ["a1", "b1"]
    # An unconstrained pod may still go there.
    assert "n1" in cache.admitted(_pod("u", "blue", constrained=False))


def test_pod_matching_another_workloads_selector_counts_for_it():
    cache = _Cache([_node("a1", "a"), _node("b1", "b")])
    # An unconstrained pod labelled blue sits in a: it counts for blue's
    # workload, not for red's.
    cache.place(_pod("u", "blue", constrained=False), "a1")
    assert cache.admitted(_pod("q", "blue")) == ["b1"]
    assert cache.admitted(_pod("q", "red")) == ["a1", "b1"]


def test_full_zone_holds_the_minimum_down():
    """Resource fit plays no part in the domains: zone b is full and empty of
    blue pods, so minMatch stays 0 and zone a (one blue pod) is closed. The
    pod is unschedulable although a1 has room."""
    cache = _Cache([_node("a1", "a", cpu=4000), _node("b1", "b", cpu=1000)])
    cache.place(_pod("filler", "grey", constrained=False), "b1")  # b1 now full
    cache.place(_pod("p1", "blue"), "a1")
    with pytest.raises(SchedulingFailure):
        cache.schedule(_pod("q", "blue"))
    assert cache.schedule(_pod("q", "blue", skew=2)) == "a1"


def _events(nodes, pods):
    cluster = [
        {
            "timestamp": 0.0,
            "event_type": {
                "__tag__": "CreateNode",
                "node": {
                    "metadata": {"name": name, "labels": {ZONE_KEY: zone}},
                    "status": {"capacity": {"cpu": cpu, "ram": 16 * GiB}},
                },
            },
        }
        for name, zone, cpu in nodes
    ]
    workload = [
        {"timestamp": t, "event_type": {"__tag__": "CreatePod", "pod": pod.to_dict()}}
        for t, pod in pods
    ]
    return GenericClusterTrace(events=cluster), GenericWorkloadTrace(events=workload)


def _full_zone_scenario():
    """Zone b's one node (3 cores) is filled by a grey 3-core pod that fits
    nowhere else, until t = 74; a blue pod runs in zone a (2 cores). A second
    blue pod arrives at t = 12: zone a is closed to it (1 + 1 - 0 > 1), zone
    b is open and full. It parks, the filler's finish wakes it, and the cycle
    at t = 80 puts it in zone b."""

    def pod(name, color, duration, constrained, cpu):
        p = _pod(name, color, constrained=constrained, cpu=cpu)
        p.spec.running_duration = duration
        p.spec.resources.limits.cpu = cpu
        return p

    return _events(
        [("node_a", "a", 2000), ("node_b", "b", 3000)],
        [
            (1.0, pod("pod_0_filler", "grey", 64.0, False, 3000)),
            (2.0, pod("pod_1_blue", "blue", 500.0, True, 1000)),
            (12.0, pod("pod_2_blue", "blue", 20.0, True, 1000)),
        ],
    )


def test_full_zone_scalar_run_parks_the_pod_until_a_finish_in_that_zone():
    config = dataclasses.replace(config_with("zero"), scheduler_profile="topology_spread")
    sim = KubernetriksSimulation(config)
    sim.initialize(*_full_zone_scenario())
    sim.step_until_time(50.0)
    assert "pod_2_blue" in sim.persistent_storage.unscheduled_pods_cache
    sim.step_until_time(700.0)
    done = sim.persistent_storage.succeeded_pods
    assert done["pod_0_filler"].status.assigned_node == "node_b"
    assert done["pod_1_blue"].status.assigned_node == "node_a"
    assert done["pod_2_blue"].status.assigned_node == "node_b"
    started = done["pod_2_blue"].get_condition(PodConditionType.POD_RUNNING).last_transition_time
    assert started == pytest.approx(80.0, abs=1e-3)


def test_constraint_roundtrips_through_the_generic_trace():
    pod = _pod("p", "blue", skew=2)
    back = Pod.from_dict(pod.to_dict())
    assert back.spec.topology_spread_constraints == pod.spec.topology_spread_constraints
    assert back.metadata.labels == {"color": "blue"}
    upstream = Pod.from_dict(
        {
            "metadata": {"name": "p"},
            "spec": {
                "topologySpreadConstraints": [
                    {
                        "maxSkew": 2,
                        "topologyKey": ZONE_KEY,
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"color": "blue"}},
                    }
                ]
            },
        }
    )
    assert upstream.spec.topology_spread_constraints == pod.spec.topology_spread_constraints


# --- (b) batched against scalar ------------------------------------------------


def _compare(scalar, batched, cluster=0, start_tol=5e-6):
    """(pods compared, pods on another node or in another phase)."""
    view = batched.pod_view(cluster)
    succeeded = scalar.persistent_storage.succeeded_pods
    parked = scalar.persistent_storage.unscheduled_pods_cache
    wrong = 0
    for name, row in view.items():
        if row["phase"] == PHASE_SUCCEEDED:
            pod = succeeded.get(name)
            if pod is None or pod.status.assigned_node != row["node"]:
                wrong += 1
                continue
            start = pod.get_condition(PodConditionType.POD_RUNNING).last_transition_time
            assert row["start_time"] == pytest.approx(start, abs=start_tol), name
        elif name in succeeded:
            wrong += 1
        elif row["phase"] == PHASE_UNSCHEDULABLE:
            wrong += int(name not in parked)
        elif row["phase"] == PHASE_REMOVED:
            wrong += int(name in succeeded)
    wrong += sum(1 for name in succeeded if name not in view)
    return len(view), wrong


def _scalar_run(config, traces):
    sim = KubernetriksSimulation(config)
    sim.initialize(*traces)
    sim.step_until_time(END)
    return sim


def _batched_run(config, traces, formulation, n_clusters=2, **kwargs):
    cluster, workload = traces
    if formulation != "scan":
        kwargs.update(use_pallas=True, pallas_interpret=True)
    sim = build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=n_clusters,
        **kwargs,
    )
    if formulation in ("select", "megakernel"):
        # Below 128 clusters the gates pick the candidate kernel; forcing the
        # dense set needs both (.claude/skills/verify, Gotchas).
        sim.use_pallas_select = True
        sim.use_megakernel = formulation == "megakernel"
    assert sim.kernel_formulation()["cycle"] == formulation
    sim.step_until_time(END)
    return sim


def _spread_config(delays="zero", profile="topology_spread"):
    return dataclasses.replace(config_with(delays), scheduler_profile=profile)


SWEEP = [
    # seed, nodes, pods, G, Z, maxSkew, delays
    (11, 8, 90, 1, 2, 1, "zero"),
    (12, 8, 90, 3, 3, 1, "zero"),
    (13, 32, 260, 8, 5, 2, "zero"),
    (14, 32, 260, 3, 3, 1, "reference"),
    (15, 100, 420, 8, 3, 1, "zero"),
]


@pytest.mark.parametrize("formulation", ["scan", "candidate", "select", "megakernel"])
@pytest.mark.parametrize("seed,nodes,pods,G,Z,skew,delays", SWEEP)
def test_batched_equals_scalar_pod_for_pod(formulation, seed, nodes, pods, G, Z, skew, delays):
    config = _spread_config(delays)
    scalar = _scalar_run(config, spread_traces(seed, nodes, pods, G, Z, skew))
    batched = _batched_run(config, spread_traces(seed, nodes, pods, G, Z, skew), formulation)
    assert batched.state.spread is not None
    assert batched.state.spread.max_skew.shape[1:] == (G, Z)
    n, wrong = _compare(scalar, batched, cluster=1)
    assert (n, wrong) == (pods, 0)
    sm = scalar.metrics_collector.accumulated_metrics
    counters = batched.metrics_summary()["counters"]
    assert counters["pods_succeeded"] == 2 * sm.pods_succeeded
    assert counters["terminated_pods"] == 2 * sm.internal.terminated_pods
    report = batched.telemetry_report()["counters"]
    assert 0 < report["spread_decisions_bound"] <= report["spread_decisions"]


@pytest.mark.parametrize("formulation", ["scan", "megakernel"])
def test_full_zone_batched(formulation):
    """The full-zone scenario of the scalar test: the batched path parks the
    pod and starts it in the cycle after the finish, as the scalar path does."""
    config = _spread_config("zero")
    scalar = _scalar_run(config, _full_zone_scenario())
    batched = _batched_run(config, _full_zone_scenario(), formulation)
    assert _compare(scalar, batched) == (3, 0)
    assert batched.pod_view(0)["pod_2_blue"]["node"] == "node_b"


@pytest.mark.parametrize("delays", ["zero", "test"])
def test_slid_pod_window_reads_its_own_columns_of_the_pod_planes(delays):
    """A sliding pod window: the spread planes stay in global pod
    coordinates and the window cuts its columns at pod_base."""
    config = _spread_config(delays)
    args = (21, 16, 600, 3, 3, 1)
    kwargs = dict(horizon=2400.0)
    scalar = _scalar_run(config, spread_traces(*args, **kwargs))
    batched = _batched_run(
        config, spread_traces(*args, **kwargs), "scan", pod_window=128, superspan=False
    )
    assert batched._pod_base > 0, "the window never slid"
    assert batched.state.spread.pod_group.shape[1] > batched.state.pods.phase.shape[1]
    sm = scalar.metrics_collector.accumulated_metrics
    counters = batched.metrics_summary()["counters"]
    assert counters["pods_succeeded"] == 2 * sm.pods_succeeded == 2 * 600
    view = batched.pod_view(0)
    succeeded = scalar.persistent_storage.succeeded_pods
    assert all(succeeded[name].status.assigned_node == row["node"] for name, row in view.items())
    # Every pod ever placed kept its placement's domain in the global plane.
    zone = np.asarray(batched.state.spread.pod_zone)[0, :600]
    assert (zone >= -1).all() and (zone >= 0).sum() > 500


def test_node_removals_and_keyless_nodes():
    """Nodes that leave the cache take their domain's pods with them (the
    rescheduled pods are placed, and counted, again)."""
    config = _spread_config("test")
    args = (31, 24, 260, 3, 3, 1)
    scalar = _scalar_run(config, spread_traces(*args, remove_nodes=True))
    batched = _batched_run(config, spread_traces(*args, remove_nodes=True), "scan")
    assert _compare(scalar, batched) == (260, 0)


# --- (c) the control ----------------------------------------------------------


def test_default_profile_puts_a_stated_share_of_pods_elsewhere():
    """The same labelled traces with the filter off (the `default` profile,
    as upstream with the plugin disabled): the build carries no spread state
    and most pods land on another node than under the constraint."""
    args = (12, 8, 90, 3, 3, 1)
    held = _scalar_run(_spread_config(), spread_traces(*args))
    batched = _batched_run(_spread_config(profile="default"), spread_traces(*args), "scan")
    assert batched.state.spread is None
    n, wrong = _compare(held, batched)
    assert n == 90 and wrong / n > 0.3, wrong
    # ... and it is the constraint that moved them: the batched default
    # equals the scalar default.
    free = _scalar_run(_spread_config(profile="default"), spread_traces(*args))
    assert _compare(free, batched) == (90, 0)


# --- (d) refusals ---------------------------------------------------------------


def _constraint_build(mutate, config=None, nodes=4):
    def constrained(name):
        pod = _pod(name, "blue")
        mutate(pod)
        return pod

    cluster, workload = _events(
        [(f"node_{i}", f"z{i % 2}", 4000) for i in range(nodes)], [(1.0, constrained("pod_0"))]
    )
    return build_batched_from_traces(
        config or _spread_config(),
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=1,
    )


def _set(**fields):
    def mutate(pod):
        for key, value in fields.items():
            setattr(pod.spec.topology_spread_constraints[0], key, value)

    return mutate


def _two_constraints(pod):
    pod.spec.topology_spread_constraints = pod.spec.topology_spread_constraints * 2


@pytest.mark.parametrize(
    "mutate,names",
    [
        (_set(when_unsatisfiable="ScheduleAnyway"), "ScheduleAnyway"),
        (_two_constraints, "more than one constraint"),
        (_set(match_expressions=[{"key": "color", "operator": "In", "values": ["blue"]}]), "matchExpressions"),
        (_set(min_domains=2), "minDomains"),
        (_set(match_label_keys=["pod-template-hash"]), "matchLabelKeys"),
    ],
    ids=["ScheduleAnyway", "two-constraints", "matchExpressions", "minDomains", "matchLabelKeys"],
)
def test_refused_by_name_on_both_paths(mutate, names):
    with pytest.raises(UnsupportedSpreadConstraint, match=names):
        _constraint_build(mutate)
    pod = _pod("q", "blue")
    mutate(pod)
    with pytest.raises(UnsupportedSpreadConstraint, match=names):
        _Cache([_node("a1", "a")]).schedule(pod)


def test_refuses_more_domains_or_workloads_than_the_table_holds():
    with pytest.raises(ValueError, match="more workloads or domains than the build's static table holds"):
        cluster, workload = _events(
            [(f"node_{i:02d}", f"z{i}", 4000) for i in range(9)], [(1.0, _pod("pod_0", "blue"))]
        )
        build_batched_from_traces(
            _spread_config(),
            cluster.convert_to_simulator_events(),
            workload.convert_to_simulator_events(),
        )
    with pytest.raises(ValueError, match="more workloads or domains"):
        cluster, workload = _events(
            [("node_0", "a", 4000)], [(1.0 + i, _pod(f"pod_{i:02d}", f"c{i}")) for i in range(17)]
        )
        build_batched_from_traces(
            _spread_config(),
            cluster.convert_to_simulator_events(),
            workload.convert_to_simulator_events(),
        )


def test_refuses_constraints_together_with_the_autoscalers():
    suffix = """
cluster_autoscaler:
  enabled: true
  scan_interval: 10.0
  max_node_count: 4
  node_groups:
  - node_template:
      metadata: {name: ca_node}
      status: {capacity: {cpu: 4000, ram: 8589934592}}
"""
    config = dataclasses.replace(config_with("test", suffix), scheduler_profile="topology_spread")
    with pytest.raises(UnsupportedProfileError, match="cluster autoscaler"):
        _constraint_build(lambda pod: None, config=config)


def test_unsupported_profile_message_lists_the_registry():
    from kubernetriks_tpu.batched.pipeline import DEVICE_FILTER_PLUGINS, compile_profile

    with pytest.raises(UnsupportedProfileError) as err:
        compile_profile({"filters": ["Fit", "InterPodAffinity"], "score": []})
    for name in DEVICE_FILTER_PLUGINS:
        assert name in str(err.value)
    assert "PodTopologySpread" in str(err.value) and "'InterPodAffinity'" in str(err.value)


# --- (e) a build without constraints compiles what it compiled -----------------


@pytest.mark.parametrize("cell", __import__("window_program_digest").CELLS)
def test_accepted_cells_lower_the_programs_they_lowered(cell):
    """The window program of each accepted cell's rehearsal build, lowered
    and stripped of debug locations, against the pinned digest
    (tests/data/window_program_digests.json, written by `python
    tests/window_program_digest.py --write`): a PR that means to leave the
    cells' programs alone finds out here that it did not. The pins were
    taken on the commit before the spread filter came (the filter's state
    is structurally None in a build without constraints, so nothing of it
    is traced) and written anew by PR 38, which changed every cell's event
    chunk on purpose (the replay's program alone came out as it was) and
    added `sched1k-spread.montecarlo`; PR 40 wrote the two autoscaled cells'
    anew (the cluster autoscaler's look-ups became dense contractions) and
    left the other five as they were; PR 48 wrote the replay's anew (the exact
    key estimates its digits from one reciprocal a denominator: same digits,
    another program) and added `sched1k-faults.montecarlo` (the parent's
    text) and `sched1k-pools.montecarlo` (pinned on its own tree, for the
    same reason as the replay's); PR 49 moved none (every rehearsal is one
    lane tile, whose state has neither of the event loop's two counters and
    whose program has no tile to choose; the multi-tile builds' tile is held
    by tests/test_event_compact.py). A PR that changes the window program
    on purpose writes the file anew on its own tree and says so."""
    import window_program_digest as wpd

    with open(wpd.DIGESTS) as fh:
        golden = json.load(fh)["digests"]
    assert wpd.digest(cell) == golden[cell]


def test_labels_alone_and_a_profile_alone_carry_no_spread_state():
    """Labelled traces without a constraint under `topology_spread`, and
    constrained traces under `default`: neither build carries the leaves, and
    both lower the program of the plain build."""
    import window_program_digest as wpd

    def lowered(profile, constrained):
        args = (41, 8, 40, 2, 2, 1)
        cluster, workload = spread_traces(*args)
        events = workload.convert_to_simulator_events()
        if not constrained:
            for _, event in events:
                event.pod.spec.topology_spread_constraints = []
        sim = build_batched_from_traces(
            _spread_config(profile=profile), cluster.convert_to_simulator_events(), events
        )
        return sim, wpd.lowered_window_program(sim)

    plain, text = lowered("default", False)
    assert plain.state.spread is None
    for profile, constrained in (("topology_spread", False), ("default", True)):
        sim, other = lowered(profile, constrained)
        assert sim.state.spread is None
        assert other == text
    sim, other = lowered("topology_spread", True)
    assert sim.state.spread is not None and other != text


# --- the state's riders: fleet lanes, checkpoints --------------------------------


def test_scenario_fleet_resets_and_repeats_a_spread_build():
    """ScenarioFleet over labelled traces under `topology_spread`: the
    pristine select covers the spread leaves (the placed-domain plane and the
    counters rewind with the lane), so a second wave repeats the first, and
    both equal the scalar path's counters."""
    from kubernetriks_tpu.batched.fleet import Scenario, ScenarioFleet

    config = _spread_config("zero")
    args = (51, 8, 90, 3, 3, 1)
    scalar = _scalar_run(config, spread_traces(*args))
    cluster, workload = spread_traces(*args)
    fleet = ScenarioFleet(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_lanes=2,
        horizon=END,
        use_pallas=False,
    )
    assert fleet.engine.state.spread is not None
    first = fleet.sweep([Scenario(), Scenario()])
    zone_after_first = np.asarray(fleet.engine.state.spread.pod_zone).copy()
    second = fleet.sweep([Scenario(), Scenario()])
    succeeded = scalar.metrics_collector.accumulated_metrics.pods_succeeded
    for result in first + second:
        assert result.counters["pods_succeeded"] == succeeded == 90
    np.testing.assert_array_equal(zone_after_first, np.asarray(fleet.engine.state.spread.pod_zone))
    decisions = np.asarray(fleet.engine.state.spread.decisions)
    assert (decisions == decisions[0]).all() and 0 < decisions[0] <= 90


def test_checkpoint_restores_the_spread_leaves(tmp_path):
    from kubernetriks_tpu.batched.state import compare_states

    config = _spread_config("test")
    args = (52, 8, 90, 3, 3, 1)

    def build():
        cluster, workload = spread_traces(*args)
        return build_batched_from_traces(
            config, cluster.convert_to_simulator_events(), workload.convert_to_simulator_events()
        )

    straight = build()
    straight.step_until_time(END)
    interrupted = build()
    interrupted.step_until_time(200.0)
    path = str(tmp_path / "spread.ckpt")
    interrupted.save_checkpoint(path)
    resumed = build()
    resumed.load_checkpoint(path)
    assert (np.asarray(resumed.state.spread.pod_zone) >= 0).any()
    resumed.step_until_time(END)
    assert compare_states(straight.state, resumed.state) == []
