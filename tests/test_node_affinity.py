"""NodeAffinity (nodeSelector and required terms) and TaintToleration
(NoSchedule) on both paths: the scalar plugins' rules one by one
(core/scheduler/plugins.py), the batched lowering held to them pod for pod in
all four cycle formulations over clusters of several machine shapes, ranked by
the exact key (batched/pipeline.py, ops/scheduler_kernel.py), a full tainted
pool, every refusal by name, and the structural promise that a build without
taints, terms or tolerations carries no plane.

Semantics: docs/PARITY.md "Node affinity and taints".
"""

import dataclasses

import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.pipeline import UnsupportedProfileError
from kubernetriks_tpu.batched.trace_compile import AFFINITY_MAX_BITS, AFFINITY_MAX_TERMS
from kubernetriks_tpu.core.scheduler.interface import SchedulingFailure
from kubernetriks_tpu.core.scheduler.kube_scheduler import (
    KubeScheduler,
    kube_scheduler_config_from_spec,
)
from kubernetriks_tpu.core.scheduler.plugins import (
    SchedulerCache,
    UnsupportedNodePlacement,
    UnsupportedSpreadConstraint,
)
from kubernetriks_tpu.core.types import (
    Node,
    NodeAffinity,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    Pod,
    PodConditionType,
    PreferredSchedulingTerm,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from pools_traces import TAINT, TOLERATION, ZONE_KEY, node_event, pod_event, pools_traces, required
from test_pending_free import config_with
from test_topology_spread import _batched_run, _compare, _scalar_run

GiB = 1024**3


def _pools_config(delays="zero", profile="node_pools"):
    return dataclasses.replace(config_with(delays), scheduler_profile=profile)


# --- (a) the scalar plugins ----------------------------------------------------


def _node(name, labels=None, taints=()):
    node = Node.new(name, 4000, 8 * GiB)
    node.metadata.labels.update(labels or {})
    node.spec.taints = [Taint(*t) for t in taints]
    return node


def _pod(name="p", selector=None, terms=None, tolerations=(), preferred=None, fields=None):
    pod = Pod.new(name, 1000, GiB, 10.0)
    pod.spec.node_selector = dict(selector or {})
    if terms is not None or preferred is not None:
        pod.spec.node_affinity = NodeAffinity(
            required_terms=[
                NodeSelectorTerm(
                    match_expressions=[NodeSelectorRequirement(k, op, list(v)) for k, op, v in term],
                    match_fields=list(fields or []),
                )
                for term in terms or []
            ],
            preferred=list(preferred or []),
            has_required=terms is not None,
        )
    pod.spec.tolerations = [Toleration(*t) for t in tolerations]
    return pod


NODES = [
    _node("n0", {"zone": "a", "pool": "general"}),
    _node("n1", {"zone": "b", "pool": "general"}),
    _node("n2", {"zone": "c", "disk": "ssd"}),
    _node("n3", {}),
]


def _admitted(pod, nodes=NODES, profile="node_pools"):
    """The nodes the profile's whole filter chain leaves (every node fits)."""
    algorithm = KubeScheduler(kube_scheduler_config_from_spec(profile))
    view = SchedulerCache(nodes={n.metadata.name: n.copy() for n in nodes})
    out = []
    for node in nodes:
        try:
            out.append(algorithm.schedule_one(pod, {node.metadata.name: node.copy()}, view))
        except SchedulingFailure:
            pass
    return out


@pytest.mark.parametrize(
    "pod,names",
    [
        (_pod(), ["n0", "n1", "n2", "n3"]),
        (_pod(selector={"pool": "general"}), ["n0", "n1"]),
        (_pod(selector={"pool": "general", "zone": "b"}), ["n1"]),
        (_pod(terms=[[("zone", "In", ["a", "c"])]]), ["n0", "n2"]),
        (_pod(terms=[[("zone", "NotIn", ["a"])]]), ["n1", "n2", "n3"]),  # a node without the key passes NotIn
        (_pod(terms=[[("disk", "Exists", [])]]), ["n2"]),
        (_pod(terms=[[("pool", "DoesNotExist", [])]]), ["n2", "n3"]),
        # a term's expressions are ANDed, the terms ORed, the selector ANDed with them
        (_pod(terms=[[("zone", "In", ["a", "b"]), ("pool", "Exists", [])], [("disk", "In", ["ssd"])]]), ["n0", "n1", "n2"]),
        (_pod(selector={"zone": "a"}, terms=[[("pool", "Exists", [])], [("disk", "Exists", [])]]), ["n0"]),
    ],
    ids=["neither", "selector", "selector-pairs", "In", "NotIn", "Exists", "DoesNotExist", "and-or", "selector-and-terms"],
)
def test_node_affinity_operators(pod, names):
    assert _admitted(pod) == names


TAINTED = [
    _node("t0"),
    _node("t1", taints=[("dedicated", "batch", "NoSchedule")]),
    _node("t2", taints=[("dedicated", "web", "NoSchedule")]),
    _node("t3", taints=[("dedicated", "batch", "NoSchedule"), ("gpu", "", "NoSchedule")]),
]


@pytest.mark.parametrize(
    "tolerations,names",
    [
        ((), ["t0"]),
        ((("dedicated", "Equal", "batch", "NoSchedule"),), ["t0", "t1"]),
        ((("dedicated", "Equal", "batch", ""),), ["t0", "t1"]),  # an empty effect matches every effect
        ((("dedicated", "Exists", "", "NoSchedule"),), ["t0", "t1", "t2"]),
        ((("", "Exists", "", ""),), ["t0", "t1", "t2", "t3"]),  # an empty key with Exists tolerates everything
        ((("dedicated", "Equal", "batch", ""), ("gpu", "Exists", "", "")), ["t0", "t1", "t3"]),
        ((("dedicated", "Equal", "", "NoSchedule"),), ["t0"]),  # Equal compares the value too
    ],
    ids=["none", "equal", "empty-effect", "exists", "exists-everything", "each-taint", "equal-other-value"],
)
def test_toleration_rules(tolerations, names):
    assert _admitted(_pod(tolerations=tolerations), TAINTED) == names


def test_a_toleration_is_no_affinity_and_the_default_profile_reads_neither():
    # The "dedicated nodes" use case needs both: the toleration lets the pod
    # onto the pool, only the affinity keeps it off the others.
    tolerant = _pod(tolerations=[("dedicated", "Equal", "batch", "NoSchedule")])
    assert _admitted(tolerant, TAINTED) == ["t0", "t1"]
    assert _admitted(_pod(), TAINTED, profile="default") == ["t0", "t1", "t2", "t3"]


def test_placement_roundtrips_through_the_generic_trace():
    pod = _pod(
        selector={"pool": "highmem"},
        terms=[[(ZONE_KEY, "In", ["zone1", "zone2"]), ("pool", "Exists", [])]],
        tolerations=[("dedicated", "Equal", "batch", "NoSchedule")],
    )
    back = Pod.from_dict(pod.to_dict())
    assert back.spec.node_selector == pod.spec.node_selector
    assert back.spec.node_affinity == pod.spec.node_affinity
    assert back.spec.tolerations == pod.spec.tolerations
    upstream = Pod.from_dict(
        {
            "metadata": {"name": "p"},
            "spec": {
                "nodeSelector": {"pool": "highmem"},
                "affinity": {
                    "nodeAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": {
                            "nodeSelectorTerms": [
                                {
                                    "matchExpressions": [
                                        {"key": ZONE_KEY, "operator": "In", "values": ["zone1", "zone2"]},
                                        {"key": "pool", "operator": "Exists"},
                                    ]
                                }
                            ]
                        }
                    }
                },
                "tolerations": [{"key": "dedicated", "operator": "Equal", "value": "batch", "effect": "NoSchedule"}],
            },
        }
    )
    assert upstream.spec.node_selector == pod.spec.node_selector
    assert upstream.spec.node_affinity == pod.spec.node_affinity
    assert upstream.spec.tolerations == pod.spec.tolerations
    node = _node("n", {"pool": "dedicated"}, taints=[("dedicated", "batch", "NoSchedule")])
    assert Node.from_dict(node.to_dict()).spec.taints == node.spec.taints
    assert node.copy().spec.taints == node.spec.taints and pod.copy().spec.tolerations == pod.spec.tolerations


# --- (b) batched against scalar --------------------------------------------------

SWEEP = [
    # seed, nodes, pods, delays, two terms a pod
    (3, 20, 200, "zero", False),
    (4, 20, 200, "zero", True),
    (5, 40, 320, "reference", False),
    (6, 100, 420, "zero", True),
]


@pytest.mark.parametrize("formulation", ["scan", "candidate", "select", "megakernel"])
@pytest.mark.parametrize("seed,nodes,pods,delays,two_terms", SWEEP)
def test_batched_equals_scalar_pod_for_pod(formulation, seed, nodes, pods, delays, two_terms):
    config = _pools_config(delays)
    args = dict(seed=seed, n_nodes=nodes, n_pods=pods, two_terms=two_terms)
    scalar = _scalar_run(config, pools_traces(**args))
    batched = _batched_run(config, pools_traces(**args), formulation)
    assert batched.kernel_formulation()["ranking"] == "exact"
    assert batched.state.affinity.pod_terms.shape[1] == (2 if two_terms else 1)
    assert _compare(scalar, batched, cluster=1) == (pods, 0)
    sm = scalar.metrics_collector.accumulated_metrics
    counters = batched.metrics_summary()["counters"]
    assert counters["pods_succeeded"] == 2 * sm.pods_succeeded
    assert counters["terminated_pods"] == 2 * sm.internal.terminated_pods
    report = batched.telemetry_report()["counters"]
    # A cluster's own, the same in all four formulations (pinned once a case).
    assert (report["affinity_attempts"], report["affinity_attempts_refused"]) == _COUNTERS.setdefault(
        (seed, delays), (report["affinity_attempts"], report["affinity_attempts_refused"])
    )
    assert 0 < report["affinity_attempts_refused"] < report["affinity_attempts"]


_COUNTERS = {}


def _full_pool_scenario():
    """The tainted pool is one node of 4 cores; two dedicated pods of 2 cores
    fill it until t = 74 and t = 500. A third arrives at t = 12: the other
    node is empty and refuses it (no toleration would help: its affinity
    names the pool), so it parks; the first finish wakes it and the cycle at
    t = 80 puts it in the pool. A plain pod never lands there."""
    dedicated = {"tolerations": [dict(TOLERATION)], "affinity": required([("dedicated", "In", ["batch"])])}
    cluster = [
        node_event("node_a", 8000, 16, {"pool": "general"}),
        node_event("node_b", 4000, 8, {"pool": "dedicated", "dedicated": "batch"}, tainted=True),
    ]
    workload = [
        pod_event("pod_0", 1.0, 2000, 4, 64.0, **dedicated),
        pod_event("pod_1", 2.0, 2000, 4, 490.0, **dedicated),
        pod_event("pod_2", 12.0, 2000, 4, 20.0, **dedicated),
        pod_event("pod_3", 13.0, 500, 1, 20.0),
    ]
    return GenericClusterTrace(events=cluster), GenericWorkloadTrace(events=workload)


def test_full_pool_scalar_run_parks_the_pod_until_a_finish_in_that_pool():
    sim = KubernetriksSimulation(_pools_config())
    sim.initialize(*_full_pool_scenario())
    sim.step_until_time(50.0)
    assert "pod_2" in sim.persistent_storage.unscheduled_pods_cache
    sim.step_until_time(700.0)
    done = sim.persistent_storage.succeeded_pods
    assert [done[f"pod_{i}"].status.assigned_node for i in range(4)] == ["node_b", "node_b", "node_b", "node_a"]
    started = done["pod_2"].get_condition(PodConditionType.POD_RUNNING).last_transition_time
    assert started == pytest.approx(80.0, abs=1e-3)


@pytest.mark.parametrize("formulation", ["scan", "candidate", "select", "megakernel"])
def test_full_pool_batched(formulation):
    scalar = _scalar_run(_pools_config(), _full_pool_scenario())
    batched = _batched_run(_pools_config(), _full_pool_scenario(), formulation, n_clusters=1)
    assert _compare(scalar, batched) == (4, 0)
    assert batched.pod_view(0)["pod_2"]["node"] == "node_b"
    # pod_2 was tried at t = 20 (refused: node_a fits it, the pool is full),
    # woken by pod_3's finish and refused again, and placed at t = 80.
    report = batched.telemetry_report()["counters"]
    assert report["affinity_attempts"] == 2 + report["affinity_attempts_refused"] + 1
    assert report["affinity_attempts_refused"] >= 1


@pytest.mark.parametrize("delays", ["zero", "test"])
def test_slid_pod_window_reads_its_own_columns_of_the_pod_planes(delays):
    config = _pools_config(delays)
    args = dict(seed=21, n_nodes=20, n_pods=600, horizon=2400.0, dedicated_share=0.04)
    scalar = _scalar_run(config, pools_traces(**args))
    batched = _batched_run(config, pools_traces(**args), "scan", pod_window=128, superspan=False)
    assert batched._pod_base > 0, "the window never slid"
    assert batched.state.affinity.pod_forbid.shape[1] > batched.state.pods.phase.shape[1]
    sm = scalar.metrics_collector.accumulated_metrics
    assert batched.metrics_summary()["counters"]["pods_succeeded"] == 2 * sm.pods_succeeded == 2 * 600
    view = batched.pod_view(0)
    succeeded = scalar.persistent_storage.succeeded_pods
    assert all(succeeded[name].status.assigned_node == row["node"] for name, row in view.items())


def test_a_node_that_leaves_and_returns_keeps_its_bits():
    """Removals and re-creations under the same name, labels and taints: the
    node returns to its own slot, whose bits never moved."""
    config = _pools_config("test")
    args = dict(seed=31, n_nodes=24, n_pods=260, remove_nodes=True)
    scalar = _scalar_run(config, pools_traces(**args))
    batched = _batched_run(config, pools_traces(**args), "scan")
    assert batched.n_nodes == 24
    assert _compare(scalar, batched) == (260, 0)


def test_default_profile_puts_a_stated_share_of_pods_elsewhere():
    """The control: the same traces with the two filters off carry no plane,
    and the pods land elsewhere (the tainted pool takes plain pods)."""
    args = dict(seed=3, n_nodes=20, n_pods=200)
    held = _scalar_run(_pools_config(), pools_traces(**args))
    batched = _batched_run(_pools_config(profile="default"), pools_traces(**args), "scan")
    assert batched.state.affinity is None
    done = held.persistent_storage.succeeded_pods
    moved = sum(
        1 for name, row in batched.pod_view(0).items()
        if name not in done or done[name].status.assigned_node != row["node"]
    )
    assert moved / 200 > 0.3, moved
    free = _scalar_run(_pools_config(profile="default"), pools_traces(**args))
    assert _compare(free, batched) == (200, 0)


# --- (c) refusals ----------------------------------------------------------------


def _build(pods, nodes=None, config=None):
    cluster = GenericClusterTrace(events=nodes or [node_event("node_0", 4000, 8, {"pool": "a"})])
    workload = GenericWorkloadTrace(
        events=[
            {"timestamp": 1.0 + i, "event_type": {"__tag__": "CreatePod", "pod": pod.to_dict()}}
            for i, pod in enumerate(pods)
        ]
    )
    return build_batched_from_traces(
        config or _pools_config(),
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=1,
    )


@pytest.mark.parametrize(
    "pod,names",
    [
        (_pod(terms=[[("cores", "Gt", ["4"])]]), "operator Gt"),
        (_pod(terms=[[("cores", "Lt", ["4"])]]), "operator Lt"),
        (_pod(terms=[[("pool", "In", ["a"])]], fields=[{"key": "metadata.name", "operator": "In", "values": ["n"]}]), "matchFields"),
        (
            _pod(preferred=[PreferredSchedulingTerm(1, NodeSelectorTerm([NodeSelectorRequirement("pool", "In", ["a"])]))]),
            "preferredDuringScheduling",
        ),
        (_pod(terms=[[("pool", "In", [])]]), "In without values"),
        (_pod(terms=[]), "without nodeSelectorTerms"),
        (_pod(tolerations=[("dedicated", "Equal", "batch", "NoExecute")]), "effect NoExecute"),
        (_pod(tolerations=[("dedicated", "Contains", "batch", "")]), "toleration operator Contains"),
    ],
    ids=["Gt", "Lt", "matchFields", "preferred", "In-empty", "no-terms", "toleration-NoExecute", "toleration-operator"],
)
def test_refused_by_name_on_both_paths(pod, names):
    with pytest.raises(UnsupportedNodePlacement, match=names):
        _build([pod])
    with pytest.raises(UnsupportedNodePlacement, match=names):
        _admitted(pod)


@pytest.mark.parametrize("effect", ["NoExecute", "PreferNoSchedule"])
def test_taint_effects_refused_by_name_on_both_paths(effect):
    with pytest.raises(UnsupportedNodePlacement, match=f"taint effect {effect}"):
        node = node_event("node_0", 4000, 8, {})
        node["event_type"]["node"]["spec"] = {"taints": [{**TAINT, "effect": effect}]}
        _build([_pod()], nodes=[node])
    with pytest.raises(UnsupportedNodePlacement, match=f"taint effect {effect}"):
        _admitted(_pod(), [_node("t", taints=[("dedicated", "batch", effect)])])


def test_a_spread_constraint_with_a_term_or_a_toleration_is_refused_on_both_paths():
    def both(**placement):
        pod = _pod(**placement)
        pod.metadata.labels["color"] = "blue"
        pod.spec.topology_spread_constraints = [
            TopologySpreadConstraint(topology_key=ZONE_KEY, match_labels={"color": "blue"})
        ]
        return pod

    every = {"filters": ["Fit", "PodTopologySpread", "NodeAffinity", "TaintToleration"], "score": []}
    for placement in (
        dict(selector={"pool": "a"}),
        dict(terms=[[("pool", "Exists", [])]]),
        dict(tolerations=[("dedicated", "Exists", "", "")]),
    ):
        for profile in ("topology_spread", "node_pools", every):
            with pytest.raises(UnsupportedSpreadConstraint, match="nodeAffinityPolicy / nodeTaintsPolicy"):
                _admitted(both(**placement), profile=profile)
        with pytest.raises(UnsupportedSpreadConstraint, match="nodeAffinityPolicy / nodeTaintsPolicy"):
            _build([both(**placement)])


def test_refuses_more_bits_or_terms_than_the_planes_hold():
    many = [_pod(f"pod_{i:02d}", terms=[[("rack", "In", [f"r{i}"])]]) for i in range(AFFINITY_MAX_BITS + 1)]
    with pytest.raises(ValueError, match=r"32 distinct node selector expressions and 0 distinct taints .* \('rack', 'In', \('r0',\)\).* 31 bits"):
        _build(many)
    _build(many[:-1])  # exactly as many as the plane holds
    wide = _pod("pod_wide", terms=[[("rack", "In", [f"r{i}"])] for i in range(AFFINITY_MAX_TERMS + 1)])
    with pytest.raises(ValueError, match="pod 'pod_wide': 5 nodeSelectorTerms, more than the 4 term planes"):
        _build([wide])


def test_refuses_planes_together_with_the_autoscalers_and_pod_groups():
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
    config = dataclasses.replace(config_with("test", suffix), scheduler_profile="node_pools")
    with pytest.raises(UnsupportedProfileError, match="cluster autoscaler"):
        _build([_pod(selector={"pool": "a"})], config=config)


# --- (d) a build without taints, terms or tolerations carries no plane ---------


def test_labels_alone_and_a_profile_alone_carry_no_plane():
    """Labelled nodes and bare pods under `node_pools`, and tainted nodes with
    terms under `default`: neither build carries the leaves, and both lower
    the program of the plain build (the ten accepted cells' own programs are
    pinned by tests/test_topology_spread.py's digests)."""
    import window_program_digest as wpd

    def lowered(profile, named):
        cluster, workload = pools_traces(seed=41, n_nodes=8, n_pods=40)
        cluster_events = cluster.convert_to_simulator_events()
        events = workload.convert_to_simulator_events()
        if not named:
            for _, event in cluster_events:
                event.node.spec.taints = []
            for _, event in events:
                event.pod.spec.node_selector, event.pod.spec.node_affinity, event.pod.spec.tolerations = {}, None, []
        sim = build_batched_from_traces(_pools_config(profile=profile), cluster_events, events)
        return sim, wpd.lowered_window_program(sim)

    plain, text = lowered("default", False)
    assert plain.state.affinity is None
    for profile, named in (("node_pools", False), ("default", True)):
        sim, other = lowered(profile, named)
        assert sim.state.affinity is None and sim._affinity_terms is None
        assert other == text
    sim, other = lowered("node_pools", True)
    assert sim.state.affinity is not None and other != text


def test_taints_alone_keep_bare_pods_off_the_pool():
    """No pod names a node, one node is tainted: the build carries the planes
    (a bare pod tolerates nothing) and counts no attempt."""
    nodes = [node_event("node_0", 4000, 8, {}), node_event("node_1", 64000, 128, {}, tainted=True)]
    sim = _build([_pod(f"pod_{i}") for i in range(3)], nodes=nodes)
    sim.step_until_time(100.0)
    assert {row["node"] for row in sim.pod_view(0).values()} == {"node_0"}
    assert sim.telemetry_report()["counters"]["affinity_attempts"] == 0


# --- the state's riders: fleet lanes, checkpoints ---------------------------------


def test_scenario_fleet_resets_and_repeats_a_pools_build():
    from kubernetriks_tpu.batched.fleet import Scenario, ScenarioFleet

    config = _pools_config("zero")
    args = dict(seed=51, n_nodes=20, n_pods=120)
    scalar = _scalar_run(config, pools_traces(**args))
    cluster, workload = pools_traces(**args)
    fleet = ScenarioFleet(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_lanes=2,
        horizon=3000.0,
        use_pallas=False,
    )
    planes = [np.asarray(x).copy() for x in fleet.engine.state.affinity[:3]]
    first = fleet.sweep([Scenario(), Scenario()])
    attempts = np.asarray(fleet.engine.state.affinity.attempts).copy()
    second = fleet.sweep([Scenario(), Scenario()])
    succeeded = scalar.metrics_collector.accumulated_metrics.pods_succeeded
    for result in first + second:
        assert result.counters["pods_succeeded"] == succeeded == 120
    for before, after in zip(planes, fleet.engine.state.affinity[:3]):
        np.testing.assert_array_equal(before, np.asarray(after))
    # The counters rewind with the lane: the second wave counts what the first did.
    np.testing.assert_array_equal(attempts, np.asarray(fleet.engine.state.affinity.attempts))
    assert (attempts == attempts[0]).all() and attempts[0] > 0


def test_checkpoint_restores_the_affinity_leaves(tmp_path):
    from kubernetriks_tpu.batched.state import compare_states

    config = _pools_config("test")

    def build():
        cluster, workload = pools_traces(seed=52, n_nodes=20, n_pods=120)
        return build_batched_from_traces(
            config, cluster.convert_to_simulator_events(), workload.convert_to_simulator_events()
        )

    straight = build()
    straight.step_until_time(3000.0)
    interrupted = build()
    interrupted.step_until_time(200.0)
    path = str(tmp_path / "pools.ckpt")
    interrupted.save_checkpoint(path)
    resumed = build()
    resumed.load_checkpoint(path)
    assert int(np.asarray(resumed.state.affinity.attempts).sum()) > 0
    resumed.step_until_time(3000.0)
    assert compare_states(straight.state, resumed.state) == []


# --- the megakernel's launch by depth carries the planes ---------------------------


def test_split_launch_carries_the_planes_of_the_lanes_it_moves():
    """Three lane tiles, two clusters with a burst deeper than a pass in one
    cycle: the second launch drains them in a tile of their own, their node
    plane and pod planes moved with them (step._launch_by_depth), and the
    state equals the single launch's leaf for leaf."""
    import jax

    from kubernetriks_tpu.batched import step
    from kubernetriks_tpu.batched.engine import BatchedSimulation
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
    from test_cycle_compact import leaves_differing, single_launch, traced_with

    C, K, burst, end = 300, 8, 24, 70.0
    deep = {3: "burst", 131: "burst"}
    config = _pools_config()
    dedicated = {"tolerations": [dict(TOLERATION)], "affinity": required([("dedicated", "In", ["batch"])])}
    placements = [{}, {}, {"tolerations": [dict(TOLERATION)]}, dedicated, {"node_selector": {"pool": "highmem"}}]
    nodes = GenericClusterTrace(
        events=[node_event(f"node_{i:03d}", 7700, 15, {"pool": "general"}) for i in range(8)]
        + [node_event(f"node_{i:03d}", 7900, 31, {"pool": "highmem"}) for i in range(8, 10)]
        + [node_event(f"node_{i:03d}", 4300, 9, {"pool": "dedicated", "dedicated": "batch"}, tainted=True) for i in range(10, 12)]
    ).convert_to_simulator_events()

    def workload(kind, seed):
        rng = np.random.default_rng(seed)
        times = [10.0 * i + off for i in range(6) for off in (3.0, 7.0)]
        times += [25.0] * burst if kind == "burst" else []
        times += [end + 100.0] * (12 + burst - len(times))
        return GenericWorkloadTrace(
            events=[
                pod_event(
                    f"pod_{i:05d}", t, 1000, 2, float(np.round(rng.uniform(20.0, 60.0), 3)),
                    **placements[int(rng.integers(len(placements)))],
                )
                for i, t in enumerate(sorted(times))
            ]
        ).convert_to_simulator_events()

    compiled = {
        kind: compile_cluster_trace(nodes, workload(kind, seed), config)
        for seed, kind in enumerate(["shallow", "burst"])
    }

    def run():
        sim = BatchedSimulation(
            config, [compiled[deep.get(c, "shallow")] for c in range(C)],
            use_pallas=True, pallas_interpret=True, max_pods_per_cycle=K, lane_major=True,
        )
        assert sim.kernel_formulation()["cycle"] == "megakernel" and sim.state.affinity is not None
        sim.step_until_time(end)
        return sim

    with traced_with(CYCLE_COMPACT_PAYS=0):
        split = run()
    with traced_with(_launch_by_depth=single_launch):
        single = run()
    assert leaves_differing(split.state, single.state, skip=("cycle_compacted",)) == []
    compacted = np.asarray(split.state.metrics.cycle_compacted)
    assert compacted[list(deep)].tolist() == [1, 1] and compacted.sum() == 2
    assert int(np.asarray(split.state.affinity.attempts)[3]) > int(np.asarray(split.state.affinity.attempts)[4]) > 0
    assert step._launch_by_depth is not single_launch
