"""Scoring as kube-scheduler scores (docs/PARITY.md): the `kube_default`
profile on both paths. The four integer scorers against the text's formulas in
Python integers, on random and edge shapes; the device quotients and unit
conversion against integer division over everything the benchmark cell can
produce; the scalar path against the batched one pod for pod in all four cycle
formulations, and the kernels against the lax.scan engine leaf for leaf, on
clusters small enough that the preferred pool fills and a soft term loses;
every refusal by its message; and a `node_pools` build still refusing a
preference it would ignore.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu.batched import pipeline
from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.pipeline import UnsupportedProfileError, compile_profile
from kubernetriks_tpu.batched.state import compare_states
from kubernetriks_tpu.batched.trace_compile import SOFT_MAX_TERMS
from kubernetriks_tpu.core.scheduler.kube_scheduler import (
    KubeScheduler,
    kube_scheduler_config_from_spec,
)
from kubernetriks_tpu.core.scheduler.plugins import (
    BALANCED_ALLOCATION,
    NODE_AFFINITY,
    NODE_RESOURCES_FIT,
    PLUGIN_REGISTRY,
    TAINT_TOLERATION,
    UnsupportedNodePlacement,
    normalize_by_max,
)
from kubernetriks_tpu.core.types import (
    Node,
    NodeAffinity,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    Pod,
    PreferredSchedulingTerm,
    Taint,
    Toleration,
)
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from kubescore_traces import SOFT_TAINT, kubescore_traces, node_event, preferred
from pools_traces import pod_event
from test_pending_free import config_with
from test_topology_spread import _batched_run, _compare, _scalar_run

GiB = 1024**3


def _config(profile="kube_default", delays="zero"):
    return dataclasses.replace(config_with(delays), scheduler_profile=profile)


# --- (a) the scorers against the text's formulas --------------------------------


def _fit_formula(a_cpu, a_ram, f_cpu, f_ram, q_cpu, q_ram):
    def left(a, f, q):
        return (a - (a - f + q)) * 100 // a if a else 0

    return (left(a_cpu, f_cpu, q_cpu) + left(a_ram, f_ram, q_ram)) // 2


def _balanced_formula(a_cpu, a_ram, f_cpu, f_ram, q_cpu, q_ram):
    if not a_cpu or not a_ram:
        return 0
    u_cpu, u_ram = a_cpu - f_cpu + q_cpu, a_ram - f_ram + q_ram
    return (100 * a_cpu * a_ram - 50 * abs(u_cpu * a_ram - u_ram * a_cpu)) // (a_cpu * a_ram)


def _node(a_cpu, a_ram, f_cpu, f_ram, labels=None, taints=()):
    node = Node.new("n", a_cpu, a_ram)
    node.status.allocatable.cpu, node.status.allocatable.ram = f_cpu, f_ram
    node.metadata.labels.update(labels or {})
    node.spec.taints = [Taint(*t) for t in taints]
    return node


def _shapes(rng, count):
    """(A_cpu, A_ram, F_cpu, F_ram, q_cpu, q_ram) with q <= F <= A: random,
    then the edges (a pod that fills its node, nothing allocatable, one unit)."""
    out = []
    for _ in range(count):
        a_cpu, a_ram = int(rng.integers(1, 400)), int(rng.integers(1, 600))
        f_cpu, f_ram = int(rng.integers(1, a_cpu + 1)), int(rng.integers(1, a_ram + 1))
        out.append((a_cpu, a_ram, f_cpu, f_ram, int(rng.integers(1, f_cpu + 1)), int(rng.integers(1, f_ram + 1))))
    return out + [
        (192, 256, 192, 256, 192, 256),  # the pod fills the empty node
        (128, 128, 7, 128, 7, 1),  # a full resource after placement
        (0, 128, 0, 64, 0, 8),  # A_cpu = 0
        (64, 0, 8, 0, 8, 0),  # A_ram = 0
        (1, 1, 1, 1, 1, 1),
        (192, 256, 192, 256, 1, 1),
    ]


def test_resource_scorers_equal_the_formulas_on_both_paths():
    rng = np.random.default_rng(50)
    shapes = _shapes(rng, 300)
    pod_scores = []
    for a_cpu, a_ram, f_cpu, f_ram, q_cpu, q_ram in shapes:
        pod = Pod.new("p", q_cpu, q_ram, 1.0)
        node = _node(a_cpu, a_ram, f_cpu, f_ram)
        want = (_fit_formula(a_cpu, a_ram, f_cpu, f_ram, q_cpu, q_ram),
                _balanced_formula(a_cpu, a_ram, f_cpu, f_ram, q_cpu, q_ram))
        got = (PLUGIN_REGISTRY[NODE_RESOURCES_FIT].score(pod, node),
               PLUGIN_REGISTRY[BALANCED_ALLOCATION].score(pod, node))
        assert got == want, (a_cpu, a_ram, f_cpu, f_ram, q_cpu, q_ram)
        assert 0 <= want[0] <= 100 and 0 <= want[1] <= 100
        pod_scores.append(want)
    # The device's: one candidate a row against its one node, in units 3 / 5
    # (the frees and requests scaled up by them, as a build's state holds them).
    cols = [np.asarray(c, np.int32)[:, None] for c in zip(*shapes)]
    a_cpu, a_ram, f_cpu, f_ram, q_cpu, q_ram = cols
    for units in [(1, 1), (3, 5), (500, 1024)]:
        profile = compile_profile(
            {"filters": ["Fit"], "score": [{"name": NODE_RESOURCES_FIT}, {"name": BALANCED_ALLOCATION, "weight": 1000}]}
        )._replace(units=units)
        nodes = pipeline.integer_nodes(jnp.asarray(a_cpu * units[0]), jnp.asarray(a_ram * units[1]), units)
        total, part, attempt = pipeline.integer_scores(
            profile, jnp.ones(a_cpu.shape, bool),
            jnp.asarray(f_cpu * units[0]), jnp.asarray(f_ram * units[1]),
            jnp.asarray(q_cpu * units[0]), jnp.asarray(q_ram * units[1]),
            nodes, None, axis=1,
        )
        assert part is None and attempt is None
        got = np.asarray(total)[:, 0]
        np.testing.assert_array_equal(got % 1000, [s[0] for s in pod_scores])
        np.testing.assert_array_equal(got // 1000, [s[1] for s in pod_scores])


@pytest.mark.parametrize(
    "raw,reverse,want",
    [
        ([0, 0, 0], False, [0, 0, 0]),  # M = 0
        ([0, 0, 0], True, [100, 100, 100]),
        ([7], False, [100]),  # one feasible node
        ([1], True, [0]),
        ([50, 1, 51, 0], False, [98, 1, 100, 0]),
        ([150, 400, 100], False, [37, 100, 25]),  # a raw above 100
        ([2, 1, 0, 3], True, [34, 67, 100, 0]),
    ],
)
def test_normalisation_over_the_feasible_nodes(raw, reverse, want):
    assert normalize_by_max(raw, reverse) == want
    score, most = pipeline._normalized(
        jnp.asarray([raw + [999]], jnp.int32), jnp.asarray([[True] * len(raw) + [False]]), 1, reverse
    )
    assert np.asarray(score)[0, : len(raw)].tolist() == want  # the infeasible node sets no M
    assert int(most[0, 0]) == max(raw)


def test_label_scorers_on_nodes_and_pods():
    pod = Pod.new("p", 1, 1, 1.0)
    pod.spec.node_affinity = NodeAffinity(
        has_required=False,
        preferred=[
            PreferredSchedulingTerm(50, NodeSelectorTerm([NodeSelectorRequirement("pool", "In", ["compute"])])),
            PreferredSchedulingTerm(1, NodeSelectorTerm([NodeSelectorRequirement("zone", "In", ["zone1"])])),
            PreferredSchedulingTerm(
                7,
                NodeSelectorTerm(
                    [NodeSelectorRequirement("gpu", "DoesNotExist", []), NodeSelectorRequirement("pool", "NotIn", ["general"])]
                ),
            ),
        ],
    )
    pod.spec.tolerations = [Toleration("reserved", "Exists", "", ""), Toleration("old", "Equal", "x", "NoSchedule")]
    nodes = [
        _node(4, 4, 4, 4, {"pool": "compute", "zone": "zone1"}),
        _node(4, 4, 4, 4, {"pool": "general", "zone": "zone1"}, [("reserved", "highmem", "PreferNoSchedule")]),
        _node(4, 4, 4, 4, {"pool": "compute", "gpu": "a100"}, [("old", "x", "PreferNoSchedule"), ("slow", "", "PreferNoSchedule")]),
        _node(4, 4, 4, 4, {}),
    ]
    affinity, taints = PLUGIN_REGISTRY[NODE_AFFINITY], PLUGIN_REGISTRY[TAINT_TOLERATION]
    assert [affinity.score(pod, n) for n in nodes] == [58, 1, 50, 7]
    # `reserved` is tolerated (empty effect matches every effect); a NoSchedule
    # toleration tolerates no PreferNoSchedule taint.
    assert [taints.score(pod, n) for n in nodes] == [0, 0, 2, 0]
    assert affinity.normalize([58, 1, 50, 7]) == [100, 1, 86, 12]
    assert taints.normalize([0, 0, 2, 0]) == [100, 100, 0, 100]


def test_kube_default_adds_the_four_at_their_weights():
    """One pod, three nodes that all fit: the total is fit + balanced + 2
    affinity + 3 taints, and the last max in name order wins."""
    algorithm = KubeScheduler(kube_scheduler_config_from_spec("kube_default"))
    pod = Pod.new("p", 2, 2, 1.0)
    pod.spec.node_affinity = NodeAffinity(
        has_required=False,
        preferred=[PreferredSchedulingTerm(10, NodeSelectorTerm([NodeSelectorRequirement("pool", "In", ["a"])]))],
    )
    nodes = {}
    for name, labels, taints in [("n0", {"pool": "a"}, [("k", "v", "PreferNoSchedule")]), ("n1", {"pool": "a"}, []), ("n2", {}, [])]:
        node = _node(8, 8, 8, 8, labels, taints)
        node.metadata.name = name
        nodes[name] = node
    # n0: 75 + 100 + 200 + 0; n1: 75 + 100 + 200 + 300; n2: 75 + 100 + 0 + 300.
    assert algorithm.schedule_one(pod, nodes) == "n1"
    del nodes["n1"]
    assert algorithm.schedule_one(pod, nodes) == "n2"  # 475 against n0's 375: the taint outweighs the term


# --- (b) the device's integer arithmetic -----------------------------------------


def test_to_units_is_exact_division_for_multiples():
    rng = np.random.default_rng(1)
    for unit in [1, 2, 3, 500, 1024, 125, 96, 1000, 7 * 64]:
        k = np.concatenate([rng.integers(0, 2**31 // unit, 4000), [0, 1, (2**31 - 1) // unit]]).astype(np.int64)
        got = np.asarray(pipeline.to_units(jnp.asarray((k * unit).astype(np.int32)), unit))
        np.testing.assert_array_equal(got, k)


def _quotients(num, den):
    num, den = jnp.asarray(num, jnp.int32), jnp.asarray(den, jnp.int32)
    return np.asarray(pipeline.floor_quotient(num, den, pipeline.biased_reciprocal(den)))


def test_quotients_over_everything_the_cell_can_produce():
    """`floor_quotient` against Python's integer division over every
    (numerator, denominator) of the benchmark cell: its four machine shapes in
    units of its gcds (500 millicores, 1 GiB) and every free a node can hold,
    the two normalisations over every raw score its classes can sum to."""
    shapes = [(128, 128), (128, 256), (192, 192), (64, 64)]
    for a_cpu, a_ram in shapes:
        for cap in (a_cpu, a_ram):
            free = np.arange(cap + 1)
            np.testing.assert_array_equal(_quotients(free * 100, np.full_like(free, cap)), free * 100 // cap)
        f_cpu, f_ram = np.meshgrid(np.arange(a_cpu + 1), np.arange(a_ram + 1), indexing="ij")
        whole = a_cpu * a_ram
        num = (100 * whole - 50 * np.abs(f_ram * a_cpu - f_cpu * a_ram)).ravel()
        assert num.min() >= 0 and num.max() < 2**31
        np.testing.assert_array_equal(_quotients(num, np.full_like(num, whole)), num // whole)
    # NodeAffinity: the classes' weights 50 and 1 (a node may match both);
    # TaintToleration: one soft taint. And the general bound: 4 terms of 100.
    for raws in ([0, 1, 50, 51], range(0, 401)):
        raw, most = (np.asarray(x).ravel() for x in np.meshgrid(list(raws), [m for m in raws if m], indexing="ij"))
        keep = raw <= most
        np.testing.assert_array_equal(_quotients(raw[keep] * 100, most[keep]), raw[keep] * 100 // most[keep])


def test_quotient_survives_a_reciprocal_some_ulp_off():
    num = jnp.asarray(np.arange(0, 4_915_200 + 1, 49_152 // 7), jnp.int32)
    den = jnp.full(num.shape, 49_152, jnp.int32)
    exact = np.asarray(num) // 49_152
    inv = pipeline.biased_reciprocal(den)
    for ulps in (-16, -1, 1, 16):
        off = jnp.asarray(np.asarray(inv).view(np.int32) + ulps).view(jnp.float32)
        np.testing.assert_array_equal(np.asarray(pipeline.floor_quotient(num, den, off)), exact)


# --- (c) batched against scalar, kernels against the scan ------------------------

SWEEP = [
    # seed, nodes, pods, delays, the cell's round machine shapes
    (3, 20, 200, "zero", False),
    (5, 40, 320, "reference", False),
    (7, 20, 200, "zero", True),
]
_SCAN = {}


def _scan_state(seed, nodes, pods, delays, round_shapes):
    key = (seed, nodes, pods, delays, round_shapes)
    if key not in _SCAN:
        _SCAN[key] = _batched_run(
            _config(delays=delays), kubescore_traces(seed, nodes, pods, round_shapes=round_shapes), "scan"
        )
    return _SCAN[key]


@pytest.mark.parametrize("formulation", ["scan", "candidate", "select", "megakernel"])
@pytest.mark.parametrize("seed,nodes,pods,delays,round_shapes", SWEEP)
def test_batched_equals_scalar_pod_for_pod_and_the_scan_leaf_for_leaf(
    formulation, seed, nodes, pods, delays, round_shapes
):
    config = _config(delays=delays)
    args = dict(seed=seed, n_nodes=nodes, n_pods=pods, round_shapes=round_shapes)
    scalar = _scalar_run(config, kubescore_traces(**args))
    scan = _scan_state(seed, nodes, pods, delays, round_shapes)
    batched = scan if formulation == "scan" else _batched_run(config, kubescore_traces(**args), formulation)
    assert batched.kernel_formulation()["ranking"] == "integer"
    assert batched._cycle_profile.exact_bits == 0 and batched._cycle_profile.soft_taints == 1
    assert batched.state.affinity.pod_soft_terms.shape[1] == 2
    assert _compare(scalar, batched, cluster=1) == (pods, 0)
    assert compare_states(scan.state, batched.state) == []
    sm = scalar.metrics_collector.accumulated_metrics
    counters = batched.metrics_summary()["counters"]
    assert counters["pods_succeeded"] == 2 * sm.pods_succeeded
    report = batched.telemetry_report()["counters"]
    # The preferred pool fills: a preference is honoured somewhere and lost somewhere.
    assert 0 < report["soft_honoured"] < report["soft_attempts"]
    assert 0 < report["affinity_attempts_refused"] < report["affinity_attempts"]


def test_a_soft_term_loses_to_a_full_pool_on_both_paths():
    """One dedicated node of 4 cores; a pod of 3 that tolerates and PREFERS it
    takes it, the next such pod cannot fit there and goes to the general node
    (the preference lost), and a third, once the first has finished, is back
    on the pool."""
    tolerant = {
        "tolerations": [{"key": "dedicated", "operator": "Equal", "value": "batch", "effect": "NoSchedule"}],
        "affinity": preferred((50, [("dedicated", "In", ["batch"])])),
    }

    def traces():
        cluster = GenericClusterTrace(
            events=[
                node_event("node_0", 16000, 32, {"pool": "general"}),
                node_event("node_1", 4000, 8, {"dedicated": "batch"}, [{"key": "dedicated", "value": "batch", "effect": "NoSchedule"}]),
            ]
        )
        workload = GenericWorkloadTrace(
            events=[
                pod_event("pod_0", 1.0, 3000, 2, 100.0, **tolerant),
                pod_event("pod_1", 12.0, 3000, 2, 100.0, **tolerant),
                pod_event("pod_2", 150.0, 3000, 2, 100.0, **tolerant),
            ]
        )
        return cluster, workload

    scalar = _scalar_run(_config(), traces())
    placed = {name: pod.status.assigned_node for name, pod in scalar.persistent_storage.succeeded_pods.items()}
    assert placed == {"pod_0": "node_1", "pod_1": "node_0", "pod_2": "node_1"}
    for formulation in ("scan", "megakernel"):
        batched = _batched_run(_config(), traces(), formulation, n_clusters=1)
        assert _compare(scalar, batched) == (3, 0)
        batched.metrics_summary()
        report = batched.telemetry_report()["counters"]
        # pod_1's only feasible preferred node is gone: M = 0, nothing to honour.
        assert (report["soft_attempts"], report["soft_honoured"]) == (2, 2)


def test_a_build_without_soft_facts_carries_no_soft_plane_and_ranks_in_integers():
    from pools_traces import pools_traces

    batched = _batched_run(_config(), pools_traces(3, 20, 120), "scan")
    assert batched.kernel_formulation()["ranking"] == "integer"
    assert batched.state.affinity.pod_soft_terms is None and batched.state.metrics.soft_attempts is None
    scalar = _scalar_run(_config(), pools_traces(3, 20, 120))
    assert _compare(scalar, batched, cluster=1) == (120, 0)
    assert "soft_attempts" not in batched.telemetry_report()["counters"]


# --- (d) parsing ------------------------------------------------------------------


def test_preferred_terms_parse_both_spellings_and_round_trip():
    upstream = {
        "metadata": {"name": "p"},
        "spec": {
            "resources": {"requests": {"cpu": 1, "ram": 1}},
            "affinity": {
                "nodeAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {"weight": 50, "preference": {"matchExpressions": [{"key": "pool", "operator": "In", "values": ["a"]}]}},
                        {"weight": 1, "preference": {"matchExpressions": [{"key": "zone", "operator": "Exists"}]}},
                    ]
                }
            },
            "tolerations": [{"key": "reserved", "operator": "Exists", "effect": "PreferNoSchedule"}],
        },
    }
    pod = Pod.from_dict(upstream)
    affinity = pod.spec.node_affinity
    assert not affinity.has_required and [t.weight for t in affinity.preferred] == [50, 1]
    assert affinity.preferred[0].preference.match_expressions[0].values == ["a"]
    again = Pod.from_dict(pod.to_dict())
    assert again == pod and "required" not in pod.to_dict()["spec"]["affinity"]["node_affinity"]
    snake = Pod.from_dict(
        {"metadata": {"name": "p"}, "spec": {"resources": {"requests": {"cpu": 1, "ram": 1}}, "affinity": preferred((50, [("pool", "In", ["a"])]), (1, [("zone", "Exists", [])])), "tolerations": [{"key": "reserved", "operator": "Exists", "effect": "PreferNoSchedule"}]}}
    )
    assert snake == pod
    node = Node.from_dict(node_event("n", 1, 1, {}, [SOFT_TAINT])["event_type"]["node"])
    assert Node.from_dict(node.to_dict()).spec.taints == [Taint("reserved", "highmem", "PreferNoSchedule")]


# --- (e) refusals, by their messages ----------------------------------------------


def _build(pods, nodes=None, profile="kube_default", **kwargs):
    cluster = GenericClusterTrace(events=nodes or [node_event("node_0", 4000, 8, {"pool": "a"})])
    workload = GenericWorkloadTrace(
        events=[
            {"timestamp": 1.0 + i, "event_type": {"__tag__": "CreatePod", "pod": pod}} for i, pod in enumerate(pods)
        ]
    )
    return build_batched_from_traces(
        _config(profile), cluster.convert_to_simulator_events(), workload.convert_to_simulator_events(),
        n_clusters=1, **kwargs,
    )


def _pod(name="p", cpu=1000, ram=GiB, **placement):
    pod = pod_event(name, 0.0, cpu, 1, 10.0, **placement)["event_type"]["pod"]
    pod["spec"]["resources"] = {"requests": {"cpu": cpu, "ram": ram}, "limits": {"cpu": cpu, "ram": ram}}
    return pod


def test_node_pools_still_refuses_a_preference_it_would_ignore_on_both_paths():
    prefers = _pod(affinity=preferred((1, [("pool", "In", ["a"])])))
    with pytest.raises(UnsupportedNodePlacement, match="pod 'p': preferredDuringScheduling.*does not score by NodeAffinity"):
        _build([prefers], profile="node_pools")
    soft_node = node_event("node_0", 4000, 8, {}, [SOFT_TAINT])
    with pytest.raises(UnsupportedNodePlacement, match="node 'node_0': the taint effect PreferNoSchedule.*does not score by TaintToleration"):
        _build([_pod()], nodes=[soft_node], profile="node_pools")
    algorithm = KubeScheduler(kube_scheduler_config_from_spec("node_pools"))
    with pytest.raises(UnsupportedNodePlacement, match="preferredDuringScheduling"):
        algorithm.schedule_one(Pod.from_dict(prefers), {"n": Node.new("n", 4000, 8 * GiB)})
    with pytest.raises(UnsupportedNodePlacement, match="taint effect PreferNoSchedule"):
        algorithm.schedule_one(Pod.from_dict(_pod()), {"n": Node.from_dict(soft_node["event_type"]["node"])})
    # Under `default` the plugins are off and both are inert, as upstream's are.
    _build([prefers], nodes=[soft_node], profile="default")
    _build([prefers], nodes=[soft_node])  # and kube_default takes them


@pytest.mark.parametrize(
    "placement,names",
    [
        (preferred((0, [("pool", "In", ["a"])])), "preferred term of weight 0"),
        (preferred((101, [("pool", "In", ["a"])])), "preferred term of weight 101"),
        (preferred((1, [("cores", "Gt", ["4"])])), "operator Gt"),
        (preferred((1, [])), "nodeSelectorTerm without matchExpressions"),
    ],
    ids=["weight-0", "weight-101", "Gt", "empty-term"],
)
def test_preferred_terms_refused_by_name_on_both_paths(placement, names):
    pod = _pod(affinity=placement)
    with pytest.raises(UnsupportedNodePlacement, match=names):
        _build([pod])
    with pytest.raises(UnsupportedNodePlacement, match=names):
        KubeScheduler(kube_scheduler_config_from_spec("kube_default")).schedule_one(
            Pod.from_dict(pod), {"n": Node.new("n", 4000, 8 * GiB)}
        )


def test_the_batched_build_refuses_what_the_planes_and_int32_cannot_hold():
    wide = _pod("pod_wide", affinity=preferred(*[(1, [("rack", "In", [f"r{i}"])]) for i in range(SOFT_MAX_TERMS + 1)]))
    with pytest.raises(ValueError, match="pod 'pod_wide': 5 preferred terms, more than the 4 preferred-term planes"):
        _build([wide])
    # 100 * A_cpu * A_ram in units: requests of 1 millicore and 1 MiB leave the
    # capacity 96000 x 262144 as it is.
    big = node_event("node_big", 96000, 256, {})
    with pytest.raises(UnsupportedProfileError, match=r"node 'node_big': capacity 96000 x 262144 .* passes int32"):
        _build([_pod(cpu=1, ram=1024 * 1024)], nodes=[big])
    with pytest.raises(UnsupportedProfileError, match="pod 'p': its RAM is not a whole number of RAM units"):
        _build([_pod(ram=GiB + 1)])
    _build([_pod(ram=GiB + 1)], profile="node_pools")  # the float profiles round up, as before


def test_profiles_that_mix_or_misweigh_the_scorers_are_refused_by_name():
    mixed = {"filters": ["Fit"], "score": [{"name": NODE_RESOURCES_FIT}, {"name": "LeastAllocatedResources"}]}
    with pytest.raises(ValueError, match=r"mixes kube-scheduler's integer scorers \['NodeResourcesFit'\] with the reference's float scorers \['LeastAllocatedResources'\]"):
        kube_scheduler_config_from_spec(mixed)
    with pytest.raises(ValueError, match="mixes"):
        compile_profile(mixed)
    hand_built = compile_profile("kube_default")._replace(
        scores=((NODE_RESOURCES_FIT, 1.0), ("MostAllocatedResources", 1.0))
    )
    with pytest.raises(UnsupportedProfileError, match="float score plugin 'MostAllocatedResources' beside integer scorers"):
        compile_profile(hand_built)
    for weight in (0.5, 0, 2.5):
        with pytest.raises(ValueError, match="weights are positive integers"):
            kube_scheduler_config_from_spec({"filters": ["Fit"], "score": [{"name": BALANCED_ALLOCATION, "weight": weight}]})
    with pytest.raises(ValueError, match="scores by 'NodeAffinity' without filtering by it"):
        kube_scheduler_config_from_spec({"filters": ["Fit"], "score": [{"name": NODE_AFFINITY, "weight": 2}]})


def test_an_integer_profile_has_no_exact_key_and_says_nothing(caplog):
    profile = compile_profile("kube_default")
    hetero = [(np.asarray([500, 1000, 7000]), np.asarray([1024, 3000, 9000]))]
    caps = [(np.asarray([64000, 96000]), np.asarray([131072, 196608]))]
    with caplog.at_level("WARNING"):
        assert pipeline.exact_score_bits(profile, hetero, caps) == 0
    assert caplog.records == []
    assert pipeline.exact_score_bits(compile_profile("node_pools"), hetero, caps) > 0
    assert pipeline.integer_score_units(hetero, caps) == (500, 8)


def test_autoscalers_are_refused_under_an_integer_profile():
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
    config = dataclasses.replace(config_with("test", suffix), scheduler_profile="kube_default")
    cluster = GenericClusterTrace(events=[node_event("node_0", 4000, 8, {})])
    workload = GenericWorkloadTrace(events=[pod_event("p", 1.0, 1000, 1, 10.0)])
    with pytest.raises(UnsupportedProfileError, match="scores in integers of the build's common resource units"):
        build_batched_from_traces(
            config, cluster.convert_to_simulator_events(), workload.convert_to_simulator_events(), n_clusters=1
        )


def test_every_combination_of_the_four_scorers_compiles_and_agrees_with_the_scalar_path():
    """A profile may take any subset of the integer scorers at any positive
    integer weights: scan against scalar on one small trace each."""
    names = [NODE_RESOURCES_FIT, BALANCED_ALLOCATION, NODE_AFFINITY, TAINT_TOLERATION]
    # The trace has preferred terms and a soft taint, so a profile must score
    # by both label plugins (else it refuses them, above).
    for subset in ([names[0]] + names[2:], names[1:], names[2:], names[::-1]):
        spec = {
            "filters": ["Fit", "NodeAffinity", "TaintToleration"],
            "score": [{"name": n, "weight": w} for n, w in zip(subset, (3, 2, 5, 1))],
        }
        config = dataclasses.replace(config_with("zero"), scheduler_profile=spec)
        scalar = _scalar_run(config, kubescore_traces(9, 12, 80))
        batched = _batched_run(config, kubescore_traces(9, 12, 80), "scan", n_clusters=1)
        assert _compare(scalar, batched) == (80, 0), subset


def test_split_launch_carries_the_integer_scorers_planes_of_the_lanes_it_moves():
    """tests/test_node_affinity.py's split-launch case under `kube_default`:
    three lane tiles, two clusters with a burst deeper than a pass; the second
    launch drains them in a tile of their own, their capacity planes and soft
    planes moved with them (step._launch_by_depth), and the state equals the
    single launch's leaf for leaf, the two soft counters included."""
    from kubernetriks_tpu.batched import step
    from kubernetriks_tpu.batched.engine import BatchedSimulation
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
    from pools_traces import TAINT, TOLERATION
    from test_cycle_compact import leaves_differing, single_launch, traced_with

    C, K, burst, end = 300, 8, 24, 70.0
    deep = {3: "burst", 131: "burst"}
    config = _config()
    placements = [
        {},
        {"affinity": preferred((50, [("pool", "In", ["highmem"])]), (1, [("pool", "Exists", [])]))},
        {"tolerations": [dict(TOLERATION)], "affinity": preferred((50, [("dedicated", "In", ["batch"])]))},
        {"node_selector": {"pool": "highmem"}, "tolerations": [{"key": "reserved", "operator": "Exists"}]},
    ]
    nodes = GenericClusterTrace(
        events=[node_event(f"node_{i:03d}", 7700, 15, {"pool": "general"}) for i in range(8)]
        + [node_event(f"node_{i:03d}", 7900, 31, {"pool": "highmem"}, [SOFT_TAINT]) for i in range(8, 10)]
        + [node_event(f"node_{i:03d}", 4300, 9, {"pool": "dedicated", "dedicated": "batch"}, [TAINT]) for i in range(10, 12)]
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
        assert sim.kernel_formulation() | {"events": 0} == {
            "cycle": "megakernel", "interpret": True, "ranking": "integer", "events": 0, "sharding": None
        }
        sim.step_until_time(end)
        return sim

    with traced_with(CYCLE_COMPACT_PAYS=0):
        split = run()
    with traced_with(_launch_by_depth=single_launch):
        single = run()
    assert leaves_differing(split.state, single.state, skip=("cycle_compacted",)) == []
    compacted = np.asarray(split.state.metrics.cycle_compacted)
    assert compacted[list(deep)].tolist() == [1, 1] and compacted.sum() == 2
    attempts = np.asarray(split.state.metrics.soft_attempts)
    assert int(attempts[3]) > int(attempts[4]) > 0
    assert step._launch_by_depth is not single_launch
