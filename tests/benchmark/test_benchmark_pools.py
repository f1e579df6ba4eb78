"""The cell PR 47 added, end to end on the CPU at toy sizes behind the
rehearsal flag: `sched1k-pools.montecarlo` (the megakernel, interpreted,
ranking by the exact key) against the oracle copy scheduling with the
REFERENCE's own algorithm (benchmark/pools_reference.py), both of its
controls failing, its per-layer metrics, the configuration and the mix held to
the issue's tables, the generator, the kernel counts by hand, and the
reference's `schedule_one` against the program's scalar plugins on seeded
random clusters: two implementations of docs/PARITY.md "Node affinity and
taints" that share no line."""

import json
import os
import random
from types import SimpleNamespace

import pytest

from benchmark import harness, kernel_counts, peaks, pools_gen, pools_kernel_counts, pools_reference
from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
POOLS = "sched1k-pools.montecarlo"
GIB = 1024**3
ZONE = "topology.kubernetes.io/zone"


def run_cell(capsys, trace, control=0):
    rc = bench_run.main(
        [
            "--workload", POOLS, "--seed", str(2**31 + 47), "--seconds", "1",
            "--trace", str(trace), "--control", str(control),
            "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", POOLS + ".json"),
        ]
    )
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    return rc, lines


def load(*path):
    with open(os.path.join(ROOT, *path)) as fh:
        return json.load(fh)


def manifest_metrics(group):
    return {m["name"]: m["unit"] for m in load("BENCHMARK.json")[group] if POOLS in m.get("workloads", [POOLS])}


# --- the configuration and the mix are the issue's tables ------------------------


def test_pools_is_sched1k_made_of_four_pools_and_nothing_else():
    base, held = load("benchmark", "configs", "sched1k.json"), load("benchmark", "configs", "sched1k-pools.json")
    dep = held["deployment"]
    for key in ("nodes", "scheduling_cycle_interval_s", "control_plane_delays_s", "horizontal_pod_autoscaler", "cluster_autoscaler"):
        assert dep[key] == base["deployment"][key], key
    assert dep["scheduler_profile"] == "node_pools"
    assert held["engine"] == base["engine"] and held["reduced"] == base["reduced"] == []
    unweakened = {k: v for k, v in held["guarantees"].items() if k not in ("statement", "counters_exact")}
    assert unweakened == {k: v for k, v in base["guarantees"].items() if k not in ("statement", "counters_exact")}
    assert held["guarantees"]["counters_exact"] == base["guarantees"]["counters_exact"] + list(pools_reference.POOL_COUNTERS)
    assert "recalled" in held["assumed"]["source_from_memory"]
    pools = {p["name"]: p for p in dep["pools"]}
    assert [(p["name"], p["nodes"], p["cpu_millicores"], p["ram_gib"]) for p in dep["pools"]] == [
        ("general", 714, 64000, 128), ("highmem", 150, 64000, 256), ("compute", 100, 96000, 192), ("dedicated", 36, 32000, 64),
    ]
    assert sum(p["nodes"] for p in dep["pools"]) == dep["nodes"] == 1000
    assert pools["dedicated"]["labels"] == {"pool": "dedicated", "dedicated": "batch"}
    assert pools["dedicated"]["taints"] == [{"key": "dedicated", "value": "batch", "effect": "NoSchedule"}]
    assert all(p["taints"] == [] and p["labels"] == {"pool": name} for name, p in pools.items() if name != "dedicated")
    assert dep["zones"]["key"] == ZONE and dep["zones"]["values"] == ["zone1", "zone2", "zone3"]
    entry = {c["name"]: c for c in load("BENCHMARK.json")["configs"]}["sched1k-pools"]
    assert entry["source"] == held["source"] and len(entry["source"]) <= 200 and entry["reduced"] == []
    cell = {w["name"]: w for w in load("BENCHMARK.json")["workloads"]}[POOLS]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sched1k-pools", "montecarlo-pools", 1)
    assert len(cell["why"]) <= 200


def test_the_mix_is_cell_ones_loop_with_the_five_classes():
    mix, one = load("benchmark", "traffic", "montecarlo-pools.json"), load("benchmark", "traffic", "montecarlo.json")
    assert mix["driver"] == "batch_jobs_pools"
    for key in ("clusters_per_chip", "job_end_s", "pod_group", "engine", "warmup_jobs", "trace_seconds"):
        assert mix[key] == one[key], key
    assert mix["plain"] == {k: one["plain"][k] for k in ("rate_per_second", "horizon_s")}
    assert mix["asserts"]["cycle"] == "megakernel" and mix["asserts"]["ranking"] == "exact"
    assert mix["asserts"]["min_decisions_per_cluster"] == one["asserts"]["min_decisions_per_cluster"] == 1500
    assert mix["asserts"]["cycle_overruns"] == 0 and mix["asserts"]["min_pods_that_waited"] >= 1
    plain = [[0.5, 1], [1, 4], [2, 4], [4, 8], [8, 32], [16, 32]]
    classes = {c["name"]: c for c in mix["classes"]}
    assert [(c["name"], c["share"]) for c in mix["classes"]] == [
        ("plain", 0.45), ("zonal", 0.20), ("highmem", 0.15), ("tolerant", 0.10), ("dedicated", 0.10),
    ]
    assert all(classes[name]["requests_cores_gib"] == plain for name in ("plain", "zonal", "tolerant"))
    assert classes["highmem"]["requests_cores_gib"] == [[4, 48], [8, 96]]
    assert classes["dedicated"]["requests_cores_gib"] == [[8, 16]] and classes["dedicated"]["duration_s"] == [400.0, 1200.0]
    assert all(c["duration_s"] == [30.0, 120.0] for name, c in classes.items() if name != "dedicated")
    toleration = [["dedicated", "Equal", "batch", "NoSchedule"]]
    assert classes["zonal"]["node_affinity_terms"] == [[[ZONE, "In", ["zone1", "zone2"]]]]
    assert classes["highmem"]["node_selector"] == {"pool": "highmem"}
    assert classes["tolerant"]["tolerations"] == toleration and "node_affinity_terms" not in classes["tolerant"]
    assert classes["dedicated"]["tolerations"] == toleration
    assert classes["dedicated"]["node_affinity_terms"] == [[["dedicated", "In", ["batch"]]]]
    assert not {"node_selector", "node_affinity_terms", "tolerations"} & set(classes["plain"])


def test_the_capacities_leave_the_exact_key_twelve_bits():
    """256 GiB in the 1 MiB RAM unit is 2**18: 31 - 19 bits a digit."""
    import numpy as np

    from kubernetriks_tpu.batched import pipeline
    from kubernetriks_tpu.batched.state import DEFAULT_RAM_UNIT

    dep = load("benchmark", "configs", "sched1k-pools.json")["deployment"]
    mix = load("benchmark", "traffic", "montecarlo-pools.json")
    nodes = [(np.array([p["cpu_millicores"] for p in dep["pools"]]), np.array([p["ram_gib"] * GIB // DEFAULT_RAM_UNIT for p in dep["pools"]]))]
    shapes = [shape for c in mix["classes"] for shape in c["requests_cores_gib"]]
    pods = [(np.array([int(c * 1000) for c, _ in shapes]), np.array([g * GIB // DEFAULT_RAM_UNIT for _, g in shapes]))]
    assert pipeline.exact_score_bits(pipeline.compile_profile("node_pools"), pods, nodes) == 12


# --- the generator -----------------------------------------------------------------


def test_cluster_records_deal_pools_and_zones_in_name_order():
    dep = load("benchmark", "configs", "sched1k-pools.json")["deployment"]
    nodes = pools_gen.cluster_records(dep)
    assert len(nodes) == 1000 and [rec[2] for rec in nodes] == sorted(rec[2] for rec in nodes)
    assert [rec[5]["pool"] for rec in nodes] == ["general"] * 714 + ["highmem"] * 150 + ["compute"] * 100 + ["dedicated"] * 36
    assert [rec[5][ZONE] for rec in nodes[:4]] == ["zone1", "zone2", "zone3", "zone1"]
    assert nodes[713][3:5] == (64000, 128 * GIB) and nodes[714][3:5] == (64000, 256 * GIB)
    assert nodes[864][3:5] == (96000, 192 * GIB) and nodes[964][3:5] == (32000, 64 * GIB)
    tainted = pools_gen.taints_by_node(nodes)
    assert sum(1 for taints in tainted.values() if taints) == 36
    assert tainted["gen_node_0999"] == [("dedicated", "batch", "NoSchedule")] and tainted["gen_node_0000"] == []
    assert nodes[999][5]["dedicated"] == "batch" and "dedicated" not in nodes[0][5]
    with pytest.raises(ValueError, match="the pools hold 1000 nodes, the deployment says 999"):
        pools_gen.cluster_records({**dep, "nodes": 999})


def test_workload_records_are_seeded_a_cluster_and_hold_the_class_shares():
    mix = load("benchmark", "traffic", "montecarlo-pools.json")
    seed = 2**31 + 5
    pods = pools_gen.workload_records(mix, seed, 3)
    assert pods == pools_gen.workload_records(mix, seed, 3)
    assert pods != pools_gen.workload_records(mix, seed, 4) and pods != pools_gen.workload_records(mix, seed + 1, 3)
    assert len(pods) == 2000 and [rec[0] for rec in pods] == sorted(rec[0] for rec in pods)
    assert [rec[2] for rec in pods[:2]] == ["pod_00000", "pod_00001"] and 0.0 <= pods[0][0] and pods[-1][0] < 1000.0
    many = [rec for c in range(10) for rec in pools_gen.workload_records(mix, seed, c)]
    by_class = {}
    for rec in many:
        by_class.setdefault(pools_gen.class_of(mix, rec[6]), []).append(rec)
    for cls in mix["classes"]:
        got = by_class[cls["name"]]
        assert abs(len(got) / len(many) - cls["share"]) < 0.015, cls["name"]
        shapes = {(rec[3], rec[4]) for rec in got}
        assert shapes == {(int(c * 1000), g * GIB) for c, g in cls["requests_cores_gib"]}
        lo, hi = cls["duration_s"]
        assert all(lo <= rec[5] <= hi for rec in got)
    held = pools_gen.placements_by_pod(pods)
    assert len(held) == 2000
    some = next(rec for rec in pods if pools_gen.class_of(mix, rec[6]) == "dedicated")
    assert some[6] == {
        "node_selector": {}, "terms": [[("dedicated", "In", ["batch"])]],
        "tolerations": [("dedicated", "Equal", "batch", "NoSchedule")],
    }


def test_reference_and_generator_import_nothing_of_the_program():
    for name in ("pools_reference.py", "pools_gen.py", "pools_kernel_counts.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as fh:
            assert "kubernetriks_tpu" not in fh.read(), name


# --- the reference by hand ---------------------------------------------------------


def _placement(selector=None, terms=(), tolerations=()):
    return {"node_selector": dict(selector or {}), "terms": [list(t) for t in terms], "tolerations": list(tolerations)}


@pytest.mark.parametrize(
    "placement,labels,admits",
    [
        (_placement(), {}, True),
        (_placement({"pool": "highmem"}), {"pool": "highmem", ZONE: "zone3"}, True),
        (_placement({"pool": "highmem"}), {"pool": "general"}, False),
        (_placement(terms=[[(ZONE, "In", ["zone1", "zone2"])]]), {ZONE: "zone2"}, True),
        (_placement(terms=[[(ZONE, "In", ["zone1", "zone2"])]]), {ZONE: "zone3"}, False),
        (_placement(terms=[[(ZONE, "In", ["zone1", "zone2"])]]), {}, False),
        (_placement(terms=[[(ZONE, "NotIn", ["zone1"])]]), {}, True),
        (_placement(terms=[[("dedicated", "Exists", [])]]), {"dedicated": "batch"}, True),
        (_placement(terms=[[("dedicated", "DoesNotExist", [])]]), {"dedicated": "batch"}, False),
        (_placement(terms=[[("a", "Exists", []), ("b", "Exists", [])]]), {"a": "1"}, False),  # a term is an AND
        (_placement(terms=[[("a", "Exists", [])], [("b", "Exists", [])]]), {"b": "1"}, True),  # the terms an OR
        (_placement({"pool": "x"}, terms=[[("b", "Exists", [])]]), {"b": "1"}, False),  # the selector ANDs with them
    ],
)
def test_reference_labels_by_hand(placement, labels, admits):
    assert pools_reference.labels_admit(placement, labels) is admits


@pytest.mark.parametrize(
    "tolerations,taints,admits",
    [
        ((), [], True),
        ((), [("dedicated", "batch", "NoSchedule")], False),
        ([("dedicated", "Equal", "batch", "NoSchedule")], [("dedicated", "batch", "NoSchedule")], True),
        ([("dedicated", "Equal", "batch", "")], [("dedicated", "batch", "NoSchedule")], True),
        ([("dedicated", "Equal", "web", "NoSchedule")], [("dedicated", "batch", "NoSchedule")], False),
        ([("dedicated", "Exists", "", "")], [("dedicated", "web", "NoSchedule")], True),
        ([("", "Exists", "", "")], [("dedicated", "web", "NoSchedule"), ("gpu", "", "NoSchedule")], True),
        ([("dedicated", "Exists", "", "")], [("dedicated", "web", "NoSchedule"), ("gpu", "", "NoSchedule")], False),
    ],
)
def test_reference_taints_by_hand(tolerations, taints, admits):
    assert pools_reference.taints_admit(_placement(tolerations=tolerations), taints) is admits
    assert pools_reference.names_nodes(_placement(tolerations=tolerations)) is bool(tolerations)


def _hand_node(name, cpu, ram_gib, labels):
    return SimpleNamespace(
        metadata=SimpleNamespace(name=name, labels=labels),
        status=SimpleNamespace(allocatable=SimpleNamespace(cpu=cpu, ram=ram_gib * GIB)),
    )


def _hand_pod(name, cpu, ram_gib):
    return SimpleNamespace(
        metadata=SimpleNamespace(name=name),
        spec=SimpleNamespace(resources=SimpleNamespace(requests=SimpleNamespace(cpu=cpu, ram=ram_gib * GIB))),
    )


def test_reference_schedule_one_by_hand():
    class Failure(Exception):
        pass

    nodes = {
        "n0": _hand_node("n0", 64000, 128, {"pool": "general"}),
        "n1": _hand_node("n1", 64000, 128, {"pool": "general"}),
        "n2": _hand_node("n2", 96000, 192, {"pool": "compute"}),
        "n3": _hand_node("n3", 8000, 16, {"pool": "dedicated", "dedicated": "batch"}),
    }
    taints = {"n3": [("dedicated", "batch", "NoSchedule")]}
    dedicated = _placement(terms=[[("dedicated", "In", ["batch"])]], tolerations=[("dedicated", "Equal", "batch", "NoSchedule")])
    placements = {
        "plain": _placement(), "general": _placement({"pool": "general"}), "tolerant": _placement(tolerations=dedicated["tolerations"]),
        "dedicated": dedicated, "second": dedicated, "huge": _placement({"pool": "general"}),
    }
    algorithm = pools_reference.PoolsScheduling(placements, taints, Failure, "no_fit", "zero", "no_nodes")
    # 4/8 leaves 95.83% of the 96-core node, 93.75% of a 64-core one: the largest machine wins
    assert algorithm.schedule_one(_hand_pod("plain", 4000, 8), nodes) == "n2"
    # among the two equal general nodes the last in name order
    assert algorithm.schedule_one(_hand_pod("general", 4000, 8), nodes) == "n1"
    # a toleration is no affinity: the tolerant pod still goes where the score is best
    assert algorithm.schedule_one(_hand_pod("tolerant", 4000, 8), nodes) == "n2"
    assert algorithm.schedule_one(_hand_pod("dedicated", 8000, 16), nodes) == "n3"
    assert algorithm.counts == {"affinity_attempts": 3, "affinity_attempts_refused": 0}
    nodes["n3"].status.allocatable.cpu = 0  # the pool is full, the cluster is not
    with pytest.raises(Failure, match="no_fit"):
        algorithm.schedule_one(_hand_pod("second", 8000, 16), nodes)
    assert algorithm.counts == {"affinity_attempts": 4, "affinity_attempts_refused": 1}
    # capacity, not labels, refuses this one: no node fits it at all
    with pytest.raises(Failure, match="no_fit"):
        algorithm.schedule_one(_hand_pod("huge", 128000, 8), nodes)
    assert algorithm.counts == {"affinity_attempts": 5, "affinity_attempts_refused": 1}


# --- two implementations of one semantics block -------------------------------------


def _random_cluster(seed):
    """Program-side nodes and pods, and the same placements and taints as the
    reference holds them (plain data beside the objects)."""
    from kubernetriks_tpu.core.types import Node, NodeAffinity, NodeSelectorRequirement, NodeSelectorTerm, Pod, Taint, Toleration

    rng = random.Random(seed)
    keys, values = ["pool", "zone", "disk"], ["a", "b", "c"]
    nodes, taints = {}, {}
    for i in range(rng.randint(3, 12)):
        node = Node.new(f"node_{i:02d}", rng.choice([2000, 4000, 8000, 12000]), rng.choice([8, 16, 24]) * GIB)
        for key in keys:
            if rng.random() < 0.7:
                node.metadata.labels[key] = rng.choice(values)
        carried = [(k, rng.choice(values), "NoSchedule") for k in ("dedicated", "gpu") if rng.random() < 0.25]
        node.spec.taints = [Taint(*t) for t in carried]
        node.status.allocatable.cpu -= rng.choice([0, 500, 1000, node.status.allocatable.cpu])
        nodes[node.metadata.name], taints[node.metadata.name] = node, carried
    pods, placements = [], {}
    for k in range(8):
        pod = Pod.new(f"cand_{k}", rng.choice([500, 1000, 2000]), rng.choice([1, 2, 4]) * GIB, 10.0)
        selector = {rng.choice(keys): rng.choice(values)} if rng.random() < 0.3 else {}
        terms = [
            [
                (rng.choice(keys), op, sorted(rng.sample(values, rng.randint(1, 2))) if op in ("In", "NotIn") else [])
                for op in rng.sample(["In", "NotIn", "Exists", "DoesNotExist"], rng.randint(1, 2))
            ]
            for _ in range(rng.choice([0, 0, 1, 2]))
        ]
        tolerations = [
            (key, op, rng.choice(values) if op == "Equal" else "", rng.choice(["", "NoSchedule"]))
            for key, op in rng.sample([("dedicated", "Equal"), ("gpu", "Exists"), ("", "Exists"), ("dedicated", "Exists")], rng.choice([0, 0, 1, 2]))
        ]
        pod.spec.node_selector = dict(selector)
        if terms:
            pod.spec.node_affinity = NodeAffinity(
                required_terms=[NodeSelectorTerm([NodeSelectorRequirement(k_, op, list(v)) for k_, op, v in term]) for term in terms]
            )
        pod.spec.tolerations = [Toleration(*t) for t in tolerations]
        pods.append(pod)
        placements[pod.metadata.name] = _placement(selector, terms, tolerations)
    return nodes, taints, pods, placements


@pytest.mark.parametrize("seed", range(30))
def test_reference_schedule_one_equals_the_programs_plugins(seed):
    from kubernetriks_tpu.core.scheduler.interface import ScheduleError, SchedulingFailure
    from kubernetriks_tpu.core.scheduler.kube_scheduler import KubeScheduler, kube_scheduler_config_from_spec

    nodes, taints, pods, placements = _random_cluster(seed)
    reference = pools_reference.PoolsScheduling(
        placements, taints, SchedulingFailure, ScheduleError.NO_SUFFICIENT_RESOURCES,
        ScheduleError.REQUESTED_RESOURCES_ARE_ZEROS, ScheduleError.NO_NODES_IN_CLUSTER,
    )
    program = KubeScheduler(kube_scheduler_config_from_spec("node_pools"))
    for pod in pods:
        def outcome(schedule):
            try:
                return schedule()
            except SchedulingFailure as failure:
                return failure.error

        assert outcome(lambda: program.schedule_one(pod, nodes)) == outcome(lambda: reference.schedule_one(pod, nodes)), (
            seed, pod.metadata.name,
        )


# --- the cell on the CPU -------------------------------------------------------------


def test_pools_rehearsal_against_the_reference_and_both_controls_fail(capsys):
    """`correct` against the oracle copy with the reference's algorithm
    installed: every sampled pod's phase, node and start time and the two
    label-filter counters, the megakernel interpreted and ranking by the exact
    key. `--control 1` fails twice over: times through float32 miss
    `start_time_gap_s`, and the same traces under the `default` profile put
    pods on other nodes."""
    rc, lines = run_cell(capsys, trace=0, control=1)
    result = lines[-1]
    assert rc == 0 and set(result) == RESULT_KEYS | {"control_correct"} and result["rehearsal"] is True
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert {k: v["unit"] for k, v in result["metrics"].items()} == manifest_metrics("end_to_end")
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    setup = next(row for row in lines if row.get("line") == "setup")
    assert setup["formulation"]["cycle"] == "megakernel" and setup["formulation"]["ranking"] == "exact"
    checks = {row["check"]: row for row in lines if row.get("line") == "check"}
    for suffix in ("pods_on_another_node", "pods_in_another_phase", "affinity_attempts", "affinity_attempts_refused"):
        rows = [row for name, row in checks.items() if name.endswith("." + suffix)]
        assert len(rows) == 2 and all(row["ok"] for row in rows), suffix
    pending = [row["pods_pending"] for row in lines if row.get("line") == "pools"]
    refused = [row["affinity_attempts_refused"] for row in lines if row.get("line") == "pools"]
    assert len(pending) == 2 and sum(refused) > 0
    assert result["control_correct"] is False
    failed = {row["check"] for row in lines if row.get("line") == "control" and not row["ok"]}
    assert any(name.startswith("oracle.") and name.endswith("start_time_gap_s") for name in failed)
    moved = [name for name in failed if name.startswith("default_profile.") and name.endswith("pods_on_another_node")]
    assert len(moved) == 2
    shares = [row["share"] for row in lines if row.get("line") == "control_default_profile"]
    assert len(shares) == 2 and all(share > 0.2 for share in shares)


def test_pools_traced_rehearsal_reports_the_refused_share(capsys):
    rc, lines = run_cell(capsys, trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    allowed = manifest_metrics("per_layer")
    got = {k: v["unit"] for k, v in result["metrics"].items()}
    assert got and set(got.items()) <= set(allowed.items())
    assert {"affinity_refused_share", "dispatches_per_job", "window_device_ms.batch", "unscoped_device_share.batch"} <= set(got)
    assert 0.0 < result["metrics"]["affinity_refused_share"]["value"] < 100.0
    counters = next(row for row in lines if row.get("line") == "counters")
    assert 0 < counters["affinity_attempts_refused"] < counters["affinity_attempts"]
    assert (counters["affinity_terms"], counters["exact_bits"], counters["ranking"]) == (1, 12, "exact")
    assert counters["cycle_overruns"] == 0 and counters["cycle_formulation"] == "megakernel"
    # an interpreted kernel is no event of a CPU trace: the megakernel's device metrics read nothing here
    assert "cycle_kernel_roofline.pools" not in got and "cycle_kernel_ms" not in got
    assert {"cycle_kernel_roofline.pools", "cycle_kernel_ms", "free_kernel_roofline"} <= set(allowed)
    assert "cycle_kernel_roofline" not in allowed  # its float32 node passes are a seventh of the exact key's


def test_the_roofline_reader_on_the_cells_shape_names_its_leg():
    """The reader on a run object as the chip's traced run fills it (cell 1's
    0.645 ms a launch, 121 launches a job): under 100%, the bound named; and
    nothing to read from a program without the term planes."""
    reader = harness.reader("cycle_kernel_roofline.pools")
    counters = dict(
        cycle_formulation="megakernel", ranking="exact", affinity_terms=1, clusters=1250, nodes=1000, pods=2048,
        max_pods_per_cycle=64, decisions=2 * 1250 * 1990, jobs=2,
    )
    trace = SimpleNamespace(kernel_events={"cycle": 242}, kernel_s={"cycle": 242 * 0.645e-3})
    run = SimpleNamespace(trace=trace, counters=counters, device={"kind": "TPU v5 lite"}, cell=SimpleNamespace(chips=1))
    share = reader.read(run)
    assert 20.0 < share < 100.0
    assert reader.read(SimpleNamespace(**{**vars(run), "counters": {k: v for k, v in counters.items() if k != "affinity_terms"}})) is None
    assert reader.read(SimpleNamespace(**{**vars(run), "trace": None})) is None
    refused = harness.reader("affinity_refused_share")
    assert refused.read(SimpleNamespace(counters={"affinity_attempts": 200, "affinity_attempts_refused": 50})) == 25.0
    assert refused.read(SimpleNamespace(counters={})) is None


# --- the kernel counts by hand ---------------------------------------------------------


def test_pools_kernel_counts_against_the_block_list_by_hand():
    # 16 nodes, 24 pods, K = 8, 3 clusters, one term plane: kernel_counts' 15 in +
    # 7 out blocks, plus in: the node bit plane (16), a term plane and the
    # untolerated-taint plane (48); out: the counter tile (8)
    base = kernel_counts.megakernel_hbm_bytes(3, 16, 24, 8)
    assert pools_kernel_counts.megakernel_hbm_bytes(3, 16, 24, 8, terms=1) == base + (16 + 48 + 8) * 4 * 128
    assert pools_kernel_counts.megakernel_hbm_bytes(3, 16, 24, 8, terms=3) == base + (16 + 96 + 8) * 4 * 128
    # a long division's digit is 17 passes, six digits a node, 6 for the guarded divisors, 14 to assemble the words
    assert pools_kernel_counts.EXACT_KEY_PASSES == 122
    assert pools_kernel_counts.label_filter_passes(1) == 8 and pools_kernel_counts.label_filter_passes(2) == 11
    assert pools_kernel_counts.node_passes(1) == 4 + 8 + 122 + 9 + 2 + 6 == 151
    assert pools_kernel_counts.pod_passes(1) == kernel_counts.MEGAKERNEL_POD_PASSES + 2
    ops = pools_kernel_counts.megakernel_ops(3, 16, 24, iterations=2.0, terms=1)
    assert ops == 2.0 * (39 * 24 + 151 * 16) * 128
    # the cell's shape: seven times cell 1's node passes (151 against 20), and still the memory leg
    # at the peak the benchmark holds vector operations to
    peak = peaks.for_device("TPU v5 lite")
    hbm = pools_kernel_counts.megakernel_hbm_bytes(1250, 1000, 2048, 64, terms=1)
    assert hbm > kernel_counts.megakernel_hbm_bytes(1250, 1000, 2048, 64)
    ops = pools_kernel_counts.megakernel_ops(1250, 1000, 2048, 16.5, terms=1)
    assert ops > 2 * kernel_counts.megakernel_ops(1250, 1000, 2048, 16.5)
    assert kernel_counts.roofline(hbm, ops, peak)["bound"] == "memory"


def test_the_block_list_is_the_kernels_own():
    import inspect

    from kubernetriks_tpu.ops import scheduler_kernel as sk

    source = inspect.getsource(sk.fused_select_cycle_commit)
    assert "in_specs=[node_spec] * 3 + [pod_spec] * 9 + [cand_spec] * 3 + spread_in + affinity_in" in source
    assert "spread_out = spread_out + [stat_spec]" in source
    operands = inspect.getsource(sk._affinity_operands)
    assert "[node_spec] + [side_spec] * len(side)" in operands
    assert sk._affinity_blocks(1) == (1, 2) and sk._affinity_blocks(None) == (0, 0)
    # the gate counts what the counts count: the cell's blocks fit, with room
    assert sk.select_commit_kernel_fits(1000, 2048, 64, None, 1)
