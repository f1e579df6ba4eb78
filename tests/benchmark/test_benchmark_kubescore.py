"""The cell PR 50 added, end to end on the CPU at toy sizes behind the
rehearsal flag: `sched1k-kubescore.montecarlo` (the megakernel, interpreted,
ranking in integers) against the oracle copy scheduling with the REFERENCE's
own algorithm (benchmark/kubescore_reference.py), its control failing, its
per-layer metrics, the configuration and the mix held to the issue's table,
the generator's shares and seeding, the kernel counts by hand, both readers on
a stub run, and the reference's `schedule_one` against the program's scalar
`kube_default` on seeded random clusters: two implementations of
docs/PARITY.md "Scoring as kube-scheduler scores" that share no line."""

import json
import os
import random
from types import SimpleNamespace

import pytest

from benchmark import harness, kernel_counts, kubescore_gen, kubescore_kernel_counts, kubescore_reference
from benchmark import peaks, pools_kernel_counts
from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
CELL = "sched1k-kubescore.montecarlo"
GIB = 1024**3
ZONE = "topology.kubernetes.io/zone"
P = [[0.5, 1], [1, 4], [2, 4], [4, 8], [8, 32], [16, 32]]


def run_cell(capsys, trace, control=0):
    rc = bench_run.main(
        [
            "--workload", CELL, "--seed", str(2**31 + 50), "--seconds", "1",
            "--trace", str(trace), "--control", str(control),
            "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", CELL + ".json"),
        ]
    )
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    return rc, lines


def load(*path):
    with open(os.path.join(ROOT, *path)) as fh:
        return json.load(fh)


def manifest_metrics(group):
    return {m["name"]: m["unit"] for m in load("BENCHMARK.json")[group] if CELL in m.get("workloads", [CELL])}


# --- the configuration and the mix are the issue's tables ------------------------


def test_kubescore_is_the_pools_configuration_with_two_differences():
    pools, held = load("benchmark", "configs", "sched1k-pools.json"), load("benchmark", "configs", "sched1k-kubescore.json")
    dep, was = held["deployment"], pools["deployment"]
    assert dep["scheduler_profile"] == "kube_default"
    for key in was:
        if key not in ("scheduler_profile", "pools"):
            assert dep[key] == was[key], key
    for pool, before in zip(dep["pools"], was["pools"]):
        if pool["name"] == "highmem":
            assert pool["taints"] == [{"key": "reserved", "value": "highmem", "effect": "PreferNoSchedule"}]
            assert {k: v for k, v in pool.items() if k != "taints"} == {k: v for k, v in before.items() if k != "taints"}
        else:
            assert pool == before
    assert [w for _, w in dep["score_plugins"]["compiled"]] == [1, 1, 2, 3]
    assert sorted(dep["score_plugins"]["listed_as_upstream"]) == sorted(
        [["TaintToleration", 3], ["NodeAffinity", 2], ["PodTopologySpread", 2], ["InterPodAffinity", 2],
         ["NodeResourcesFit", 1], ["NodeResourcesBalancedAllocation", 1], ["ImageLocality", 1]]
    )
    assert held["engine"] == pools["engine"] and held["reduced"] == [] and held["reduced_why"] == pools["reduced_why"]
    unweakened = {k: v for k, v in held["guarantees"].items() if k not in ("statement", "counters_exact")}
    assert unweakened == {k: v for k, v in pools["guarantees"].items() if k not in ("statement", "counters_exact")}
    assert held["guarantees"]["counters_exact"] == pools["guarantees"]["counters_exact"] + ["soft_attempts", "soft_honoured"]
    for name in ("NodeResourcesFit", "NodeResourcesBalancedAllocation", "NodeAffinity", "TaintToleration"):
        assert name in held["guarantees"]["statement"]
    assert set(pools["assumed"]) <= set(held["assumed"])
    for fact in ("default_score_weights", "least_allocated_formula", "balanced_allocation_formula", "node_affinity_score",
                 "taint_toleration_score", "preferred_weights", "prefer_no_schedule", "percentage_of_nodes_to_score",
                 "ties", "non_zero_requested", "system_default_spread"):
        assert held["assumed"][fact], fact


def test_the_mix_is_the_pools_loop_with_the_issues_six_classes():
    pools, mix = load("benchmark", "traffic", "montecarlo-pools.json"), load("benchmark", "traffic", "montecarlo-kubescore.json")
    assert mix["driver"] == "batch_jobs_kubescore"
    for key in ("clusters_per_chip", "job_end_s", "plain", "pod_group", "engine", "warmup_jobs", "trace_seconds"):
        assert mix[key] == pools[key], key
    tolerate = [["dedicated", "Equal", "batch", "NoSchedule"]]
    table = {
        "plain": (0.25, {}, P, [30.0, 120.0]),
        "prefers": (
            0.20,
            {"preferred_terms": [[50, [["pool", "In", ["compute"]]]], [1, [[ZONE, "In", ["zone1"]]]]]},
            P, [30.0, 120.0],
        ),
        "zonal": (0.20, {"node_affinity_terms": [[[ZONE, "In", ["zone1", "zone2"]]]]}, P, [30.0, 120.0]),
        "highmem": (
            0.15, {"node_selector": {"pool": "highmem"}, "tolerations": [["reserved", "Exists", "", ""]]},
            [[4, 48], [8, 96]], [30.0, 120.0],
        ),
        "tolerant": (
            0.10, {"tolerations": tolerate, "preferred_terms": [[50, [["dedicated", "In", ["batch"]]]]]}, P, [30.0, 120.0]
        ),
        "dedicated": (
            0.10, {"tolerations": tolerate, "node_affinity_terms": [[["dedicated", "In", ["batch"]]]]},
            [[8, 16]], [400.0, 1200.0],
        ),
    }
    assert [c["name"] for c in mix["classes"]] == list(table)
    for cls in mix["classes"]:
        share, placement, requests, duration = table[cls["name"]]
        rest = {k: v for k, v in cls.items() if k not in ("name", "share", "requests_cores_gib", "duration_s")}
        assert (cls["share"], rest, cls["requests_cores_gib"], cls["duration_s"]) == (share, placement, requests, duration)
    assert abs(sum(c["share"] for c in mix["classes"]) - 1.0) < 1e-12
    assert mix["asserts"] == {
        "cycle": "megakernel", "ranking": "integer", "min_decisions_per_cluster": 1500, "min_pods_that_waited": 1,
        "cycle_overruns": 0, "soft_honoured_strictly_between": True,
    }


def test_the_manifest_lists_the_cell_where_the_pools_cell_is_and_its_own_two():
    manifest = load("BENCHMARK.json")
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": "sched1k-kubescore", "traffic": "montecarlo-kubescore", "chips": 1,
        "why": manifest["workloads"][-1]["why"],
    }
    config = manifest["configs"][-1]
    assert (config["name"], config["file"], config["reduced"]) == ("sched1k-kubescore", "benchmark/configs/sched1k-kubescore.json", [])
    assert config["source"] == load("benchmark", "configs", "sched1k-kubescore.json")["source"] and len(config["source"]) <= 200
    assert len(config["why"]) <= 200 and len(manifest["workloads"][-1]["why"]) <= 200
    pools = "sched1k-pools.montecarlo"
    for group in ("end_to_end", "per_layer"):
        for metric in manifest[group]:
            listed = metric.get("workloads")
            if listed is None or metric["name"] in ("cycle_kernel_roofline.pools", "soft_honoured_share", "cycle_kernel_roofline.kubescore"):
                continue
            assert (CELL in listed) == (pools in listed), metric["name"]
            if CELL in listed:
                assert listed[-1] == CELL
    own = {m["name"]: m for m in manifest["per_layer"] if m.get("workloads") == [CELL]}
    assert own == {
        "soft_honoured_share": {
            "name": "soft_honoured_share", "unit": "%", "better": "higher", "source": "program_counter",
            "layer": "window body", "moves": "decisions_per_s", "workloads": [CELL],
        },
        "cycle_kernel_roofline.kubescore": {
            "name": "cycle_kernel_roofline.kubescore", "unit": "%", "better": "higher", "source": "device_trace",
            "layer": "kernels", "moves": "decisions_per_s", "workloads": [CELL],
        },
    }
    assert [m["name"] for m in manifest["per_layer"][-2:]] == list(own)


# --- the generator --------------------------------------------------------------


def test_cluster_records_carry_the_soft_taint_on_the_highmem_pool():
    dep = load("benchmark", "configs", "sched1k-kubescore.json")["deployment"]
    nodes = kubescore_gen.cluster_records(dep)
    assert len(nodes) == 1000
    soft = [rec for rec in nodes if rec[6] == [("reserved", "highmem", "PreferNoSchedule")]]
    hard = [rec for rec in nodes if rec[6] == [("dedicated", "batch", "NoSchedule")]]
    assert len(soft) == 150 and {rec[5]["pool"] for rec in soft} == {"highmem"}
    assert len(hard) == 36 and len([rec for rec in nodes if not rec[6]]) == 814
    assert kubescore_gen.taints_by_node(nodes)["gen_node_0714"] == [("reserved", "highmem", "PreferNoSchedule")]


def test_workload_records_are_seeded_a_cluster_and_hold_the_class_shares():
    mix = load("benchmark", "traffic", "montecarlo-kubescore.json")
    seed = 2**31 + 5
    pods = kubescore_gen.workload_records(mix, seed, 3)
    assert pods == kubescore_gen.workload_records(mix, seed, 3)
    assert pods != kubescore_gen.workload_records(mix, seed, 4) and pods != kubescore_gen.workload_records(mix, seed + 1, 3)
    from benchmark import pools_gen

    assert [rec[0] for rec in pods] != [rec[0] for rec in pools_gen.workload_records(mix, seed, 3)]  # a stream of its own
    assert len(pods) == 2000 and [rec[0] for rec in pods] == sorted(rec[0] for rec in pods)
    assert [rec[2] for rec in pods[:2]] == ["pod_00000", "pod_00001"] and 0.0 <= pods[0][0] and pods[-1][0] < 1000.0
    many = [rec for c in range(10) for rec in kubescore_gen.workload_records(mix, seed, c)]
    by_class = {}
    for rec in many:
        by_class.setdefault(kubescore_gen.class_of(mix, rec[6]), []).append(rec)
    for cls in mix["classes"]:
        got = by_class[cls["name"]]
        assert abs(len(got) / len(many) - cls["share"]) < 0.015, cls["name"]
        assert {(rec[3], rec[4]) for rec in got} == {(int(c * 1000), g * GIB) for c, g in cls["requests_cores_gib"]}
        lo, hi = cls["duration_s"]
        assert all(lo <= rec[5] <= hi for rec in got)
    # 30% carry preferred terms; every class but highmem meets the soft taint untolerated
    assert abs(sum(1 for rec in many if rec[6]["preferred"]) / len(many) - 0.30) < 0.015
    some = next(rec for rec in pods if kubescore_gen.class_of(mix, rec[6]) == "prefers")
    assert some[6] == {
        "node_selector": {}, "terms": [], "tolerations": [],
        "preferred": [(50, [("pool", "In", ["compute"])]), (1, [(ZONE, "In", ["zone1"])])],
    }
    tolerant = next(rec for rec in pods if kubescore_gen.class_of(mix, rec[6]) == "tolerant")
    assert tolerant[6]["preferred"] == [(50, [("dedicated", "In", ["batch"])])] and not tolerant[6]["terms"]


def test_reference_generator_and_counts_import_nothing_of_the_program():
    for name in ("kubescore_reference.py", "kubescore_gen.py", "kubescore_kernel_counts.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as fh:
            assert "kubernetriks_tpu" not in fh.read(), name


# --- the reference by hand, and against the program's scalar plugins ---------------


def _resources(cpu, ram):
    return SimpleNamespace(cpu=cpu, ram=ram)


def test_reference_scores_by_hand():
    capacity, free, want = _resources(192, 256), _resources(100, 200), _resources(4, 8)
    # cpu (100 - 4) * 100 // 192 = 50, ram (200 - 8) * 100 // 256 = 75
    assert kubescore_reference.fit_score(capacity, free, want) == 62
    # U = 96, 64: |96 * 256 - 64 * 192| = 12288; (100 * 49152 - 50 * 12288) // 49152 = 87
    assert kubescore_reference.balanced_score(capacity, free, want) == 87
    assert kubescore_reference.fit_score(_resources(0, 8), _resources(0, 8), _resources(0, 8)) == 0
    assert kubescore_reference.balanced_score(_resources(0, 8), _resources(0, 8), _resources(0, 8)) == 0
    placement = {
        "node_selector": {}, "terms": [], "tolerations": [("reserved", "Exists", "", ""), ("old", "Equal", "x", "NoSchedule")],
        "preferred": [(50, [("pool", "In", ["compute"])]), (1, [("zone", "In", ["zone1"])])],
    }
    assert kubescore_reference.affinity_raw(placement, {"pool": "compute", "zone": "zone1"}) == 51
    assert kubescore_reference.affinity_raw(placement, {"pool": "general", "zone": "zone1"}) == 1
    taints = [("reserved", "highmem", "PreferNoSchedule"), ("old", "x", "PreferNoSchedule"), ("dedicated", "batch", "NoSchedule")]
    assert kubescore_reference.taints_raw(placement, taints) == 1  # a NoSchedule toleration tolerates no soft taint
    assert not kubescore_reference.hard_taints_admit(placement, taints)


def _random_cluster(seed):
    """Program-side nodes and pods, and the same placements and taints as the
    reference holds them (plain data beside the objects)."""
    from kubernetriks_tpu.core.types import (
        Node, NodeAffinity, NodeSelectorRequirement, NodeSelectorTerm, Pod, PreferredSchedulingTerm, Taint, Toleration,
    )

    rng = random.Random(seed)
    keys, values = ["pool", "zone", "disk"], ["a", "b", "c"]
    nodes, taints = {}, {}
    for i in range(rng.randint(3, 12)):
        node = Node.new(f"node_{i:02d}", rng.choice([2000, 4000, 8000, 12000]), rng.choice([8, 16, 24]) * GIB)
        for key in keys:
            if rng.random() < 0.7:
                node.metadata.labels[key] = rng.choice(values)
        carried = [(k, rng.choice(values), "NoSchedule") for k in ("dedicated",) if rng.random() < 0.2]
        carried += [(k, rng.choice(values), "PreferNoSchedule") for k in ("reserved", "gpu") if rng.random() < 0.35]
        node.spec.taints = [Taint(*t) for t in carried]
        node.status.allocatable.cpu -= rng.choice([0, 500, 1000, node.status.allocatable.cpu])
        node.status.allocatable.ram -= rng.choice([0, GIB, 3 * GIB])
        nodes[node.metadata.name], taints[node.metadata.name] = node, carried
    pods, placements = [], {}

    def expressions():
        return [
            (rng.choice(keys), op, sorted(rng.sample(values, rng.randint(1, 2))) if op in ("In", "NotIn") else [])
            for op in rng.sample(["In", "NotIn", "Exists", "DoesNotExist"], rng.randint(1, 2))
        ]

    def term(exprs):
        return NodeSelectorTerm([NodeSelectorRequirement(k_, op, list(v)) for k_, op, v in exprs])

    for k in range(8):
        pod = Pod.new(f"cand_{k}", rng.choice([500, 1000, 2000]), rng.choice([1, 2, 4]) * GIB, 10.0)
        selector = {rng.choice(keys): rng.choice(values)} if rng.random() < 0.2 else {}
        terms = [expressions() for _ in range(rng.choice([0, 0, 0, 1, 2]))]
        soft = [(rng.choice([1, 10, 50, 100]), expressions()) for _ in range(rng.choice([0, 1, 2, 4]))]
        tolerations = [
            (key, op, rng.choice(values) if op == "Equal" else "", rng.choice(["", "NoSchedule", "PreferNoSchedule"]))
            for key, op in rng.sample(
                [("dedicated", "Equal"), ("gpu", "Exists"), ("", "Exists"), ("reserved", "Exists")], rng.choice([0, 0, 1, 2])
            )
        ]
        pod.spec.node_selector = dict(selector)
        if terms or soft:
            pod.spec.node_affinity = NodeAffinity(
                required_terms=[term(t) for t in terms],
                preferred=[PreferredSchedulingTerm(w, term(t)) for w, t in soft],
                has_required=bool(terms),
            )
        pod.spec.tolerations = [Toleration(*t) for t in tolerations]
        pods.append(pod)
        placements[pod.metadata.name] = {
            "node_selector": selector, "terms": terms, "tolerations": tolerations, "preferred": soft,
        }
    return nodes, taints, pods, placements


@pytest.mark.parametrize("seed", range(40))
def test_reference_schedule_one_equals_the_programs_plugins(seed):
    from kubernetriks_tpu.core.scheduler.interface import ScheduleError, SchedulingFailure
    from kubernetriks_tpu.core.scheduler.kube_scheduler import KubeScheduler, kube_scheduler_config_from_spec

    nodes, taints, pods, placements = _random_cluster(seed)
    reference = kubescore_reference.KubeScoreScheduling(
        placements, taints, SchedulingFailure, ScheduleError.NO_SUFFICIENT_RESOURCES,
        ScheduleError.REQUESTED_RESOURCES_ARE_ZEROS, ScheduleError.NO_NODES_IN_CLUSTER,
    )
    program = KubeScheduler(kube_scheduler_config_from_spec("kube_default"))
    for pod in pods:
        def outcome(schedule):
            try:
                return schedule()
            except SchedulingFailure as failure:
                return failure.error

        assert outcome(lambda: program.schedule_one(pod, nodes)) == outcome(lambda: reference.schedule_one(pod, nodes)), (
            seed, pod.metadata.name,
        )
    assert reference.counts["soft_honoured"] <= reference.counts["soft_attempts"] <= len(pods)


# --- the cell on the CPU -------------------------------------------------------------


def test_kubescore_rehearsal_against_the_reference_and_both_controls_fail(capsys):
    """`correct` against the oracle copy with the reference's algorithm
    installed: every sampled pod's phase, node and start time and the four
    label counters, the megakernel interpreted and ranking in integers.
    `--control 1` fails twice over: times through float32 miss
    `start_time_gap_s`, and the hard halves alone under `node_pools` (one
    float scorer, no soft term) put pods on other nodes."""
    rc, lines = run_cell(capsys, trace=0, control=1)
    result = lines[-1]
    assert rc == 0 and set(result) == RESULT_KEYS | {"control_correct"} and result["rehearsal"] is True
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert {k: v["unit"] for k, v in result["metrics"].items()} == manifest_metrics("end_to_end")
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    setup = next(row for row in lines if row.get("line") == "setup")
    assert setup["formulation"]["cycle"] == "megakernel" and setup["formulation"]["ranking"] == "integer"
    checks = {row["check"]: row for row in lines if row.get("line") == "check"}
    for suffix in ("pods_on_another_node", "pods_in_another_phase", *kubescore_reference.SCORE_COUNTERS):
        rows = [row for name, row in checks.items() if name.endswith("." + suffix)]
        assert len(rows) == 2 and all(row["ok"] for row in rows), suffix
    rows = [row for row in lines if row.get("line") == "kubescore"]
    assert len(rows) == 2 and all(0 < row["soft_honoured"] < row["soft_attempts"] for row in rows)
    assert result["control_correct"] is False
    failed = {row["check"] for row in lines if row.get("line") == "control" and not row["ok"]}
    assert any(name.startswith("oracle.") and name.endswith("start_time_gap_s") for name in failed)
    moved = [name for name in failed if name.startswith("node_pools_profile.") and name.endswith("pods_on_another_node")]
    assert len(moved) == 2
    shares = [row["share"] for row in lines if row.get("line") == "control_node_pools_profile"]
    assert len(shares) == 2 and all(share > 0.2 for share in shares)


def test_kubescore_traced_rehearsal_reports_the_honoured_share(capsys, monkeypatch, tmp_path):
    # the trace in a directory of this test's own: `.bench_out/trace-*` is shared by every
    # xdist worker, and `test_benchmark_trace_replay.py` asserts that it holds none
    init = harness.Harness.__init__

    def with_its_own_trace_dir(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self._trace_dir = str(tmp_path / "trace")

    monkeypatch.setattr(harness.Harness, "__init__", with_its_own_trace_dir)
    rc, lines = run_cell(capsys, trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    allowed = manifest_metrics("per_layer")
    got = {k: v["unit"] for k, v in result["metrics"].items()}
    assert got and set(got.items()) <= set(allowed.items())
    assert {"soft_honoured_share", "affinity_refused_share", "dispatches_per_job", "window_device_ms.batch"} <= set(got)
    assert 0.0 < result["metrics"]["soft_honoured_share"]["value"] < 100.0
    counters = next(row for row in lines if row.get("line") == "counters")
    assert 0 < counters["soft_honoured"] < counters["soft_attempts"]
    assert (counters["affinity_terms"], counters["soft_terms"], counters["soft_taints"]) == (1, 2, 1)
    assert (counters["ranking"], counters["score_units"]) == ("integer", [500, 1024])
    assert counters["cycle_overruns"] == 0 and counters["cycle_formulation"] == "megakernel"
    # an interpreted kernel is no event of a CPU trace: the megakernel's device metrics read nothing here
    assert "cycle_kernel_roofline.kubescore" not in got and "cycle_kernel_ms" not in got
    assert {"cycle_kernel_roofline.kubescore", "cycle_kernel_ms", "free_kernel_roofline"} <= set(allowed)
    assert "cycle_kernel_roofline" not in allowed and "cycle_kernel_roofline.pools" not in allowed


def test_both_readers_on_a_stub_run():
    """The roofline reader on a run object as the chip's traced run fills it
    (the pools cell's 2.5 ms a launch, 121 launches a job): under 100%, the
    bound named; nothing to read from a program without the soft planes, nor
    from the pools cell's own counters. The share reader likewise."""
    reader = harness.reader("cycle_kernel_roofline.kubescore")
    counters = dict(
        cycle_formulation="megakernel", ranking="integer", affinity_terms=1, soft_terms=2, soft_taints=1,
        clusters=1250, nodes=1000, pods=2048, max_pods_per_cycle=64, decisions=2 * 1250 * 1990, jobs=2,
    )
    trace = SimpleNamespace(kernel_events={"cycle": 242}, kernel_s={"cycle": 242 * 2.5e-3})
    run = SimpleNamespace(trace=trace, counters=counters, device={"kind": "TPU v5 lite"}, cell=SimpleNamespace(chips=1))
    share = reader.read(run)
    assert 5.0 < share < 100.0
    for gone in ("soft_terms", "affinity_terms"):
        assert reader.read(SimpleNamespace(**{**vars(run), "counters": {k: v for k, v in counters.items() if k != gone}})) is None
    assert reader.read(SimpleNamespace(**{**vars(run), "counters": {**counters, "ranking": "exact"}})) is None
    assert reader.read(SimpleNamespace(**{**vars(run), "trace": None})) is None
    honoured = harness.reader("soft_honoured_share")
    assert honoured.read(SimpleNamespace(counters={"soft_attempts": 200, "soft_honoured": 150})) == 75.0
    assert honoured.read(SimpleNamespace(counters={})) is None
    assert honoured.read(SimpleNamespace(counters={"soft_attempts": 0, "soft_honoured": 0})) is None


# --- the kernel counts by hand ---------------------------------------------------------


def test_kubescore_kernel_counts_against_the_block_list_by_hand():
    # 16 nodes, 24 pods, K = 8, 3 clusters, one hard term plane, two preferred-term
    # planes: pools_kernel_counts' blocks, plus in: the two capacity planes (32),
    # two term planes, the weights and the untolerated soft taints (96); out: a
    # counter tile (8)
    base = pools_kernel_counts.megakernel_hbm_bytes(3, 16, 24, 8, terms=1)
    assert kubescore_kernel_counts.megakernel_hbm_bytes(3, 16, 24, 8, terms=1, soft_terms=2) == base + (32 + 96 + 8) * 4 * 128
    assert kubescore_kernel_counts.QUOTIENT_PASSES == 10 and kubescore_kernel_counts.FIT_SCORE_PASSES == 22
    assert kubescore_kernel_counts.BALANCED_PASSES == 17
    assert kubescore_kernel_counts.affinity_score_passes(2) == 7 + 2 + 10 + 2 == 21
    assert kubescore_kernel_counts.taint_score_passes(1) == 1 + 2 + 2 + 10 + 3 == 18
    assert kubescore_kernel_counts.node_passes(1, 2, 1) == 4 + 8 + 6 + 22 + 17 + 21 + 18 + 7 + 4 + 2 + 6 == 115
    assert kubescore_kernel_counts.pod_passes(1, 2) == kernel_counts.MEGAKERNEL_POD_PASSES + 2 + 4
    ops = kubescore_kernel_counts.megakernel_ops(3, 16, 24, iterations=2.0, terms=1, soft_terms=2, soft_taints=1)
    assert ops == 2.0 * (43 * 24 + 115 * 16) * 128
    # the cell's shape: more bytes than the pools cell's launch, fewer counted passes than the exact
    # key's (115 against 151: one-digit quotients), and still the memory leg at the benchmark's peak
    peak = peaks.for_device("TPU v5 lite")
    hbm = kubescore_kernel_counts.megakernel_hbm_bytes(1250, 1000, 2048, 64, 1, 2)
    assert hbm > pools_kernel_counts.megakernel_hbm_bytes(1250, 1000, 2048, 64, 1)
    ops = kubescore_kernel_counts.megakernel_ops(1250, 1000, 2048, 16.5, 1, 2, 1)
    assert ops < pools_kernel_counts.megakernel_ops(1250, 1000, 2048, 16.5, 1)
    assert kernel_counts.roofline(hbm, ops, peak)["bound"] == "memory"


def test_the_block_list_is_the_kernels_own():
    import inspect

    from kubernetriks_tpu.ops import scheduler_kernel as sk

    source = inspect.getsource(sk.fused_select_cycle_commit)
    assert "in_specs=[node_spec] * 3 + [pod_spec] * 9 + [cand_spec] * 3 + spread_in + affinity_in + kube_in" in source
    operands = inspect.getsource(sk._kube_operands)
    assert "[node_spec] * 2 + [side_spec] * len(side)" in operands
    assert sk._kube_blocks(None) == (0, 0, 0) and sk._kube_blocks(0) == (8, 0, 0) and sk._kube_blocks(2) == (8, 4, 1)
    # the gate counts what the counts count: the cell's blocks fit, with room
    assert sk.select_commit_kernel_fits(1000, 2176, 64, None, 1, 2)
