"""The cell PR 37 added, end to end on the CPU at toy sizes behind the
rehearsal flag: `sched1k-spread.montecarlo` against the oracle copy scheduling
with the REFERENCE's own algorithm (benchmark/spread_reference.py), both of
its controls failing, its per-layer metrics, the configuration held to
`sched1k`'s, the labelled generator, and the reference's `schedule_one`
against the program's scalar plugin on seeded random clusters: two
implementations of docs/PARITY.md "Topology spread" that share no line."""

import json
import os
import random

import pytest

from benchmark import kernel_counts, peaks, spread_gen, spread_kernel_counts, spread_reference, traffic_gen
from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
SPREAD = "sched1k-spread.montecarlo"
GIB = 1024**3


def run_cell(capsys, trace, control=0):
    rc = bench_run.main(
        [
            "--workload", SPREAD, "--seed", str(2**31 + 37), "--seconds", "1",
            "--trace", str(trace), "--control", str(control),
            "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", SPREAD + ".json"),
        ]
    )
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    return rc, lines


def load(*path):
    with open(os.path.join(ROOT, *path)) as fh:
        return json.load(fh)


def manifest_metrics(group):
    return {m["name"]: m["unit"] for m in load("BENCHMARK.json")[group] if SPREAD in m.get("workloads", [SPREAD])}


def test_spread_is_sched1k_with_labels_and_the_constraint_and_nothing_else():
    base, held = load("benchmark", "configs", "sched1k.json"), load("benchmark", "configs", "sched1k-spread.json")
    added = {"zones", "spread_workloads", "spread_label_key", "spread_max_skew", "spread_constraint"}
    assert set(held["deployment"]) - set(base["deployment"]) == added
    for key, value in base["deployment"].items():
        assert held["deployment"][key] == (value if key != "scheduler_profile" else "topology_spread"), key
    assert held["deployment"]["zones"]["values"] == ["moon-1", "moon-2", "moon-3"]
    assert held["deployment"]["spread_workloads"] == 8 and held["deployment"]["spread_max_skew"] == 1
    assert held["engine"] == base["engine"] and held["reduced"] == base["reduced"] == []
    weakened = {k: v for k, v in held["guarantees"].items() if k != "statement"}
    assert weakened == {k: v for k, v in base["guarantees"].items() if k != "statement"}
    assert "from memory" in held["assumed"]["source_from_memory"] or "recalled" in held["assumed"]["source_from_memory"]
    mix, one = load("benchmark", "traffic", "montecarlo-spread.json"), load("benchmark", "traffic", "montecarlo.json")
    assert mix["driver"] == "batch_jobs_labelled" and mix["spread"] == {"unconstrained_share": 0.25}
    for key in set(one) - {"driver", "what"}:
        assert mix[key] == one[key], key
    cell = {w["name"]: w for w in load("BENCHMARK.json")["workloads"]}[SPREAD]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sched1k-spread", "montecarlo-spread", 1)


def test_labelled_records_are_cell_ones_records_with_labels_on():
    dep = load("benchmark", "configs", "sched1k-spread.json")["deployment"]
    mix = load("benchmark", "traffic", "montecarlo-spread.json")
    nodes = spread_gen.cluster_records(dep)
    assert [rec[:5] for rec in nodes] == traffic_gen.cluster_records(dep)
    assert [rec[5][dep["zones"]["key"]] for rec in nodes[:4]] == ["moon-1", "moon-2", "moon-3", "moon-1"]
    seed = 2**31 + 5
    pods = spread_gen.workload_records(dep, mix, seed, 3)
    assert [rec[:6] for rec in pods] == traffic_gen.workload_records(mix, seed, 3)
    assert pods == spread_gen.workload_records(dep, mix, seed, 3)
    assert pods != spread_gen.workload_records(dep, mix, seed, 4)
    free = sum(1 for rec in pods if rec[7] is None)
    assert len(pods) == 2000 and 0.20 < free / 2000 < 0.30
    held = spread_gen.constraints_by_pod(pods)
    assert len(held) == 2000 - free
    assert {c[2]["color"] for c in held.values()} == {f"c{w}" for w in range(8)}
    assert all(c[0] == 1 and c[1] == dep["zones"]["key"] for c in held.values())
    assert all((rec[6] == {}) == (rec[7] is None) for rec in pods)


def test_spread_rehearsal_against_the_reference_and_both_controls_fail(capsys):
    """`correct` against the oracle copy with the reference's algorithm
    installed: every sampled pod's phase, node and start time. `--control 1`
    fails twice over: times through float32 miss `start_time_gap_s`, and the
    same traces under the `default` profile put pods on other nodes."""
    rc, lines = run_cell(capsys, trace=0, control=1)
    result = lines[-1]
    assert rc == 0 and set(result) == RESULT_KEYS | {"control_correct"} and result["rehearsal"] is True
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert {k: v["unit"] for k, v in result["metrics"].items()} == manifest_metrics("end_to_end")
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    checks = {row["check"]: row for row in lines if row.get("line") == "check"}
    on_node = [row for name, row in checks.items() if name.endswith("pods_on_another_node")]
    assert len(on_node) == 2 and all(row["ok"] and "200 pods" in row["note"] for row in on_node)
    assert result["control_correct"] is False
    failed = {row["check"] for row in lines if row.get("line") == "control" and not row["ok"]}
    assert any(name.startswith("oracle.") and name.endswith("start_time_gap_s") for name in failed)
    moved = [name for name in failed if name.startswith("default_profile.") and name.endswith("pods_on_another_node")]
    assert len(moved) == 2
    shares = [row["share"] for row in lines if row.get("line") == "control_default_profile"]
    assert len(shares) == 2 and all(share > 0.3 for share in shares)


def test_spread_traced_rehearsal_reports_the_bound_share(capsys):
    rc, lines = run_cell(capsys, trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    allowed = manifest_metrics("per_layer")
    got = {k: v["unit"] for k, v in result["metrics"].items()}
    assert got and set(got.items()) <= set(allowed.items())
    assert {"spread_bound_share", "dispatches_per_job", "window_device_ms.batch"} <= set(got)
    assert 0.0 < result["metrics"]["spread_bound_share"]["value"] <= 100.0
    counters = next(row for row in lines if row.get("line") == "counters")
    assert 0 < counters["spread_decisions_bound"] <= counters["spread_decisions"] <= 4 * 200
    assert (counters["spread_workloads"], counters["spread_domains"]) == (2, 3)
    # the toy build runs the candidate kernel: the megakernel's metrics read nothing
    assert "cycle_kernel_roofline.spread" not in got and "cycle_kernel_ms" not in got
    assert {"cycle_kernel_roofline.spread", "cycle_kernel_ms", "free_kernel_roofline"} <= set(allowed)


def test_spread_kernel_counts_against_the_block_list_by_hand():
    # 16 nodes, 24 pods, K = 8, 3 clusters, G = 2: kernel_counts' 15 in + 7 out
    # blocks, plus in: domain (16), two pod planes (48), table and limits
    # (2 x 16), live domains (8); out: a pod plane (24), the table (16), stats (8)
    base = kernel_counts.megakernel_hbm_bytes(3, 16, 24, 8)
    extra = (16 + 48 + 32 + 8) + (24 + 16 + 8)
    assert spread_kernel_counts.megakernel_hbm_bytes(3, 16, 24, 8, workloads=2) == base + extra * 4 * 128
    assert spread_kernel_counts.node_passes(3) == kernel_counts.MEGAKERNEL_NODE_PASSES + 13
    assert spread_kernel_counts.POD_PASSES == kernel_counts.MEGAKERNEL_POD_PASSES + 3
    ops = spread_kernel_counts.megakernel_ops(3, 16, 24, iterations=2.0, domains=3)
    assert ops == 2.0 * (40 * 24 + 33 * 16) * 128
    # the cell's shape: more bytes than cell 1's launch, still the memory leg
    peak = peaks.for_device("TPU v5 lite")
    hbm = spread_kernel_counts.megakernel_hbm_bytes(1250, 1000, 2048, 64, workloads=8)
    assert hbm > kernel_counts.megakernel_hbm_bytes(1250, 1000, 2048, 64)
    least = kernel_counts.roofline(hbm, spread_kernel_counts.megakernel_ops(1250, 1000, 2048, 16.7, 3), peak)
    assert least["bound"] == "memory"


def test_the_block_list_is_the_kernels_own():
    import inspect

    from kubernetriks_tpu.ops import scheduler_kernel as sk

    source = inspect.getsource(sk.fused_select_cycle_commit)
    assert "in_specs=[node_spec] * 3 + [pod_spec] * 9 + [cand_spec] * 3 + spread_in" in source
    assert "out_specs=[node_spec] * 2 + [pod_spec] * 4 + [stat_spec] + spread_out" in source
    assert "spread_out = [table_spec, pod_spec, tile_spec]" in source
    specs = inspect.getsource(sk._spread_in_specs)
    assert "[node_spec, table, table, tile] + [side_spec] * side_blocks" in specs
    assert sk.SPREAD_ZONE_TILE == spread_kernel_counts.ZONE_TILE


def test_reference_imports_nothing_of_the_program():
    for name in ("spread_reference.py", "spread_gen.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as fh:
            assert "kubernetriks_tpu" not in fh.read().replace("`kubernetriks_tpu/`", ""), name


# --- two implementations of one semantics block ---------------------------------


class _Scheduler:
    """What spread_reference.SpreadScheduling reads of the scheduler it is
    installed into."""

    def __init__(self):
        from kubernetriks_tpu.core.types import ObjectsInfo

        self.objects_cache = ObjectsInfo()
        self.assignments = {}


def _random_cluster(seed):
    """Program-side objects (the reference reads labels, allocatable and
    requests by attribute): nodes over zones with some keyless and some full,
    placed pods of three colours, and candidates with and without a constraint."""
    from kubernetriks_tpu.core.types import Node, Pod, TopologySpreadConstraint

    rng = random.Random(seed)
    key = "topology.kubernetes.io/zone"
    sched = _Scheduler()
    for i in range(rng.randint(3, 14)):
        node = Node.new(f"node_{i:02d}", rng.choice([2000, 4000, 8000]), 16 * GIB)
        if rng.random() < 0.85:
            node.metadata.labels[key] = f"z{rng.randrange(rng.randint(1, 4))}"
        sched.objects_cache.nodes[node.metadata.name] = node
    names = sorted(sched.objects_cache.nodes)
    for j in range(rng.randint(0, 30)):
        pod = Pod.new(f"placed_{j:02d}", 1000, GIB, 10.0)
        if rng.random() < 0.8:
            pod.metadata.labels["color"] = rng.choice(["blue", "red", "green"])
        node = sched.objects_cache.nodes[rng.choice(names)]
        if node.status.allocatable.cpu < 1000:
            continue
        node.status.allocatable.cpu -= 1000
        node.status.allocatable.ram -= GIB
        sched.objects_cache.pods[pod.metadata.name] = pod
        sched.assignments.setdefault(node.metadata.name, set()).add(pod.metadata.name)
    candidates, constraints = [], {}
    for k in range(6):
        pod = Pod.new(f"cand_{k}", rng.choice([1000, 2000]), GIB, 10.0)
        colour = rng.choice(["blue", "red", "green", None])
        if colour:
            pod.metadata.labels["color"] = colour
        if rng.random() < 0.75:
            selector = {"color": rng.choice(["blue", "red", "green"])}
            skew = rng.choice([1, 1, 2])
            pod.spec.topology_spread_constraints = [
                TopologySpreadConstraint(max_skew=skew, topology_key=key, match_labels=selector)
            ]
            constraints[pod.metadata.name] = (skew, key, selector)
        candidates.append(pod)
    return sched, candidates, constraints


@pytest.mark.parametrize("seed", range(40))
def test_reference_schedule_one_equals_the_programs_plugin(seed):
    from kubernetriks_tpu.core.scheduler.interface import ScheduleError, SchedulingFailure
    from kubernetriks_tpu.core.scheduler.kube_scheduler import KubeScheduler, kube_scheduler_config_from_spec
    from kubernetriks_tpu.core.scheduler.plugins import SchedulerCache

    sched, candidates, constraints = _random_cluster(seed)
    reference = spread_reference.SpreadScheduling(
        sched, constraints, SchedulingFailure, ScheduleError.NO_SUFFICIENT_RESOURCES,
        ScheduleError.REQUESTED_RESOURCES_ARE_ZEROS, ScheduleError.NO_NODES_IN_CLUSTER,
    )
    program = KubeScheduler(kube_scheduler_config_from_spec("topology_spread"))
    cache = SchedulerCache(sched.objects_cache.nodes, sched.objects_cache.pods, sched.assignments)
    for pod in candidates:
        def outcome(schedule):
            try:
                return schedule()
            except SchedulingFailure as failure:
                return failure.error

        ours = outcome(lambda: program.schedule_one(pod, sched.objects_cache.nodes, cache))
        theirs = outcome(lambda: reference.schedule_one(pod, sched.objects_cache.nodes))
        assert ours == theirs, (seed, pod.metadata.name)
