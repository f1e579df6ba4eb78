"""The cell PR 43 added, on the CPU at toy sizes: `sched1k-faults.montecarlo`
end to end behind the rehearsal flag against the oracle copy fed plain
removals and creations (benchmark/faults_reference.py), both controls failing,
the configuration held to `sched1k`'s, the generator's seeds and stated
constraints, the reference against the program on IDENTICAL nodes with a rack
loss and a return, pod for pod, and the event kernel (interpreted) against the
scatter path under crash and recovery, bit for bit."""

import json
import os

import numpy as np
import pytest

from benchmark import deployment, event_kernel_counts, faults_gen, faults_program, faults_reference
from benchmark import program, reference, traffic_gen
from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAULTS = "sched1k-faults.montecarlo"
SEED = 2**31 + 354
GIB = 1024**3


def load(*path):
    with open(os.path.join(ROOT, *path)) as fh:
        return json.load(fh)


def run_cell(capsys, trace, control=0, seed=SEED):
    rc = bench_run.main(
        [
            "--workload", FAULTS, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--control", str(control),
            "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", FAULTS + ".json"),
        ]
    )
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    return rc, lines


def toy_config(nodes=12, racks=3, node_mttf=4000.0, rack_mttf=3000.0):
    config = load("benchmark", "configs", "sched1k-faults.json")
    config["deployment"]["nodes"] = nodes
    config["racks"].update(count=racks, nodes_per_rack=nodes // racks)
    config["fault_injection"]["node"]["mttf"] = node_mttf
    config["fault_injection"]["failure_groups"]["mttf"] = rack_mttf
    return config


TOY_TRAFFIC = dict(
    load("benchmark", "traffic", "montecarlo-faults.json"),
    clusters_per_chip=4,
    plain=dict(load("benchmark", "traffic", "montecarlo.json")["plain"], rate_per_second=0.1),
    faults={"event_capacity": 256, "crash_capacity": 64},
    engine={},
)


def test_faults_is_sched1k_with_a_fault_schedule_and_nothing_else():
    base, held = load("benchmark", "configs", "sched1k.json"), load("benchmark", "configs", "sched1k-faults.json")
    assert held["deployment"] == base["deployment"] and held["engine"] == base["engine"]
    assert held["reduced"] == ["failure_clock"] and "failure_clock" in held["assumed"]
    assert held["racks"]["count"] * held["racks"]["nodes_per_rack"] == held["deployment"]["nodes"] == 1000
    faults = held["fault_injection"]
    assert (faults["node"]["mttf"], faults["node"]["mttr"]) == (24000.0, 120.0)
    assert (faults["failure_groups"]["mttf"], faults["failure_groups"]["mttr"]) == (24000.0, 240.0)
    assert faults["no_fault_after_s"] == 1000.0 and len(faults["constraints"]) == 4
    kept = {k: v for k, v in held["guarantees"].items() if k not in ("statement", "counters_exact", "no_pod_lost")}
    assert kept == {k: v for k, v in base["guarantees"].items() if k not in ("statement", "counters_exact")}
    assert held["guarantees"]["counters_exact"] == base["guarantees"]["counters_exact"] + list(
        faults_reference.FAULT_COUNTERS
    )
    assert held["guarantees"]["statement"].startswith(base["guarantees"]["statement"])
    assert "recalled" in held["assumed"]["source_from_memory"]
    for key in base["assumed"]:
        assert held["assumed"][key] == base["assumed"][key]
    mix, one = load("benchmark", "traffic", "montecarlo-faults.json"), load("benchmark", "traffic", "montecarlo.json")
    assert mix["driver"] == "batch_jobs_faults"
    for key in set(one) - {"driver", "what", "asserts", "engine"}:
        assert mix[key] == one[key], key
    assert mix["engine"] == {"max_events_per_window": 96}  # what the build reads off the traces, pinned
    assert {k: mix["asserts"][k] for k in one["asserts"]} == one["asserts"]
    assert mix["asserts"]["events"] == "kernel" and mix["asserts"]["min_node_crashes_per_cluster"] == 20
    nodes, pods = held["deployment"]["nodes"], int(one["plain"]["rate_per_second"] * one["plain"]["horizon_s"])
    assert nodes + pods + 2 * mix["faults"]["crash_capacity"] <= mix["faults"]["event_capacity"]
    cell = {w["name"]: w for w in load("BENCHMARK.json")["workloads"]}[FAULTS]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sched1k-faults", "montecarlo-faults", 1)


def test_the_schedule_repeats_from_its_seed_and_keeps_its_stated_constraints():
    config = load("benchmark", "configs", "sched1k-faults.json")
    interval = config["deployment"]["scheduling_cycle_interval_s"]
    stop = config["fault_injection"]["no_fault_after_s"]
    pairs = faults_gen.fault_pairs(config, SEED, 7)
    assert pairs == faults_gen.fault_pairs(config, SEED, 7)
    assert pairs != faults_gen.fault_pairs(config, SEED, 8) and pairs != faults_gen.fault_pairs(config, SEED + 1, 7)
    counts = [len(faults_gen.fault_pairs(config, SEED, c)) for c in range(40)]
    assert 55 < np.mean(counts) < 110 and min(counts) >= 20  # 41.7 single + 0.83 racks of 50 a job
    racks = faults_gen.rack_members(config)
    assert len(racks) == 20 and racks[3] == list(range(150, 200))
    rack_events = 0
    for cluster in range(12):
        pairs = faults_gen.fault_pairs(config, SEED, cluster)
        by_node = {}
        for crash, recover, node in pairs:
            assert 0 < crash < stop and recover - crash >= interval - 1e-9
            by_node.setdefault(node, []).append((crash, recover))
        for spans in by_node.values():
            spans.sort()
            assert spans[0][0] >= interval
            for (_, back), (gone, _) in zip(spans, spans[1:]):
                assert gone - back >= interval - 1e-9  # never down twice at once, transitions an interval apart
        by_instant = {}
        for crash, recover, node in pairs:
            by_instant.setdefault((crash, recover), []).append(node)
        for nodes in by_instant.values():
            if len(nodes) > 1:  # a rack: its members that were up, down and back together
                rack_events += 1
                assert len({n // 50 for n in nodes}) == 1 and len(nodes) >= 45
    assert rack_events >= 3
    records = faults_gen.cluster_records(config, SEED, 7)
    assert records[:1000] == traffic_gen.cluster_records(config["deployment"])
    times = [rec[0] for rec in records]
    assert times == sorted(times) and {rec[1] for rec in records[1000:]} == {"crash_node", "recover_node"}
    with pytest.raises(ValueError):
        faults_gen.fault_pairs(toy_config(nodes=12, racks=5), SEED, 0)


def toy_cluster(seed, cluster, config=None):
    config = config or toy_config()
    return (
        config,
        deployment.config_yaml("toy", config["deployment"]),
        faults_gen.cluster_records(config, seed, cluster),
        traffic_gen.workload_records(TOY_TRAFFIC, seed, cluster),
    )


def build(config, config_text, seed, clusters, **kwargs):
    compiled = faults_program._compile_chunk((config_text, config, TOY_TRAFFIC, seed, clusters))
    return program.build_engine(config_text, compiled, resettable=False, **kwargs)


def test_reference_and_program_agree_pod_for_pod_on_identical_nodes_through_a_rack_loss():
    """Twelve IDENTICAL nodes, so nearly every placement is a tie between
    empty nodes: a recovered node has to sit where its name sorts, or the
    pods after the first recovery land elsewhere than the oracle's."""
    seed, clusters = SEED + 3, [0, 1, 2, 3]
    config, config_text, _, _ = toy_cluster(seed, 0)
    assert faults_program.why_not() is None
    sim = build(config, config_text, seed, clusters)
    assert sim.n_nodes == 12 and sim.fault_params.node_faults and not sim.config.fault_injection.enabled
    sim.step_until_time(1200.0)
    limits = (load("benchmark", "configs", "sched1k-faults.json")["guarantees"]["counters_exact"], 5e-6)
    rack_losses = 0
    for c in clusters:
        _, _, nodes, pods = toy_cluster(seed, c)
        oracle = faults_reference.run_oracle(config_text, nodes, pods, 1200.0)
        crashes = [rec for rec in nodes if rec[1] == "crash_node" and rec[0] < 1200.0]
        assert oracle.counters["node_crashes"] == len(crashes) > 0
        rack_losses += len(crashes) - len({rec[0] for rec in crashes})
        checks = reference.compare_pods(
            f"c{c}", program.normalized_pod_view(sim, c), faults_program.cluster_counters(sim, c), oracle, *limits
        )
        assert all(ch.ok for ch in checks), [ch.row() for ch in checks if not ch.ok]
        assert oracle.counters["pods_succeeded"] == len(pods)
    assert rack_losses >= 3  # some rack went, with its members at one instant
    assert sim.metrics_summary()["counters"]["pod_interruptions"] > 0
    report = sim.telemetry_report()["counters"]
    assert {k: report[k] > 0 for k in faults_reference.FAULT_COUNTERS} == dict.fromkeys(faults_reference.FAULT_COUNTERS, True)


def test_the_reference_counts_what_sat_on_a_node_when_it_went():
    """Two nodes, three pods that run long, one crash: the two pods on the
    crashed node run again on the other; a recovery is counted only for a
    node that had gone."""
    config_text = deployment.config_yaml("toy", toy_config()["deployment"])
    nodes = [(0.0, "create_node", "n0", 8000, 16 * GIB), (0.0, "create_node", "n1", 8000, 16 * GIB)]
    nodes += [(100.0, "crash_node", "n1", 50.0), (150.0, "recover_node", "n1", 8000, 16 * GIB)]
    pods = [(1.0 + i, "create_pod", f"p{i}", 1000, GIB, 300.0) for i in range(3)]
    oracle = faults_reference.run_oracle(config_text, nodes, pods, 1000.0)
    on_n1 = [name for name, (node, start) in oracle.succeeded.items() if start > 100.0]
    assert oracle.counters["node_crashes"] == 1 and oracle.counters["node_recoveries"] == 1
    assert oracle.counters["pod_interruptions"] == len(on_n1) == 2
    assert all(oracle.succeeded[name][0] == "n0" for name in on_n1) and oracle.counters["pods_succeeded"] == 3


def test_event_kernel_is_the_scatter_path_bit_for_bit_under_crash_and_recovery():
    from kubernetriks_tpu.batched.state import compare_states

    seed, clusters = SEED + 5, [0, 1, 2, 3, 4]
    config, config_text, _, _ = toy_cluster(seed, 0)
    kernel = build(config, config_text, seed, clusters, use_pallas=True, pallas_interpret=True, lane_major=True)
    kernel.use_pallas_select = kernel.use_megakernel = True
    plain = build(config, config_text, seed, clusters, use_pallas=False)
    assert kernel.kernel_formulation()["events"] == "kernel" and plain.kernel_formulation()["events"] == "scatter"
    for sim in (kernel, plain):
        sim.step_until_time(1200.0)
    counters = kernel.metrics_summary()["counters"]
    assert counters["node_crashes"] > 5 and counters["node_recoveries"] > 5 and counters["pod_interruptions"] > 0
    assert counters["node_downtime_s"] > 0
    bad = compare_states(plain.state, kernel.state)
    assert not bad, bad


@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    """One traced rehearsal, shared, its trace under a directory of this
    module's own: `.bench_out/trace-*` is where other files' traced
    rehearsals race (PERF.md section 7) and where the replay's test looks
    for leftovers."""
    import contextlib
    import io

    from benchmark import harness

    trace_dir = tmp_path_factory.mktemp("faults") / ("trace-" + FAULTS)
    plain = harness.Harness.__init__

    def init(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        self._trace_dir = str(trace_dir)

    harness.Harness.__init__ = init
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = bench_run.main(
                [
                    "--workload", FAULTS, "--seed", str(SEED), "--seconds", "1", "--trace", "1",
                    "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", FAULTS + ".json"),
                ]
            )
    finally:
        harness.Harness.__init__ = plain
    return rc, [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")], trace_dir


def test_traced_rehearsal_reports_the_new_metrics_and_leaves_no_trace_behind(traced_rehearsal):
    rc, lines, trace_dir = traced_rehearsal
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is True and result["rehearsal"] is True and result["failed"] == 0
    metrics = result["metrics"]
    for name in ("pods_interrupted_share", "node_faults_device_ms.batch", "events_device_ms.batch",
                 "window_device_ms.batch", "dispatches_per_job", "engine_build_s", "compiles_in_setup"):
        assert name in metrics, name
    assert 0 <= metrics["pods_interrupted_share"]["value"] < 20
    assert 0 < metrics["node_faults_device_ms.batch"]["value"] < metrics["events_device_ms.batch"]["value"]
    # the rehearsal's event loop is on its scatter path: the kernel's two readers find nothing and say nothing
    assert "event_kernel_ms" not in metrics and "event_kernel_roofline" not in metrics
    phases = next(line for line in lines if line.get("line") == "phases")
    assert "node_faults" in phases["inner_ms"]
    counters = next(line for line in lines if line.get("line") == "counters")
    assert counters["node_crashes"] > 0 and counters["event_formulation"] == "scatter"
    assert not trace_dir.exists() and not os.path.exists(os.path.join(ROOT, ".bench_out", "trace-" + FAULTS))


def test_rehearsal_controls_are_caught(capsys):
    rc, lines = run_cell(capsys, trace=0, control=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True and result["control_correct"] is False
    controls = [line for line in lines if line.get("line") == "control"]
    float32 = [c for c in controls if c["check"].startswith("oracle.") and not c["ok"]]
    assert float32 and {c["check"].rsplit(".", 1)[1] for c in float32} == {"start_time_gap_s"}
    dropped = [line for line in lines if line.get("line") == "control_crashes_dropped"]
    assert len(dropped) == 2 and all(line["pods_on_another_node"] > 0 for line in dropped)
    faults = [line for line in lines if line.get("line") == "faults"]
    assert len(faults) == 2 and all(line["node_crashes"] >= 1 for line in faults)


def test_event_kernel_counts_at_the_cell_shapes():
    """The roofline's counts: in-place accumulators read and written whole,
    one more node plane and five more node passes under node faults."""
    plain = event_kernel_counts.event_hbm_bytes(1250, 1000, 2048, 96, False)
    faults = event_kernel_counts.event_hbm_bytes(1250, 1000, 2048, 96, True)
    assert plain == (5 * 96 + 2 * 2 * 1000 + 2 * 3 * 2048) * 4 * 1280
    assert faults - plain == 2 * 1000 * 4 * 1280
    ops = event_kernel_counts.event_ops(1250, 1000, 2048, 30.0, True)
    assert ops == 30.0 * (11 * 128 + 11 * 1000) * 1280
    assert event_kernel_counts.event_ops(1250, 1000, 2048, 30.0, False) < ops


def test_a_program_that_cannot_take_the_schedule_is_refused_in_prepare(monkeypatch):
    from benchmark.drivers import batch_jobs_faults
    from benchmark.harness import Cell

    cell = Cell(load("BENCHMARK.json"), FAULTS, load("benchmark", "rehearsal", FAULTS + ".json"))
    monkeypatch.setattr(faults_program, "why_not", lambda: "a recovered node comes back on a fresh slot")
    with pytest.raises(SystemExit, match="fresh slot"):
        batch_jobs_faults.prepare(cell, SEED)
