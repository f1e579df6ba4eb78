"""The `alibaba1313.replay` cell off the chip: the seeded generator, the plain
parser against the program's own parsers on quirked files, the driver end to
end at toy size behind the rehearsal flag (with its control and with one
answer altered), the four-chip montecarlo cell on forced host devices, and the
new readers on a recorded trace."""

import json
import os

import pytest

from benchmark import alibaba_gen
from benchmark import run as bench_run
from benchmark.oracle.trace import alibaba as plain

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "alibaba1313.replay"


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "alibaba1313.json")) as fh:
        return json.load(fh)


def rate_metric():
    """The existing end-to-end rate the cell reports (PERF.md section 2 says
    how it was chosen), and the suffix its per-layer metrics carry."""
    with open(os.path.join(ROOT, "benchmark", "traffic", "replay.json")) as fh:
        rate = json.load(fh)["rate_metric"]
    return rate, {"decisions_per_s": ".batch", "decisions_per_s.stream": ".stream"}[rate]


def run_cell(capsys, workload, trace=0, seconds="1", seed=str(2**31 + 5), control=0):
    rc = bench_run.main(
        [
            "--workload", workload, "--seed", seed, "--seconds", seconds, "--trace", str(trace),
            "--control", str(control),
            "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", workload + ".json"),
        ]
    )
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    return rc, lines


# --- the generator -----------------------------------------------------------


def test_the_generator_gives_every_seed_the_same_counts(tmp_path):
    cfg = config()
    trace = dict(cfg["trace"], tasks=600, span_s=3000)
    seeds = (7, 2**31 + 11)
    tables = [alibaba_gen.workload_rows(trace, 1313, seed) for seed in seeds]
    assert tables[0] != tables[1]
    assert tables[0] == alibaba_gen.workload_rows(trace, 1313, seeds[0])
    for tasks, instances in tables:
        assert len(tasks) == 600 + trace["dropped_rows"]["task_without_plan"]
        assert len(instances) == alibaba_gen.valid_instances(trace) + alibaba_gen.dropped_instance_rows(trace)
        planned = [row for row in tasks if row[6] != ""]
        assert [sum(1 for row in planned if row[4] == k) for k in (1, 2, 3)] == [200, 200, 200]
        # 2% heavy, 12 of 600 (one whose draw lands on the 8-core boundary reads as light)
        assert 11 <= sum(1 for row in planned if row[6] >= trace["cpu_santicores"][1]) <= 13
    # the files parse to the same number of pods for both seeds, and to the count the config states
    counts = []
    for seed in seeds:
        paths = alibaba_gen.write_trace(str(tmp_path / str(seed)), cfg["deployment"], trace, seed)
        records, parsed = plain.workload_records(paths["batch_instance"], paths["batch_task"])
        counts.append((len(records), parsed["rows"], parsed["dropped"]))
        nodes = plain.cluster_records(paths["machine_events"])
        assert len(nodes) == 1313 and nodes[8][2] == "alibaba_node_9" and nodes[0][3:] == (64000, 88 * 1024**3)
    assert counts[0] == counts[1] == (
        alibaba_gen.valid_instances(trace),
        alibaba_gen.valid_instances(trace) + alibaba_gen.dropped_instance_rows(trace),
        alibaba_gen.dropped_instance_rows(trace),
    )
    assert alibaba_gen.valid_instances(cfg["trace"]) == 17823  # what the configuration's file says


# --- the plain parser against the program's ---------------------------------


def _program_records(machines, tasks, instances):
    from kubernetriks_tpu.trace import alibaba as theirs

    nodes = theirs.AlibabaClusterTraceV2017.from_file(machines).convert_to_simulator_events()
    pods = theirs.AlibabaWorkloadTraceV2017.from_files(instances, tasks).convert_to_simulator_events()
    return (
        [
            (t, "create_node", e.node.metadata.name, e.node.status.capacity.cpu, e.node.status.capacity.ram)
            for t, e in nodes
        ],
        [
            (
                t, "create_pod", e.pod.metadata.name, e.pod.spec.resources.requests.cpu,
                e.pod.spec.resources.requests.ram, e.pod.spec.running_duration,
            )
            for t, e in pods
        ],
    )


@pytest.mark.parametrize(
    "quirk",
    [dict(), dict(header=True), dict(crlf=True), dict(quote=True), dict(header=True, crlf=True, quote=True)],
    ids=str,
)
def test_the_plain_parser_reads_what_the_programs_parsers_read(tmp_path, quirk):
    from kubernetriks_tpu.test_util import (
        ALIBABA_INSTANCE_HEADER,
        ALIBABA_MACHINE_HEADER,
        ALIBABA_TASK_HEADER,
        quirkify_csv,
    )
    from kubernetriks_tpu.trace import feeder

    cfg = config()
    trace = dict(cfg["trace"], tasks=120, span_s=900)
    paths = alibaba_gen.write_trace(str(tmp_path), dict(cfg["deployment"], machines=30), trace, 2**31 + 3)
    kw = dict(quirk)
    use_header = kw.pop("header", False)
    headers = {
        "machine_events": ALIBABA_MACHINE_HEADER, "batch_task": ALIBABA_TASK_HEADER,
        "batch_instance": ALIBABA_INSTANCE_HEADER,
    }
    for name, path in paths.items():
        with open(path) as fh:
            text = fh.read()
        with open(path, "w", newline="") as fh:
            fh.write(quirkify_csv(text, header=headers[name] if use_header else None, **kw))

    nodes = plain.cluster_records(paths["machine_events"])
    pods, parsed = plain.workload_records(paths["batch_instance"], paths["batch_task"])
    their_nodes, their_pods = _program_records(paths["machine_events"], paths["batch_task"], paths["batch_instance"])
    assert nodes == their_nodes and pods == their_pods
    assert len(pods) == alibaba_gen.valid_instances(trace) and parsed["dropped"] == 18 * 10
    # and the native feeder, which is what the cell's program side runs
    arrays = feeder.load_workload_arrays(paths["batch_instance"], paths["batch_task"])
    assert [arrays.pod_name(i) for i in range(len(arrays.start_ts))] == [rec[2] for rec in pods]
    assert arrays.rows_read == parsed["rows"]


def test_the_plain_parser_refuses_what_the_deployment_does_not_have(tmp_path):
    path = tmp_path / "machine_events.csv"
    path.write_text("0,1,add,,64,0.6875\n50,1,softerror,,,\n")
    with pytest.raises(ValueError, match="add-only"):
        plain.cluster_records(str(path))
    tasks, instances = tmp_path / "t.csv", tmp_path / "i.csv"
    tasks.write_text("1,2,1,10,1,Terminated,50,0.5\n1,2,1,10,1,Terminated,50,0.5\n")
    instances.write_text("5,9,1,10,3,Terminated,0,1\n")
    with pytest.raises(ValueError, match="duplicated task id"):
        plain.workload_records(str(instances), str(tasks))


# --- the driver, end to end at toy size -------------------------------------


def test_rehearsal_end_to_end_with_its_control(capsys):
    """The normal path (native feeder, compile_from_arrays, a streaming window
    that grows inside the first job and is reset between jobs, kernels
    interpreted) against the plain parser and the scalar oracle on the same
    seeded files; the control (times in float32) fails the start-time limit
    and nothing else."""
    rc, lines = run_cell(capsys, CELL, control=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert result["control_correct"] is False and result["rehearsal"] is True
    assert set(result["metrics"]) == {rate_metric()[0], "setup_s"}
    setup = next(row for row in lines if row.get("line") == "setup")
    assert setup["formulation"]["ranking"] == "exact"
    # built at the rehearsal's width, grown by the first job, and reset at the grown width ever after
    assert (setup["pod_window_built"], setup["pod_window"]) == (128, 256)
    assert setup["ingestion"] == {"rows": 1417, "dropped": 18} and setup["native_build_error"] is None
    counters = next(row for row in lines if row.get("line") == "counters")
    assert counters["slides_per_job"] >= 4 and counters["dispatches_per_job"] >= 2
    # the formulation the engine's gates picked is reported, not asserted
    assert counters["cycle_formulation"] == setup["formulation"]["cycle"] and counters["pod_window"] == 256
    checks = {row["check"]: row for row in lines if row.get("line") == "check"}
    assert len(checks) == 2 + 4 + 3 + 8 and all(row["ok"] for row in checks.values())
    assert checks["oracle.c0.resident_pods_on_another_node"]["note"].startswith("2")  # some 200 resident pods
    failed = [row for row in lines if row.get("line") == "control" and not row["ok"]]
    assert [row["check"] for row in failed] == ["oracle.c0.start_time_gap_s"]
    assert failed[0]["value"] > failed[0]["limit"]
    assert not any(name.startswith("trace-") for name in os.listdir(os.path.join(ROOT, ".bench_out")))


def test_rehearsal_traced_run_reports_the_cells_layers(capsys):
    rc, lines = run_cell(capsys, CELL, trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    got = set(result["metrics"])
    suffix = rate_metric()[1]
    gap = "superspan_gap_ms" if suffix == ".stream" else "superspan_gap_ms.replay"
    dispatches = "dispatches_per_job" + (suffix if suffix == ".stream" else "")
    assert {"trace_ingest_s", gap, dispatches, "window_device_ms" + suffix, "hbm_peak_gb" + suffix,
            "engine_build_s", "compiles_in_setup"} <= got
    # interpreted kernels leave no device event of the kernel's name: the readers say nothing
    assert not {"candidate_kernel_ms", "candidate_kernel_roofline"} & got
    ingest = next(row for row in lines if row.get("line") == "ingest")
    assert ingest["rows_read"] - ingest["rows_dropped"] == 1399
    assert 0 < result["metrics"]["trace_ingest_s"]["value"] < result["metrics"]["engine_build_s"]["value"]


def test_a_pod_reported_on_another_node_is_not_correct(capsys, monkeypatch):
    from benchmark import program

    real = program.normalized_pod_view

    def view(sim, cluster):
        out = real(sim, cluster)
        name = sorted(n for n, row in out.items() if row[0] == "succeeded")[0]
        phase, node, start = out[name]
        out[name] = (phase, "alibaba_node_1" if node != "alibaba_node_1" else "alibaba_node_2", start)
        return out

    monkeypatch.setattr(program, "normalized_pod_view", view)
    rc, lines = run_cell(capsys, CELL)
    assert rc == 0 and lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    failed = [row["check"] for row in lines if row.get("line") == "check" and not row["ok"]]
    assert failed == ["oracle.c0.resident_pods_on_another_node"]


# --- the four-chip montecarlo cell on forced host devices ---------------------


def test_the_four_chip_cell_shards_over_a_mesh_and_samples_every_shard(capsys):
    """`sched1k.montecarlo-x4` through its rehearsal: conftest forces 8 host
    devices, the cell takes four, `batch_jobs._mesh` builds the cluster-axis
    mesh over them, and the oracle judges a cluster of every shard."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four host devices")
    rc, lines = run_cell(capsys, "sched1k.montecarlo-x4")
    result = lines[-1]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    setup = next(row for row in lines if row.get("line") == "setup")
    assert setup["clusters"] == 8  # 2 a chip x 4
    shards = {int(row["check"].split(".")[1][1:]) // 2 for row in lines
              if row.get("line") == "check" and row["check"].startswith("oracle.c")}
    assert shards == {0, 1, 2, 3}


# --- the new readers on a recorded trace --------------------------------------


def test_the_candidate_kernel_readers_on_a_recorded_trace():
    """3 ms of a traced `alibaba1313.replay` window on a TPU v5 lite (PR 28,
    from 11 ms after the window opened, in the neutral form): ten windows,
    each with one launch of `fused_schedule_cycle`."""
    from types import SimpleNamespace

    from benchmark import trace_reduce as tr
    from benchmark.harness import reader

    with open(os.path.join(DATA, "alibaba1313_replay_v5e.trace.json")) as fh:
        events = tr.TraceEvents.from_json(json.load(fh))
    launches = sum(1 for name, _, _ in events.devices[0] if name.startswith("fused_schedule_cycle"))
    assert launches == 10
    summary = tr.reduce_events(events)
    assert summary.kernel_events["cycle"] == 0  # the megakernel's table does not match this kernel
    run = SimpleNamespace(
        trace=summary, device={"kind": "TPU v5 lite"}, cell=SimpleNamespace(chips=1),
        counters=dict(
            windows_stepped=10, candidate_kernel_launches=10.0, cycle_formulation="candidate",
            node_ranking="exact", clusters=1, nodes=1313, max_pods_per_cycle=256, decisions=105,
        ),
    )
    kernel_ms = reader("candidate_kernel_ms").read(run)
    assert kernel_ms == pytest.approx(0.0735828, rel=1e-6)  # 0.735828 ms of kernel in ten windows
    share = reader("candidate_kernel_roofline").read(run)
    # 5 node + 6 candidate blocks of one 128-lane tile: 4.17 MB, 5.09 us a launch at 819 GB/s
    assert share == pytest.approx(100 * 5.086241758e-6 / (kernel_ms * 1e-3), rel=1e-6) and share < 105
    # nothing to read: another formulation, no launch counted, no trace
    run.counters["cycle_formulation"] = "megakernel"
    assert reader("candidate_kernel_roofline").read(run) is None
    run.trace = None
    assert reader("candidate_kernel_ms").read(run) is None


def test_the_candidate_kernel_counts():
    from benchmark import candidate_kernel_counts as counts

    assert counts.candidate_hbm_bytes(1, 1313, 256) == (5 * 1320 + 6 * 256) * 4 * 128
    assert counts.candidate_hbm_bytes(129, 1313, 256) == 2 * counts.candidate_hbm_bytes(1, 1313, 256)
    exact, plain32 = (counts.candidate_ops(1, 1313, 10.0, r) for r in ("exact", "float32"))
    assert exact == 10.0 * 133 * 1320 * 128 and plain32 == 10.0 * 20 * 1320 * 128
