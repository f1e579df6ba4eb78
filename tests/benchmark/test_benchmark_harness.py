"""The harness end to end on the CPU at toy sizes, behind the explicit
rehearsal flag (Pallas interpreted, never a device number), for both drivers;
and a run whose timed path is broken underneath, which must come out as not
correct."""

import json
import os

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}  # and no other


def run_cell(capsys, workload, trace, seconds="2", seed=str(2**31 + 5), control=0):
    rc = bench_run.main(
        [
            "--workload", workload, "--seed", seed, "--seconds", seconds, "--trace", str(trace),
            "--control", str(control),
            "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", workload + ".json"),
        ]
    )
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    return rc, lines


def manifest_metrics(group, workload):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    return {
        m["name"]: m["unit"]
        for m in manifest[group]
        if workload in m.get("workloads", [workload])
    }


@pytest.mark.parametrize(
    "workload",
    ["sched1k.montecarlo", "autoscaled.stream", "autoscaled.whatif"],
)
def test_rehearsal_end_to_end(capsys, workload):
    rc, lines = run_cell(capsys, workload, trace=0)
    result = lines[-1]
    assert rc == 0 and set(result) == RESULT_KEYS and result["rehearsal"] is True
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    wanted = manifest_metrics("end_to_end", workload)
    assert {k: v["unit"] for k, v in result["metrics"].items()} == wanted
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu" and "memory_peak_bytes" in result["device"]
    checks = [row for row in lines if row.get("line") == "check"]
    assert checks and all({"value", "limit", "ok"} <= set(row) for row in checks)


@pytest.mark.parametrize("workload", ["sched1k.montecarlo", "autoscaled.whatif"])
def test_rehearsal_traced_run_reports_layers(capsys, workload):
    rc, lines = run_cell(capsys, workload, trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    allowed = manifest_metrics("per_layer", workload)
    got = {k: v["unit"] for k, v in result["metrics"].items()}
    assert got and set(got.items()) <= set(allowed.items())
    assert "engine_build_s" in got and "compiles_in_setup" in got
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"] * 8
    assert len(result["breakdown"]["device_ops"]) <= 10 and len(result["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize(
    "workload,failing,seconds",
    [
        ("sched1k.montecarlo", "start_time_gap_s", "1"),  # times held in float32
        ("autoscaled.stream", "plain_formulation.mismatching_leaves", "1"),  # the same, in the state
        # the sampled lanes' final states with their times through float32; no returned integer moves
        ("autoscaled.whatif", "plain_formulation.mismatching_leaves", "4"),
    ],
)
def test_the_control_comes_out_as_not_correct(capsys, workload, failing, seconds):
    """The control in the program's place fails the number that is there to
    catch it, while the program itself passes every one."""
    rc, lines = run_cell(capsys, workload, trace=0, seconds=seconds, control=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True and result["control_correct"] is False
    control = [row for row in lines if row.get("line") == "control"]
    failed = [row for row in control if not row["ok"]]
    assert failed and all(row["check"].endswith(failing) for row in failed)
    assert all(row["value"] > row["limit"] for row in failed)


def test_a_run_without_a_tpu_prints_no_result(capsys):
    rc = bench_run.main(["--workload", "sched1k.montecarlo", "--seed", "1", "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == "" and "needs a TPU" in captured.err


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    """After the warm-up job every step call does nothing: later jobs commit
    no decision and no pod succeeds, so the job check and the oracle fail."""
    from kubernetriks_tpu.batched.engine import BatchedSimulation

    real = BatchedSimulation.step_until_time
    calls = {"n": 0}

    def step(self, until_time):
        calls["n"] += 1
        if calls["n"] > 1:
            return None
        return real(self, until_time)

    monkeypatch.setattr(BatchedSimulation, "step_until_time", step)
    rc, lines = run_cell(capsys, "sched1k.montecarlo", trace=0, seconds="1")
    result = lines[-1]
    assert rc == 0 and result["correct"] is False and result["failed"] == result["attempted"] > 0
    failed = {row["check"] for row in lines if row.get("line") == "check" and not row["ok"]}
    assert "jobs_with_other_decisions" in failed
    assert any(name.endswith("pods_in_another_phase") for name in failed)


def test_an_answer_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    """One pod of one sampled cluster reported on another node."""
    from benchmark import program

    real = program.normalized_pod_view

    def view(sim, cluster):
        out = real(sim, cluster)
        name = sorted(n for n, row in out.items() if row[0] == "succeeded")[0]
        phase, node, start = out[name]
        out[name] = (phase, "gen_node_0000" if node != "gen_node_0000" else "gen_node_0001", start)
        return out

    monkeypatch.setattr(program, "normalized_pod_view", view)
    rc, lines = run_cell(capsys, "sched1k.montecarlo", trace=0, seconds="1")
    assert rc == 0 and lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    failed = [row for row in lines if row.get("line") == "check" and not row["ok"]]
    assert failed and all(row["check"].endswith("pods_on_another_node") for row in failed)


def test_a_lane_whose_one_start_time_is_a_float32_ulp_off_is_not_correct(capsys, monkeypatch):
    """The served path's sampled lane states carry times: one pod's start
    offset moved by one float32 ulp fails the state comparison, though every
    integer the query returns is unchanged."""
    import numpy as np

    from benchmark import program

    real = program.lane_reader
    built = []

    def lane_reader(fleet):
        read = real(fleet)
        built.append(fleet)
        if len(built) > 1:  # the plain fleet's reader stays sound
            return read

        def off_by_an_ulp(lane):
            state = read(lane)
            off = np.array(state.pods.start_time.off)
            started = np.flatnonzero(off > 0)
            if started.size:
                off[started[0]] = np.nextafter(off[started[0]], np.float32(np.inf))
            return state._replace(pods=state.pods._replace(start_time=state.pods.start_time._replace(off=off)))

        return off_by_an_ulp

    monkeypatch.setattr(program, "lane_reader", lane_reader)
    rc, lines = run_cell(capsys, "autoscaled.whatif", trace=0, seconds="4")
    assert rc == 0 and lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    failed = [row for row in lines if row.get("line") == "check" and not row["ok"]]
    assert [row["check"] for row in failed] == ["plain_formulation.mismatching_leaves"]
    assert failed[0]["value"] >= 1 and ".pods.start_time.off" in failed[0]["note"]
