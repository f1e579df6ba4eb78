"""The cell PR 32 added, end to end on the CPU at toy sizes behind the
rehearsal flag, as tests/benchmark/test_benchmark_harness.py holds the cells
before it: `sched1k-netdelay.montecarlo` against the oracle WITH the
reference's control-plane delays, its control failing its one limit, the
three per-layer metrics it brings, and `free_kernel_counts` against a block
list counted by hand."""

import json
import os

import pytest

from benchmark import free_kernel_counts, kernel_counts, peaks
from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
NETDELAY = "sched1k-netdelay.montecarlo"


def run_cell(capsys, workload, trace, seconds, control=0):
    rc = bench_run.main(
        [
            "--workload", workload, "--seed", str(2**31 + 11), "--seconds", seconds,
            "--trace", str(trace), "--control", str(control),
            "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", workload + ".json"),
        ]
    )
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    return rc, lines


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def manifest_metrics(group, workload):
    return {
        m["name"]: m["unit"] for m in manifest()[group] if workload in m.get("workloads", [workload])
    }


def failed_controls(lines):
    return [row for row in lines if row.get("line") == "control" and not row["ok"]]


def test_netdelay_is_sched1k_with_the_references_delays_and_nothing_else():
    with open(os.path.join(ROOT, "benchmark", "configs", "sched1k.json")) as fh:
        base = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs", "sched1k-netdelay.json")) as fh:
        held = json.load(fh)
    assert held["deployment"]["control_plane_delays_s"] == {
        "as_to_ps_network_delay": 0.050,
        "ps_to_sched_network_delay": 0.089,
        "sched_to_as_network_delay": 0.023,
        "as_to_node_network_delay": 0.152,
        "as_to_ca_network_delay": 0.67,
        "as_to_hpa_network_delay": 0.50,
    }
    for key in ("engine", "guarantees", "reduced", "stands_for"):
        assert held[key] == base[key], key
    same = {k: v for k, v in held["deployment"].items() if k != "control_plane_delays_s"}
    assert same == {k: v for k, v in base["deployment"].items() if k != "control_plane_delays_s"}
    assert set(base["assumed"]) - set(held["assumed"]) == {"control_plane_delays_s"}
    cells = {w["name"]: w for w in manifest()["workloads"]}
    assert cells[NETDELAY]["traffic"] == cells["sched1k.montecarlo"]["traffic"] == "montecarlo"


def test_netdelay_rehearsal_against_the_oracle_with_the_delays(capsys):
    """`correct` is decided by the scalar oracle given the same rendered
    delays: every sampled pod's phase, node and start time. The control
    (times through float32) fails `start_time_gap_s` and nothing else."""
    rc, lines = run_cell(capsys, NETDELAY, trace=0, seconds="1", control=1)
    result = lines[-1]
    assert rc == 0 and set(result) == RESULT_KEYS | {"control_correct"} and result["rehearsal"] is True
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert {k: v["unit"] for k, v in result["metrics"].items()} == manifest_metrics("end_to_end", NETDELAY)
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    checks = {row["check"]: row for row in lines if row.get("line") == "check"}
    on_node = [row for name, row in checks.items() if name.endswith("pods_on_another_node")]
    assert len(on_node) == 2 and all(row["ok"] and "100 pods" in row["note"] for row in on_node)
    assert result["control_correct"] is False
    failed = failed_controls(lines)
    assert failed and all(row["check"].endswith("start_time_gap_s") for row in failed)


def test_netdelay_traced_rehearsal_reports_the_deferred_share(capsys):
    rc, lines = run_cell(capsys, NETDELAY, trace=1, seconds="1")
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    allowed = manifest_metrics("per_layer", NETDELAY)
    got = {k: v["unit"] for k, v in result["metrics"].items()}
    assert got and set(got.items()) <= set(allowed.items())
    assert {"frees_deferred_share", "dispatches_per_job", "window_device_ms.batch"} <= set(got)
    # 400 frees at toy size; chain / interval is 2.9%
    assert 0.0 < result["metrics"]["frees_deferred_share"]["value"] < 10.0
    # the toy build runs no Pallas free kernel: its two metrics read nothing and are left out
    assert "free_kernel_ms" not in got and "free_kernel_roofline" not in got
    assert {"free_kernel_ms", "free_kernel_roofline"} <= set(allowed)
    assert {"free_kernel_ms", "free_kernel_roofline"} <= set(manifest_metrics("per_layer", "sched1k.montecarlo"))


def test_free_kernel_counts_against_the_block_list_by_hand():
    # 16 nodes, 24 pods, 3 clusters: one 128-lane tile; six pod blocks in, two
    # node blocks in and two out, the (8, 128) estimator block out; int32/float32
    rows = 6 * 24 + (2 + 2) * 16 + 8
    assert free_kernel_counts.free_hbm_bytes(3, 16, 24) == rows * 4 * 128 == 110_592
    # rows pad to the sublane tile of 8, clusters to the lane tile of 128
    assert free_kernel_counts.free_hbm_bytes(129, 17, 25) == (6 * 32 + 4 * 24 + 8) * 4 * 256
    # a step: 12 passes over the one live tile (24 rows here, 128 at most), 5 over the nodes
    assert free_kernel_counts.free_ops(3, 16, 24, steps=2.0) == 2.0 * (12 * 24 + 5 * 16) * 128
    assert free_kernel_counts.free_ops(3, 16, 4096, steps=1.0) == (12 * 128 + 5 * 16) * 128
    # the cell's shape: memory-bound, 87.4 MB and 107 us a launch
    peak = peaks.for_device("TPU v5 lite")
    hbm = free_kernel_counts.free_hbm_bytes(1250, 1000, 2176)
    least = kernel_counts.roofline(hbm, free_kernel_counts.free_ops(1250, 1000, 2176, 20.0), peak)
    assert hbm == (6 * 2176 + 4 * 1000 + 8) * 4 * 1280 and least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(106.7e-6, rel=0.01)


def test_the_block_list_is_the_kernels_own():
    """The free kernel's pallas_call takes six pod-shaped and two node-shaped
    inputs and gives two node-shaped outputs and the stats block, under the
    name the readers match."""
    import inspect

    from kubernetriks_tpu.ops import scheduler_kernel as sk

    source = inspect.getsource(sk.fused_free_resources)
    assert f'name="{free_kernel_counts.KERNEL}"' in source
    assert "in_specs=[pod_spec] * 6 + [node_spec] * 2" in source
    assert "out_specs=[node_spec] * 2 + [stats_spec]" in source


def test_program_counters_read_nothing_where_the_program_has_none():
    from kubernetriks_tpu.telemetry import recorder

    counters = recorder().counters
    saved = {k: counters.pop(k) for k in ("frees_total", "frees_deferred", "event_windows") if k in counters}
    try:
        assert free_kernel_counts.program_counters("frees_total", "frees_deferred") is None
        counters.update(frees_total=200, frees_deferred=6)
        assert free_kernel_counts.program_counters("frees_total", "frees_deferred") == {
            "frees_total": 200, "frees_deferred": 6,
        }
        assert free_kernel_counts.program_counters("frees_total", "event_windows") is None
    finally:
        for key in ("frees_total", "frees_deferred", "event_windows"):
            counters.pop(key, None)
        counters.update(saved)
