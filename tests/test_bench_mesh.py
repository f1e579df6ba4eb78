"""Smoke for scripts/bench_mesh.py: the one-command mesh benchmark runs
end to end on the suite's 8-device virtual CPU mesh and reports a sane
JSON record (the runnable form of the README's v5e-8 projection — see
bench_mesh.py docstring)."""

import os
import sys

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")
)

import pytest

import bench_mesh


@pytest.mark.slow
def test_bench_mesh_composed_smoke_streams_on_virtual_mesh():
    """--composed --smoke: the composed + chaos flagship shard_mapped
    over the 8-device virtual mesh with the STREAMING feeder staging
    every slab — the dry-run form of the multi-chip protocol (ISSUE
    10). Slow: the sharded composed superspan program is a heavy CPU
    compile; CI runs the same line as its own step and uploads the JSON
    artifact."""
    result = bench_mesh.run_mesh_composed(
        8, clusters_per_device=2, n_nodes=8, smoke=True
    )
    assert result["devices"] == 8
    assert result["platform"] == "cpu"
    assert result["measured"] is True
    assert result["value"] > 0
    assert result["spans"]["n"] >= 5
    budget = result["slide_budget"]
    assert budget["streaming_ring_bound_bytes"] > 0
    assert budget["budget_bytes"] == 2 << 30
    tel = result["telemetry"]
    assert tel["dispatch_stats"]["superspans"] > 0
    assert tel["dispatch_stats"]["feeder_slabs_produced"] > 0
    assert set(tel["feeder"]["stalls"]) == {
        "feeder_not_ready", "upload_wait",
    }


def test_bench_mesh_smoke_runs_on_virtual_mesh():
    result = bench_mesh.run_mesh(
        8, clusters_per_device=2, n_nodes=8,
        horizon=200.0, warm_until=50.0, chunk=50.0,
    )
    assert result["devices"] == 8
    assert result["platform"] == "cpu"
    assert result["decisions"] > 0
    assert result["value"] > 0
    assert "8-device mesh, 16x8-node clusters" in result["metric"]
