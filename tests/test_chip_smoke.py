"""chip_smoke.py off the chip: the default command refuses a CPU backend
without printing a result, and the explicit --cpu-plumbing mode runs every
leg at toy shapes (accelerator program family forced on, Pallas interpreted)
and prints one pinned JSON line per leg, the chip check's result line last.
The chip run itself is the driver's and the builder's (`python chip_smoke.py`
through the chip tool)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_default_command_refuses_cpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err


def test_cpu_plumbing_runs_every_leg(capsys):
    assert chip_smoke.main(["--cpu-plumbing"]) == 0
    lines = capsys.readouterr().out.splitlines()
    records = [json.loads(line) for line in lines if line.startswith("{")]
    assert json.loads(lines[-1]) == records[-1]
    assert [r.get("leg") for r in records] == [
        "start", "pure", "composed", "served", "cli", "faults", "summary", None,
    ]
    start, pure, composed, served, cli, faults, summary, result = records
    assert start["cpu_plumbing"] is True
    assert start["device"]["platform"] == "cpu"
    assert os.path.basename(start["compile_cache"]) == ".jax_cache"

    # uniform pods: lockstep; no mesh; four clusters: the event loop on its scatter path
    kernels = {"cycle": "candidate", "interpret": True, "ranking": "float32", "events": "scatter", "sharding": None}
    ca_kernels = {**kernels, "ca_up": "kernel", "ca_down": "kernel"}
    for rec in (pure, composed, served, cli, faults):
        assert rec.pop("wall_s") >= 0
    assert pure == {
        "leg": "pure", "clusters": 4, "nodes": 8, "pods": 512,
        "formulation": kernels, "decisions": 1276, "reference": "lax.scan",
        "mismatches": 0,
    }
    assert composed == {
        "leg": "composed", "clusters": 4, "nodes": 24, "pod_window": 128,
        "formulation": ca_kernels, "lane_major": True, "reclaim": True,
        "superspans": 4, "feeder_slabs": 1, "pod_base": 64, "decisions": 864,
        "scaled_up_pods": 80, "scaled_up_nodes": 16, "scaled_down_nodes": 16,
        "reference": "scan+ladder, statics off", "mismatches": 0,
    }
    assert served.pop("warmup_compiles") >= 1
    assert served == {
        "leg": "served", "lanes": 4, "nodes": 24, "formulation": ca_kernels,
        "queries": 8, "query_errors": 0, "decisions": 403,
        "recompiles_after_warmup": 0,
    }
    assert cli == {
        "leg": "cli", "clusters": 2, "pods_succeeded": 4, "decisions": 4,
    }
    # identical nodes, crashes and a rack's loss and return, every pod run to its end
    assert faults == {
        "leg": "faults", "clusters": 4, "nodes": 8, "pods": 896,
        "formulation": kernels, "node_crashes": 17, "node_recoveries": 16,
        "pod_interruptions": 238, "pods_succeeded": 2821, "reference": "lax.scan",
        "mismatches": 0,
    }
    assert summary.pop("wall_s") >= 0
    assert summary == {
        "leg": "summary", "cpu_plumbing": True, "devices_used": 1,
        "legs": ["pure", "composed", "served", "cli", "faults"], "claim": None,
    }
    # The chip check reads the last stdout line and takes these keys only.
    assert result == {"ok": True, "device": start["device"]}
    assert set(result["device"]) == {"platform", "kind", "count"}
