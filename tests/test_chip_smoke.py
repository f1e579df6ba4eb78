"""chip_smoke.py off the chip: the default command refuses a CPU backend
without printing a result, and the explicit --cpu-plumbing mode runs every
leg at its cell's rehearsal shape (accelerator program family forced on,
Pallas interpreted) and prints one pinned JSON line per leg, the chip check's
result line last; a leg's deployment and load are its cell's data files.
The chip run itself is the driver's and the builder's (`python chip_smoke.py`
through the chip tool)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_default_command_refuses_cpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err


def _data(kind, name):
    with open(os.path.join(chip_smoke.CHECKOUT, "benchmark", kind, name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize(
    "cell, config, traffic",
    [
        ("sched1k.montecarlo", "sched1k", "montecarlo"),
        ("autoscaled.stream", "autoscaled", "stream"),
        ("autoscaled.whatif", "autoscaled", "whatif-steady"),
        ("sched1k-faults.montecarlo", "sched1k-faults", "montecarlo-faults"),
    ],
)
def test_a_legs_deployment_and_load_are_the_cells_files(cell, config, traffic):
    """What a leg builds on the chip IS what the cell's data files say: node
    count and shape, arrival rate and count, the batch's width, the HPA group
    where the mix has one."""
    dep, mix = _data("configs", config)["deployment"], _data("traffic", traffic)
    _, sim_config, cluster_events, workload, width, _ = chip_smoke.leg_inputs(cell, rehearsed=False)
    nodes = [event.node for _, event in cluster_events]
    assert len(nodes) == dep["nodes"]
    assert {n.status.capacity.cpu for n in nodes} == {dep["node_cpu_millicores"]}
    assert {n.status.capacity.ram for n in nodes} == {dep["node_ram_gib"] * 1024**3}
    assert nodes[7].metadata.name == "gen_node_0007"
    assert width == mix.get("clusters_per_chip", mix.get("lanes"))
    plain = mix["plain"]
    pods = [event.pod for _, event in workload if hasattr(event, "pod")]
    assert len(pods) == round(plain["rate_per_second"] * plain["horizon_s"])
    assert {p.spec.resources.requests.cpu for p in pods} == {plain["cpu_millicores"]}
    assert max(t for t, _ in workload) < plain["horizon_s"]
    groups = [event for _, event in workload if hasattr(event, "pod_group")]
    assert len(groups) == (1 if mix["pod_group"] else 0)
    assert (sim_config.cluster_autoscaler is not None and sim_config.cluster_autoscaler.enabled) == bool(
        dep["cluster_autoscaler"]
    )
    assert sim_config.scheduling_cycle_interval == dep["scheduling_cycle_interval_s"]


def test_the_kubescore_legs_records_carry_the_pools_taints_and_soft_terms():
    """The one leg whose records are not traffic_gen's bare ones: the cell's
    own generator and placer (benchmark/kubescore_gen.py, kubescore_program.py)."""
    dep = _data("configs", "sched1k-kubescore")["deployment"]
    leg = chip_smoke.leg_inputs("sched1k-kubescore.montecarlo", rehearsed=False, clusters=128)
    nodes = [event.node for _, event in leg.cluster_events]
    assert len(nodes) == dep["nodes"] == 1000 and leg.width == 128
    assert {(n.status.capacity.cpu, n.status.capacity.ram // 1024**3) for n in nodes} == {
        (p["cpu_millicores"], p["ram_gib"]) for p in dep["pools"]
    }
    assert sorted({t.effect for n in nodes for t in n.spec.taints}) == ["NoSchedule", "PreferNoSchedule"]
    pods = [event.pod for _, event in leg.workload]
    assert len(pods) == 2000 and leg.config.scheduler_profile == "kube_default"
    preferring = [p for p in pods if p.spec.node_affinity is not None and p.spec.node_affinity.preferred]
    assert 0.25 < len(preferring) / len(pods) < 0.35
    assert {t.weight for p in preferring for t in p.spec.node_affinity.preferred} == {1, 50}


def test_the_pools_legs_records_carry_the_pools_taint_and_hard_terms():
    """The leg that runs the exact key on the chip outside the benchmark: the
    pools cell's own generator and placer (benchmark/pools_gen.py,
    pools_program.py), requests that do not move in lockstep with the four
    machine shapes."""
    dep = _data("configs", "sched1k-pools")["deployment"]
    leg = chip_smoke.leg_inputs("sched1k-pools.montecarlo", rehearsed=False, clusters=128)
    nodes = [event.node for _, event in leg.cluster_events]
    assert len(nodes) == dep["nodes"] == 1000 and leg.width == 128
    assert {(n.status.capacity.cpu, n.status.capacity.ram // 1024**3) for n in nodes} == {
        (p["cpu_millicores"], p["ram_gib"]) for p in dep["pools"]
    }
    assert {t.effect for n in nodes for t in n.spec.taints} == {"NoSchedule"}
    pods = [event.pod for _, event in leg.workload]
    assert leg.config.scheduler_profile == "node_pools"
    assert any(p.spec.node_selector for p in pods) and any(p.spec.tolerations for p in pods)
    assert any(p.spec.node_affinity is not None and p.spec.node_affinity.required_terms for p in pods)
    assert len({(p.spec.resources.requests.cpu, p.spec.resources.requests.ram) for p in pods}) > 1


def test_a_legs_overrides_replace_one_number_of_the_files():
    """The shapes no cell has: the nodes come from another configuration's
    machine count, the arrivals run longer, the batch is narrower; everything
    else stays the files'."""
    machines = _data("configs", "alibaba1313")["deployment"]["machines"]
    _, _, cluster_events, workload, width, _ = chip_smoke.leg_inputs(
        "sched1k.montecarlo", rehearsed=False, clusters=1, nodes="alibaba1313", horizon_s=4000.0
    )
    assert (len(cluster_events), width) == (machines, 1)
    assert len(workload) == round(_data("traffic", "montecarlo")["plain"]["rate_per_second"] * 4000.0)
    toy = _data("rehearsal", "sched1k.montecarlo")
    _, _, cluster_events, _, width, _ = chip_smoke.leg_inputs("sched1k.montecarlo", rehearsed=True)
    assert len(cluster_events) == toy["config"]["deployment"]["nodes"]
    assert width == toy["traffic"]["clusters_per_chip"]


def test_cpu_plumbing_runs_every_leg(capsys):
    assert chip_smoke.main(["--cpu-plumbing"]) == 0
    lines = capsys.readouterr().out.splitlines()
    records = [json.loads(line) for line in lines if line.startswith("{")]
    assert json.loads(lines[-1]) == records[-1]
    assert [r.get("leg") for r in records] == [
        "start", "pure", "composed", "served", "cli", "faults", "kubescore", "pools", "summary", None,
    ]
    start, pure, composed, served, cli, faults, kubescore, pools, summary, result = records
    assert start["cpu_plumbing"] is True
    assert start["device"]["platform"] == "cpu"
    assert os.path.basename(start["compile_cache"]) == ".jax_cache"

    # uniform pods: lockstep; no mesh; four clusters: the event loop on its scatter path
    kernels = {"cycle": "candidate", "interpret": True, "ranking": "float32", "events": "scatter", "sharding": None}
    ca_kernels = {**kernels, "ca_up": "kernel", "ca_down": "kernel"}
    for rec in (pure, composed, served, cli, faults, kubescore, pools):
        assert rec.pop("wall_s") >= 0
    assert pure == {
        "leg": "pure", "clusters": 4, "nodes": 8, "pods": 128,
        "formulation": kernels, "decisions": 104, "reference": "lax.scan",
        "mismatches": 0,
    }
    assert composed == {
        "leg": "composed", "clusters": 4, "nodes": 24, "pod_window": 128,
        "formulation": ca_kernels, "lane_major": True, "reclaim": True,
        "superspans": 4, "feeder_slabs": 1, "pod_base": 64, "decisions": 860,
        "scaled_up_pods": 68, "scaled_up_nodes": 8, "scaled_down_nodes": 8,
        "reference": "scan+ladder, statics off", "mismatches": 0,
    }
    assert served.pop("warmup_compiles") >= 1
    assert served == {
        "leg": "served", "lanes": 4, "nodes": 24, "formulation": ca_kernels,
        "queries": 8, "query_errors": 0, "decisions": 469,
        "recompiles_after_warmup": 0,
    }
    assert cli == {
        "leg": "cli", "clusters": 2, "pods_succeeded": 4, "decisions": 4,
    }
    # identical nodes, crashes and a rack's loss and return, every pod run to its end
    assert faults == {
        "leg": "faults", "clusters": 4, "nodes": 12, "pods": 128,
        "formulation": kernels, "node_crashes": 20, "node_recoveries": 16,
        "pod_interruptions": 11, "pods_succeeded": 400, "reference": "lax.scan",
        "mismatches": 0,
    }
    # four machine shapes, preferred terms, a soft-tainted pool: no float ranks a node
    assert kubescore == {
        "leg": "kubescore", "clusters": 4, "nodes": 20, "pods": 256,
        "formulation": {**kernels, "ranking": "integer"}, "decisions": 132,
        "soft_attempts": 116, "soft_honoured": 96, "reference": "lax.scan",
        "mismatches": 0,
    }
    # the same pools under hard terms alone, requests out of lockstep: the exact key ranks
    assert pools == {
        "leg": "pools", "clusters": 4, "nodes": 20, "pods": 256,
        "formulation": {**kernels, "ranking": "exact"}, "decisions": 180,
        "affinity_attempts": 104, "affinity_attempts_refused": 0, "reference": "lax.scan",
        "mismatches": 0,
    }
    assert summary.pop("wall_s") >= 0
    assert summary == {
        "leg": "summary", "cpu_plumbing": True, "devices_used": 1,
        "legs": ["pure", "composed", "served", "cli", "faults", "kubescore", "pools"], "claim": None,
    }
    # The chip check reads the last stdout line and takes these keys only.
    assert result == {"ok": True, "device": start["device"]}
    assert set(result["device"]) == {"platform", "kind", "count"}
