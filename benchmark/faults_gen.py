"""Seeded node-fault schedules for the `batch_jobs_faults` driver, beside
benchmark/traffic_gen.py (whose clusters never lose a node): when each node
of a cluster crashes and when it is back, drawn from (`--seed`, "faults",
cluster) with numpy, before either side runs.

Independent of kubernetriks_tpu/chaos.py: the plain reference must not take
its schedule from the package under test. The constraints below are the ones
the configuration's file states (`fault_injection.constraints`), which are
the ones chaos.py states for its own sampler; this file keeps them by itself:

- a time to failure and a time to repair are exponential draws about their
  means, each clamped below at one scheduling interval, so that consecutive
  transitions of a node are at least an interval apart;
- no crash is drawn at or after `no_fault_after_s` (its recovery may land
  later, past the job's end too: it is then never applied);
- per-node chains first, then the failure groups in the file's order: one
  shared chain a group, every member down and back together;
- a pair is dropped for a member that is already down, or that has another
  transition within one interval of the pair's.

Records are neutral data, as traffic_gen's are; `to_events` turns them into
the objects of one side. To the program a crash is a
`RemoveNodeRequest(crashed=True, downtime_s=...)` and a recovery a
`CreateNodeRequest(recovered=True)` of the same name at full capacity; to the
oracle copy they are a plain remove and a plain create
(benchmark/faults_reference.py counts crashes, recoveries and interrupted
pods itself).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import traffic_gen

# ("crash_node", name, downtime_s) and ("recover_node", name, cpu, ram), each
# after its time, beside traffic_gen's ("create_node", name, cpu, ram).
Record = Tuple
Pair = Tuple[float, float, int]  # (crash, recover, node index in name order)


def rack_members(config: Dict) -> List[List[int]]:
    """Node indices (in node-name order, which is the order traffic_gen makes
    them in) of each rack: `racks.count` racks of `racks.nodes_per_rack`
    consecutive nodes."""
    racks = config["racks"]
    count, size = int(racks["count"]), int(racks["nodes_per_rack"])
    if count * size != int(config["deployment"]["nodes"]):
        raise ValueError(f"faults_gen: {count} racks of {size} are not the deployment's {config['deployment']['nodes']} nodes")
    return [list(range(r * size, (r + 1) * size)) for r in range(count)]


def _spans(rng, process: Dict, n: int, interval: float) -> Tuple[np.ndarray, np.ndarray]:
    """(times to failure, times to repair) of `n` processes, one draw each."""
    if process.get("distribution", "exponential") != "exponential":
        raise ValueError(f"faults_gen: distribution {process['distribution']!r}; the generator draws exponential spans")
    return (
        np.maximum(rng.exponential(float(process["mttf"]), n), interval),
        np.maximum(rng.exponential(float(process["mttr"]), n), interval),
    )


def fault_pairs(config: Dict, seed: int, cluster: int) -> List[Pair]:
    """The cluster's (crash, recover, node) pairs in the order they are
    decided: per-node chains node by node, then the groups' members."""
    faults = config["fault_injection"]
    interval = float(config["deployment"]["scheduling_cycle_interval_s"])
    stop = float(faults["no_fault_after_s"])
    n = int(config["deployment"]["nodes"])
    rng = np.random.default_rng(traffic_gen.derive_seed(seed, "faults", cluster))
    down: List[List[Tuple[float, float]]] = [[] for _ in range(n)]

    # Per-node chains, every node's k-th incarnation drawn in one call (a
    # chain that has ended still takes its draws, so a node's k-th spans do
    # not depend on how long its neighbours' chains run).
    t = np.zeros(n)
    active = np.ones(n, bool)
    while active.any():
        ttf, ttr = _spans(rng, faults["node"], n, interval)
        crash = t + ttf
        active &= crash < stop
        recover = crash + ttr
        for i in np.nonzero(active)[0]:
            down[i].append((float(crash[i]), float(recover[i])))
        t = np.where(active, recover, t)
    pairs = [(c, r, i) for i in range(n) for c, r in down[i]]

    def clear(i: int, crash: float, recover: float) -> bool:
        return all(recover + interval <= c or crash >= r + interval for c, r in down[i])

    groups = faults.get("failure_groups") or {}
    for members in rack_members(config) if groups else []:
        t_group = 0.0
        while True:
            ttf, ttr = _spans(rng, groups, 1, interval)
            crash = t_group + float(ttf[0])
            if crash >= stop:
                break
            recover = crash + float(ttr[0])
            for i in members:
                if clear(i, crash, recover):
                    down[i].append((crash, recover))
                    pairs.append((crash, recover, i))
            t_group = recover
    return pairs


def fault_records(config: Dict, seed: int, cluster: int) -> List[Record]:
    """The pairs as records in time order (stable: pairs of one instant keep
    the order they were decided in)."""
    nodes = traffic_gen.cluster_records(config["deployment"])
    out: List[Record] = []
    for crash, recover, i in fault_pairs(config, seed, cluster):
        _, _, name, cpu, ram = nodes[i]
        out.append((crash, "crash_node", name, recover - crash))
        out.append((recover, "recover_node", name, cpu, ram))
    out.sort(key=lambda rec: rec[0])
    return out


def cluster_records(config: Dict, seed: int, cluster: int) -> List[Record]:
    """traffic_gen's nodes, then the cluster's fault schedule."""
    return traffic_gen.cluster_records(config["deployment"]) + fault_records(config, seed, cluster)


def to_events(records: Sequence[Record], api, flagged: bool) -> List[Tuple[float, object]]:
    """Records -> (time, event) pairs of one side. `api` carries that side's
    Node, Pod, CreateNodeRequest, CreatePodRequest and RemoveNodeRequest.
    `flagged`: the program's side, whose remove and create carry the chaos
    flags and the sampled downtime; the oracle copy gets them plain."""
    out = []
    for rec in records:
        kind = rec[1]
        if kind == "crash_node":
            t, _, name, downtime = rec
            extra = dict(crashed=True, downtime_s=float(downtime)) if flagged else {}
            out.append((t, api.RemoveNodeRequest(node_name=name, **extra)))
        elif kind == "recover_node":
            t, _, name, cpu, ram = rec
            extra = dict(recovered=True) if flagged else {}
            out.append((t, api.CreateNodeRequest(node=api.Node.new(name, cpu, ram), **extra)))
        else:
            out.extend(traffic_gen.to_events([rec], api))
    return out
