"""Seeded traffic for every cell: the one general generator.

A traffic mix is a data file under benchmark/traffic/; this module turns its
parameters and `--seed` into neutral event records, and `to_events` turns
records into the event objects of one side (the program's or the oracle
copy's), so both sides get the same load and neither sees the other's types.

Copied, with the seed made a parameter, from bench.py's `_shape_inputs`,
`_composed_inputs`, `_sweep_setup` and `_sweep_scenarios` (PERF.md, verdict
table). Three deliberate differences from those: every cluster of a batch gets
its own Poisson stream, seeded from (`--seed`, cluster index); node and pod
names are zero-padded, because the oracle breaks score ties by sorted name and
the batched path by slot (`gen_node_9` sorts after `gen_node_63`); and a stream
is conditioned on its count, so that shapes and work do not move with the seed.
"""

from __future__ import annotations

import hashlib
import math
import random
from typing import Dict, List, Sequence, Tuple

GIB = 1024**3

# (time, kind, ...) records. Kinds:
#   ("create_node", name, cpu_millicores, ram_bytes)
#   ("create_pod", name, cpu_millicores, ram_bytes, duration_s)
#   ("workload_yaml", yaml_text)   # parsed by each side's generic trace
Record = Tuple


def derive_seed(seed: int, *parts) -> int:
    """A 64-bit seed from `--seed` and a path of labels: the same inputs
    give the same stream, and neighbouring clusters share nothing."""
    text = ":".join(str(p) for p in (int(seed),) + parts)
    return int.from_bytes(hashlib.sha256(text.encode()).digest()[:8], "big")


def uniform_nodes(count: int, cpu: int, ram: int) -> List[Record]:
    return [(0.0, "create_node", f"gen_node_{i:04d}", cpu, ram) for i in range(count)]


def poisson_pods(
    *,
    rate_per_second: float,
    horizon_s: float,
    seed: int,
    cpu: int,
    ram: int,
    duration_s: Sequence[float],
    prefix: str,
) -> List[Record]:
    """Poisson arrivals over the horizon, conditioned on their count: exactly
    rate x horizon pods at sorted uniform instants (the order statistics a
    Poisson process has once its count is given), each with a uniform duration.
    Every cluster and every seed therefore has the same number of pods, so the
    engine's shapes (pod slots, the event slab) and the work of a job do not
    move with the seed: a new seed finds every program in the compile cache.
    (With a free count the event slab's width changed with the seed and every
    new seed recompiled: PERF.md, findings.)"""
    rng = random.Random(seed)
    lo, hi = duration_s
    count = int(round(rate_per_second * horizon_s))
    times = sorted(rng.random() * horizon_s for _ in range(count))
    return [
        (t, "create_pod", f"{prefix}_{i:05d}", cpu, ram, rng.uniform(lo, hi))
        for i, t in enumerate(times)
    ]


POD_GROUP_YAML = """
events:
- timestamp: {created_at_s}
  event_type:
    !CreatePodGroup
      pod_group:
        name: {name}
        initial_pod_count: {initial_pod_count}
        max_pod_count: {max_pod_count}
        pod_template:
          metadata: {{name: {name}}}
          spec:
            resources:
              requests: {{cpu: {cpu}, ram: {ram}}}
              limits: {{cpu: {cpu}, ram: {ram}}}
        target_resources_usage: {{cpu_utilization: {target_cpu_utilization}}}
        resources_usage_model_config:
          cpu_config:
            model_name: pod_group
            config: |
{load_units}
"""


def pod_group_record(group: Dict) -> Record:
    """The HPA pod group with its load curve, as one workload-trace YAML."""
    units = "\n".join(
        f"              - duration: {float(d)}\n"
        f"                total_load: {float(load)}"
        for d, load in group["load_curve"]
    )
    text = POD_GROUP_YAML.format(
        created_at_s=float(group["created_at_s"]),
        name=group["name"],
        initial_pod_count=int(group["initial_pod_count"]),
        max_pod_count=int(group["max_pod_count"]),
        cpu=int(group["cpu_millicores"]),
        ram=int(group["ram_gib"] * GIB),
        target_cpu_utilization=float(group["target_cpu_utilization"]),
        load_units=units,
    )
    return (float(group["created_at_s"]), "workload_yaml", text)


def cluster_records(deployment: Dict) -> List[Record]:
    return uniform_nodes(
        int(deployment["nodes"]),
        int(deployment["node_cpu_millicores"]),
        int(deployment["node_ram_gib"] * GIB),
    )


def workload_records(traffic: Dict, seed: int, cluster: int) -> List[Record]:
    """One cluster's workload: its own Poisson stream, plus the HPA group
    where the mix has one. Sorted by time, the group after a pod of the same
    instant (bench._composed_inputs' stable sort)."""
    plain = traffic["plain"]
    out = poisson_pods(
        rate_per_second=float(plain["rate_per_second"]),
        horizon_s=float(plain["horizon_s"]),
        seed=derive_seed(seed, "plain", cluster),
        cpu=int(plain["cpu_millicores"]),
        ram=int(plain["ram_gib"] * GIB),
        duration_s=plain["duration_s"],
        prefix="plain",
    )
    if traffic.get("pod_group"):
        out.append(pod_group_record(traffic["pod_group"]))
        out.sort(key=lambda rec: rec[0])
    return out


def to_events(records: Sequence[Record], api) -> List[Tuple[float, object]]:
    """Records -> (time, event) pairs of one side. `api` carries that side's
    Node, Pod, CreateNodeRequest, CreatePodRequest and GenericWorkloadTrace."""
    out = []
    for rec in records:
        kind = rec[1]
        if kind == "create_node":
            _, _, name, cpu, ram = rec
            out.append((rec[0], api.CreateNodeRequest(node=api.Node.new(name, cpu, ram))))
        elif kind == "create_pod":
            _, _, name, cpu, ram, duration = rec
            out.append(
                (rec[0], api.CreatePodRequest(pod=api.Pod.new(name, cpu, ram, duration)))
            )
        elif kind == "workload_yaml":
            out.extend(
                api.GenericWorkloadTrace.from_yaml(rec[2]).convert_to_simulator_events()
            )
        else:
            raise ValueError(f"unknown traffic record kind {kind!r}")
    return out


# --- what-if queries ---------------------------------------------------------


def scenario_catalogue(n: int) -> List[Dict]:
    """N control-law overrides, arithmetic in the index (bench's
    `_sweep_scenarios` without its planted duplicates): HPA scan interval and
    tolerance, CA scan interval and scale-down threshold."""
    return [
        dict(
            hpa_scan_interval=(30.0, 60.0, 90.0, 120.0)[i % 4],
            hpa_tolerance=0.05 + 0.05 * (i % 5),
            ca_scan_interval=10.0 + 5.0 * ((i // 2) % 4),
            ca_threshold=0.3 + 0.1 * ((i // 3) % 4),
        )
        for i in range(n)
    ]


def _apportion(weights: Sequence[float], total: int) -> List[int]:
    """Whole counts that sum to `total`, by largest remainder."""
    scale = total / sum(weights)
    exact = [w * scale for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(weights)), key=lambda i: exact[i] - counts[i], reverse=True)
    for i in order[: total - sum(counts)]:
        counts[i] += 1
    return counts


def query_stream(traffic: Dict, seed: int, seconds: float) -> List[Tuple[float, int, float]]:
    """(due_s, scenario index, horizon_s) for an open loop of `seconds`.

    Every seed gets the same work in another order: the gaps are the
    exponential distribution's own quantiles at the cell's rate (a Poisson
    process with its sampling noise taken out), the horizons and the Zipf
    popularity are apportioned exactly, and the seed only shuffles the three.
    A seed therefore moves which query meets which queue, not how much is
    asked."""
    q = traffic["queries"]
    rate = float(q["rate_per_second"])
    n = max(1, int(round(rate * seconds)))
    rng = random.Random(derive_seed(seed, "queries"))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    scale = seconds / sum(gaps)  # the last query is due as the window closes
    rng.shuffle(gaps)
    horizons: List[float] = []
    for h, count in zip(q["horizons_s"], _apportion(q["horizon_weights"], n)):
        horizons += [float(h)] * count
    rng.shuffle(horizons)
    size = int(q["catalogue_size"])
    popularity = [1.0 / (rank + 1) ** float(q["zipf_s"]) for rank in range(size)]
    scenarios: List[int] = []
    for idx, count in enumerate(_apportion(popularity, n)):
        scenarios += [idx] * count
    rng.shuffle(scenarios)
    out = []
    t = 0.0
    for gap, scen, h in zip(gaps, scenarios, horizons):
        t += gap * scale
        out.append((t, scen, h))
    return out


def seeded_order(seed: int, label: str, population: int) -> List[int]:
    """The order in which the correctness check draws its sample (clusters or
    queries): a seeded shuffle of the whole population, taken from the front."""
    order = list(range(population))
    random.Random(derive_seed(seed, "sample", label)).shuffle(order)
    return order
