"""The plain reference of the `sched1k-kubescore` cells: the scalar oracle copy
(benchmark/oracle, which no later PR edits and whose scheduler knows neither
labels nor taints nor integer scores) with a scheduling algorithm of this file
installed through its own `Scheduler.set_scheduler_algorithm`, as
benchmark/pools_reference.py does it. Imports nothing of the program, no bit
plane and no float: the interning, the units and the quotients are the things
under test.

`KubeScoreScheduling.schedule_one` is written from docs/PARITY.md "Scoring as
kube-scheduler scores" (and, for the three filters, "Node affinity and
taints"), on plain dicts, strings and Python integers. With A the node's
capacity, F its current free, q the pod's request, U = A - F + q, and FEASIBLE
the nodes that pass all three filters for this pod now:

- Fit, NodeAffinity (nodeSelector and required terms), TaintToleration
  (NoSchedule; a toleration's effect empty or NoSchedule): pools_reference's.
- NodeResourcesFit: per resource (A - U) * 100 // A, 0 where A is 0; the sum
  of cpu's and ram's, // 2.
- NodeResourcesBalancedAllocation: (100 A_cpu A_ram - 50 |U_cpu A_ram - U_ram
  A_cpu|) // (A_cpu A_ram); 0 where an A is 0.
- NodeAffinity's score: raw = the sum of the weights of the pod's preferred
  terms whose expressions all hold on the node's labels; M = the largest raw
  over FEASIBLE; 0 where M is 0, else 100 * raw // M.
- TaintToleration's score: raw = how many PreferNoSchedule taints of the node
  no toleration of effect empty or PreferNoSchedule matches; M likewise; 100
  where M is 0, else 100 - 100 * raw // M.
- total = fit + balanced + 2 affinity + 3 taints; the last node in
  sorted-name order among the highest totals wins.

Placements are kept beside the pods by name, taints beside the nodes. Four
counters are counted here, at the scheduler itself: pools_reference's two
(`affinity_attempts`: the pod carries a selector, a required or preferred
term, or a toleration; `affinity_attempts_refused`), `soft_attempts` (a call
in which a label scorer's M was above 0) and `soft_honoured` (of those, the
chosen node's 2 affinity + 3 taints is the largest among FEASIBLE).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from benchmark import kubescore_gen
from benchmark.pools_reference import expression_holds, labels_admit, taint_tolerated
from benchmark.reference import OracleRun, oracle_api

SCORE_COUNTERS = ("affinity_attempts", "affinity_attempts_refused", "soft_attempts", "soft_honoured")
WEIGHTS = {"fit": 1, "balanced": 1, "affinity": 2, "taints": 3}


def hard_taints_admit(placement: Dict, taints) -> bool:
    return all(
        taint_tolerated(t, placement["tolerations"]) for t in taints if t[2] == "NoSchedule"
    )


def fit_score(capacity, free, want) -> int:
    def left(a: int, f: int, q: int) -> int:
        return (f - q) * 100 // a if a else 0

    return (left(capacity.cpu, free.cpu, want.cpu) + left(capacity.ram, free.ram, want.ram)) // 2


def balanced_score(capacity, free, want) -> int:
    a_cpu, a_ram = capacity.cpu, capacity.ram
    if not a_cpu or not a_ram:
        return 0
    u_cpu, u_ram = a_cpu - free.cpu + want.cpu, a_ram - free.ram + want.ram
    return (100 * a_cpu * a_ram - 50 * abs(u_cpu * a_ram - u_ram * a_cpu)) // (a_cpu * a_ram)


def affinity_raw(placement: Dict, labels: Dict[str, str]) -> int:
    return sum(
        weight for weight, term in placement["preferred"] if all(expression_holds(e, labels) for e in term)
    )


def taints_raw(placement: Dict, taints) -> int:
    return sum(
        1 for t in taints if t[2] == "PreferNoSchedule" and not taint_tolerated(t, placement["tolerations"])
    )


def names_nodes(placement: Dict) -> bool:
    return bool(
        placement["node_selector"] or placement["terms"] or placement["preferred"] or placement["tolerations"]
    )


class KubeScoreScheduling:
    """The three filters, the four scorers at 1 / 1 / 2 / 3, the last max in
    name order; counts the four counters as it is called."""

    def __init__(self, placements: Dict[str, Dict], taints: Dict[str, List], failure, no_fit, zero_request, no_nodes):
        self.placements = placements
        self.taints = taints
        self.counts = dict.fromkeys(SCORE_COUNTERS, 0)
        self._failure = failure
        self._errors = (no_fit, zero_request, no_nodes)

    def schedule_one(self, pod, nodes) -> str:
        no_fit, zero_request, no_nodes = self._errors
        want = pod.spec.resources.requests
        if want.cpu == 0 and want.ram == 0:
            raise self._failure(zero_request)
        if not nodes:
            raise self._failure(no_nodes)
        placement = self.placements[pod.metadata.name]
        named = names_nodes(placement)
        self.counts["affinity_attempts"] += int(named)
        feasible, some_node_fits = [], False
        for name in sorted(nodes):
            node = nodes[name]
            free = node.status.allocatable
            if want.cpu > free.cpu or want.ram > free.ram:
                continue
            some_node_fits = True
            taints = self.taints.get(name, ())
            for taint in taints:
                if taint[2] not in ("NoSchedule", "PreferNoSchedule"):
                    raise ValueError(f"kubescore_reference: taint effect {taint[2]!r} is not in the semantics")
            if labels_admit(placement, node.metadata.labels) and hard_taints_admit(placement, taints):
                feasible.append((name, node, taints))
        if not feasible:
            self.counts["affinity_attempts_refused"] += int(named and some_node_fits)
            raise self._failure(no_fit)
        raw_affinity = [affinity_raw(placement, node.metadata.labels) for _, node, _ in feasible]
        raw_taints = [taints_raw(placement, taints) for _, _, taints in feasible]
        most_affinity, most_taints = max(raw_affinity), max(raw_taints)
        chosen = best = None
        soft = []
        for (name, node, _), ra, rt in zip(feasible, raw_affinity, raw_taints):
            affinity = 100 * ra // most_affinity if most_affinity else 0
            taints = 100 - 100 * rt // most_taints if most_taints else 100
            soft.append(WEIGHTS["affinity"] * affinity + WEIGHTS["taints"] * taints)
            total = (
                WEIGHTS["fit"] * fit_score(node.status.capacity, node.status.allocatable, want)
                + WEIGHTS["balanced"] * balanced_score(node.status.capacity, node.status.allocatable, want)
                + soft[-1]
            )
            if chosen is None or total >= best:
                chosen, best, chosen_soft = name, total, soft[-1]
        if most_affinity or most_taints:
            self.counts["soft_attempts"] += 1
            self.counts["soft_honoured"] += int(chosen_soft == max(soft))
        return chosen


def install(sim, placements: Dict[str, Dict], taints: Dict[str, List]) -> KubeScoreScheduling:
    """Put the algorithm into an oracle simulation's scheduler."""
    from benchmark.oracle.core.scheduler.interface import ScheduleError, SchedulingFailure

    algorithm = KubeScoreScheduling(
        placements,
        taints,
        SchedulingFailure,
        ScheduleError.NO_SUFFICIENT_RESOURCES,
        ScheduleError.REQUESTED_RESOURCES_ARE_ZEROS,
        ScheduleError.NO_NODES_IN_CLUSTER,
    )
    sim.scheduler.set_scheduler_algorithm(algorithm)
    return algorithm


def run_oracle(config_text: str, cluster_records: Sequence, workload_records: Sequence, until_s: float) -> OracleRun:
    """One cluster through the scalar simulator to `until_s`, as
    pools_reference.run_oracle runs one: `config_text` names no scheduler
    profile, the installed algorithm is the profile. The run's counters carry
    the four this file counts."""
    api = oracle_api()

    class _Events(api.Trace):
        def __init__(self, events):
            self._events = events

        def convert_to_simulator_events(self):
            return self._events

        def event_count(self):
            return len(self._events)

    sim = api.KubernetriksSimulation(api.SimulationConfig.from_yaml(config_text))
    algorithm = install(
        sim, kubescore_gen.placements_by_pod(workload_records), kubescore_gen.taints_by_node(cluster_records)
    )
    sim.initialize(
        _Events(kubescore_gen.to_events(cluster_records, api)),
        _Events(kubescore_gen.to_events(workload_records, api)),
    )
    sim.step_until_time(until_s)
    m = sim.metrics_collector.accumulated_metrics
    storage = sim.persistent_storage
    succeeded = {}
    for name, pod in storage.succeeded_pods.items():
        running = pod.get_condition(api.PodConditionType.POD_RUNNING)
        succeeded[name] = (pod.status.assigned_node, float(running.last_transition_time))
    return OracleRun(
        counters={
            "pods_succeeded": int(m.pods_succeeded),
            "pods_removed": int(m.pods_removed),
            "terminated_pods": int(m.internal.terminated_pods),
            **algorithm.counts,
        },
        succeeded=succeeded,
        unscheduled=frozenset(storage.unscheduled_pods_cache),
    )
