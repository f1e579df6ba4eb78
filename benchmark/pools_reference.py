"""The plain reference of the node-pool cells: the scalar oracle copy
(benchmark/oracle, which no later PR edits and whose scheduler knows neither
labels nor taints) with a scheduling algorithm of this file installed through
its own `Scheduler.set_scheduler_algorithm`. Imports nothing of the program
and no bit plane: the interning is the thing under test.

`PoolsScheduling.schedule_one` is written from the semantics block of
docs/PARITY.md "Node affinity and taints", on plain dicts and strings:

- Fit: the node's allocatable covers the pod's requests.
- NodeAffinity (required): the node's labels carry every pair of the pod's
  nodeSelector AND satisfy at least one of its terms, a term being the AND of
  its expressions (key, operator, values) with In, NotIn, Exists and
  DoesNotExist. A pod with neither passes every node.
- TaintToleration: each NoSchedule taint (key, value) of the node is matched
  by one of the pod's tolerations (key, operator, value, effect): effect empty
  or NoSchedule, and either Exists with the taint's key (or no key at all), or
  Equal with the taint's key and value.
- LeastAllocatedResources in float64: the mean over cpu and ram of the
  percentage of the node's current allocatable left after the placement.
- The last node in sorted-name order among the highest scores wins.

Terms and tolerations are kept beside the pods by name, taints beside the
nodes (the oracle copy's Pod and Node have labels and nothing else). The two
counters are counted here, at the scheduler itself: `affinity_attempts`, the
calls that try to place a pod which carries a selector, a term or a
toleration; `affinity_attempts_refused`, those of them that found no node
although some node of the cache passed Fit.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from benchmark import pools_gen
from benchmark.reference import OracleRun, oracle_api

POOL_COUNTERS = ("affinity_attempts", "affinity_attempts_refused")


def expression_holds(expression, labels: Dict[str, str]) -> bool:
    key, operator, values = expression
    if operator == "In":
        return key in labels and labels[key] in values
    if operator == "NotIn":
        return not (key in labels and labels[key] in values)
    if operator == "Exists":
        return key in labels
    if operator == "DoesNotExist":
        return key not in labels
    raise ValueError(f"pools_reference: unknown node selector operator {operator!r}")


def labels_admit(placement: Dict, labels: Dict[str, str]) -> bool:
    for key, value in placement["node_selector"].items():
        if labels.get(key) != value:
            return False
    terms = placement["terms"]
    if not terms:
        return True
    for term in terms:
        if all(expression_holds(e, labels) for e in term):
            return True
    return False


def taint_tolerated(taint, tolerations) -> bool:
    key, value, effect = taint
    for t_key, operator, t_value, t_effect in tolerations:
        if t_effect not in ("", effect):
            continue
        if operator == "Exists" and t_key in ("", key):
            return True
        if operator == "Equal" and t_key == key and t_value == value:
            return True
    return False


def taints_admit(placement: Dict, taints) -> bool:
    for taint in taints:
        if taint[2] != "NoSchedule":
            raise ValueError(f"pools_reference: taint effect {taint[2]!r} is not in the semantics")
        if not taint_tolerated(taint, placement["tolerations"]):
            return False
    return True


def names_nodes(placement: Dict) -> bool:
    return bool(placement["node_selector"] or placement["terms"] or placement["tolerations"])


class PoolsScheduling:
    """Fit + NodeAffinity + TaintToleration, LeastAllocatedResources, last
    max in name order; counts the two counters as it is called."""

    def __init__(self, placements: Dict[str, Dict], taints: Dict[str, List], failure, no_fit, zero_request, no_nodes):
        self.placements = placements
        self.taints = taints
        self.counts = dict.fromkeys(POOL_COUNTERS, 0)
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
        chosen, best, some_node_fits = None, None, False
        for name in sorted(nodes):
            node = nodes[name]
            free = node.status.allocatable
            if want.cpu > free.cpu or want.ram > free.ram:
                continue
            some_node_fits = True
            if not labels_admit(placement, node.metadata.labels):
                continue
            if not taints_admit(placement, self.taints.get(name, ())):
                continue
            cpu_left = (free.cpu - want.cpu) * 100.0 / free.cpu if free.cpu else float("nan")
            ram_left = (free.ram - want.ram) * 100.0 / free.ram if free.ram else float("nan")
            score = 0.0 + (cpu_left + ram_left) / 2.0
            if chosen is None or score >= best:
                chosen, best = name, score
        if chosen is None:
            self.counts["affinity_attempts_refused"] += int(named and some_node_fits)
            raise self._failure(no_fit)
        return chosen


def install(sim, placements: Dict[str, Dict], taints: Dict[str, List]) -> PoolsScheduling:
    """Put the algorithm into an oracle simulation's scheduler."""
    from benchmark.oracle.core.scheduler.interface import ScheduleError, SchedulingFailure

    algorithm = PoolsScheduling(
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
    """One cluster of node pools through the scalar simulator to `until_s`, as
    reference.run_oracle runs a plain one. `config_text` names no scheduler
    profile (the oracle copy knows none for this): the installed algorithm is
    the profile. The run's counters carry the two this file counts."""
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
        sim, pools_gen.placements_by_pod(workload_records), pools_gen.taints_by_node(cluster_records)
    )
    sim.initialize(
        _Events(pools_gen.to_events(cluster_records, api)),
        _Events(pools_gen.to_events(workload_records, api)),
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

