"""The plain reference of the topology-spread cells: the scalar oracle copy
(benchmark/oracle, which no later PR edits and whose scheduler knows two
plugins) with a scheduling algorithm of this file installed through its own
`Scheduler.set_scheduler_algorithm`. Imports nothing of the program.

`SpreadScheduling.schedule_one` is written from the semantics block of
docs/PARITY.md "Topology spread", not from the program's plugin:

- Fit: the node's allocatable covers the pod's requests.
- PodTopologySpread (DoNotSchedule), for a pod with a constraint (maxSkew,
  topologyKey, selector): the domains are the values of topologyKey over ALL
  nodes in the scheduler's cache that carry the key, whether they fit or not;
  match(d) counts the pods the scheduler has assigned and not yet seen leave
  (`scheduler.assignments`) on nodes of domain d whose labels satisfy the
  selector; self is 1 if the pod's own labels do; a node passes iff it
  carries the key and match(its domain) + self - min over domains <= maxSkew.
- LeastAllocatedResources in float64: the mean over cpu and ram of the
  percentage of the node's current allocatable left after the placement.
- The last node in sorted-name order among the highest scores wins.

Constraints are kept beside the pods (name -> constraint): the oracle copy's
Pod has labels and no constraint field.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence, Tuple

from benchmark import spread_gen
from benchmark.reference import OracleRun, oracle_api

Constraint = Tuple[int, str, Dict[str, str]]


def _satisfies(selector: Dict[str, str], labels: Dict[str, str]) -> bool:
    for key, value in selector.items():
        if labels.get(key) != value:
            return False
    return True


class SpreadScheduling:
    """Fit + PodTopologySpread, LeastAllocatedResources, last max in name
    order; reads the scheduler it is installed into for the placed pods."""

    def __init__(self, scheduler, constraints: Dict[str, Constraint], failure, no_fit, zero_request, no_nodes):
        self.scheduler = scheduler
        self.constraints = constraints
        self._failure = failure
        self._errors = (no_fit, zero_request, no_nodes)

    def open_domains(self, pod, nodes) -> Optional[Tuple[str, set]]:
        """(topologyKey, the domains the pod's constraint admits), or None
        for a pod without a constraint."""
        constraint = self.constraints.get(pod.metadata.name)
        if constraint is None:
            return None
        max_skew, key, selector = constraint
        matching: Dict[str, int] = {}
        for name in nodes:
            labels = nodes[name].metadata.labels
            if key not in labels:
                continue
            count = matching.get(labels[key], 0)
            for placed_name in self.scheduler.assignments.get(name, ()):
                placed = self.scheduler.objects_cache.pods.get(placed_name)
                if placed is not None and _satisfies(selector, placed.metadata.labels):
                    count += 1
            matching[labels[key]] = count
        if not matching:
            return key, set()
        fewest = min(matching.values())
        extra = 1 if _satisfies(selector, pod.metadata.labels) else 0
        return key, {d for d, count in matching.items() if count + extra - fewest <= max_skew}

    def schedule_one(self, pod, nodes) -> str:
        no_fit, zero_request, no_nodes = self._errors
        want = pod.spec.resources.requests
        if want.cpu == 0 and want.ram == 0:
            raise self._failure(zero_request)
        if not nodes:
            raise self._failure(no_nodes)
        spread = self.open_domains(pod, nodes)
        chosen, best = None, None
        for name in sorted(nodes):
            node = nodes[name]
            free = node.status.allocatable
            if want.cpu > free.cpu or want.ram > free.ram:
                continue
            if spread is not None:
                key, admitted = spread
                if node.metadata.labels.get(key) not in admitted:
                    continue
            cpu_left = (free.cpu - want.cpu) * 100.0 / free.cpu if free.cpu else float("nan")
            ram_left = (free.ram - want.ram) * 100.0 / free.ram if free.ram else float("nan")
            score = 0.0 + (cpu_left + ram_left) / 2.0
            if chosen is None or score >= best:
                chosen, best = name, score
        if chosen is None:
            raise self._failure(no_fit)
        return chosen


def install(sim, constraints: Dict[str, Constraint]) -> SpreadScheduling:
    """Put the algorithm into an oracle simulation's scheduler."""
    from benchmark.oracle.core.scheduler.interface import ScheduleError, SchedulingFailure

    algorithm = SpreadScheduling(
        sim.scheduler,
        constraints,
        SchedulingFailure,
        ScheduleError.NO_SUFFICIENT_RESOURCES,
        ScheduleError.REQUESTED_RESOURCES_ARE_ZEROS,
        ScheduleError.NO_NODES_IN_CLUSTER,
    )
    sim.scheduler.set_scheduler_algorithm(algorithm)
    return algorithm


def run_oracle(config_text: str, cluster_records: Sequence, workload_records: Sequence, until_s: float) -> OracleRun:
    """One labelled cluster through the scalar simulator to `until_s`, as
    reference.run_oracle runs an unlabelled one. `config_text` names no
    scheduler profile (the oracle copy knows none for this): the installed
    algorithm is the profile."""
    api = oracle_api()

    class _Events(api.Trace):
        def __init__(self, events):
            self._events = events

        def convert_to_simulator_events(self):
            return self._events

        def event_count(self):
            return len(self._events)

    sim = api.KubernetriksSimulation(api.SimulationConfig.from_yaml(config_text))
    install(sim, spread_gen.constraints_by_pod(workload_records))
    sim.initialize(
        _Events(spread_gen.to_events(cluster_records, api)),
        _Events(spread_gen.to_events(workload_records, api)),
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
        },
        succeeded=succeeded,
        unscheduled=frozenset(storage.unscheduled_pods_cache),
    )


def timed_oracle(*args) -> Tuple[OracleRun, float]:
    t0 = time.perf_counter()
    return run_oracle(*args), time.perf_counter() - t0
