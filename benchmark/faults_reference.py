"""The plain reference of the node-fault cells: the scalar oracle copy
(benchmark/oracle, which no later PR edits) fed PLAIN remove and create
events at the instants benchmark/faults_gen.py drew. Imports nothing of the
program.

A crash IS a node removal and a recovery IS a creation of the same name at
full capacity (the reference's one fault path: a removed node's component
cancels and frees its pods, the scheduler reschedules every pod of the dead
node in sorted-name order; SURVEY.md, failure). The oracle copy's own chaos
accounting is not used: the events carry no `crashed` / `recovered` flag, and
the three fault counters are counted here, by listening at the scheduler:

- `node_crashes`: removals of a node that reached the scheduler's cache;
- `node_recoveries`: a node added to the scheduler's cache again after it
  had been removed;
- `pod_interruptions`: the pods that sat on a node when it went, which is
  what the scheduler rescheduled for it.
"""

from __future__ import annotations

import time
from types import SimpleNamespace
from typing import Dict, Sequence, Tuple

from benchmark import faults_gen, traffic_gen
from benchmark.reference import OracleRun

FAULT_COUNTERS = ("node_crashes", "node_recoveries", "pod_interruptions")


def oracle_api() -> SimpleNamespace:
    from benchmark import reference
    from benchmark.oracle.core.events import RemoveNodeRequest

    api = reference.oracle_api()
    api.RemoveNodeRequest = RemoveNodeRequest
    return api


def listen(scheduler) -> Dict[str, int]:
    """Count crashes, recoveries and interrupted pods at an oracle
    simulation's scheduler; returns the counts, filled as it runs."""
    counts = dict.fromkeys(FAULT_COUNTERS, 0)
    gone = set()
    reschedule, add_node = scheduler.reschedule_unfinished_pods, scheduler.on_add_node_to_cache

    def reschedule_unfinished_pods(node_name, event_time):
        n = reschedule(node_name, event_time)
        gone.add(node_name)
        counts["node_crashes"] += 1
        counts["pod_interruptions"] += n
        return n

    def on_add_node_to_cache(data, time):
        if data.node.metadata.name in gone:
            gone.discard(data.node.metadata.name)
            counts["node_recoveries"] += 1
        return add_node(data, time)

    scheduler.reschedule_unfinished_pods = reschedule_unfinished_pods
    scheduler.on_add_node_to_cache = on_add_node_to_cache
    return counts


def run_oracle(config_text: str, cluster_records: Sequence, workload_records: Sequence, until_s: float) -> OracleRun:
    """One cluster with its fault schedule through the scalar simulator to
    `until_s`, as reference.run_oracle runs one without."""
    api = oracle_api()

    class _Events(api.Trace):
        def __init__(self, events):
            self._events = events

        def convert_to_simulator_events(self):
            return self._events

        def event_count(self):
            return len(self._events)

    sim = api.KubernetriksSimulation(api.SimulationConfig.from_yaml(config_text))
    counts = listen(sim.scheduler)
    sim.initialize(
        _Events(faults_gen.to_events(cluster_records, api, flagged=False)),
        _Events(traffic_gen.to_events(workload_records, api)),
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
            **counts,
        },
        succeeded=succeeded,
        unscheduled=frozenset(storage.unscheduled_pods_cache),
    )


def timed_oracle(*args) -> Tuple[OracleRun, float]:
    t0 = time.perf_counter()
    return run_oracle(*args), time.perf_counter() - t0
