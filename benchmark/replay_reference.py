"""The `trace_replay` cell's comparisons, beside benchmark/reference.py (which
no later PR edits): the scalar oracle run over the records of the benchmark's
own Alibaba parser, with the whole run's timing statistics kept, and the
checks a slid pod window allows.

A sliding window holds only the newest pods at the end of a job, so the pod-
for-pod comparison covers the resident ones; every earlier pod enters through
the terminal counters and through the collector's statistics over ALL pods.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.reference import Check, OracleRun, at_most, exactly, oracle_api
from benchmark.traffic_gen import to_events


@dataclass
class ReplayOracle:
    run: OracleRun  # counters incl. scheduling_decisions, every succeeded pod
    timings: Dict[str, Dict[str, float]]  # statistic -> min / max / mean / variance
    seconds: float


def run_oracle(config_text: str, cluster_records: Sequence, workload_records: Sequence, until_s: float) -> ReplayOracle:
    """One cluster through the scalar simulator to `until_s` (reference.
    run_oracle, which does not keep the collector's statistics)."""
    import time

    api = oracle_api()

    class _Events(api.Trace):
        def __init__(self, events):
            self._events = events

        def convert_to_simulator_events(self):
            return self._events

        def event_count(self):
            return len(self._events)

    t0 = time.perf_counter()
    sim = api.KubernetriksSimulation(api.SimulationConfig.from_yaml(config_text))
    sim.initialize(_Events(to_events(cluster_records, api)), _Events(to_events(workload_records, api)))
    sim.step_until_time(until_s)
    m = sim.metrics_collector.accumulated_metrics
    storage = sim.persistent_storage
    succeeded = {}
    for name, pod in storage.succeeded_pods.items():
        running = pod.get_condition(api.PodConditionType.POD_RUNNING)
        succeeded[name] = (pod.status.assigned_node, float(running.last_transition_time))
    run = OracleRun(
        counters={
            "pods_succeeded": int(m.pods_succeeded),
            "pods_removed": int(m.pods_removed),
            "terminated_pods": int(m.internal.terminated_pods),
            # one queue-time sample a pod the scheduler assigned
            "scheduling_decisions": int(m.pod_queue_time_stats.count()),
        },
        succeeded=succeeded,
        unscheduled=frozenset(storage.unscheduled_pods_cache),
    )
    timings = {
        "pod_queue_time": m.pod_queue_time_stats.as_dict(),
        "pod_duration": m.pod_duration_stats.as_dict(),
    }
    return ReplayOracle(run, timings, time.perf_counter() - t0)


def compare_resident_pods(
    label: str,
    view: Dict[str, Tuple[str, Optional[str], float]],
    oracle: OracleRun,
    start_time_tolerance_s: float,
) -> List[Check]:
    """Every pod resident in the program's final window against the oracle:
    phase and node exactly, start time to the tolerance. `view` as
    reference.compare_pods takes it."""
    wrong_phase = wrong_node = 0
    worst_gap = 0.0
    for name, (phase, node, start) in view.items():
        ref = oracle.succeeded.get(name)
        if phase == "succeeded" and ref is not None:
            wrong_node += int(node != ref[0])
            worst_gap = max(worst_gap, abs(start - ref[1]))
        elif phase == "succeeded" or ref is not None:
            wrong_phase += 1
        elif phase == "unschedulable" and name not in oracle.unscheduled:
            wrong_phase += 1
    note = f"{len(view)} resident pods of {len(oracle.succeeded)}"
    return [
        exactly(f"{label}.resident_pods_in_another_phase", wrong_phase, 0, note),
        exactly(f"{label}.resident_pods_on_another_node", wrong_node, 0, note),
        at_most(f"{label}.start_time_gap_s", worst_gap, start_time_tolerance_s, note),
    ]


def compare_timings(
    label: str,
    timings: Dict[str, Dict[str, float]],
    oracle: Dict[str, Dict[str, float]],
    statistics: Sequence[str],
    rtol: Dict[str, float],
) -> List[Check]:
    """The whole run's statistics: the relative gap of each number against
    the limit the configuration gives for its kind."""
    checks = []
    for stat in statistics:
        for kind, limit in rtol.items():
            got, want = float(timings[stat][kind]), float(oracle[stat][kind])
            gap = abs(got - want) / max(abs(want), 1e-300)
            checks.append(at_most(f"{label}.{stat}.{kind}.relative_gap", gap, limit, f"{got!r} against {want!r}"))
    return checks
