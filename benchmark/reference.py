"""Running the plain reference and deciding `correct` against it.

The reference is benchmark/oracle (the scalar discrete-event simulator, a copy
that imports nothing of the program). It is host Python: drivers call it after
the measured window has closed, so it never idles the chip inside it.

Every comparison returns `Check` rows: a name, the number measured, its limit
and whether it held. run.py prints each row, so every run shows each number
compared beside its limit.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple


@dataclass
class Check:
    name: str
    value: float
    limit: float
    ok: bool
    note: str = ""

    def row(self) -> Dict:
        return {
            "check": self.name,
            "value": self.value,
            "limit": self.limit,
            "ok": self.ok,
            "note": self.note,
        }


def at_most(name: str, value: float, limit: float, note: str = "") -> Check:
    return Check(name, float(value), float(limit), bool(value <= limit), note)


def exactly(name: str, value: float, wanted: float, note: str = "") -> Check:
    """An exact comparison: the number reported is the gap, the limit 0."""
    gap = abs(float(value) - float(wanted))
    return Check(name, gap, 0.0, gap == 0.0, note or f"{value} against {wanted}")


def oracle_api() -> SimpleNamespace:
    from benchmark.oracle.config import SimulationConfig
    from benchmark.oracle.core.events import CreateNodeRequest, CreatePodRequest
    from benchmark.oracle.core.types import Node, Pod, PodConditionType
    from benchmark.oracle.sim.simulator import KubernetriksSimulation
    from benchmark.oracle.trace.generic import GenericWorkloadTrace
    from benchmark.oracle.trace.interface import Trace

    return SimpleNamespace(**locals())


@dataclass
class OracleRun:
    counters: Dict[str, int]
    # name -> (node, start time) of every pod that succeeded
    succeeded: Dict[str, Tuple[Optional[str], float]]
    unscheduled: frozenset
    node_series: List[Tuple[float, int]] = field(default_factory=list)


def run_oracle(
    config_text: str,
    cluster_records: Sequence,
    workload_records: Sequence,
    until_s: float,
    sample_every_s: Optional[float] = None,
) -> OracleRun:
    """One cluster through the scalar simulator to `until_s`. With
    `sample_every_s`, the node count is read at each multiple of it on the
    way (the CA trajectory that is printed, not judged)."""
    from benchmark.traffic_gen import to_events

    api = oracle_api()

    class _Events(api.Trace):
        def __init__(self, events):
            self._events = events

        def convert_to_simulator_events(self):
            return self._events

        def event_count(self):
            return len(self._events)

    sim = api.KubernetriksSimulation(api.SimulationConfig.from_yaml(config_text))
    sim.initialize(
        _Events(to_events(cluster_records, api)),
        _Events(to_events(workload_records, api)),
    )
    series = []
    if sample_every_s:
        t = 0.0
        while t < until_s:
            t = min(t + sample_every_s, until_s)
            sim.step_until_time(t)
            series.append((t, sim.api_server.node_count()))
    else:
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
            "total_scaled_up_pods": int(m.total_scaled_up_pods),
            "total_scaled_down_pods": int(m.total_scaled_down_pods),
            "total_scaled_up_nodes": int(m.total_scaled_up_nodes),
            "total_scaled_down_nodes": int(m.total_scaled_down_nodes),
        },
        succeeded=succeeded,
        unscheduled=frozenset(storage.unscheduled_pods_cache),
        node_series=series,
    )


class OracleFault(Exception):
    """The scalar simulator itself failed on these events. At zero control-
    plane delays an HPA scale-down can remove a pod in the tick in which the
    scheduler assigns it, and persistent storage then raises KeyError (seen
    on about one cluster in twelve at the autoscaled load: PERF.md, open
    questions). A reference that crashes gives no verdict: the caller draws
    the next cluster of its seeded order and says so on a line."""


def run_oracle_or_fault(*args, **kwargs) -> OracleRun:
    try:
        return run_oracle(*args, **kwargs)
    except KeyError as e:
        raise OracleFault(f"KeyError {e}") from e


def compare_pods(
    label: str,
    view: Dict[str, Tuple[str, Optional[str], float]],
    counters: Dict[str, int],
    oracle: OracleRun,
    counter_names: Sequence[str],
    start_time_tolerance_s: float,
) -> List[Check]:
    """One cluster of the timed path against the oracle: terminal counters
    and every pod's phase and node exactly, start times to the tolerance.
    `view` maps pod name -> (phase, node, start time), phase one of
    "succeeded", "unschedulable", "removed" or "other"."""
    checks = [
        exactly(f"{label}.{name}", counters[name], oracle.counters[name])
        for name in counter_names
    ]
    wrong_phase = 0
    wrong_node = 0
    worst_gap = 0.0
    for name, (phase, node, start) in view.items():
        ref = oracle.succeeded.get(name)
        if phase == "succeeded":
            if ref is None:
                wrong_phase += 1
                continue
            wrong_node += int(node != ref[0])
            worst_gap = max(worst_gap, abs(start - ref[1]))
        elif ref is not None:
            wrong_phase += 1
        elif phase == "unschedulable" and name not in oracle.unscheduled:
            wrong_phase += 1
    wrong_phase += sum(1 for name in oracle.succeeded if name not in view)
    note = f"{len(view)} pods"
    checks.append(exactly(f"{label}.pods_in_another_phase", wrong_phase, 0, note))
    checks.append(exactly(f"{label}.pods_on_another_node", wrong_node, 0, note))
    checks.append(
        at_most(f"{label}.start_time_gap_s", worst_gap, start_time_tolerance_s, note)
    )
    return checks


def compare_counts(
    label: str,
    counters: Dict[str, int],
    oracle: OracleRun,
    names: Sequence[str],
    within: Optional[Dict[str, float]] = None,
) -> List[Check]:
    """Counters against the oracle's: `names` exactly; each name of `within`
    to that many units (a count the two paths' CA trajectories move by a few,
    held against the fault it is there to catch: PERF.md gives the readings)."""
    checks = [
        exactly(f"{label}.{name}", counters[name], oracle.counters[name])
        for name in names
    ]
    for name, limit in (within or {}).items():
        gap = abs(counters[name] - oracle.counters[name])
        checks.append(
            at_most(f"{label}.{name}", gap, limit, f"{counters[name]} against {oracle.counters[name]}")
        )
    return checks


def mismatching_leaves(a, b) -> List[str]:
    """Paths of the leaves at which two final-state pytrees differ: all
    simulation state exactly; the float32 metric accumulators to rtol 1e-6
    (their masked cycle folds are tiled per program by XLA, so differently
    fused programs can differ by an ulp: docs/PARITY.md). The parity policy of
    the program's `compare_states`, kept here so that no PR can move it."""
    import jax
    import numpy as np

    flat_a, tree_a = jax.tree_util.tree_flatten_with_path(a)
    flat_b, tree_b = jax.tree_util.tree_flatten_with_path(b)
    if tree_a != tree_b:
        return [f"<tree structure: {tree_a} != {tree_b}>"]
    bad = []
    for (path, x), (_, y) in zip(flat_a, flat_b):
        key = jax.tree_util.keystr(path)
        xa, ya = np.asarray(x), np.asarray(y)
        if xa.shape != ya.shape:
            ok = False
        elif ".metrics." in key and xa.dtype == np.float32:
            ok = bool(np.allclose(xa, ya, rtol=1e-6, atol=0.0))
        else:
            ok = bool((xa == ya).all())
        if not ok:
            bad.append(key)
    return bad


def state_in_float32(state, interval_s: float):
    """The control for a bit-identity check: the same final state with every
    simulation time held as one float32 of absolute seconds. The program keeps
    a time as (window index, float32 offset within the window), good to 1e-6 s
    at any t; one float32 is good to 6e-5 s at t = 1000 s. Times at +infinity
    (window index past 2**29) are left alone."""
    import jax
    import numpy as np

    flat, tree = jax.tree_util.tree_flatten_with_path(state)
    by_key = {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in flat}
    out = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        win = by_key.get(key[: -len(".off")] + ".win") if key.endswith(".off") else None
        if win is None:
            out.append(leaf)
            continue
        off = np.asarray(leaf)
        base = win.astype(np.float64) * interval_s
        absolute = (base + off.astype(np.float64)).astype(np.float32).astype(np.float64)
        rounded = (absolute - base).astype(np.float32)
        out.append(np.where(win >= (1 << 29), off, rounded))
    return jax.tree_util.tree_unflatten(tree, out)


def in_float32(view: Dict[str, Tuple[str, Optional[str], float]]):
    """The control: the same answers with times held in float32, the step a
    later PR would be tempted by (ulp 6e-5 s at t = 1000 s against a 5e-6 s
    tolerance). Used by the tests and the control runs, never by a cell."""
    import numpy as np

    return {
        name: (phase, node, float(np.float32(start)))
        for name, (phase, node, start) in view.items()
    }
