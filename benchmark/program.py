"""Everything the benchmark takes from the program, in one place: the system
under test (engine and fleet builds), its counters and its kernel names. The
yardstick (traffic, reference, reductions, peaks) lives beside this file and
imports none of it."""

from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple

# The accelerator defaults of the engine's tristates, spelled out for the CPU
# rehearsal: on a CPU backend they all resolve off.
ACCELERATOR_STATICS = dict(
    donate=True,
    fuse_slide=True,
    superspan=True,
    stream=True,
    lane_major=True,
    window_razor=True,
    reclaim=True,
)
# A lane-async engine turns these off by itself and refuses them by name.
GLOBAL_CLOCK_STATICS = ("superspan", "stream", "fuse_slide")


def program_api() -> SimpleNamespace:
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.core.events import CreateNodeRequest, CreatePodRequest
    from kubernetriks_tpu.core.types import Node, Pod
    from kubernetriks_tpu.trace.generic import GenericWorkloadTrace

    return SimpleNamespace(**locals())


def rehearsal_kwargs(lane_async: bool = False) -> Dict:
    """What the CPU rehearsal forces so that it drives the chip's program
    family: kernels on and interpreted, every accelerator static on."""
    statics = {
        k: v
        for k, v in ACCELERATOR_STATICS.items()
        if not (lane_async and k in GLOBAL_CLOCK_STATICS)
    }
    return dict(use_pallas=True, pallas_interpret=True, **statics)


def plain_formulation_kwargs(reclaim: bool) -> Dict:
    """The program's plain formulation: scan kernels, ladder, host slides;
    every static off but reclaim (reclaim on/off is not state-comparable)."""
    return dict(
        use_pallas=False, **{**dict.fromkeys(ACCELERATOR_STATICS, False), "reclaim": reclaim}
    )


def _compile_chunk(job):
    """Pool worker: generate and compile the traces of some clusters. Runs
    in a child that never needs the chip (spawned with JAX held to the CPU;
    the trace compiler is host numpy)."""
    config_text, deployment, traffic, seed, clusters = job
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace

    from benchmark import traffic_gen

    api = program_api()
    config = api.SimulationConfig.from_yaml(config_text)
    cluster_events = traffic_gen.to_events(traffic_gen.cluster_records(deployment), api)
    return [
        compile_cluster_trace(
            cluster_events,
            traffic_gen.to_events(traffic_gen.workload_records(traffic, seed, c), api),
            config,
        )
        for c in clusters
    ]


def _hold_to_cpu() -> None:
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"


class TracePool:
    """Every cluster's own compiled trace, cluster c seeded from (seed, c).
    Building the event objects is host Python (about 20 ms a cluster at 2,000
    pods), so clusters are spread over a pool of spawned workers. `start()`
    needs no JAX, so run.py starts it before JAX reaches for the chip and the
    two overlap; every worker has ended when `result()` or `cancel()` returns."""

    def __init__(self, config_text: str, deployment: Dict, traffic: Dict, seed: int, n_clusters: int):
        import os

        workers = min(len(os.sched_getaffinity(0)), 16, max(1, n_clusters // 8))
        chunk = -(-n_clusters // (workers * 4))
        self.jobs = [
            (config_text, deployment, traffic, seed, list(range(lo, min(lo + chunk, n_clusters))))
            for lo in range(0, n_clusters, chunk)
        ]
        self.workers = workers
        self.pool = None
        self.futures = []

    def start(self) -> "TracePool":
        import concurrent.futures
        import multiprocessing

        if self.workers > 1:
            self.pool = concurrent.futures.ProcessPoolExecutor(
                max_workers=self.workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_hold_to_cpu,
            )
            self.futures = [self.pool.submit(_compile_chunk, job) for job in self.jobs]
        return self

    def result(self) -> List:
        if self.pool is None:
            return [trace for job in self.jobs for trace in _compile_chunk(job)]
        try:
            return [trace for future in self.futures for trace in future.result()]
        finally:
            self.pool.shutdown(wait=True)

    def cancel(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)


def build_engine(config_text: str, compiled: Sequence, *, resettable: bool, mesh=None, **engine_kwargs):
    """One engine over per-cluster compiled traces. With `resettable`, the
    build ScenarioFleet makes (a neutral scenario, so the engine keeps its
    pristine snapshot and `fleet_reset()` works)."""
    from kubernetriks_tpu.batched.engine import BatchedSimulation
    from kubernetriks_tpu.batched.fleet import scenario_vectors

    config = program_api().SimulationConfig.from_yaml(config_text)
    if resettable:
        engine_kwargs["scenario"] = dict(scenario_vectors(config, len(compiled), None))
    return BatchedSimulation(config, list(compiled), mesh=mesh, **engine_kwargs)


def cluster_counters(sim, cluster: int) -> Dict[str, int]:
    """One cluster's terminal counters, under the oracle's names."""
    m = sim.state.metrics
    fields = dict(
        pods_succeeded="pods_succeeded",
        pods_removed="pods_removed",
        terminated_pods="terminated_pods",
        scheduling_decisions="scheduling_decisions",
        total_scaled_up_pods="scaled_up_pods",
        total_scaled_down_pods="scaled_down_pods",
        total_scaled_up_nodes="scaled_up_nodes",
        total_scaled_down_nodes="scaled_down_nodes",
    )
    return {name: int(getattr(m, leaf)[cluster]) for name, leaf in fields.items()}


def normalized_pod_view(sim, cluster: int) -> Dict[str, Tuple[str, Optional[str], float]]:
    """pod name -> (phase, node, start time) in the reference's vocabulary."""
    from kubernetriks_tpu.batched.state import (
        PHASE_REMOVED,
        PHASE_SUCCEEDED,
        PHASE_UNSCHEDULABLE,
    )

    names = {
        PHASE_SUCCEEDED: "succeeded",
        PHASE_UNSCHEDULABLE: "unschedulable",
        PHASE_REMOVED: "removed",
    }
    return {
        name: (names.get(row["phase"], "other"), row["node"], row["start_time"])
        for name, row in sim.pod_view(cluster).items()
    }


def lane_reader(fleet):
    """`read(lane)`: one lane's slice of the fleet's resident state, on the
    host. Every leaf of the state leads with the lane axis. One compiled
    program with the lane traced (a Python index would compile once per lane,
    inside the window); about 0.1 MB a call at the autoscaled width."""
    import jax
    import numpy as np

    take = jax.jit(
        lambda state, lane: jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, lane, 0, keepdims=False), state
        )
    )
    return lambda lane: jax.device_get(take(fleet.engine.state, np.int32(lane)))


def decisions_per_cluster(sim):
    """Device fetch of the (C,) decisions counter: a real sync point."""
    import numpy as np

    return np.asarray(sim.state.metrics.scheduling_decisions)
