"""What the `batch_jobs_pools` driver takes from the program, beside
benchmark/program.py (which no later PR edits): whether the program knows the
`node_pools` profile at all, its object types with the taint, affinity and
toleration types among them, how a record's taints and placement go onto its
objects, the pool that compiles every cluster's trace of node pools
(program.TracePool compiles traffic_gen's bare records by name), and the two
label-filter counters."""

from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List

from benchmark import program

POOL_COUNTERS = ("affinity_attempts", "affinity_attempts_refused")


def knows_node_pools() -> bool:
    """Whether the program has the `node_pools` profile (a commit before
    PR 47 has not: it would refuse the configuration at the engine build, or,
    asked for the `default` profile, ignore every taint and term)."""
    try:
        from kubernetriks_tpu.core.scheduler.kube_scheduler import NAMED_PROFILE_SPECS
    except ImportError:
        return False
    return "node_pools" in NAMED_PROFILE_SPECS


def program_api() -> SimpleNamespace:
    from kubernetriks_tpu.core.types import (
        NodeAffinity,
        NodeSelectorRequirement,
        NodeSelectorTerm,
        Taint,
        Toleration,
    )

    api = program.program_api()
    for cls in (NodeAffinity, NodeSelectorRequirement, NodeSelectorTerm, Taint, Toleration):
        setattr(api, cls.__name__, cls)
    return api


def placer(api):
    """pools_gen's `place`: a node record's taints and a pod record's
    placement onto the program's objects."""

    def place(obj, record):
        if record[1] == "create_node":
            obj.spec.taints = [api.Taint(key=k, value=v, effect=e) for k, v, e in record[6]]
            return
        placement = record[6]
        obj.spec.node_selector = dict(placement["node_selector"])
        if placement["terms"]:
            obj.spec.node_affinity = api.NodeAffinity(
                required_terms=[
                    api.NodeSelectorTerm(
                        match_expressions=[
                            api.NodeSelectorRequirement(key=k, operator=op, values=list(values))
                            for k, op, values in term
                        ]
                    )
                    for term in placement["terms"]
                ]
            )
        obj.spec.tolerations = [
            api.Toleration(key=k, operator=op, value=v, effect=e) for k, op, v, e in placement["tolerations"]
        ]

    return place


def _compile_chunk(job):
    """Pool worker (program._compile_chunk over node-pool records): runs in a
    child that never needs the chip."""
    config_text, deployment, traffic, seed, clusters = job
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace

    from benchmark import pools_gen

    api = program_api()
    config = api.SimulationConfig.from_yaml(config_text)
    place = placer(api)
    cluster_events = pools_gen.to_events(pools_gen.cluster_records(deployment), api, place)
    return [
        compile_cluster_trace(
            cluster_events,
            pools_gen.to_events(pools_gen.workload_records(traffic, seed, c), api, place),
            config,
        )
        for c in clusters
    ]


class PoolsTracePool(program.TracePool):
    """program.TracePool with this module's worker: the same chunking, the
    same spawned pool held to the CPU, every worker ended when `result()` or
    `cancel()` returns."""

    def start(self) -> "PoolsTracePool":
        import concurrent.futures
        import multiprocessing

        if self.workers > 1:
            self.pool = concurrent.futures.ProcessPoolExecutor(
                max_workers=self.workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=program._hold_to_cpu,
            )
            self.futures = [self.pool.submit(_compile_chunk, job) for job in self.jobs]
        return self

    def result(self) -> List:
        if self.pool is None:
            return [trace for job in self.jobs for trace in _compile_chunk(job)]
        return super().result()


def cluster_counters(sim, cluster: int) -> Dict[str, int]:
    """program.cluster_counters with the two label-filter counters, a
    cluster's own leaves of the state."""
    out = program.cluster_counters(sim, cluster)
    affinity = sim.state.affinity
    out["affinity_attempts"] = int(affinity.attempts[cluster])
    out["affinity_attempts_refused"] = int(affinity.attempts_refused[cluster])
    return out


def pools_counters() -> Dict[str, int]:
    """The batch's label-filter counters and drain counters as the program's
    last `metrics_summary()` left them on its recorder ({} where it
    publishes none)."""
    from benchmark import program_spans

    found = program_spans._program()
    if found is None:
        return {}
    counters = found[0].counters
    return {k: int(counters[k]) for k in POOL_COUNTERS + ("cycle_overruns",) if k in counters}
