"""What the `batch_jobs_labelled` driver takes from the program, beside
benchmark/program.py (which no later PR edits): whether the program knows the
topology-spread plugin at all, its object types with the constraint type
among them, and the pool that compiles every cluster's LABELLED trace
(program.TracePool compiles traffic_gen's unlabelled records by name)."""

from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List

from benchmark import program


def knows_topology_spread() -> bool:
    """Whether the program has the `topology_spread` profile (a commit before
    PR 37 has not: it would refuse the configuration at the engine build, or,
    asked for the `default` profile, ignore every constraint)."""
    try:
        from kubernetriks_tpu.core.scheduler.kube_scheduler import NAMED_PROFILE_SPECS
    except ImportError:
        return False
    return "topology_spread" in NAMED_PROFILE_SPECS


def program_api() -> SimpleNamespace:
    from kubernetriks_tpu.core.types import TopologySpreadConstraint

    api = program.program_api()
    api.TopologySpreadConstraint = TopologySpreadConstraint
    return api


def constraint_object(api):
    """spread_gen's constraint record -> the program's object."""

    def make(constraint):
        max_skew, key, selector = constraint
        return api.TopologySpreadConstraint(
            max_skew=max_skew, topology_key=key, when_unsatisfiable="DoNotSchedule",
            match_labels=dict(selector),
        )

    return make


def _compile_chunk(job):
    """Pool worker (program._compile_chunk over labelled records): runs in a
    child that never needs the chip."""
    config_text, deployment, traffic, seed, clusters = job
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace

    from benchmark import spread_gen

    api = program_api()
    config = api.SimulationConfig.from_yaml(config_text)
    make = constraint_object(api)
    cluster_events = spread_gen.to_events(spread_gen.cluster_records(deployment), api)
    return [
        compile_cluster_trace(
            cluster_events,
            spread_gen.to_events(spread_gen.workload_records(deployment, traffic, seed, c), api, make),
            config,
        )
        for c in clusters
    ]


class LabelledTracePool(program.TracePool):
    """program.TracePool with this module's worker: the same chunking, the
    same spawned pool held to the CPU, every worker ended when `result()` or
    `cancel()` returns."""

    def start(self) -> "LabelledTracePool":
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


def spread_counters() -> Dict[str, int]:
    """The spread filter's counters as the program's last `metrics_summary()`
    left them on its recorder ({} where it has none)."""
    from benchmark import program_spans

    found = program_spans._program()
    if found is None:
        return {}
    counters = found[0].counters
    return {k: int(counters[k]) for k in ("spread_decisions", "spread_decisions_bound") if k in counters}
