"""What the `batch_jobs_kubescore` driver takes from the program, beside
benchmark/pools_program.py (which no later PR edits and whose placer knows
hard terms alone): whether the program knows the `kube_default` profile at
all, how a record's preferred terms go onto its pods, the pool that compiles
every cluster's trace, and the label filters' and label scorers' counters."""

from __future__ import annotations

from typing import Dict, List

from benchmark import pools_program, program

SCORE_COUNTERS = ("affinity_attempts", "affinity_attempts_refused", "soft_attempts", "soft_honoured")


def knows_kube_default() -> bool:
    """Whether the program has the `kube_default` profile (a commit before
    PR 50 has not: it refuses a preferred term and a PreferNoSchedule taint by
    name, and would rank by one scorer in float32)."""
    try:
        from kubernetriks_tpu.core.scheduler.kube_scheduler import NAMED_PROFILE_SPECS
    except ImportError:
        return False
    return "kube_default" in NAMED_PROFILE_SPECS


def program_api():
    from kubernetriks_tpu.core.types import PreferredSchedulingTerm

    api = pools_program.program_api()
    api.PreferredSchedulingTerm = PreferredSchedulingTerm
    return api


def placer(api):
    """kubescore_gen's `place`: pools_program's, then the pod's preferred
    terms (a pod with no required term states no required half)."""
    hard = pools_program.placer(api)

    def place(obj, record):
        hard(obj, record)
        if record[1] != "create_pod" or not record[6]["preferred"]:
            return
        if obj.spec.node_affinity is None:
            obj.spec.node_affinity = api.NodeAffinity(has_required=False)
        obj.spec.node_affinity.preferred = [
            api.PreferredSchedulingTerm(
                weight=weight,
                preference=api.NodeSelectorTerm(
                    match_expressions=[
                        api.NodeSelectorRequirement(key=k, operator=op, values=list(values))
                        for k, op, values in term
                    ]
                ),
            )
            for weight, term in record[6]["preferred"]
        ]

    return place


def _compile_chunk(job):
    """Pool worker (pools_program._compile_chunk over this mix's records):
    runs in a child that never needs the chip."""
    config_text, deployment, traffic, seed, clusters = job
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace

    from benchmark import kubescore_gen

    api = program_api()
    config = api.SimulationConfig.from_yaml(config_text)
    place = placer(api)
    cluster_events = kubescore_gen.to_events(kubescore_gen.cluster_records(deployment), api, place)
    return [
        compile_cluster_trace(
            cluster_events,
            kubescore_gen.to_events(kubescore_gen.workload_records(traffic, seed, c), api, place),
            config,
        )
        for c in clusters
    ]


class KubeScoreTracePool(program.TracePool):
    """program.TracePool with this module's worker, as
    pools_program.PoolsTracePool is one."""

    def start(self) -> "KubeScoreTracePool":
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
    """pools_program.cluster_counters with the two label-score counters, a
    cluster's own leaves of the state."""
    out = pools_program.cluster_counters(sim, cluster)
    metrics = sim.state.metrics
    out["soft_attempts"] = int(metrics.soft_attempts[cluster])
    out["soft_honoured"] = int(metrics.soft_honoured[cluster])
    return out


def published_counters() -> Dict[str, int]:
    """The batch's label counters and drain counters as the program's last
    `metrics_summary()` left them on its recorder ({} where it publishes
    none)."""
    from benchmark import program_spans

    found = program_spans._program()
    if found is None:
        return {}
    counters = found[0].counters
    return {k: int(counters[k]) for k in SCORE_COUNTERS + ("cycle_overruns",) if k in counters}
