"""Driver `batch_jobs_kubescore`: `batch_jobs_pools` over clusters ranked as
kube-scheduler ranks them.

The same closed loop of whole jobs on one resident engine (one
`fleet_reset()`, one `step_until_time`, one fetch a job; the window ends with
the job that is running when `--seconds` is up), the same lines and counters
and the same `decisions_per_s` arithmetic as
benchmark/drivers/batch_jobs_pools.py, which no later PR edits and which calls
its own generator, program hook and reference by name. This one differs in
those three: pods carry PREFERRED node affinity terms beside the hard ones and
a pool may be tainted `PreferNoSchedule` (benchmark/kubescore_gen.py), and
`correct` is decided against the oracle copy with an independent
implementation of the three filters and the four integer scorers installed
(benchmark/kubescore_reference.py), which also counts the two label-filter
and the two label-score counters at the scheduler.

A program that does not know the `kube_default` profile is refused before JAX
reaches for the chip (`prepare`), with a non-zero exit.

`--control 1` puts two controls in the program's place: the same answers with
times held in float32 (as `batch_jobs` does), and the same traces run under
the `node_pools` filters with the reference's one float scorer
(`LeastAllocatedResources`: the soft halves then carry no weight), and the
share of pods that land on another node is the check's value.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import deployment, kubescore_gen, kubescore_program, kubescore_reference, program, reference, traffic_gen
from benchmark.drivers.batch_jobs import _assert_engaged, _engine_kwargs, _mesh
from benchmark.harness import say


def _config_text(cell, profile=None) -> str:
    dep = dict(cell.config["deployment"])
    if profile is not None:
        dep["scheduler_profile"] = profile
    return deployment.config_yaml(cell.config_name, dep)


def prepare(cell, seed: int):
    """Host-only work before JAX reaches for the chip: refuse a program that
    cannot run the configuration, then start the pool that generates and
    compiles every cluster's trace."""
    if not kubescore_program.knows_kube_default():
        raise SystemExit(
            f"batch_jobs_kubescore: {cell.name} runs the `kube_default` scheduler profile, which this "
            "program does not know (a commit before PR 50)"
        )
    n_clusters = int(cell.traffic["clusters_per_chip"]) * cell.chips
    return kubescore_program.KubeScoreTracePool(
        _config_text(cell), cell.config["deployment"], cell.traffic, seed, n_clusters
    ).start()


def run(harness) -> None:
    from kubernetriks_tpu.recompile import RecompileSentinel

    cell, spans = harness.cell, harness.spans
    traffic = cell.traffic
    n_clusters = int(traffic["clusters_per_chip"]) * cell.chips
    job_end = float(traffic["job_end_s"])
    config_text = _config_text(cell)

    sentinel = RecompileSentinel("raise").install()
    with spans.span("trace_generation"):
        compiled = harness.prepared.result()
    with spans.span("engine_build"):
        sim = program.build_engine(
            config_text, compiled, resettable=True, mesh=_mesh(harness), **_engine_kwargs(cell)
        )

    def job():
        with spans.span("reset"):
            sim.fleet_reset()
        with spans.span("dispatch"):
            sim.step_until_time(job_end)
        with spans.span("fetch"):
            return program.decisions_per_cluster(sim)

    with spans.span("first_dispatch"):
        for _ in range(int(traffic.get("warmup_jobs", 1))):
            expected = job()
    harness.counters["compiles_in_setup"] = len(sentinel.events)
    sentinel.seal("benchmark warm-up: engine build, reset and one whole job")
    formulation = sim.kernel_formulation()
    say(
        line="setup", clusters=n_clusters, nodes=sim.n_nodes, pods=sim.n_pods,
        formulation=formulation, decisions_per_job=int(expected.sum()),
        sim_seconds_per_job=job_end * n_clusters,
        setup_spans_s={k: spans.total(k) for k in ("trace_generation", "engine_build", "first_dispatch")},
        since_process_start_s=time.perf_counter() - harness.process_t0,
    )

    stats_before = dict(sim.dispatch_stats)
    jobs, job_ends = [], []
    # The harness's own heap (1,250 compiled traces kept for the controls)
    # is not the program's: keep a full collection of it out of the window.
    gc.collect()
    gc.freeze()
    with harness.window():
        t0 = time.perf_counter()
        while True:
            jobs.append(job())
            job_ends.append(time.perf_counter() - t0)
            if job_ends[-1] >= harness.window_seconds:
                break
    sentinel.check("the measured window")
    sentinel.uninstall()
    harness.counters["memory_peak_bytes"] = harness.memory_peak_bytes()

    stats = dict(sim.dispatch_stats)
    totals = sim.metrics_summary()["counters"]
    wanted = traffic["asserts"]
    _assert_engaged(sim, traffic, stats, totals, slid=getattr(sim, "_pod_base", 0) > 0)
    if "ranking" in wanted and formulation.get("ranking") != wanted["ranking"]:
        raise SystemExit(
            f"batch_jobs_kubescore: ranking is {formulation.get('ranking')!r}, the cell asserts {wanted['ranking']!r}"
        )
    floor = int(wanted.get("min_decisions_per_cluster", 1))
    if int(expected.min()) < floor:
        raise SystemExit(f"batch_jobs_kubescore: a cluster committed {int(expected.min())} decisions, under {floor}")
    # the last job's, as metrics_summary() published them (a reset state reads 0)
    published = kubescore_program.published_counters()
    waited = published.get("affinity_attempts_refused", 0)
    if waited < int(wanted.get("min_pods_that_waited", 0)):
        raise SystemExit(
            f"batch_jobs_kubescore: {waited} attempts of the batch found their pool full, the cell asserts "
            f"{wanted['min_pods_that_waited']}: no pod waited on a full pool"
        )
    attempts, honoured = published.get("soft_attempts", 0), published.get("soft_honoured", 0)
    if wanted.get("soft_honoured_strictly_between") and not 0 < honoured < attempts:
        raise SystemExit(
            f"batch_jobs_kubescore: soft_honoured is {honoured} of soft_attempts {attempts} over the batch, the "
            "cell asserts strictly between: a preference must be honoured somewhere and lost somewhere"
        )
    if "cycle_overruns" in wanted and published.get("cycle_overruns") != int(wanted["cycle_overruns"]):
        raise SystemExit(
            f"batch_jobs_kubescore: cycle_overruns is {published.get('cycle_overruns')}, the cell asserts "
            f"{wanted['cycle_overruns']}"
        )

    decisions = int(sum(int(j.sum()) for j in jobs))
    windows_per_job = int(sim.next_window_idx)
    harness.attempted = len(jobs)
    harness.failed = sum(1 for j in jobs if not np.array_equal(j, expected))
    harness.end_to_end[traffic.get("rate_metric", "decisions_per_s")] = decisions / harness.window_s
    harness.counters.update(
        jobs=len(jobs),
        decisions=decisions,
        windows_stepped=windows_per_job * len(jobs),
        windows_per_job=windows_per_job,
        clusters=n_clusters,
        cycle_formulation=formulation["cycle"],
        ranking=formulation.get("ranking"),
        nodes=int(sim.n_nodes),
        pods=int(sim.n_pods),
        max_pods_per_cycle=int(cell.config["engine"]["max_pods_per_cycle"]),
        affinity_terms=int(sim.state.affinity.pod_terms.shape[1]),
        soft_terms=int(sim.state.affinity.pod_soft_terms.shape[1]),
        soft_taints=int(sim._cycle_profile.soft_taints),
        score_units=list(sim._cycle_profile.units),
        dispatches_per_job=sum(
            stats[k] - stats_before[k] for k in ("window_chunks", "superspans", "stage_refills")
        )
        / len(jobs),
        sim_seconds_per_wall_second=job_end * n_clusters * len(jobs) / harness.window_s,
        **published,
    )
    job_s = [b - a for a, b in zip([0.0] + job_ends, job_ends)]
    say(line="window", jobs=len(jobs), window_s=harness.window_s, decisions=decisions, job_s=job_s,
        sim_seconds_per_wall_second=harness.counters["sim_seconds_per_wall_second"],
        dispatch_stats=stats)
    harness.checks.append(
        reference.exactly(
            "jobs_with_other_decisions", harness.failed, 0,
            f"{len(jobs)} jobs, {int(expected.sum())} decisions each",
        )
    )

    t_ref = time.perf_counter()
    sample = _check_oracle(harness, sim, n_clusters, job_end)
    harness.counters["reference_s"] = time.perf_counter() - t_ref
    sim.close()
    if harness.control:
        _control_default_profile(harness, compiled, sample, job_end)


def _check_oracle(harness, sim, n_clusters, job_end):
    """batch_jobs_pools._check_oracle with this mix's records and reference:
    the scalar oracle on a seeded sample of clusters, every pod's phase, node
    and start time, the terminal counters, the two label-filter and the two
    label-score counters. Returns the sample, (cluster, oracle run) pairs,
    for the controls."""
    cell = harness.cell
    dep, traffic, guarantees = cell.config["deployment"], cell.traffic, cell.config["guarantees"]
    if guarantees["reference"] != "oracle" or cell.chips != 1:
        raise SystemExit("batch_jobs_kubescore: the driver judges pods against the oracle on one chip")
    oracle_config = _config_text(cell, profile="default")  # the installed algorithm is the profile
    cluster_records = kubescore_gen.cluster_records(dep)
    limits = (guarantees["counters_exact"], float(guarantees["start_time_tolerance_s"]))
    sample = []
    for c in traffic_gen.seeded_order(harness.seed, "clusters.shard0", n_clusters)[
        : int(guarantees["oracle_sample_clusters"])
    ]:
        oracle = kubescore_reference.run_oracle(
            oracle_config, cluster_records, kubescore_gen.workload_records(traffic, harness.seed, c), job_end
        )
        sample.append((c, oracle))
        view = program.normalized_pod_view(sim, c)
        counters = kubescore_program.cluster_counters(sim, c)
        harness.checks += reference.compare_pods(f"oracle.c{c}", view, counters, oracle, *limits)
        say(line="kubescore", cluster=c, pods_pending=sum(1 for row in view.values() if row[0] == "unschedulable"),
            **{k: counters[k] for k in kubescore_reference.SCORE_COUNTERS})
        if harness.control:
            harness.control_checks += reference.compare_pods(
                f"oracle.c{c}", reference.in_float32(view), counters, oracle, *limits
            )
    return sample


def _control_default_profile(harness, compiled, sample, job_end) -> None:
    """The control that shows the scorers decide placements: the same pods
    and nodes with the soft halves taken off (a `node_pools` build refuses a
    preference by name, so the control compiles the records anew without
    them) under `node_pools`, one job, against the same oracle runs."""
    import copy

    cell = harness.cell
    guarantees = cell.config["guarantees"]
    terminal = [name for name in guarantees["counters_exact"] if name not in kubescore_reference.SCORE_COUNTERS]
    dep = copy.deepcopy(cell.config["deployment"])
    for pool in dep["pools"]:
        pool["taints"] = [t for t in pool["taints"] if t["effect"] != "PreferNoSchedule"]
    traffic = copy.deepcopy(cell.traffic)
    for cls in traffic["classes"]:
        cls.pop("preferred_terms", None)
    config_text = _config_text(cell, profile="node_pools")
    clusters = [c for c, _ in sample]
    hard = {
        c: trace
        for c, trace in zip(
            clusters, kubescore_program._compile_chunk((config_text, dep, traffic, harness.seed, clusters))
        )
    }
    plain = program.build_engine(
        config_text, [hard[c] for c in clusters], resettable=True, mesh=_mesh(harness), **_engine_kwargs(cell)
    )
    plain.step_until_time(job_end)
    for lane, (c, oracle) in enumerate(sample):
        view = program.normalized_pod_view(plain, lane)
        checks = reference.compare_pods(
            f"node_pools_profile.c{c}", view, program.cluster_counters(plain, lane), oracle, terminal,
            float(guarantees["start_time_tolerance_s"]),
        )
        harness.control_checks += checks
        moved = next(ch.value for ch in checks if ch.name.endswith("pods_on_another_node"))
        say(line="control_node_pools_profile", cluster=c, pods=len(view), pods_on_another_node=moved,
            share=moved / max(len(view), 1))
    plain.close()
