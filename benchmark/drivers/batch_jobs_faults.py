"""Driver `batch_jobs_faults`: `batch_jobs` over clusters that LOSE NODES.

The same closed loop of whole jobs on one resident engine (one
`fleet_reset()`, one `step_until_time`, one fetch a job; the window ends with
the job that is running when `--seconds` is up), the same lines and counters
and the same `decisions_per_s` arithmetic as benchmark/drivers/batch_jobs.py.
It differs in where a cluster's node events and the reference come from:
every cluster carries its own schedule of node crashes and recoveries, drawn
before either side runs (benchmark/faults_gen.py) and handed to the program
already sampled, through its ordinary build; `correct` is decided against the
oracle copy fed plain removals and creations at the same instants
(benchmark/faults_reference.py), which counts crashes, recoveries and
interrupted pods itself. `batch_jobs` cannot serve such a cell: it and
benchmark/program.py call traffic_gen's fault-free node records by name.

A program that cannot take a sampled schedule, or that brings a recovered
node back on a fresh slot, is refused before JAX reaches for the chip
(`prepare`), with a non-zero exit.

`--control 1` puts two controls in the program's place: the same answers with
times held in float32 (as `batch_jobs` does), and the same workload with the
crashes DROPPED (cell 1's clusters, no node ever lost), one job, against the
same oracle runs: the share of sampled pods that land on another node is the
check's value.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import deployment, faults_gen, faults_program, faults_reference, program, reference, traffic_gen
from benchmark.drivers.batch_jobs import _assert_engaged, _engine_kwargs, _mesh
from benchmark.harness import say


def _config_text(cell) -> str:
    return deployment.config_yaml(cell.config_name, cell.config["deployment"])


def prepare(cell, seed: int):
    """Host-only work before JAX reaches for the chip: refuse a program that
    cannot run the configuration, then start the pool that generates and
    compiles every cluster's trace with its fault schedule."""
    refused = faults_program.why_not()
    if refused is not None:
        raise SystemExit(f"batch_jobs_faults: {cell.name} cannot run on this program: {refused}")
    n_clusters = int(cell.traffic["clusters_per_chip"]) * cell.chips
    return faults_program.FaultsTracePool(
        _config_text(cell), cell.config, cell.traffic, seed, n_clusters
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
    say(
        line="setup", clusters=n_clusters, nodes=sim.n_nodes, pods=sim.n_pods,
        formulation=sim.kernel_formulation(), decisions_per_job=int(expected.sum()),
        sim_seconds_per_job=job_end * n_clusters, event_chunk=int(sim.max_events_per_window),
        setup_spans_s={k: spans.total(k) for k in ("trace_generation", "engine_build", "first_dispatch")},
        since_process_start_s=time.perf_counter() - harness.process_t0,
    )

    stats_before = dict(sim.dispatch_stats)
    jobs, job_ends = [], []
    # The harness's own heap (1,250 compiled traces kept for the control) is
    # not the program's: keep a full collection of it out of the window.
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
    formulation = sim.kernel_formulation()
    if "events" in wanted and formulation.get("events") != wanted["events"]:
        raise SystemExit(
            f"batch_jobs_faults: events is {formulation.get('events')!r}, the cell asserts {wanted['events']!r}"
        )
    if int(sim.n_nodes) != int(cell.config["deployment"]["nodes"]):
        raise SystemExit(
            f"batch_jobs_faults: the engine holds {sim.n_nodes} node slots for the deployment's "
            f"{cell.config['deployment']['nodes']} nodes: a recovery took a fresh slot"
        )
    floor = int(wanted.get("min_decisions_per_cluster", 1))
    if int(expected.min()) < floor:
        raise SystemExit(f"batch_jobs_faults: a cluster committed {int(expected.min())} decisions, under {floor}")

    decisions = int(sum(int(j.sum()) for j in jobs))
    windows_per_job = int(sim.next_window_idx)
    harness.attempted = len(jobs)
    harness.failed = sum(1 for j in jobs if not np.array_equal(j, expected))
    harness.end_to_end[traffic.get("rate_metric", "decisions_per_s")] = decisions / harness.window_s
    harness.counters.update(
        jobs=len(jobs),
        decisions=decisions,
        decisions_last_job=int(jobs[-1].sum()),
        windows_stepped=windows_per_job * len(jobs),
        windows_per_job=windows_per_job,
        clusters=n_clusters,
        cycle_formulation=formulation["cycle"],
        event_formulation=formulation.get("events"),
        node_faults=1,
        event_chunk=int(sim.max_events_per_window),
        # what a job's event loop applies, a cluster: the trace's real events
        events_per_cluster=float(np.mean([int(np.isfinite(trace.ev_time).sum()) for trace in compiled])),
        nodes=int(sim.n_nodes),
        pods=int(sim.n_pods),
        max_pods_per_cycle=int(cell.config["engine"]["max_pods_per_cycle"]),
        dispatches_per_job=sum(
            stats[k] - stats_before[k] for k in ("window_chunks", "superspans", "stage_refills")
        )
        / len(jobs),
        sim_seconds_per_wall_second=job_end * n_clusters * len(jobs) / harness.window_s,
        # the last job's, as metrics_summary() published them (a reset state reads 0)
        **faults_program.fault_counters(),
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
        _control_crashes_dropped(harness, sample, job_end)


def _check_oracle(harness, sim, n_clusters, job_end):
    """batch_jobs._check_oracle with each cluster's fault schedule on both
    sides: the scalar oracle on a seeded sample of clusters, every pod's
    phase, node and start time, the terminal counters and the three fault
    counters, no pod lost, and the cell's own floor of crashes (a cluster)
    and interrupted pods (the sample together: one cluster in some hundreds
    loses 80 nodes and not one of them holds a pod). Returns the sample, (cluster, oracle run) pairs, for the
    control."""
    cell = harness.cell
    traffic, guarantees, wanted = cell.traffic, cell.config["guarantees"], cell.traffic["asserts"]
    if guarantees["reference"] != "oracle" or cell.chips != 1:
        raise SystemExit("batch_jobs_faults: the driver judges pods against the oracle on one chip")
    config_text = _config_text(cell)
    limits = (guarantees["counters_exact"], float(guarantees["start_time_tolerance_s"]))
    sample, interrupted = [], 0
    for c in traffic_gen.seeded_order(harness.seed, "clusters.shard0", n_clusters)[
        : int(guarantees["oracle_sample_clusters"])
    ]:
        workload = traffic_gen.workload_records(traffic, harness.seed, c)
        oracle = faults_reference.run_oracle(
            config_text, faults_gen.cluster_records(cell.config, harness.seed, c), workload, job_end
        )
        sample.append((c, oracle))
        view = program.normalized_pod_view(sim, c)
        counters = faults_program.cluster_counters(sim, c)
        harness.checks += reference.compare_pods(f"oracle.c{c}", view, counters, oracle, *limits)
        if guarantees.get("no_pod_lost"):
            harness.checks.append(
                reference.exactly(f"oracle.c{c}.pods_lost", len(workload) - counters["pods_succeeded"], 0)
            )
        least = int(wanted.get("min_node_crashes_per_cluster", 0))
        if counters["node_crashes"] < least:
            raise SystemExit(f"batch_jobs_faults: cluster {c} counts {counters['node_crashes']} node crashes, the cell asserts {least}")
        interrupted += counters["pod_interruptions"]
        say(line="faults", cluster=c, **{k: counters[k] for k in faults_reference.FAULT_COUNTERS})
        if harness.control:
            harness.control_checks += reference.compare_pods(
                f"oracle.c{c}", reference.in_float32(view), counters, oracle, *limits
            )
    least = int(wanted.get("min_pod_interruptions_in_sample", 0))
    if interrupted < least:
        raise SystemExit(f"batch_jobs_faults: the sampled clusters count {interrupted} interrupted pods, the cell asserts {least}")
    return sample


def _control_crashes_dropped(harness, sample, job_end) -> None:
    """The control that shows the fault path decides placements: the same
    workload on clusters that never lose a node (cell 1's traces: the
    program given no schedule), one job, against the same oracle runs."""
    cell = harness.cell
    guarantees = cell.config["guarantees"]
    limits = (guarantees["counters_exact"], float(guarantees["start_time_tolerance_s"]))
    n_clusters = int(cell.traffic["clusters_per_chip"]) * cell.chips
    config_text = _config_text(cell)
    compiled = program.TracePool(
        config_text, cell.config["deployment"], cell.traffic, harness.seed, n_clusters
    ).start().result()
    plain = program.build_engine(
        config_text, compiled, resettable=True, mesh=_mesh(harness), **_engine_kwargs(cell)
    )
    plain.step_until_time(job_end)
    for c, oracle in sample:
        view = program.normalized_pod_view(plain, c)
        checks = reference.compare_pods(
            f"crashes_dropped.c{c}", view, faults_program.cluster_counters(plain, c), oracle, *limits
        )
        harness.control_checks += checks
        moved = next(ch.value for ch in checks if ch.name.endswith("pods_on_another_node"))
        say(line="control_crashes_dropped", cluster=c, pods=len(view), pods_on_another_node=moved,
            share=moved / max(len(view), 1))
    plain.close()
