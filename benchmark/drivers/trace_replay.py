"""Driver `trace_replay`: a closed loop of whole jobs, each one replay of the
configuration's trace FILES by one resident engine.

The benchmark writes the three Alibaba-format CSVs from `--seed`
(benchmark/alibaba_gen.py). The program reads them through its normal path
(`cli.build_batched_simulation`: native feeder, `compile_from_arrays`,
streaming pod window); the reference reads THE SAME files through the
benchmark's own parser (benchmark/oracle/trace/alibaba.py) into the scalar
oracle. Neither side sees the other's objects. A job is the cluster from
t = 0 to `job_end_s`; between jobs the engine is reset by the program's own
`fleet_reset()`, inside the window, as `batch_jobs` does it, and the rate
divides by the window's real length.

The cell measures what it names or the run fails: exact node ranking,
superspans over a streaming feeder, a window that slides, native ingestion, K
never reached. Which scheduling-cycle formulation the engine's gates picked is
reported (`cycle_formulation`), not asserted: the rate judges it. `correct` is
decided after the window on the state the last job left
(benchmark/replay_reference.py).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import alibaba_gen, deployment, program, reference, replay_program, replay_reference
from benchmark.candidate_kernel_counts import KERNEL as CANDIDATE_KERNEL
from benchmark.harness import CHECKOUT, say
from benchmark.oracle.trace import alibaba as plain_parser


class _TraceFiles:
    """The run's CSVs under the checkout's ignored .bench_out/."""

    def __init__(self, cell, seed: int):
        root = os.path.join(CHECKOUT, ".bench_out")
        os.makedirs(root, exist_ok=True)
        self.dir = tempfile.mkdtemp(prefix=f"trace-{cell.name}-{seed}-", dir=root)
        self.paths = alibaba_gen.write_trace(self.dir, cell.config["deployment"], cell.config["trace"], seed)

    def result(self):
        return self.paths

    def cancel(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def prepare(cell, seed: int):
    """Host-only work before JAX reaches for the chip: refuse a program that
    cannot be held to this cell's asserts, then write the trace files."""
    if not replay_program.recorder_knows_trace_ingest():
        raise SystemExit(
            f"trace_replay: {cell.name} asserts native ingestion by the program's `trace_ingest` span and "
            "row counters, which this program does not record (a commit before PR 28)"
        )
    return _TraceFiles(cell, seed)


def _engine_kwargs(cell) -> dict:
    kwargs = {**cell.config["engine"], **cell.traffic.get("engine", {})}
    if cell.rehearsal:
        kwargs.update(program.rehearsal_kwargs())
    return kwargs


def _max_arrivals_per_cycle(workload_records, interval_s: float) -> int:
    cycles = np.floor(np.asarray([rec[0] for rec in workload_records]) / interval_s).astype(np.int64)
    return int(np.bincount(cycles - cycles.min()).max())


def _assert_engaged(sim, cell, stats, slides_per_job, ingest, arrivals, decisions_per_job) -> None:
    wanted = cell.traffic["asserts"]
    formulation = sim.kernel_formulation()
    facts = {
        "exact_node_ranking": formulation.get("ranking") == "exact",
        "superspans": stats["superspans"] > 0,
        "no_ladder_fallback": stats["ladder_fallbacks"] == 0 and stats["window_chunks"] == 0,
        "feeder_slabs": stats["feeder_slabs_produced"] > 0 and stats["stage_refills"] > 0,
        "min_window_slides_per_job": slides_per_job >= wanted["min_window_slides_per_job"],
        "native_ingestion": replay_program.native_build_error() is None
        and ingest["rows"] == alibaba_gen.valid_instances(cell.config["trace"])
        + alibaba_gen.dropped_instance_rows(cell.config["trace"])
        and ingest["dropped"] == alibaba_gen.dropped_instance_rows(cell.config["trace"]),
        "max_arrivals_per_cycle_times_2_under_k": 2 * arrivals < int(cell.config["engine"]["max_pods_per_cycle"]),
        "min_decisions_per_job": decisions_per_job >= wanted["min_decisions_per_job"],
    }
    for key, held in facts.items():
        if wanted.get(key) and not held:
            raise SystemExit(
                f"trace_replay: the cell asserts {key} = {wanted[key]!r}; formulation {formulation}, stats {stats}, "
                f"slides a job {slides_per_job}, ingestion {ingest} (native build error "
                f"{replay_program.native_build_error()!r}), most arrivals a cycle {arrivals}, "
                f"decisions a job {decisions_per_job}"
            )


def _count_candidate_launches(harness) -> None:
    """Launches of the candidate kernel in the traced window, per chip: the
    reduction keeps seconds by op name but not how many events made them."""
    from benchmark import trace_reduce

    events = trace_reduce.load_xplane(
        trace_reduce.find_xplane(harness._trace_dir), harness.cell.chips, cpu_rehearsal=harness.cell.rehearsal
    )
    launches = sum(1 for dev in events.devices for name, _, _ in dev if name.startswith(CANDIDATE_KERNEL))
    harness.counters["candidate_kernel_launches"] = launches / len(events.devices)


def run(harness) -> None:
    try:
        _run(harness)
    finally:
        harness.prepared.cancel()  # the trace files, whatever became of the run


def _run(harness) -> None:
    from kubernetriks_tpu.recompile import RecompileSentinel

    cell, spans = harness.cell, harness.spans
    dep, traffic, guarantees = cell.config["deployment"], cell.traffic, cell.config["guarantees"]
    n_clusters = int(traffic["clusters_per_chip"]) * cell.chips
    job_end = float(traffic["job_end_s"])
    config_text = deployment.config_yaml(cell.config_name, dep)

    sentinel = RecompileSentinel("raise").install()
    with spans.span("trace_generation"):
        paths = harness.prepared.result()
    ingest_before = replay_program.ingest_counters()
    with spans.span("engine_build"):
        sim = replay_program.build_engine(config_text, paths, n_clusters, **_engine_kwargs(cell))
    pod_window_built = int(sim.pod_window)
    ingest = {k: v - ingest_before[k] for k, v in replay_program.ingest_counters().items()}
    harness.counters.update(trace_ingest_rows=ingest["rows"], trace_ingest_rows_dropped=ingest["dropped"])

    def job():
        with spans.span("reset"):
            sim.fleet_reset()
        with spans.span("dispatch"):
            sim.step_until_time(job_end)
        with spans.span("fetch"):
            return program.decisions_per_cluster(sim)

    with spans.span("first_dispatch"):
        # the first job grows the pod window to what the trace's live span
        # needs and compiles the grown programs; the first job after a reset
        # compiles `reset` and the superspan once more (PERF.md, finding 3),
        # so the mix warms with two
        for _ in range(int(traffic.get("warmup_jobs", 1))):
            expected = job()
    harness.counters["compiles_in_setup"] = len(sentinel.events)
    sentinel.seal("benchmark warm-up: trace ingestion, engine build, reset and whole jobs")
    say(
        line="setup", clusters=n_clusters, nodes=sim.n_nodes, pods=sim.n_pods,
        pod_window_built=pod_window_built, pod_window=int(sim.pod_window),
        formulation=sim.kernel_formulation(), decisions_per_job=int(expected.sum()),
        sim_seconds_per_job=job_end * n_clusters, ingestion=ingest,
        native_build_error=replay_program.native_build_error(),
        setup_spans_s={k: spans.total(k) for k in ("trace_generation", "engine_build", "first_dispatch")},
        since_process_start_s=time.perf_counter() - harness.process_t0,
    )

    stats_before = dict(sim.dispatch_stats)
    jobs, job_ends = [], []
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
    if harness.tracing:
        _count_candidate_launches(harness)

    stats = dict(sim.dispatch_stats)
    grew = {k: stats[k] - stats_before[k] for k in stats}
    # a slide of the pod window: a slide-span a superspan completed on the
    # device, a slide fused into a ladder chunk, or a host slide of its own
    slides_per_job = (grew["superspan_spans"] + grew["fused_slides"] + grew["slide_dispatches"]) / len(jobs)
    t_parse = time.perf_counter()
    workload_records, parsed = plain_parser.workload_records(paths["batch_instance"], paths["batch_task"])
    cluster_records = plain_parser.cluster_records(paths["machine_events"])
    parse_s = time.perf_counter() - t_parse
    arrivals = _max_arrivals_per_cycle(workload_records, float(dep["scheduling_cycle_interval_s"]))
    _assert_engaged(sim, cell, stats, slides_per_job, ingest, arrivals, int(expected.sum()))

    decisions = int(sum(int(j.sum()) for j in jobs))
    formulation = sim.kernel_formulation()
    windows_per_job = int(sim.next_window_idx)
    harness.attempted = len(jobs)
    harness.failed = sum(1 for j in jobs if not np.array_equal(j, expected))
    harness.end_to_end[traffic["rate_metric"]] = decisions / harness.window_s
    harness.counters.update(
        jobs=len(jobs),
        decisions=decisions,
        windows_stepped=windows_per_job * len(jobs),
        windows_per_job=windows_per_job,
        clusters=n_clusters,
        cycle_formulation=formulation["cycle"],
        node_ranking=formulation["ranking"],
        nodes=int(sim.n_nodes),
        pods=int(sim.n_pods),
        pod_window_built=pod_window_built,
        pod_window=int(sim.pod_window),
        max_pods_per_cycle=int(cell.config["engine"]["max_pods_per_cycle"]),
        dispatches_per_job=sum(grew[k] for k in ("window_chunks", "superspans", "stage_refills")) / len(jobs),
        slides_per_job=slides_per_job,
        max_arrivals_per_cycle=arrivals,
        sim_seconds_per_wall_second=job_end * n_clusters * len(jobs) / harness.window_s,
    )
    job_s = [b - a for a, b in zip([0.0] + job_ends, job_ends)]
    say(line="window", jobs=len(jobs), window_s=harness.window_s, decisions=decisions, job_s=job_s,
        sim_seconds_per_wall_second=harness.counters["sim_seconds_per_wall_second"],
        dispatch_stats=stats, dispatch_stats_in_window=grew)
    harness.checks.append(
        reference.exactly(
            "jobs_with_other_decisions", harness.failed, 0,
            f"{len(jobs)} jobs, {int(expected.sum())} decisions each",
        )
    )
    harness.checks.append(
        reference.exactly("ingestion.rows_dropped", ingest["dropped"], parsed["dropped"],
                          f"program read {ingest['rows']} rows, the plain parser {parsed['rows']}")
    )

    oracle = replay_reference.run_oracle(config_text, cluster_records, workload_records, job_end)
    harness.counters["reference_s"] = parse_s + oracle.seconds
    label = "oracle.c0"
    counters = program.cluster_counters(sim, 0)
    view = program.normalized_pod_view(sim, 0)
    timings = replay_program.timing_stats(sim)
    limits = float(guarantees["start_time_tolerance_s"])

    def compare(into, view, timings):
        into += [
            reference.exactly(f"{label}.{name}", counters[name], oracle.run.counters[name])
            for name in guarantees["counters_exact"]
        ]
        into += replay_reference.compare_resident_pods(label, view, oracle.run, limits)
        into += replay_reference.compare_timings(
            label, timings, oracle.timings, guarantees["timing_stats"], guarantees["timing_stats_rtol"]
        )

    compare(harness.checks, view, timings)
    if harness.control:
        compare(harness.control_checks, reference.in_float32(view), timings)
    say(line="reference", seconds=harness.counters["reference_s"], oracle_s=oracle.seconds, parse_s=parse_s,
        resident_pods=len(view), pods=len(oracle.run.succeeded), oracle_timings=oracle.timings,
        program_timings={k: timings[k] for k in guarantees["timing_stats"]})
    sim.close()
