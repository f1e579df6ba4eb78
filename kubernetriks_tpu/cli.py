"""CLI entry point (reference: src/main.rs).

Usage: python -m kubernetriks_tpu.cli --config-file <yaml>
           [--backend scalar|batched] [--clusters N] [--gauge-csv <path>]

Loads the config, selects the trace source (alibaba XOR generic, asserted like
the reference at main.rs:62-65), builds the simulation, runs until all pods
finish, and prints metrics.

--backend batched runs the vectorized JAX path: N identical clusters stepped
in lockstep on the accelerator. Alibaba traces with the native C++ feeder
available go CSV -> dense arrays -> compile_from_arrays without ever
materializing per-event Python objects (the object-free fast path).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.metrics.printer import print_metrics
from kubernetriks_tpu.sim.callbacks import RunUntilAllPodsAreFinishedCallbacks
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.trace.interface import EmptyTrace


def setup_logging(config: SimulationConfig) -> None:
    """Level from KUBERNETRIKS_LOG (RUST_LOG equivalent), optional rotating
    file sink — 50 files x 100 MiB like the reference's FileRotate
    (reference: main.rs:33-50)."""
    from logging.handlers import RotatingFileHandler

    from kubernetriks_tpu.flags import flag_str

    level = (flag_str("KUBERNETRIKS_LOG") or "INFO").upper()
    if config.logs_filepath:
        # The reference logs EXCLUSIVELY to the rotating file when a path is
        # configured (main.rs:40-47) — no console duplicate.
        os.makedirs(os.path.dirname(config.logs_filepath) or ".", exist_ok=True)
        handlers = [
            RotatingFileHandler(
                config.logs_filepath,
                maxBytes=100 * 1024 * 1024,
                backupCount=50,
            )
        ]
    else:
        handlers = [logging.StreamHandler()]
    logging.basicConfig(
        level=getattr(logging, level, logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
        handlers=handlers,
        force=True,
    )


def build_traces(config: SimulationConfig):
    trace_config = config.trace_config
    if trace_config is None:
        return EmptyTrace(), EmptyTrace()
    alibaba = trace_config.alibaba_cluster_trace_v2017
    generic = trace_config.generic_trace
    assert (alibaba is None) != (generic is None), (
        "Exactly one of alibaba_cluster_trace_v2017 or generic_trace must be set"
    )
    if generic is not None:
        from kubernetriks_tpu.trace.generic import (
            GenericClusterTrace,
            GenericWorkloadTrace,
        )

        return (
            GenericClusterTrace.from_file(generic.cluster_trace_path),
            GenericWorkloadTrace.from_file(generic.workload_trace_path),
        )
    from kubernetriks_tpu.trace import feeder

    if feeder.native_available():
        cluster_cls = feeder.NativeAlibabaClusterTrace
        workload_cls = feeder.NativeAlibabaWorkloadTrace
    else:
        logging.getLogger(__name__).info(
            "native trace feeder unavailable (%s); using the Python parser",
            feeder.native_build_error(),
        )
        from kubernetriks_tpu.trace.alibaba import (
            AlibabaClusterTraceV2017,
            AlibabaWorkloadTraceV2017,
        )

        cluster_cls = AlibabaClusterTraceV2017
        workload_cls = AlibabaWorkloadTraceV2017

    cluster = (
        cluster_cls.from_file(alibaba.machine_events_trace_path)
        if alibaba.machine_events_trace_path
        else EmptyTrace()
    )
    workload = workload_cls.from_files(
        alibaba.batch_instance_trace_path, alibaba.batch_task_trace_path
    )
    return cluster, workload


def build_batched_simulation(
    config: SimulationConfig,
    n_clusters: int,
    max_pods_per_cycle: int = 0,
    pod_window: int = 0,
    **engine_kwargs,
):
    """Build a BatchedSimulation from the config's trace source.

    Alibaba + native feeder: CSVs parse natively into dense arrays and
    compile via compile_from_arrays — no per-event Python objects on the
    multi-million-row pod axis. Otherwise: the object-based trace path.
    engine_kwargs pass through to the BatchedSimulation constructor
    (e.g. ca_slot_multiplier, use_pallas, mesh).
    """
    from kubernetriks_tpu.batched.engine import (
        BatchedSimulation,
        build_batched_from_traces,
    )
    from kubernetriks_tpu.batched.trace_compile import compile_from_arrays
    from kubernetriks_tpu.trace import feeder

    # 0 = auto: bound each scheduling cycle's work at 256 pods (the scalar
    # path drains the queue unboundedly, reference scheduler.rs:261; the
    # batched path defers overflow to the next cycle — SURVEY §7 "bounded
    # lax.scan microcycles"). Exact-drain runs pass the pod count explicitly.
    # The bound applies identically on every trace/build path so a config
    # simulates the same regardless of native-feeder availability (the engine
    # clamps the slice to the pod-slot count when it is smaller).
    kwargs = {"max_pods_per_cycle": max_pods_per_cycle or 256}
    if pod_window:
        kwargs["pod_window"] = pod_window
    kwargs.update(engine_kwargs)

    trace_config = config.trace_config
    alibaba = trace_config.alibaba_cluster_trace_v2017 if trace_config else None
    if alibaba is not None and feeder.native_available():
        from kubernetriks_tpu.chaos import has_node_faults

        if has_node_faults(config.fault_injection):
            # Node crash/recover events are injected at trace compile time
            # (chaos.inject_node_faults); the native array fast path skips
            # that stage. Pod-level faults (engine-side draws) still work.
            raise ValueError(
                "node-level fault injection is not supported on the "
                "alibaba native-feeder path — use the generic trace path "
                "or set fault_injection.node.mttf to 0 (pod-level faults "
                "are unaffected)"
            )
        from kubernetriks_tpu.telemetry.tracer import PH_TRACE_INGEST, recorder

        rec = recorder()
        t0 = rec.begin(PH_TRACE_INGEST)
        try:
            workload_arrays = feeder.load_workload_arrays(
                alibaba.batch_instance_trace_path, alibaba.batch_task_trace_path
            )
            cluster_arrays = (
                feeder.load_cluster_arrays(alibaba.machine_events_trace_path)
                if alibaba.machine_events_trace_path
                else None
            )
            compiled = compile_from_arrays(cluster_arrays, workload_arrays, config)
        finally:
            rec.end(PH_TRACE_INGEST, t0)
        rec.count("trace_ingest_rows", workload_arrays.rows_read)
        rec.count(
            "trace_ingest_rows_dropped",
            workload_arrays.rows_read - len(workload_arrays.start_ts),
        )
        return BatchedSimulation(config, [compiled] * n_clusters, **kwargs)
    cluster_trace, workload_trace = build_traces(config)
    return build_batched_from_traces(
        config,
        cluster_trace.convert_to_simulator_events(),
        workload_trace.convert_to_simulator_events(),
        n_clusters=n_clusters,
        **kwargs,
    )


def run_batched(config: SimulationConfig, args) -> int:
    import time

    from kubernetriks_tpu.compile_cache import place_compile_cache

    place_compile_cache()
    sim = build_batched_simulation(
        config, args.clusters, args.max_pods_per_cycle, args.pod_window
    )
    logging.getLogger(__name__).info(
        "batched run: %d clusters x %d node slots x %d pod slots (pallas=%s)",
        sim.n_clusters, sim.n_nodes, sim.n_pods, sim.use_pallas,
    )
    if args.metrics_export:
        # Capacity-observatory time-series export: every telemetry-ring
        # drain appends a JSONL record (occupancy gauges, memory
        # watermarks, watchdog verdicts); the final report lands as a
        # Prometheus textfile next to it. Requires the flight recorder
        # (KTPU_TRACE=1) — attach_metrics_exporter raises otherwise.
        from kubernetriks_tpu.telemetry.export import JsonlExporter

        sim.attach_metrics_exporter(JsonlExporter(args.metrics_export + ".jsonl"))
    sim.collect_gauges = bool(args.gauge_csv)
    t0 = time.perf_counter()
    sim.run_to_completion()
    elapsed = time.perf_counter() - t0
    if args.gauge_csv:
        sim.write_gauge_csv(args.gauge_csv)
    summary = sim.metrics_summary()
    decisions = summary["counters"]["scheduling_decisions"]
    logging.getLogger(__name__).info(
        "Processed %d scheduling decisions in %.2fs (%.0f decisions/s)",
        decisions, elapsed, decisions / max(elapsed, 1e-9),
    )
    from kubernetriks_tpu.metrics.render import render_metrics, render_telemetry

    print(render_metrics(summary, args.report or "json"))
    if sim._telemetry:
        # Flight recorder was armed (KTPU_TRACE=1): emit the telemetry
        # report in the same format and write the Perfetto trace. ONE
        # report serves both the render and the Prometheus textfile (a
        # second call would only force a redundant drain).
        # Read the op-to-phase map of each program this engine dispatched
        # first (on demand, nothing is compiled for it), so that the
        # report's `device_phases` counts the programs' instructions a phase.
        sim.tracer.program_phases()
        telemetry_rep = sim.telemetry_report()
        print(render_telemetry(telemetry_rep, args.report or "json"))
        from kubernetriks_tpu.flags import flag_str

        trace_path = (flag_str("KTPU_TRACE_PATH") or "ktpu_trace") + ".json"
        sim.write_chrome_trace(trace_path)
        logging.getLogger(__name__).info(
            "wrote Chrome trace (Perfetto-loadable) to %s", trace_path
        )
        if args.metrics_export:
            from kubernetriks_tpu.telemetry.export import (
                write_prometheus_textfile,
            )

            prom = write_prometheus_textfile(
                args.metrics_export + ".prom", telemetry_rep
            )
            logging.getLogger(__name__).info(
                "wrote observatory metrics to %s.jsonl and %s",
                args.metrics_export, prom,
            )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="kubernetriks-tpu simulator")
    parser.add_argument("--config-file", required=True, help="Path to YAML config")
    parser.add_argument(
        "--backend",
        choices=("scalar", "batched"),
        default="scalar",
        help="scalar event-loop oracle or the vectorized JAX path",
    )
    parser.add_argument(
        "--clusters",
        type=int,
        default=1,
        help="batched backend: number of identical clusters to step in lockstep",
    )
    parser.add_argument(
        "--max-pods-per-cycle",
        type=int,
        default=0,
        help="batched backend: per-cycle scheduling work bound (0 = auto)",
    )
    parser.add_argument(
        "--pod-window",
        type=int,
        default=0,
        help="batched backend: sliding pod-slot window size (0 = whole trace "
        "resident; set to ~2x peak pod concurrency to stream long traces)",
    )
    parser.add_argument(
        "--profile",
        default=None,
        help="Scheduler profile: a named profile (default, best_fit, "
        "balanced_packing) overriding the config's scheduler_profile "
        "block. Both backends honor it; the batched backend compiles it "
        "into the scan/Pallas decision kernels and fails loudly on a "
        "profile it cannot lower.",
    )
    parser.add_argument(
        "--gauge-csv",
        default=None,
        help="Path for the 5s gauge-metrics CSV (off by default)",
    )
    parser.add_argument(
        "--metrics-export",
        default=None,
        help="batched backend: capacity-observatory export stem — drain "
        "records append to <stem>.jsonl (bounded rotation) and the final "
        "telemetry report is written as <stem>.prom (Prometheus "
        "textfile). Requires the flight recorder (KTPU_TRACE=1).",
    )
    parser.add_argument(
        "--report",
        choices=("json", "table"),
        default=None,
        help="End-of-run report format for BOTH backends (one rendering "
        "path, metrics/render.py). Default: the legacy behavior — JSON, "
        "or the config's metrics_printer format on the scalar backend.",
    )
    args = parser.parse_args(argv)

    config = SimulationConfig.from_file(args.config_file)
    setup_logging(config)
    if args.profile is not None:
        # --profile supersedes the config's scheduler_profile block for
        # BOTH backends (the scalar simulator parses it through the same
        # spec parser; the batched engine compiles it).
        import dataclasses

        config = dataclasses.replace(config, scheduler_profile=args.profile)
    if args.report is not None:
        # --report supersedes the config's metrics_printer block; nulling
        # it here keeps the run-loop callbacks from ALSO printing the
        # configured report (one report, in the CLI-chosen format).
        import dataclasses

        config = dataclasses.replace(config, metrics_printer=None)

    if args.backend == "batched":
        return run_batched(config, args)

    cluster_trace, workload_trace = build_traces(config)
    sim = KubernetriksSimulation(config, gauge_csv_path=args.gauge_csv)
    sim.initialize(cluster_trace, workload_trace)
    sim.run_with_callbacks(RunUntilAllPodsAreFinishedCallbacks())
    if args.report is not None:
        # Explicit format: render through the shared path regardless of
        # the config's metrics_printer block (batched runs honor the same
        # flag, so both backends emit the same schema both ways).
        from kubernetriks_tpu.metrics.printer import metrics_as_dict
        from kubernetriks_tpu.metrics.render import render_metrics

        print(render_metrics(metrics_as_dict(sim.metrics_collector), args.report))
    elif config.metrics_printer is None:
        print_metrics(sim.metrics_collector, None)
    sim.metrics_collector.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
