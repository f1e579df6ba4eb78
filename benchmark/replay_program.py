"""What the `trace_replay` driver takes from the program, beside
benchmark/program.py (which no later PR edits): the engine built from trace
FILES through the program's own entry, its whole-run timing statistics, and
what its recorder says of the ingestion."""

from __future__ import annotations

from typing import Dict, Optional

TRACE_CONFIG_YAML = """trace_config:
  alibaba_cluster_trace_v2017:
    machine_events_trace_path: {machine_events}
    batch_task_trace_path: {batch_task}
    batch_instance_trace_path: {batch_instance}
"""


def recorder_knows_trace_ingest() -> bool:
    """Whether the program records the `trace_ingest` span and its row
    counters (a commit before PR 28 does not: the cell cannot assert native
    ingestion there, nor rank heterogeneous nodes as the oracle does)."""
    try:
        from kubernetriks_tpu.telemetry.tracer import PHASE_NAMES
    except ImportError:
        return False
    return "trace_ingest" in PHASE_NAMES


def build_engine(config_text: str, paths: Dict[str, str], n_clusters: int, **engine_kwargs):
    """cli.build_batched_simulation over the trace files: native feeder,
    compile_from_arrays, BatchedSimulation; resettable as ScenarioFleet
    builds it (a neutral scenario keeps the pristine snapshot)."""
    from kubernetriks_tpu.batched.fleet import scenario_vectors
    from kubernetriks_tpu.cli import build_batched_simulation
    from kubernetriks_tpu.config import SimulationConfig

    config = SimulationConfig.from_yaml(config_text + TRACE_CONFIG_YAML.format(**paths))
    max_pods_per_cycle = int(engine_kwargs.pop("max_pods_per_cycle"))
    pod_window = int(engine_kwargs.pop("pod_window"))
    return build_batched_simulation(
        config, n_clusters, max_pods_per_cycle, pod_window,
        scenario=dict(scenario_vectors(config, n_clusters, None)), **engine_kwargs,
    )


def native_build_error() -> Optional[str]:
    from kubernetriks_tpu.trace import feeder

    return feeder.native_build_error()


def ingest_counters() -> Dict[str, int]:
    """Rows of batch_instance the program's ingestion read and dropped, as
    its recorder counted them (cumulative over the process)."""
    from kubernetriks_tpu.telemetry.tracer import recorder

    counters = recorder().counters
    return {
        "rows": int(counters.get("trace_ingest_rows", 0)),
        "dropped": int(counters.get("trace_ingest_rows_dropped", 0)),
    }


def timing_stats(sim) -> Dict[str, Dict[str, float]]:
    """min / max / mean / variance of queue time and pod duration over every
    pod of the run, slid out of the window or not (the collector's)."""
    return sim.metrics_summary()["timings"]
