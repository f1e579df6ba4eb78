"""kubernetriks_tpu.telemetry — the composed hot path's flight recorder.

Two synchronized halves (docs/DESIGN.md §"Telemetry"):

- **Host span recorder** (tracer.py): ONE process-wide, always-on ring
  (`recorder()`) of perf_counter_ns begin/end records over every engine
  and fleet phase — window chunks, the fused chunk+slide megastep,
  superspan dispatches, stage prefetch/assembly/upload, slides, window
  growth, checkpoint I/O, pump rounds, query lifecycles, compilations —
  each also a `ktpu:<phase>` event in a live jax.profiler session, with
  the async shift/progress readbacks modeled as flow events, exported as
  Chrome trace-event JSON (Perfetto) and an aggregated per-phase report
  (per engine: each engine writes through a handle that keeps its own
  aggregates); the benchmark's per-layer metrics read the shared ring.
- **Device metrics ring** (ring.py): per-window scheduling/autoscaler/
  fault aggregates accumulated inside ClusterBatchState and drained only
  at existing host sync boundaries, so telemetry-on adds zero new host
  syncs and stays bit-identical to telemetry-off on every simulation
  leaf.

Plus the capacity half (docs/DESIGN.md §10):

- **Capacity observatory** (observatory.py): reserve-occupancy series
  (the ring's hpa/ca/headroom gauge columns), host/device memory
  watermarks sampled at ring drains, and the saturation watchdog
  (`KTPU_WATCHDOG`) whose time-to-exhaustion estimates fire BEFORE the
  loud reserve bound.
- **Time-series export** (export.py): bounded JSONL drain records + an
  atomic Prometheus-textfile writer, fed strictly from drained host
  copies.

And the query half (docs/DESIGN.md §14, PR 17):

- **Latency histogram** (histogram.py): the log-bucketed streaming
  histogram (O(buckets), exact count/sum, ~5% relative resolution)
  behind the lane-async fleet's per-query latency stats, the
  observatory's `query_stats()`, and the native Prometheus
  `_bucket`/`_sum`/`_count` series — replacing every O(queries) host
  structure on the serving path.

The span recorder is always on; the device ring and the observatory are
enabled with `KTPU_TRACE=1` (or `BatchedSimulation(telemetry=True)`);
`engine.telemetry_report()` / `engine.write_chrome_trace()` /
`engine.drain_telemetry()` read it out, and `cli.py --report` prints it.
"""

from kubernetriks_tpu.telemetry.gauges import GaugeSeries
from kubernetriks_tpu.telemetry.histogram import LatencyHistogram
from kubernetriks_tpu.telemetry.tracer import (
    PHASE_NAMES,
    SpanTracer,
    log_chunk_throughput,
    recorder,
)

__all__ = [
    "GaugeSeries",
    "LatencyHistogram",
    "PHASE_NAMES",
    "SpanTracer",
    "log_chunk_throughput",
    "recorder",
]
