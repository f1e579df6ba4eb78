# ktpu: hot-path
"""Host-side span recorder: the flight recorder's wall-clock half.

ONE recorder per process (`recorder()`), always on: every engine and
fleet writes to the same ring, whatever `telemetry=` / `KTPU_TRACE` say
(that switch keeps its meaning for what changes compiled programs: the
device ring, the observatory, the watchdog). A span is `begin(phase)` —
one `jax.profiler.TraceAnnotation` named `ktpu:<phase>` (a no-op TraceMe
unless a profiler session is live, then an event on the xplane's host
plane, on the device trace's clock) and one `time.perf_counter_ns()`
read — and `end(phase, t0, ident=...)`: one row `[t0, dur, phase, id]`
of a preallocated int64 ring plus three aggregate updates. About a
microsecond a span (tests/test_telemetry.py gates it; PERF.md has the
chip host's reading). The clock is `benchmark/spans.py`'s, so a reader
cuts the ring to a measured window. Spans nest by interval containment
on the one engine thread; the feeder thread never writes here. The id says what a row belongs to: the query id on `query_*`, the
pump round on `pump` and its children, the superspan ordinal on
`superspan`, `progress_wait` and the stage spans, the ordinal into
`compiles` on `compile`. Spans given an explicit `dur` after the fact
(`query_*`, `compile`, the feeder stalls) are ring-only. Flow events
model the engine's ASYNC readbacks (the fused slide's 4-byte shift, the
superspan's (4,)-i32 progress vector) so the prefetch/execute overlap is
an arrow in the rendered trace instead of an inference. Counters are
time-resolved: `count()` also appends `[t, key, value]` to a sample
ring, so a reader takes a counter's delta over any window.

Consumers:
- `benchmark/program_spans.py` — the per-layer metrics (`rows()`,
  `counter_samples()`, `compiles`).
- `chrome_trace()` — Chrome trace-event JSON (Perfetto-loadable): host
  spans as complete ("X") events, async readbacks as flow ("s"/"f")
  pairs, plus optional device-ring counter tracks on a sim-time process
  (telemetry/ring.py builds those).
- `report()` — the aggregated per-phase table (count / total / mean /
  max), exact even when the event ring wraps, because aggregates update
  on every `end()` rather than from the kept events.
- `EngineSpans` (`recorder().handle()`) — what an engine holds as
  `engine.tracer`: the same surface, every span and counter written to
  the shared ring AND into the handle's own aggregates, so
  `engine.telemetry_report()` is that engine's alone however many
  engines the process builds or interleaves.

This module carries the `# ktpu: hot-path` pragma ON PURPOSE: the lint
host-sync pass patrols it like the engine, and it stays golden-clean with
ZERO sync-ok waivers — the tracer must never touch a device value.
"""

from __future__ import annotations

import functools
import json
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# Span phase ids. Names index PHASE_NAMES; keep both in lockstep.
PH_WINDOW_CHUNK = 0  # run_windows / run_windows_skip dispatch
PH_FUSED_CHUNK_SLIDE = 1  # fused chunk+slide megastep dispatch
PH_SUPERSPAN = 2  # run_superspan dispatch
PH_PROGRESS_WAIT = 3  # blocking superspan progress readback
PH_SHIFT_WAIT = 4  # blocking fused-slide shift readback
PH_STAGE_ASSEMBLE = 5  # host assembly of a staging slab segment
PH_STAGE_PUT = 6  # H2D upload of a staging slab
PH_STAGE_PREFETCH = 7  # double-buffered successor-stage prefetch
PH_REFILL_PREFETCH = 8  # host slide path refill payload prefetch
PH_SLIDE = 9  # pod-window advance (shift + refill apply)
PH_WINDOW_GROW = 10  # in-place pod-window growth
PH_CKPT_SAVE = 11  # checkpoint save I/O
PH_CKPT_RESTORE = 12  # checkpoint restore I/O
PH_PRECOMPILE = 13  # AOT warm-up of dispatch program shapes
PH_CHUNK_FENCED = 14  # instrumented dispatch + device fence (profiled runs)
# Streaming feeder stall split (batched/stream.py): the engine thread
# waited for a staging slab the producer had not PUBLISHED yet (assembly /
# ring backlog bound) vs a published slab whose H2D transfer had not
# SETTLED (transfer bound). Both recorded with explicit durations via
# end(phase, t0, dur=...) from the feeder's consumer side.
PH_STAGE_WAIT_FEEDER = 15
PH_STAGE_WAIT_UPLOAD = 16
# Query-observatory lifecycle stages (PR 17, batched/fleet.py): the
# queue-wait half (submit -> lane admission) and the service half
# (admission -> horizon drain) of every lane-async query, both recorded
# with explicit host durations via end(phase, t0, dur=...) and linked by
# a submit->drain Chrome flow arrow per query.
PH_QUERY_QUEUE = 17
PH_QUERY_SERVICE = 18
# Fault-domain phases (batched/fleet.py): a query's terminal failure
# (span covers submit -> failure delivery, dur from host stamps) and a
# lane's quarantine interval (span covers quarantine fire -> full
# re-admission). Both host-stamped via end(phase, t0, dur=...).
PH_QUERY_FAIL = 19
PH_LANE_QUARANTINE = 20
# Lane-async pump round (batched/fleet.py), id = the round: the whole of
# pump(), its admission step (rounds that admit only), each engine
# dispatch, the drain step and, inside it, the blocking result fetch.
PH_PUMP = 21
PH_PUMP_ADMIT = 22
PH_LANE_DISPATCH = 23
PH_PUMP_DRAIN = 24
PH_RESULT_WAIT = 25
# Public engine entries: the parent of the stream path's spans, the batch
# cells' job, and the builds (BatchedSimulation / ScenarioFleet __init__).
PH_STEP_UNTIL_TIME = 26
PH_FLEET_RESET = 27
PH_ENGINE_BUILD = 28
# One XLA compilation or persistent-cache load, from jax's compile log
# (recompile.py): t0 = log time less the logged seconds, id = ordinal
# into `compiles`.
PH_COMPILE = 29
# The native trace parse and compile_from_arrays of a build from trace files
# (cli.build_batched_simulation); its counters are `trace_ingest_rows` and
# `trace_ingest_rows_dropped`.
PH_TRACE_INGEST = 30

PHASE_NAMES = (
    "window_chunk",
    "fused_chunk_slide",
    "superspan",
    "progress_wait",
    "shift_wait",
    "stage_assemble",
    "stage_put",
    "stage_prefetch",
    "refill_prefetch",
    "slide",
    "window_grow",
    "ckpt_save",
    "ckpt_restore",
    "precompile",
    "chunk_fenced",
    "stage_wait_feeder",
    "stage_wait_upload",
    "query_queue",
    "query_service",
    "query_fail",
    "lane_quarantine",
    "pump",
    "pump_admit",
    "lane_dispatch",
    "pump_drain",
    "result_wait",
    "step_until_time",
    "fleet_reset",
    "engine_build",
    "compile",
    "trace_ingest",
)

_N_PHASES = len(PHASE_NAMES)
ANNOTATION_PREFIX = "ktpu:"
_ANNOTATION_NAMES = tuple(ANNOTATION_PREFIX + name for name in PHASE_NAMES)
_COMPILES_KEPT = 4096
_FLOW_START = 0
_FLOW_END = 1

# Chrome-trace process ids: pid 0 = host spans, pid 1 = device-ring
# sim-time counter tracks (telemetry/ring.py), pid 2 = fleet lane
# swimlanes (one tid per lane, spans named by the occupying query id).
LANE_PID = 2


class _Span:
    """Context-manager span for cold paths (checkpoint I/O, the fenced
    per-chunk loop of log_throughput)."""

    __slots__ = ("_tracer", "_phase", "_t0")

    def __init__(self, tracer: "SpanTracer", phase: int):
        self._tracer = tracer
        self._phase = phase

    def __enter__(self):
        self._t0 = self._tracer.begin(self._phase)
        return self

    def __exit__(self, *exc):
        self._tracer.end(self._phase, self._t0)
        return False


class _Aggregates:
    """Exact per-phase span aggregates (ns) and freeform counters of one
    writer; Python ints, a third of the cost of a numpy scalar update."""

    def __init__(self):
        self._agg_count = [0] * _N_PHASES
        self._agg_total = [0] * _N_PHASES
        self._agg_max = [0] * _N_PHASES
        # Freeform counters (stage prefetch hits/misses, dispatch
        # histogram buckets, lane-window ledger, ...). Host ints only.
        self.counters: Dict[str, int] = {}

    def span(self, phase: int) -> _Span:
        """Context-manager span for cold paths; hot dispatch sites use
        begin/end directly to stay allocation-light."""
        return _Span(self, phase)

    def report(self) -> dict:
        """Aggregated per-phase wall time (ms totals, µs mean/max) plus
        the freeform counters — exact even when the span ring wrapped."""
        spans = {}
        for pid in range(_N_PHASES):
            n = self._agg_count[pid]
            if n == 0:
                continue
            total = self._agg_total[pid]
            spans[PHASE_NAMES[pid]] = {
                "count": n,
                "total_ms": total / 1e6,
                "mean_us": total / n / 1e3,
                "max_us": self._agg_max[pid] / 1e3,
            }
        return {"spans": spans, "counters": dict(self.counters)}


class SpanTracer(_Aggregates):
    def __init__(
        self,
        capacity: int = 1 << 16,
        flow_capacity: int = 1 << 14,
        lane_capacity: int = 1 << 14,
        counter_capacity: int = 1 << 14,
    ):
        super().__init__()
        # Span event ring: [t0_ns, dur_ns, phase, id]; kept events wrap,
        # the per-phase aggregates stay exact regardless.
        self._spans = np.zeros((capacity, 4), np.int64)
        self._n_spans = 0
        # Open begin()s, innermost last: the phase and its live
        # TraceAnnotation (closed by the matching end()).
        self._open_phase: List[int] = []
        self._open_ann: List[TraceAnnotation] = []
        # Flow event ring: [t_ns, phase, flow_id, kind].
        self._flows = np.zeros((flow_capacity, 4), np.int64)
        self._n_flows = 0
        self._next_flow = 1
        # Lane-occupancy ring (query observatory): [t0_ns, dur_ns, lane,
        # qid] — rendered as one Perfetto swimlane per fleet lane with
        # the occupying query id as the span name.
        self._lane_spans = np.zeros((lane_capacity, 4), np.int64)
        self._n_lane_spans = 0
        # Every counter update also lands in the sample ring
        # [t_ns, key, value].
        self._counter_keys: Dict[str, int] = {}
        self._samples = np.zeros((counter_capacity, 3), np.int64)
        self._n_samples = 0
        # (entry name, seconds) of the newest compilations jax logged
        # while a recompile sentinel was installed; a `compile` row's id
        # is the ordinal of its entry since process start.
        self.compiles: deque = deque(maxlen=_COMPILES_KEPT)
        self.compiles_recorded = 0
        self._epoch = time.perf_counter_ns()

    # -- hot path ----------------------------------------------------------

    def begin(self, phase: int) -> int:
        self._open_phase.append(phase)
        self._open_ann.append(TraceAnnotation(_ANNOTATION_NAMES[phase]))
        return time.perf_counter_ns()

    def end(
        self, phase: int, t0: int, dur: Optional[int] = None, ident: int = 0
    ) -> int:
        """Record the span; returns its duration (ns)."""
        if dur is None:
            dur = time.perf_counter_ns() - t0
            # Close this span's annotation — and any left open above it
            # by an exception between a begin() and its end().
            open_phase = self._open_phase
            while open_phase:
                self._open_ann.pop().__exit__(None, None, None)
                if open_phase.pop() == phase:
                    break
        i = self._n_spans % self._spans.shape[0]
        buf = self._spans
        buf[i, 0] = t0
        buf[i, 1] = dur
        buf[i, 2] = phase
        buf[i, 3] = ident
        self._n_spans += 1
        self._agg_count[phase] += 1
        self._agg_total[phase] += dur
        if dur > self._agg_max[phase]:
            self._agg_max[phase] = dur
        return dur

    def count(self, name: str, n: int = 1) -> None:
        value = self.counters.get(name, 0) + n
        self.counters[name] = value
        key = self._counter_keys.get(name)
        if key is None:
            key = self._counter_keys[name] = len(self._counter_keys)
        i = self._n_samples % self._samples.shape[0]
        buf = self._samples
        buf[i, 0] = time.perf_counter_ns()
        buf[i, 1] = key
        buf[i, 2] = value
        self._n_samples += 1

    def compile_event(self, name: str, seconds: float) -> None:
        """One XLA compilation (or cache load) that just finished, as
        jax's compile log reports it: a `compile` row ending now, and
        its name in `compiles`."""
        dur = int(seconds * 1e9)
        self.end(
            PH_COMPILE,
            time.perf_counter_ns() - dur,
            dur=dur,
            ident=self.compiles_recorded,
        )
        self.compiles.append((name, seconds))
        self.compiles_recorded += 1

    def flow_start(self, phase: int) -> int:
        fid = self._next_flow
        self._next_flow += 1
        self._flow_event(phase, fid, _FLOW_START)
        return fid

    def flow_end(self, phase: int, fid: int) -> None:
        self._flow_event(phase, fid, _FLOW_END)

    def _flow_event(self, phase: int, fid: int, kind: int) -> None:
        i = self._n_flows % self._flows.shape[0]
        buf = self._flows
        buf[i, 0] = time.perf_counter_ns()
        buf[i, 1] = phase
        buf[i, 2] = fid
        buf[i, 3] = kind
        self._n_flows += 1

    def lane_event(self, lane: int, qid: int, t0: int, dur: int) -> None:
        """One lane-occupancy interval: query ``qid`` held fleet lane
        ``lane`` for ``dur`` ns starting at ``t0`` (host clock). Ring
        write only — O(1), no allocation, no device touch."""
        i = self._n_lane_spans % self._lane_spans.shape[0]
        buf = self._lane_spans
        buf[i, 0] = t0
        buf[i, 1] = dur
        buf[i, 2] = lane
        buf[i, 3] = qid
        self._n_lane_spans += 1

    def handle(self) -> "EngineSpans":
        """A new per-engine handle on this recorder."""
        return EngineSpans(self)

    # -- readers -------------------------------------------------------------

    def rows(self) -> np.ndarray:
        """The kept span rows `[t0_ns, dur_ns, phase, id]`, oldest first
        (in the order their spans ENDED), as an owned copy."""
        return self._kept(self._spans, self._n_spans).copy()

    def counter_samples(self, name: str) -> np.ndarray:
        """The kept samples `[t_ns, value]` of one counter, oldest first
        (empty where the counter never counted)."""
        key = self._counter_keys.get(name)
        kept = self._kept(self._samples, self._n_samples)
        if key is None:
            return kept[:0, :2].copy()
        return kept[kept[:, 1] == key][:, (0, 2)]

    def dropped(self) -> Dict[str, int]:
        """Rows each ring has wrapped out (0 = everything recorded is
        still kept)."""
        return {
            "spans": max(0, self._n_spans - self._spans.shape[0]),
            "counter_samples": max(0, self._n_samples - self._samples.shape[0]),
        }

    # -- export ------------------------------------------------------------

    def _kept(self, buf: np.ndarray, n: int) -> np.ndarray:
        cap = buf.shape[0]
        if n <= cap:
            return buf[:n]
        cut = n % cap
        return np.concatenate([buf[cut:], buf[:cut]], axis=0)

    def chrome_trace(self, extra_events: Optional[list] = None) -> dict:
        """Chrome trace-event JSON dict (load the written file straight
        into Perfetto / chrome://tracing). ts is microseconds relative to
        tracer construction; host spans live on pid 0, the device ring's
        sim-time counter tracks (extra_events, built by telemetry/ring.py)
        on pid 1."""
        ev = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": 0,
                "tid": 0,
                "args": {"name": "ktpu-host"},
            },
            {
                "ph": "M",
                "name": "thread_name",
                "pid": 0,
                "tid": 0,
                "args": {"name": "engine dispatch loop"},
            },
        ]
        epoch = self._epoch
        for t0, dur, phase, ident in self._kept(
            self._spans, self._n_spans
        ).tolist():
            ev.append(
                {
                    "ph": "X",
                    "name": PHASE_NAMES[int(phase)],
                    "cat": "host",
                    "ts": (t0 - epoch) / 1e3,
                    "dur": dur / 1e3,
                    "pid": 0,
                    "tid": 0,
                    "args": {"id": int(ident)},
                }
            )
        # Flow arrows need both ends: a readback still pending (any
        # engine's of the process) or one whose start wrapped out is left
        # out.
        flows = self._kept(self._flows, self._n_flows)
        started = flows[flows[:, 3] == _FLOW_START, 2]
        ended = flows[flows[:, 3] == _FLOW_END, 2]
        paired = np.isin(flows[:, 2], np.intersect1d(started, ended))
        for t, phase, fid, kind in flows[paired].tolist():
            ev.append(
                {
                    "ph": "s" if kind == _FLOW_START else "f",
                    "bp": "e",
                    "name": PHASE_NAMES[int(phase)] + "_readback",
                    "cat": "readback",
                    "id": int(fid),
                    "ts": (t - epoch) / 1e3,
                    "pid": 0,
                    "tid": 0,
                }
            )
        lane_rows = self._kept(self._lane_spans, self._n_lane_spans).tolist()
        if lane_rows:
            ev.append(
                {
                    "ph": "M",
                    "name": "process_name",
                    "pid": LANE_PID,
                    "tid": 0,
                    "args": {"name": "ktpu-lanes"},
                }
            )
            for lane in sorted({int(r[2]) for r in lane_rows}):
                ev.append(
                    {
                        "ph": "M",
                        "name": "thread_name",
                        "pid": LANE_PID,
                        "tid": lane,
                        "args": {"name": f"lane {lane}"},
                    }
                )
            for t0, dur, lane, qid in lane_rows:
                ev.append(
                    {
                        "ph": "X",
                        "name": f"q{int(qid)}",
                        "cat": "lane",
                        "ts": (t0 - epoch) / 1e3,
                        "dur": dur / 1e3,
                        "pid": LANE_PID,
                        "tid": int(lane),
                    }
                )
        if extra_events:
            ev.extend(extra_events)
        return {
            "traceEvents": ev,
            "displayTimeUnit": "ms",
            "otherData": {
                "spans_recorded": int(self._n_spans),
                "spans_kept": int(min(self._n_spans, self._spans.shape[0])),
            },
        }

    def write_chrome_trace(
        self, path: str, extra_events: Optional[list] = None
    ) -> str:
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(extra_events), fh)
        return path

    def report(self) -> dict:
        """The process-wide table and counters, plus how much of the
        span and lane rings is still kept."""
        rep = super().report()
        rep["span_events"] = {
            "recorded": int(self._n_spans),
            "kept": int(min(self._n_spans, self._spans.shape[0])),
        }
        rep["lane_spans"] = {
            "recorded": int(self._n_lane_spans),
            "kept": int(min(self._n_lane_spans, self._lane_spans.shape[0])),
        }
        return rep


class EngineSpans(_Aggregates):
    """One engine's handle on the process-wide recorder (`engine.tracer`;
    a fleet writes through its engine's). Every span, flow, lane event
    and counter goes to the shared rings; the aggregates and counters
    kept HERE count this engine's alone, so `report()` does not carry
    another engine's time. `span_events` / `lane_spans` in it describe
    the shared rings."""

    def __init__(self, rec: SpanTracer):
        super().__init__()
        self._rec = rec
        self.begin = rec.begin
        self.flow_start = rec.flow_start
        self.flow_end = rec.flow_end
        self.lane_event = rec.lane_event
        self.write_chrome_trace = rec.write_chrome_trace

    def end(
        self, phase: int, t0: int, dur: Optional[int] = None, ident: int = 0
    ) -> int:
        dur = self._rec.end(phase, t0, dur, ident)
        self._agg_count[phase] += 1
        self._agg_total[phase] += dur
        if dur > self._agg_max[phase]:
            self._agg_max[phase] = dur
        return dur

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n
        self._rec.count(name, n)

    def report(self) -> dict:
        rep = self._rec.report()
        rep.update(super().report())
        return rep


_RECORDER = SpanTracer()


def recorder() -> SpanTracer:
    """THE process-wide recorder every engine and fleet writes to."""
    return _RECORDER


def build_span(init):
    """Decorator for an engine's or fleet's `__init__`: one `engine_build`
    span round the whole constructor, closed whatever it raises, on the
    handle the constructor left as `self.tracer` (an engine's) or else on
    the recorder (a fleet's, or a build that failed before its handle)."""

    @functools.wraps(init)
    def build(self, *args, **kwargs):
        rec = recorder()
        t0 = rec.begin(PH_ENGINE_BUILD)
        try:
            init(self, *args, **kwargs)
        finally:
            getattr(self, "tracer", rec).end(PH_ENGINE_BUILD, t0)

    return build


def log_chunk_throughput(logger, n_windows, n_clusters, decisions, elapsed):
    """The per-chunk decisions/s + cluster-windows/s log line (TPU analog
    of the scalar events/s log, reference: src/simulator.rs:363-368) — ONE
    owner of the format, shared by the engine's log_throughput path."""
    logger.info(
        "chunk of %d windows in %.3fs: %.0f decisions/s, "
        "%.0f cluster-windows/s",
        n_windows,
        elapsed,
        decisions / max(elapsed, 1e-9),
        n_windows * n_clusters / max(elapsed, 1e-9),
    )
