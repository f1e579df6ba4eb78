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
- `program_phases()` — for every program an engine noted at a dispatch
  site (`program()`), which device phase (`DEVICE_PHASES`, the named
  scopes of the window body) each instruction of its compiled text
  belongs to; `benchmark/phase_times.py` joins it with a device trace.
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
import re
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
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

# Device phases: the closed set of `jax.named_scope` names the window
# programs carry (batched/step.py, batched/autoscale.py and the kernel
# wrappers of ops/), so that every device op of a window says in its
# `op_name` path which part of the simulator it belongs to. Seven top-level
# phases partition `_window_body` and the loops round it; two are NESTED:
# `kernel_io`, inside the kernel wrappers, round the pads, transposes,
# casts and slices that marshal a pallas_call's operands and results (never
# round the call itself), and `node_faults`, inside `events`, round what a
# build under node faults adds to the event application. An op's TOP-LEVEL phase is the FIRST name of this
# tuple in its `op_name` path, its INNERMOST phase the LAST (`phase_of`);
# other scopes (`spread_counts`, `ca_scale_up`, `ca_scale_down`) nest inside
# a phase and name no phase themselves. A scope is location metadata: the
# lowered program with debug locations stripped does not change by a byte.
DEVICE_PHASES = (
    "events",  # _apply_window_events: the razor, the slab read, the event loop, the frees, the wake
    "cycle",  # _run_scheduling_cycle: queue, candidates, the decision kernels, the commit
    "hpa_pass",
    "ca_pass",
    "ca_reclaim",
    "slide",  # the pod window's shift, refill and the superspan's capacity read
    "bookkeeping",  # lane freeze, the ring's record, fast-forward, layout swaps, loop counters
    "kernel_io",  # nested: a kernel wrapper's operand and result marshalling
    "node_faults",  # nested in events: what crash and recovery add (attribution, counters, the reschedule order)
)


def phase_of(op_name: str) -> Optional[Tuple[str, str]]:
    """(top-level, innermost) device phase of one op, from the scopes of its
    `op_name` path (`jit(run_windows)/while/body/cycle/kernel_io/pad`):
    the first and the last component that names a phase; None where the
    path names none."""
    found = [part for part in op_name.split("/") if part in DEVICE_PHASES]
    return (found[0], found[-1]) if found else None


# An instruction line of optimized HLO text, its opcode, its `op_name`, and
# the computations it names (`compiled.as_text()`).
_HLO_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s=\s(.*)$")
_HLO_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_ATTRIBUTES = re.compile(r", (?:metadata|backend_config|frontend_attributes)=")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_HLO_CALLED = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation"
    r"|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)"
)
# Who runs a called computation's instructions as device ops of their own
# (the trace times them one by one): a loop, a branch, a call. A fusion's,
# a reduce's or a sort's computation runs inside the one op that names it.
_HLO_RUNS_CALLED = {"while", "conditional", "call", "async-start"}


def instruction_phases(hlo_text: str) -> Dict[str, Optional[Tuple[str, str, str]]]:
    """{instruction name: (top-level phase, innermost phase, how it is
    known), None without one} over the instructions of an optimized HLO
    module that run as device ops of their own: those of the entry
    computation and of every loop body, loop condition, branch and called
    computation reached from it. A fused computation's inner instructions
    are left out: the trace times the fusion, which carries ONE `op_name`,
    its root's, and goes whole to that phase even where XLA fused ops of
    two.

    `how` is `"scope"` where the instruction's own `op_name` names the phase
    (`phase_of`). The compiler also makes instructions that carry none: the
    copies, `copy-start` / `copy-done` pairs and broadcasts that lay out a
    loop's or a branch's operands, the pieces a cumulative sum is expanded
    into. Such an instruction takes the phase of what CONSUMES it
    (`"consumer"`: followed through other phaseless instructions, tuples
    included, to the first that name a phase, where those agree on the
    top-level phase) or, where nothing that consumes it names one (a loop's
    carry), of what PRODUCES its operands (`"producer"`); where its
    neighbours disagree or name nothing (a branch that hands an operand on
    in another layout: a parameter, a copy, the root tuple) it takes the
    phase of the loop, branch or call instruction that RUNS its computation,
    where that one's own `op_name` names it (`"caller"`), and else stays
    None."""
    computations: Dict[str, list] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        if current is None:
            head = _HLO_COMPUTATION.match(line)
            if head:
                current = computations.setdefault(head.group(2), [])
                if head.group(1):
                    entry = head.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        inst = _HLO_INSTRUCTION.match(line)
        if inst:
            current.append(inst.groups())
    out: Dict[str, Optional[Tuple[str, str, str]]] = {}
    todo, seen = [(entry, None)], {entry}
    while todo:
        computation, caller = todo.pop()
        instructions = computations.get(computation, ())
        own: Dict[str, Optional[Tuple[str, str]]] = {}
        for name, rest in instructions:
            op_name = _HLO_OP_NAME.search(rest)
            own[name] = phase_of(op_name.group(1)) if op_name else None
        operands: Dict[str, list] = {}
        users: Dict[str, list] = {name: [] for name in own}
        for name, rest in instructions:
            reads = _HLO_ATTRIBUTES.split(rest, 1)[0]
            operands[name] = [o for o in _HLO_OPERAND.findall(reads) if o in own and o != name]
            for operand in operands[name]:
                users[operand].append(name)
            opcode = _HLO_OPCODE.search(rest)
            if opcode is None or opcode.group(1) not in _HLO_RUNS_CALLED:
                continue
            for _, called in _HLO_CALLED.findall(rest):
                for comp in re.findall(r"[\w.\-]+", called):
                    if comp not in seen:
                        seen.add(comp)
                        todo.append((comp, own[name]))
        for name, phases in own.items():
            out[name] = (
                phases + ("scope",)
                if phases
                else _neighbours_phase(name, own, users, operands) or (caller and caller + ("caller",))
            )
    return out


def gather_instructions(hlo_text: str) -> frozenset:
    """The names of the instructions of an optimized HLO module whose
    `op_name` ends in the primitive `gather`: a fusion whose root is an XLA
    gather, and the reshapes and copies the compiler lays round one. On the
    TPU such an op costs per INDEX whatever it reads (PERF.md section 5),
    so a reader of a `phases` line wants them counted beside the ops."""
    found = set()
    for line in hlo_text.splitlines():
        inst = _HLO_INSTRUCTION.match(line)
        op_name = _HLO_OP_NAME.search(line) if inst else None
        if op_name and op_name.group(1).rsplit("/", 1)[-1] == "gather":
            found.add(inst.group(1))
    return frozenset(found)


def _neighbours_phase(name, own, users, operands) -> Optional[Tuple[str, str, str]]:
    """The phase a phaseless instruction takes from its consumers, else from
    its producers (`instruction_phases`), or None."""
    for edges, how in ((users, "consumer"), (operands, "producer")):
        found, todo, seen = set(), [name], {name}
        while todo:
            for nxt in edges[todo.pop()]:
                if nxt in seen:
                    continue
                seen.add(nxt)
                if own[nxt] is not None:
                    found.add(own[nxt])
                else:
                    todo.append(nxt)
        tops = {top for top, _ in found}
        if len(tops) == 1:
            inners = {inner for _, inner in found}
            top = tops.pop()
            return top, inners.pop() if len(inners) == 1 else top, how
        if found:
            return None  # the consumers disagree
    return None


def _abstract(leaf):
    """A dispatched operand as the shape a later lowering needs: no array,
    no device memory. A committed device array keeps its sharding, so that
    the lowering is the dispatch's own and its executable is found again."""
    if isinstance(leaf, jax.Array):
        sharding = leaf.sharding if getattr(leaf, "committed", True) else None
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)
    if isinstance(leaf, (np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return leaf


def _program_label(key: tuple) -> str:
    serial, name, variant = key
    return f"{name}{list(variant)}@engine{serial}"


class _Program:
    """One dispatched program: what gets its compiled text later, and the
    map read from it once asked for."""

    __slots__ = ("fn", "args", "kwargs", "phases", "gathers", "first_ns", "last_ns")

    def __init__(self, fn: Callable, args, kwargs):
        self.fn = fn
        self.args, self.kwargs = jax.tree.map(_abstract, (args, kwargs))
        self.phases: Optional[Dict[str, Optional[Tuple[str, str, str]]]] = None
        self.gathers: frozenset = frozenset()  # of `phases`: gather_instructions
        # perf_counter_ns of the first and the newest dispatch
        self.first_ns = self.last_ns = time.perf_counter_ns()

    def read_phases(self) -> Dict[str, Optional[Tuple[str, str, str]]]:
        if self.phases is None:
            compiled = self.fn.lower(*self.args, **self.kwargs).compile()
            text = compiled.as_text()
            self.phases = instruction_phases(text)
            self.gathers = gather_instructions(text) & self.phases.keys()
            self.fn = self.args = self.kwargs = None
        return self.phases


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
        # Every distinct program an engine dispatched inside a window, by
        # (engine handle, name, variant): what gets its compiled text later
        # (program_phases). A dozen a process.
        self._programs: Dict[tuple, _Program] = {}
        self._n_handles = 0
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

    def program(self, key: tuple, fn: Callable, args: tuple, kwargs: dict) -> None:
        """Note the program a dispatch site is about to run: `fn(*args,
        **kwargs)`, a jitted function with the operands it gets. One
        dictionary lookup a dispatch; the first dispatch of a distinct
        `key` keeps the function and the abstract shapes and shardings of
        its operands (statics as they are), nothing lowered or compiled.
        Every dispatch leaves its time, so that a reader can tell the
        programs that ran in a window from those a warm-up ran."""
        program = self._programs.get(key)
        if program is None:
            self._programs[key] = _Program(fn, args, kwargs)
        else:
            program.last_ns = time.perf_counter_ns()

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
        self._n_handles += 1
        return EngineSpans(self, self._n_handles)

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

    def program_phases(
        self,
        since_ns: int = 0,
        until_ns: Optional[int] = None,
        handle: Optional[int] = None,
    ) -> Dict[str, Dict[str, Optional[Tuple[str, str, str]]]]:
        """{program: {instruction name: (top-level device phase, innermost
        device phase, how it is known: by its own scope, or from its
        consumers or producers) or None}} of every program dispatched so far
        (`instruction_phases` of its compiled text), or of those whose dispatches, first to
        newest, reach into `[since_ns, until_ns)` on
        `time.perf_counter_ns()`: the programs that ran in a window, not
        what a warm-up or a later engine ran (`handle`: one engine
        handle's alone, `EngineSpans.program_phases`). On demand: the first call
        after a program's first dispatch lowers it from the kept shapes and
        compiles it, which finds the dispatch's own executable again (jax
        keeps it by the same lowering; at worst a load from the persistent
        compile cache), and the answer is kept."""
        return {
            _program_label(key): program.read_phases()
            for key, program in list(self._programs.items())
            if program.last_ns >= since_ns
            and (until_ns is None or program.first_ns < until_ns)
            and handle in (None, key[0])
        }

    def device_phases(self, handle: Optional[int] = None) -> dict:
        """The closed set of device phases and, for each program dispatched
        so far (one engine handle's, or all), how many of its instructions
        each top-level phase holds (`inherited`: those of them that name no
        phase themselves and take their consumers' or producers'), and
        under `gathers` how many of a phase's instructions are XLA gathers
        (`gather_instructions`: their `op_name` ends in `gather`). Forces
        no compile: a program nobody asked `program_phases()` about yet
        reports nothing."""
        programs, gathers = {}, {}
        for key, program in self._programs.items():
            if program.phases is None or handle not in (None, key[0]):
                continue
            counts: Dict[str, int] = {}
            gathered: Dict[str, int] = {}
            for name, phases in program.phases.items():
                top = phases[0] if phases else "unscoped"
                counts[top] = counts.get(top, 0) + 1
                if phases and phases[2] != "scope":
                    counts["inherited"] = counts.get("inherited", 0) + 1
                if name in program.gathers:
                    gathered[top] = gathered.get(top, 0) + 1
            programs[_program_label(key)] = counts
            gathers[_program_label(key)] = gathered
        return {"phases": list(DEVICE_PHASES), "programs": programs, "gathers": gathers}

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
        rep["device_phases"] = self.device_phases()
        return rep


class EngineSpans(_Aggregates):
    """One engine's handle on the process-wide recorder (`engine.tracer`;
    a fleet writes through its engine's). Every span, flow, lane event
    and counter goes to the shared rings; the aggregates and counters
    kept HERE count this engine's alone, so `report()` does not carry
    another engine's time. `span_events` / `lane_spans` in it describe
    the shared rings."""

    def __init__(self, rec: SpanTracer, serial: int):
        super().__init__()
        self._rec = rec
        self._serial = serial
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

    def program(self, name: str, variant: tuple, fn: Callable, args: tuple, kwargs: dict) -> None:
        """`SpanTracer.program` under this engine's key: the program's
        name and whatever tells two of its compiled shapes apart (a chunk's
        length, a flag, the pod window's width)."""
        self._rec.program((self._serial, name, variant), fn, args, kwargs)

    def program_phases(self) -> Dict[str, Dict[str, Optional[Tuple[str, str, str]]]]:
        """`SpanTracer.program_phases` of this engine's programs alone: a
        process that has built other engines reads (and, where jax has
        dropped their executables, compiles) none of theirs."""
        return self._rec.program_phases(handle=self._serial)

    def report(self) -> dict:
        rep = self._rec.report()
        rep.update(super().report())
        rep["device_phases"] = self._rec.device_phases(self._serial)
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
