"""Device time by the phase of the simulator, from the program's own map.

The program names every device op of its window programs with the part of
the simulator it belongs to (kubernetriks_tpu/telemetry/tracer.py
`DEVICE_PHASES`: `jax.named_scope`s over the window body, so the phase is in
each instruction's `op_name`), and its recorder exports, for every program it
dispatched, `{instruction name: (top-level phase, innermost phase, how it is
known) or None}` (`recorder().program_phases()`, read from the compiled text:
the trace keeps only an op's short name, the program knows the rest; `how` is
`scope` for an op that names its phase, `consumer` / `producer` for one the
compiler made without a name, which takes its neighbours'). This file holds the one
import of that and the join with `run.trace.op_self_s`: seconds of a phase =
the self time of the ops whose name the programs that ran map to it.

`TraceSummary` keeps no module name, so the join is by instruction name over
ALL programs dispatched in the window: a name that two of them
carry under DIFFERENT phases goes to neither and counts as unscoped (a program
that gives the name NO phase does not contest it: the small programs between
windows carry no scope), as does an op no program maps (a program nobody
noted) and an op no program can place. A fusion carries one `op_name`, its
root's, and goes whole to that phase.

Where the program has no such map (a commit before PR 39) every reader gets
None and reports nothing. One `phases` line a traced run.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from benchmark import program_spans
from benchmark.harness import say

Phases = Optional[Tuple[str, str, str]]  # (top-level, innermost, how known), None = unscoped
NAME_CUT = 96  # trace_reduce.short_name keeps this much of an op's name
CONFLICT = ("", "", "")  # two programs put the name under different phases


def _program():
    """(program_phases, DEVICE_PHASES) of the program, or None where it has
    no op-to-phase map."""
    try:
        from kubernetriks_tpu.telemetry.tracer import DEVICE_PHASES, recorder
    except ImportError:
        return None
    return recorder().program_phases, DEVICE_PHASES


def join_names(programs: Dict[str, Dict[str, Phases]]) -> Dict[str, Phases]:
    """{op name as the trace prints it: its phases} over every program; a
    name under different (top-level, innermost) phases in two programs maps
    to CONFLICT; a program that gives it none leaves it to the other."""
    out: Dict[str, Phases] = {}
    for instructions in programs.values():
        for name, phases in instructions.items():
            name = name[:NAME_CUT]
            known = out.setdefault(name, phases)
            if known is None:
                out[name] = phases
            elif phases is not None and known[:2] != phases[:2]:
                out[name] = CONFLICT
    return out


@dataclass
class PhaseTimes:
    top_s: Dict[str, float]  # by top-level phase, every phase of the closed set
    inner_s: Dict[str, float]  # by innermost phase
    unscoped_s: float
    inherited_s: Dict[str, float] = field(default_factory=dict)  # of top_s: from consumers or producers
    ops: Dict[str, List[Tuple[str, float]]] = field(default_factory=dict)  # phase -> its ops, largest first
    unscoped_ops: List[Tuple[str, float]] = field(default_factory=list)
    conflicts_s: float = 0.0  # of unscoped_s: names two programs disagree on
    unmapped_s: float = 0.0  # of unscoped_s: names no program carries
    map_s: float = 0.0  # seconds program_phases() took

    @property
    def total_s(self) -> float:
        return sum(self.top_s.values()) + self.unscoped_s


def split(op_self_s: Dict[str, float], names: Dict[str, Phases], device_phases) -> PhaseTimes:
    """The self times of a trace's ops, shared out by `names`."""
    top = {p: 0.0 for p in device_phases}
    inner = {p: 0.0 for p in device_phases}
    ops: Dict[str, List[Tuple[str, float]]] = {p: [] for p in device_phases}
    out = PhaseTimes(top, inner, 0.0, {p: 0.0 for p in device_phases}, ops)
    for name, seconds in op_self_s.items():
        phases = names.get(name)
        if phases is None or phases == CONFLICT:
            out.unscoped_s += seconds
            out.unscoped_ops.append((name, seconds))
            if phases == CONFLICT:
                out.conflicts_s += seconds
            elif name not in names:
                out.unmapped_s += seconds
            continue
        top[phases[0]] += seconds
        inner[phases[1]] += seconds
        if phases[2] != "scope":
            out.inherited_s[phases[0]] += seconds
        ops[phases[0]].append((name, seconds))
        if phases[1] != phases[0]:
            ops[phases[1]].append((name, seconds))
    for rows in list(ops.values()) + [out.unscoped_ops]:
        rows.sort(key=lambda row: row[1], reverse=True)
    return out


def units(run) -> Optional[float]:
    """What a phase's time is divided by: the simulated windows the traced
    window stepped (`windows_stepped`, as `window_device_ms` divides), or in
    a served cell, which counts none, the `pump` rounds that started in it."""
    windows = run.counters.get("windows_stepped")
    if windows:
        return float(windows)
    rows = program_spans.window_rows(run)
    rounds = len(rows.of("pump")) if rows is not None else 0
    return float(rounds) or None


def read(run) -> Optional[PhaseTimes]:
    """The traced window's device time by phase, computed once a run (and the
    `phases` line printed then); None without a trace or without the map."""
    if run.trace is None:
        return None
    if not hasattr(run, "_phase_times"):
        run._phase_times = _read(run)
    return run._phase_times


def _read(run) -> Optional[PhaseTimes]:
    program = _program()
    if program is None:
        return None
    program_phases, device_phases = program
    t0 = time.perf_counter()
    # The programs that ran in the window: one that only a warm-up
    # dispatched, or an engine built after the window (the reference's), has
    # no op in the trace, and its names would only collide.
    since_ns, until_ns = program_spans.window_ns(run)
    programs = program_phases(since_ns=since_ns, until_ns=until_ns)
    map_s = time.perf_counter() - t0
    times = split(run.trace.op_self_s, join_names(programs), device_phases)
    times.map_s = map_s
    per = units(run)
    scale = 1e3 / per if per else None

    def ms(seconds: float) -> Optional[float]:
        return seconds * scale if scale else None

    say(
        line="phases",
        per="window" if run.counters.get("windows_stepped") else "pump_round",
        units=per,
        top_ms={p: ms(s) for p, s in times.top_s.items()},
        inner_ms={p: ms(s) for p, s in times.inner_s.items() if s != times.top_s[p]},
        inherited_ms={p: ms(s) for p, s in times.inherited_s.items() if s},
        largest={p: [[n, ms(s)] for n, s in rows[:3]] for p, rows in times.ops.items() if rows},
        unscoped_ms=ms(times.unscoped_s),
        unscoped_share=times.unscoped_s / run.trace.busy_s if run.trace.busy_s else None,
        unscoped_over_1pct=[
            [n, ms(s)] for n, s in times.unscoped_ops if s > 0.01 * run.trace.busy_s
        ],
        unscoped_conflicts_ms=ms(times.conflicts_s),
        unscoped_unmapped_ms=ms(times.unmapped_s),
        total_ms=ms(times.total_s),
        busy_ms=ms(run.trace.busy_s),
        programs={name: len(instructions) for name, instructions in programs.items()},
        program_phases_s=map_s,
    )
    return times


def device_ms(run, *phases: str, innermost: bool = False) -> Optional[float]:
    """Device milliseconds a unit (a simulated window, or a pump round) in
    the named top-level phases together, or with `innermost` in the ops whose
    innermost phase they are."""
    times = read(run)
    per = units(run)
    if times is None or not per:
        return None
    table = times.inner_s if innermost else times.top_s
    return sum(table[p] for p in phases) * 1e3 / per
