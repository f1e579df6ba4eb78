"""The program's own spans and counters, cut to the measured window.

The program keeps one process-wide recorder (kubernetriks_tpu/telemetry/
tracer.py `recorder()`): a ring of rows `[t0_ns, dur_ns, phase, id]` on
`time.perf_counter_ns()`, which is the clock of benchmark/spans.py, time-
resolved counters, and the compilations jax logged. This file holds the one
import of it and every cut and reduction the per-layer readers share; a
reader is a few lines over it. Where the program has no recorder (a commit
before PR 26), or recorded none of a reader's spans, the reader gets None and
reports nothing.

The id of a row says what it belongs to: the query on `query_*`, the pump
round on `pump` and its children, the superspan's ordinal on `superspan`,
`progress_wait` and the stage spans, the compilation's ordinal on `compile`.
Spans nest by interval containment on the one engine thread; a span's self
time is its duration less what its children cover, the rule
trace_reduce.self_times uses for device ops.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

T0, DUR, PHASE, IDENT = range(4)


def _program():
    """(recorder, phase names) of the program, or None where it has none."""
    try:
        from kubernetriks_tpu.telemetry.tracer import PHASE_NAMES, recorder
    except ImportError:
        return None
    return recorder(), PHASE_NAMES


def window_ns(run) -> Tuple[int, int]:
    """The measured window on the recorder's clock."""
    lo = int(run.spans.window_t0 * 1e9)
    return lo, lo + int(run.window_s * 1e9)


class Rows:
    """The recorder's kept rows with the names of their phases."""

    def __init__(self, rows: np.ndarray, names: Tuple[str, ...]):
        self.rows = rows
        self.names = names

    def of(self, *phases: str) -> np.ndarray:
        """The rows of the named phases, by start time; a phase the program
        does not know has no rows."""
        ids = [self.names.index(p) for p in phases if p in self.names]
        rows = self.rows[np.isin(self.rows[:, PHASE], ids)]
        return rows[np.argsort(rows[:, T0], kind="stable")]

    def name(self, row) -> str:
        return self.names[int(row[PHASE])]


def window_rows(run) -> Optional[Rows]:
    """The rows of spans that STARTED inside the measured window. Raises if
    the ring has wrapped rows of the window out (rows are kept in the order
    their spans ended, so that is the case exactly when a row was dropped and
    the oldest kept row ended inside the window or after it)."""
    program = _program()
    if program is None:
        return None
    rec, names = program
    rows = rec.rows()
    lo, hi = window_ns(run)
    dropped = rec.dropped()["spans"]
    if dropped and (not len(rows) or rows[0, T0] + rows[0, DUR] > lo):
        raise RuntimeError(
            f"program_spans: the recorder's ring dropped {dropped} rows and its oldest kept row "
            "ends inside the measured window: the window wrapped out"
        )
    return Rows(rows[(rows[:, T0] >= lo) & (rows[:, T0] < hi)], names)


def setup_rows(run) -> Optional[Rows]:
    """The rows of this run's set-up: spans that started no earlier than the
    harness's first span (the drivers install their sentinel and build inside
    it; an earlier run of the same process is not this run's set-up) and ENDED
    before the window opened. Raises if the ring has dropped any row at all
    (set-up comes first)."""
    program = _program()
    if program is None:
        return None
    rec, names = program
    dropped = rec.dropped()["spans"]
    if dropped:
        raise RuntimeError(f"program_spans: the recorder's ring dropped {dropped} rows: set-up wrapped out")
    rows = rec.rows()
    first = int(min((start for _, start, _ in run.spans.rows), default=run.process_t0) * 1e9)
    lo, _ = window_ns(run)
    return Rows(rows[(rows[:, T0] >= first) & (rows[:, T0] + rows[:, DUR] <= lo)], names)


def self_ns(rows: np.ndarray) -> np.ndarray:
    """Per-row self time of span rows: the duration less what the spans
    nested inside cover (each child charged to its innermost parent)."""
    out = rows[:, DUR].astype(np.int64).copy()
    stack: List[Tuple[int, int]] = []  # (row index, end_ns), innermost last
    for i in sorted(range(len(rows)), key=lambda k: (rows[k, T0], -rows[k, DUR])):
        t0, dur = int(rows[i, T0]), int(rows[i, DUR])
        while stack and stack[-1][1] <= t0:
            stack.pop()
        if stack:
            parent, parent_end = stack[-1]
            out[parent] -= min(dur, parent_end - t0)
        stack.append((i, t0 + dur))
    return out


def inside(rows: np.ndarray, parent) -> np.ndarray:
    """The rows that lie inside one parent row's interval."""
    lo, hi = parent[T0], parent[T0] + parent[DUR]
    return rows[(rows[:, T0] >= lo) & (rows[:, T0] + rows[:, DUR] <= hi)]


def ms(ns) -> float:
    return float(ns) / 1e6


def compile_names(rows: np.ndarray) -> List[str]:
    """The program name of each `compile` row (its id is the compilation's
    ordinal since process start; the recorder keeps the newest names)."""
    rec, _ = _program()
    first_kept = rec.compiles_recorded - len(rec.compiles)
    kept = list(rec.compiles)
    return [
        kept[int(i) - first_kept][0] if int(i) >= first_kept else "<name dropped>"
        for i in rows[:, IDENT]
    ]


def counter_deltas(run, *names: str) -> Optional[Dict[str, int]]:
    """How much each named counter of the recorder grew inside the window,
    from its time-resolved samples; None where the program has no recorder or
    a counter never counted. Raises if samples of the window wrapped out."""
    program = _program()
    if program is None:
        return None
    rec, _ = program
    lo, hi = window_ns(run)
    out = {}
    for name in names:
        samples = rec.counter_samples(name)
        if not len(samples):
            return None
        if rec.dropped()["counter_samples"] and samples[0, 0] > lo:
            raise RuntimeError(f"program_spans: samples of counter {name!r} inside the window wrapped out")
        before = samples[samples[:, 0] < lo]
        upto = samples[samples[:, 0] < hi]
        start = int(before[-1, 1]) if len(before) else 0
        out[name] = (int(upto[-1, 1]) if len(upto) else start) - start
    return out
