"""The harness's own spans: name, start and end on the host clock, recorded in
memory around the calls into each layer. While the profiler runs, each span
also enters a `jax.profiler.TraceAnnotation` named `bench:<name>`, so that
trace_reduce can label the device's idle gaps with what the host was doing."""

from __future__ import annotations

import contextlib
import statistics
import time
from typing import List, Optional, Tuple

ANNOTATION_PREFIX = "bench:"


class Spans:
    def __init__(self) -> None:
        self.rows: List[Tuple[str, float, float]] = []  # (name, start_s, end_s)
        self.annotate = False
        self.window_t0: Optional[float] = None

    def mark_window(self, t0: float) -> None:
        self.window_t0 = t0

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str, in_window: bool = True) -> List[float]:
        lo = self.window_t0 if in_window and self.window_t0 is not None else float("-inf")
        return [t1 - t0 for n, t0, t1 in self.rows if n == name and t0 >= lo]

    def total(self, name: str, in_window: bool = False) -> float:
        return sum(self.durations(name, in_window))


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation; one value is its
    own percentile."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of nothing")
    if len(data) == 1:
        return float(data[0])
    pos = (len(data) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(data) - 1)
    return float(data[lo] + (data[hi] - data[lo]) * (pos - lo))


def median(values) -> float:
    return float(statistics.median(values))
