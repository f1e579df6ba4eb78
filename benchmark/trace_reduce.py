"""From a profiler trace to the numbers the benchmark reports.

`load_xplane` reads the `.xplane.pb` that `jax.profiler` writes (with nothing
but JAX) into a neutral form: per device, the operations that ran (name,
start, duration in ns), and the harness's own spans (`bench:<name>`
annotations) on the same clock. `reduce_events` turns that into

- `busy_s`: seconds in which an operation ran on the device (the union of
  the op intervals, averaged over the devices used) and `window_s`, the
  length of the traced window, so idle share = 1 - busy_s / window_s;
- per-operation self time (a `while` does not count its body twice);
- kernel time, by the match table in kernel_names.json;
- the device's idle gaps, labelled by the harness span that was open.

The neutral form is JSON, so a small recorded trace sits beside the test
(tests/benchmark/data) and every PR computes the same numbers the same way.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.spans import ANNOTATION_PREFIX

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def short_name(name: str) -> str:
    """An op's own name: the trace prints the whole HLO instruction
    (`%fusion.3 = (s32[...]...) fusion(...)`), kilobytes for a kernel call."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclass
class TraceEvents:
    devices: List[List[Event]]
    host_spans: List[Event]

    def to_json(self) -> Dict:
        return {"devices": self.devices, "host_spans": self.host_spans}

    @staticmethod
    def from_json(data: Dict) -> "TraceEvents":
        return TraceEvents(
            devices=[[tuple(e) for e in dev] for dev in data["devices"]],
            host_spans=[tuple(e) for e in data["host_spans"]],
        )


def load_xplane(path: str, n_devices: int, cpu_rehearsal: bool = False) -> TraceEvents:
    """The device op lines of the first `n_devices` TPU planes, and every
    `bench:` annotation of the host planes. In the CPU rehearsal there is no
    device plane: the CPU client's own thread lines stand in as one device,
    so that the plumbing runs end to end (never a device number)."""
    import jax

    def events_of(line) -> List[Event]:
        return [
            (short_name(e.name), float(e.start_ns), float(e.duration_ns))
            for e in line.events
            if e.duration_ns > 0
        ]

    devices: Dict[int, List[Event]] = {}
    host_spans: List[Event] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(match.group(1))] = events_of(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if cpu_rehearsal and line.name.startswith("tf_XLA"):
                    devices.setdefault(0, []).extend(events_of(line))
                host_spans += [
                    (e.name[len(ANNOTATION_PREFIX):], float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)
                ]
    if cpu_rehearsal:
        n_devices = 1
    if len(devices) < n_devices:
        raise ValueError(
            f"{path}: {len(devices)} device planes with an {OPS_LINE!r} line, the cell uses {n_devices}"
        )
    host_spans.sort(key=lambda e: e[1])
    return TraceEvents([devices[k] for k in sorted(devices)[:n_devices]], host_spans)


def load_kernel_names() -> Dict[str, List[str]]:
    with open(os.path.join(HERE, "kernel_names.json")) as fh:
        return {k: v for k, v in json.load(fh).items() if not k.startswith("_")}


def union_ns(intervals: Sequence[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total length of the union of (start, end) intervals, and the merged
    intervals themselves."""
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return sum(e - s for s, e in merged), merged


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds per operation name, a parent (a `while`, a `conditional`) not
    counting the time of the operations nested inside it."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end_ns, self_ns]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    op_self_s: Dict[str, float]  # averaged over the devices
    kernel_s: Dict[str, float]  # by the match table's keys, averaged over devices
    idle_gaps_s: Dict[str, float]  # idle seconds by the open harness span
    n_devices: int = 1
    kernel_events: Dict[str, int] = field(default_factory=dict)

    def breakdown(self) -> Dict:
        def top(table: Dict[str, float]) -> List[List]:
            rows = sorted(table.items(), key=lambda kv: kv[1], reverse=True)
            return [[name, seconds] for name, seconds in rows[:10]]

        return {"device_ops": top(self.op_self_s), "idle_gaps": top(self.idle_gaps_s)}


def _open_span(host_spans: Sequence[Event], start: float, end: float) -> str:
    """The harness span that covers most of [start, end); the innermost
    wins a tie, since it starts later."""
    best, best_cover = "no_span", 0.0
    for name, s, d in host_spans:
        cover = min(end, s + d) - max(start, s)
        if cover > 0 and cover >= best_cover:
            best, best_cover = name, cover
    return best


def reduce_events(events: TraceEvents, kernel_names: Optional[Dict[str, List[str]]] = None) -> TraceSummary:
    kernel_names = load_kernel_names() if kernel_names is None else kernel_names
    n = len(events.devices)
    all_ops = [e for dev in events.devices for e in dev]
    if not all_ops:
        raise ValueError("the trace holds no device operation: nothing ran on the device")
    if events.host_spans:
        lo = min(s for _, s, _ in events.host_spans)
        hi = max(s + d for _, s, d in events.host_spans)
    else:
        lo = min(s for _, s, _ in all_ops)
        hi = max(s + d for _, s, d in all_ops)
    busy = 0.0
    ops: Dict[str, float] = {}
    kernels: Dict[str, float] = {k: 0.0 for k in kernel_names}
    counts: Dict[str, int] = {k: 0 for k in kernel_names}
    gaps: Dict[str, float] = {}
    patterns = {k: [re.compile(p) for p in pats] for k, pats in kernel_names.items()}
    for dev in events.devices:
        inside = [(max(s, lo), min(s + d, hi)) for _, s, d in dev if s < hi and s + d > lo]
        dev_busy, merged = union_ns(inside)
        busy += dev_busy / 1e9 / n
        for name, seconds in self_times(dev).items():
            ops[name] = ops.get(name, 0.0) + seconds / n
        for name, _, dur in dev:
            for key, pats in patterns.items():
                if any(p.search(name) for p in pats):
                    kernels[key] += dur / 1e9 / n
                    counts[key] += 1
        cursor = lo
        for s, e in merged + [(hi, hi)]:
            if s > cursor:
                label = _open_span(events.host_spans, cursor, s)
                gaps[label] = gaps.get(label, 0.0) + (s - cursor) / 1e9 / n
            cursor = max(cursor, e)
    return TraceSummary(
        busy_s=busy,
        window_s=(hi - lo) / 1e9,
        op_self_s=ops,
        kernel_s=kernels,
        idle_gaps_s=gaps,
        n_devices=n,
        kernel_events=counts,
    )


def describe_xplane(path: str) -> List[str]:
    """Planes, lines and event counts of a trace: what to look at by hand
    before writing a pattern against it."""
    import jax

    rows = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            first = short_name(events[0].name) if events else ""
            rows.append(f"  LINE {line.name!r} events={len(events)} first={first!r}")
    return rows


def record(events: TraceEvents, out_path: str, keep_s: float) -> None:
    """Write the first `keep_s` seconds of a trace in the neutral form, events
    and spans clipped at the cut, for a test to keep beside it."""
    starts = [s for _, s, _ in events.host_spans] or [s for dev in events.devices for _, s, _ in dev]
    cut = min(starts) + keep_s * 1e9

    def clip(rows):
        return [(name, s, min(d, cut - s)) for name, s, d in rows if s < cut]

    kept = TraceEvents([clip(dev) for dev in events.devices], clip(events.host_spans))
    with open(out_path, "w") as fh:
        json.dump(kept.to_json(), fh, separators=(",", ":"))
