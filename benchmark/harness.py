"""The harness a driver works with: the cell found by name, spans, the
measured window with the profiler around it, and the per-layer readers."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def deep_merge(base: Dict, override: Dict) -> Dict:
    out = dict(base)
    for key, value in override.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = deep_merge(out[key], value)
        else:
            out[key] = value
    return out


class Cell:
    """One entry of `workloads`, with its configuration and traffic files."""

    def __init__(self, manifest: Dict, name: str, rehearsal: Optional[Dict]):
        entries = {w["name"]: w for w in manifest["workloads"]}
        if name not in entries:
            raise SystemExit(f"run.py: no workload {name!r}; have {sorted(entries)}")
        entry = entries[name]
        configs = {c["name"]: c for c in manifest["configs"]}
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.config = load_json(os.path.join(CHECKOUT, configs[entry["config"]]["file"]))
        self.traffic_name = entry["traffic"]
        self.traffic = load_json(
            os.path.join(HERE, "traffic", entry["traffic"] + ".json")
        )
        self.rehearsal = rehearsal is not None
        if rehearsal:
            self.config = deep_merge(self.config, rehearsal.get("config", {}))
            self.traffic = deep_merge(self.traffic, rehearsal.get("traffic", {}))
        self.manifest = manifest

    def metrics(self, group: str) -> List[Dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        e2e_here = {
            m["name"]
            for m in self.manifest["end_to_end"]
            if self.name in m.get("workloads", [self.name])
        }
        if group == "end_to_end":
            return [m for m in self.manifest["end_to_end"] if m["name"] in e2e_here]
        return [
            m
            for m in self.manifest["per_layer"]
            if self.name in m.get("workloads", [self.name]) and m["moves"] in e2e_here
        ]


class Harness:
    """What a driver gets: the cell, the arguments, spans, and the window."""

    def __init__(self, cell: Cell, args, device: Dict, devices, process_t0: float) -> None:
        from benchmark.spans import Spans

        self.cell = cell
        self.process_t0 = process_t0
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.tracing = bool(args.trace)
        self.keep_trace = args.keep_trace
        self.device = device
        self.devices = devices
        self.spans = Spans()
        self.counters: Dict[str, float] = {}
        self.samples: Dict[str, List[float]] = {}
        self.end_to_end: Dict[str, float] = {}
        self.checks: List = []
        self.control = bool(getattr(args, "control", 0))
        self.control_checks: List = []  # the same checks with the control in the program's place
        self.attempted = 0
        self.failed = 0
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.trace = None  # trace_reduce.TraceSummary of the traced window
        self._trace_dir = os.path.join(CHECKOUT, ".bench_out", "trace-" + cell.name)

    @property
    def window_seconds(self) -> float:
        """How long this run's window lasts: `--seconds`, or with the
        profiler on the traffic file's shorter `trace_seconds` (a trace of
        the full window is too large to bring back and slows the host)."""
        if self.tracing:
            return min(self.seconds, float(self.cell.traffic.get("trace_seconds", 6.0)))
        return self.seconds

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; with `--trace 1`
        the harness's own profiler runs around the ordinary calls inside."""
        import jax

        self.setup_s = time.perf_counter() - self.process_t0
        if self.tracing:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # a Python-call trace of the window is huge
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self._trace_dir, profiler_options=options)
            self.spans.annotate = True
        t0 = time.perf_counter()
        self.spans.mark_window(t0)
        try:
            yield
        finally:
            self.window_s = time.perf_counter() - t0
            if self.tracing:
                self.spans.annotate = False
                jax.profiler.stop_trace()

    def reduce_trace(self) -> None:
        from benchmark import trace_reduce

        path = trace_reduce.find_xplane(self._trace_dir)
        events = trace_reduce.load_xplane(path, self.cell.chips, cpu_rehearsal=self.cell.rehearsal)
        self.trace = trace_reduce.reduce_events(events)
        if self.keep_trace:
            os.makedirs(self.keep_trace, exist_ok=True)
            stem = os.path.join(self.keep_trace, self.cell.name)
            trace_reduce.record(events, stem + ".trace.json", keep_s=1.0)
            with open(stem + ".planes.txt", "w") as fh:
                fh.write("\n".join(trace_reduce.describe_xplane(path)) + "\n")
        shutil.rmtree(self._trace_dir, ignore_errors=True)

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        return int(max(peaks))


def reader_path(name: str) -> str:
    """The reader file of one per-layer metric, found by the metric's name:
    `metrics/<name>.py`, or for a name with a dotted suffix that has no file
    of its own, `metrics/<name less the suffix>.py`. A quantity split over
    cells with different end-to-end metrics (`cycle_kernel_ms`,
    `cycle_kernel_ms.stream`) is then read by one file."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, "metrics", name.rsplit(".", 1)[0] + ".py")
    return path


def reader(name: str):
    """The reader module of one per-layer metric (names hold dots and dashes,
    so it is loaded by path)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), reader_path(name)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_per_layer(harness: Harness) -> Dict[str, Dict]:
    out = {}
    for metric in harness.cell.metrics("per_layer"):
        value = reader(metric["name"]).read(harness)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
