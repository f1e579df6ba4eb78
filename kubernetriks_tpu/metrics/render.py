"""One JSON/table rendering path for every end-of-run report (PR 8
satellite).

Before this module, the scalar printer owned a hand-rolled table with
hardcoded row labels, the batched engine printed raw json.dumps, and the
telemetry report had no renderer at all. Everything now renders through
`render_metrics` / `render_telemetry`: a report is a dict shaped
`{"counters": {...}, "timings": {name: {min,max,mean,variance}}}` (the
schema both `metrics/printer.metrics_as_dict` and
`BatchedSimulation.metrics_summary` already emit), and the format is a
CLI choice (`--report json|table`), not a backend property."""

from __future__ import annotations

import json
from typing import Any, Dict, List


def format_table(rows: List[list], header: List[str]) -> str:
    """Aligned ASCII table (the scalar printer's format, reference:
    src/metrics/printer.rs:20-164) — the one table formatter."""
    widths = [
        max(len(str(row[i])) for row in [header] + rows)
        for i in range(len(header))
    ]

    def fmt_row(row):
        return (
            "| "
            + " | ".join(str(v).ljust(w) for v, w in zip(row, widths))
            + " |"
        )

    sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
    lines = [sep, fmt_row(header), sep]
    lines += [fmt_row(row) for row in rows]
    lines.append(sep)
    return "\n".join(lines)


# Keys whose generic snake_case -> label transform would drop meaning
# (units); pinned to the labels the scalar table always printed.
_LABELS = {
    "node_downtime_s": "Node downtime (s)",
}


def humanize(key: str) -> str:
    """snake_case metric key -> row label ("pod_queue_time" ->
    "Pod queue time"), matching the labels the scalar table always
    printed."""
    return _LABELS.get(key, key.replace("_", " ").capitalize())


def render_metrics(d: Dict[str, Any], fmt: str) -> str:
    """Render a {"counters", "timings"} report dict as "json" or "table".
    Scalar and batched runs share this path, so both backends emit the
    same schema in the same two shapes."""
    if fmt == "json":
        return json.dumps(d, indent=2, default=float)
    if fmt != "table":
        raise ValueError(f"unknown report format {fmt!r} (json|table)")
    parts = []
    counters = d.get("counters")
    if counters:
        parts.append(
            format_table(
                [[humanize(k), v] for k, v in counters.items()],
                ["Metric", "Count"],
            )
        )
    timings = d.get("timings")
    if timings:
        parts.append(
            format_table(
                [
                    [
                        humanize(name),
                        *(stats[k] for k in ("min", "max", "mean", "variance")),
                    ]
                    for name, stats in timings.items()
                ],
                ["Metric", "Min", "Max", "Mean", "Variance"],
            )
        )
    return "\n".join(parts)


def render_telemetry(rep: Dict[str, Any], fmt: str) -> str:
    """Render engine.telemetry_report() as "json" or "table": the
    per-phase span table, the dispatch stats, the sync budget, the
    device-ring totals, and each program's instructions by device phase."""
    if fmt == "json":
        return json.dumps(rep, indent=2, default=float)
    if fmt != "table":
        raise ValueError(f"unknown report format {fmt!r} (json|table)")
    parts = []
    spans = rep.get("spans")
    if spans:
        parts.append(
            format_table(
                [
                    [
                        name,
                        s["count"],
                        round(s["total_ms"], 3),
                        round(s["mean_us"], 1),
                        round(s["max_us"], 1),
                    ]
                    for name, s in spans.items()
                ],
                ["Phase", "Count", "Total ms", "Mean µs", "Max µs"],
            )
        )
    rows = [[humanize(k), v] for k, v in rep.get("dispatch_stats", {}).items()]
    rows += [
        [humanize(k), v] for k, v in rep.get("sync_budget", {}).items()
    ]
    rows += [[humanize(k), v] for k, v in rep.get("counters", {}).items()]
    ring = rep.get("ring")
    if ring:
        rows += [
            ["Ring windows recorded", ring["windows_recorded"]],
            ["Ring windows kept", ring["windows_kept"]],
        ]
        rows += [
            [f"Ring total {humanize(k).lower()}", v]
            for k, v in ring.get("totals", {}).items()
        ]
        rows += [
            [f"Ring high-water {humanize(k).lower()}", v]
            for k, v in ring.get("high_water", {}).items()
        ]
    for program, counts in rep.get("device_phases", {}).get("programs", {}).items():
        # Instructions of each dispatched program by device phase (after
        # recorder().program_phases() has read the compiled text).
        rows += [
            [f"Device phase {phase} ops, {program}", n] for phase, n in counts.items()
        ]
    for program, counts in rep.get("device_phases", {}).get("gathers", {}).items():
        # Of them, the XLA gathers: per index on the TPU, whatever they read.
        rows += [
            [f"Device phase {phase} gathers, {program}", n] for phase, n in counts.items()
        ]
    resources = rep.get("resources")
    if resources:
        # Capacity-observatory summary: occupancy vs reserve, memory
        # watermarks, watchdog verdicts (full detail stays in the JSON).
        for name, entry in resources.get("occupancy", {}).items():
            if isinstance(entry, dict) and "used_max" in entry:
                cap = entry.get("capacity_min")
                rows.append(
                    [
                        f"Occupancy {humanize(name).lower()}",
                        f"{entry['used_max']}/{cap}" if cap else entry["used_max"],
                    ]
                )
        mem = resources.get("memory", {})
        if mem.get("rss_bytes"):
            rows.append(["Host RSS (MB)", round(mem["rss_bytes"] / 1e6, 1)])
        fired = resources.get("watchdog", {}).get("fired", {})
        rows.append(["Watchdog verdicts fired", len(fired)])
    if rows:
        parts.append(format_table(rows, ["Metric", "Count"]))
    return "\n".join(parts)
