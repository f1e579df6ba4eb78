"""Host time per job in which the device has nothing queued on the stream
path: inside each `step_until_time` span of the window, from the call's start
to its first `superspan`, from the end of each `progress_wait` (the blocking
readback that says the superspan is done) to the start of the next
`superspan`, and from the last `progress_wait` to the call's end; summed per
call, median over the window's calls. The stage and growth spans that lie in
those gaps are printed as shares of them on a `superspan_gap` line."""

from benchmark import program_spans
from benchmark.harness import say
from benchmark.program_spans import DUR, T0
from benchmark.spans import median

IN_GAPS = ("stage_wait_feeder", "stage_wait_upload", "stage_prefetch", "stage_assemble", "stage_put",
           "window_grow")


def gaps_of(call, supers, waits):
    """[(start_ns, end_ns)] of one call's unqueued intervals."""
    edges = sorted([(int(r[T0]), "dispatch") for r in supers] + [(int(r[T0] + r[DUR]), "done") for r in waits])
    gaps, idle_since = [], int(call[T0])
    for t, kind in edges:
        if kind == "dispatch" and idle_since is not None:
            gaps.append((idle_since, t))
            idle_since = None
        elif kind == "done":
            idle_since = t
    if idle_since is not None:
        gaps.append((idle_since, int(call[T0] + call[DUR])))
    return gaps


def read(run):
    rows = program_spans.window_rows(run)
    if rows is None:
        return None
    supers, waits, stage_work = rows.of("superspan"), rows.of("progress_wait"), rows.of(*IN_GAPS)
    per_job, in_gaps = [], {}
    for call in rows.of("step_until_time"):
        mine = program_spans.inside(supers, call)
        if not len(mine):
            continue
        gaps = gaps_of(call, mine, program_spans.inside(waits, call))
        per_job.append(sum(end - start for start, end in gaps))
        for row in program_spans.inside(stage_work, call):
            covered = sum(
                max(0, min(end, int(row[T0] + row[DUR])) - max(start, int(row[T0]))) for start, end in gaps
            )
            if covered:
                in_gaps[rows.name(row)] = in_gaps.get(rows.name(row), 0) + covered
    if not per_job:
        return None
    total = sum(per_job)
    say(line="superspan_gap", jobs=len(per_job), gap_ms_per_job=[program_spans.ms(g) for g in per_job],
        shares={name: ns / total for name, ns in sorted(in_gaps.items())} if total else {})
    return program_spans.ms(median(per_job))
