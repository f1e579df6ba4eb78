"""Seconds the program spent reading the trace files and compiling them to
device slabs before the window opened: the recorder's `trace_ingest` rows
(cli.build_batched_simulation round the native parse and compile_from_arrays).
The rows read and dropped by the validity filter, as the recorder's counters
grew over this run's build, are printed on an `ingest` line."""

from benchmark import program_spans
from benchmark.harness import say


def read(run):
    rows = program_spans.setup_rows(run)
    if rows is None:
        return None
    ingests = rows.of("trace_ingest")
    if not len(ingests):
        return None
    say(line="ingest", spans=len(ingests), rows_read=run.counters.get("trace_ingest_rows"),
        rows_dropped=run.counters.get("trace_ingest_rows_dropped"))
    return float(ingests[:, program_spans.DUR].sum()) / 1e9
