"""95th percentile of the time from `submit()` to lane admission
(`query_queue` rows, one per query, id = the query) over the queries submitted
in the window, exact from the rows."""

from benchmark import program_spans
from benchmark.spans import percentile


def read(run):
    rows = program_spans.window_rows(run)
    waits = rows.of("query_queue") if rows is not None else ()
    return program_spans.ms(percentile(waits[:, program_spans.DUR].tolist(), 95)) if len(waits) else None
