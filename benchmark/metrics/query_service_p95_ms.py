"""95th percentile of the time from lane admission to the drain of the
result (`query_service` rows) over the queries submitted in the window: the
same queries `query_queue_wait_p95_ms` reads, found by the rows' query id."""

import numpy as np

from benchmark import program_spans
from benchmark.program_spans import DUR, IDENT
from benchmark.spans import percentile


def read(run):
    rows = program_spans.window_rows(run)
    if rows is None:
        return None
    submitted = rows.of("query_queue")[:, IDENT]
    service = rows.of("query_service")
    service = service[np.isin(service[:, IDENT], submitted)]
    return program_spans.ms(percentile(service[:, DUR].tolist(), 95)) if len(service) else None
