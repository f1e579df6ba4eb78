"""How late the load generator ran: 95th percentile of sent minus due."""

from benchmark.spans import percentile


def read(run):
    late = run.samples.get("gen_late_s")
    return percentile(late, 95) * 1e3 if late else None
