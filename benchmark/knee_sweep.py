"""Find the knee of a served cell once, on the chip: offer the cell's mix at
each of `--rates` to ONE warm fleet and print, per rate, what completed, the
latencies from the due time and the queue at the window's middle and end. The
knee is the highest rate at which queries complete as fast as they arrive and
the queue is no longer at the end of a window than at its middle; 0.8 of it goes
into the traffic file as a number, with this table in PERF.md.

    python3 benchmark/knee_sweep.py --workload autoscaled.whatif --seed 1 \\
        --seconds 20 --rates 20,40,80
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402
from benchmark import traffic_gen  # noqa: E402
from benchmark.drivers import served_open_loop  # noqa: E402
from benchmark.harness import say  # noqa: E402
from benchmark.spans import percentile  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    bench_run.add_arguments(parser)
    parser.add_argument("--rates", required=True, help="comma-separated queries/s, ascending")
    args = parser.parse_args(argv)
    harness = bench_run.open_harness(args)
    if harness is None:
        return 2
    fleet, ctx = served_open_loop.build(harness)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(harness.cell.traffic)
        traffic["queries"] = {**traffic["queries"], "rate_per_second": rate}
        stream = traffic_gen.query_stream(traffic, harness.seed + k, args.seconds)
        rows, depth = served_open_loop.serve(fleet, harness.spans, stream, ctx["scenarios"], args.seconds)
        s = served_open_loop.summarize(rows, depth, args.seconds)
        say(
            line="knee", offered_per_s=len(stream) / args.seconds,
            completed_per_s=s["queries_per_s"], query_p50_ms=s["query_p50_ms"],
            query_p95_ms=s["query_p95_ms"], queue_mid=s["queue_mid"], queue_end=s["queue_end"],
            failed=s["failed"], gen_late_p95_ms=percentile(s["gen_late_s"], 95) * 1e3,
            drained_at_s=max(r["done"] or 0.0 for r in rows),
        )
    ctx["sentinel"].check("the sweep")
    ctx["sentinel"].uninstall()
    fleet.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
