"""Read a served cell's check on many seeds in ONE process: the program's
numbers and the control's, side by side, at the cell's own load.

Set-up of a served cell is long (the fleet and the plain-formulation fleet each
compile), so the readings a limit is set from are taken on one warm fleet: for
each of `--seeds` seeds from `--seed` up, a window of `--seconds` at the cell's
rate (long enough to finish the mix's longest queries), the check's own sample,
each sampled query run again on the plain formulation, and three numbers: the
answers differing, the lane-state leaves mismatching, and the same leaves with
the timed path's times held in float32 (the control). The oracle's counts are
printed beside the program's. The driver never runs this; PERF.md's limits name
the run they came from.

    python3 benchmark/control_sweep.py --workload autoscaled.whatif --seed 500 \\
        --seconds 6 --seeds 12
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402
from benchmark import traffic_gen  # noqa: E402
from benchmark.drivers import served_open_loop as driver  # noqa: E402
from benchmark.harness import say  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    bench_run.add_arguments(parser)
    parser.add_argument("--seeds", type=int, default=12, help="how many seeds, from --seed up")
    args = parser.parse_args(argv)
    harness = bench_run.open_harness(args)
    if harness is None:
        return 2
    cell = harness.cell
    fleet, ctx = driver.build(harness)
    ctx["sentinel"].uninstall()  # the plain fleet compiles below; nothing here is timed
    want = int(cell.config["guarantees"]["oracle_sample_clusters"])
    plain = driver.build_plain(cell, ctx, fleet, want)
    for seed in range(harness.seed, harness.seed + args.seeds):
        stream = traffic_gen.query_stream(cell.traffic, seed, args.seconds)
        sample = driver.sample_positions(cell, stream, seed)
        rows, depth = driver.serve(fleet, harness.spans, stream, ctx["scenarios"], args.seconds,
                                   keep=set(sample), read_lane=ctx["read_lane"])
        kept = driver.kept_rows(rows, sample)
        again = driver.run_again(plain, ctx, stream, kept)
        differing, leaves = driver.compare_with_plain(cell, kept, again)
        _, control_leaves = driver.compare_with_plain(cell, kept, again, control=True)
        by_query = [sum(leaf.startswith(f"q{r['index']}:") for leaf in control_leaves) for r in kept]
        harness.checks = []
        driver.check_oracle_counts(harness, cell, ctx, stream, kept)
        summary = driver.summarize(rows, depth, args.seconds)
        say(
            line="reading", seed=seed, queries=len(rows), failed=summary["failed"], compared=len(kept),
            horizons=[stream[r["index"]][2] for r in kept],
            queries_differing=differing, mismatching_leaves=len(leaves), which=leaves[:12],
            control_mismatching_leaves=len(control_leaves), control_leaves_by_query=by_query,
            oracle_checks=[c.row() for c in harness.checks],
            query_p50_ms=summary["query_p50_ms"], query_p95_ms=summary["query_p95_ms"],
        )
    plain[0].close()
    fleet.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
