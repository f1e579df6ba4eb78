"""Driver `served_open_loop`: what-if queries offered at a fixed rate to a
resident lane-async fleet, from one thread.

A query is a scenario (control-law overrides from a catalogue, Zipf
popularity) and a horizon; every lane replays one base workload. Queries are due on a schedule fixed by the traffic file's rate
(traffic_gen.query_stream), whatever the fleet does, and a query's latency
runs from the instant it was DUE to the `poll()` that returned it. How late the
generator sent is reported beside it. After the window closes nothing more is
sent and the queries already due are drained, so the tail is the tail of all
requests; one that fails, is rejected or outlives the drain limit counts as
missing every latency limit.

`correct` is decided after the window on a sample of the returned queries
fixed before it: the lane's final state is read as `poll()` returns such a
query (a handful of 0.1 MB fetches a window), and the query is run again on the
program's plain formulation and through the scalar oracle.
"""

from __future__ import annotations

import math
import time

from benchmark import deployment, program, reference, traffic_gen
from benchmark.harness import say
from benchmark.spans import median, percentile

DRAIN_LIMIT_S = 60.0
IDLE_SLEEP_S = 0.0005


def _base_workload(cell, seed):
    """The one workload every lane replays: seeded by the mix where it fixes
    `base_workload_seed` (so that the work does not move with `--seed`, which
    then only orders the queries), else by `--seed`."""
    api = program.program_api()
    dep = cell.config["deployment"]
    cluster_records = traffic_gen.cluster_records(dep)
    workload_seed = int(cell.traffic.get("base_workload_seed", seed))
    workload_records = traffic_gen.workload_records(cell.traffic, workload_seed, 0)
    return (
        cluster_records,
        workload_records,
        traffic_gen.to_events(cluster_records, api),
        traffic_gen.to_events(workload_records, api),
    )


def _build_fleet(cell, config_text, cluster_events, workload_events, lanes, horizon, **forced):
    from kubernetriks_tpu.batched.fleet import ScenarioFleet

    kwargs = {**cell.config["engine"], **cell.traffic.get("engine", {}), **forced}
    if cell.rehearsal and "use_pallas" not in forced:
        kwargs.update(program.rehearsal_kwargs(lane_async=True))
    config = program.program_api().SimulationConfig.from_yaml(config_text)
    return ScenarioFleet(
        config, cluster_events, workload_events, n_lanes=lanes, horizon=horizon,
        lane_async=True, span_windows=int(cell.traffic["span_windows"]), **kwargs,
    )


def serve(fleet, spans, stream, scenarios, seconds, drain_limit_s=DRAIN_LIMIT_S, keep=(), read_lane=None):
    """Offer `stream` [(due_s, scenario index, horizon_s)] on its schedule
    for `seconds`, then drain. Returns one row per query and the queue depth
    (sent and not yet returned) sampled at every round. For the stream
    positions in `keep`, the lane's final state is read (`read_lane`) when
    `poll()` returns the query, before the next `pump()` can reseed the lane:
    the check's sample, a handful of small fetches a window."""
    rows = {}  # qid -> dict(due, sent, done, ok, index)
    depth = []  # (t, outstanding)
    t0 = time.perf_counter()
    i, returned = 0, 0
    while True:
        now = time.perf_counter() - t0
        while i < len(stream) and stream[i][0] <= now:
            due, scen, horizon = stream[i]
            qid = fleet.submit(scenarios[scen], horizon)
            rows[qid] = dict(index=i, due=due, sent=time.perf_counter() - t0, done=None, ok=False)
            i += 1
        outstanding = i - returned
        depth.append((now, outstanding))
        if outstanding:
            with spans.span("pump"):
                fleet.pump()
            with spans.span("poll"):
                outcomes = fleet.poll()
            t_back = time.perf_counter() - t0
            for outcome in outcomes:
                row = rows[outcome.query]
                row.update(done=t_back, ok=bool(outcome.ok), outcome=outcome)
                if row["index"] in keep and outcome.ok:
                    with spans.span("state_fetch"):
                        row["state"] = read_lane(outcome.lane)
            returned += len(outcomes)
        elif i < len(stream):
            with spans.span("generator_sleep"):
                time.sleep(min(IDLE_SLEEP_S, max(0.0, stream[i][0] - now)))
        if now >= seconds and (outstanding == 0 or now >= seconds + drain_limit_s):
            break
        if i >= len(stream) and outstanding == 0:
            break
    return [rows[q] for q in sorted(rows)], depth


def summarize(rows, depth, seconds):
    """The window's end-to-end numbers from the per-query rows."""
    latencies = [
        (r["done"] - r["due"]) if (r["ok"] and r["done"] is not None) else math.inf
        for r in rows
    ]
    done_in_window = sum(1 for r in rows if r["ok"] and r["done"] is not None and r["done"] <= seconds)
    mid = [n for t, n in depth if 0.45 * seconds <= t <= 0.55 * seconds]
    end = [n for t, n in depth if 0.9 * seconds <= t <= seconds]
    return dict(
        queries_per_s=done_in_window / seconds,
        query_p50_ms=percentile(latencies, 50) * 1e3,
        query_p95_ms=percentile(latencies, 95) * 1e3,
        gen_late_s=[r["sent"] - r["due"] for r in rows],
        failed=sum(1 for lat in latencies if math.isinf(lat)),
        queue_mid=median(mid) if mid else 0.0,
        queue_end=median(end) if end else 0.0,
    )


def make_scenarios(traffic):
    from kubernetriks_tpu.batched.fleet import Scenario

    catalogue = traffic_gen.scenario_catalogue(int(traffic["queries"]["catalogue_size"]))
    return catalogue, [Scenario(**overrides) for overrides in catalogue]


def build(harness):
    """Set-up up to a warm fleet: base workload from the seed, fleet build,
    one short query stream through every program the window can touch."""
    from kubernetriks_tpu.recompile import RecompileSentinel

    cell, spans = harness.cell, harness.spans
    traffic = cell.traffic
    config_text = deployment.config_yaml(cell.config_name, cell.config["deployment"])
    horizon = max(float(h) for h in traffic["queries"]["horizons_s"])
    sentinel = RecompileSentinel("raise").install()
    with spans.span("trace_generation"):
        cluster_records, workload_records, cluster_events, workload_events = _base_workload(cell, harness.seed)
    with spans.span("engine_build"):
        fleet = _build_fleet(cell, config_text, cluster_events, workload_events, int(traffic["lanes"]), horizon)
    catalogue, scenarios = make_scenarios(traffic)
    formulation = fleet.engine.kernel_formulation()
    for key, wanted in traffic["asserts"].items():
        if formulation.get(key) != wanted:
            raise SystemExit(f"served_open_loop: {key} is {formulation.get(key)!r}, the cell asserts {wanted!r}")
    with spans.span("first_dispatch"):
        n_warm = int(traffic["queries"]["warmup_queries"])
        warm = [(0.0, s, h) for _, s, h in traffic_gen.query_stream(
            {"queries": {**traffic["queries"], "rate_per_second": n_warm}}, harness.seed + 1, 1.0)]
        read_lane = program.lane_reader(fleet)
        rows, _ = serve(fleet, spans, warm, scenarios, 0.0, drain_limit_s=math.inf, keep={0}, read_lane=read_lane)
        bad = [r.get("outcome") for r in rows if not r["ok"]]
        if bad:
            raise SystemExit(f"served_open_loop: {len(bad)} warm-up queries failed, first: {bad[0]!r}")
    harness.counters["compiles_in_setup"] = len(sentinel.events)
    sentinel.seal("benchmark warm-up: fleet build and one short query stream")
    say(line="setup", lanes=int(traffic["lanes"]), nodes=fleet.engine.n_nodes, pods=fleet.engine.n_pods,
        formulation=formulation, warmup_queries=len(warm), base_workload_events=len(workload_records),
        setup_spans_s={k: spans.total(k) for k in ("trace_generation", "engine_build", "first_dispatch")},
        since_process_start_s=time.perf_counter() - harness.process_t0)
    ctx = dict(config_text=config_text, cluster_records=cluster_records, workload_records=workload_records,
               cluster_events=cluster_events, workload_events=workload_events, horizon=horizon,
               catalogue=catalogue, scenarios=scenarios, sentinel=sentinel, read_lane=read_lane)
    return fleet, ctx


def sample_positions(cell, stream, seed):
    """The stream positions the check compares, fixed before the window: the
    first query of the longest horizon and a seeded draw of the others."""
    k = int(cell.config["guarantees"]["oracle_sample_clusters"])
    longest = max(range(len(stream)), key=lambda i: (stream[i][2], -i))
    order = traffic_gen.seeded_order(seed, "queries", len(stream))
    return [longest] + [i for i in order if i != longest][: k - 1]


def kept_rows(rows, sample):
    """The rows of the sampled positions whose lane state was read."""
    by_position = {r["index"]: r for r in rows}
    return [by_position[i] for i in sample if "state" in by_position.get(i, ())]


def run(harness) -> None:
    cell, spans = harness.cell, harness.spans
    traffic = cell.traffic
    fleet, ctx = build(harness)
    stream = traffic_gen.query_stream(traffic, harness.seed, harness.window_seconds)
    busy_before = int(fleet.lane_busy_windows.sum())
    sample = sample_positions(cell, stream, harness.seed)
    with harness.window():
        rows, depth = serve(fleet, spans, stream, ctx["scenarios"], harness.window_seconds,
                            keep=set(sample), read_lane=ctx["read_lane"])
    ctx["sentinel"].check("the measured window")
    ctx["sentinel"].uninstall()
    harness.counters["memory_peak_bytes"] = harness.memory_peak_bytes()

    seconds = harness.window_seconds
    summary = summarize(rows, depth, seconds)
    harness.samples["gen_late_s"] = summary["gen_late_s"]
    harness.attempted = len(rows)
    harness.failed = summary["failed"]
    for key in ("queries_per_s", "query_p50_ms", "query_p95_ms"):
        harness.end_to_end[key] = summary[key]
    harness.counters.update(
        queries=len(rows),
        offered_per_s=len(stream) / seconds,
        lane_windows=int(fleet.lane_busy_windows.sum()) - busy_before,
        pump_rounds=len(spans.durations("pump")),
        queue_mid=summary["queue_mid"],
        queue_end=summary["queue_end"],
        drain_s=harness.window_s - seconds,
        lane_occupancy=fleet.lane_occupancy().get("mean", 0.0),
    )
    say(line="window", queries=len(rows), failed=summary["failed"], window_s=harness.window_s,
        queue_mid=summary["queue_mid"], queue_end=summary["queue_end"],
        **{k: summary[k] for k in ("queries_per_s", "query_p50_ms", "query_p95_ms")})
    harness.checks.append(reference.exactly("queries_failed", summary["failed"], 0, f"{len(rows)} queries"))

    t_ref = time.perf_counter()
    kept = kept_rows(rows, sample)
    plain = build_plain(cell, ctx, fleet, len(kept))
    check_against_plain(harness, cell, plain, ctx, stream, kept)
    plain[0].close()
    check_oracle_counts(harness, cell, ctx, stream, kept)
    harness.counters["reference_s"] = time.perf_counter() - t_ref
    fleet.close()


def build_plain(cell, ctx, fleet, lanes):
    """The program's plain formulation as a fleet of `lanes` lanes over the
    same base workload, and its lane reader."""
    plain = _build_fleet(
        cell, ctx["config_text"], ctx["cluster_events"], ctx["workload_events"], max(1, lanes),
        ctx["horizon"], **program.plain_formulation_kwargs(reclaim=fleet.engine.reclaim),
    )
    return plain, program.lane_reader(plain)


def run_again(plain, ctx, stream, kept):
    """Each kept query once more, under the same scenario and horizon, on the
    plain fleet: [(outcome, final lane state)] in the order of `kept`."""
    plain_fleet, read_lane = plain
    qids = {}
    for n, row in enumerate(kept):
        _, scen, horizon = stream[row["index"]]
        qids[plain_fleet.submit(ctx["scenarios"][scen], horizon)] = n
    again = {}
    while len(again) < len(qids):
        plain_fleet.pump()
        for outcome in plain_fleet.poll():
            again[qids[outcome.query]] = (outcome, read_lane(outcome.lane) if outcome.ok else None)
    return [again[n] for n in range(len(kept))]


def _answers(outcome):
    return outcome.ok and (outcome.counters, outcome.hpa_replicas, outcome.ca_nodes)


def compare_with_plain(cell, kept, again, control=False):
    """(queries whose returned answer differs, the mismatching state leaves of
    all the queries as `q<position>:<leaf>`). With `control`, the timed
    path's states are first held in float32 time (reference.state_in_float32):
    no integer answer moves, the state does."""
    interval = float(cell.config["deployment"]["scheduling_cycle_interval_s"])
    differing, leaves = 0, []
    for row, (outcome, state) in zip(kept, again):
        differing += int(_answers(outcome) != _answers(row["outcome"]))
        if state is None:
            leaves.append(f"q{row['index']}:<no result>")
            continue
        mine = reference.state_in_float32(row["state"], interval) if control else row["state"]
        leaves += [f"q{row['index']}:{leaf}" for leaf in reference.mismatching_leaves(state, mine)]
    return differing, leaves


def check_against_plain(harness, cell, plain, ctx, stream, kept) -> None:
    """The sample of returned queries (fixed before the window, the longest in
    it), each run again on the plain formulation: the answers a user reads
    exactly, and the lane's whole final state, times included, bit for bit."""
    again = run_again(plain, ctx, stream, kept)
    differing, leaves = compare_with_plain(cell, kept, again)
    note = f"{len(kept)} queries run again"
    harness.checks.append(reference.exactly("plain_formulation.queries_differing", differing, 0, note))
    harness.checks.append(
        reference.exactly(
            "plain_formulation.mismatching_leaves", len(leaves), 0,
            ", ".join(leaves[:4]) or note + ", every leaf of the lane's final state",
        )
    )
    want = int(cell.config["guarantees"]["oracle_sample_clusters"])
    harness.checks.append(reference.exactly("plain_formulation.queries_compared", len(kept), want))
    if harness.control:
        _, leaves = compare_with_plain(cell, kept, again, control=True)
        harness.control_checks.append(
            reference.exactly("plain_formulation.mismatching_leaves", len(leaves), 0, ", ".join(leaves[:4]))
        )


def check_oracle_counts(harness, cell, ctx, stream, kept) -> None:
    """The scalar oracle on the same sample, for the counts that agree today."""
    guarantees = cell.config["guarantees"]
    dep = cell.config["deployment"]
    judged = cell.traffic.get("judged_oracle_counts", guarantees["oracle_counts_exact"])
    for row in kept:
        _, scen, horizon = stream[row["index"]]
        text = deployment.config_yaml(cell.config_name, dep, ctx["catalogue"][scen])
        try:
            oracle = reference.run_oracle_or_fault(text, ctx["cluster_records"], ctx["workload_records"], horizon)
        except reference.OracleFault as fault:
            say(line="oracle_fault", query=row["index"], fault=str(fault))
            continue
        counters = row["outcome"].counters
        mine = dict(
            pods_succeeded=int(counters["pods_succeeded"]),
            total_scaled_up_pods=int(counters["scaled_up_pods"]),
            total_scaled_down_pods=int(counters["scaled_down_pods"]),
        )
        label = f"oracle.q{row['index']}.h{int(horizon)}"
        harness.checks += reference.compare_counts(label, mine, oracle, judged)
        say(line="oracle_counts", query=row["index"], horizon_s=horizon, scenario=scen, judged=list(judged),
            program=mine, oracle={k: oracle.counters[k] for k in mine})
