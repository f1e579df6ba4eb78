"""Driver `batch_jobs`: a closed loop of whole jobs on one resident engine.

A job is every cluster of the batch from t = 0 to the end of its trace; each
cluster runs its own Poisson stream, seeded from (`--seed`, cluster). Between
jobs the engine is reset by the program's own `fleet_reset()`, inside the
window: a user who runs batch after batch pays it. The window ends with the
job that is running when `--seconds` is up, and the rate divides by the
window's real length, so every sample holds every phase of the load curve.

`correct` is decided after the window, on the state the last job left: the
configuration says against which reference (benchmark/configs/*.json,
`guarantees`).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import deployment, program, reference, traffic_gen
from benchmark.harness import say


def _mesh(harness):
    if harness.cell.chips == 1:
        return None
    from jax.sharding import Mesh

    return Mesh(np.array(harness.devices), ("clusters",))


def _engine_kwargs(cell) -> dict:
    kwargs = {**cell.config["engine"], **cell.traffic.get("engine", {})}
    if cell.rehearsal:
        kwargs.update(program.rehearsal_kwargs())
    return kwargs


def _assert_engaged(sim, traffic, stats, counters, slid) -> None:
    """The cell measures the formulation it names, or the run fails."""
    wanted = traffic["asserts"]
    formulation = sim.kernel_formulation()
    for key in ("cycle", "ca_up", "ca_down"):
        if key in wanted and formulation.get(key) != wanted[key]:
            raise SystemExit(f"batch_jobs: {key} is {formulation.get(key)!r}, the cell asserts {wanted[key]!r}")
    facts = {
        "superspans": stats["superspans"] > 0,
        "no_ladder_fallback": stats["ladder_fallbacks"] == 0 and stats["window_chunks"] == 0,
        "feeder_slabs": stats["feeder_slabs_produced"] > 0 and stats["stage_refills"] > 0,
        "pod_window_slid": slid,
        "hpa_scaled_up": counters["total_scaled_up_pods"] > 0,
        "ca_scaled_up_and_down": counters["total_scaled_up_nodes"] > 0
        and counters["total_scaled_down_nodes"] > 0,
    }
    for key, held in facts.items():
        if wanted.get(key) and not held:
            raise SystemExit(f"batch_jobs: the cell asserts {key}; stats {stats}, counters {counters}")


def prepare(cell, seed: int):
    """Host-only work that needs no JAX, started before JAX reaches for the
    chip: the pool that generates and compiles every cluster's trace."""
    config_text = deployment.config_yaml(cell.config_name, cell.config["deployment"])
    n_clusters = int(cell.traffic["clusters_per_chip"]) * cell.chips
    return program.TracePool(
        config_text, cell.config["deployment"], cell.traffic, seed, n_clusters
    ).start()


def run(harness) -> None:
    from kubernetriks_tpu.recompile import RecompileSentinel

    cell, spans = harness.cell, harness.spans
    dep, traffic = cell.config["deployment"], cell.traffic
    guarantees = cell.config["guarantees"]
    n_clusters = int(traffic["clusters_per_chip"]) * cell.chips
    job_end = float(traffic["job_end_s"])
    config_text = deployment.config_yaml(cell.config_name, dep)

    sentinel = RecompileSentinel("raise").install()
    with spans.span("trace_generation"):
        compiled = harness.prepared.result()
    with spans.span("engine_build"):
        sim = program.build_engine(
            config_text, compiled, resettable=True, mesh=_mesh(harness), **_engine_kwargs(cell)
        )

    def job():
        with spans.span("reset"):
            sim.fleet_reset()
        with spans.span("dispatch"):
            sim.step_until_time(job_end)
        with spans.span("fetch"):
            return program.decisions_per_cluster(sim)

    with spans.span("first_dispatch"):
        # A job after a reset can differ in program from the job on the fresh
        # build (a slid pod window recompiles reset and superspan once), so the
        # mix says how many whole jobs warm every program the window drives.
        for _ in range(int(traffic.get("warmup_jobs", 1))):
            expected = job()
    harness.counters["compiles_in_setup"] = len(sentinel.events)
    sentinel.seal("benchmark warm-up: engine build, reset and one whole job")
    say(
        line="setup", clusters=n_clusters, nodes=sim.n_nodes, pods=sim.n_pods,
        formulation=sim.kernel_formulation(), decisions_per_job=int(expected.sum()),
        sim_seconds_per_job=job_end * n_clusters,
        setup_spans_s={k: spans.total(k) for k in ("trace_generation", "engine_build", "first_dispatch")},
        since_process_start_s=time.perf_counter() - harness.process_t0,
    )

    stats_before = dict(sim.dispatch_stats)
    jobs, job_ends = [], []
    with harness.window():
        t0 = time.perf_counter()
        while True:
            jobs.append(job())
            job_ends.append(time.perf_counter() - t0)
            if job_ends[-1] >= harness.window_seconds:
                break
    sentinel.check("the measured window")
    sentinel.uninstall()
    harness.counters["memory_peak_bytes"] = harness.memory_peak_bytes()

    stats = dict(sim.dispatch_stats)
    totals = sim.metrics_summary()["counters"]
    _assert_engaged(sim, traffic, stats, totals, slid=getattr(sim, "_pod_base", 0) > 0)
    floor = int(traffic["asserts"].get("min_decisions_per_cluster", 1))
    if int(expected.min()) < floor:
        raise SystemExit(f"batch_jobs: a cluster committed {int(expected.min())} decisions, under {floor}")

    decisions = int(sum(int(j.sum()) for j in jobs))
    windows_per_job = int(sim.next_window_idx)
    harness.attempted = len(jobs)
    harness.failed = sum(1 for j in jobs if not np.array_equal(j, expected))
    # The mix may name its own end-to-end metric (the stream cell's rate has a
    # bound of its own, because its runs spread twenty times as widely).
    harness.end_to_end[traffic.get("rate_metric", "decisions_per_s")] = decisions / harness.window_s
    harness.counters.update(
        jobs=len(jobs),
        decisions=decisions,
        windows_stepped=windows_per_job * len(jobs),
        windows_per_job=windows_per_job,
        clusters=n_clusters,
        cycle_formulation=sim.kernel_formulation()["cycle"],
        nodes=int(sim.n_nodes),
        pods=int(sim.n_pods),
        max_pods_per_cycle=int(cell.config["engine"]["max_pods_per_cycle"]),
        dispatches_per_job=sum(
            stats[k] - stats_before[k] for k in ("window_chunks", "superspans", "stage_refills")
        )
        / len(jobs),
        sim_seconds_per_wall_second=job_end * n_clusters * len(jobs) / harness.window_s,
    )
    # each job's own seconds: tells a slow process from a slow job (PERF.md, bounds)
    job_s = [b - a for a, b in zip([0.0] + job_ends, job_ends)]
    say(line="window", jobs=len(jobs), window_s=harness.window_s, decisions=decisions, job_s=job_s,
        sim_seconds_per_wall_second=harness.counters["sim_seconds_per_wall_second"],
        dispatch_stats=stats)
    harness.checks.append(
        reference.exactly(
            "jobs_with_other_decisions", harness.failed, 0,
            f"{len(jobs)} jobs, {int(expected.sum())} decisions each",
        )
    )

    t_ref = time.perf_counter()
    if "plain_formulation" in guarantees["reference"]:
        _check_plain_formulation(harness, sim, compiled, config_text, job_end)
    _check_oracle(harness, sim, config_text, n_clusters, job_end)
    harness.counters["reference_s"] = time.perf_counter() - t_ref
    sim.close()


def _check_plain_formulation(harness, sim, compiled, config_text, job_end) -> None:
    """Bit-identity of the whole final state with the program's plain
    formulation, built once from the same traces. It shares step.py with the
    code under test (PERF.md says so); the oracle below does not."""
    cell = harness.cell
    kwargs = {**_engine_kwargs(cell), **program.plain_formulation_kwargs(reclaim=sim.reclaim)}
    kwargs.pop("pallas_interpret", None)
    ref = program.build_engine(config_text, compiled, resettable=True, mesh=_mesh(harness), **kwargs)
    # One window a call: the ladder then compiles its one-window chunk and no
    # other (a whole-job call cuts spans into up to eight chunk shapes, each a
    # cold compile of the whole window body); the final state is the same.
    interval = float(cell.config["deployment"]["scheduling_cycle_interval_s"])
    t = 0.0
    while t < job_end:
        t = min(t + interval, job_end)
        ref.step_until_time(t)
    bad = reference.mismatching_leaves(ref.state, sim.state)
    harness.checks.append(
        reference.exactly("plain_formulation.mismatching_leaves", len(bad), 0, ", ".join(bad[:4]))
    )
    if harness.control:
        interval = float(cell.config["deployment"]["scheduling_cycle_interval_s"])
        bad = reference.mismatching_leaves(ref.state, reference.state_in_float32(sim.state, interval))
        harness.control_checks.append(
            reference.exactly("plain_formulation.mismatching_leaves", len(bad), 0, ", ".join(bad[:4]))
        )
    ref.close()


def _check_oracle(harness, sim, config_text, n_clusters, job_end) -> None:
    """The scalar oracle on a seeded sample of clusters (on several chips, as
    many from each shard), given the same generated events."""
    cell = harness.cell
    dep, traffic, guarantees = cell.config["deployment"], cell.traffic, cell.config["guarantees"]
    per_shard = n_clusters // cell.chips
    want = int(guarantees["oracle_sample_clusters"])
    per_shard_want = max(1, want // cell.chips) if cell.chips > 1 else want
    cluster_records = traffic_gen.cluster_records(dep)
    judged_on_pods = guarantees["reference"] == "oracle"
    judged_counts = traffic.get("judged_oracle_counts", guarantees.get("oracle_counts_exact", []))
    for shard in range(cell.chips):
        taken = 0
        for local in traffic_gen.seeded_order(harness.seed, f"clusters.shard{shard}", per_shard):
            if taken == per_shard_want:
                break
            c = shard * per_shard + local
            try:
                oracle = reference.run_oracle_or_fault(
                    config_text, cluster_records,
                    traffic_gen.workload_records(traffic, harness.seed, c), job_end,
                    sample_every_s=None if judged_on_pods else 50.0,
                )
            except reference.OracleFault as fault:
                say(line="oracle_fault", cluster=c, fault=str(fault), drawn_instead="next in seeded order")
                continue
            taken += 1
            counters = program.cluster_counters(sim, c)
            if judged_on_pods:
                view = program.normalized_pod_view(sim, c)
                limits = (guarantees["counters_exact"], float(guarantees["start_time_tolerance_s"]))
                harness.checks += reference.compare_pods(f"oracle.c{c}", view, counters, oracle, *limits)
                if harness.control:
                    harness.control_checks += reference.compare_pods(
                        f"oracle.c{c}", reference.in_float32(view), counters, oracle, *limits
                    )
                continue
            harness.checks += reference.compare_counts(
                f"oracle.c{c}", counters, oracle, judged_counts, traffic.get("judged_oracle_counts_within")
            )
            say(
                line="ca_trajectory", cluster=c, judged=False,
                oracle_nodes_every_50s=[n for _, n in oracle.node_series],
                oracle={k: oracle.counters[k] for k in sorted(oracle.counters)},
                program={k: counters[k] for k in sorted(oracle.counters)},
            )
        if taken < per_shard_want:
            harness.checks.append(reference.exactly(f"oracle.shard{shard}.clusters_compared", taken, per_shard_want))
