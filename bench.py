"""Headline benchmark: pod-scheduling decisions/second on the batched backend.

Prints one JSON line per tracked shape; the LAST line is the headline:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Shapes:
- 1024 x 256-node clusters — the BASELINE.md tracked "1024x256-node vmap
  batch on single TPU" config.
- composed flagship: 256 clusters x (HPA pod group + cluster autoscaler +
  sliding pod window + Pallas kernels) — the composed-path tracker (r4);
  regressions in autoscaler passes / window slides / segmented slots show
  here even when the pure-scheduler shapes hold. This line times >= 5
  repeated spans and reports the MEDIAN, with the min/max spread in a
  "spans" field on the same JSON line (cold-outlier robustness, r5
  VERDICT weakness #2).
- 1250 x 1000-node clusters — the NORTH-STAR per-chip share: >=10k
  concurrent 1000-node clusters on a v5e-8 is 1250 per chip
  (BASELINE.json). vs_baseline is computed on this line (the LAST line).

The reference publishes no benchmark numbers (BASELINE.md); vs_baseline is
measured against the driver-set north star of 1M decisions/s on a v5e-8,
i.e. 125k decisions/s per chip (BASELINE.json).

Scenario per shape: Poisson pod arrivals (2 pods/s for 1000 s, ~2k pods per
cluster), default kube-scheduler filter/score, stepped in 20-window device
chunks.

`--smoke` runs the tracked lines at CPU-safe toy shapes (tiny batches,
short horizons, no ladder precompile) purely to prove the bench plumbing
runs and parses end-to-end — the values are meaningless as performance
numbers — plus a superspan-MACHINERY line (scanned executor forced on,
in-bench asserts fail on silent fallback to the ladder), a
streaming-FEEDER line (superspan + the bounded-ring trace-ingestion
pipeline forced on, in-bench asserts fail on silent fallback to
whole-trace staging), and a compiled-PROFILE line (the best_fit scheduler
profile lowered into the decision kernels, in-bench asserts fail on
silent fallback to the default pipeline). tests/test_bench_smoke.py pins
it under JAX_PLATFORMS=cpu.

`--profile NAME` runs every tracked line under a named scheduler profile
(core/scheduler/kube_scheduler.NAMED_PROFILE_SPECS), compiled into the
scan and Pallas kernel paths at engine build (batched/pipeline.py).

`--sweep [N]` runs the scenario-vector fleet line standalone: N (default
64) heterogeneous what-if scenarios — per-lane HPA/CA control-law
parameters as traced (C,) data (batched/fleet.py) — through ONE resident
engine vs the one-process-per-scenario baseline, asserting zero
post-warm-up recompiles and zero lane cross-talk in-bench and writing
the full record to the KTPU_SWEEP_PATH JSON artifact. `--smoke` runs an
8-scenario/4-lane variant as its last line.

`--trace` arms the flight recorder (kubernetriks_tpu/telemetry) on the
composed lines: the JSON record gains a "telemetry" summary (per-phase
host wall time, observed syncs vs the documented steady-state budget,
dispatch stats, device-ring totals) and each traced line writes a
Perfetto-loadable Chrome trace next to the bench (KTPU_TRACE_PATH stem).
Telemetry-on is bit-identical to telemetry-off and gated <3% overhead
(tests/test_telemetry.py), so the traced number IS the tracked number.
"""

import json
import os
import sys
import time
import warnings

import numpy as np

BASELINE_DECISIONS_PER_SEC_PER_CHIP = 1_000_000 / 8


def _assert_profile_compiled(sim, profile, ctx: str) -> None:
    """Loud no-silent-fallback contract for --profile lines: the requested
    scheduler profile REALLY compiled into the pipeline (the bug class the
    compiled-profile subsystem kills), mirroring the superspan/streaming
    smoke asserts. No-op when no profile was requested."""
    if profile is None:
        return
    from kubernetriks_tpu.batched.pipeline import DEFAULT_PROFILE

    assert sim.profile.name == profile, (
        f"{ctx}: requested scheduler profile {profile!r} but the engine "
        f"compiled {sim.profile.name!r}"
    )
    assert profile == "default" or sim.profile != DEFAULT_PROFILE, (
        f"{ctx}: non-default profile silently fell back to the default "
        "pipeline"
    )


def _shape_inputs(n_nodes: int, horizon: float = 1000.0):
    """The pure-scheduling scenario's (config, cluster events, workload
    events) — shared by run_shape and chip_smoke.py, so the smoke drives
    EXACTLY the tracked line's traces."""
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )

    config = SimulationConfig.from_yaml(
        "sim_name: bench\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(n_nodes, cpu=64000, ram=128 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=2.0,
        horizon=horizon,
        seed=3,
        cpu=4000,
        ram=8 * 1024**3,
        duration_range=(30.0, 120.0),
    )
    return (
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
    )


def run_shape(
    n_clusters: int,
    n_nodes: int,
    *,
    horizon: float = 1000.0,
    warm_until: float = 190.0,
    t_end: float = 1200.0,
    step: float = 200.0,
    profile: str = None,  # --profile: named scheduler profile (None = default)
) -> float:
    from kubernetriks_tpu.batched.engine import build_batched_from_traces

    config, cluster_events, workload = _shape_inputs(n_nodes, horizon)
    sim = build_batched_from_traces(
        config,
        cluster_events,
        workload,
        n_clusters=n_clusters,
        max_pods_per_cycle=64,
        scheduler_profile=profile,
    )
    _assert_profile_compiled(sim, profile, "bench")

    def decisions_now() -> int:
        # Device->host fetch of the (C,) decisions counter: a REAL sync
        # point, so no device work leaks past the clock stop.
        return int(np.asarray(sim.state.metrics.scheduling_decisions).sum())

    # Warm-up: the default 0..190 is 20 windows — the exact chunk shape the
    # timed loop dispatches, so no compilation happens inside the measured
    # region.
    sim.step_until_time(warm_until)
    decisions_before = decisions_now()

    t0 = time.perf_counter()
    end = warm_until + step
    while end <= t_end:
        sim.step_until_time(end)  # fixed-size window chunks
        end += step
    decisions = decisions_now() - decisions_before
    elapsed = time.perf_counter() - t0
    return decisions / elapsed


# --faults: chaos-engine block appended to the composed config so the fault
# path (crash/recover slab events, per-attempt failure draws, CrashLoopBackOff
# requeues) gets its own measured dispatch/throughput line.
FAULTS_YAML = """
fault_injection:
  enabled: true
  node:
    mttf: 900.0
    mttr: 120.0
  pod:
    fail_prob: 0.05
    restart_limit: 3
"""


COMPOSED_GROUP_YAML = """
events:
- timestamp: 49.5
  event_type:
    !CreatePodGroup
      pod_group:
        name: grp
        initial_pod_count: 8
        max_pod_count: {max_pods}
        pod_template:
          metadata: {{name: grp}}
          spec:
            resources:
              requests: {{cpu: 8000, ram: 17179869184}}
              limits: {{cpu: 8000, ram: 17179869184}}
        target_resources_usage: {{cpu_utilization: 0.5}}
        resources_usage_model_config:
          cpu_config:
            model_name: pod_group
            config: |
              - duration: {d1}
                total_load: 4.0
              - duration: {d2}
                total_load: 24.0
              - duration: {d3}
                total_load: 2.0
"""


def _composed_inputs(
    n_nodes: int,
    *,
    rate_per_second: float,
    horizon: float,
    max_group_pods: int,
    burst: tuple,
    faults: bool = False,
):
    """The composed flagship scenario's (config, cluster events, workload
    events)."""
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )
    from kubernetriks_tpu.trace.generic import GenericWorkloadTrace

    faults_block = FAULTS_YAML if faults else ""
    config = SimulationConfig.from_yaml(
        f"""
sim_name: bench_composed
seed: 1
scheduling_cycle_interval: 10.0
horizontal_pod_autoscaler:
  enabled: true
cluster_autoscaler:
  enabled: true
  scan_interval: 10.0
  max_node_count: {n_nodes}
  node_groups:
  - node_template:
      metadata: {{name: ca_node}}
      status: {{capacity: {{cpu: 64000, ram: 137438953472}}}}
{faults_block}
"""
    )
    cluster = UniformClusterTrace(n_nodes, cpu=64000, ram=128 * 1024**3)
    # Plain load ~88% of base capacity: the HPA burst pushes past it, so
    # pods park and the CA provisions (and later retires) template nodes.
    plain = PoissonWorkloadTrace(
        rate_per_second=rate_per_second,
        horizon=horizon,
        seed=3,
        cpu=16000,
        ram=32 * 1024**3,
        duration_range=(30.0, 120.0),
        name_prefix="plain",
    )
    group = GenericWorkloadTrace.from_yaml(
        COMPOSED_GROUP_YAML.format(
            max_pods=max_group_pods, d1=burst[0], d2=burst[1], d3=burst[2]
        )
    ).convert_to_simulator_events()
    workload = sorted(
        plain.convert_to_simulator_events() + group, key=lambda e: e[0]
    )
    return config, cluster.convert_to_simulator_events(), workload


def run_composed(
    n_clusters: int = 256,
    n_nodes: int = 32,
    *,
    rate_per_second: float = 1.5,
    horizon: float = 1000.0,
    pod_window: int = 512,
    warm_until: float = 590.0,
    t_end: float = 1200.0,
    step: float = 100.0,
    max_group_pods: int = 64,
    burst: tuple = (300.0, 300.0, 400.0),
    precompile: bool = True,
    use_pallas=True,  # True force-on (hardware bench), False off, None auto
    faults: bool = False,
    superspan=None,  # tri-state like use_pallas; True also asserts it engaged
    stream=None,  # tri-state; True also asserts the feeder really staged
    stream_segment=None,  # staging-slab width (columns); None = 4W default
    stream_depth=None,  # feeder ring capacity K; None = registry default
    mesh=None,  # jax.sharding.Mesh: shard the cluster batch (bench_mesh.py)
    fast_forward=None,
    trace: bool = False,  # --trace: flight recorder + telemetry in the JSON
    trace_path: str = None,  # Chrome trace output (Perfetto-loadable)
    metrics_path: str = None,  # capacity-observatory JSONL/prom export stem
    # PR 9 window-cost switches (None = engine/platform default) — exposed
    # so an A/B can isolate each front against the same bench scenario.
    lane_major=None,
    window_razor=None,
    ca_descatter=None,
    profile=None,  # --profile: named scheduler profile (None = default)
) -> dict:
    """The COMPOSED flagship configuration as a tracked line (VERDICT r3
    item 4): HPA pod groups + cluster autoscaler + sliding pod window +
    Pallas kernels on a dense cluster batch. Regressions in the composed
    path (autoscaler passes, window slides, segmented slot layout) show up
    here even when the pure-scheduler shapes above hold.

    Returns {"value": median, "spans": {...}}: the timed region is >= 5
    REPEATED spans, each clocked separately, and the line reports the
    median with min/max spread — one cold-compile outlier
    span no longer moves the headline the way it moved a single monolithic
    timed region (round-5 VERDICT weakness #2: driver-captured cold runs
    undershot claimed numbers by 23%)."""
    from kubernetriks_tpu.batched.engine import build_batched_from_traces

    config, cluster_events, workload = _composed_inputs(
        n_nodes,
        rate_per_second=rate_per_second,
        horizon=horizon,
        max_group_pods=max_group_pods,
        burst=burst,
        faults=faults,
    )
    sim = build_batched_from_traces(
        config,
        cluster_events,
        workload,
        n_clusters=n_clusters,
        max_pods_per_cycle=64,
        pod_window=pod_window,
        # Tri-states pass straight through: the engine treats None as the
        # platform default (the CPU smoke path passes False — it must not
        # force Pallas kernels onto a host backend; the superspan smoke
        # line passes superspan=True to engage the scanned path on CPU).
        use_pallas=use_pallas,
        superspan=superspan,
        stream=stream,
        stream_segment=stream_segment,
        stream_depth=stream_depth,
        mesh=mesh,
        fast_forward=fast_forward,
        lane_major=lane_major,
        window_razor=window_razor,
        ca_descatter=ca_descatter,
        scheduler_profile=profile,
        # --trace arms the flight recorder: host span tracer + device
        # metrics ring. Bit-identical to telemetry-off and inside the <3%
        # overhead gate (tests/test_telemetry.py), so the traced line IS
        # the tracked line — the BENCH JSON carries its own anatomy.
        # Without --trace, pass None so a user's KTPU_TRACE=1 still arms
        # the recorder (a concrete False would override the env flag).
        telemetry=True if trace else None,
    )

    _assert_profile_compiled(sim, profile, "composed bench")

    if trace and metrics_path:
        # Capacity-observatory time-series export (telemetry/export.py):
        # every ring drain appends one JSONL record (occupancy gauges,
        # memory watermarks, watchdog verdicts) — the artifact CI uploads
        # next to the Chrome trace; the final report also lands as a
        # Prometheus textfile so standard scrape tooling can watch a run.
        from kubernetriks_tpu.telemetry.export import JsonlExporter

        sim.attach_metrics_exporter(JsonlExporter(metrics_path + ".jsonl"))

    def decisions_now() -> int:
        return int(np.asarray(sim.state.metrics.scheduling_decisions).sum())

    # Warm-up through the HPA burst and several window slides, so both
    # quantized slide shapes and every dispatch-chunk shape compile before
    # the clock starts (a novel slide or chunk shape costs seconds of
    # compile and would otherwise land inside the timed
    # region); precompile_chunks covers the shapes the warm span happens
    # not to dispatch — the ladder (+ fused chunk+slide variants), or on a
    # superspan engine the ONE scanned program every steady-state span
    # uses, so a driver-captured cold run pays no compile inside the timed
    # region.
    sim.step_until_time(warm_until)
    if precompile:
        sim.precompile_chunks()
    # >= 5 repeated timed spans; each span's decision fetch is a real sync,
    # so no device work leaks across span clocks.
    #
    # Span VALIDITY (r7 protocol fix): a timed span that committed ZERO
    # decisions ran past trace exhaustion (or landed wholly inside an HPA
    # load-curve trough) — its rate is 0 by construction and poisons the
    # min/median.
    # Zero-decision spans are DROPPED from the protocol; if fewer than 5
    # valid spans remain by t_end, the bench re-arms extra spans (the HPA
    # churn cycles indefinitely, so decisions resume) up to a hard cap and
    # fails loudly rather than reporting a median over dead air.
    rates, span_decisions = [], []
    end = warm_until + step
    max_end = t_end + 5 * step  # re-arm bound

    def n_valid() -> int:
        return sum(1 for d in span_decisions if d > 0)

    while end <= t_end or (n_valid() < 5 and end <= max_end):
        decisions_before = decisions_now()
        t0 = time.perf_counter()
        sim.step_until_time(end)
        decisions = decisions_now() - decisions_before
        span_decisions.append(decisions)
        rates.append(decisions / (time.perf_counter() - t0))
        end += step
    valid = [r for r, d in zip(rates, span_decisions) if d > 0]
    dropped = len(rates) - len(valid)
    assert len(valid) >= 5, (
        f"composed bench: only {len(valid)} valid timed spans "
        f"({dropped} dropped as zero-decision/trace-exhausted, re-arm cap "
        f"{max_end}s reached) — extend horizon or shrink step"
    )
    assert sim._pod_base > 0, "composed bench: pod window never slid"
    c = sim.metrics_summary()["counters"]
    assert c["total_scaled_up_pods"] > 0, "composed bench: HPA idle"
    assert c["total_scaled_up_nodes"] > 0, "composed bench: CA idle"
    if superspan:
        # The scanned path actually engaged — a silent fallback to the
        # ladder would make this line vacuous (CI smoke pins this).
        assert sim.dispatch_stats["superspans"] > 0, (
            "composed bench: superspan requested but never dispatched"
        )
        assert sim.dispatch_stats["window_chunks"] == 0, (
            "composed bench: superspan engine dispatched ladder chunks"
        )
    if stream:
        # The streaming feeder actually staged the run — a silent fallback
        # to the resident whole-trace payload (the bug class this line
        # exists to catch, same pattern as the superspan fallback asserts)
        # would leave the device slide payload materialized and the feeder
        # idle.
        assert sim._device_slide is None, (
            "composed bench: streaming requested but the whole-trace "
            "device slide payload was materialized (silent fallback to "
            "resident staging)"
        )
        assert sim.dispatch_stats["feeder_slabs_produced"] > 0, (
            "composed bench: streaming requested but the feeder produced "
            "no slabs"
        )
        assert sim.dispatch_stats["stage_refills"] > 0, (
            "composed bench: streaming requested but no feeder slab was "
            "ever installed"
        )
        # Feeder work rides its own thread, not new host syncs: the
        # steady-state budget stays one progress readback per superspan.
        assert (
            sim.dispatch_stats["slide_syncs"]
            == sim.dispatch_stats["superspans"]
        ), "composed bench: streaming added host syncs beyond the budget"
    # Span-spread disclosure: the median is still the honest headline,
    # but a wide max/min span ratio means the per-span rate is
    # load-phase-dependent and single A/B deltas within the spread band
    # are noise. WARN (never fail):
    # spread is a property of the scenario's load curve, not a bench bug.
    spread_frac = (
        round(max(valid) / min(valid), 3) if min(valid) > 0 else 0.0
    )
    if spread_frac > 2.0:
        warnings.warn(
            f"composed bench: timed-span spread max/min = {spread_frac}x "
            "(> 2x): per-span rates are load-phase-dependent; trust the "
            "median, not single-span deltas",
            RuntimeWarning,
            stacklevel=2,
        )
    out = {
        "value": float(np.median(valid)),
        "spans": {
            "n": len(valid),
            "min": round(min(valid)),
            "max": round(max(valid)),
            "dropped": dropped,
            "spread_frac": spread_frac,
        },
    }
    if trace:
        # Compact telemetry summary riding in the same JSON line: per-phase
        # host wall time, the observed sync count vs the documented
        # steady-state budget (1 progress readback per superspan + 1 shift
        # readback per fused slide), dispatch stats incl. ladder_fallbacks,
        # the device ring's per-window totals, and the per-window
        # window-program cost (the lane-major/razor/de-scatter observable).
        rep = sim.telemetry_report()
        out["telemetry"] = {
            "spans_ms": {
                name: round(s["total_ms"], 3)
                for name, s in rep["spans"].items()
            },
            "sync_budget": rep["sync_budget"],
            "dispatch_stats": rep["dispatch_stats"],
            "ring_totals": rep.get("ring", {}).get("totals", {}),
        }
        if "feeder" in rep:
            # Streaming-feeder anatomy: slab production vs installs, the
            # ring-depth gauge, and the stage-stall split (feeder-not-ready
            # vs upload-wait) — the starved-feeder observable.
            out["telemetry"]["feeder"] = rep["feeder"]
        # Per-window device-cost line: must exist and be positive on every
        # traced run — CPU CI runs --smoke --trace, so a change that stops
        # windows (or their cost accounting) from being recorded fails
        # loudly there, and layout regressions move a number CI can diff.
        pw = rep.get("per_window")
        assert pw and pw["ms_per_window"] > 0, (
            "composed bench --trace: telemetry report carries no "
            "per-window cost line (no windows recorded?)"
        )
        out["telemetry"]["per_window"] = {
            "windows": pw["windows"],
            "ms_per_window": round(pw["ms_per_window"], 4),
        }
        # Capacity-observatory section: occupancy high-water vs reserve
        # capacity plus RSS/slab watermarks — present and sane on every
        # traced run (CPU CI runs --smoke --trace, so a change that stops
        # the observatory sampling fails loudly there).
        res = rep.get("resources")
        assert res and res["memory"].get("rss_bytes", 0) > 0, (
            "composed bench --trace: telemetry report carries no "
            "resources section (observatory not sampling?)"
        )
        occ = res["occupancy"]
        assert {"hpa_reserve_used", "ca_reserve_used"} <= set(occ), occ
        out["telemetry"]["resources"] = {
            "occupancy": occ,
            "rss_mb": round(res["memory"]["rss_bytes"] / 1e6, 1),
            "rss_high_water_mb": round(
                res["memory"]["high_water"].get("rss_bytes", 0) / 1e6, 1
            ),
            "slabs": res["memory"].get("slabs", {}),
            "watchdog_fired": res["watchdog"]["fired"],
        }
        if trace_path:
            sim.write_chrome_trace(trace_path)
        if metrics_path:
            from kubernetriks_tpu.telemetry.export import (
                write_prometheus_textfile,
            )

            write_prometheus_textfile(metrics_path + ".prom", rep)
    # Release the streaming feeder's producer thread (and the engine it
    # keeps alive through its bound callbacks) — a driver looping bench
    # configurations must not accumulate parked feeders + staged slabs.
    sim.close()
    return out


# --endurance / the endurance SMOKE line: sustained churn through a
# deliberately tight CA reserve, so the run only finishes when slot
# reclaim (KTPU_RECLAIM, r14) actually recycles retired slots — the
# bounded-memory endurance machinery as a tracked line. Node-group pods
# only fit the CA template and fully retire between waves; the plain
# Poisson load keeps the scheduler busy so the line measures composed
# decisions/s, not idle windows.
ENDURANCE_CONFIG_YAML = """
sim_name: bench_endurance
seed: 1
scheduling_cycle_interval: 10.0
cluster_autoscaler:
  enabled: true
  scan_interval: 10.0
  max_node_count: 2
  node_groups:
  - node_template:
      metadata: {{name: ca_node}}
      status: {{capacity: {{cpu: 32000, ram: 68719476736}}}}
{faults_block}
"""


def _endurance_churn_events(n_waves: int, spacing: float, t0: float = 30.0):
    """Churn waves: each wave's pods only fit the CA template (24000 mcpu
    vs 16000 base nodes), run shorter than the wave spacing, and fully
    retire before the next wave — one reserve slot consumed per pod, so
    cumulative allocations overrun the 2-slot static reserve many times
    and the run RAISES without reclaim. Every third wave sends two pods
    (staggered finishes) so multi-slot retirement and the name-ordered
    scale-down walk both run."""
    from kubernetriks_tpu.trace.generic import GenericWorkloadTrace

    events, pod = [], 0
    for k in range(n_waves):
        t = t0 + k * spacing
        for j in range(2 if k % 3 == 2 else 1):
            events.append(
                f"""
- timestamp: {round(t + 7.0 * j, 1)}
  event_type:
    !CreatePod
      pod:
        metadata:
          name: churn_{pod:04d}
        spec:
          resources:
            requests: {{cpu: 24000, ram: 25769803776}}
            limits: {{cpu: 24000, ram: 25769803776}}
          running_duration: {round(min(60.0, spacing / 2) + 14.0 * j, 1)}
"""
            )
            pod += 1
    return GenericWorkloadTrace.from_yaml(
        "events:" + "".join(events)
    ).convert_to_simulator_events()


def run_endurance(
    n_clusters: int = 4,
    n_nodes: int = 8,
    *,
    n_waves: int = 24,
    spacing: float = 160.0,
    rate_per_second: float = 0.25,
    pod_window: int = 128,
    warm_waves: int = 3,
    ca_slot_multiplier: int = 1,
    use_pallas=False,
    faults: bool = True,
    trace_path: str = None,
    metrics_path: str = None,
) -> dict:
    """The ENDURANCE line (ROADMAP #2, r14): composed churn many times
    the static CA reserve with slot reclaim + superspan + the streaming
    feeder on and the capacity observatory watching. In-bench asserts —
    the reasons this line exists, each failing loudly on CI:

    - reclaim actually FIRED (cumulative allocations >= 3x the static
      reserve, retired slots returned, the loud bound clean);
    - RSS/slab WATERMARKS flat (slab byte accounting identical at every
      quartile boundary, RSS high-water non-trending after warm-up);
    - zero RECOMPILES after warm-up (every dispatch-loop jit entry's
      cache size unchanged);
    - the saturation watchdog stayed QUIET (no reserve verdict: live
      occupancy never trends toward exhaustion when reclaim recycles).

    Returns the run_composed record shape plus an "endurance" block with
    the quartile decisions/s spread (first vs last quartile disclosed —
    reserve-pressure throughput decay would show there)."""
    import warnings as _warnings

    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.batched.fleet import jit_cache_sizes
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.telemetry.observatory import SaturationWarning
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )

    config = SimulationConfig.from_yaml(
        ENDURANCE_CONFIG_YAML.format(
            faults_block=FAULTS_YAML if faults else ""
        )
    )
    horizon = 30.0 + n_waves * spacing
    cluster = UniformClusterTrace(n_nodes, cpu=16000, ram=32 * 1024**3)
    plain = PoissonWorkloadTrace(
        rate_per_second=rate_per_second,
        horizon=horizon - 60.0,
        seed=3,
        cpu=2000,
        ram=4 * 1024**3,
        duration_range=(20.0, 60.0),
        name_prefix="plain",
    )
    workload = sorted(
        plain.convert_to_simulator_events()
        + _endurance_churn_events(n_waves, spacing),
        key=lambda e: e[0],
    )
    # Recompile sentinel (KTPU_EXPLAIN_RECOMPILES): names the jit entry
    # if anything compiles in the measured region; the cache-count
    # equality assert below stays as the count-level cross-check.
    from kubernetriks_tpu.recompile import RecompileSentinel, sentinel_mode

    sentinel = (
        RecompileSentinel("raise").install()
        if sentinel_mode() is not False
        else None
    )
    sim = build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload,
        n_clusters=n_clusters,
        max_pods_per_cycle=32,
        pod_window=pod_window,
        use_pallas=use_pallas,
        superspan=True,
        stream=True,
        fast_forward=False,
        reclaim=True,
        # Multiplier 1 over the 2-node quota = a TWO-slot reserve per
        # lane — the churn overruns it many times, so finishing at all
        # proves reclaim recycles (reclaim=False raises at readout
        # here). Long runs with pod faults pass multiplier 2: a failed
        # churn pod's CrashLoopBackOff retry can demand a slot while its
        # OWN node's removal is still inside the visibility horizon
        # (retirement is semantically gated on it, DESIGN §12.1), so at
        # scale the reserve needs quota + a drain-limbo margin — the
        # reference pre-sizes its component pools with the same headroom
        # (simulator.rs:212-230).
        ca_slot_multiplier=ca_slot_multiplier,
        telemetry=True,
        watchdog=True,
    )
    assert sim.reclaim, "endurance bench: reclaim requested but not armed"

    if metrics_path:
        from kubernetriks_tpu.telemetry.export import JsonlExporter

        sim.attach_metrics_exporter(JsonlExporter(metrics_path + ".jsonl"))

    def decisions_now() -> int:
        return int(np.asarray(sim.state.metrics.scheduling_decisions).sum())

    warm_until = 30.0 + warm_waves * spacing
    with _warnings.catch_warnings(record=True):
        # Warm-up verdicts are discarded: the feeder thread's cold start
        # can stall one dispatch (a one-shot feeder_starved verdict), and
        # the first churn ramp has no reclaim history yet. The measured
        # region below asserts ZERO verdicts.
        _warnings.simplefilter("always")
        sim.step_until_time(warm_until)
        while sim._pod_base == 0 and warm_until < horizon / 2:
            # The staged-slide superspan program compiles at the FIRST
            # window slide; warm-up must cover it or the zero-recompile
            # gate would flag that legitimate cold compile.
            warm_until += spacing
            sim.step_until_time(warm_until)
        assert sim._pod_base > 0, (
            "endurance bench: pod window never slid inside the warm-up "
            "half — raise rate_per_second or shrink pod_window"
        )
    cache_after_warm = jit_cache_sizes()
    if sentinel is not None:
        sentinel.seal("endurance warm-up (build + first churn waves)")
    rss_after_warm = sim._sample_resources()["rss_bytes"]

    # One timed span per remaining wave (each span carries plain load
    # + one full churn cycle), every boundary sampling the slab
    # watermarks — flat is the claim, so every sample must agree.
    rates, span_decisions, slab_samples, end = [], [], [], warm_until
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        while end < horizon - 1.0:
            end = min(end + spacing, horizon - 1.0)
            before = decisions_now()
            t0 = time.perf_counter()
            sim.step_until_time(end)
            d = decisions_now() - before
            span_decisions.append(d)
            rates.append(d / (time.perf_counter() - t0))
            slab_samples.append((sim.pod_window, sim._slab_accounting()))
        # Flush the ring inside the capture scope so the final rows'
        # verdicts (if any) land in `caught`, not in a later readout.
        sim.drain_telemetry()
    saturation = [
        str(w.message)
        for w in caught
        if issubclass(w.category, SaturationWarning)
    ]
    # The hard gate is the RESERVE trajectory (the reclaim observable);
    # pipeline verdicts (feeder stalls / sync budget) depend on host
    # speed at these shapes and are disclosed, not asserted.
    reserve_verdicts = [m for m in saturation if "reserve" in m]
    pipeline_verdicts = [m for m in saturation if "reserve" not in m]

    # -- the in-bench endurance gates ------------------------------------
    reclaimed = int(sim.ca_slots_reclaimed().sum())
    total_alloc = int(np.asarray(sim.state.auto.ca_total).sum())
    reserve = int(sum(sim._reserve_capacities["ca_reserve"]))
    assert total_alloc >= 3 * reserve, (
        f"endurance bench: cumulative churn ({total_alloc} allocations) "
        f"never overran the static reserve ({reserve} slots) — the "
        "reclaim gate is vacuous; raise n_waves"
    )
    assert reclaimed >= total_alloc - reserve, (
        f"endurance bench: reclaim returned {reclaimed} slots for "
        f"{total_alloc} allocations over a {reserve}-slot reserve"
    )
    sim.check_autoscaler_bounds()  # loud bound must be CLEAN
    assert not reserve_verdicts, (
        "endurance bench: a reserve saturation verdict fired despite "
        f"reclaim: {reserve_verdicts}"
    )
    fired_final = sim.telemetry_report()["resources"]["watchdog"]["fired"]
    assert not any(k.endswith("_reserve_used") for k in fired_final), (
        f"endurance bench: a reserve verdict is live at the end: "
        f"{fired_final} — reclaim should keep occupancy off the "
        "exhaustion trajectory"
    )
    by_geometry = {}
    for pw, slabs in slab_samples:
        by_geometry.setdefault(pw, []).append(slabs)
    for pw, rows in by_geometry.items():
        for later in rows[1:]:
            assert later == rows[0], (
                "endurance bench: slab watermarks moved at fixed "
                f"geometry (pod_window {pw}): {rows[0]} -> {later}"
            )
    assert jit_cache_sizes() == cache_after_warm, (
        "endurance bench: dispatch-loop jit entries recompiled after "
        f"warm-up: {cache_after_warm} -> {jit_cache_sizes()}"
    )
    if sentinel is not None:
        # Names the entry where the count diff above can only count.
        sentinel.check("the endurance measured region")
        sentinel.uninstall()
    rss_end = sim._sample_resources()["rss_bytes"]
    assert rss_end < rss_after_warm * 1.5 + 256e6, (
        "endurance bench: host RSS trended after warm-up "
        f"({rss_after_warm / 1e6:.0f} MB -> {rss_end / 1e6:.0f} MB)"
    )

    valid = [r for r, d in zip(rates, span_decisions) if d > 0]
    dropped = len(rates) - len(valid)
    assert len(valid) >= 4, (
        f"endurance bench: only {len(valid)} valid timed spans"
    )
    q = max(1, len(valid) // 4)
    first_q, last_q = valid[:q], valid[-q:]
    out = {
        "value": float(np.median(valid)),
        "spans": {
            "n": len(valid),
            "min": round(min(valid)),
            "max": round(max(valid)),
            "dropped": dropped,
        },
        "endurance": {
            "waves": n_waves,
            "sim_horizon_s": horizon,
            "reserve_slots": reserve,
            "allocations": total_alloc,
            "reclaimed": reclaimed,
            "reclaim_over_reserve": round(total_alloc / max(reserve, 1), 1),
            "first_quartile_median": round(float(np.median(first_q))),
            "last_quartile_median": round(float(np.median(last_q))),
            "quartile_spread_pct": round(
                100.0
                * (np.median(last_q) - np.median(first_q))
                / max(float(np.median(first_q)), 1e-9),
                1,
            ),
            "rss_after_warm_mb": round(rss_after_warm / 1e6, 1),
            "rss_end_mb": round(rss_end / 1e6, 1),
            "watchdog_fired": sorted(fired_final),
            "pipeline_verdicts": pipeline_verdicts,
            "recompiles_after_warmup": 0,
        },
    }
    if trace_path:
        sim.write_chrome_trace(trace_path)
    if metrics_path:
        from kubernetriks_tpu.telemetry.export import (
            write_prometheus_textfile,
        )

        write_prometheus_textfile(
            metrics_path + ".prom", sim.telemetry_report()
        )
    sim.close()
    return out


SWEEP_GROUP_YAML = COMPOSED_GROUP_YAML  # same HPA burst group as composed


def _sweep_scenarios(n: int):
    """N deterministic heterogeneous scenarios over the vectorizable
    autoscaler parameters (batched/fleet.py SCENARIO_KEYS), plus two
    exact duplicates of scenario 0 planted at positions that land in a
    DIFFERENT lane and a DIFFERENT wave — the lane cross-talk probes the
    in-bench asserts compare bit-for-bit. Arithmetic in the index (no
    RNG): the sweep is reproducible by construction."""
    from kubernetriks_tpu.batched.fleet import Scenario

    out = []
    for i in range(n):
        out.append(
            Scenario(
                hpa_scan_interval=(30.0, 60.0, 90.0, 120.0)[i % 4],
                hpa_tolerance=0.05 + 0.05 * (i % 5),
                ca_scan_interval=10.0 + 5.0 * ((i // 2) % 4),
                ca_threshold=0.3 + 0.1 * ((i // 3) % 4),
            )
        )
    probes = []
    for pos in (min(n // 2 + 1, n - 1), n - 1):
        if pos > 0:
            out[pos] = out[0]
            probes.append(pos)
    return out, sorted(set(probes))


def _scenario_config(base_yaml: str, scen) -> "object":
    """A standalone SimulationConfig carrying one scenario's overrides as
    plain config scalars — the per-engine baseline's input (and the
    scalar-oracle shape tests/test_fleet.py compares lanes against)."""
    from kubernetriks_tpu.config import (
        KubeClusterAutoscalerConfig,
        KubeHorizontalPodAutoscalerConfig,
        SimulationConfig,
    )

    config = SimulationConfig.from_yaml(base_yaml)
    if scen.hpa_scan_interval is not None:
        config.horizontal_pod_autoscaler.scan_interval = scen.hpa_scan_interval
    if scen.hpa_tolerance is not None:
        config.horizontal_pod_autoscaler.kube_horizontal_pod_autoscaler_config = (
            KubeHorizontalPodAutoscalerConfig(
                target_threshold_tolerance=scen.hpa_tolerance
            )
        )
    if scen.ca_scan_interval is not None:
        config.cluster_autoscaler.scan_interval = scen.ca_scan_interval
    if scen.ca_threshold is not None:
        config.cluster_autoscaler.kube_cluster_autoscaler = (
            KubeClusterAutoscalerConfig(
                scale_down_utilization_threshold=scen.ca_threshold
            )
        )
    if scen.ca_max_node_count is not None:
        config.cluster_autoscaler.max_node_count = scen.ca_max_node_count
    if scen.as_to_ca_network_delay is not None:
        config.as_to_ca_network_delay = scen.as_to_ca_network_delay
    if scen.hpa_enabled is not None:
        config.horizontal_pod_autoscaler.enabled = scen.hpa_enabled
    return config


def _sweep_setup(
    n_nodes: int,
    rate_per_second: float,
    horizon: float,
    max_group_pods: int,
    burst: tuple,
):
    """Shared config + trace builder of the --sweep and open-loop lines:
    one composed (plain Poisson + HPA burst group) workload over a
    uniform cluster, autoscalers on. Returns (base_yaml, config,
    cluster_events, workload)."""
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )
    from kubernetriks_tpu.trace.generic import GenericWorkloadTrace

    base_yaml = f"""
sim_name: bench_sweep
seed: 1
scheduling_cycle_interval: 10.0
horizontal_pod_autoscaler:
  enabled: true
cluster_autoscaler:
  enabled: true
  scan_interval: 10.0
  max_node_count: {n_nodes}
  node_groups:
  - node_template:
      metadata: {{name: ca_node}}
      status: {{capacity: {{cpu: 64000, ram: 137438953472}}}}
"""
    config = SimulationConfig.from_yaml(base_yaml)
    cluster = UniformClusterTrace(n_nodes, cpu=64000, ram=128 * 1024**3)
    plain = PoissonWorkloadTrace(
        rate_per_second=rate_per_second,
        horizon=horizon,
        seed=3,
        cpu=16000,
        ram=32 * 1024**3,
        duration_range=(30.0, 120.0),
        name_prefix="plain",
    )
    group = GenericWorkloadTrace.from_yaml(
        SWEEP_GROUP_YAML.format(
            max_pods=max_group_pods, d1=burst[0], d2=burst[1], d3=burst[2]
        )
    ).convert_to_simulator_events()
    cluster_events = cluster.convert_to_simulator_events()
    workload = sorted(
        plain.convert_to_simulator_events() + group, key=lambda e: e[0]
    )
    return base_yaml, config, cluster_events, workload


def run_sweep(
    n_scenarios: int = 64,
    n_lanes: int = None,
    n_nodes: int = 8,
    *,
    rate_per_second: float = 0.375,
    horizon: float = 400.0,
    query_horizon: float = 450.0,
    max_group_pods: int = 16,
    burst: tuple = (100.0, 150.0, 250.0),
    baseline_engines: int = None,
    smoke: bool = False,
    sweep_path: str = None,
) -> dict:
    """The scenario-vector SWEEP line (ROADMAP #4 made measurable): N
    heterogeneous what-if scenarios — per-lane HPA scan interval /
    tolerance, CA scan interval / scale-down threshold — run through ONE
    resident `ScenarioFleet` (batched/fleet.py) over C cluster lanes, vs
    the old cost model of one engine (compile + warm-up + run) PER
    scenario.

    In-bench asserts (the bug classes this line exists to catch):
    - ZERO recompiles after warm-up: every jit entry's compiled-variant
      count (fleet.jit_cache_sizes) is captured after the first wave and
      must be unchanged after the full query stream — a scenario
      parameter that silently became a jit-static fails here loudly.
    - NO lane cross-talk: exact duplicates of scenario 0 planted in a
      different lane and a different wave must return bit-identical
      per-lane counters.
    - On the full sweep (N >= 64): fleet wall-clock beats the N-engine
      baseline by >= 5x. The baseline builds + runs `baseline_engines`
      real independent engines (the first pays the compile) and
      extrapolates to N from the warm per-engine mean — disclosed in the
      JSON as baseline.extrapolated.
    """
    import time as _time

    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.batched.fleet import (
        ScenarioFleet,
        jit_cache_sizes,
    )
    from kubernetriks_tpu.flags import flag_int

    if n_lanes is None:
        n_lanes = flag_int("KTPU_SWEEP_LANES") or (4 if smoke else 16)
    if baseline_engines is None:
        baseline_engines = flag_int("KTPU_SWEEP_BASELINE") or 3
    baseline_engines = max(1, min(baseline_engines, n_scenarios))

    base_yaml, config, cluster_events, workload = _sweep_setup(
        n_nodes, rate_per_second, horizon, max_group_pods, burst
    )
    scenarios, probe_positions = _sweep_scenarios(n_scenarios)

    # Recompile sentinel: the in-bench zero-recompile assert below
    # compares jit-cache COUNTS; the sentinel additionally NAMES the
    # entry on any post-warm-up compilation (KTPU_EXPLAIN_RECOMPILES=0
    # disarms it; unset arms it here, where the contract is the line's
    # whole point).
    from kubernetriks_tpu.recompile import RecompileSentinel, sentinel_mode

    sentinel = (
        RecompileSentinel("raise").install()
        if sentinel_mode() is not False
        else None
    )

    # --- the fleet: ONE engine, N scenarios as per-lane config data -----
    t0 = _time.perf_counter()
    fleet = ScenarioFleet(
        config,
        cluster_events,
        workload,
        n_lanes=n_lanes,
        horizon=query_horizon,
        max_pods_per_cycle=64,
        use_pallas=None if not smoke else False,
    )
    qids = [fleet.submit(s) for s in scenarios]
    # Warm-up = the first wave (compile + warm dispatch shapes), then the
    # zero-recompile capture, then the rest of the query stream.
    first_wave = [
        fleet._queue.popleft() for _ in range(min(n_lanes, len(fleet._queue)))
    ]
    fleet._run_wave(first_wave)
    sizes_after_warm = jit_cache_sizes()
    if sentinel is not None:
        sentinel.seal("sweep warm-up (build + first wave)")
    fleet.run()
    fleet_s = _time.perf_counter() - t0
    sizes_after_sweep = jit_cache_sizes()
    results = [fleet.results[q] for q in qids]
    fleet.close()
    sentinel_events = 0
    if sentinel is not None:
        # In-bench assert: raises RecompileError NAMING the jit entry if
        # anything compiled during the post-warm-up query stream.
        sentinel.check("the --sweep post-warm-up query stream")
        sentinel_events = len(sentinel.post_seal_events())
        sentinel.uninstall()

    recompiled = {
        name: (sizes_after_sweep[name], sizes_after_warm[name])
        for name in sizes_after_warm
        if sizes_after_sweep[name] != sizes_after_warm[name]
    }
    assert not recompiled, (
        "sweep: scenario updates RECOMPILED jit entries after warm-up "
        f"(compiled-variant counts moved: {recompiled}) — a scenario "
        "parameter regressed from traced data to a jit-static"
    )
    for pos in probe_positions:
        assert results[pos].counters == results[0].counters, (
            f"sweep: lane cross-talk — scenario {pos} is an exact "
            f"duplicate of scenario 0 but its per-lane counters differ "
            f"(lane {results[pos].lane}/wave {results[pos].wave} vs lane "
            f"{results[0].lane}/wave {results[0].wave}):\n"
            f"{results[pos].counters}\n{results[0].counters}"
        )
    decisions = sum(r.counters["scheduling_decisions"] for r in results)
    assert decisions > 0, "sweep: no scenario committed any decision"
    assert any(
        r.counters["scaled_up_nodes"] > 0 for r in results
    ), "sweep: CA idle across every scenario"

    # --- the per-engine baseline: one engine PER scenario ---------------
    # The pre-fleet cost model is one CLI run (one PROCESS) per what-if
    # scenario: every query pays engine build + XLA compile + warm-up
    # (ROADMAP #4's framing). Measured in-process, later engines would
    # silently hit the jit cache and understate that model, so each
    # baseline engine starts compile-COLD (jax.clear_caches) — the
    # honest stand-in for a fresh process — and the JSON discloses both
    # the per-engine measurements and the extrapolation.
    import jax

    base_times = []
    for i in range(baseline_engines):
        scen = scenarios[i]
        if not smoke:
            # Smoke is a plumbing check (recompile/cross-talk asserts,
            # no speedup gate): keep the jit caches warm so the CI smoke
            # job does not pay cold recompiles for a number nobody reads.
            jax.clear_caches()
        t1 = _time.perf_counter()
        sim = build_batched_from_traces(
            _scenario_config(base_yaml, scen),
            cluster_events,
            workload,
            n_clusters=1,
            max_pods_per_cycle=64,
            use_pallas=None if not smoke else False,
        )
        sim.step_until_time(query_horizon)
        int(np.asarray(sim.state.metrics.scheduling_decisions).sum())
        sim.close()
        base_times.append(_time.perf_counter() - t1)
    baseline_s = float(np.mean(base_times)) * n_scenarios
    speedup = baseline_s / fleet_s if fleet_s > 0 else float("inf")
    if not smoke and n_scenarios >= 64:
        assert speedup >= 5.0, (
            f"sweep: fleet wall-clock {fleet_s:.2f}s vs extrapolated "
            f"{n_scenarios}-engine baseline {baseline_s:.2f}s = "
            f"{speedup:.2f}x < the 5x gate"
        )

    out = {
        "value": n_scenarios / fleet_s,
        "sweep": {
            "scenarios": n_scenarios,
            "lanes": n_lanes,
            "waves": -(-n_scenarios // n_lanes),
            "fleet_s": round(fleet_s, 3),
            "scenarios_per_s": round(n_scenarios / fleet_s, 3),
            "baseline": {
                "engines_measured": baseline_engines,
                "measured_s": [round(t, 3) for t in base_times],
                # One-process-per-scenario cost model: each measured
                # engine starts compile-cold (jax.clear_caches), like the
                # fresh CLI run every pre-fleet what-if query paid.
                # False on --smoke: the plumbing check keeps caches warm
                # (its baseline number is not a tracked comparison).
                "cold_process_model": not smoke,
                "extrapolated": baseline_engines < n_scenarios,
                "total_s": round(baseline_s, 3),
            },
            "speedup": round(speedup, 2),
            "recompiles_after_warmup": 0,
            "recompile_sentinel": {
                "armed": sentinel is not None,
                "post_warmup_events": sentinel_events,
            },
            "crosstalk_probes": probe_positions,
            "decisions_total": int(decisions),
        },
    }
    if sweep_path:
        with open(sweep_path, "w") as fh:
            json.dump(out["sweep"], fh, indent=2)
            fh.write("\n")
    return out


# Heterogeneous-horizon mix of the open-loop line: every 4-query block
# holds one full-horizon query and three shorter ones, so a WAVE-aligned
# fleet pays the block's max horizon on every lane while the lane-async
# fleet re-seeds each lane the round its own query finishes — the idle
# tail the per-lane window clock exists to delete.
OPEN_LOOP_HORIZON_MIX = (1.0, 0.0625, 0.125, 0.0625)


def run_open_loop(
    n_queries: int = 32,
    n_lanes: int = 4,
    n_nodes: int = 64,
    *,
    rate_per_second: float = 3.0,
    horizon: float = 400.0,
    query_horizon: float = 450.0,
    max_group_pods: int = 32,
    burst: tuple = (100.0, 150.0, 250.0),
    max_pods_per_cycle: int = 256,
    rounds: int = 5,
    span_windows: int = 4,
    horizon_mix: tuple = None,
    smoke: bool = False,
    json_path: str = None,
    trace_path: str = None,  # per-lane Chrome trace (query swimlanes)
    metrics_path: str = None,  # observatory JSONL/prom export stem
) -> dict:
    """The OPEN-LOOP client line (lane-async fleet, DESIGN §13): the same
    heterogeneous scenario stream submitted to a wave-aligned fleet and a
    lane-asynchronous fleet, with per-query horizons cycling
    OPEN_LOOP_HORIZON_MIX — the workload shape where wave alignment
    wastes the most device time (every wave runs to its longest lane).

    Protocol: both fleets run the full stream once as warm-up (compile +
    program warm), the jit caches and the recompile sentinel are sealed,
    then `rounds` timed repeats run on the RESIDENT fleets; the reported
    queries/s are medians (median-of->=5 in full mode).

    In-bench asserts:
    - A/B identity: every query's counters/replica readouts are
      bit-identical between the wave and lane-async fleets.
    - Zero post-warm-up recompiles (jit-cache counts + sentinel), as in
      --sweep.
    - Query observatory (PR 17): the bounded latency histogram's count
      equals the number of polled queries, and its bucket-derived p99
      lands within one bucket width of the exact sorted-array p99 over
      the bounded exact-sample window (while both exist).
    - Full mode only: mean lane occupancy > 90% on the mix, and the
      lane-async fleet sustains >= 1.5x the wave fleet's queries/s.
    """
    import time as _time

    from kubernetriks_tpu.batched.fleet import ScenarioFleet, jit_cache_sizes
    from kubernetriks_tpu.recompile import RecompileSentinel, sentinel_mode

    base_yaml, config, cluster_events, workload = _sweep_setup(
        n_nodes, rate_per_second, horizon, max_group_pods, burst
    )
    scenarios, _ = _sweep_scenarios(n_queries)
    mix = tuple(horizon_mix) if horizon_mix else OPEN_LOOP_HORIZON_MIX
    horizons = [
        query_horizon * mix[i % len(mix)] for i in range(n_queries)
    ]

    sentinel = (
        RecompileSentinel("raise").install()
        if sentinel_mode() is not False
        else None
    )

    def build(lane_async):
        return ScenarioFleet(
            config,
            cluster_events,
            workload,
            n_lanes=n_lanes,
            horizon=query_horizon,
            max_pods_per_cycle=max_pods_per_cycle,
            use_pallas=None if not smoke else False,
            lane_async=lane_async,
            span_windows=span_windows if lane_async else None,
            # Flight recorder on BOTH fleets so the A/B timing compares
            # identical window programs (the ring record is in-graph);
            # the observatory's lane_occupancy entry reports the pump
            # ledger's counters (ring_lane_occupancy in the record) and
            # the per-query latency stats flow into the observatory.
            telemetry=True,
        )

    def submit_stream(fleet):
        return [
            fleet.submit(s, h) for s, h in zip(scenarios, horizons)
        ]

    wave = build(False)
    asy = build(True)
    if metrics_path:
        # Observatory time-series export for the serving line, like the
        # composed line's: JSONL drain records now, the final report as
        # a Prometheus textfile (with the native query-latency histogram
        # series) after the timed rounds.
        from kubernetriks_tpu.telemetry.export import JsonlExporter

        asy.engine.attach_metrics_exporter(
            JsonlExporter(metrics_path + ".jsonl")
        )
    # Warm-up: the full stream once per fleet, plus the A/B identity
    # gate — every query's results bit-match across the two executions.
    warm_wave = submit_stream(wave)
    wave.run()
    warm_asy = submit_stream(asy)
    asy.run_async()
    for i, (qw, qa) in enumerate(zip(warm_wave, warm_asy)):
        rw, ra = wave.results[qw], asy.results[qa]
        assert (
            rw.counters == ra.counters
            and rw.hpa_replicas == ra.hpa_replicas
            and rw.ca_nodes == ra.ca_nodes
        ), (
            f"open-loop: query {i} diverges between the wave-aligned and "
            f"lane-async fleets (scenario {scenarios[i]}, horizon "
            f"{horizons[i]}):\n{rw.counters}\n{ra.counters}"
        )
    sizes_after_warm = jit_cache_sizes()
    if sentinel is not None:
        sentinel.seal("open-loop warm-up (both fleets, full stream)")
    # Drain the warm-up completions, then start the timed rounds from a
    # clean ledger: warm-up latencies are dominated by compile time and
    # would swamp the percentiles. reset_query_stats() resets the fleet
    # histograms AND the observatory's query stats atomically.
    asy.poll()
    asy.reset_query_stats()

    wave_times, asy_times = [], []
    polled_queries = 0
    for _ in range(max(1, rounds) if not smoke else 1):
        submit_stream(wave)
        t0 = _time.perf_counter()
        wave.run()
        wave_times.append(_time.perf_counter() - t0)
        submit_stream(asy)
        t0 = _time.perf_counter()
        asy.run_async()
        asy_times.append(_time.perf_counter() - t0)
        polled_queries += len(asy.poll())

    sizes_after = jit_cache_sizes()
    recompiled = {
        name: (sizes_after[name], sizes_after_warm[name])
        for name in sizes_after_warm
        if sizes_after[name] != sizes_after_warm[name]
    }
    assert not recompiled, (
        "open-loop: the post-warm-up query stream RECOMPILED jit entries "
        f"(compiled-variant counts moved: {recompiled})"
    )
    sentinel_events = 0
    if sentinel is not None:
        sentinel.check("the open-loop post-warm-up query stream")
        sentinel_events = len(sentinel.post_seal_events())
        sentinel.uninstall()

    wave_qps = n_queries / float(np.median(wave_times))
    asy_qps = n_queries / float(np.median(asy_times))
    speedup = asy_qps / wave_qps if wave_qps > 0 else float("inf")
    occupancy = asy.lane_occupancy()
    latency = asy.query_latency_percentiles()
    breakdown = asy.query_latency_breakdown()
    # Query-observatory asserts (PR 17): the bounded histogram must agree
    # with ground truth. (a) Exact count: one histogram sample per polled
    # query. (b) Percentile quantisation: while the exact-sample window
    # still holds the whole post-warm-up stream, the bucket-derived p99
    # (numpy's method="higher" rank convention) sits within one bucket
    # width (~5% relative) of the exact sorted-array p99.
    hist = asy.latency_hist
    assert hist.count == polled_queries, (
        f"open-loop: latency histogram holds {hist.count} samples but "
        f"{polled_queries} queries were polled — a drain path skipped "
        "the histogram (or double-counted)"
    )
    exact_window = list(asy.latency_exact_window)
    if exact_window and len(exact_window) == hist.count:
        exact_p99 = float(
            np.percentile(np.asarray(exact_window), 99, method="higher")
        )
        hist_p99 = hist.percentile(99.0)
        width = hist.bucket_width(exact_p99)
        assert abs(hist_p99 - exact_p99) <= width + 1e-12, (
            f"open-loop: histogram p99 {hist_p99 * 1e3:.3f}ms is more "
            f"than one bucket width ({width * 1e3:.3f}ms) from the exact "
            f"p99 {exact_p99 * 1e3:.3f}ms"
        )
    report = asy.engine.telemetry_report() if asy.engine._telemetry else {}
    ring_occ = (
        report.get("resources", {}).get("occupancy", {}).get("lane_occupancy")
    )
    if trace_path:
        # The per-lane Chrome trace: pid 2 carries one swimlane per
        # fleet lane, spans named by the occupying query id, flow arrows
        # linking each submit to its drain (CI uploads it; open it in
        # Perfetto — README "Query observatory").
        asy.engine.write_chrome_trace(trace_path)
    if metrics_path:
        from kubernetriks_tpu.telemetry.export import (
            write_prometheus_textfile,
        )

        write_prometheus_textfile(metrics_path + ".prom", report)
    wave.close()
    asy.close()

    if not smoke:
        assert occupancy["mean"] > 0.90, (
            f"open-loop: mean lane occupancy {occupancy['mean']:.3f} <= "
            "0.90 on the heterogeneous-horizon mix — dispatched lane-"
            "windows are being wasted (span too wide for the mix?)"
        )
        assert speedup >= 1.5, (
            f"open-loop: lane-async fleet at {asy_qps:.2f} queries/s vs "
            f"wave-aligned {wave_qps:.2f} = {speedup:.2f}x < the 1.5x gate"
        )

    out = {
        "value": asy_qps,
        "open_loop": {
            "queries": n_queries,
            "lanes": n_lanes,
            "span_windows": span_windows,
            "horizon_mix": list(mix),
            "rounds_timed": len(asy_times),
            "wave_queries_per_s": round(wave_qps, 3),
            "async_queries_per_s": round(asy_qps, 3),
            "speedup_vs_wave": round(speedup, 3),
            "lane_occupancy": {
                "mean": round(occupancy["mean"], 4),
                "min": round(occupancy["min"], 4),
            },
            "ring_lane_occupancy": ring_occ,
            "latency_ms": {
                k: round(v, 3)
                for k, v in latency.items()
                if k != "count"
            },
            # Queue-wait (submit->admit) vs service (admit->drain) split
            # + the raw bounded-histogram dump (log buckets, ~5%
            # relative resolution, exact count/sum) — PR 17's per-query
            # observability embedded in the SWEEP artifact.
            "latency_breakdown": {
                "queue_wait_ms": breakdown["queue_wait_ms"],
                "service_ms": breakdown["service_ms"],
            },
            "latency_histogram": breakdown["histogram"],
            "histogram_polled_queries": polled_queries,
            "ab_identity_checked": n_queries,
            "recompiles_after_warmup": 0,
            "recompile_sentinel": {
                "armed": sentinel is not None,
                "post_warmup_events": sentinel_events,
            },
        },
    }
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(out["open_loop"], fh, indent=2)
            fh.write("\n")
    return out


def run_host_chaos(
    n_queries: int = 24,
    n_lanes: int = 4,
    n_nodes: int = 8,
    *,
    rate_per_second: float = 0.375,
    horizon: float = 300.0,
    query_horizon: float = 350.0,
    max_group_pods: int = 16,
    burst: tuple = (100.0, 150.0, 250.0),
    max_pods_per_cycle: int = 64,
    rounds: int = 4,
    chaos_seed: int = 7,
    dispatch_rate: float = 0.05,
    stall_rate: float = 0.05,
    stall_ms: float = 1.0,
    smoke: bool = False,
    json_path: str = None,
) -> dict:
    """The HOST-CHAOS line (fault-tolerant serving, DESIGN §15): the
    open-loop query stream through a lane-async fleet while a
    deterministic `HostChaos` injector (counter-seeded threefry, like the
    in-simulation chaos engine) fails dispatches and stalls lanes — the
    unit of failure must be a query or a lane, never the fleet.

    Protocol and in-bench gates:
    - QUIET A/B (the robustness layer is free when off): a plain fleet
      and the chaos-configured fleet (injector NOT yet armed, aggressive
      quarantine thresholds configured) run the same stream —
      bit-identical per-query results AND equal engine dispatch_stats,
      with the recompile sentinel armed and zero chaos events.
    - CHAOS phase (pinned seed => the exact same fault schedule every
      run): `rounds` repeats of the stream with the injector armed.
      The fleet must finish every round (no engine death), availability
      over the injected phase >= 90%, every failed qid streams exactly
      ONE typed error through poll() (stream-once audit), every lane
      faults at least once (the injector's least-faulted victim rule
      makes coverage deterministic), at least one lane quarantines AND
      is later re-admitted, and zero post-warm-up recompiles
      (quarantine/reset are data ops — jit-cache counts + sentinel).
    """
    import warnings as _warnings

    from kubernetriks_tpu.batched.faults import HostChaos
    from kubernetriks_tpu.batched.fleet import ScenarioFleet, jit_cache_sizes
    from kubernetriks_tpu.recompile import RecompileSentinel, sentinel_mode

    base_yaml, config, cluster_events, workload = _sweep_setup(
        n_nodes, rate_per_second, horizon, max_group_pods, burst
    )
    scenarios, _ = _sweep_scenarios(n_queries)
    mix = OPEN_LOOP_HORIZON_MIX
    horizons = [
        query_horizon * mix[i % len(mix)] for i in range(n_queries)
    ]

    sentinel = (
        RecompileSentinel("raise").install()
        if sentinel_mode() is not False
        else None
    )

    def build(**kw):
        return ScenarioFleet(
            config,
            cluster_events,
            workload,
            n_lanes=n_lanes,
            horizon=query_horizon,
            max_pods_per_cycle=max_pods_per_cycle,
            use_pallas=None if not smoke else False,
            lane_async=True,
            telemetry=True,
            **kw,
        )

    def submit_stream(fleet):
        return [fleet.submit(s, h) for s, h in zip(scenarios, horizons)]

    # QUIET layer A/B: plain fleet vs chaos-configured-but-disarmed
    # fleet. quarantine_faults=1 + a 2-round backoff makes the chaos
    # phase's fire -> probe -> re-admit cycle fast and deterministic;
    # when quiet it must cost NOTHING observable.
    plain = build()
    fl = build(
        quarantine_faults=1, quarantine_window=64, quarantine_backoff=2
    )
    q_plain = submit_stream(plain)
    plain.run_async()
    q_warm = submit_stream(fl)
    fl.run_async()
    for i, (qp, qw) in enumerate(zip(q_plain, q_warm)):
        rp, rw = plain.results[qp], fl.results[qw]
        assert (
            rp.counters == rw.counters
            and rp.hpa_replicas == rw.hpa_replicas
            and rp.ca_nodes == rw.ca_nodes
        ), (
            f"host-chaos: query {i} diverges between the plain fleet and "
            "the chaos-configured (disarmed) fleet — the robustness "
            f"layer is NOT free when quiet:\n{rp.counters}\n{rw.counters}"
        )
    stats_plain = dict(plain.engine.dispatch_stats)
    stats_quiet = dict(fl.engine.dispatch_stats)
    assert stats_plain == stats_quiet, (
        "host-chaos: dispatch_stats diverge between the plain fleet and "
        "the chaos-configured (disarmed) fleet on the same stream: "
        f"{stats_plain} vs {stats_quiet}"
    )
    assert fl.fault_report()["chaos"] is None
    plain.close()

    sizes_after_warm = jit_cache_sizes()
    if sentinel is not None:
        sentinel.seal("host-chaos warm-up (quiet A/B, full stream)")
    fl.poll()

    # CHAOS phase: pinned seed => deterministic fault schedule.
    chaos = HostChaos(
        seed=chaos_seed,
        dispatch_rate=dispatch_rate,
        stall_rate=stall_rate,
        stall_ms=stall_ms,
    )
    fl.arm_host_chaos(chaos)
    qids = []
    outcomes: dict = {}
    with _warnings.catch_warnings():
        # Quarantine verdicts warn by design (SaturationWarning); the
        # bench run expects them — the JSON record carries the counts.
        _warnings.simplefilter("ignore")
        for _ in range(max(1, rounds)):
            qids += submit_stream(fl)
            fl.run_async()
            for outcome in fl.poll():
                outcomes[outcome.query] = outcomes.get(outcome.query, 0) + 1
    res = [fl.results[q] for q in qids]
    fails = [r for r in res if not r.ok]
    availability = 1.0 - len(fails) / float(len(res))
    victim_lanes = sorted({r.lane for r in fails if r.lane >= 0})
    report = fl.fault_report()
    failed_by_kind = dict(report["failed"])

    # Stream-once audit: every chaos-phase qid produced exactly one
    # terminal outcome through poll(), result or typed error alike.
    missing = [q for q in qids if outcomes.get(q, 0) != 1]
    assert not missing, (
        f"host-chaos: {len(missing)} qids did not stream exactly one "
        f"terminal outcome via poll() (first: {missing[:5]})"
    )
    assert all(isinstance(r.kind, str) and not r.ok for r in fails)
    assert availability >= 0.90, (
        f"host-chaos: availability {availability:.4f} < 0.90 over the "
        f"injected phase ({len(fails)}/{len(res)} failed)"
    )
    assert victim_lanes == list(range(n_lanes)), (
        f"host-chaos: dispatch faults hit lanes {victim_lanes}, not all "
        f"{n_lanes} lanes — the least-faulted victim rule regressed"
    )
    assert report["quarantine_events"] >= 1, "no lane ever quarantined"
    assert report["readmissions"] >= 1, (
        "no quarantined lane was re-admitted (probe/backoff path dead)"
    )

    sizes_after = jit_cache_sizes()
    recompiled = {
        name: (sizes_after[name], sizes_after_warm[name])
        for name in sizes_after_warm
        if sizes_after[name] != sizes_after_warm[name]
    }
    assert not recompiled, (
        "host-chaos: the injected phase RECOMPILED jit entries — "
        "quarantine/lane-reset must stay data ops "
        f"(compiled-variant counts moved: {recompiled})"
    )
    sentinel_events = 0
    if sentinel is not None:
        sentinel.check("the host-chaos injected phase")
        sentinel_events = len(sentinel.post_seal_events())
        sentinel.uninstall()
    fl.close()

    out = {
        "value": availability,
        "host_chaos": {
            "queries_per_round": n_queries,
            "rounds": max(1, rounds),
            "lanes": n_lanes,
            "seed": chaos_seed,
            "rates": {
                "dispatch": dispatch_rate,
                "stall": stall_rate,
                "stall_ms": stall_ms,
            },
            "availability": round(availability, 4),
            "submitted": len(res),
            "failed": len(fails),
            "failed_by_kind": failed_by_kind,
            "victim_lanes": victim_lanes,
            "quarantine_events": report["quarantine_events"],
            "readmissions": report["readmissions"],
            "lane_states_final": report["lane_states"],
            "chaos_events": report["chaos"]["events"],
            "stream_once_audited": len(qids),
            "quiet_ab_identity_checked": n_queries,
            "quiet_dispatch_stats_equal": True,
            "recompiles_after_warmup": 0,
            "recompile_sentinel": {
                "armed": sentinel is not None,
                "post_warmup_events": sentinel_events,
            },
        },
    }
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(out["host_chaos"], fh, indent=2)
            fh.write("\n")
    return out


def _sweep_path() -> str:
    from kubernetriks_tpu.flags import flag_str

    stem = flag_str("KTPU_SWEEP_PATH") or "ktpu_sweep"
    return f"{stem}.json"


def _open_loop_path() -> str:
    """The open-loop line's JSON artifact rides the sweep stem:
    <KTPU_SWEEP_PATH or ./ktpu_sweep>_openloop.json (CI uploads both)."""
    from kubernetriks_tpu.flags import flag_str

    stem = flag_str("KTPU_SWEEP_PATH") or "ktpu_sweep"
    return f"{stem}_openloop.json"


def _host_chaos_path() -> str:
    """The host-chaos line's JSON artifact rides the sweep stem:
    <KTPU_SWEEP_PATH or ./ktpu_sweep>_hostchaos.json (CI uploads it as
    the `ktpu-host-chaos` artifact)."""
    from kubernetriks_tpu.flags import flag_str

    stem = flag_str("KTPU_SWEEP_PATH") or "ktpu_sweep"
    return f"{stem}_hostchaos.json"


def _trace_path(label: str) -> str:
    """Per-line Chrome trace file: <KTPU_TRACE_PATH or ./ktpu_trace>_<label>.json
    (each traced composed line writes its own file; CI uploads the glob)."""
    from kubernetriks_tpu.flags import flag_str

    stem = flag_str("KTPU_TRACE_PATH") or "ktpu_trace"
    return f"{stem}_{label}.json"


def _metrics_path(label: str) -> str:
    """Per-line capacity-observatory export stem:
    <KTPU_METRICS_PATH or ./ktpu_metrics>_<label> — the engine appends
    drain records to <stem>.jsonl (bounded rotation) and the bench writes
    the final report to <stem>.prom (Prometheus textfile); CI uploads the
    glob next to the Chrome traces."""
    from kubernetriks_tpu.flags import flag_str

    stem = flag_str("KTPU_METRICS_PATH") or "ktpu_metrics"
    return f"{stem}_{label}"


def _emit_sweep(metric: str, value: dict) -> None:
    """The sweep line's unit is scenarios/s (what-if queries drained per
    wall-clock second through the resident fleet), not decisions/s — it
    gets its own emitter so the headline decisions/s contract of the
    other lines stays untouched."""
    rec = {
        "metric": metric,
        "sweep": value["sweep"],
        "value": round(value["value"], 3),
        "unit": "scenarios/s",
    }
    print(json.dumps(rec), flush=True)


def _emit_open_loop(metric: str, value: dict) -> None:
    """The open-loop line's unit is queries/s (continuous submit/poll
    completions per wall-clock second through the lane-async fleet)."""
    rec = {
        "metric": metric,
        "open_loop": value["open_loop"],
        "value": round(value["value"], 3),
        "unit": "queries/s",
    }
    print(json.dumps(rec), flush=True)


def _emit_host_chaos(metric: str, value: dict) -> None:
    """The host-chaos line's unit is availability (completed/submitted
    over the injected phase) — a robustness gate, not a throughput
    number; the full fault-domain disclosure rides in the record."""
    rec = {
        "metric": metric,
        "host_chaos": value["host_chaos"],
        "value": round(value["value"], 4),
        "unit": "availability",
    }
    print(json.dumps(rec), flush=True)


def _emit(metric: str, value) -> None:
    # run_composed returns {"value": median, "spans": {n, min, max}} plus,
    # under --trace, a "telemetry" summary — both ride along in the same
    # JSON line; run_shape returns a bare float (single timed region, no
    # spread to report).
    rec = {"metric": metric}
    if isinstance(value, dict):
        rec["spans"] = value["spans"]
        if "telemetry" in value:
            rec["telemetry"] = value["telemetry"]
        if "endurance" in value:
            # run_endurance's gate disclosure (reclaim counts, quartile
            # throughput spread, watermark/recompile verdicts).
            rec["endurance"] = value["endurance"]
        value = value["value"]
    rec.update(
        value=round(value),
        unit="decisions/s",
        vs_baseline=round(value / BASELINE_DECISIONS_PER_SEC_PER_CHIP, 3),
    )
    print(json.dumps(rec), flush=True)


def main(argv=None) -> None:
    from kubernetriks_tpu.compile_cache import place_compile_cache

    place_compile_cache()
    args = argv if argv is not None else sys.argv[1:]
    smoke = "--smoke" in args
    faults = "--faults" in args
    # --host-chaos: append the fault-tolerant-serving line (DESIGN §15)
    # after the open-loop line — a deterministic HostChaos injector
    # failing dispatches/stalling lanes with the availability,
    # quarantine and zero-recompile gates armed in-bench. Rides both
    # --smoke and --sweep; the sweep line stays LAST in smoke mode.
    host_chaos = "--host-chaos" in args
    # --trace: arm the flight recorder on the composed lines — the
    # telemetry summary lands in their JSON records and each traced line
    # writes a Perfetto-loadable Chrome trace (see _trace_path).
    trace = "--trace" in args
    # --profile NAME: run every tracked line under a named scheduler
    # profile (batched/pipeline.py compiles it into the scan and Pallas
    # decision kernels; the in-bench asserts fail loudly on a silent
    # fallback to the default pipeline). Default: the reference profile.
    profile = None
    if "--profile" in args:
        idx = args.index("--profile") + 1
        if idx >= len(args) or args[idx].startswith("--"):
            raise SystemExit(
                "bench: --profile needs a profile name "
                "(default | best_fit | balanced_packing)"
            )
        profile = args[idx]
    # --sweep [N]: the scenario-vector fleet line standalone — N (default
    # 64) heterogeneous what-if scenarios through ONE resident engine vs
    # the per-engine baseline, with the zero-recompile and lane-cross-talk
    # asserts armed. Writes the full sweep record to the KTPU_SWEEP_PATH
    # JSON artifact (CI uploads it).
    if "--sweep" in args:
        idx = args.index("--sweep") + 1
        n = 64
        if idx < len(args) and not args[idx].startswith("--"):
            n = int(args[idx])
        _emit_sweep(
            f"what-if scenarios/sec (scenario-vector fleet, {n} "
            "heterogeneous scenarios over resident lanes)",
            run_sweep(n_scenarios=n, sweep_path=_sweep_path()),
        )
        _emit_open_loop(
            # The OPEN-LOOP companion line: a continuous submit/poll
            # client streaming heterogeneous-horizon queries through the
            # lane-asynchronous fleet vs the wave-aligned fleet on the
            # same stream. In-bench gates: per-query A/B bit-identity,
            # zero post-warm-up recompiles, lane occupancy > 90%, and
            # >= 1.5x wave-aligned queries/s. Writes the open-loop
            # record next to the sweep artifact (SWEEP_rXX.json
            # material).
            "what-if queries/sec (open-loop lane-async fleet: 32 "
            "heterogeneous-horizon queries over 4 resident lanes)",
            run_open_loop(
                json_path=_open_loop_path(),
                trace_path=(
                    _trace_path("open_loop") if trace else None
                ),
                metrics_path=(
                    _metrics_path("open_loop") if trace else None
                ),
            ),
        )
        if host_chaos:
            _emit_host_chaos(
                "availability (host-chaos lane-async fleet: deterministic "
                "dispatch faults + stalls, quarantine/backoff armed)",
                run_host_chaos(json_path=_host_chaos_path()),
            )
        return
    # --endurance [N]: the bounded-memory endurance line standalone — N
    # (default 96) churn waves through the 4-slot-per-lane CA reserve with
    # reclaim + streaming + the watchdog armed; the in-bench gates
    # (reclaim fired, flat watermarks, zero recompiles, quiet watchdog)
    # run at full scale and the record disclosed the first/last-quartile
    # throughput spread (ENDUR_rXX.json material).
    if "--endurance" in args:
        idx = args.index("--endurance") + 1
        n = 96
        if idx < len(args) and not args[idx].startswith("--"):
            n = int(args[idx])
        _emit(
            f"pod-scheduling decisions/sec (endurance: {n} churn waves "
            "through a 4-slot CA reserve, reclaim + streaming + watchdog)",
            run_endurance(
                n_waves=n,
                # Quota (2) + drain-limbo margin: chaos pod-fault retries
                # race their own node's removal visibility at this scale
                # (see run_endurance).
                ca_slot_multiplier=2,
                trace_path=_trace_path("endurance") if trace else None,
                metrics_path=_metrics_path("endurance"),
            ),
        )
        return
    if smoke:
        # CPU-safe plumbing check: every line must build, run its full
        # composed machinery (slides, HPA, CA asserts included) and print
        # parseable JSON. Values are NOT performance numbers. step=40 keeps
        # the composed lines' >= 5-timed-spans contract at toy shapes.
        smoke_composed = dict(
            rate_per_second=0.375, horizon=500.0, pod_window=128,
            warm_until=290.0, t_end=490.0, step=40.0, max_group_pods=16,
            burst=(100.0, 150.0, 250.0), precompile=False, use_pallas=False,
        )
        _emit(
            "pod-scheduling decisions/sec (SMOKE, 4x8-node clusters)",
            run_shape(4, 8, horizon=200.0, warm_until=90.0, t_end=290.0,
                      step=100.0),
        )
        _emit(
            "pod-scheduling decisions/sec (SMOKE, composed flagship: "
            "4 clusters x HPA+CA+sliding window)",
            run_composed(4, 8, trace=trace,
                         trace_path=_trace_path("smoke_composed") if trace else None,
                         metrics_path=_metrics_path("smoke_composed") if trace else None,
                         **smoke_composed),
        )
        _emit(
            # The superspan-MACHINERY line: same composed shape, scanned
            # multi-slide executor forced on (CPU default is off). The
            # in-bench asserts require the superspan path really dispatched
            # (and never fell back to the ladder), so the CPU CI job
            # catches a silent fallback — tests/test_bench_smoke.py pins
            # this line's presence.
            "pod-scheduling decisions/sec (SMOKE, composed flagship + "
            "superspan executor)",
            run_composed(4, 8, superspan=True, fast_forward=False,
                         trace=trace,
                         trace_path=_trace_path("smoke_superspan") if trace else None,
                         metrics_path=_metrics_path("smoke_superspan") if trace else None,
                         **smoke_composed),
        )
        _emit(
            # The streaming-FEEDER line: same composed shape, superspan +
            # the K-deep streaming ingestion ring forced on (CPU default
            # is off). The in-bench asserts require the feeder really
            # staged the run (device slide payload NOT materialized,
            # slabs produced AND installed, sync budget unchanged), so
            # the CPU CI job catches a silent fallback to whole-trace
            # staging — tests/test_bench_smoke.py pins this line. The
            # default segment width at this toy shape clamps to the whole
            # padded payload, so the superspan program is the
            # cache-warmed one from the previous line (zero extra
            # compile); the staging machinery still runs end to end
            # through the feeder ring.
            "pod-scheduling decisions/sec (SMOKE, composed flagship + "
            "superspan + streaming feeder)",
            run_composed(4, 8, superspan=True, stream=True,
                         fast_forward=False, trace=trace,
                         trace_path=_trace_path("smoke_stream") if trace else None,
                         metrics_path=_metrics_path("smoke_stream") if trace else None,
                         **smoke_composed),
        )
        _emit(
            # The ENDURANCE line (r14): churn waves through a 2-slot CA
            # reserve with slot reclaim + streaming + the saturation
            # watchdog armed — the run only finishes because reclaim
            # recycles retired slots (reclaim off raises at readout
            # here). The in-bench asserts (reclaim fired, flat RSS/slab
            # watermarks, zero recompiles after warm-up, quiet watchdog)
            # make a reclaim regression loud in CI —
            # tests/test_bench_smoke.py pins this line and its endurance
            # block.
            "pod-scheduling decisions/sec (SMOKE, endurance churn: CA "
            "reserve reclaim + streaming feeder)",
            run_endurance(
                n_clusters=2,
                n_waves=9,
                spacing=120.0,
                warm_waves=2,
                pod_window=64,
                trace_path=_trace_path("smoke_endurance") if trace else None,
                metrics_path=(
                    _metrics_path("smoke_endurance") if trace else None
                ),
            ),
        )
        _emit(
            # The compiled-PROFILE line: the same toy shape under the
            # second (best_fit packing) scheduler profile, exercising the
            # profile -> kernel-static lowering end to end. The in-bench
            # asserts require the engine really compiled the requested
            # profile (never a silent fallback to the default pipeline,
            # mirroring the streaming smoke line) —
            # tests/test_bench_smoke.py pins this line's presence.
            # Pinned to best_fit regardless of --profile: this line IS the
            # second-profile machinery gate, and its label must match what
            # ran (--profile still steers the non-smoke tracked lines).
            "pod-scheduling decisions/sec (SMOKE, 4x8-node clusters, "
            "best_fit profile)",
            run_shape(4, 8, horizon=200.0, warm_until=90.0, t_end=290.0,
                      step=100.0, profile="best_fit"),
        )
        _emit(
            "pod-scheduling decisions/sec (SMOKE, 4x8-node clusters = "
            "north-star stand-in)",
            # Same shape as the continuity line ON PURPOSE: the second run
            # is a jit-cache hit, so the plumbing check pays one
            # plain-shape compile, not two. Smoke values are meaningless as
            # performance numbers either way.
            run_shape(4, 8, horizon=200.0, warm_until=90.0, t_end=290.0,
                      step=100.0),
        )
        if faults:
            _emit(
                "pod-scheduling decisions/sec (SMOKE, composed flagship + "
                "chaos faults)",
                run_composed(4, 8, faults=True, **smoke_composed),
            )
        _emit_open_loop(
            # The OPEN-LOOP line: 8 heterogeneous-horizon queries
            # streamed through a continuous submit/poll lane-async
            # fleet next to the wave-aligned fleet on the same stream —
            # the in-bench asserts require per-query A/B bit-identity
            # (lane-async completion order must not change any result)
            # and zero post-warm-up recompiles across pump rounds
            # (a per-lane clock or trace offset regressing to a
            # jit-static recompiles per reseed and fails loudly here).
            # tests/test_bench_smoke.py pins this line's presence and
            # position: BEFORE the sweep line, which must stay LAST
            # (its baseline's jax.clear_caches would cold-start this
            # line's fleets).
            "what-if queries/sec (SMOKE, open-loop lane-async fleet: 8 "
            "queries over 4 resident lanes)",
            run_open_loop(
                n_queries=8,
                n_lanes=4,
                n_nodes=8,
                rate_per_second=0.375,
                horizon=300.0,
                query_horizon=350.0,
                max_group_pods=16,
                max_pods_per_cycle=64,
                smoke=True,
                json_path=_open_loop_path(),
                trace_path=(
                    _trace_path("open_loop") if trace else None
                ),
                metrics_path=(
                    _metrics_path("open_loop") if trace else None
                ),
            ),
        )
        if host_chaos:
            _emit_host_chaos(
                # The HOST-CHAOS line (DESIGN §15): the open-loop stream
                # under a pinned-seed HostChaos injector — quiet-layer
                # A/B bit-identity, availability >= 90%, every-lane
                # fault coverage, quarantine fire -> probe -> re-admit,
                # stream-once error delivery and zero post-warm-up
                # recompiles are all asserted inside run_host_chaos.
                # AFTER the open-loop line (shares its warm jit caches),
                # BEFORE the sweep line (which must stay LAST: its
                # cold-process baseline clears the jit caches) —
                # tests/test_bench_smoke.py pins this order.
                "availability (SMOKE, host-chaos lane-async fleet: "
                "deterministic dispatch faults + stalls over 4 lanes)",
                run_host_chaos(
                    smoke=True,
                    json_path=_host_chaos_path(),
                ),
            )
        _emit_sweep(
            # The scenario-FLEET line: 8 heterogeneous what-if scenarios
            # through one resident 4-lane fleet (batched/fleet.py) — the
            # in-bench asserts fail loudly on a silent recompile after
            # warm-up (a scenario parameter regressing to a jit-static)
            # or on lane cross-talk (duplicate scenarios planted in a
            # different lane and wave must return bit-identical rows).
            # tests/test_bench_smoke.py pins this line's presence. LAST
            # among the smoke lines: its per-engine baseline models one
            # process per scenario via jax.clear_caches, which would
            # cold-start any line that ran after it.
            "what-if scenarios/sec (SMOKE, scenario-vector fleet: 8 "
            "scenarios over 4 resident lanes)",
            run_sweep(
                n_scenarios=8,
                n_lanes=4,
                horizon=300.0,
                query_horizon=350.0,
                smoke=True,
                # One cold baseline engine is enough for the smoke
                # plumbing check (the asserts this line exists for are
                # the recompile/cross-talk gates, not the speedup).
                baseline_engines=1,
                sweep_path=_sweep_path(),
            ),
        )
        return
    suffix = f", {profile} profile" if profile else ""
    if faults:
        _emit(
            "pod-scheduling decisions/sec (single chip, composed flagship + "
            f"chaos faults: crashes/recoveries + CrashLoopBackOff{suffix})",
            run_composed(faults=True, profile=profile),
        )
    _emit(
        f"pod-scheduling decisions/sec (single chip, 1024x256-node clusters{suffix})",
        run_shape(1024, 256, profile=profile),
    )
    _emit(
        "pod-scheduling decisions/sec (single chip, composed flagship: "
        f"256 clusters x HPA+CA+sliding window+Pallas{suffix})",
        run_composed(
            trace=trace,
            trace_path=_trace_path("composed") if trace else None,
            metrics_path=_metrics_path("composed") if trace else None,
            profile=profile,
        ),
    )
    _emit(
        "pod-scheduling decisions/sec (single chip, 1250x1000-node clusters "
        f"= north-star per-chip share{suffix})",
        run_shape(1250, 1000, profile=profile),
    )


if __name__ == "__main__":
    sys.exit(main())
