"""Chip smoke: the quickest proof that the batched engine still starts on the
accelerator with its kernels compiled.

    python chip_smoke.py                  # one TPU chip, every leg
    python chip_smoke.py --devices 4      # the mesh legs on a four-chip host
    python chip_smoke.py --cpu-plumbing   # toy shapes, Pallas INTERPRETED:
                                          # debugs this script off-chip and
                                          # proves nothing about the chip

One process, the entry points a user calls (engine build + step_until_time,
ScenarioFleet submit/pump/poll, cli.main), random traces from fixed seeds.
Nothing is caught: any failure is a traceback and a non-zero exit. Without
--cpu-plumbing the run refuses any platform but "tpu" before it builds
anything. Each leg prints one JSON line, then a summary line; the last
stdout line is the result the chip check reads, exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}} with
the device as JAX reports it. Wall seconds are set-up facts (compile
included), never speeds.

Legs:
- pure: the bench.run_shape scenario at the north-star per-chip share
  (1250 clusters x 1000 nodes, BASELINE.json) and at 1024 x 256 — warm-up
  plus one 200 sim-s chunk — against a use_pallas=False lax.scan engine on
  the same inputs (batched.state.compare_states). Two more shapes sit on the
  other sides of the engine's VMEM fit gates, so every scheduling
  formulation is compiled by Mosaic: a 4000 s trace (pod axis too wide for
  the megakernel: select + commit kernels) and one 1313-node cluster (the
  reference's Alibaba cluster; lane tile mostly padding: candidate kernel).
- composed: bench._composed_inputs (HPA burst, CA up and down, sliding pod
  window) with every tristate at its accelerator default, against an
  all-off reference build (scan kernels, ladder, host slides). Statics never
  change semantics; this is where that is checked on the chip.
- served: a lane-async ScenarioFleet of one full lane tile, heterogeneous
  horizons, every query answered with a result. fleet.pump turns a failed
  dispatch (a Mosaic compile error included) into per-query errors and
  carries on, so an error outcome is raised here.
- cli: cli.main --backend batched on the bundled data/*.yaml traces.
- faults: the pure scenario on 1000 identical nodes with the chaos engine's
  node channel on (per-node chains and one failure group a rack of 50, each
  cluster its own schedule, sampled inside the build), so that the event
  kernel applies crashes and recoveries and a dead node's pods run again,
  against the lax.scan engine on its scatter path. `--only faults` runs it
  alone.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

# The accelerator defaults of the engine's tristates, spelled out for
# --cpu-plumbing: on a CPU backend they all resolve off, and the run would
# compare the reference with itself.
ACCELERATOR_STATICS = dict(
    donate=True,
    fuse_slide=True,
    superspan=True,
    stream=True,
    lane_major=True,
    window_razor=True,
    reclaim=True,
)
ALL_OFF = dict.fromkeys(ACCELERATOR_STATICS, False)

CHIP_SHAPES = dict(
    # (clusters per device, nodes, trace seconds, formulation the fit gates
    # must pick); the first is what the mesh run shards.
    pure=[
        (1250, 1000, 1000.0, "megakernel"),
        (1024, 256, 1000.0, "megakernel"),
        (1024, 256, 4000.0, "select"),
        (1, 1313, 1000.0, "candidate"),
    ],
    pure_run=dict(warm_until=190.0, chunk=200.0),
    composed=dict(
        n_clusters=256, n_nodes=32, pod_window=512, t_end=1200.0,
        cycle="megakernel",
        inputs=dict(
            rate_per_second=1.5, horizon=1000.0, max_group_pods=64,
            burst=(300.0, 300.0, 400.0),
        ),
    ),
    served=dict(
        # One full lane tile is what engages the dense kernel set.
        n_lanes=128, n_queries=256, query_horizon=450.0,
        max_pods_per_cycle=256, cycle="megakernel",
        setup=dict(
            n_nodes=64, rate_per_second=3.0, horizon=400.0,
            max_group_pods=32, burst=(100.0, 150.0, 250.0),
        ),
    ),
    cli_clusters=1024,
    # (clusters, nodes, rack size, trace seconds, traces to a node's and a
    # rack's failure, formulations): a fifth of the north-star batch keeps
    # the two builds' per-cluster trace compiles short.
    faults=(256, 1000, 50, 1000.0, 24.0, "megakernel", "kernel"),
)
PLUMBING_SHAPES = dict(
    pure=[(4, 8, 200.0, "candidate")],
    pure_run=dict(warm_until=90.0, chunk=100.0),
    composed=dict(
        n_clusters=4, n_nodes=8, pod_window=128, t_end=700.0,
        cycle="candidate",
        inputs=dict(
            rate_per_second=0.375, horizon=500.0, max_group_pods=16,
            burst=(100.0, 150.0, 250.0),
        ),
    ),
    served=dict(
        n_lanes=4, n_queries=8, query_horizon=450.0, max_pods_per_cycle=64,
        cycle="candidate",
        setup=dict(
            n_nodes=8, rate_per_second=0.375, horizon=400.0,
            max_group_pods=16, burst=(100.0, 150.0, 250.0),
        ),
    ),
    cli_clusters=2,
    faults=(4, 8, 4, 400.0, 2.0, "candidate", "scatter"),
)


def emit(leg: str, t0: float, **fields) -> dict:
    rec = {"leg": leg, **fields, "wall_s": round(time.perf_counter() - t0, 1)}
    print(json.dumps(rec), flush=True)
    return rec


def assert_sharded(sim, mesh) -> None:
    """State really lives on every device of the mesh, in equal shards, and
    the window programs are wrapped in their one shard_map."""
    sharding = sim.kernel_formulation()["sharding"]
    if mesh is None:
        assert sharding is None, sharding
        return
    assert sharding == "shard_map", sharding
    phase = sim.state.pods.phase
    shards = phase.addressable_shards
    shapes = [s.data.shape for s in shards]
    assert len(phase.devices()) == mesh.size, phase.sharding
    assert len(shapes) == mesh.size and len(set(shapes)) == 1, shapes


def pure_leg(n_clusters, n_nodes, horizon, cycle, run, forced, mesh) -> dict:
    import bench
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.batched.state import compare_states

    t0 = time.perf_counter()
    config, cluster_events, workload = bench._shape_inputs(n_nodes, horizon)

    def build(**kw):
        return build_batched_from_traces(
            config, cluster_events, workload, n_clusters=n_clusters,
            max_pods_per_cycle=64, **kw,
        )

    sim = build(mesh=mesh, **forced)
    ref = build(use_pallas=False)
    for s in (sim, ref):
        s.step_until_time(run["warm_until"])
        s.step_until_time(run["warm_until"] + run["chunk"])
    formulation = sim.kernel_formulation()
    assert formulation["cycle"] == cycle, (formulation, cycle)
    assert_sharded(sim, mesh)
    decisions = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())
    assert decisions > 0
    mismatches = compare_states(ref.state, sim.state)
    assert not mismatches, mismatches
    return emit(
        "pure", t0, clusters=n_clusters, nodes=n_nodes, pods=sim.n_pods,
        formulation=formulation, decisions=decisions, reference="lax.scan",
        mismatches=0,
    )


def faults_leg(shape, run, forced) -> dict:
    """Node crashes and recoveries through the dense kernel set against the
    lax.scan engine: identical nodes, so a recovered node has to sit where
    its name sorts, and the final state holds every rescheduled pod."""
    import bench
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.batched.state import compare_states
    from kubernetriks_tpu.config import FailureGroupConfig, FaultInjectionConfig, NodeFaultConfig

    n_clusters, n_nodes, rack, horizon, traces_to_failure, cycle, events = shape
    t0 = time.perf_counter()
    config, cluster_events, workload = bench._shape_inputs(n_nodes, horizon)
    mttf = horizon * traces_to_failure
    config.fault_injection = FaultInjectionConfig(
        enabled=True,
        horizon=horizon,
        node=NodeFaultConfig(mttf=mttf, mttr=120.0),
        failure_groups=[
            FailureGroupConfig(
                members=[f"gen_node_{i}" for i in range(lo, lo + rack)],
                mttf=mttf,
                mttr=240.0,
            )
            for lo in range(0, n_nodes, rack)
        ],
    )

    def build(**kw):
        return build_batched_from_traces(
            config, cluster_events, workload, n_clusters=n_clusters,
            max_pods_per_cycle=64, **kw,
        )

    sim = build(**forced)
    ref = build(use_pallas=False)
    for s in (sim, ref):
        s.step_until_time(run["warm_until"])
        s.step_until_time(horizon + 200.0)
    formulation = sim.kernel_formulation()
    assert formulation["cycle"] == cycle and formulation["events"] == events, formulation
    assert sim.n_nodes == n_nodes, (sim.n_nodes, "a recovery took a fresh slot")
    counters = sim.metrics_summary()["counters"]
    assert counters["node_crashes"] > 0 and counters["node_recoveries"] > 0, counters
    assert counters["pod_interruptions"] > 0, counters
    mismatches = compare_states(ref.state, sim.state)
    assert not mismatches, mismatches
    return emit(
        "faults", t0, clusters=n_clusters, nodes=n_nodes, pods=sim.n_pods,
        formulation=formulation, node_crashes=counters["node_crashes"],
        node_recoveries=counters["node_recoveries"],
        pod_interruptions=counters["pod_interruptions"],
        pods_succeeded=counters["pods_succeeded"], reference="lax.scan",
        mismatches=0,
    )


def composed_leg(shape, forced, mesh) -> dict:
    import bench
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.batched.state import compare_states

    t0 = time.perf_counter()
    config, cluster_events, workload = bench._composed_inputs(
        shape["n_nodes"], **shape["inputs"]
    )

    def build(**kw):
        return build_batched_from_traces(
            config, cluster_events, workload, n_clusters=shape["n_clusters"],
            max_pods_per_cycle=64, pod_window=shape["pod_window"], **kw,
        )

    sim = build(mesh=mesh, **forced)
    # Reclaim compacts CA slots and adds state leaves, so its on/off pair is
    # comparable by trajectory only (tests/test_reclaim.py); the reference
    # keeps the value under test and turns everything else off.
    ref = build(use_pallas=False, **{**ALL_OFF, "reclaim": sim.reclaim})
    for t in np.linspace(0.0, shape["t_end"], 5)[1:]:
        sim.step_until_time(float(t))
        ref.step_until_time(float(t))
    formulation = sim.kernel_formulation()
    stats = dict(sim.dispatch_stats)
    counters = sim.metrics_summary()["counters"]
    assert formulation["cycle"] == shape["cycle"], formulation
    assert sim.lane_major, "lane-major node state is off"
    assert stats["superspans"] > 0, stats
    assert stats["window_chunks"] == 0, stats
    assert stats["ladder_fallbacks"] == 0, stats
    assert stats["feeder_slabs_produced"] > 0, stats
    assert stats["stage_refills"] > 0, stats
    assert sim._pod_base > 0, "the pod window never slid"
    for key in (
        "total_scaled_up_pods",
        "total_scaled_up_nodes",
        "total_scaled_down_nodes",
    ):
        assert counters[key] > 0, (key, counters)
    assert_sharded(sim, mesh)
    mismatches = compare_states(ref.state, sim.state)
    assert not mismatches, mismatches
    sim.close()
    ref.close()
    return emit(
        "composed", t0, clusters=shape["n_clusters"], nodes=sim.n_nodes,
        pod_window=shape["pod_window"], formulation=formulation,
        lane_major=sim.lane_major, reclaim=sim.reclaim,
        superspans=stats["superspans"],
        feeder_slabs=stats["feeder_slabs_produced"], pod_base=sim._pod_base,
        decisions=counters["scheduling_decisions"],
        scaled_up_pods=counters["total_scaled_up_pods"],
        scaled_up_nodes=counters["total_scaled_up_nodes"],
        scaled_down_nodes=counters["total_scaled_down_nodes"],
        reference="scan+ladder, statics off", mismatches=0,
    )


def served_leg(shape, forced) -> dict:
    import bench
    from kubernetriks_tpu.batched.fleet import ScenarioFleet
    from kubernetriks_tpu.recompile import RecompileSentinel

    t0 = time.perf_counter()
    _, config, cluster_events, workload = bench._sweep_setup(**shape["setup"])
    scenarios, _ = bench._sweep_scenarios(shape["n_queries"])
    mix = bench.OPEN_LOOP_HORIZON_MIX
    horizons = [
        shape["query_horizon"] * mix[i % len(mix)]
        for i in range(shape["n_queries"])
    ]
    sentinel = RecompileSentinel("raise").install()
    fleet = ScenarioFleet(
        config, cluster_events, workload, n_lanes=shape["n_lanes"],
        horizon=shape["query_horizon"],
        max_pods_per_cycle=shape["max_pods_per_cycle"], lane_async=True,
        span_windows=4, **forced,
    )
    formulation = fleet.engine.kernel_formulation()
    assert formulation["cycle"] == shape["cycle"], formulation
    windows = sum(fleet.engine.horizon_windows(h) for h in horizons)

    def serve():
        qids = [fleet.submit(s, h) for s, h in zip(scenarios, horizons)]
        outcomes = []
        for _ in range(windows + len(qids)):  # every round steps >= 1 window
            fleet.pump()
            outcomes += fleet.poll()
            if len(outcomes) == len(qids):
                break
        assert sorted(o.query for o in outcomes) == qids, (
            len(outcomes), len(qids),
        )
        for outcome in outcomes:
            if not outcome.ok:
                raise outcome
        return outcomes

    serve()
    warmup_compiles = len(sentinel.events)
    assert warmup_compiles >= 1, "the recompile sentinel saw no compile"
    sentinel.seal("served leg warm-up (fleet build + the full stream once)")
    outcomes = serve()
    sentinel.check("the served leg's post-warm-up stream")
    sentinel.uninstall()
    decisions = sum(o.counters["scheduling_decisions"] for o in outcomes)
    assert decisions > 0
    fleet.close()
    return emit(
        "served", t0, lanes=shape["n_lanes"], nodes=fleet.engine.n_nodes,
        formulation=formulation, queries=len(outcomes), query_errors=0,
        decisions=int(decisions), warmup_compiles=warmup_compiles,
        recompiles_after_warmup=0,
    )


def cli_leg(n_clusters) -> dict:
    from kubernetriks_tpu import cli

    t0 = time.perf_counter()
    data = os.path.join(os.path.dirname(cli.__file__), "data")
    with tempfile.TemporaryDirectory() as tmp:
        config_file = os.path.join(tmp, "config.yaml")
        with open(config_file, "w") as fh:
            fh.write(
                f"""
sim_name: chip_smoke_cli
seed: 7
scheduling_cycle_interval: 10.0
as_to_ps_network_delay: 0.050
ps_to_sched_network_delay: 0.010
sched_to_as_network_delay: 0.020
as_to_node_network_delay: 0.150
trace_config:
  generic_trace:
    cluster_trace_path: {data}/generic_cluster_trace_example.yaml
    workload_trace_path: {data}/generic_workload_trace_example.yaml
"""
            )
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.main(
                [
                    "--config-file", config_file, "--backend", "batched",
                    "--clusters", str(n_clusters),
                ]
            )
    assert rc == 0, rc
    counters = json.loads(out.getvalue())["counters"]
    # The bundled workload trace holds two pods per cluster.
    assert counters["pods_succeeded"] == 2 * n_clusters, counters
    return emit(
        "cli", t0, clusters=n_clusters,
        pods_succeeded=counters["pods_succeeded"],
        decisions=counters["scheduling_decisions"],
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--devices", type=int, default=1,
        help="run the pure and composed legs under Mesh(devices[:N], "
        "('clusters',)), N x the per-chip clusters, checked against one "
        "chip's result",
    )
    parser.add_argument(
        "--cpu-plumbing", action="store_true",
        help="toy shapes with Pallas in interpret mode on whatever backend "
        "JAX has: debugs this script, proves nothing about the chip",
    )
    parser.add_argument(
        "--only", choices=("pure", "composed", "served", "cli", "faults"),
        help="run this leg alone (one chip)",
    )
    args = parser.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    from kubernetriks_tpu.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    on_chip = not args.cpu_plumbing
    if on_chip and device["platform"] != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {device} "
            "(--cpu-plumbing debugs the script off-chip)",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.devices:
        print(
            f"chip_smoke: --devices {args.devices} but JAX found {device}",
            file=sys.stderr,
        )
        return 2
    print(
        json.dumps(
            {
                "leg": "start", "device": device, "jax": jax.__version__,
                "compile_cache": cache_dir, "cpu_plumbing": not on_chip,
                "devices_used": args.devices,
            }
        ),
        flush=True,
    )

    shapes = CHIP_SHAPES if on_chip else PLUMBING_SHAPES
    # On the chip every choice is the engine's own default; off it the same
    # program family is forced on and its kernels interpreted.
    forced = (
        {}
        if on_chip
        else dict(use_pallas=True, pallas_interpret=True, **ACCELERATOR_STATICS)
    )
    mesh = None
    if args.devices > 1:
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devices[: args.devices]), ("clusters",))

    def wanted(leg: str) -> bool:
        return args.only in (None, leg)

    legs = []
    for per_device, n_nodes, horizon, cycle in (
        shapes["pure"] if mesh is None else shapes["pure"][:1]
    ) if wanted("pure") else ():
        legs.append(
            pure_leg(
                per_device * args.devices, n_nodes, horizon, cycle,
                shapes["pure_run"], forced, mesh,
            )
        )
    composed = dict(shapes["composed"])
    composed["n_clusters"] *= args.devices
    # use_pallas=True as in bench.run_composed: the flagship is the kernel
    # path by definition, not by the auto gate.
    if wanted("composed"):
        legs.append(composed_leg(composed, {**forced, "use_pallas": True}, mesh))
    if mesh is None:
        # A lane-async engine turns the global-clock statics off by itself
        # and refuses them when asked for by name.
        per_lane = {
            k: v
            for k, v in forced.items()
            if k not in ("superspan", "stream", "fuse_slide")
        }
        if wanted("served"):
            legs.append(served_leg(shapes["served"], per_lane))
        if wanted("cli"):
            legs.append(cli_leg(shapes["cli_clusters"]))
        if wanted("faults"):
            legs.append(faults_leg(shapes["faults"], shapes["pure_run"], forced))

    print(
        json.dumps(
            {
                "leg": "summary", "cpu_plumbing": not on_chip,
                "devices_used": args.devices,
                "legs": [leg["leg"] for leg in legs],
                "wall_s": round(time.perf_counter() - t_start, 1),
                "claim": None,
            }
        ),
        flush=True,
    )
    # The chip check's contract: these keys and no others, last on stdout.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
