"""Chip smoke: the quickest proof that the batched engine still starts on the
accelerator with its kernels compiled.

    python chip_smoke.py                  # one TPU chip, every leg
    python chip_smoke.py --devices 4      # the mesh legs on a four-chip host
    python chip_smoke.py --cpu-plumbing   # toy shapes, Pallas INTERPRETED:
                                          # debugs this script off-chip and
                                          # proves nothing about the chip

One process, the entry points a user calls (engine build + step_until_time,
ScenarioFleet submit/pump/poll, cli.main). Nothing is caught: any failure is
a traceback and a non-zero exit. Without --cpu-plumbing the run refuses any
platform but "tpu" before it builds anything. Each leg prints one JSON line,
then a summary line; the last stdout line is the result the chip check reads,
exactly {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
with the device as JAX reports it. Wall seconds are set-up facts (compile
included), never speeds.

The legs stand on the benchmark's inputs, read-only: a leg names a cell of
BENCHMARK.json, its deployment and load are that cell's data files
(benchmark/configs/*.json, benchmark/traffic/*.json) through
benchmark/deployment.py and benchmark/traffic_gen.py, and --cpu-plumbing
lays the cell's rehearsal file (benchmark/rehearsal/*.json) over them, so
the smoke compiles the cells' own shapes. One seeded stream (cluster 0's) is
replicated over the batch. Where a leg needs a shape no cell has, it
overrides a number of the files and says which.

Legs:
- pure: `sched1k.montecarlo` (1250 clusters x 1000 nodes, the north-star
  per-chip share) and the same load on 1024 x 256 nodes, warm-up plus one
  200 sim-s chunk, against a use_pallas=False lax.scan engine on the same
  inputs (batched.state.compare_states). Two more shapes sit on the other
  sides of the engine's VMEM fit gates, so every scheduling formulation is
  compiled by Mosaic: arrivals over 4000 s (pod axis too wide for the
  megakernel: select + commit kernels) and one cluster of as many nodes as
  `alibaba1313` has machines (lane tile mostly padding: candidate kernel).
- composed: `autoscaled.stream` (HPA burst, CA up and down, sliding pod
  window) with every tristate at its accelerator default, against the
  program's plain formulation (scan kernels, ladder, host slides). Statics
  never change semantics; this is where that is checked on the chip.
- served: `autoscaled.whatif`, a lane-async ScenarioFleet of one full lane
  tile, the mix's catalogue and horizons, every query answered with a
  result. fleet.pump turns a failed dispatch (a Mosaic compile error
  included) into per-query errors and carries on, so an error outcome is
  raised here.
- cli: cli.main --backend batched on the bundled data/*.yaml traces.
- faults: `sched1k-faults.montecarlo`'s nodes, racks and failure clocks
  with the chaos engine's node channel on (per-node chains and one failure
  group a rack, each cluster its own schedule, sampled inside the build,
  where the cell hands its schedule over already sampled), so that the
  event kernel applies crashes and recoveries and a dead node's pods run
  again, against the lax.scan engine on its scatter path. `--only faults`
  runs it alone.
- kubescore: `sched1k-kubescore.montecarlo`'s pools, labels, taints and
  classes (benchmark/kubescore_gen.py) on one lane tile of clusters, ranked
  as kube-scheduler ranks (`ranking: integer`), the megakernel against the
  lax.scan engine.
- pools: `sched1k-pools.montecarlo`'s four machine shapes, tainted pool and
  classes (benchmark/pools_gen.py) on one lane tile of clusters, ranked by
  the exact key (`ranking: exact`), the megakernel against the lax.scan
  engine. With kubescore the two legs whose argmax is not a float32 compare.
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
from typing import NamedTuple

import numpy as np

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

# Every stream of the smoke is drawn from this seed (traffic_gen.derive_seed);
# the what-if mix fixes its base workload's seed itself.
SEED = 3

# Per leg: the cell, the overrides of its files' numbers (none: the cell as
# it runs) and the formulation the fit gates must pick. The first pure shape
# is what the mesh run shards.
CHIP_LEGS = dict(
    pure=[
        ({}, "megakernel"),
        ({"clusters": 1024, "nodes": 256}, "megakernel"),
        ({"clusters": 1024, "nodes": 256, "horizon_s": 4000.0}, "select"),
        ({"clusters": 1, "nodes": "alibaba1313"}, "candidate"),
    ],
    pure_run=dict(warm_until=190.0, chunk=200.0),
    composed="megakernel",
    served=dict(n_queries=256, cycle="megakernel"),
    cli_clusters=1024,
    # A fifth of the north-star batch keeps the two builds' per-cluster
    # trace compiles short.
    faults=({"clusters": 256}, "megakernel", "kernel"),
    # One lane tile: the integer chain is a lane's own.
    kubescore=({"clusters": 128}, "megakernel"),
    pools=({"clusters": 128}, "megakernel"),
)
PLUMBING_LEGS = dict(
    pure=[({}, "candidate")],
    pure_run=dict(warm_until=90.0, chunk=100.0),
    composed="candidate",
    served=dict(n_queries=8, cycle="candidate"),
    cli_clusters=2,
    faults=({}, "candidate", "scatter"),
    kubescore=({"clusters": 4}, "candidate"),
    pools=({"clusters": 4}, "candidate"),
)


# The drivers whose records are not traffic_gen's bare ones: their generator
# and the module that puts a record's labels, taints and terms on the
# program's objects.
RECORDS_OF = {
    "batch_jobs_pools": ("pools_gen", "pools_program"),
    "batch_jobs_kubescore": ("kubescore_gen", "kubescore_program"),
}


class Leg(NamedTuple):
    """A cell's files as a leg runs them."""

    cell: object  # benchmark.harness.Cell
    config: object  # the deployment as the program's SimulationConfig
    cluster_events: list
    workload: list
    width: int  # clusters a chip, or lanes
    rehearsed: bool

    def engine_kwargs(self) -> dict:
        return {**self.cell.config["engine"], **self.cell.traffic.get("engine", {})}

    def forced(self, lane_async: bool = False) -> dict:
        """What the engine under test is built with beside the cell's
        settings. On the chip nothing: every choice is the engine's own
        default; rehearsed, the same program family is forced on and its
        kernels interpreted, as a cell's rehearsal does."""
        from benchmark import program

        return program.rehearsal_kwargs(lane_async) if self.rehearsed else {}

    def build(self, n_clusters: int, **kw):
        from kubernetriks_tpu.batched.engine import build_batched_from_traces

        return build_batched_from_traces(
            self.config, self.cluster_events, self.workload, n_clusters=n_clusters,
            **{**self.engine_kwargs(), **kw},
        )


def leg_inputs(cell_name: str, rehearsed: bool, clusters=None, nodes=None, horizon_s=None) -> Leg:
    """`rehearsed` lays the cell's rehearsal file over its files; `clusters`,
    `nodes` (a count, or the configuration whose machines to count) and
    `horizon_s` replace that number of the files."""
    from benchmark import deployment, program, traffic_gen
    from benchmark.harness import Cell, load_json

    rehearsal = os.path.join(CHECKOUT, "benchmark", "rehearsal", cell_name + ".json")
    cell = Cell(
        load_json(os.path.join(CHECKOUT, "BENCHMARK.json")), cell_name,
        load_json(rehearsal) if rehearsed else None,
    )
    dep, traffic = cell.config["deployment"], cell.traffic
    if isinstance(nodes, str):
        nodes = load_json(os.path.join(CHECKOUT, "benchmark", "configs", nodes + ".json"))[
            "deployment"]["machines"]
    if nodes is not None:
        dep["nodes"] = nodes
    if horizon_s is not None:
        traffic["plain"]["horizon_s"] = horizon_s
    api, gen, place = program.program_api(), traffic_gen, ()
    if traffic["driver"] in RECORDS_OF:
        # Records with labels, taints and placements, and how they go onto
        # the program's objects.
        import importlib

        gen, records_program = (importlib.import_module("benchmark." + m) for m in RECORDS_OF[traffic["driver"]])
        api = records_program.program_api()
        place = (records_program.placer(api),)
    config = api.SimulationConfig.from_yaml(deployment.config_yaml(cell.config_name, dep))
    seed = int(traffic.get("base_workload_seed", SEED))
    cluster_events = gen.to_events(gen.cluster_records(dep), api, *place)
    workload = gen.to_events(gen.workload_records(traffic, seed, 0), api, *place)
    width = clusters or traffic.get("clusters_per_chip") or traffic["lanes"]
    return Leg(cell, config, cluster_events, workload, int(width), rehearsed)


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


def pure_leg(overrides, cycle, run, rehearsed, devices, mesh) -> dict:
    from kubernetriks_tpu.batched.state import compare_states

    t0 = time.perf_counter()
    leg = leg_inputs("sched1k.montecarlo", rehearsed, **overrides)
    n_clusters = leg.width * devices
    sim = leg.build(n_clusters, mesh=mesh, **leg.forced())
    ref = leg.build(n_clusters, use_pallas=False)
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
        "pure", t0, clusters=n_clusters, nodes=sim.n_nodes, pods=sim.n_pods,
        formulation=formulation, decisions=decisions, reference="lax.scan",
        mismatches=0,
    )


def faults_leg(shape, run, rehearsed) -> dict:
    """Node crashes and recoveries through the dense kernel set against the
    lax.scan engine: identical nodes, so a recovered node has to sit where
    its name sorts, and the final state holds every rescheduled pod."""
    from kubernetriks_tpu.batched.state import compare_states
    from kubernetriks_tpu.config import FailureGroupConfig, FaultInjectionConfig, NodeFaultConfig

    overrides, cycle, events = shape
    t0 = time.perf_counter()
    leg = leg_inputs("sched1k-faults.montecarlo", rehearsed, **overrides)
    cell, n_clusters = leg.cell, leg.width
    clocks, rack = cell.config["fault_injection"], cell.config["racks"]["nodes_per_rack"]
    names = [event.node.metadata.name for _, event in leg.cluster_events]
    leg.config.fault_injection = FaultInjectionConfig(
        enabled=True,
        horizon=float(clocks["no_fault_after_s"]),
        node=NodeFaultConfig(mttf=clocks["node"]["mttf"], mttr=clocks["node"]["mttr"]),
        failure_groups=[
            FailureGroupConfig(
                members=names[lo : lo + rack],
                mttf=clocks["failure_groups"]["mttf"],
                mttr=clocks["failure_groups"]["mttr"],
            )
            for lo in range(0, len(names), rack)
        ],
    )
    n_nodes = len(names)
    sim = leg.build(n_clusters, **leg.forced())
    ref = leg.build(n_clusters, use_pallas=False)
    for s in (sim, ref):
        s.step_until_time(run["warm_until"])
        s.step_until_time(float(cell.traffic["job_end_s"]))
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


def ranked_leg(name: str, ranking: str, counters, shape, run, rehearsed) -> dict:
    """A node-pool cell (`sched1k-<name>.montecarlo`: four machine shapes,
    labels, a tainted pool, pods placed by class) through the dense kernel
    set against the lax.scan engine, where no float32 compare ranks a node:
    the exact key (pools), kube-scheduler's integer scores over preferred
    terms and a PreferNoSchedule pool (kubescore). `counters`: the cell's
    pair of label counters, the second a part of the first."""
    from kubernetriks_tpu.batched.state import compare_states

    overrides, cycle = shape
    t0 = time.perf_counter()
    leg = leg_inputs(f"sched1k-{name}.montecarlo", rehearsed, **overrides)
    sim = leg.build(leg.width, **leg.forced())
    ref = leg.build(leg.width, use_pallas=False)
    for s in (sim, ref):
        s.step_until_time(run["warm_until"])
        s.step_until_time(run["warm_until"] + run["chunk"])
    formulation = sim.kernel_formulation()
    assert formulation["cycle"] == cycle and formulation["ranking"] == ranking, formulation
    decisions = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())
    sim.metrics_summary()
    report = sim.telemetry_report()["counters"]
    whole, part = counters
    assert 0 <= report[part] <= report[whole] and report[whole] > 0, report
    mismatches = compare_states(ref.state, sim.state)
    assert not mismatches, mismatches
    return emit(
        name, t0, clusters=leg.width, nodes=sim.n_nodes, pods=sim.n_pods,
        formulation=formulation, decisions=decisions,
        **{whole: report[whole], part: report[part]},
        reference="lax.scan", mismatches=0,
    )


def composed_leg(cycle, rehearsed, devices, mesh) -> dict:
    from benchmark import program
    from kubernetriks_tpu.batched.state import compare_states

    t0 = time.perf_counter()
    leg = leg_inputs("autoscaled.stream", rehearsed)
    n_clusters = leg.width * devices
    sim = leg.build(n_clusters, mesh=mesh, **leg.forced())
    # Reclaim compacts CA slots and adds state leaves, so its on/off pair is
    # comparable by trajectory only (tests/test_reclaim.py); the reference
    # keeps the value under test and turns everything else off.
    ref = leg.build(n_clusters, **program.plain_formulation_kwargs(sim.reclaim))
    for t in np.linspace(0.0, float(leg.cell.traffic["job_end_s"]), 5)[1:]:
        sim.step_until_time(float(t))
        ref.step_until_time(float(t))
    formulation = sim.kernel_formulation()
    stats = dict(sim.dispatch_stats)
    counters = sim.metrics_summary()["counters"]
    assert formulation["cycle"] == cycle, formulation
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
        "composed", t0, clusters=n_clusters, nodes=sim.n_nodes,
        pod_window=sim.pod_window, formulation=formulation,
        lane_major=sim.lane_major, reclaim=sim.reclaim,
        superspans=stats["superspans"],
        feeder_slabs=stats["feeder_slabs_produced"], pod_base=sim._pod_base,
        decisions=counters["scheduling_decisions"],
        scaled_up_pods=counters["total_scaled_up_pods"],
        scaled_up_nodes=counters["total_scaled_up_nodes"],
        scaled_down_nodes=counters["total_scaled_down_nodes"],
        reference="scan+ladder, statics off", mismatches=0,
    )


def served_leg(shape, rehearsed) -> dict:
    from benchmark import traffic_gen
    from kubernetriks_tpu.batched.fleet import Scenario, ScenarioFleet
    from kubernetriks_tpu.recompile import RecompileSentinel

    t0 = time.perf_counter()
    leg = leg_inputs("autoscaled.whatif", rehearsed)
    cell, n_lanes = leg.cell, leg.width
    queries = cell.traffic["queries"]
    catalogue = [
        Scenario(**overrides)
        for overrides in traffic_gen.scenario_catalogue(int(queries["catalogue_size"]))
    ]
    # As many queries of the mix's open loop as the leg asks for; the smoke
    # offers them all at once, so their due times are dropped.
    stream = traffic_gen.query_stream(
        cell.traffic, SEED, shape["n_queries"] / float(queries["rate_per_second"])
    )
    assert len(stream) == shape["n_queries"], len(stream)
    scenarios = [catalogue[index] for _, index, _ in stream]
    horizons = [horizon for _, _, horizon in stream]
    sentinel = RecompileSentinel("raise").install()
    fleet = ScenarioFleet(
        leg.config, leg.cluster_events, leg.workload, n_lanes=n_lanes,
        horizon=float(cell.traffic["base_workload_s"]), lane_async=True,
        span_windows=int(cell.traffic["span_windows"]),
        **{**leg.engine_kwargs(), **leg.forced(lane_async=True)},
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
        "served", t0, lanes=n_lanes, nodes=fleet.engine.n_nodes,
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
        "--only", choices=("pure", "composed", "served", "cli", "faults", "kubescore", "pools"),
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

    shapes = CHIP_LEGS if on_chip else PLUMBING_LEGS
    rehearsed = not on_chip
    mesh = None
    if args.devices > 1:
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devices[: args.devices]), ("clusters",))

    def wanted(leg: str) -> bool:
        return args.only in (None, leg)

    legs = []
    for overrides, cycle in (
        shapes["pure"] if mesh is None else shapes["pure"][:1]
    ) if wanted("pure") else ():
        legs.append(
            pure_leg(
                overrides, cycle, shapes["pure_run"], rehearsed, args.devices, mesh,
            )
        )
    if wanted("composed"):
        legs.append(
            composed_leg(shapes["composed"], rehearsed, args.devices, mesh)
        )
    if mesh is None:
        if wanted("served"):
            legs.append(served_leg(shapes["served"], rehearsed))
        if wanted("cli"):
            legs.append(cli_leg(shapes["cli_clusters"]))
        if wanted("faults"):
            legs.append(faults_leg(shapes["faults"], shapes["pure_run"], rehearsed))
        for name, ranking, counters in (
            ("kubescore", "integer", ("soft_attempts", "soft_honoured")),
            ("pools", "exact", ("affinity_attempts", "affinity_attempts_refused")),
        ):
            if wanted(name):
                legs.append(ranked_leg(name, ranking, counters, shapes[name], shapes["pure_run"], rehearsed))

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
