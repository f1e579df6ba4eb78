"""Batched path sharded over a device mesh: results must be identical to the
unsharded run, with the cluster axis split across all 8 virtual CPU devices.

A mesh build has ONE sharding boundary (batched/sharding.py): each window
program sits in a single shard_map over the cluster axis, every device runs
the one-chip program on its shard, and GSPMD never partitions the window
body. The guard below reads the compiled HLO for that; the other tests hold
the sharded run to the unsharded one leaf for leaf."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubernetriks_tpu.batched import step
from kubernetriks_tpu.batched.engine import BatchedSimulation, build_batched_from_traces
from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
from kubernetriks_tpu.test_util import default_test_simulation_config, leaves_differing
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from tests.sharded_builds import POD_FAULTS, autoscaled_batch, bare_batch, mesh_of
from tests.test_batched_equivalence import CLUSTER_YAML, make_workload


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual devices, got {len(devices)}"
    return Mesh(np.array(devices), ("clusters",))


def test_sharded_run_matches_unsharded(mesh):
    config = default_test_simulation_config()
    workload_yaml, pod_names = make_workload()
    cluster_events = GenericClusterTrace.from_yaml(CLUSTER_YAML).convert_to_simulator_events()
    workload_events = GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events()

    compiled = compile_cluster_trace(cluster_events, workload_events, config)
    unsharded = BatchedSimulation(config, [compiled] * 16)
    sharded = BatchedSimulation(config, [compiled] * 16, mesh=mesh)

    # State actually lives distributed across the mesh.
    sharding = sharded.state.pods.phase.sharding
    assert isinstance(sharding, NamedSharding)
    assert sharding.spec[0] == "clusters"
    assert len(sharded.state.pods.phase.devices()) == 8

    unsharded.step_until_time(2000.0)
    sharded.step_until_time(2000.0)

    assert leaves_differing(unsharded.state, sharded.state) == []
    assert sharded.metrics_summary()["counters"]["pods_succeeded"] == 16 * len(pod_names)


def test_kernel_formulation_names_the_sharding(mesh):
    """kernel_formulation() says how the build is sharded: one shard_map
    round each window program, with the axis and the shard count; None
    without a mesh (no program is wrapped)."""
    assert bare_batch(8).kernel_formulation()["sharding"] is None
    formulation = bare_batch(8, mesh=mesh_of(4)).kernel_formulation()
    assert formulation["sharding"] == "shard_map"
    assert formulation["shard_axis"] == "clusters"
    assert formulation["shards"] == 4


def _compiled_window_program(sim, program: str) -> str:
    """Optimised HLO of the window program the engine would dispatch."""
    if program == "run_windows":
        lowered = step.run_windows.lower(
            sim.state, sim.slab, jnp.arange(4, dtype=jnp.int32), sim.consts,
            collect_gauges=False, **sim._window_call_kwargs(),
        )
    else:
        from test_device_phases import lowered_superspan_program

        lowered = lowered_superspan_program(sim)
    return lowered.compile().as_text()


_COLLECTIVE = re.compile(
    r"= (?P<type>.*?) (?P<op>all-gather|all-to-all|collective-permute|all-reduce"
    r"|reduce-scatter|collective-broadcast)(?:-start)?\("
)


@pytest.mark.parametrize("n_devices", [4, 8])
@pytest.mark.parametrize("build", ["bare", "autoscaled"])
@pytest.mark.parametrize("program", ["run_windows", "run_superspan"])
def test_sharded_window_program_holds_no_gspmd_collective(program, build, n_devices):
    """The guard that keeps GSPMD out of the window body. Clusters exchange
    nothing, so the compiled sharded program may hold no all-gather,
    all-to-all or collective-permute at all, and every all-reduce is the
    pmin of a scalar (the superspan's pod_base, capacity read and shift) or
    at most a progress-sized vector. Partitioned by GSPMD the same programs
    held 39 all-gathers and 10 all-reduces over (C_global, ...) operands:
    the `x.at[arange(C)[:, None], idx]` gathers and scatters."""
    make = bare_batch if build == "bare" else autoscaled_batch
    sim = make(16, mesh=mesh_of(n_devices), pod_window=64, superspan=True)
    hlo = _compiled_window_program(sim, program)
    found = [m.groupdict() for m in _COLLECTIVE.finditer(hlo)]
    assert [c for c in found if c["op"] != "all-reduce"] == []
    for c in found:
        shapes = re.findall(r"\w+\[([\d,]*)\]", c["type"])
        assert shapes and all(
            int(np.prod([int(d) for d in dims.split(",") if d] or [1])) <= 4
            for dims in shapes
        ), c
    # not vacuous: the superspan really reduces over the mesh, the plain
    # window scan has nothing to
    assert bool(found) == (program == "run_superspan"), found


def test_kernels_and_lane_major_under_mesh_match_unsharded_every_leaf():
    """The combination that could not exist before: interpreted kernels
    (megakernel, event and free scatters) called directly on the shard with
    lane-major node state, two clusters a device, pod faults on (their draw
    keys on the cluster's index in the BUILD, not in the shard). Every leaf
    of the final state equals the unsharded run's."""

    def run(**kwargs):
        sim = bare_batch(
            16, POD_FAULTS, use_pallas=True, pallas_interpret=True,
            lane_major=True, fast_forward=False, **kwargs,
        )
        # the dense kernel set below 128 clusters a shard (interpret mode)
        sim.use_pallas_select = sim.use_megakernel = True
        sim.step_until_time(900.0)
        return sim

    unsharded, sharded = run(), run(mesh=mesh_of(8))
    assert sharded.lane_major and sharded.kernel_formulation()["cycle"] == "megakernel"
    counters = sharded.metrics_summary()["counters"]
    assert counters["pod_restarts"] > 0 and counters["pods_succeeded"] > 0
    assert leaves_differing(unsharded.state, sharded.state) == []


@pytest.mark.slow
def test_profiling_hooks(tmp_path, caplog):
    """A jax.profiler capture started round an ordinary call holds the
    recorder's spans as `ktpu:` host events (here the fenced per-chunk
    span of the throughput log); log_throughput emits the per-chunk
    decisions/s line (TPU analog of the scalar events/s log, reference:
    src/simulator.rs:363-368). Slow lane (tier-1 wall-clock budget):
    instrumentation plumbing, not a correctness gate — the flight
    recorder's tier-1 suite (test_telemetry) covers the capture round the
    path the engine actually runs in steady state."""
    import logging

    import jax

    from test_telemetry import host_annotations

    from kubernetriks_tpu.test_util import default_test_simulation_config

    config = default_test_simulation_config()
    workload_yaml, _ = make_workload()
    sim = build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(CLUSTER_YAML).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload_yaml).convert_to_simulator_events(),
        n_clusters=4,
    )
    sim.log_throughput = True
    with caplog.at_level(logging.INFO, logger="kubernetriks_tpu.batched.engine"):
        with jax.profiler.trace(str(tmp_path / "trace")):
            sim.step_until_time(100.0)
    assert any("decisions/s" in rec.message for rec in caplog.records)
    names = host_annotations(str(tmp_path / "trace"))
    assert {"ktpu:step_until_time", "ktpu:chunk_fenced", "ktpu:window_chunk"} <= names


def test_pod_axis_alignment_full_resident_only():
    """Full-resident builds pad the pod axis to a 128 multiple (Pallas
    wrapper pads become no-ops); padded slots are batch-padding slots that
    never leave PHASE_EMPTY, and the sliding path keeps exact widths."""
    import numpy as np

    from kubernetriks_tpu.batched.state import PHASE_EMPTY
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )
    from kubernetriks_tpu.batched.engine import build_batched_from_traces

    config = SimulationConfig.from_yaml(
        "sim_name: align\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(4, cpu=16000, ram=32 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=0.5, horizon=100.0, seed=2, cpu=2000,
        ram=4 * 1024**3, duration_range=(10.0, 30.0),
    )

    full = build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=2,
    )
    assert full.n_pods % 128 == 0
    assert full.n_real_pods <= full.n_pods
    full.step_until_time(200.0)
    phases = np.asarray(full.state.pods.phase)
    assert (phases[:, full.n_real_pods:] == PHASE_EMPTY).all(), (
        "alignment padding slots must never be touched"
    )

    windowed = build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=2,
        pod_window=16,
    )
    assert windowed.n_pods == 16, "sliding path keeps exact widths"
    windowed.step_until_time(200.0)
    assert (
        windowed.metrics_summary()["counters"]
        == full.metrics_summary()["counters"]
    )
