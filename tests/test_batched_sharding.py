"""Batched path sharded over a device mesh: results must be identical to the
unsharded run, with the cluster axis split across all 8 virtual CPU devices."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubernetriks_tpu.batched.engine import BatchedSimulation, build_batched_from_traces
from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
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

    for field in ["pods_succeeded", "terminated_pods", "scheduling_decisions"]:
        np.testing.assert_array_equal(
            np.asarray(getattr(unsharded.state.metrics, field)),
            np.asarray(getattr(sharded.state.metrics, field)),
            err_msg=field,
        )
    np.testing.assert_array_equal(
        np.asarray(unsharded.state.pods.phase), np.asarray(sharded.state.pods.phase)
    )
    np.testing.assert_allclose(
        np.asarray(unsharded.state.pods.start_time),
        np.asarray(sharded.state.pods.start_time),
        rtol=1e-6,
    )
    assert sharded.metrics_summary()["counters"]["pods_succeeded"] == 16 * len(pod_names)


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
