"""Fast-forward window skipping (step.run_windows_skip) is EXACT: the final
state pytree of a fast-forwarded run must be bit-identical to stepping every
window index — across sparse traces (where whole spans skip), autoscalers
(tick bookkeeping catch-up), conditional-move wakes, flush cadences, node
failures, and the sliding pod window."""

import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.state import compare_states
from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generator import PoissonWorkloadTrace, UniformClusterTrace
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace


def _sparse_traces(rate=0.02, horizon=3000.0, seed=5):
    """~1 pod per 5 windows: plenty of provably-empty spans to skip."""
    cluster = UniformClusterTrace(6, cpu=16000, ram=32 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=rate,
        horizon=horizon,
        seed=seed,
        cpu=3000,
        ram=6 * 1024**3,
        duration_range=(15.0, 120.0),
    )
    return (
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
    )


def _run_both(config, cluster, workload, until, n_clusters=3, **kwargs):
    plain = build_batched_from_traces(
        config, list(cluster), list(workload), n_clusters=n_clusters,
        max_pods_per_cycle=8, fast_forward=False, **kwargs,
    )
    fast = build_batched_from_traces(
        config, list(cluster), list(workload), n_clusters=n_clusters,
        max_pods_per_cycle=8, fast_forward=True, **kwargs,
    )
    assert fast.fast_forward and not plain.fast_forward
    plain.step_until_time(until)
    fast.step_until_time(until)
    assert fast.next_window_idx == plain.next_window_idx
    bad = compare_states(plain.state, fast.state)
    assert not bad, bad
    return plain, fast


def test_sparse_trace_exact():
    config = SimulationConfig.from_yaml(
        "sim_name: ff\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster, workload = _sparse_traces()
    plain, fast = _run_both(config, cluster, workload, 4000.0)
    assert plain.metrics_summary()["counters"]["pods_succeeded"] > 0


def test_sparse_trace_with_autoscalers_exact():
    """HPA + CA enabled on a sparse mixed trace: the tick catch-up must
    reproduce hpa_next/ca_next and the CA/HPA trajectories exactly."""
    from tests.test_hpa_ca_combined import (
        CLUSTER_TRACE,
        CONFIG_SUFFIX,
        WORKLOAD_TRACE,
    )

    config = default_test_simulation_config(CONFIG_SUFFIX)
    plain_events = PoissonWorkloadTrace(
        rate_per_second=0.03,
        horizon=1500.0,
        seed=11,
        cpu=1000,
        ram=2 * 1024**3,
        duration_range=(20.0, 60.0),
    ).convert_to_simulator_events()
    group = GenericWorkloadTrace.from_yaml(WORKLOAD_TRACE).convert_to_simulator_events()
    workload = sorted(plain_events + group, key=lambda e: e[0])
    cluster = GenericClusterTrace.from_yaml(CLUSTER_TRACE).convert_to_simulator_events()
    plain, fast = _run_both(config, cluster, workload, 2000.0)
    counters = fast.metrics_summary()["counters"]
    assert counters["total_scaled_up_pods"] > 0
    assert counters["total_scaled_up_nodes"] > 0


def test_parked_pods_and_flush_cadence_exact():
    """Pods that can never fit park forever; the 30 s flush and 300 s stale
    windows must fire at identical indices in both modes."""
    config = default_test_simulation_config()
    cluster = GenericClusterTrace.from_yaml(
        """
events:
- timestamp: 2.0
  event_type:
    !CreateNode
      node:
        metadata: {name: tiny}
        status: {capacity: {cpu: 2000, ram: 4294967296}}
"""
    ).convert_to_simulator_events()
    workload = GenericWorkloadTrace.from_yaml(
        """
events:
- timestamp: 13.0
  event_type:
    !CreatePod
      pod:
        metadata: {name: too_big}
        spec:
          resources:
            requests: {cpu: 64000, ram: 4294967296}
            limits: {cpu: 64000, ram: 4294967296}
          running_duration: 50.0
- timestamp: 700.0
  event_type:
    !CreatePod
      pod:
        metadata: {name: fits}
        spec:
          resources:
            requests: {cpu: 1000, ram: 1073741824}
            limits: {cpu: 1000, ram: 1073741824}
          running_duration: 40.0
"""
    ).convert_to_simulator_events()
    _run_both(config, cluster, workload, 1500.0)


def test_conditional_move_exact():
    config = default_test_simulation_config(
        "enable_unscheduled_pods_conditional_move: true\n"
    )
    cluster, workload = _sparse_traces(rate=0.05, horizon=1500.0, seed=23)
    _run_both(config, cluster, workload, 2500.0)


def test_sliding_pod_window_fast_forward_exact():
    config = SimulationConfig.from_yaml(
        "sim_name: ffw\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster, workload = _sparse_traces(rate=0.05, horizon=4000.0, seed=31)
    _run_both(config, cluster, workload, 5000.0, pod_window=24)


def test_dense_trace_exact():
    """Dense spans (every window interesting): the skip must degenerate to
    plain stepping with an identical result."""
    config = SimulationConfig.from_yaml(
        "sim_name: ffd\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster, workload = _sparse_traces(rate=1.5, horizon=400.0, seed=41)
    _run_both(config, cluster, workload, 700.0)


def test_fast_forward_under_mesh_exact():
    """Fast-forward on an 8-device mesh: the skip's global reductions and
    bookkeeping catch-up must behave identically sharded."""
    import jax
    from jax.sharding import Mesh

    config = SimulationConfig.from_yaml(
        "sim_name: ffm\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster, workload = _sparse_traces(rate=0.04, horizon=2000.0, seed=47)
    mesh = Mesh(np.array(jax.devices()), ("clusters",))
    plain = build_batched_from_traces(
        config, list(cluster), list(workload), n_clusters=8,
        max_pods_per_cycle=8, fast_forward=False,
    )
    fast = build_batched_from_traces(
        config, list(cluster), list(workload), n_clusters=8,
        max_pods_per_cycle=8, fast_forward=True, mesh=mesh,
    )
    plain.step_until_time(3000.0)
    fast.step_until_time(3000.0)
    assert len(fast.state.pods.phase.devices()) == 8
    bad = compare_states(plain.state, fast.state)
    assert not bad, bad


def test_gauge_collection_forces_per_window_stepping():
    """collect_gauges needs one sample per window, so the fast-forward
    dispatch must fall back to the scan — the gauge series stays dense."""
    config = SimulationConfig.from_yaml(
        "sim_name: ffg\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster, workload = _sparse_traces(rate=0.03, horizon=800.0, seed=53)
    sim = build_batched_from_traces(
        config, list(cluster), list(workload), n_clusters=2,
        max_pods_per_cycle=8, fast_forward=True,
    )
    assert sim.fast_forward
    sim.collect_gauges = True
    sim.step_until_time(1000.0)
    times, samples = sim.gauge_series()
    # One gauge row per window (0..100 inclusive), no gaps despite
    # fast_forward being on.
    assert len(times) == 101
    np.testing.assert_allclose(np.diff(times), 10.0)
    assert samples.shape[0] == 101


def test_fast_forward_sharded_matches_unsharded_every_leaf():
    """Fast-forward inside the window program's one shard_map, over clusters
    that differ (sparse next to dense): each shard finds its own next due
    window and one pmin takes the earliest, so every shard runs the same
    windows. Every leaf of the final state equals the unsharded
    fast-forwarded run's and the dense-stepped one's."""
    from kubernetriks_tpu.test_util import leaves_differing
    from tests.sharded_builds import bare_batch, mesh_of

    def run(**kwargs):
        sim = bare_batch(16, **kwargs)
        sim.step_until_time(1200.0)
        return sim

    stepped = run(fast_forward=False)
    unsharded = run(fast_forward=True)
    sharded = run(fast_forward=True, mesh=mesh_of(8))
    assert sharded.fast_forward and sharded.next_window_idx == stepped.next_window_idx
    assert sharded.metrics_summary()["counters"]["pods_succeeded"] > 0
    assert leaves_differing(unsharded.state, sharded.state) == []
    assert leaves_differing(stepped.state, sharded.state) == []
