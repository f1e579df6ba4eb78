"""Heterogeneous batches for the sharded-against-unsharded tests.

A batch of copies of one trace cannot tell a shard's own reduction from the
build's: every shard then holds the same minimum. Here every cluster has a
workload of its own (arrival rate and seed differ), so shards differ in their
first blocking pod slot, their next due window and their autoscaler
activity, and a collective in the wrong place, or a missing one, moves a leaf.
"""

import jax
import numpy as np
from jax.sharding import Mesh

from kubernetriks_tpu.batched.engine import BatchedSimulation
from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generator import PoissonWorkloadTrace, UniformClusterTrace
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

from tests.test_hpa_ca_combined import (
    CLUSTER_TRACE as HPA_CA_CLUSTER,
    CONFIG_SUFFIX as HPA_CA_SUFFIX,
    WORKLOAD_TRACE as HPA_CA_WORKLOAD,
)

POD_FAULTS = """
fault_injection:
  enabled: true
  pod:
    fail_prob: 0.15
    backoff_base: 10.0
    backoff_cap: 300.0
    restart_limit: 3
"""


def mesh_of(n_devices: int) -> Mesh:
    devices = jax.devices()
    assert len(devices) >= n_devices, devices
    return Mesh(np.array(devices[:n_devices]), ("clusters",))


def bare_batch(n_clusters: int = 16, config_suffix: str = "", horizon: float = 600.0, **kwargs):
    """Bare scheduler: 6 nodes a cluster, Poisson pods at 0.02 to 0.3 a
    second by cluster (sparse clusters next to dense ones)."""
    config = SimulationConfig.from_yaml(
        "sim_name: sharded_bare\nseed: 1\nscheduling_cycle_interval: 10.0\n" + config_suffix
    )
    cluster = UniformClusterTrace(6, cpu=16000, ram=32 * 1024**3).convert_to_simulator_events()
    compiled = [
        compile_cluster_trace(
            cluster,
            PoissonWorkloadTrace(
                rate_per_second=0.02 + 0.28 * ((i * 7) % n_clusters) / n_clusters,
                horizon=horizon,
                seed=100 + i,
                cpu=3000,
                ram=6 * 1024**3,
                duration_range=(15.0, 120.0),
            ).convert_to_simulator_events(),
            config,
        )
        for i in range(n_clusters)
    ]
    kwargs.setdefault("max_pods_per_cycle", 8)
    return BatchedSimulation(config, compiled, **kwargs)


def autoscaled_batch(n_clusters: int = 8, config_suffix: str = "", **kwargs):
    """The small autoscaled build (tests/test_hpa_ca_combined: one base node,
    one CA node group, HPA on one pod group) under plain Poisson pods whose
    rate and seed differ by cluster, so that CA activity and the pod
    window's slides do."""
    config = default_test_simulation_config(HPA_CA_SUFFIX + config_suffix)
    cluster = GenericClusterTrace.from_yaml(HPA_CA_CLUSTER).convert_to_simulator_events()
    group = GenericWorkloadTrace.from_yaml(HPA_CA_WORKLOAD).convert_to_simulator_events()
    compiled = [
        compile_cluster_trace(
            list(cluster),
            sorted(
                PoissonWorkloadTrace(
                    rate_per_second=0.1 + 0.05 * (i % 4),
                    horizon=900.0,
                    seed=13 + i,
                    cpu=1200,
                    ram=2 * 1024**3,
                    duration_range=(15.0, 70.0),
                    name_prefix="plain",
                ).convert_to_simulator_events()
                + list(group),
                key=lambda e: e[0],
            ),
            config,
        )
        for i in range(n_clusters)
    ]
    kwargs.setdefault("max_pods_per_cycle", 16)
    kwargs.setdefault("ca_slot_multiplier", 4)
    return BatchedSimulation(config, compiled, **kwargs)
