"""Shared test fixtures and cross-component consistency asserts
(reference: src/test_util/helpers.rs)."""

from __future__ import annotations

from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.core.types import Node
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation

DEFAULT_TEST_CONFIG_YAML = """
sim_name: "test_kubernetriks"
seed: 123
scheduling_cycle_interval: 10.0
as_to_ps_network_delay: 0.050
ps_to_sched_network_delay: 0.010
sched_to_as_network_delay: 0.020
as_to_node_network_delay: 0.150
as_to_ca_network_delay: 0.30
as_to_hpa_network_delay: 0.40
"""


def default_test_simulation_config(with_suffix: str = "") -> SimulationConfig:
    """reference: src/test_util/helpers.rs:60-80."""
    return SimulationConfig.from_yaml(DEFAULT_TEST_CONFIG_YAML + with_suffix)


def check_expected_node_is_equal_to_nodes_in_components(
    expected_node: Node, kube_sim: KubernetriksSimulation
) -> None:
    """State must agree in api server, storage and scheduler at once
    (reference: src/test_util/helpers.rs:7-33)."""
    name = expected_node.metadata.name
    assert expected_node == kube_sim.api_server.get_node_component(name).get_node()
    assert expected_node == kube_sim.persistent_storage.get_node(name)
    assert expected_node == kube_sim.scheduler.get_node(name)


def check_count_of_nodes_in_components_equals_to(
    count: int, kube_sim: KubernetriksSimulation
) -> None:
    assert count == kube_sim.api_server.node_count()
    assert count == kube_sim.persistent_storage.node_count()
    assert count == kube_sim.scheduler.node_count()


def check_expected_node_appeared_in_components(
    node_name: str, kube_sim: KubernetriksSimulation
) -> None:
    assert kube_sim.api_server.get_node_component(node_name) is not None
    assert kube_sim.persistent_storage.get_node(node_name) is not None
    kube_sim.scheduler.get_node(node_name)


# --- Alibaba CSV real-format quirk rendering (shared by the Python-oracle
# and native-feeder quirk suites, so both always test the SAME quirked
# input) --------------------------------------------------------------------

ALIBABA_INSTANCE_HEADER = (
    "start_ts,end_ts,job_id,task_id,machine_id,status,seq_no,total_seq_no"
)
ALIBABA_TASK_HEADER = (
    "create_ts,end_ts,job_id,task_id,inst_num,status,plan_cpu,plan_mem"
)
ALIBABA_MACHINE_HEADER = "ts,machine_id,event_type,event_detail,cap_cpu,cap_mem"


def quirkify_csv(text, crlf=False, quote=False, header=None):
    """Re-render a clean CSV body with real-format quirks: quote every other
    field (RFC4180 — including empty fields, which stay empty), prepend an
    optional header row, and optionally join with CRLF endings."""
    lines = text.strip("\n").split("\n")
    if quote:
        lines = [
            ",".join(
                f'"{f}"' if (li + fi) % 2 == 0 else f
                for fi, f in enumerate(line.split(","))
            )
            for li, line in enumerate(lines)
        ]
    if header is not None:
        lines.insert(0, header)
    eol = "\r\n" if crlf else "\n"
    return eol.join(lines) + eol


# --- sharded against unsharded ------------------------------------------------


def leaves_differing(a, b) -> list:
    """Key paths of the leaves of two state pytrees that are not EXACTLY
    equal, float accumulators included (a sharded run is the same simulation
    as the unsharded one: each device runs the one-chip program on its shard,
    so not even a reduction's order may differ). [] = identical."""
    import jax
    import numpy as np

    flat_a, tree_a = jax.tree_util.tree_flatten_with_path(a)
    flat_b, tree_b = jax.tree_util.tree_flatten_with_path(b)
    if tree_a != tree_b:
        return [f"<tree structure: {tree_a} != {tree_b}>"]
    return [
        jax.tree_util.keystr(path)
        for (path, x), (_, y) in zip(flat_a, flat_b)
        if not np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
    ]
