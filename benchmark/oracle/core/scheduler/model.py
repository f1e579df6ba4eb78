"""Models simulating the latency of the scheduling algorithm itself
(reference: src/core/scheduler/model.rs)."""

from __future__ import annotations

from typing import Dict

from benchmark.oracle.core.types import Node, Pod


class PodSchedulingTimeModel:
    def simulate_time(self, pod: Pod, nodes: Dict[str, Node]) -> float:
        raise NotImplementedError


class ConstantTimePerNodeModel(PodSchedulingTimeModel):
    """1 microsecond per node in the cluster
    (reference: src/core/scheduler/model.rs:11-27)."""

    def __init__(self, constant_time_per_node: float = 1e-6) -> None:
        self.constant_time_per_node = constant_time_per_node

    def simulate_time(self, pod: Pod, nodes: Dict[str, Node]) -> float:
        return self.constant_time_per_node * len(nodes)
