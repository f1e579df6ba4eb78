"""Scheduling-algorithm interface (reference: src/core/scheduler/interface.rs)."""

from __future__ import annotations

import enum
from typing import TYPE_CHECKING, Dict, Optional

from kubernetriks_tpu.core.types import Node, Pod

if TYPE_CHECKING:
    from kubernetriks_tpu.core.scheduler.plugins import SchedulerCache


class ScheduleError(enum.Enum):
    NO_NODES_IN_CLUSTER = "NoNodesInCluster"
    NO_SUFFICIENT_RESOURCES = "NoSufficientResources"
    REQUESTED_RESOURCES_ARE_ZEROS = "RequestedResourcesAreZeros"


class SchedulingFailure(Exception):
    """Raised by schedule_one when no node can be assigned."""

    def __init__(self, error: ScheduleError) -> None:
        super().__init__(error.value)
        self.error = error


class PodSchedulingAlgorithm:
    """Any scheduler must implement schedule_one(pod, nodes, cache) -> node
    name, raising SchedulingFailure on error (reference:
    src/core/scheduler/interface.rs:14-23). ``nodes`` is name-keyed; algorithms
    must iterate in sorted-name order for determinism parity. ``cache`` is the
    scheduler's cache (plugins.SchedulerCache: cached nodes and pods, and the
    pods it has assigned to each node), for decisions that depend on where
    other pods sit."""

    def schedule_one(
        self, pod: Pod, nodes: Dict[str, Node], cache: "Optional[SchedulerCache]" = None
    ) -> str:
        raise NotImplementedError
