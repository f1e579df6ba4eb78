"""Pod resource-usage models.

Mirrors the reference's resource_usage package (reference:
src/core/resource_usage/{interface,constant,pod_group,helpers}.rs): a model
maps simulation time (+ optional live pod count) to a utilization fraction.
Configs carry a nested YAML string so arbitrary models stay config-driven.
"""

from __future__ import annotations

from typing import List, Optional

import yaml

from benchmark.oracle.core.types import ResourceUsageModelConfig


class ResourceUsageModel:
    """reference: src/core/resource_usage/interface.rs:8-10."""

    def current_usage(self, time: float, pod_count: Optional[int] = None) -> float:
        raise NotImplementedError


class ConstantResourceUsageModel(ResourceUsageModel):
    """Always returns the configured usage
    (reference: src/core/resource_usage/constant.rs:7-38)."""

    def __init__(self, usage: float) -> None:
        self.usage = usage

    @staticmethod
    def from_str(config: str) -> "ConstantResourceUsageModel":
        parsed = yaml.safe_load(config)
        return ConstantResourceUsageModel(usage=float(parsed["usage"]))

    def current_usage(self, time: float, pod_count: Optional[int] = None) -> float:
        return self.usage


class UsageUnit:
    def __init__(self, duration: float, total_load: float) -> None:
        self.duration = duration
        self.total_load = total_load


class PodGroupResourceUsageModel(ResourceUsageModel):
    """Piecewise-constant cyclic load curve anchored at pod-group creation time
    (reference: src/core/resource_usage/pod_group.rs:16-101).

    Utilization = min(1, total_load / pod_count): the group's total load is
    spread equally over the group's live pods. Poll times must be monotonically
    non-decreasing (the cursor only steps forward); going backwards raises.
    """

    def __init__(
        self, time_from_pod_group_creation: float, usage_sequence: List[UsageUnit]
    ) -> None:
        assert usage_sequence, "usage sequence cannot be empty"
        self.last_unit_start_time = time_from_pod_group_creation
        self.last_poll_time = time_from_pod_group_creation
        self.usage_sequence = usage_sequence
        self.current_idx_in_sequence = 0

    @staticmethod
    def from_str(config: str, time_from_pod_group_creation: float) -> "PodGroupResourceUsageModel":
        parsed = yaml.safe_load(config)
        units = [UsageUnit(float(u["duration"]), float(u["total_load"])) for u in parsed]
        return PodGroupResourceUsageModel(time_from_pod_group_creation, units)

    def _step_usage_until_current_time(self, time: float) -> None:
        current = self.usage_sequence[self.current_idx_in_sequence]
        while self.last_unit_start_time + current.duration <= time:
            self.last_unit_start_time += current.duration
            self.current_idx_in_sequence = (self.current_idx_in_sequence + 1) % len(
                self.usage_sequence
            )
            current = self.usage_sequence[self.current_idx_in_sequence]

    def _current_load(self, time: float) -> float:
        self._step_usage_until_current_time(time)
        return self.usage_sequence[self.current_idx_in_sequence].total_load

    def current_usage(self, time: float, pod_count: Optional[int] = None) -> float:
        if time < self.last_poll_time:
            raise RuntimeError(
                f"Trying to get current usage of time which is behind last poll "
                f"time: {time} vs {self.last_poll_time}"
            )
        self.last_poll_time = time
        return min(1.0, self._current_load(time) / pod_count)


def default_resource_usage_config(usage: float) -> ResourceUsageModelConfig:
    """Default model for pods without one: constant usage at their full request
    (reference: src/core/resource_usage/helpers.rs:8-13)."""
    return ResourceUsageModelConfig(model_name="constant", config=f"usage: {usage}")


def resource_usage_model_from_config(
    config: ResourceUsageModelConfig, pod_group_creation_time: Optional[str] = None
) -> ResourceUsageModel:
    """reference: src/core/resource_usage/helpers.rs:15-27."""
    if config.model_name == "constant":
        return ConstantResourceUsageModel.from_str(config.config)
    if config.model_name == "pod_group":
        return PodGroupResourceUsageModel.from_str(
            config.config, float(pod_group_creation_time)
        )
    raise ValueError(f"Unsupported resource usage model: {config.model_name!r}")
