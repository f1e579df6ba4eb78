"""Generic YAML trace format (reference: src/trace/generic.rs).

Workload events: CreatePod / RemovePod / CreatePodGroup; cluster events:
CreateNode / RemoveNode. The YAML uses serde-style tags
(``event_type: !CreatePod {pod: ...}``) which the tagged loader flattens to
{"__tag__": "CreatePod", ...}.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.oracle.autoscalers.interface import PodGroup
from benchmark.oracle.config import load_yaml_with_tags
from benchmark.oracle.core.events import (
    CreateNodeRequest,
    CreatePodGroupRequest,
    CreatePodRequest,
    RemoveNodeRequest,
    RemovePodRequest,
)
from benchmark.oracle.core.types import Node, Pod
from benchmark.oracle.trace.interface import Trace, TraceEvents


def _tag_of(event_type: Any) -> str:
    if isinstance(event_type, str):
        return event_type
    return event_type.get("__tag__", "")


class GenericWorkloadTrace(Trace):
    def __init__(self, events: List[Dict[str, Any]]) -> None:
        self.events = events

    @staticmethod
    def from_yaml(text: str) -> "GenericWorkloadTrace":
        doc = load_yaml_with_tags(text) or {}
        return GenericWorkloadTrace(events=doc.get("events") or [])

    @staticmethod
    def from_file(path: str) -> "GenericWorkloadTrace":
        with open(path) as f:
            return GenericWorkloadTrace.from_yaml(f.read())

    def convert_to_simulator_events(self) -> TraceEvents:
        """reference: src/trace/generic.rs:57-86."""
        converted: TraceEvents = []
        events, self.events = self.events, []
        for event in events:
            ts = float(event["timestamp"])
            event_type = event["event_type"]
            tag = _tag_of(event_type)
            if tag == "CreatePod":
                converted.append(
                    (ts, CreatePodRequest(pod=Pod.from_dict(event_type["pod"])))
                )
            elif tag == "RemovePod":
                converted.append(
                    (ts, RemovePodRequest(pod_name=event_type["pod_name"]))
                )
            elif tag == "CreatePodGroup":
                converted.append(
                    (
                        ts,
                        CreatePodGroupRequest(
                            pod_group=PodGroup.from_dict(event_type["pod_group"])
                        ),
                    )
                )
            else:
                raise ValueError(f"unknown workload event type {tag!r}")
        converted.sort(key=lambda pair: pair[0])
        return converted

    def event_count(self) -> int:
        return len(self.events)


class GenericClusterTrace(Trace):
    def __init__(self, events: List[Dict[str, Any]]) -> None:
        self.events = events

    @staticmethod
    def from_yaml(text: str) -> "GenericClusterTrace":
        doc = load_yaml_with_tags(text) or {}
        return GenericClusterTrace(events=doc.get("events") or [])

    @staticmethod
    def from_file(path: str) -> "GenericClusterTrace":
        with open(path) as f:
            return GenericClusterTrace.from_yaml(f.read())

    def convert_to_simulator_events(self) -> TraceEvents:
        """Sets allocatable = capacity on node creation
        (reference: src/trace/generic.rs:88-112)."""
        converted: TraceEvents = []
        events, self.events = self.events, []
        for event in events:
            ts = float(event["timestamp"])
            event_type = event["event_type"]
            tag = _tag_of(event_type)
            if tag == "CreateNode":
                node = Node.from_dict(event_type["node"])
                node.status.allocatable = node.status.capacity.copy()
                converted.append((ts, CreateNodeRequest(node=node)))
            elif tag == "RemoveNode":
                converted.append(
                    (ts, RemoveNodeRequest(node_name=event_type["node_name"]))
                )
            else:
                raise ValueError(f"unknown cluster event type {tag!r}")
        converted.sort(key=lambda pair: pair[0])
        return converted

    def event_count(self) -> int:
        return len(self.events)
