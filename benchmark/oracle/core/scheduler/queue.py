"""Scheduler queue types (reference: src/core/scheduler/queue.rs).

The active queue is a min-heap by (timestamp, seq) — the explicit insertion-seq
tie-break replaces Rust BinaryHeap's unspecified equal-key order with a
deterministic one. The unschedulable map iterates in (insert_timestamp,
pod_name) order, matching the reference's BTreeMap key ordering.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

# Max time (secs) a pod may stay in unschedulable_pods before being flushed to
# the active queue regardless of resource events
# (reference: src/core/scheduler/queue.rs:8).
DEFAULT_POD_MAX_IN_UNSCHEDULABLE_PODS_DURATION = 5.0 * 60.0
# Interval (secs) of the leftover-flushing cycle
# (reference: src/core/scheduler/queue.rs:11).
POD_FLUSH_INTERVAL = 30.0


@dataclass
class QueuedPodInfo:
    """reference: src/core/scheduler/queue.rs:13-27."""

    timestamp: float
    attempts: int
    initial_attempt_timestamp: float
    pod_name: str


class ActiveQueue:
    """Min-heap of QueuedPodInfo by (timestamp, insertion seq)."""

    def __init__(self) -> None:
        self._heap: List[Tuple[float, int, QueuedPodInfo]] = []
        self._seq = 0

    def push(self, info: QueuedPodInfo) -> None:
        heapq.heappush(self._heap, (info.timestamp, self._seq, info))
        self._seq += 1

    def pop(self) -> Optional[QueuedPodInfo]:
        if not self._heap:
            return None
        return heapq.heappop(self._heap)[2]

    def __len__(self) -> int:
        return len(self._heap)


@dataclass(frozen=True)
class UnschedulablePodKey:
    """Ordered by (insert_timestamp, pod_name)
    (reference: src/core/scheduler/queue.rs:50-75)."""

    pod_name: str
    insert_timestamp: float

    def sort_key(self) -> Tuple[float, str]:
        return (self.insert_timestamp, self.pod_name)


class UnschedulableQueue:
    """(insert_timestamp, pod_name)-ordered map of QueuedPodInfo."""

    def __init__(self) -> None:
        self._map: Dict[UnschedulablePodKey, QueuedPodInfo] = {}

    def insert(self, key: UnschedulablePodKey, info: QueuedPodInfo) -> None:
        self._map[key] = info

    def remove(self, key: UnschedulablePodKey) -> QueuedPodInfo:
        return self._map.pop(key)

    def sorted_items(self) -> Iterator[Tuple[UnschedulablePodKey, QueuedPodInfo]]:
        for key in sorted(self._map, key=UnschedulablePodKey.sort_key):
            yield key, self._map[key]

    def sorted_keys(self) -> List[UnschedulablePodKey]:
        return sorted(self._map, key=UnschedulablePodKey.sort_key)

    def remove_pod(self, pod_name: str) -> None:
        """Drop every entry for a pod (used when the pod is removed outright)."""
        stale = [key for key in self._map if key.pod_name == pod_name]
        for key in stale:
            del self._map[key]

    def __len__(self) -> int:
        return len(self._map)

    def __contains__(self, key: UnschedulablePodKey) -> bool:
        return key in self._map
