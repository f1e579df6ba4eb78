"""Trace interface: any input format converts to timestamped simulator events
(reference: src/trace/interface.rs)."""

from __future__ import annotations

from typing import Any, List, Tuple

# (timestamp, event) pairs, sorted by timestamp ascending.
TraceEvents = List[Tuple[float, Any]]


class Trace:
    def convert_to_simulator_events(self) -> TraceEvents:
        """Move-out semantics in the reference; callable once per trace."""
        raise NotImplementedError

    def event_count(self) -> int:
        raise NotImplementedError


class EmptyTrace(Trace):
    def convert_to_simulator_events(self) -> TraceEvents:
        return []

    def event_count(self) -> int:
        return 0
