"""Deterministic discrete-event simulation kernel.

Stands in for the external DSLab core the reference builds on (reference:
Cargo.toml:8 `dslab-core`; usage at src/simulator.rs:74-186): a global
time-ordered event queue with FIFO tie-break at equal timestamps, a component
registry (name -> id), per-component contexts that emit timestamped events,
event cancellation, and one seeded RNG owned by the simulation.

Determinism contract (mirroring the reference's tests/test_determinism.rs):
given the same seed, config and trace,
every run pops the same events in the same order and produces bit-identical
metrics. The heap orders by (time, event_id); event ids increase monotonically
in emission order, which reproduces DSLab's stable FIFO-per-timestamp ordering.
"""

from __future__ import annotations

import heapq
import random  # ktpu: prng-ok(scalar oracle kernel: the reference simulator's own seeded RNG — reference-port semantics, isolated from the batched path)
import string
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


@dataclass(order=True)
class Event:
    """A scheduled message: matches DSLab's Event shape {id, time, src, dst, data}
    (reference: tests/test_cast_box.rs:16-24)."""

    time: float
    id: int
    src: int = field(compare=False)
    dst: int = field(compare=False)
    data: Any = field(compare=False)


def _snake_case(name: str) -> str:
    out = []
    for i, ch in enumerate(name):
        if ch.isupper() and i > 0:
            out.append("_")
        out.append(ch.lower())
    return "".join(out)


class EventHandler:
    """Base class for simulation components.

    Dispatches incoming events to ``on_<snake_case_payload_type>`` methods —
    the Python equivalent of the reference's `cast!`/`cast_box!` match macros
    (reference: src/core/events.rs:247-268).
    """

    def on(self, event: Event) -> None:
        method = getattr(self, "on_" + _snake_case(type(event.data).__name__), None)
        if method is None:
            raise RuntimeError(
                f"{type(self).__name__}: unhandled event {type(event.data).__name__}"
            )
        method(event.data, event.time)


class SimulationContext:
    """Per-component handle for emitting events (DSLab SimulationContext
    equivalent; usage reference: src/core/node_component.rs:137-145)."""

    def __init__(self, sim: "Simulation", name: str, comp_id: int) -> None:
        self._sim = sim
        self.name = name
        self.id = comp_id

    def time(self) -> float:
        return self._sim.time()

    def emit(self, data: Any, dst: int, delay: float = 0.0) -> int:
        return self._sim._schedule(data, self.id, dst, delay)

    def emit_now(self, data: Any, dst: int) -> int:
        return self._sim._schedule(data, self.id, dst, 0.0)

    def emit_self(self, data: Any, delay: float = 0.0) -> int:
        return self._sim._schedule(data, self.id, self.id, delay)

    def emit_self_now(self, data: Any) -> int:
        return self._sim._schedule(data, self.id, self.id, 0.0)

    def cancel_event(self, event_id: int) -> None:
        self._sim.cancel_event(event_id)

    # Seeded RNG helpers, all drawing from the single simulation-owned RNG so
    # that call order fully determines the stream (DSLab equivalent:
    # ctx.gen_range / ctx.random_string, used by tests and the trace generator).
    def rand(self) -> float:
        return self._sim.rand()

    def gen_range_float(self, low: float, high: float) -> float:
        return self._sim.rng.uniform(low, high)

    def gen_range_int(self, low: int, high: int) -> int:
        """Integer in [low, high) — matches Rust's `gen_range(low..high)`."""
        return self._sim.rng.randrange(low, high)

    def random_string(self, length: int) -> str:
        return self._sim.random_string(length)


class Simulation:
    """The global event loop (DSLab Simulation equivalent)."""

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)  # ktpu: prng-ok(seeded reference-port RNG; the batched path never consumes it)
        self._queue: List[Event] = []
        self._next_event_id = 0
        self._time = 0.0
        self._event_count = 0
        self._contexts: Dict[str, SimulationContext] = {}
        self._handlers: Dict[int, EventHandler] = {}
        self._names: Dict[int, str] = {}
        self._next_component_id = 0
        self._cancelled: set = set()

    # --- component registry -------------------------------------------------

    def create_context(self, name: str) -> SimulationContext:
        """Get-or-create by name (DSLab semantics): a second create_context with
        the same name returns a context with the same component id, so a
        handler registered under that name receives its self-events."""
        existing = self._contexts.get(name)
        if existing is not None:
            return existing
        comp_id = self._next_component_id
        self._next_component_id += 1
        ctx = SimulationContext(self, name, comp_id)
        self._contexts[name] = ctx
        self._names[comp_id] = name
        return ctx

    def add_handler(self, name: str, handler: EventHandler) -> int:
        ctx = self._contexts.get(name)
        if ctx is None:
            ctx = self.create_context(name)
        self._handlers[ctx.id] = handler
        return ctx.id

    def lookup_name(self, comp_id: int) -> str:
        return self._names.get(comp_id, f"<component {comp_id}>")

    # --- event queue --------------------------------------------------------

    def _schedule(self, data: Any, src: int, dst: int, delay: float) -> int:
        assert delay >= 0.0, f"negative delay {delay}"
        event_id = self._next_event_id
        self._next_event_id += 1
        heapq.heappush(self._queue, Event(self._time + delay, event_id, src, dst, data))
        return event_id

    def cancel_event(self, event_id: int) -> None:
        """Lazy cancellation: the event stays queued, the pop skips it
        (replaces DSLab cancel_event; usage reference:
        src/core/node_component.rs:102-104,281-283)."""
        self._cancelled.add(event_id)

    def step(self) -> bool:
        """Pop and dispatch the next event. Returns False when the queue is empty."""
        while self._queue:
            event = heapq.heappop(self._queue)
            if event.id in self._cancelled:
                self._cancelled.discard(event.id)
                continue
            self._time = event.time
            self._event_count += 1
            handler = self._handlers.get(event.dst)
            if handler is not None:
                handler.on(event)
            return True
        return False

    def steps(self, n: int) -> bool:
        for _ in range(n):
            if not self.step():
                return False
        return True

    def step_until_no_events(self) -> None:
        while self.step():
            pass

    def step_for_duration(self, duration: float) -> None:
        self.step_until_time(self._time + duration)

    def step_until_time(self, until: float) -> None:
        while self._queue:
            nxt = self._peek_time()
            if nxt is None or nxt > until:
                break
            self.step()
        self._time = max(self._time, until)

    def _peek_time(self) -> Optional[float]:
        while self._queue and self._queue[0].id in self._cancelled:
            cancelled = heapq.heappop(self._queue)
            self._cancelled.discard(cancelled.id)
        return self._queue[0].time if self._queue else None

    def time(self) -> float:
        return self._time

    # Simulation-level RNG helpers (DSLab exposes the same on Simulation).
    def rand(self) -> float:
        return self.rng.random()

    def random_string(self, length: int) -> str:
        alphabet = string.ascii_letters + string.digits
        return "".join(self.rng.choice(alphabet) for _ in range(length))

    def event_count(self) -> int:
        """Number of events processed so far."""
        return self._event_count

    def pending_events(self) -> int:
        return sum(1 for e in self._queue if e.id not in self._cancelled)
