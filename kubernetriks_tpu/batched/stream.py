# ktpu: hot-path
# ktpu: threaded
"""Streaming trace-ingestion pipeline: a bounded-memory feeder for the
superspan executor's staging slabs.

PR 3's double-buffered staging (`engine._prefetch_stage`) is the 2-deep
special case of the general mechanism this module provides: a PRODUCER
thread assembles refill-payload segments (`trace_compile.stage_segment`
via the engine's assemble callback) and `device_put`s them into a bounded
ring of at most K device-resident `state.RefillStage` slabs, running AHEAD
of the consumer — the engine's superspan dispatch loop — so a
stage-exhaustion exit finds the next slab already uploaded instead of
paying `stage_assemble` + `stage_put` on the span boundary's critical
path. This is the classic accelerator input pipeline (keep the device fed
from a producer that runs ahead of consumption), applied to the compiled
trace instead of training examples.

Memory bound: the pipeline holds at most K slabs of C x L columns on
device plus ONE segment being assembled on the host — O(K * C * L), not
O(trace length). A streaming engine never materializes the whole-trace
device slide payload (`engine._init_device_slide` is skipped), so
arbitrarily long traces stream through fixed-size staging state; see
docs/DESIGN.md §"Streaming ingestion pipeline" for the full formula and
the remaining host-side O(T) terms (the compiled payload source the
segment callbacks read — the native feeder's segment iteration,
`trace.feeder.WorkloadSegmentReader`, is the seam for bounding those
next).

Slab schedule. Stage geometry is STATIC (the slab width L is compiled
into the superspan program), so the producer does not need feedback to
know what to build: successive slabs advance by the deterministic stride

    stride = (L - W) - W//2

— exactly the lower bound `engine._prefetch_stage` derives for the
restage base of an exhaustion exit (the failed slide's shift is at most
W/2 and its refill columns crossed lo + L), so the scheduled successor
always covers the next restage point. A consumer whose ring ran empty
floors the schedule at its observed base (the non-streaming path's
miss-rebuild point). Minimal-width stages (L == W + W/2, stride 0) have
no headroom to predict into: there the producer runs DEMAND-driven —
builds exactly the slab the consumer's base asks for, reproducing the
old rebuild-at-base slab schedule (and hence its dispatch/sync counts)
with the assembly moved off the engine thread.

Spent slabs. A slab whose coverage the base has passed
(lo + L - W < base) is popped at the next `get_stage`; a slab the engine
explicitly retires after a SUPERSPAN_STAGE exit is popped immediately and
its lo recorded — `get_stage` asserts every served slab sits strictly
past the retired high-water mark, so the ring can NEVER re-offer a spent
slab (re-offering would spin the dispatch loop on an exhausted buffer —
the PR 3 bug class this pins down structurally). Moving the base
BACKWARDS (checkpoint restore, window growth) requires a re-seek: the
engine closes the feeder and builds a fresh one at the new base/geometry
(`engine._close_feeder`), so a restored run's slabs are rebuilt at the
restored base rather than replayed — slab content is a pure function of
(lo, width), which is why re-seek cannot diverge.

Stall accounting. The consumer-side wait for a covering slab is split
into the two causes a tuner needs to tell apart: `stage_wait_feeder`
(the producer has not PUBLISHED the slab yet — assembly/backlog bound;
raise the ring depth K or widen segments) vs `stage_wait_upload` (the
slab is published but its H2D transfer has not settled — PCIe/DMA bound;
wider segments amortize, deeper rings don't help). Both land on the
span recorder through the engine's handle (the wait happens on the
engine thread); the
producer's own assembly/upload wall time is kept as plain counters here
(the feeder thread never touches the single-threaded span ring).

This module carries the `# ktpu: hot-path` pragma: the lint host-sync
pass patrols it. Its one blocking primitive on device values —
`block_until_ready` on a freshly uploaded slab, HOST-to-device settle,
run on the FEEDER thread — carries an explicit waiver below; the feeder
never reads a device value back to the host.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional

from kubernetriks_tpu.batched.faults import (
    FeederProducerError,
    InjectedFeederKill,
)
from kubernetriks_tpu.telemetry.tracer import (
    PH_STAGE_WAIT_FEEDER,
    PH_STAGE_WAIT_UPLOAD,
    recorder,
)


class _Slot:
    """One ring entry: a device slab covering payload columns
    [lo, lo + L), plus the H2D settle event the producer sets once the
    upload has landed (the upload-wait half of the stall split)."""

    __slots__ = ("lo", "stage", "ready")

    def __init__(self, lo: int, stage, ready: threading.Event):
        self.lo = lo
        self.stage = stage
        self.ready = ready


def _settle_default(stage) -> None:
    """Block until the slab's H2D transfers have landed (feeder-thread
    call; host-to-device settle, not a device readback)."""
    import jax

    jax.block_until_ready(stage)  # ktpu: sync-ok(feeder thread H2D settle of a freshly uploaded staging slab — marks the upload-wait boundary, never reads device values back)


class StreamFeeder:
    """Bounded-ring producer of device-resident staging slabs.

    Parameters:
    - assemble(lo, width) -> host segment payload (numpy; the engine binds
      `trace_compile.stage_segment` over its compiled payload source).
    - upload(segment) -> device RefillStage (jnp.asarray + mesh placement;
      the engine binds its sharding-aware upload half).
    - base: first pod base the consumer will request (slab 0 lands here).
    - width/window: stage width L and pod window W (static geometry).
    - trace_cols: total payload columns (T + W incl. right padding) — a
      slab reaching them is the FINAL slab and the producer exits.
    - depth: ring capacity K (the memory bound); K = 1 degenerates to
      synchronous-but-off-thread staging and stays exact.
    - settle: H2D settle hook (tests inject a no-op for numpy slabs).
    - retired_lo: retired-slab high-water mark carried over from a dead
      predecessor — a SUPERVISOR restart (engine._restart_feeder) builds
      the replacement feeder with the old feeder's mark so the
      never-re-offer invariant spans restarts: the new ring starts empty
      but still refuses every slab the old ring already served spent.
    - chaos: optional `faults.HostChaos`; when armed, each produced slab
      first draws the feeder-kill channel and a hit raises
      `InjectedFeederKill` inside the producer thread (exercising the
      whole death -> FeederProducerError -> supervisor path).
    """

    def __init__(
        self,
        assemble: Callable[[int, int], dict],
        upload: Callable[[dict], object],
        *,
        base: int,
        width: int,
        window: int,
        trace_cols: int,
        depth: int = 3,
        settle: Optional[Callable[[object], None]] = _settle_default,
        retired_lo: int = -1,
        chaos=None,
    ) -> None:
        self._assemble = assemble
        self._upload = upload
        self._settle = settle
        self._chaos = chaos
        self.width = int(width)
        self.window = int(window)
        self.depth = max(1, int(depth))
        self.trace_cols = int(trace_cols)
        self.stride = self.width - self.window - self.window // 2
        # Run-ahead only works when the stride is positive — a slab must
        # cover strictly more bases than its predecessor for the schedule
        # to make progress. Minimal-width stages (L == W + W/2) have zero
        # slide headroom to predict into: the producer then runs
        # DEMAND-driven — it builds exactly the slab the consumer's base
        # asks for, off the engine thread, reproducing the non-streaming
        # path's rebuild-at-base miss behavior (and its slab schedule,
        # hence its dispatch counts) with the assembly moved off-thread.
        self.ahead = self.stride > 0

        self._cond = threading.Condition()
        self._ring: deque = deque()  # _Slot entries, strictly increasing lo
        self._next_lo = int(base)
        self._demand_lo = int(base)
        self._last_lo = -1  # highest slab lo ever published
        self._retired_lo = int(retired_lo)  # highest explicitly-retired lo
        self._served_lo = -1  # last slab lo handed to the consumer
        self._building_lo = -1  # slab the producer is currently building
        self._done = False  # producer published the final slab
        self._stop = False
        self._error: Optional[BaseException] = None

        # Stats (host ints; read under the lock or after close()).
        self.produced = 0
        self.spent_dropped = 0
        self.demand_fastforwards = 0
        self.ring_high_water = 0
        self._depth_sum = 0
        self._depth_samples = 0
        self.assemble_ns = 0
        self.upload_ns = 0
        self.settle_ns = 0
        self.stall_not_ready = 0
        self.stall_not_ready_ns = 0
        self.stall_upload = 0
        self.stall_upload_ns = 0

        self._thread = threading.Thread(
            target=self._produce, name="ktpu-stream-feeder", daemon=True
        )
        self._thread.start()

    # -- producer (feeder thread) -----------------------------------------

    def _produce(self) -> None:
        try:
            while True:
                with self._cond:
                    while not self._stop and (
                        len(self._ring) >= self.depth
                        or (
                            not self.ahead
                            and (
                                len(self._ring) > 0
                                or self._demand_lo <= self._last_lo
                            )
                        )
                    ):
                        self._cond.wait()
                    if self._stop:
                        return
                    if not self.ahead:
                        # Demand mode: build exactly the slab the
                        # consumer's base asks for (the ring is empty and
                        # the demand sits past everything already built —
                        # a retired slab's lo is never re-demanded, see
                        # get_stage's never-re-offer assert).
                        lo = self._demand_lo
                    else:
                        lo = self._next_lo
                        if not self._ring and self._demand_lo > lo:
                            # Starvation floor: with the ring empty and
                            # the consumer's base past the schedule, a
                            # scheduled slab would be dominated on arrival
                            # — fast-forward to the demanded base (the
                            # non-streaming path's miss-rebuild point).
                            lo = self._demand_lo
                            self.demand_fastforwards += 1
                    # Record what we are about to build so a death
                    # mid-build surfaces with its slab context
                    # (FeederProducerError.slab_lo).
                    self._building_lo = lo
                if self._chaos is not None and self._chaos.feeder_kill():
                    raise InjectedFeederKill(
                        f"host chaos: injected stream-feeder kill while "
                        f"building slab lo={lo}"
                    )
                # Build OUTSIDE the lock: assembly + upload are the slow
                # halves and must overlap the consumer's dispatches.
                t0 = time.perf_counter_ns()
                seg = self._assemble(lo, self.width)
                t1 = time.perf_counter_ns()
                stage = self._upload(seg)
                t2 = time.perf_counter_ns()
                slot = _Slot(lo, stage, threading.Event())
                with self._cond:
                    if self._stop:
                        return
                    self.assemble_ns += t1 - t0
                    self.upload_ns += t2 - t1
                    self._ring.append(slot)
                    self.produced += 1
                    self._last_lo = lo
                    if len(self._ring) > self.ring_high_water:
                        self.ring_high_water = len(self._ring)
                    self._next_lo = lo + max(self.stride, 1)
                    self._done = lo + self.width >= self.trace_cols
                    done = self._done
                    self._cond.notify_all()
                # Settle the H2D transfer before marking the slot ready:
                # a consumer that grabbed it meanwhile waits on the event
                # (the upload-wait half of the stall split).
                if self._settle is not None:
                    self._settle(slot.stage)
                    settle_ns = time.perf_counter_ns() - t2
                    with self._cond:
                        self.settle_ns += settle_ns
                slot.ready.set()
                if done:
                    return
        except BaseException as exc:  # propagate into the consumer
            with self._cond:
                self._error = exc
                # A consumer may already hold a published slab and be
                # blocked on its settle event (upload wait) — wake it so
                # the failure surfaces instead of hanging; get_stage
                # re-raises via _error on its next lock acquisition.
                for slot in self._ring:
                    slot.ready.set()
                self._cond.notify_all()

    # -- consumer (engine thread) ------------------------------------------

    def _producer_error(self) -> FeederProducerError:
        """Build the consumer-facing producer-death error with the slab
        context carried across the thread boundary (call under the
        lock): the slab index `lo` and payload span the producer was
        building when it died."""
        lo = self._building_lo  # ktpu: lock-ok(only called from get_stage while holding self._cond)
        span = (
            f"slab lo={lo} span=[{lo}, {lo + self.width})"
            if lo >= 0
            else "before the first slab"
        )
        return FeederProducerError(
            f"stream feeder producer failed ({span}): {self._error!r}",  # ktpu: lock-ok(only called from get_stage while holding self._cond)
            slab_lo=lo if lo >= 0 else None,
            width=self.width,
        )

    def retired_watermark(self) -> int:
        """Highest retired slab lo — the supervisor passes this as the
        replacement feeder's `retired_lo` so never-re-offer survives a
        restart."""
        with self._cond:
            return self._retired_lo

    def get_stage(self, base: int, spans=None, ident: int = 0):
        """Return (stage, lo, fresh) for the LARGEST-lo ring slab covering
        `base` (lo <= base and base - lo + W <= L; dominated predecessors
        pop as spent — the max-headroom rule), blocking until the
        producer publishes it; `fresh` is True the first time a slab is
        served (the engine's stage_refills accounting); the stall spans go
        to `spans` (the calling engine's handle; else the process-wide
        recorder) under `ident`, the superspan ordinal. Raises
        AssertionError if the ring would have to re-offer a spent/retired
        slab — the never-re-offer invariant — or if `base` moved backwards
        without a re-seek."""
        if spans is None:
            spans = recorder()
        waited = False
        with self._cond:
            # Tell the producer where the consumer is: the next scheduled
            # slab never needs to start below the latest observed base (a
            # restage always lands at or past it).
            if base > self._demand_lo:
                self._demand_lo = base
                self._cond.notify_all()
            while True:
                if self._error is not None:
                    raise self._producer_error() from self._error
                # Drop slabs that can no longer cover any base >= `base`,
                # and DOMINATED slabs — a head whose successor also sits
                # at or below the base serves strictly less headroom than
                # that successor (the max-lo rule that mirrors the
                # non-streaming path's rebuild-at-base).
                while (
                    self._ring
                    and self._ring[0].lo + self.width - self.window < base
                ) or (len(self._ring) >= 2 and self._ring[1].lo <= base):
                    self._ring.popleft()
                    self.spent_dropped += 1
                    self._cond.notify_all()  # ring space freed
                if self._ring and self._ring[0].lo <= base:
                    slot = self._ring[0]
                    break
                if self._ring:  # head.lo > base: base moved backwards
                    raise AssertionError(
                        f"stream ring would re-offer below its head: "
                        f"requested base {base} precedes slab lo="
                        f"{self._ring[0].lo} — spent slabs are never "
                        "re-offered; re-seek the feeder (close + rebuild) "
                        "after moving the base backwards"
                    )
                if self._done:
                    raise AssertionError(
                        f"stream feeder exhausted the trace "
                        f"(trace_cols={self.trace_cols}) with base {base} "
                        "uncovered — stride/coverage invariant broken"
                    )
                # Slab not published yet: the feeder-not-ready stall.
                if not waited:
                    waited = True
                    t_wait = time.perf_counter_ns()
                self._cond.wait()
            if waited:
                dur = time.perf_counter_ns() - t_wait
                self.stall_not_ready += 1
                self.stall_not_ready_ns += dur
                spans.end(PH_STAGE_WAIT_FEEDER, t_wait, dur=dur, ident=ident)
            assert slot.lo > self._retired_lo, (
                f"stream ring re-offered a retired slab (lo={slot.lo} <= "
                f"retired {self._retired_lo})"
            )
            fresh = slot.lo != self._served_lo
            self._served_lo = slot.lo
            self._depth_sum += len(self._ring)
            self._depth_samples += 1
        if not slot.ready.is_set():
            # Published but the H2D transfer has not settled: upload wait.
            t_wait = time.perf_counter_ns()
            slot.ready.wait()
            dur = time.perf_counter_ns() - t_wait
            with self._cond:
                self.stall_upload += 1
                self.stall_upload_ns += dur
                if self._error is not None:
                    # The settle failed — the event was set only so this
                    # wait could observe the failure, not a usable slab.
                    raise self._producer_error() from self._error
            spans.end(PH_STAGE_WAIT_UPLOAD, t_wait, dur=dur, ident=ident)
        return slot.stage, slot.lo, fresh

    def retire(self, lo: int) -> None:
        """Drop the slab at `lo` after a SUPERSPAN_STAGE exhaustion exit
        and record it as spent — `get_stage` will assert rather than ever
        hand it out again (the exhausted slab may still COVER the final
        base; serving it again would spin the dispatch loop)."""
        with self._cond:
            if self._ring and self._ring[0].lo == lo:
                self._ring.popleft()
            if lo > self._retired_lo:
                self._retired_lo = lo
            self._cond.notify_all()

    def close(self, timeout: float = 30.0) -> bool:
        """Stop the producer and join it. Idempotent; the engine's re-seek
        (checkpoint restore, window growth) is close + rebuild. Returns
        False (with a warning) if the producer outlived the join timeout —
        it is mid-build on a huge segment; it will discard its slab at the
        stop check before publishing and exit on its own, but the caller
        should know the overlap happened."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)
        if self._thread.is_alive():
            import logging

            logging.getLogger(__name__).warning(
                "stream feeder producer did not exit within %.0fs of "
                "close() (mid-build on a %d-column segment); it will "
                "discard the slab and exit at the next stop check",
                timeout,
                self.width,
            )
            return False
        return True

    # -- readout ------------------------------------------------------------

    def report(self) -> dict:
        """Feeder-side stats for engine.telemetry_report()['feeder']:
        production counters, the ring-depth gauge (mean + high-water vs
        capacity), producer wall time, and the stall split the consumer
        recorded."""
        with self._cond:
            depth_mean = (
                self._depth_sum / self._depth_samples
                if self._depth_samples
                else 0.0
            )
            return {
                "slabs_produced": self.produced,
                "spent_dropped": self.spent_dropped,
                "demand_fastforwards": self.demand_fastforwards,
                "ring_capacity": self.depth,
                "ring_depth_high_water": self.ring_high_water,
                "ring_depth_mean": round(depth_mean, 3),
                "segment_cols": self.width,
                "stride_cols": self.stride,
                "trace_cols": self.trace_cols,
                "assemble_ms": round(self.assemble_ns / 1e6, 3),
                "upload_ms": round(self.upload_ns / 1e6, 3),
                "settle_ms": round(self.settle_ns / 1e6, 3),
                "stalls": {
                    "feeder_not_ready": {
                        "count": self.stall_not_ready,
                        "ms": round(self.stall_not_ready_ns / 1e6, 3),
                    },
                    "upload_wait": {
                        "count": self.stall_upload,
                        "ms": round(self.stall_upload_ns / 1e6, 3),
                    },
                },
            }


class LaneTraceMux:
    """Per-lane workload multiplexer over the compiled trace slab — the
    full-resident analog of `trace.feeder.WorkloadSegmentReader`'s
    row-range contract (the PayloadSource seam), turned 90 degrees: where
    the streaming feeder offers every lane the SAME row window of an
    unbounded trace, the mux offers each lane its OWN row-range of the
    resident slab, so a lane-async fleet can replay a workload subset per
    query without recompiling anything (the masked rows are pure data).

    Semantics (`offer(lane, lo, hi)`): slab rows [lo, hi) of the lane are
    the kept range. Plain-pod CREATE events outside it are masked to
    EV_NONE IN PLACE — `win` stays untouched, so the per-lane time sort
    the event loop's searchsorted gathers rely on is preserved — and pod
    REMOVE events are masked by SLOT membership: a remove whose slot's
    create was masked is masked too (never a remove without its create),
    while a remove of a slot the slab never creates (pre-existing pods)
    is always kept. Node and chaos events are never masked: cluster shape
    and fault streams belong to the scenario vectors, not the workload
    range.

    Never-re-offer (per lane): `offer` REFUSES a lane whose previous
    range is still flying — the engine retires a lane's range at its
    reset boundary (`engine.lane_reset` -> `retire`), exactly like the
    feeder ring's retired-slab high-water mark refuses to re-serve a
    spent slab. Mutating an in-flight lane's rows would change history
    the scan carry already consumed.

    Host-only: the mux owns a host copy of the slab's real event rows
    ((C, E, 4), TraceSlab.rows() less the sentinel tail) and returns host
    row blocks; the ENGINE blocks them and owns the device install
    (`engine.set_lane_trace`, a data-only dynamic_update_slice at the
    reseed host-block boundary — zero new steady-state syncs).
    """

    def __init__(self, rows) -> None:
        import numpy as np

        base = np.array(rows, np.int32)  # ktpu: sync-ok(mux construction: one owned host copy of the freshly built slab, never on the steady-state path)
        if base.ndim != 3 or base.shape[-1] != 4:
            raise ValueError(
                f"LaneTraceMux wants (C, E, 4) event rows, got {base.shape}"
            )
        self._base = base
        C = base.shape[0]
        self._flying = [False] * C  # offer outstanding (not yet retired)
        self._installed = [None] * C  # last (lo, hi) served per lane
        self.offers = 0

    @property
    def n_rows(self) -> int:
        return self._base.shape[1]

    def offer(self, lane: int, lo: int = 0, hi: Optional[int] = None):
        """Masked host row block (E, 4) for `lane`, or None when the lane
        already has exactly this range installed (the caller skips the
        device update). Raises on a re-offer to a lane whose previous
        range was never retired."""
        import numpy as np

        from kubernetriks_tpu.batched.state import (
            EV_CREATE_POD,
            EV_NONE,
            EV_REMOVE_POD,
        )

        E = self._base.shape[1]
        hi = E if hi is None else int(hi)
        lo = int(lo)
        if not (0 <= lo <= hi <= E):
            raise ValueError(
                f"lane {lane}: trace row-range [{lo}, {hi}) outside [0, {E})"
            )
        if self._flying[lane]:
            raise RuntimeError(
                f"lane {lane}: trace rows re-offered while its previous "
                "range is still flying — retire the lane (lane_reset) "
                "before re-seeding (never-re-offer invariant)"
            )
        self._flying[lane] = True
        self.offers += 1
        if self._installed[lane] == (lo, hi):
            return None
        self._installed[lane] = (lo, hi)
        rows = self._base[lane].copy()
        kind = rows[:, 2]
        slot = rows[:, 3]
        is_create = kind == EV_CREATE_POD
        is_remove = kind == EV_REMOVE_POD
        if not bool(is_create.any()):
            return rows
        in_range = np.zeros((E,), bool)
        in_range[lo:hi] = True
        n_slots = int(slot[is_create | is_remove].max()) + 1
        created = np.zeros((n_slots,), bool)
        created[slot[is_create]] = True
        kept = np.zeros((n_slots,), bool)
        kept[slot[is_create & in_range]] = True
        drop = (is_create & ~in_range) | (
            is_remove & created[slot] & ~kept[slot]
        )
        rows[drop, 2] = EV_NONE
        return rows

    def retire(self, lanes) -> None:
        """Mark lanes' offered ranges as consumed (reset boundary): the
        next offer for them is legal again."""
        for lane in lanes:
            self._flying[int(lane)] = False

    def report(self) -> dict:
        return {
            "offers": self.offers,
            "installed": {
                lane: rng
                for lane, rng in enumerate(self._installed)
                if rng is not None
            },
        }
