"""Dense array state for the batched (vectorized) simulation path.

This is the TPU-native reformulation of the reference's actor state
(reference: src/core/{api_server,persistent_storage,scheduler,node_component}.rs
hold overlapping per-object maps; here the consistent merged view lives in
arrays of shape (clusters, nodes) / (clusters, pods)).

Design rules:
- Static shapes: N_max node slots and P_max pod slots per cluster, pre-sized
  from the trace like the reference's node pool (reference: src/simulator.rs:51-65).
- All payloads (capacities, requests, durations) are pre-staged per slot at
  trace-compile time; on-device events only flip phases/masks. Strings never
  reach the device.
- cpu is int32 millicores; ram is quantized to RAM_UNIT-byte units (ceil for
  requests, floor for capacity) so int32 never overflows and the batched path
  never overcommits relative to the byte-exact scalar path.
- Simulation time is the (win:int32, off:float32) window-indexed pair of
  batched/timerep.py: exact integer window classification plus a bounded
  float32 offset (ulp ≈ 1e-6 s at the default 10 s interval, three orders of
  magnitude under the smallest modeled delay) — full fidelity at
  Alibaba-scale timestamps without any 64-bit array in the hot loop.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax

# NOTE: importing this module enables jax_enable_x64 PROCESS-WIDE (a hard
# requirement of the batched subsystem, not an accident). The hot loop is
# all-32-bit by design (timerep.py pairs), but two cold spots still want
# 64-bit types: the HPA load-curve lookup evaluates elapsed time in f64
# (tiny (C, G)-shaped elementwise math), and the conditional-move wake
# budgets accumulate in i64 (unbounded in the scalar oracle). Tests also
# compare device output against the float64 scalar oracle.
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kubernetriks_tpu.batched.timerep import (  # noqa: E402
    INF_WIN,
    TPair,
    from_f64_np,
    t_inf,
    t_zeros,
)

# Pod phases.
PHASE_EMPTY = 0  # slot not yet created
PHASE_QUEUED = 1  # in the scheduler's active queue
PHASE_UNSCHEDULABLE = 2  # parked in the unschedulable queue
PHASE_RUNNING = 3  # bound to a node (incl. binding in flight)
PHASE_SUCCEEDED = 4
PHASE_REMOVED = 5
PHASE_FAILED = 6

# Event kinds in the compiled trace slab.
EV_NONE = 0
EV_CREATE_NODE = 1
EV_REMOVE_NODE = 2
EV_CREATE_POD = 3
EV_REMOVE_POD = 4
# Chaos engine (chaos.py): a crash is EV_REMOVE_NODE semantics plus fault
# accounting (the crash counter, the downtime table of TraceSlab, the
# interruption counter); a recovery is EV_CREATE_NODE semantics, on the
# node's own slot (trace_compile), plus the recovery counter.
EV_NODE_CRASH = 5
EV_NODE_RECOVER = 6

DEFAULT_RAM_UNIT = 1024 * 1024  # 1 MiB

INF = jnp.inf


class NodeArrays(NamedTuple):
    """(C, N) per-node-slot arrays."""

    alive: jnp.ndarray  # bool
    cap_cpu: jnp.ndarray  # int32 millicores
    cap_ram: jnp.ndarray  # int32 ram units
    alloc_cpu: jnp.ndarray  # int32
    alloc_ram: jnp.ndarray  # int32
    # Pending on-device effects (cluster-autoscaler actions); +inf = none.
    create_time: TPair
    remove_time: TPair
    # Zeros, read by nothing: the sampled repair spans lived here a slot
    # while a slot crashed at most once; a recovery now returns to its slot
    # and they ride the trace slab by crash event (TraceSlab.crash_downtime).
    # The plane stays so that the state's tree, and with it every program
    # built without faults, is what it was (window_program_digests.json).
    crash_downtime: jnp.ndarray  # float32


class PodArrays(NamedTuple):
    """(C, P) per-pod-slot arrays."""

    phase: jnp.ndarray  # int32
    req_cpu: jnp.ndarray  # int32 millicores
    req_ram: jnp.ndarray  # int32 ram units
    # Static running duration as a time pair; win < 0 marks a long-running
    # service (the scalar path's running_duration=None).
    duration: TPair
    queue_ts: TPair  # queue-priority / eligibility timestamp
    queue_seq: jnp.ndarray  # int32: FIFO tie-break within equal timestamps
    initial_attempt_ts: TPair
    attempts: jnp.ndarray  # int32
    node: jnp.ndarray  # int32 node slot, -1 = none
    start_time: TPair
    # Finite while the SCHEDULER's allocatable still holds the pod's
    # requests: a running pod's finish, and after it the NODE-side time of
    # a free still on its way to the scheduler's cache (the pending-free
    # channel, step._apply_window_events_work; never outlives the window of
    # the finish when the control-plane delays are zero). +inf otherwise.
    finish_time: TPair
    removal_time: TPair  # pending HPA scale-down effect; +inf = none
    # HPA replica index of the slot's CURRENT occupant ("{group}_{idx}"
    # names; -1 = not an HPA replica). Set at activation; the scale-down
    # victim selection pops the lexicographically-smallest name from it
    # (kube_horizontal_pod_autoscaler.rs:197-205).
    hpa_idx: jnp.ndarray  # int32
    # Chaos engine (CrashLoopBackOff): completed failure count, and whether
    # the CURRENT running attempt fails at finish_time (drawn at commit from
    # the counter PRNG on (cluster, global slot, restarts)). Inert zeros
    # when fault injection is off.
    restarts: jnp.ndarray  # int32
    will_fail: jnp.ndarray  # bool


def held_frees(pods: PodArrays) -> jnp.ndarray:
    """(C, P) the pods ON the pending-free channel: off their node (finished,
    failed or removed there) with their requests still in the scheduler's
    allocatable, because the news has not reached its cache by the last
    cycle. Their finish_time is the node-side time the free set out at.
    Empty by construction where StepConstants.delta_free_visible is None."""
    return (pods.phase != PHASE_RUNNING) & (pods.finish_time.win < INF_WIN)


def alloc_holders(pods: PodArrays, consts: "StepConstants") -> jnp.ndarray:
    """(C, P) the pods whose finish_time still owes the window body work:
    the running ones and, where the build has the channel, those on it."""
    running = pods.phase == PHASE_RUNNING
    if consts.delta_free_visible is None:
        return running
    return running | held_frees(pods)


def slide_phase(pods: PodArrays, consts: "StepConstants") -> jnp.ndarray:
    """The phases the pod window's slide may read: a pod on the pending-free
    channel counts as RUNNING, so that no slide moves it out of the window
    with a free still owed to its node."""
    if consts.delta_free_visible is None:
        return pods.phase
    return jnp.where(held_frees(pods), PHASE_RUNNING, pods.phase)


class EstArrays(NamedTuple):
    """(C,) streaming estimator accumulators -> min/max/mean/variance at readout
    (mirrors the scalar Estimator, kubernetriks_tpu/metrics/collector.py)."""

    count: jnp.ndarray  # int32
    total: jnp.ndarray  # float32 sum
    total_sq: jnp.ndarray  # float32 sum of squares
    minimum: jnp.ndarray  # float32
    maximum: jnp.ndarray  # float32

    @staticmethod
    def zeros(shape) -> "EstArrays":
        return EstArrays(
            count=jnp.zeros(shape, jnp.int32),
            total=jnp.zeros(shape, jnp.float32),
            total_sq=jnp.zeros(shape, jnp.float32),
            minimum=jnp.full(shape, INF, jnp.float32),
            maximum=jnp.full(shape, -INF, jnp.float32),
        )

    def add(self, value: jnp.ndarray, mask: jnp.ndarray) -> "EstArrays":
        value = value.astype(jnp.float32)
        return EstArrays(
            count=self.count + mask.astype(jnp.int32),
            total=self.total + jnp.where(mask, value, 0.0),
            total_sq=self.total_sq + jnp.where(mask, value * value, 0.0),
            minimum=jnp.where(mask, jnp.minimum(self.minimum, value), self.minimum),
            maximum=jnp.where(mask, jnp.maximum(self.maximum, value), self.maximum),
        )


class MetricArrays(NamedTuple):
    """(C,) per-cluster counters (mirrors AccumulatedMetrics)."""

    pods_succeeded: jnp.ndarray  # int32
    pods_removed: jnp.ndarray  # int32
    terminated_pods: jnp.ndarray  # int32
    processed_nodes: jnp.ndarray  # int32
    scheduling_decisions: jnp.ndarray  # int32: successful assignments (bench metric)
    scaled_up_pods: jnp.ndarray  # int32 (HPA)
    scaled_down_pods: jnp.ndarray  # int32 (HPA)
    scaled_up_nodes: jnp.ndarray  # int32 (CA)
    scaled_down_nodes: jnp.ndarray  # int32 (CA)
    # Replicas an HPA cycle wanted but could not activate because the
    # group's slot reserve had no reusable slot (autoscale.py "Remaining
    # bounded deviations"); nonzero means the run diverged from the scalar
    # trajectory and the engine raises loudly at readout
    # (engine.check_autoscaler_bounds) instead of reporting wrong counts.
    hpa_reserve_clamped: jnp.ndarray  # int32
    # CA scale-up open attempts blocked ONLY by the consumed (never
    # reclaimed) slot reserve while the group had quota headroom and a
    # fitting template — the CA-side silent divergence, same loud-readout
    # treatment.
    ca_reserve_starved: jnp.ndarray  # int32
    # Chaos-engine fault counters (mirroring the scalar AccumulatedMetrics
    # additions): crashes/recoveries applied, summed sampled repair spans,
    # crash-caused pod reschedules, CrashLoopBackOff requeues, and pods
    # permanently failed past the restart limit.
    node_crashes: jnp.ndarray  # int32
    node_recoveries: jnp.ndarray  # int32
    node_downtime_s: jnp.ndarray  # float32
    pod_interruptions: jnp.ndarray  # int32
    pod_restarts: jnp.ndarray  # int32
    pods_failed: jnp.ndarray  # int32
    # The pending-free channel's two counters (step._apply_window_events_work;
    # no scalar counterpart): the frees that left a node (a finish, a failed
    # attempt, the removal of a running pod), and those of them that missed
    # the next cycle, because the news was still on its way to the scheduler.
    frees_total: jnp.ndarray  # int32
    frees_deferred: jnp.ndarray  # int32
    # Windows in which the cluster's event application was due
    # (step._window_work_due). Under the window razor the free kernel and
    # the event scatter kernel launch in the windows in which ANY cluster's
    # was, so the largest count of a batch is a lower bound of their
    # launches, and the launch count itself where the clusters carry like
    # loads.
    event_windows: jnp.ndarray  # int32
    # The drained scheduling cycle's counters (step.fold_cycle_totals; no
    # scalar counterpart, the scalar cycle is one `while pop`): a cluster's
    # passes of max_pods_per_cycle, its cycles that had a pod to decide, the
    # assignments made after a cycle's first pass (what a cycle bounded at
    # the pass size would have put off), its deepest cycle in pods, and the
    # cycles whose simulated duration reached the scheduling interval, where
    # the scalar scheduler delays its next cycle and the fixed windows here
    # do not (docs/PARITY.md). Each a cluster's own, whatever its neighbours
    # do: the passes the BATCH ran (a K-shaped kernel's launches, one while
    # any cluster has work) are no leaf, since a lane's row must not depend
    # on the lanes beside it nor on the sharding.
    cycle_passes: jnp.ndarray  # int32
    cycle_count: jnp.ndarray  # int32
    cycle_late_decisions: jnp.ndarray  # int32
    cycle_deepest: jnp.ndarray  # int32
    cycle_overruns: jnp.ndarray  # int32
    # The cycles in which the cluster's queue was deeper than one pass, and
    # those of them the megakernel's second launch drained, the cluster
    # brought into a lane tile of the batch's deep ones
    # (step._launch_by_depth; the rest ran in the one launch: one tile, more
    # clusters deep at once than a tile holds, or a move that would not have
    # paid: step._lanes_to_move). No scalar counterpart.
    cycle_deep: jnp.ndarray  # int32
    cycle_compacted: jnp.ndarray  # int32
    # The reschedule order's two counters (step._stable_queue_rank; no scalar
    # counterpart): the windows in which the cluster had a pod of a removed
    # node to rank, and those of them in which it had more than the
    # compacted rank holds (step.RANK_COMPACT_SLOTS), which is what sends a
    # window of the batch to the sort of the whole pod axis. A cluster's own,
    # like the cycle's: zero in the second over a batch says no window sorted.
    resched_rank_windows: jnp.ndarray  # int32
    resched_rank_sorted: jnp.ndarray  # int32
    queue_time: EstArrays
    algo_latency: EstArrays
    pod_duration: EstArrays
    # The event chunk loop's two counters (step._apply_window_events_work; no
    # scalar counterpart): the windows in which the cluster had more slab
    # events due than one pass of the loop applies (max_events_per_window: a
    # cluster's own count), and those of them it finished in a lane tile of
    # the batch's deep clusters, outside the batch's loop (the rest took
    # their passes in the batch's loop: too few events past the chunk for the
    # move to pay, or more clusters deep than a tile holds, as where every
    # cluster creates its nodes at t = 0: step._event_lanes_to_move). As
    # cycle_compacted, the second reads the batch (the chip's own, under a
    # mesh). None in a batch of one lane tile, which has nothing to choose:
    # its window programs trace neither (the structural idiom of
    # ClusterBatchState.spread).
    events_deep: Optional[jnp.ndarray] = None  # int32
    events_compacted: Optional[jnp.ndarray] = None  # int32
    # The label scorers' two counters (pipeline.integer_scores; docs/PARITY.md
    # "Scoring as kube-scheduler scores"): a cycle's valid candidates for
    # which NodeAffinity or TaintToleration had something to normalise by (a
    # feasible node matched a preferred term or carries an untolerated
    # PreferNoSchedule taint), and those of them placed on a node whose label
    # score is the largest among the feasible ones. None in a build without
    # soft planes (AffinityState.pod_soft_terms), which traces neither.
    soft_attempts: Optional[jnp.ndarray] = None  # int32
    soft_honoured: Optional[jnp.ndarray] = None  # int32


class SpreadState(NamedTuple):
    """The PodTopologySpread filter's device state (batched/pipeline.py;
    semantics: core/scheduler/plugins.PodTopologySpread). Present only in a
    build whose profile runs the filter over traces that carry a constraint;
    None otherwise, and the window programs then trace none of it (the
    structural idiom of `auto` and `telemetry`).

    The first four are the trace's interned vocabulary
    (trace_compile.CompiledSpread) and never change. The pod planes are in
    GLOBAL pod-slot coordinates, the whole trace wide: the device pod window
    reads and writes its own columns of them at `pod_base`
    (step.spread_window_view), so no slide, refill or window growth moves
    them. `pod_zone` is the one plane the cycle writes: the domain of the
    node each placement went to, which spares the count pass a (C, P)
    gather of `domain` at `pods.node` (a gather on the TPU costs per index)."""

    domain: jnp.ndarray  # (C, N) int32 domain of the node slot, -1 without the key
    # (C, G, Z) int32 maxSkew of workload g, repeated over the domains: its
    # shape is where the programs read G and Z from.
    max_skew: jnp.ndarray
    pod_group: jnp.ndarray  # (C, T) int32 workload whose constraint the pod carries, -1 none
    pod_bits: jnp.ndarray  # (C, T) int32 bit g: the pod's labels satisfy workload g's selector
    pod_zone: jnp.ndarray  # (C, T) int32 domain of the pod's last placement, -1 never placed
    # Always-on counters (no scalar counterpart): assignments of pods that
    # carry a constraint, and those of them at whose instant the skew closed
    # at least one live domain.
    decisions: jnp.ndarray  # (C,) int32
    decisions_bound: jnp.ndarray  # (C,) int32


class AffinityState(NamedTuple):
    """The NodeAffinity and TaintToleration filters' device state
    (batched/pipeline.py; semantics: core/scheduler/plugins.py and
    docs/PARITY.md "Node affinity and taints"). Present only in a build whose
    profile runs one of the two filters over traces that carry a taint, a
    nodeSelector, a node affinity or a toleration; None otherwise, and the
    window programs then trace none of it (the structural idiom of `spread`).

    The three planes are the traces' interned vocabulary
    (trace_compile.CompiledAffinity), integers only, and never change: no
    filter of the two reads where other pods sit, so nothing is carried from
    one placement to the next. The pod planes are in GLOBAL pod-slot
    coordinates, the whole trace wide, as SpreadState's are: the device pod
    window reads its own columns of them at `pod_base`
    (step.affinity_window_view), so no slide, refill or window growth moves
    them. `node_bits` is laid out like the hot node leaves."""

    node_bits: jnp.ndarray  # (C, N) int32 the expressions the node satisfies, the taints it carries
    # (C, T, W) int32 a pod's t-th term: the bits a node must carry
    # (0: every node passes; bit 31 alone: the pod has no t-th term).
    pod_terms: jnp.ndarray
    # (C, W) int32 the taint bits the pod does not tolerate; bit 31: the pod
    # carries a selector, an affinity or a toleration.
    pod_forbid: jnp.ndarray
    # Always-on counters, a cluster's own: a cycle's attempts to place a pod
    # that carries a selector, an affinity or a toleration, and those of them
    # that ended unschedulable although some live node passed every other
    # filter of the chain (the labels and taints, not capacity, refused it).
    attempts: jnp.ndarray  # (C,) int32
    attempts_refused: jnp.ndarray  # (C,) int32
    # The score halves' planes (trace_compile.CompiledAffinity.soft_*), in the
    # pod planes' global coordinates; None unless the profile scores by
    # NodeAffinity or TaintToleration AND a trace carries a preferred term or
    # a PreferNoSchedule taint (the taints are bits of node_bits, from bit 30
    # downwards). (C, S, W) a pod's s-th preferred term's mask; (C, W) its
    # terms' weights, seven bits each; (C, W) the PreferNoSchedule taint bits
    # it does not tolerate.
    pod_soft_terms: Optional[jnp.ndarray] = None
    pod_soft_weights: Optional[jnp.ndarray] = None
    pod_soft_forbid: Optional[jnp.ndarray] = None


class ClusterBatchState(NamedTuple):
    """Complete batched simulation state; a pytree of arrays with leading
    cluster axis C, shardable across a device mesh on that axis."""

    time: jnp.ndarray  # (C,) int32 last completed window index
    queue_seq_counter: jnp.ndarray  # (C,) int32 next queue sequence number
    event_cursor: jnp.ndarray  # (C,) int32 next unapplied trace event
    # First GLOBAL pod slot covered by the device pod arrays (sliding pod
    # window; 0 and never advanced when the window is the whole trace).
    pod_base: jnp.ndarray  # (C,) int32
    last_flush_win: jnp.ndarray  # (C,) int32 last unschedulable-leftover flush window
    requeue_signal: jnp.ndarray  # (C,) bool: node-add/pod-finish since last cycle
    # (Conditional-move wake budgets are NOT state: they are intra-window
    # WakeEvents threaded from event application to the same window's
    # prepare_cycle — step._conditional_wake_exact.)
    nodes: NodeArrays
    pods: PodArrays
    metrics: MetricArrays
    # Dynamic autoscaler state (AutoscaleState) or None when autoscaling is off.
    auto: Optional[NamedTuple] = None
    # Device-side per-window telemetry ring (TelemetryRing) or None when
    # telemetry is off — None compiles programs identical to the
    # pre-telemetry build, the same structural-static trick `auto` and
    # `fault_params` use.
    telemetry: Optional[TelemetryRing] = None
    # Topology-spread vocabulary, pod planes and counters (SpreadState) or
    # None when no pod of the build is held to a constraint.
    spread: Optional[SpreadState] = None
    # Node-affinity and taint planes and counters (AffinityState) or None
    # when no node of the build is tainted and no pod names its nodes.
    affinity: Optional[AffinityState] = None


# Column layout of the device-side telemetry ring (TelemetryRing.buf).
# All int32: per-window aggregates cheap to fold from state the window body
# already holds — no new reductions over the trace slab, no float state.
TELEM_WINDOW = 0  # window index this record describes
TELEM_DECISIONS = 1  # scheduling decisions committed this window
TELEM_QUEUED = 2  # active-queue depth after the cycle
TELEM_UNSCHED = 3  # unschedulable-queue depth (failed fits parked)
TELEM_HPA_PODS = 4  # HPA pod actions this window (scale-ups + scale-downs)
TELEM_CA_NODES = 5  # CA node actions this window (scale-ups + scale-downs)
TELEM_FAULTS = 6  # chaos events this window (crashes/recoveries/retries/fails)
TELEM_ALIVE_NODES = 7  # alive node count after the window
# Capacity-observatory occupancy gauges (telemetry/observatory.py): the
# reserve consumptions whose exhaustion kills a long run (ROADMAP #2),
# folded from tiny (C, G)/(C,) state the window body already holds — no
# reductions over the trace slab or pod axis beyond what the record
# already pays, zeros when autoscaling is off.
TELEM_HPA_RESERVE = 8  # live HPA replicas across groups (hpa_tail - hpa_head)
TELEM_CA_RESERVE = 9  # CA reserve slots consumed across groups (ca_cursor:
# monotone without reclaim; LIVE occupancy under KTPU_RECLAIM, where the
# compaction pulls the cursor back — the watchdog fits the NET slope)
# Plain-trace refill columns the device pod window has NOT yet covered
# (trace_pod_bound - pod_base - plain window width). Values at or above
# telemetry/observatory.UNBOUNDED_SENTINEL mean "no sliding window /
# whole trace resident" (the trace_pod_bound default is a huge sentinel).
TELEM_POD_HEADROOM = 10
# Lane-asynchronous fleet (batched/fleet.py lane_async mode): 1 when this
# lane was ACTIVE for the window (its per-lane clock placed the global
# window inside [lane_clock, lane_clock + lane_horizon)), else 0. Always 1
# outside lane-async builds. The observatory folds the column into the
# lane-occupancy gauge and the idle-lane-waste verdict; in lane-async mode
# the TELEM_WINDOW column records the GLOBAL window index (uniform across
# lanes — ring.merge_snapshot keys on it), while every other column is the
# lane's own (virtual-clock) value.
TELEM_LANE_ACTIVE = 11
# The scheduling megakernel's sweep counter (ops/scheduler_kernel.py, stats
# rows 5-7): the row tiles of the pod block its steps swept this window, and
# its steps times the block's tiles (what whole-block sweeps would have
# cost). Their ratio over a run is telemetry_report()'s
# cycle_rows_swept_share. Both are one value per 128-cluster grid program,
# repeated on its lanes; zeros on formulations without the megakernel.
TELEM_CYCLE_TILES_SWEPT = 12
TELEM_CYCLE_TILE_STEPS = 13
# The event loop's chunk count (step._apply_window_events_work): how many
# chunks of max_events_per_window slab entries this cluster's due events
# took this window. The loop runs until no cluster has one left, so a
# window's MAXIMUM over clusters is the slab reads it paid;
# telemetry_report()'s event_chunks_per_window is the mean of those maxima.
# 0 on a window the razor skipped.
TELEM_EVENT_CHUNKS = 14
# Frees the pending-free channel deferred this window (the growth of
# MetricArrays.frees_deferred): 0 on every window of a build whose
# control-plane delays are zero.
TELEM_FREES_DEFERRED = 15
# Assignments this window of pods carrying a topology-spread constraint at
# whose instant the skew closed a live domain (the growth of
# SpreadState.decisions_bound): 0 in a build without constraints.
TELEM_SPREAD_BOUND = 16
TELEMETRY_COLS = 17


class TelemetryRing(NamedTuple):
    """(C, R, TELEMETRY_COLS) device-side per-window metrics ring.

    Carried inside ClusterBatchState like `auto`: None (telemetry off)
    compiles programs identical to the pre-telemetry build; when present,
    every executed window scatters ONE record row per cluster at
    `cursor % R` and bumps the cursor — the ring accumulates on device and
    is drained host-side only at boundaries where the host already blocks
    (engine step_until_time exit / readout), never inside the dispatch
    loop, so telemetry-on adds zero new host syncs (the dispatch-count
    regression gate in tests/test_telemetry.py pins this).

    Unwritten rows carry window = -1 (the drain filters on it); a cursor
    past R means early windows wrapped out — the engine's pressure-based
    drain keeps long runs lossless by snapshotting before the wrap."""

    buf: jnp.ndarray  # (C, R, TELEMETRY_COLS) int32
    cursor: jnp.ndarray  # (C,) int32 total windows recorded (slot = cursor % R)


def strip_telemetry(state: "ClusterBatchState") -> "ClusterBatchState":
    """The state minus its telemetry ring — the comparison view for the
    telemetry-on vs telemetry-off bit-identity gate (the ring is the ONE
    leaf allowed to differ: it only exists on one side)."""
    return state._replace(telemetry=None)


class RefillStage(NamedTuple):
    """Device-resident staging slab for the superspan executor
    (step.run_superspan): refill payload columns [lo, lo + L) of the trace's
    PLAIN pod segment — requests, duration pairs, create windows and (under
    autoscalers) name ranks — pre-assembled host-side
    (trace_compile.stage_segment) and consumed by on-device window slides.
    Columns past the trace's plain segment carry the fresh-slot padding the
    host refill path produces (req 0, service-sentinel duration, no-create
    window), so a stage sliced anywhere near the trace end is still exact.
    `rank` is None when no autoscale statics exist (the pytree structure is
    part of the compiled program's identity, like every other None static).

    The engine keeps at most two stages alive: the one the in-flight
    superspan reads and the double-buffered successor assembled while the
    device runs (engine._prefetch_stage). An engine whose full slide payload
    fits the device budget wraps it as one whole-trace stage (lo = 0) and
    never restages."""

    req_cpu: jnp.ndarray  # (C, L) int32 millicores
    req_ram: jnp.ndarray  # (C, L) int32 ram units
    dur_win: jnp.ndarray  # (C, L) int32 duration pair (win < 0 = service)
    dur_off: jnp.ndarray  # (C, L) float32 duration pair offset
    create_win: jnp.ndarray  # (C, L) int32 create-event window; INT32_MAX = none
    rank: Optional[jnp.ndarray] = None  # (C, L) int32 lexicographic name ranks


# Events a slab block: 4 int32 fields each, so a block is ONE 128-lane row
# (512 aligned bytes), the unit the TPU's gather moves whole.
SLAB_BLOCK_EVENTS = 32
_SLAB_FIELDS = 4  # [win, off-bits, kind, slot]
_SLAB_BLOCK_LANES = _SLAB_FIELDS * SLAB_BLOCK_EVENTS


class TraceSlab(NamedTuple):
    """(C, E) compiled trace events, time-sorted per cluster, padded with
    EV_NONE/time=+inf (win=INF_WIN).

    Stored BLOCKED, and only so: (C, n_blocks, 128) int32, a block the
    [win, off-bits, kind, slot] of SLAB_BLOCK_EVENTS consecutive events
    (lane 4 * e + field: the row-major (E, 4) rows reshaped), after the
    last real event a tail of sentinel rows (win=INF_WIN, EV_NONE) that
    fills its block and one whole block more. A gather on the TPU costs
    per INDEX, some 12 ns each whether the index fetches 16 bytes or 512
    (PERF.md section 6, PR 31): the chunk a cluster's cursor points at
    lies in at most b + 1 neighbouring blocks when it is no longer than b
    blocks (the engine sizes it in whole blocks, one to four:
    engine.event_chunk_size), so `read_chunk` fetches those as whole rows,
    (b + 1) x C indices (2 x C for a chunk of one block, 3 x C for 64
    events), and realigns them in registers, where the point gather it
    replaced paid C x chunk indices of one 16-byte row each. The sentinel
    block makes
    every read total: a cursor at or past the end, and a block index
    clamped to the last block, read events that are never due, so no
    caller compares a cursor with the number of real rows. The slab (the
    one component that still scales with trace length) carries no
    duplicate device memory."""

    packed: jnp.ndarray  # (C, n_blocks, 128) int32, lane = 4 * event + field
    # Chaos payload by crash EVENT (a slot may crash again once its node has
    # recovered onto it): (C, K + 1) float32, entry k the summed sampled
    # repair spans of the cluster's first k EV_NODE_CRASH events in slab
    # order, so metrics.node_downtime_s is one look-up a cluster at the
    # running crash count. None (no leaf) without node faults.
    crash_downtime: Optional[jnp.ndarray] = None

    @staticmethod
    def block_rows(rows) -> np.ndarray:
        """Host (..., E, 4) int32 event rows -> (..., n_blocks, 128) blocks
        with the sentinel tail."""
        rows = np.asarray(rows, np.int32)
        n_blocks = -(-rows.shape[-2] // SLAB_BLOCK_EVENTS) + 1
        out = np.empty(
            rows.shape[:-2] + (n_blocks * SLAB_BLOCK_EVENTS, _SLAB_FIELDS),
            np.int32,
        )
        out[..., : rows.shape[-2], :] = rows
        out[..., rows.shape[-2] :, :] = (INF_WIN, 0, EV_NONE, 0)
        return out.reshape(rows.shape[:-2] + (n_blocks, _SLAB_BLOCK_LANES))

    @staticmethod
    def build(win, off, kind, slot, crash_downtime=None) -> "TraceSlab":
        rows = np.stack(
            [
                np.asarray(win, np.int32),
                np.asarray(off, np.float32).view(np.int32),
                np.asarray(kind, np.int32),
                np.asarray(slot, np.int32),
            ],
            axis=-1,
        )
        return TraceSlab(
            packed=jnp.asarray(TraceSlab.block_rows(rows)),
            crash_downtime=None
            if crash_downtime is None
            else jnp.asarray(crash_downtime, jnp.float32),
        )

    def rows(self) -> jnp.ndarray:
        """The slab as (C, n_blocks * SLAB_BLOCK_EVENTS, 4) event rows,
        sentinel tail included (a reshape: the blocks ARE the rows)."""
        C, n_blocks, _ = self.packed.shape
        return self.packed.reshape(
            C, n_blocks * SLAB_BLOCK_EVENTS, _SLAB_FIELDS
        )

    def _clip_block(self, block):
        return jnp.clip(block, 0, self.packed.shape[1] - 1)

    def win_at(self, cursor, rows=None) -> jnp.ndarray:
        """(C,) window index of the event at each cluster's cursor; INF_WIN
        at and past the end. `rows` (R,) int32 names the clusters whose
        cursors these are where they are not the whole batch in order."""
        if rows is None:
            rows = jnp.arange(self.packed.shape[0], dtype=jnp.int32)
        return self.packed.at[
            rows,
            self._clip_block(cursor // SLAB_BLOCK_EVENTS),
            _SLAB_FIELDS * (cursor % SLAB_BLOCK_EVENTS),
        ].get(mode="promise_in_bounds")

    def read_chunk(self, cursor, chunk: int, rows=None) -> jnp.ndarray:
        """(C, chunk, 4) rows [cursor, cursor + chunk) of each cluster,
        sentinel rows past the end: the blocks that hold them as one row
        gather, then a barrel shift by cursor % SLAB_BLOCK_EVENTS (five
        selects over static slices; no second gather). `rows` as win_at's."""
        C = cursor.shape[0]
        n_read = (chunk + SLAB_BLOCK_EVENTS - 2) // SLAB_BLOCK_EVENTS + 1
        if rows is None:
            rows = jnp.arange(C, dtype=jnp.int32)
        rows = rows[:, None]
        blocks = self._clip_block(
            cursor[:, None] // SLAB_BLOCK_EVENTS
            + jnp.arange(n_read, dtype=jnp.int32)[None, :]
        )
        x = self.packed.at[rows, blocks].get(mode="promise_in_bounds")
        x = x.reshape(C, n_read * _SLAB_BLOCK_LANES)
        shift = cursor % SLAB_BLOCK_EVENTS
        step = SLAB_BLOCK_EVENTS // 2
        while step:
            lanes = _SLAB_FIELDS * step
            x = jnp.where(
                ((shift & step) != 0)[:, None],
                x[:, lanes:],
                x[:, : x.shape[1] - lanes],
            )
            step //= 2
        return x[:, : _SLAB_FIELDS * chunk].reshape(C, chunk, _SLAB_FIELDS)


class StepConstants(NamedTuple):
    """Static per-run scalars derived from SimulationConfig; the control-plane
    hop delays of the scalar path composed into effective offsets
    (reference chains: SURVEY.md §3.2/3.4)."""

    scheduling_interval: float
    time_per_node: float  # scheduler latency model (reference: model.rs 1us)
    delta_pod_enqueue: float  # create -> pod in scheduler queue
    delta_bind_start: float  # assignment (incl. cycle duration) -> pod starts
    delta_reschedule: float  # node removal -> its pods re-enqueued
    flush_interval: float  # 30 s (reference: queue.rs:11)
    max_unschedulable_stay: float  # 300 s (reference: queue.rs:8)
    # Segmented pod layout (sliding window + resident pod-group tail): global
    # pod slots < trace_pod_bound are plain trace pods, mapped to device slots
    # by subtracting the per-cluster pod_base; slots >= trace_pod_bound are
    # resident pod-group ring slots, mapped by subtracting resident_shift.
    # Defaults (bound = huge, shift = 0) make the mapping the identity for
    # full-resident runs. np.int32 so the traced scalars stay 32-bit under
    # jax_enable_x64.
    trace_pod_bound: np.int32 = np.int32(1 << 30)
    resident_shift: np.int32 = np.int32(0)
    # Scenario-vector fleet (batched/fleet.py): per-cluster pod-fault PRNG
    # seeds, (C,) uint32, or None (the default — programs identical to the
    # pre-fleet build; the chaos draw then keys on the jit-static
    # FaultParams.seed plus the cluster index). When set, each lane's
    # draws key on (seed[c], cluster=0, slot, attempt): a lane's fault
    # stream is then a pure function of its SCENARIO, not its lane index,
    # which is what makes lane placement permutation-invariant and lane c
    # bit-identical to a standalone run with that seed. Traced data — a
    # fleet can re-seed lanes between queries without recompiling.
    fault_seed: Optional[jnp.ndarray] = None
    # Lane-asynchronous fleet (engine lane_async=True): per-lane window
    # clocks. A lane's VIRTUAL window for global window W is W -
    # lane_clock[c]; the lane is active while 0 <= W - lane_clock[c] <
    # lane_horizon[c], and the window body freezes (reverts) every state
    # leaf of inactive lanes so a finished lane parks bit-exactly at its
    # final state until the host re-seeds it in place (engine
    # set_lane_plan — traced data, so a reseed never recompiles). None
    # (the default) keeps programs identical to the wave-aligned build.
    lane_clock: Optional[jnp.ndarray] = None  # (C,) int32 global start window
    lane_horizon: Optional[jnp.ndarray] = None  # (C,) int32 windows to run
    # The pending-free channel's two chains (step._apply_window_events_work).
    # A pod's requests leave its node at the node-side finish (or cancel)
    # and reach the scheduler's cache delta_free_visible later: node -> api
    # server -> storage -> scheduler. A removal the storage applied reaches
    # the node delta_free_unbind after the storage's drop: storage -> api
    # server -> node. None where the three delays they are made of are all
    # zero: no free can then be deferred, and the None compiles the window
    # programs of a build without the channel (the structural-static idiom
    # of fault_seed and lane_clock; both None or neither).
    delta_free_visible: Optional[float] = None
    delta_free_unbind: Optional[float] = None


def make_step_constants(config) -> StepConstants:
    """Compose effective delays from the six config delays, mirroring the event
    chains of the scalar path (SURVEY.md §3.2: eleven hops pod lifecycle)."""
    # What a node reports (a finished pod, its own removal) reaches the
    # scheduler through api server and storage.
    node_to_sched = (
        config.as_to_node_network_delay
        + config.as_to_ps_network_delay
        + config.ps_to_sched_network_delay
    )
    channel = node_to_sched > 0
    return StepConstants(
        scheduling_interval=config.scheduling_cycle_interval,
        time_per_node=1e-6,
        delta_pod_enqueue=config.as_to_ps_network_delay
        + config.ps_to_sched_network_delay,
        delta_bind_start=config.sched_to_as_network_delay
        + 2.0 * config.as_to_ps_network_delay
        + config.as_to_node_network_delay,
        # Relative to the (already-shifted) node-removal effect time.
        delta_reschedule=node_to_sched,
        flush_interval=30.0,
        max_unschedulable_stay=300.0,
        delta_free_visible=node_to_sched if channel else None,
        delta_free_unbind=(
            config.as_to_ps_network_delay + config.as_to_node_network_delay
            if channel
            else None
        ),
    )


def duration_pair_np(pod_duration: np.ndarray, interval: float) -> TPair:
    """Host float64 durations -> device TPair; <0 marks a long-running
    service (win = -1 sentinel)."""
    dur = np.asarray(pod_duration, np.float64)
    service = dur < 0
    dwin, doff = from_f64_np(np.where(service, 0.0, dur), interval)
    return TPair(
        win=jnp.asarray(np.where(service, -1, dwin), jnp.int32),
        off=jnp.asarray(np.where(service, 0.0, doff), jnp.float32),
    )


def fresh_pod_arrays(
    C: int,
    P: int,
    req_cpu,
    req_ram,
    duration: TPair,
) -> PodArrays:
    """Pod-slot arrays in their pristine (EMPTY, never-created) state — the
    single source of fresh-slot defaults, shared by init_state and the
    sliding pod window's refill."""
    return PodArrays(
        phase=jnp.zeros((C, P), jnp.int32),
        req_cpu=jnp.asarray(req_cpu, jnp.int32),
        req_ram=jnp.asarray(req_ram, jnp.int32),
        duration=duration,
        queue_ts=t_zeros((C, P)),
        queue_seq=jnp.zeros((C, P), jnp.int32),
        initial_attempt_ts=t_zeros((C, P)),
        attempts=jnp.zeros((C, P), jnp.int32),
        node=jnp.full((C, P), -1, jnp.int32),
        start_time=t_zeros((C, P)),
        finish_time=t_inf((C, P)),
        removal_time=t_inf((C, P)),
        hpa_idx=jnp.full((C, P), -1, jnp.int32),
        restarts=jnp.zeros((C, P), jnp.int32),
        will_fail=jnp.zeros((C, P), bool),
    )


def init_state(
    n_clusters: int,
    n_nodes: int,
    n_pods: int,
    node_cap_cpu: np.ndarray,
    node_cap_ram: np.ndarray,
    pod_req_cpu: np.ndarray,
    pod_req_ram: np.ndarray,
    pod_duration: np.ndarray,
    interval: float,
) -> ClusterBatchState:
    """Build the initial state with pre-staged payloads (all slots start
    EMPTY/dead; trace events bring them to life). pod_duration: float64
    seconds, <0 marks a long-running service."""
    from kubernetriks_tpu.ops.scheduler_kernel import _LANE

    C, N, P = n_clusters, n_nodes, n_pods
    duration = duration_pair_np(pod_duration, interval)
    nodes = NodeArrays(
        alive=jnp.zeros((C, N), bool),
        cap_cpu=jnp.asarray(node_cap_cpu, jnp.int32),
        cap_ram=jnp.asarray(node_cap_ram, jnp.int32),
        alloc_cpu=jnp.asarray(node_cap_cpu, jnp.int32),
        alloc_ram=jnp.asarray(node_cap_ram, jnp.int32),
        create_time=t_inf((C, N)),
        remove_time=t_inf((C, N)),
        crash_downtime=jnp.zeros((C, N), jnp.float32),
    )
    pods = fresh_pod_arrays(C, P, pod_req_cpu, pod_req_ram, duration)
    metrics = MetricArrays(
        pods_succeeded=jnp.zeros((C,), jnp.int32),
        pods_removed=jnp.zeros((C,), jnp.int32),
        terminated_pods=jnp.zeros((C,), jnp.int32),
        processed_nodes=jnp.zeros((C,), jnp.int32),
        scheduling_decisions=jnp.zeros((C,), jnp.int32),
        scaled_up_pods=jnp.zeros((C,), jnp.int32),
        scaled_down_pods=jnp.zeros((C,), jnp.int32),
        scaled_up_nodes=jnp.zeros((C,), jnp.int32),
        scaled_down_nodes=jnp.zeros((C,), jnp.int32),
        hpa_reserve_clamped=jnp.zeros((C,), jnp.int32),
        ca_reserve_starved=jnp.zeros((C,), jnp.int32),
        node_crashes=jnp.zeros((C,), jnp.int32),
        node_recoveries=jnp.zeros((C,), jnp.int32),
        node_downtime_s=jnp.zeros((C,), jnp.float32),
        pod_interruptions=jnp.zeros((C,), jnp.int32),
        pod_restarts=jnp.zeros((C,), jnp.int32),
        pods_failed=jnp.zeros((C,), jnp.int32),
        frees_total=jnp.zeros((C,), jnp.int32),
        frees_deferred=jnp.zeros((C,), jnp.int32),
        event_windows=jnp.zeros((C,), jnp.int32),
        cycle_passes=jnp.zeros((C,), jnp.int32),
        cycle_count=jnp.zeros((C,), jnp.int32),
        cycle_late_decisions=jnp.zeros((C,), jnp.int32),
        cycle_deepest=jnp.zeros((C,), jnp.int32),
        cycle_overruns=jnp.zeros((C,), jnp.int32),
        cycle_deep=jnp.zeros((C,), jnp.int32),
        cycle_compacted=jnp.zeros((C,), jnp.int32),
        resched_rank_windows=jnp.zeros((C,), jnp.int32),
        resched_rank_sorted=jnp.zeros((C,), jnp.int32),
        queue_time=EstArrays.zeros((C,)),
        algo_latency=EstArrays.zeros((C,)),
        pod_duration=EstArrays.zeros((C,)),
        events_deep=jnp.zeros((C,), jnp.int32) if C > _LANE else None,
        events_compacted=jnp.zeros((C,), jnp.int32) if C > _LANE else None,
    )
    return ClusterBatchState(
        time=jnp.zeros((C,), jnp.int32),
        queue_seq_counter=jnp.zeros((C,), jnp.int32),
        event_cursor=jnp.zeros((C,), jnp.int32),
        pod_base=jnp.zeros((C,), jnp.int32),
        last_flush_win=jnp.zeros((C,), jnp.int32),
        requeue_signal=jnp.zeros((C,), bool),
        nodes=nodes,
        pods=pods,
        metrics=metrics,
    )


# --- lane-major hot node state -----------------------------------------------
# The Pallas kernels all consume node-shaped operands TRANSPOSED (clusters on
# the 128-wide lane axis, node slots on sublanes — ops/scheduler_kernel.py's
# one-layout rule), while the XLA glue historically worked row-major (C, N):
# every kernel boundary then materializes a transposed copy (pallas_call pins
# default layouts on operands — measured ~1.2 ms/window of marshalling at the
# composed shape, docs/DESIGN.md window-cost anatomy). Lane-major mode
# (KTPU_LANE_MAJOR / engine lane_major=) carries the HOT node leaves below
# transposed (N, C) across the whole window program: the wrappers skip their
# node-side transposes, the elementwise soup runs layout-agnostic on the
# kernel layout, and conversion happens ONCE per dispatch at the jit entry /
# exit (step.run_windows & friends), not per kernel boundary.
#
# Scope: exactly these NodeArrays leaves. The pending-effect pairs
# (create_time / remove_time) stay row-major — they are written by the CA
# pass's (C, N)-oriented scatters and read a handful of times per window —
# and the pod axis stays row-major everywhere (its sorts / rank builders /
# candidate gathers are row-major-shaped throughout step.py; see ROADMAP).
# At rest (engine.state between dispatches, checkpoints, readout) state is
# ALWAYS row-major; lane-major layout exists only inside compiled programs
# (under a mesh: inside the program's one shard_map, on the shard's (N,
# C_local), so a mesh build takes the mode like any other).
NODE_HOT_LEAVES = (
    "alive",
    "cap_cpu",
    "cap_ram",
    "alloc_cpu",
    "alloc_ram",
    "crash_downtime",
)


@jax.named_scope("bookkeeping")
def swap_node_layout(state: "ClusterBatchState") -> "ClusterBatchState":
    """Transpose the hot node leaves between row-major (C, N) and lane-major
    (N, C). Self-inverse; everything else (pods, metrics, pending-effect
    pairs, auto, telemetry) is untouched. Exact — a transpose moves bits."""
    nodes = state.nodes
    spread = state.spread
    affinity = state.affinity
    return state._replace(
        nodes=nodes._replace(
            **{name: getattr(nodes, name).T for name in NODE_HOT_LEAVES}
        ),
        # The label filters' node planes are read by the same kernels.
        spread=spread if spread is None else spread._replace(domain=spread.domain.T),
        affinity=affinity
        if affinity is None
        else affinity._replace(node_bits=affinity.node_bits.T),
    )


# --- state-leaf & axis registries (ktpu-lint contract-prover passes) ---------
# THE "how to add a state leaf" anchor (DESIGN §7.7): the stateleaf lint
# pass proves these manifests equal the NamedTuple fields exactly, so a
# new leaf that skips the checklist fails at commit time, naming the
# registry it missed. Checklist for a new ClusterBatchState/AutoscaleState
# leaf: (1) it rides the pytree (fleet lane resets, checkpoints,
# compare_states and the sanitizer then cover it automatically — the
# PR 14 reclaim-counter lesson); (2) structural (= None default) leaves
# record their coverage story in engine.CKPT_COVERED_LEAVES; (3)
# allocation-index leaves are documented in DESIGN §12; (4) add the name
# here (and its axis signature below if it is per-cluster-shaped).
CLUSTER_STATE_LEAVES = (
    "time",
    "queue_seq_counter",
    "event_cursor",
    "pod_base",
    "last_flush_win",
    "requeue_signal",
    "nodes",
    "pods",
    "metrics",
    "auto",
    "telemetry",
    "spread",
    "affinity",
)
TELEMETRY_RING_LEAVES = ("buf", "cursor")
SPREAD_STATE_LEAVES = (
    "domain",
    "max_skew",
    "pod_group",
    "pod_bits",
    "pod_zone",
    "decisions",
    "decisions_bound",
)

# StepConstants leaves that are per-lane TRACED scenario data (the
# scenariotrace lint pass forbids them from flowing into Python control
# flow, host casts, jit statics or shape expressions — the fleet's
# compile-once guarantee; `is None` presence checks stay legal). The
# lane-async clock leaves are traced for the same reason: re-seeding a
# finished lane (engine.set_lane_plan) is a data update, never a
# recompile. Host-side mirrors live under different names
# (engine._lane_clock_np / _lane_horizon_np) so host arithmetic never
# reads the traced leaves.
SCENARIO_TRACED_CONSTS = ("fault_seed", "lane_clock", "lane_horizon")

# StepConstants manifest for the stateleaf lint pass: like
# CLUSTER_STATE_LEAVES, a new consts leaf must be added here (and to
# AXIS_SIGNATURES below if per-lane-shaped) or the pass fails naming it —
# the lane-async clock leaves are the template.
STEP_CONSTANTS_LEAVES = (
    "scheduling_interval",
    "time_per_node",
    "delta_pod_enqueue",
    "delta_bind_start",
    "delta_reschedule",
    "flush_interval",
    "max_unschedulable_stay",
    "trace_pod_bound",
    "resident_shift",
    "fault_seed",
    "lane_clock",
    "lane_horizon",
    "delta_free_visible",
    "delta_free_unbind",
)

# Declared axis signatures of state leaves (the shapecontract lint pass):
# "C" = per-cluster lane vector, "C,P"/"C,N" = per-object planes, "C,*" =
# leading-C with an unspecified second axis (PodArrays (C, P) vs
# RefillStage (C, L) share these names), "@node" = the lane-major hot
# node leaves (NODE_HOT_LEAVES below: (C, N) at rest, (N, C) inside
# lane-major programs — mixes with (C,) lane vectors must go through the
# axis-parameterized helpers, never a bare broadcast).
AXIS_SIGNATURES = {
    "time": "C",
    # StepConstants lane-async clock leaves (per-lane vectors)
    "lane_clock": "C",
    "lane_horizon": "C",
    "queue_seq_counter": "C",
    "event_cursor": "C",
    "pod_base": "C",
    "last_flush_win": "C",
    "requeue_signal": "C",
    # PodArrays
    "phase": "C,P",
    "req_cpu": "C,*",
    "req_ram": "C,*",
    "duration": "C,P",
    "queue_ts": "C,P",
    "queue_seq": "C,P",
    "initial_attempt_ts": "C,P",
    "attempts": "C,P",
    "hpa_idx": "C,P",
    "restarts": "C,P",
    "will_fail": "C,P",
    "start_time": "C,P",
    "finish_time": "C,P",
    "removal_time": "C,P",
    # NodeArrays: pending-effect pairs stay row-major by contract; the
    # hot leaves are lane-major-ambiguous inside window programs.
    "create_time": "C,N",
    "remove_time": "C,N",
    "alive": "@node",
    "cap_cpu": "@node",
    "cap_ram": "@node",
    "alloc_cpu": "@node",
    "alloc_ram": "@node",
    "crash_downtime": "@node",
    # MetricArrays per-cluster counters
    "pods_succeeded": "C",
    "pods_removed": "C",
    "terminated_pods": "C",
    "processed_nodes": "C",
    "scheduling_decisions": "C",
    "scaled_up_pods": "C",
    "scaled_down_pods": "C",
    "scaled_up_nodes": "C",
    "scaled_down_nodes": "C",
    "hpa_reserve_clamped": "C",
    "ca_reserve_starved": "C",
    "node_crashes": "C",
    "node_recoveries": "C",
    "node_downtime_s": "C",
    "pod_interruptions": "C",
    "pod_restarts": "C",
    "pods_failed": "C",
    "frees_total": "C",
    "frees_deferred": "C",
    "event_windows": "C",
    "cycle_passes": "C",
    "cycle_count": "C",
    "cycle_late_decisions": "C",
    "cycle_deepest": "C",
    "cycle_overruns": "C",
    "cycle_deep": "C",
    "cycle_compacted": "C",
    "events_deep": "C",
    "events_compacted": "C",
    "soft_attempts": "C",
    "soft_honoured": "C",
    "resched_rank_windows": "C",
    "resched_rank_sorted": "C",
}


@jax.jit
def tree_copy(tree):
    """Fresh device buffers carrying the inputs' shardings (jit outputs
    never alias undonated inputs). The buffer-donation-era state copier:
    a state pytree passed to a donated entry point (step.run_windows_donated
    and friends, engine._fused_chunk_slide) is CONSUMED — callers that must
    keep their state across such a dispatch (warm-up, A/B experiments,
    equivalence tests) dispatch a copy instead."""
    return jax.tree.map(jnp.copy, tree)


def compare_states(a: ClusterBatchState, b: ClusterBatchState) -> list:
    """Compare two final state pytrees under the documented parity policy:
    all simulation state exactly equal; float32 metric estimator accumulators
    to rtol 1e-6 (their masked (C, K) cycle folds are tiled per program by
    XLA, so differently-fused programs — scan vs Pallas, resident vs sliding
    window — can differ by an ulp; see docs/PARITY.md). Returns the keystr
    paths of mismatching leaves (empty list = parity).

    The single comparison predicate shared by the suite's interpret-mode
    Pallas tests and chip_smoke.py's on-hardware check.
    """
    flat_a, tdef_a = jax.tree_util.tree_flatten_with_path(a)
    flat_b, tdef_b = jax.tree_util.tree_flatten_with_path(b)
    if tdef_a != tdef_b:
        # Structurally different states (e.g. autoscaling enabled in only
        # one) must report as a mismatch, not silently zip-truncate.
        return [f"<tree structure: {tdef_a} != {tdef_b}>"]
    bad = []
    for (path, x), (_, y) in zip(flat_a, flat_b):
        key = jax.tree_util.keystr(path)
        xa, ya = np.asarray(x), np.asarray(y)
        if xa.shape != ya.shape:
            ok = False
        elif ".metrics." in key and xa.dtype == np.float32:
            # atol=0: a should-be-zero accumulator must BE zero.
            ok = bool(np.allclose(xa, ya, rtol=1e-6, atol=0.0))
        else:
            ok = bool((xa == ya).all())
        if not ok:
            bad.append(key)
    return bad
