"""The vectorized window step: trace-event application + pod finishes + one
scheduling cycle, over a whole batch of clusters at once.

This replaces the scalar event loop (reference: src/simulator.rs:355-372 pops
one event at a time) with array programs:

- Each control-plane hop of the reference becomes a time-shifted effect
  (SURVEY.md §5.8); the compiler pre-shifts event times to their effect times.
- Pod completions are precomputed finish times invalidated by masks (replacing
  DSLab cancel_event, reference: src/core/node_component.rs:102-104).
- The allocatable is the SCHEDULER's cache: a pod that left its node stays on
  the pending-free channel until the news has crossed the control plane
  (_apply_window_events_work; no channel where the delays are zero).
- Event application is BULK: the window's slab segment is read once per
  cluster as whole blocks (state.TraceSlab.read_chunk), node/pod removal
  times become scatter-min arrays, and the finish-vs-removal interleaving is resolved elementwise per pod by comparing
  finish_time against min(window_end, node_removal_time, pod_removal_time) —
  ordering fidelity without a per-event loop.
- The kube-scheduler cycle drains its queue, max_pods_per_cycle pods a
  pass, and has three equivalent formulations (see
  _run_scheduling_cycle): a sorted queue + lax.scan (the oracle;
  queue order (queue_ts, queue_seq) == the scalar ActiveQueue's (timestamp,
  insertion seq) min-heap; Fit mask + LeastAllocatedResources score +
  last-wins argmax, reference semantics:
  src/core/scheduler/kube_scheduler.rs:63-152, plugin.rs:33-63), the same
  sort feeding a Pallas candidate kernel with a data-dependent early exit,
  and — on dense cluster batches — a fully fused Pallas selection kernel
  with no sort at all (ops/scheduler_kernel.py). Dense batches also route
  the freed-resource, event-application and decision-commit scatters
  through one-hot Pallas kernels (TPU scatter cost is per-index).
- run_windows_skip fast-forwards over provably no-op windows (bit-exact;
  the engine auto-enables it on sparse traces).

Time is the 32-bit (win, off) pair of timerep.py. Each step runs at window
index W (cycle time T = W * interval); all event/effect times applied in the
window are carried as float32 seconds RELATIVE to the previous window's start
((W-1) * interval) — bounded values whose scatter/gather/sort stay on the
TPU's fast 32-bit paths — and are renormalized to pairs only when written
back to persistent state.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from kubernetriks_tpu.batched.autoscale import _rows_at
from kubernetriks_tpu.batched.state import (
    ClusterBatchState,
    EstArrays,
    EV_CREATE_NODE,
    EV_CREATE_POD,
    EV_NODE_CRASH,
    EV_NODE_RECOVER,
    EV_REMOVE_NODE,
    EV_REMOVE_POD,
    PHASE_EMPTY,
    PHASE_FAILED,
    PHASE_QUEUED,
    PHASE_REMOVED,
    PHASE_RUNNING,
    PHASE_SUCCEEDED,
    PHASE_UNSCHEDULABLE,
    NODE_HOT_LEAVES,
    StepConstants,
    TraceSlab,
    alloc_holders,
    held_frees,
    slide_phase,
    swap_node_layout,
)
from kubernetriks_tpu.batched.sharding import (
    all_min,
    cluster_ids,
    over_clusters,
    shard_axis_of,
)
from kubernetriks_tpu.batched.timerep import (
    TPair,
    t_add,
    t_inf,
    t_le,
    t_lt,
    t_norm,
    t_where,
)

INF = jnp.inf


def t_seconds_f32(a: TPair, interval) -> jnp.ndarray:
    """Pair -> float32 seconds (for metric values and bounded spans)."""
    return a.win.astype(jnp.float32) * jnp.float32(interval) + a.off


def lexsort_time_i32(t: TPair, seq: jnp.ndarray) -> jnp.ndarray:
    """Row-wise stable argsort by (time pair, seq) -> int32 indices: the
    batched ActiveQueue ordering ((timestamp, insertion seq) min-heap,
    reference: src/core/scheduler/queue.rs:13-75)."""
    C, P = seq.shape
    iota = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None, :], (C, P))
    _, _, _, order = jax.lax.sort(
        (t.win, t.off, seq, iota), dimension=1, num_keys=3, is_stable=True
    )
    return order


def _est_add_reduced(est: EstArrays, values: jnp.ndarray, mask: jnp.ndarray) -> EstArrays:
    """Fold a (C, P) masked batch of samples into (C,) estimator accumulators."""
    values = values.astype(jnp.float32)
    maskf = mask.astype(jnp.float32)
    return EstArrays(
        count=est.count + mask.sum(axis=1, dtype=jnp.int32),
        total=est.total + (values * maskf).sum(axis=1),
        total_sq=est.total_sq + (values * values * maskf).sum(axis=1),
        minimum=jnp.minimum(est.minimum, jnp.where(mask, values, INF).min(axis=1)),
        maximum=jnp.maximum(est.maximum, jnp.where(mask, values, -INF).max(axis=1)),
    )


def _rel_seconds(t: TPair, base_win: jnp.ndarray, interval) -> jnp.ndarray:
    """Pair -> float32 seconds relative to base_win * interval. Exact (zero
    multiplier) for times inside the base window — the common case for
    this window's events/effects — and correctly ordered for earlier ones."""
    return (t.win - base_win).astype(jnp.float32) * jnp.float32(interval) + t.off


# Slots of the compacted queue rank (_stable_queue_rank). What it costs grows
# with the width (sched1k-faults.montecarlo on one v5e: 5.31 ms a window at 64,
# 6.11 at 128), what it must hold is one cluster's re-queued pods of one
# window: there 1 or 2 (a single node's) or 40 to 50 (a rack of 50 in the band
# of nodes the tie-break keeps occupied, a pod a node), 50 at most in 1,250
# clusters x 120 windows x 3 seeds. 64 holds a rack and a dozen single nodes;
# two such racks in one cluster's window (one cluster-window in 7,400) go to
# the sort, as anything larger does (PERF.md section 6, PR 44).
RANK_COMPACT_SLOTS = 64

# What bringing the deep clusters together and putting their rows back costs
# the scheduling cycle (_lanes_to_move), in steps of a lane tile, a lane tile
# of the batch: read on the chip (PERF.md section 6, PR 45: 0.36 ms a window
# of ten tiles at 2 us a step, 0.9-1.3 ms at 4 us: 16-32).
CYCLE_COMPACT_PAYS = 32


# What finishing the deep clusters' events in a tile of their own costs a
# window (_event_lanes_to_move: the lanes taken, the tile loop's own glue and
# the planes put back), in steps of the event kernel a lane tile of the
# batch: reasoned from the compiled text, not fitted (PERF.md section 6,
# PR 49; no cell's traffic sits near it but autoscaled.stream's).
EVENT_COMPACT_PAYS = 32


def _rank_compacted(keys, mask, n, pos, R: int) -> jnp.ndarray:
    """_stable_queue_rank's ranks where no cluster masks more than R rows
    (n (C,): rows masked; pos (C, P): their running count less one, a masked
    row's slot-order position among them). The keys are brought to (C, R) by
    `pos` (a compare-and-reduce over (C, R, P); a table key is looked up at
    the R compacted indices, (C, L, R)), each compacted row counts the rows
    that sort before it (C, R, R), and the counts return through `pos`
    (autoscale._rows_at). No sort, gather or scatter: paid per element,
    where those pay per slot or per index of the whole pod axis for the
    handful a window re-queues (PERF.md section 6, PR 44)."""
    slot = jnp.arange(R, dtype=jnp.int32)
    # Laid out (C, R, P) and reduced over its last axis. As
    # autoscale._rows_put has it, (C, P, R) reduced over P, the same sums
    # were one fusion of 2.10 ms a window at 1,250 x 2,048 x 128 where these
    # are one of 0.62, and 0.33 at 64 (PERF.md section 6, PR 44).
    hit = jnp.where(mask, pos, -1)[:, None, :] == slot[None, :, None]

    def bring(v):
        bits = v if v.dtype == jnp.int32 else jax.lax.bitcast_convert_type(v, jnp.int32)
        out = jnp.where(hit, bits[:, None, :], 0).sum(axis=2, dtype=jnp.int32)
        return out if v.dtype == jnp.int32 else jax.lax.bitcast_convert_type(out, v.dtype)

    ks = [
        _rows_at(key[0], bring(key[1])) if isinstance(key, tuple) else bring(key)
        for key in keys
    ]
    # before[c, r, s]: compacted row s sorts before compacted row r.
    before = slot[None, None, :] < slot[None, :, None]
    for k in reversed(ks):
        k_r, k_s = k[:, :, None], k[:, None, :]
        before = (k_s < k_r) | ((k_s == k_r) & before)
    live = slot[None, None, :] < n[:, None, None]
    rank = (before & live).sum(axis=2, dtype=jnp.int32)
    return _rows_at(rank, jnp.clip(pos, 0, R - 1))


def _rank_by_sort(keys, mask) -> jnp.ndarray:
    """_stable_queue_rank's ranks whatever the mask holds: a stable sort of
    the whole pod axis with the unmasked rows keyed last, and a second sort
    of its permutation to invert it."""
    C, P = mask.shape
    ks = []
    for key in keys:
        k = _rows_at(*key) if isinstance(key, tuple) else key
        last = INF if k.dtype == jnp.float32 else 1 << 30
        ks.append(jnp.where(mask, k, jnp.asarray(last, k.dtype)))
    iota_pp = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None, :], (C, P))
    out = jax.lax.sort((*ks, iota_pp), dimension=1, num_keys=len(ks), is_stable=True)
    return jax.lax.sort((out[-1], iota_pp), dimension=1, num_keys=1)[1]


def _stable_queue_rank(keys, mask):
    """Dense queue ranks of the `mask` rows ((C, P) bool) under lexicographic
    sort keys, slot order breaking exact key ties: on a masked row, its
    position in a stable sort of the pod axis that puts the masked rows
    first; elsewhere unspecified (callers read it under `mask`). Shared by
    the reschedule and CrashLoopBackOff retry dispositions so the
    scalar-parity ordering rules live in ONE place.

    A key is a (C, P) plane (float32 compared as lax.sort compares it:
    numerically, -0.0 equal to 0.0; a masked row's key is never nan or inf,
    and its int32 keys are under 2**30) or a pair (table (C, L) int32 or a
    thunk of one, idx (C, P) in [0, L)) standing for table[c, idx[c, p]],
    made and looked up only in a window that ranks.

    Three branches, by what the window holds (the predicates reduce over
    the clusters a program holds, like the razor's): no row masked, zeros,
    which nobody reads; every cluster masks at most R =
    min(RANK_COMPACT_SLOTS, P) rows, _rank_compacted; some cluster masks
    more, _rank_by_sort: the same ranks on the masked rows bit for bit, kept
    so that R is a width and never a limit on what a window may re-queue.

    Returns (ranks (C, P) int32, over (C,) bool: the clusters whose rows
    passed R, any of which sends the window to the sort)."""
    R = min(RANK_COMPACT_SLOTS, mask.shape[1])
    n = mask.sum(axis=1, dtype=jnp.int32)
    over = n > R

    def ranked():
        pos = jnp.cumsum(mask, axis=1, dtype=jnp.int32) - 1
        # A thunk's table is made once, for whichever form ranks.
        held = tuple(
            (key[0]() if callable(key[0]) else key[0], key[1])
            if isinstance(key, tuple)
            else key
            for key in keys
        )
        return jax.lax.cond(
            over.any(),
            lambda: _rank_by_sort(held, mask),
            lambda: _rank_compacted(held, mask, n, pos, R),
        )

    # Nothing masked: nobody reads the ranks, and the running count that
    # stood here is not free (a `cumsum` over the pod axis of planes that
    # lie cluster-minor: 0.28 ms a window of cell 1 in one draft).
    return (
        jax.lax.cond(
            (n > 0).any(), ranked, lambda: jnp.zeros(mask.shape, jnp.int32)
        ),
        over,
    )


def _window_work_due(
    state: ClusterBatchState,
    slab: TraceSlab,
    W: jnp.ndarray,
    consts: StepConstants,
) -> jnp.ndarray:
    """(C,) bool: could _apply_window_events_work change ANY state leaf of
    the cluster at window W? Any cluster's is the window-cost razor's
    due-ness predicate (each cluster's own is counted in
    MetricArrays.event_windows) — a handful of
    cheap compares + reductions against the ~35 masked elementwise passes
    of the resolution soup. CONSERVATIVE by construction (true whenever any
    trigger below could fire; running the soup needlessly is always exact):

    - a due trace event (the chunk loop's own entry condition);
    - a pending autoscaler/chaos effect due: CA node create/remove, HPA pod
      removal (win < W exactly, the soup's own due tests minus the ~alive /
      phase refinements — supersets, so never missed);
    - a running pod's finish due by the window end. With none of the other
      triggers firing, every interrupt source is +inf, so the soup's cutoff
      is exactly the window-end pair this predicate compares against;
    - a free on the pending-free channel (its node-side time is behind the
      window end by construction, so the same compare catches it once the
      phase no longer masks it).

    When false, the soup is the identity on everything except
    time = max(time, W) (metric folds add masked zeros, estimator min/max
    merge against +/-inf identities, requeue_signal ors False) — the skip
    branch replicates exactly that. Layout-agnostic: only row-major leaves
    (pending pairs, pod arrays) and the slab are read."""
    C = state.time.shape[0]
    ev_due = slab.win_at(state.event_cursor) < W
    pend_due = (
        (state.nodes.create_time.win < W[:, None]).any(axis=1)
        | (state.nodes.remove_time.win < W[:, None]).any(axis=1)
        | (state.pods.removal_time.win < W[:, None]).any(axis=1)
    )
    P = state.pods.phase.shape[1]
    window_end = TPair(
        win=jnp.broadcast_to(W[:, None], (C, P)),
        off=jnp.zeros((C, P), jnp.float32),
    )
    fin_due = (
        alloc_holders(state.pods, consts)
        & t_le(state.pods.finish_time, window_end)
    ).any(axis=1)
    return ev_due | pend_due | fin_due


@jax.named_scope("events")
def _apply_window_events(
    state: ClusterBatchState,
    slab: TraceSlab,
    W: jnp.ndarray,
    consts: StepConstants,
    max_events_per_window: int,
    conditional_move: bool = False,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    use_pallas_select: bool = False,
    node_name_rank=None,
    pod_name_rank=None,
    fault_params=None,
    lane_major: bool = False,
    window_razor: bool = True,
    node_key_fn=None,
):
    """Event application + finish resolution, behind the window-cost razor
    (KTPU_WINDOW_RAZOR): when the due-ness predicate proves the window has
    no resolution work, the whole soup is skipped via lax.cond — empty and
    near-empty windows in dense traces stop paying the ~35 masked
    elementwise passes (fast-forward only helps when WHOLE spans are empty;
    this gates per window inside dense spans). Bit-exact: the skip branch
    fires only when the soup is provably the identity (see
    _window_work_due). window_razor=False keeps the always-run path for
    A/B measurement.

    A batch of more than one lane tile has a third branch: the soup with the
    clusters that have a burst of slab events due finished in a lane tile of
    their own (_apply_window_events_work's `moved`), taken in the windows in
    which some cluster moves (_event_lanes_to_move). It is the razor's `cond`
    made a `switch`, so a window in which nobody moves runs the single
    loop's program and pays the look at the slab and nothing else: a `cond`
    of its own round the loop cost every window some 25 small operations,
    its operands' copies among them (PERF.md section 6, PR 49)."""
    from kubernetriks_tpu.ops.scheduler_kernel import _LANE

    args = (
        consts,
        max_events_per_window,
        conditional_move,
        use_pallas,
        pallas_interpret,
        use_pallas_select,
        node_name_rank,
        pod_name_rank,
        fault_params,
        lane_major,
        node_key_fn,
    )
    due = _window_work_due(state, slab, W, consts)
    moved = None
    if state.time.shape[0] > _LANE:
        moved = _event_lanes_to_move(
            slab, state.event_cursor, W, max_events_per_window, _LANE
        )

    def run(st, moved=None):
        """The soup, and the window counted for the clusters it was due in
        (a function of the state alone, so the razor's on and off builds
        and a sharded build count alike)."""
        st, wake, chunks = _apply_window_events_work(st, slab, W, *args, moved=moved)
        metrics = st.metrics._replace(
            event_windows=st.metrics.event_windows + due.astype(jnp.int32)
        )
        return st._replace(metrics=metrics), wake, chunks

    tiled = partial(run, moved=moved)
    if not window_razor:
        if moved is not None:
            return jax.lax.cond(moved.any(), tiled, run, state)
        return run(state)

    def skip(st):
        # No chunk read: the ring's event_chunks column (ring on) records 0.
        chunks = (
            jnp.zeros_like(W) if st.telemetry is not None else None
        )
        if conditional_move:
            C, P = st.pods.phase.shape
            N = (
                st.nodes.cap_cpu.shape[0]
                if lane_major
                else st.nodes.cap_cpu.shape[1]
            )
            f32inf = jnp.float32(INF)
            wake = WakeEvents(
                node_mask=jnp.zeros((C, N), bool),
                node_rel=jnp.full((C, N), f32inf, jnp.float32),
                freed_mask=jnp.zeros((C, P), bool),
                freed_rel=jnp.full((C, P), f32inf, jnp.float32),
            )
        else:
            wake = None
        return st._replace(time=jnp.maximum(st.time, W)), wake, chunks

    if moved is not None:
        # A cluster that moves has events due: the last branch implies the second's test.
        branch = jnp.where(moved.any(), 2, due.any().astype(jnp.int32))
        return jax.lax.switch(branch, (skip, run, tiled), state)
    return jax.lax.cond(due.any(), run, skip, state)


def event_path(
    use_pallas: bool,
    use_pallas_select: bool,
    n_nodes: int,
    n_pods: int,
    chunk: int,
    node_faults: bool,
) -> str:
    """How the event chunk loop applies a chunk of `chunk` due events to
    (n_nodes, n_pods) slots: "kernel" (ops.scheduler_kernel.fused_event_scatter,
    where the kernels are on, the cluster lanes dense and the blocks fit
    VMEM) or "scatter" (XLA scatters). The ONE owner of the gate:
    _apply_window_events_work and engine.kernel_formulation() both ask."""
    from kubernetriks_tpu.ops.scheduler_kernel import event_kernel_fits

    fits = event_kernel_fits(n_nodes, n_pods, chunk, node_faults)
    return "kernel" if use_pallas and use_pallas_select and fits else "scatter"


class _EventLanes(NamedTuple):
    """The clusters one event chunk loop runs over
    (_apply_window_events_work), (L,) each: the whole batch in order, or a
    lane tile of its clusters."""

    W: jnp.ndarray  # the window; _NOTHING_DUE in a slot that holds no cluster
    base: jnp.ndarray  # the window the applied events fall in
    rows: jnp.ndarray  # (L, 1) a lane's position in the loop's planes
    pod_base: jnp.ndarray
    queue_seq_counter: jnp.ndarray
    slab_rows: Optional[jnp.ndarray] = None  # the clusters, where not all in order


# A window no slab event lies before (TraceSlab.win_at(...) < W is never true).
_NOTHING_DUE = np.int32(np.iinfo(np.int32).min)


def _event_lanes_to_move(slab: TraceSlab, cursor, W, E: int, R: int):
    """The clusters whose due slab events finish in a lane tile of their own
    (_apply_window_events_work), (C,) bool. The loop over the batch runs as
    many passes of E events as its deepest cluster needs, every pass the
    event kernel over all the batch's tiles and the slab read, the masks and
    the pads over all its clusters; a cluster moved to the tile takes its
    passes there, over one tile of R lanes. That pays where it saves the
    batch more kernel steps than the move costs (EVENT_COMPACT_PAYS a tile
    of the batch): the events a cluster has due past its first pass are
    steps of every pass after it in all the other tiles, so a cluster moves
    if it has more than E + EVENT_COMPACT_PAYS * tiles / (tiles - 1) due,
    which one look at the slab that far past its cursor says (due events are
    a sorted prefix). A job's thousand creations pay many times over, alone
    too; a rack's crashes on top of a window's arrivals, a few past the
    chunk, do not. And nobody moves if more clusters have such a burst due
    than a tile holds (a node burst in every cluster at t = 0): a width,
    never a limit."""
    tiles = -(-cursor.shape[0] // R)
    depth = E + -(-EVENT_COMPACT_PAYS * tiles // (tiles - 1))
    bursts = slab.win_at(cursor + depth) < W
    return bursts & (bursts.sum(dtype=jnp.int32) <= R)


def _apply_window_events_work(
    state: ClusterBatchState,
    slab: TraceSlab,
    W: jnp.ndarray,
    consts: StepConstants,
    max_events_per_window: int,
    conditional_move: bool = False,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    use_pallas_select: bool = False,
    node_name_rank=None,
    pod_name_rank=None,
    fault_params=None,
    lane_major: bool = False,
    node_key_fn=None,
    moved=None,
) -> ClusterBatchState:
    """Apply every trace event with effect time STRICTLY before the cycle time
    W * interval, and resolve all pod finishes due in the window.

    moved (_event_lanes_to_move; None: every pass in the one loop over the
    batch): the clusters, at most a lane tile's worth, that take their
    passes of the chunk loop in one tile of their own before the batch's.

    lane_major (KTPU_LANE_MAJOR): the hot node leaves
    (state.NODE_HOT_LEAVES) and every node-shaped accumulator in this
    function are carried TRANSPOSED (N, C) — the Pallas kernels' layout —
    so the event/free kernel boundaries stop materializing transposed
    copies. Pod arrays, the pending-effect pairs and WakeEvents keep the
    row-major convention (their producers/consumers are row-major-shaped
    sorts/gathers); the handful of row-major pending-effect masks that
    merge into lane-major accumulators transpose exactly once below.
    The one exception is inside the event chunk loop: where the build takes
    the event kernel, all five accumulators of the loop, the three pod
    planes among them, are carried in that kernel's padded lane-major
    layout (scheduler_kernel.event_accumulators) whatever lane_major says,
    and leave it once after the loop; the plain scatter path carries them
    in the conventions above.

    fault_params (chaos.FaultParams, static): with node_faults, the slab may
    carry EV_NODE_CRASH (remove semantics + crash/downtime accounting; a
    removal accumulator of its own keeps crash attribution for the
    interruption counter, a sixth plane of the event kernel or a separate
    scatter) and EV_NODE_RECOVER (create semantics on the node's own slot +
    recovery count); what they add to the window body runs under the named
    scope `node_faults`;
    with pod_faults, running pods whose will_fail flag is set FAIL at their
    finish_time instead of succeeding — retry via CrashLoopBackOff requeue
    or terminate as PHASE_FAILED past the restart limit.

    Strictness: an effect landing exactly at cycle time T is processed after
    the cycle in the scalar kernel (older-event-id-first FIFO), so it belongs
    to the next window. With pair times that check is exact: effect applied
    iff its window index < W.

    Dtype note (applies to this whole module): jax_enable_x64 is on (see
    state.py), so every index/count op must pin an explicit 32-bit dtype —
    untyped arange/argmax/bool-sum default to i64 under x64, and stray i64
    lanes measurably slow the TPU hot loop (emulated 64-bit).
    """
    pods, nodes, metrics = state.pods, state.nodes, state.metrics
    C, P = pods.phase.shape
    N = nodes.alive.shape[0] if lane_major else nodes.alive.shape[1]
    # Node-shaped accumulators follow the hot leaves' layout: (N, C) lane
    # major, (C, N) row major. n_sum_ax reduces them to (C,).
    n_shape = (N, C) if lane_major else (C, N)
    n_sum_ax = 0 if lane_major else 1
    E = max_events_per_window
    interval = jnp.float32(consts.scheduling_interval)
    rows = jnp.arange(C, dtype=jnp.int32)[:, None]
    base = W - 1  # (C,) the window the applied events fall in
    f32inf = jnp.float32(INF)

    from kubernetriks_tpu.ops.scheduler_kernel import (
        _LANE,
        event_accumulators,
        event_accumulators_unpack,
        fused_event_scatter,
    )

    node_faults = fault_params is not None and fault_params.node_faults
    pod_faults = fault_params is not None and fault_params.fail_prob > 0
    # Device ring on (a structural static): one more loop carry counts the
    # chunks each cluster needed; their maximum is the loop's iteration
    # count (TELEM_EVENT_CHUNKS). Ring off: no leaf, the same program.
    count_chunks = state.telemetry is not None

    # The one-hot scatter kernels sweep whole (P, 128-lane) tiles per event,
    # so like the selection kernel they only pay when the cluster lanes are
    # dense — use_pallas_select carries exactly that gate (measured: the
    # C=1 replay regressed 229 s -> 350 s with them always-on). The plain
    # scatter path is the bit-identical fallback.
    use_event_kernel = event_path(
        use_pallas, use_pallas_select, N, P, E, node_faults
    ) == "kernel"
    if use_event_kernel:
        event_core = partial(fused_event_scatter, interpret=pallas_interpret)
    # What node faults add runs under a scope of its own (nested in `events`).
    faults_scope = partial(jax.named_scope, "node_faults")

    # --- bulk-apply the window's slab events, E at a time -------------------
    # E is a CHUNK size, not a worst-case bound: chunks apply inside a
    # while_loop until no cluster has a due event left. A trace with a burst
    # window (e.g. 1000 CreateNodes at t=0) takes a few extra iterations in
    # that one window instead of taxing every window with a burst-sized
    # gather/scatter. Due events are a sorted prefix of the slab, so a chunk
    # boundary never skips one.
    # It runs over `lanes` (_EventLanes): the whole batch, or a lane tile of
    # its clusters (`moved`, below).
    def chunk_cond(carry, lanes):
        return jnp.any(slab.win_at(carry[0], lanes.slab_rows) < lanes.W)

    def chunk_body(carry, lanes):
        W, base, rows, slab_rows = lanes.W, lanes.base, lanes.rows, lanes.slab_rows
        (cursor, created, node_removal, pod_create, pod_create_seq,
         pod_removal, n_creates) = carry[:7]
        tail = 7
        if conditional_move:
            node_create_rel = carry[tail]
            tail += 1
        if node_faults:
            crash_rm, n_recover = carry[tail], carry[tail + 1]
        # The E entries that follow each cursor, as whole 128-lane blocks:
        # (E / 32 + 1) x C gather indices, not C x E (gather cost is per
        # index on the TPU; TraceSlab). Past the end the slab reads as
        # sentinel events (win=INF_WIN), which are never due.
        pk = slab.read_chunk(cursor, E, slab_rows)  # (C, E, 4) int32
        ev_win = pk[..., 0]
        ev_off = jax.lax.bitcast_convert_type(pk[..., 1], jnp.float32)
        ev_k = pk[..., 2]
        ev_s_raw = pk[..., 3]
        valid = ev_win < W[:, None]
        # Pod event slots are GLOBAL; the device pod arrays are segmented into
        # a sliding window over plain trace pods (global slot <
        # consts.trace_pod_bound, device slot = global - pod_base) and a
        # RESIDENT tail of pod-group ring slots (device slot = global -
        # consts.resident_shift; pod groups are long-running services, which
        # would block the window's terminal-prefix shift forever). Both
        # subtractions are the identity on full-resident runs. Out-of-window
        # slots (already-shifted-out, necessarily terminal pods — e.g. a
        # RemovePod after its pod finished and scrolled away) drop at the
        # scatters.
        is_pod_ev = (ev_k == EV_CREATE_POD) | (ev_k == EV_REMOVE_POD)
        seg_shift = jnp.where(
            ev_s_raw < consts.trace_pod_bound,
            lanes.pod_base[:, None],
            consts.resident_shift,
        )
        ev_s = jnp.where(is_pod_ev, ev_s_raw - seg_shift, ev_s_raw)
        ev_s = jnp.where(is_pod_ev & (ev_s < 0), jnp.int32(1 << 29), ev_s)
        # Event time in f32 seconds relative to base (== ev_off when the
        # event is in this window, which consecutive stepping guarantees).
        ev_rel = (ev_win - base[:, None]).astype(jnp.float32) * interval + ev_off

        is_cn = valid & (ev_k == EV_CREATE_NODE)
        is_rn = valid & (ev_k == EV_REMOVE_NODE)
        is_cp = valid & (ev_k == EV_CREATE_POD)
        is_rp = valid & (ev_k == EV_REMOVE_POD)
        if node_faults:
            # Recoveries ARE creations (the node's slot, fresh capacity) —
            # fold into is_cn so every create-side effect (alive/alloc, wake
            # events, pending-create interplay) applies identically; crashes
            # go to their own removal accumulator so crash attribution
            # survives for the interruption metric, and merge into
            # node_removal after the loop.
            with faults_scope():
                is_crash = valid & (ev_k == EV_NODE_CRASH)
                is_recover = valid & (ev_k == EV_NODE_RECOVER)
                is_cn = is_cn | is_recover
        # Queue sequence numbers follow slab (== emission) order, continuing
        # across chunks via the running n_creates.
        create_rank = jnp.cumsum(is_cp, axis=1, dtype=jnp.int32) - 1
        ev_seq = lanes.queue_seq_counter[:, None] + n_creates[:, None] + create_rank

        if use_event_kernel:
            # One Pallas call replaces the five (C, E)-indexed scatters
            # below (~5 ms/window at dense shapes; scatter cost is
            # per-index on TPU). The five accumulators ride the loop in
            # the kernel's padded lane-major layout (carry0): a pass pays
            # no pad, transpose or slice for them.
            # Under node faults the kernel knows the two chaos kinds and
            # carries the crash accumulator as a sixth plane.
            acc = event_core(
                ev_k, ev_s, ev_rel, ev_seq, valid,
                created, node_removal, pod_create, pod_create_seq,
                pod_removal, crash_removal=crash_rm if node_faults else None,
            )
            created, node_removal, pod_create, pod_create_seq, pod_removal = acc[:5]
            if node_faults:
                crash_rm = acc[5]
        else:
            # Scatter helpers: out-of-range slot drops the write. Node
            # accumulators are lane-major under lane_major — the scatter
            # indices swap axes ((slot, cluster) pairs), same index count.
            def drop_slot(mask, width):
                return jnp.where(mask, ev_s, width)

            def n_scatter(acc, mask, op, values=None):
                idx = (
                    (drop_slot(mask, N), rows)
                    if lane_major
                    else (rows, drop_slot(mask, N))
                )
                ref = acc.at[idx[0], idx[1]]
                if values is None:
                    return ref.set(True, mode="drop")
                return getattr(ref, op)(values, mode="drop")

            created = n_scatter(created, is_cn, "set")
            node_removal = n_scatter(
                node_removal, is_rn, "min",
                jnp.where(is_rn, ev_rel, f32inf),
            )
            pod_create = pod_create.at[rows, drop_slot(is_cp, P)].min(
                jnp.where(is_cp, ev_rel, f32inf), mode="drop"
            )
            pod_create_seq = pod_create_seq.at[rows, drop_slot(is_cp, P)].max(
                jnp.where(is_cp, ev_seq, 0), mode="drop"
            )
            pod_removal = pod_removal.at[rows, drop_slot(is_rp, P)].min(
                jnp.where(is_rp, ev_rel, f32inf), mode="drop"
            )
        out = (
            cursor + valid.sum(axis=1, dtype=jnp.int32),
            created,
            node_removal,
            pod_create,
            pod_create_seq,
            pod_removal,
            n_creates + is_cp.sum(axis=1, dtype=jnp.int32),
        )
        if conditional_move:
            # Node-add times feed the per-event wake scans (scalar
            # on_add_node_to_cache runs once PER node at its visibility
            # time; _conditional_wake_exact). Only built on the
            # conditional-move path — an extra (C, N) scatter otherwise.
            node_create_rel = n_scatter_min(
                node_create_rel, is_cn, rows, ev_s,
                jnp.where(is_cn, ev_rel, f32inf),
            )
            out = out + (node_create_rel,)
        if node_faults:
            with faults_scope():
                if not use_event_kernel:
                    crash_rm = n_scatter_min(
                        crash_rm, is_crash, rows, ev_s,
                        jnp.where(is_crash, ev_rel, f32inf),
                    )
                out = out + (
                    crash_rm,
                    n_recover + is_recover.sum(axis=1, dtype=jnp.int32),
                )
        if count_chunks:
            # A cluster needed this chunk iff its first entry was due.
            out = out + (carry[-1] + valid[:, 0].astype(jnp.int32),)
        return out

    def n_scatter_min(acc, mask, rows, ev_s, values):
        tgt = jnp.where(mask, ev_s, N)
        if lane_major:
            return acc.at[tgt, rows].min(values, mode="drop")
        return acc.at[rows, tgt].min(values, mode="drop")

    def carry_at(cursor):
        """The loop's carry before the first pass over the L clusters whose
        cursors these are."""
        L = cursor.shape[0]
        nodes_shape = (N, L) if lane_major else (L, N)
        if use_event_kernel:
            accumulators0 = event_accumulators(L, N, P)
        else:
            accumulators0 = (
                jnp.zeros(nodes_shape, bool),
                jnp.full(nodes_shape, INF, jnp.float32),
                jnp.full((L, P), INF, jnp.float32),
                jnp.zeros((L, P), jnp.int32),
                jnp.full((L, P), INF, jnp.float32),
            )
        carry0 = (cursor,) + accumulators0 + (jnp.zeros((L,), jnp.int32),)
        if conditional_move:
            carry0 = carry0 + (jnp.full(nodes_shape, INF, jnp.float32),)
        if node_faults:
            carry0 = carry0 + (
                # the crash accumulator starts as node_removal does, in its layout
                accumulators0[1],
                jnp.zeros((L,), jnp.int32),
            )
        if count_chunks:
            carry0 = carry0 + (jnp.zeros((L,), jnp.int32),)
        return carry0

    def chunk_loop(lanes, carry0):
        return jax.lax.while_loop(
            partial(chunk_cond, lanes=lanes), partial(chunk_body, lanes=lanes), carry0
        )

    lanes = _EventLanes(W, base, rows, state.pod_base, state.queue_seq_counter)
    carry0 = carry_at(state.event_cursor)
    if moved is not None:
        # The clusters with a burst due finish in ONE lane tile of their own
        # (the event kernel's; the scatters take the same width, so that
        # both builds hold one state), before the batch's loop and not in
        # it, where every further pass would run all the batch's tiles, and
        # all its glue, for them (a grid program loops to its tile's largest
        # count, and the loop to the batch's). The tile's accumulators start
        # empty and are put into the batch's, empty too: a cluster's lanes
        # never read another's, and the batch's loop, handed the tile's
        # cursors, finds nothing due in those clusters (every merge is a
        # min, a max or an or, and these lanes see one side of it), so the
        # carry is bit for bit the single loop's (tests/test_event_compact.py).
        slot, index = _tile_slots(moved, _LANE)
        tile = _EventLanes(
            jnp.where(index < C, _take_lanes(W, index), _NOTHING_DUE),
            _take_lanes(base, index),
            jnp.arange(_LANE, dtype=jnp.int32)[:, None],
            _take_lanes(state.pod_base, index),
            _take_lanes(state.queue_seq_counter, index),
            slab_rows=jnp.minimum(index, C - 1),
        )
        done = chunk_loop(tile, carry_at(_take_lanes(state.event_cursor, index)))

        def put(full, part):
            # The cluster axis is the one along which the tile is narrower:
            # last in the kernel's padded layout, first in a row-major plane.
            axis = [f == p for f, p in zip(full.shape, part.shape)].index(False)
            if axis != full.ndim - 1:
                return put(full.T, part.T).T
            pad = (0, full.shape[-1] - C)
            return _put_lanes(full, part, jnp.pad(slot, pad), jnp.pad(moved, pad))

        carry0 = tuple(put(full, part) for full, part in zip(carry0, done))
    carry_out = chunk_loop(lanes, carry0)
    event_chunks = carry_out[-1] if count_chunks else None
    (event_cursor, created, node_removal, pod_create, pod_create_seq,
     pod_removal, n_creates) = carry_out[:7]
    tail = 7
    node_create_rel = None
    if conditional_move:
        node_create_rel = carry_out[tail]
        tail += 1
    if node_faults:
        crash_rm, n_recover = carry_out[tail], carry_out[tail + 1]
    if metrics.events_deep is not None:
        # The windows in which the cluster had more events due than a pass
        # applies, and those of them it finished in the tile.
        deep = event_cursor - state.event_cursor > E
        metrics = metrics._replace(
            events_deep=metrics.events_deep + deep.astype(jnp.int32),
            events_compacted=metrics.events_compacted
            if moved is None
            else metrics.events_compacted + moved.astype(jnp.int32),
        )
    if use_event_kernel:
        # Out of the kernel's layout once a window, where the row-major
        # consumers start.
        acc = event_accumulators_unpack(
            (created, node_removal, pod_create, pod_create_seq, pod_removal)
            + ((crash_rm,) if node_faults else ()),
            C, N, P, lane_major,
        )
        created, node_removal, pod_create, pod_create_seq, pod_removal = acc[:5]
        if node_faults:
            crash_rm = acc[5]
    if node_faults:
        with faults_scope():
            crashed_now = crash_rm < f32inf
            node_crashes = metrics.node_crashes + crashed_now.sum(
                axis=n_sum_ax, dtype=jnp.int32
            )
            metrics = metrics._replace(
                node_crashes=node_crashes,
                node_recoveries=metrics.node_recoveries + n_recover,
                # Downtime = the summed pre-sampled repair spans of the
                # crashes applied so far: the slab's table by crash event
                # at the running count (a slot crashes again once its node
                # has recovered onto it, so no per-slot plane can hold the
                # spans). Crashes apply in slab order, a slot at most once
                # a window, so the count IS the event ordinal.
                node_downtime_s=slab.crash_downtime[
                    rows[:, 0],
                    jnp.minimum(node_crashes, slab.crash_downtime.shape[1] - 1),
                ],
            )
            node_removal = jnp.minimum(node_removal, crash_rm)

    def to_nmaj(x):
        """Row-major (C, N) mask/value -> the node accumulators' layout."""
        return x.T if lane_major else x

    # Pending autoscaler creations due this window (CA scale-up effects).
    # The pending pairs stay row-major (see state.NODE_HOT_LEAVES): their
    # masks/values compute row-major — where the t_where writebacks need
    # them — and transpose once to merge with the lane-major accumulators.
    alive_row = nodes.alive.T if lane_major else nodes.alive
    pend_create_row = (nodes.create_time.win < W[:, None]) & ~alive_row
    created = created | to_nmaj(pend_create_row)
    if conditional_move:
        node_create_rel = jnp.minimum(
            node_create_rel,
            to_nmaj(
                jnp.where(
                    pend_create_row,
                    _rel_seconds(nodes.create_time, base[:, None], interval),
                    f32inf,
                )
            ),
        )
    node_create_time = t_where(
        pend_create_row, t_inf((C, N)), nodes.create_time
    )
    # Pending autoscaler removals due this window (CA scale-down effects).
    pend_rm_due = nodes.remove_time.win < W[:, None]
    pend_remove = jnp.where(
        pend_rm_due, _rel_seconds(nodes.remove_time, base[:, None], interval), f32inf
    )
    node_removal = jnp.minimum(node_removal, to_nmaj(pend_remove))
    node_remove_time = t_where(pend_rm_due, t_inf((C, N)), nodes.remove_time)
    # Pending HPA scale-down removals due this window.
    pend_prm_due = pods.removal_time.win < W[:, None]
    pend_pod_removal = jnp.where(
        pend_prm_due, _rel_seconds(pods.removal_time, base[:, None], interval), f32inf
    )
    pod_removal = jnp.minimum(pod_removal, pend_pod_removal)
    pod_removal_time = t_where(pend_prm_due, t_inf((C, P)), pods.removal_time)

    # --- apply creations ----------------------------------------------------
    alive = nodes.alive | created
    alloc_cpu = jnp.where(created, nodes.cap_cpu, nodes.alloc_cpu)
    alloc_ram = jnp.where(created, nodes.cap_ram, nodes.alloc_ram)

    was_empty_created = (pods.phase == 0) & (pod_create < f32inf)
    enqueue_ts = t_norm(
        jnp.broadcast_to(base[:, None], (C, P)),
        jnp.where(was_empty_created, pod_create, 0.0)
        + jnp.float32(consts.delta_pod_enqueue),
        interval,
    )
    phase = jnp.where(was_empty_created, PHASE_QUEUED, pods.phase)
    queue_ts = t_where(was_empty_created, enqueue_ts, pods.queue_ts)
    queue_seq = jnp.where(was_empty_created, pod_create_seq, pods.queue_seq)
    initial_attempt_ts = t_where(
        was_empty_created, enqueue_ts, pods.initial_attempt_ts
    )
    attempts = jnp.where(was_empty_created, 1, pods.attempts)

    # --- resolve running pods: finish vs node removal vs pod removal --------
    running = phase == PHASE_RUNNING
    node_idx = jnp.clip(pods.node, 0, None)

    def n_gather(acc):
        """(C, P) per-pod gather from a node-layout accumulator: result
        [c, p] = acc[node_idx[c, p]] of cluster c — index pairs swap axes
        under lane-major, same index count."""
        if lane_major:
            return acc[node_idx, rows]
        return acc[rows, node_idx]

    # The per-pod node-removal gather is a (C, P)-indexed op — one of the two
    # most expensive ops in the step — and most windows remove no node at
    # all; branch around it (the predicate reduction is replicated, so the
    # cond also holds under a C-sharded mesh).
    if node_faults:
        # Under node faults some cluster of the batch loses a node in nearly
        # every window, so the branch below is taken nearly always and a
        # (C, P) gather costs per INDEX (8.3 ns: 21 ms a window at 1250 x
        # 2048, three of them 64 of the window's 78 ms; PERF.md section 6,
        # PR 43). One dense look-up instead (autoscale._rows_at: a fused
        # compare-and-reduce over the node axis, paid per element) brings
        # each pod its node's removal time AND, in the sign bit the time
        # never uses, whether that removal was the crash's (ties attribute
        # to the crash, matching the scalar chain where the crash IS the
        # removal): crash_rm <= the merged removal time.
        def node_rows(x):
            """A node-layout plane as (C, N) rows."""
            return x.T if lane_major else x

        def removal_and_crash():
            bits = jax.lax.bitcast_convert_type(node_removal, jnp.int32)
            bits = bits | jnp.where(
                crash_rm <= node_removal, jnp.int32(-(2**31)), jnp.int32(0)
            )
            return _rows_at(node_rows(bits), node_idx)

        with faults_scope():
            removal_bits = jax.lax.cond(
                (node_removal < f32inf).any(),
                removal_and_crash,
                lambda: jax.lax.bitcast_convert_type(
                    jnp.full((C, P), INF, jnp.float32), jnp.int32
                ),
            )
            pod_node_removal = jnp.where(
                pods.node >= 0,
                jax.lax.bitcast_convert_type(
                    removal_bits & jnp.int32(2**31 - 1), jnp.float32
                ),
                f32inf,
            )
            pod_by_crash = (pods.node >= 0) & (removal_bits < 0)
    else:
        pod_node_removal = jax.lax.cond(
            (node_removal < f32inf).any(),
            lambda: jnp.where(pods.node >= 0, n_gather(node_removal), f32inf),
            lambda: jnp.full((C, P), INF, jnp.float32),
        )
    # Earliest interruption of this pod in rel-seconds; +inf = none.
    interrupt = jnp.minimum(pod_node_removal, pod_removal)
    has_interrupt = interrupt < f32inf
    # cutoff = min(window_end, interruption): window_end is the pair (W, 0),
    # an interruption the pair (base, interrupt); compare the pod's finish
    # pair against whichever applies.
    cut = t_norm(
        jnp.where(has_interrupt, base[:, None], W[:, None]),
        jnp.where(has_interrupt, interrupt, 0.0),
        interval,
    )
    finishes = running & t_le(pods.finish_time, cut)
    interrupted = running & ~finishes & has_interrupt
    rescheds = interrupted & (pod_node_removal < pod_removal)
    removed_running = interrupted & (pod_removal <= pod_node_removal)

    # Chaos: split completions into real finishes and failing attempts
    # (will_fail drawn at commit; finish_time IS the fail time). Both free
    # their resources through the shared `freed` path below; only real
    # finishes count succeeded/duration stats.
    if pod_faults:
        fails = finishes & pods.will_fail
        real_fin = finishes & ~pods.will_fail
    else:
        fails = None
        real_fin = finishes

    if node_faults:
        # Crash-caused reschedules (the interruption metric): the pod's
        # earliest node removal came from a crash (the look-up above).
        with faults_scope():
            crash_caused = rescheds & pod_by_crash
            metrics = metrics._replace(
                pod_interruptions=metrics.pod_interruptions
                + crash_caused.sum(axis=1, dtype=jnp.int32)
            )

    # Free resources of finished and removed-while-running pods (a dead node's
    # allocatable is irrelevant; slots are never reused). A straight
    # (C, P)-indexed scatter is the single most expensive op in the step
    # (measured 27 ms/window at 1024x256), and only a handful of pods free
    # per window. Preferred: the Pallas free kernel (per-lane iterated
    # extraction + node one-hot adds, early exit at the deepest lane's freed
    # count — integer adds commute, so it is bit-identical). Fallback:
    # compact up to F freed pods per round with top_k and scatter F-sized
    # chunks — correct everywhere, but each round's lax.top_k lowers to a
    # full (C, P) sort on TPU (~4 ms/window at dense shapes).
    freed = finishes | removed_running
    # The pending-free channel. `freed` is the NODE's side: the pods that
    # left their nodes this window. The allocatable below is the
    # SCHEDULER's cache, which hears of a free one chain later (scalar:
    # node -> api server -> storage -> scheduler.on_pod_finished_running /
    # on_remove_pod_from_cache, where the requests return and the
    # unschedulable queue wakes). So a free joins the channel at its
    # node-side time (pods.finish_time stays finite, state.held_frees) and
    # leaves it in the window whose cycle is the first to see it: visible
    # iff finish_time + chain lies in a window before W. Strictly: the
    # cycle's own event was emitted a whole interval ago, the
    # notification's by the storage a hop ago, and the scalar queue runs
    # events of one instant in emission order, so a free that arrives AT
    # the cycle instant is the next cycle's. A removal's node-side time is
    # the node's cancel, delta_free_unbind after the storage's drop that
    # `pod_removal` is. Node-side effects (phases, counters, the duration
    # estimator, finish against removal) keep the node's time, as before.
    channel = consts.delta_free_visible is not None
    if channel:
        held = held_frees(pods)
        free_t = t_where(
            removed_running,
            t_norm(
                jnp.broadcast_to(base[:, None], (C, P)),
                jnp.where(removed_running, pod_removal, 0.0)
                + jnp.float32(consts.delta_free_unbind),
                interval,
            ),
            pods.finish_time,
        )
        free_vis = t_norm(
            free_t.win,
            free_t.off + jnp.float32(consts.delta_free_visible),
            interval,
        )
        visible = (freed | held) & (free_vis.win < W[:, None])
        deferred = (freed | held) & ~visible
        # The free kernel visits the rows of its first mask, adds a visited
        # row's requests to the node it names and folds the row's sample
        # where the finish bit is set: one launch visits the visible frees
        # and this window's finishes, a deferred finish naming no node.
        kernel_rows = visible | real_fin
        kernel_node = jnp.where(visible, pods.node, -1)
    else:
        visible = kernel_rows = freed
        kernel_node = pods.node
    from kubernetriks_tpu.ops.scheduler_kernel import (
        free_kernel_fits,
        fused_free_resources,
    )

    duration_s = t_seconds_f32(pods.duration, interval)
    dur_stats = None
    if use_pallas and use_pallas_select and free_kernel_fits(N, P):
        core = partial(
            fused_free_resources,
            interpret=pallas_interpret,
            nodes_lane_major=lane_major,
        )
        # The kernel also folds the finished pods' duration-estimator
        # samples (count/total/total_sq/min/max), replacing the five
        # (C, P) masked reductions below.
        alloc_cpu, alloc_ram, dur_stats = core(
            kernel_rows, kernel_node, pods.req_cpu, pods.req_ram,
            real_fin, duration_s, alloc_cpu, alloc_ram,
        )
    else:
        F = min(P, 32)  # freed-compaction chunk width (independent of E)

        def free_cond(carry):
            return carry[0].any()

        def free_body(carry):
            pending, acpu, aram = carry
            _, idx = jax.lax.top_k(pending.astype(jnp.int32), F)
            fv = pending[rows, idx]
            tgt = jnp.where(fv, node_idx[rows, idx], N)
            add_cpu = jnp.where(fv, pods.req_cpu[rows, idx], 0)
            add_ram = jnp.where(fv, pods.req_ram[rows, idx], 0)
            if lane_major:
                acpu = acpu.at[tgt, rows].add(add_cpu, mode="drop")
                aram = aram.at[tgt, rows].add(add_ram, mode="drop")
            else:
                acpu = acpu.at[rows, tgt].add(add_cpu, mode="drop")
                aram = aram.at[rows, tgt].add(add_ram, mode="drop")
            pending = pending.at[rows, jnp.where(fv, idx, P)].set(False, mode="drop")
            return (pending, acpu, aram)

        _, alloc_cpu, alloc_ram = jax.lax.while_loop(
            free_cond, free_body, (visible, alloc_cpu, alloc_ram)
        )

    # Finished pods.
    if dur_stats is not None:
        n_done = dur_stats[:, 0].astype(jnp.int32)
        est = metrics.pod_duration
        pod_duration_est = EstArrays(
            count=est.count + n_done,
            total=est.total + dur_stats[:, 1],
            total_sq=est.total_sq + dur_stats[:, 2],
            minimum=jnp.minimum(est.minimum, dur_stats[:, 3]),
            maximum=jnp.maximum(est.maximum, dur_stats[:, 4]),
        )
    else:
        n_done = real_fin.sum(axis=1, dtype=jnp.int32)
        pod_duration_est = _est_add_reduced(
            metrics.pod_duration, duration_s, real_fin
        )
    metrics = metrics._replace(
        pods_succeeded=metrics.pods_succeeded + n_done,
        terminated_pods=metrics.terminated_pods + n_done,
        pod_duration=pod_duration_est,
        processed_nodes=metrics.processed_nodes
        + created.sum(axis=n_sum_ax, dtype=jnp.int32),
    )
    phase = jnp.where(real_fin, PHASE_SUCCEEDED, phase)
    finish_time = t_where(finishes, t_inf((C, P)), pods.finish_time)

    # Reschedule pods of removed nodes (reference: scheduler.rs:336-364).
    # Queue order among same-window rescheds must match the scalar's event
    # order: removal visibility time first, then — for same-time removals —
    # the order the removal requests were EMITTED (the CA walks scale-down
    # candidates in node-name order), then sorted pod names within a node.
    # Name ranks come from the autoscale statics when available; slot order
    # is the fallback (equal keys keep slot order in _stable_queue_rank).
    if node_key_fn is not None:
        # Slot reclaim: removed CA nodes order by their occupants' CURRENT
        # names (allocation-index keys, autoscale.ca_name_order), made only
        # in a window that ranks: the static table describes the slots'
        # first occupants.
        node_key = (node_key_fn, node_idx)
    elif node_name_rank is not None:
        node_key = (node_name_rank, node_idx)
    else:
        node_key = node_idx
    resched_keys = (pod_node_removal, node_key)
    if pod_name_rank is not None:
        resched_keys += (pod_name_rank,)
    # (Under node faults the ordering is the crash path's: a rack's pods
    # re-enter the queue together.)
    with faults_scope() if node_faults else contextlib.nullcontext():
        resched_rank, rank_over = _stable_queue_rank(resched_keys, rescheds)
    resched_ts = t_norm(
        jnp.broadcast_to(base[:, None], (C, P)),
        jnp.where(rescheds, pod_node_removal, 0.0)
        + jnp.float32(consts.delta_reschedule),
        interval,
    )
    phase = jnp.where(rescheds, PHASE_QUEUED, phase)
    queue_ts = t_where(rescheds, resched_ts, queue_ts)
    queue_seq = jnp.where(
        rescheds, state.queue_seq_counter[:, None] + n_creates[:, None] + resched_rank,
        queue_seq,
    )
    initial_attempt_ts = t_where(rescheds, resched_ts, initial_attempt_ts)
    attempts = jnp.where(rescheds, 1, attempts)
    finish_time = t_where(rescheds, t_inf((C, P)), finish_time)
    pod_node = jnp.where(rescheds, -1, pods.node)
    n_rescheds = rescheds.sum(axis=1, dtype=jnp.int32)
    metrics = metrics._replace(
        resched_rank_windows=metrics.resched_rank_windows
        + (n_rescheds > 0).astype(jnp.int32),
        resched_rank_sorted=metrics.resched_rank_sorted
        + rank_over.astype(jnp.int32),
    )

    # Chaos: dispose of failing attempts — CrashLoopBackOff retry (requeue
    # at fail + min(base * 2^k, cap), fresh initial-attempt timestamp,
    # mirroring the scalar RequeuePodAfterBackoff delivery) or permanent
    # failure past the restart limit (terminal PHASE_FAILED).
    restarts_arr = pods.restarts
    will_fail_arr = pods.will_fail
    n_fail_retries = jnp.zeros_like(n_rescheds)
    if pod_faults:
        new_restarts = pods.restarts + 1
        retry = fails & (new_restarts <= jnp.int32(fault_params.restart_limit))
        perma = fails & ~retry
        fail_rel = _rel_seconds(pods.finish_time, base[:, None], interval)
        backoff = jnp.minimum(
            jnp.float32(fault_params.backoff_base)
            * jnp.exp2(pods.restarts.astype(jnp.float32)),
            jnp.float32(fault_params.backoff_cap),
        )
        # The retry cannot enter the queue before the failure itself reaches
        # the scheduler (node -> api server -> storage -> scheduler — the
        # same chain as a node-removal reschedule), so a backoff shorter
        # than that delay is floored at it, like the scalar delivery.
        retry_ts = t_norm(
            jnp.broadcast_to(base[:, None], (C, P)),
            jnp.where(
                retry,
                fail_rel
                + jnp.maximum(backoff, jnp.float32(consts.delta_reschedule)),
                0.0,
            ),
            interval,
        )

        # Seq ranks among this window's retries follow the scalar's
        # failure-event order: fail time, then pod name (slot order as the
        # rank-less fallback).
        fail_keys = (fail_rel,)
        if pod_name_rank is not None:
            fail_keys += (pod_name_rank,)
        fail_rank, _ = _stable_queue_rank(fail_keys, retry)
        phase = jnp.where(
            retry,
            PHASE_QUEUED,
            jnp.where(perma, PHASE_FAILED, phase),
        )
        queue_ts = t_where(retry, retry_ts, queue_ts)
        queue_seq = jnp.where(
            retry,
            state.queue_seq_counter[:, None]
            + n_creates[:, None]
            + n_rescheds[:, None]
            + fail_rank,
            queue_seq,
        )
        initial_attempt_ts = t_where(retry, retry_ts, initial_attempt_ts)
        attempts = jnp.where(retry, 1, attempts)
        if channel:
            # A failed attempt leaves its node when its free does: the
            # channel reads the node until then. (The retry enters the
            # queue no earlier than the free is visible, so no cycle can
            # bind it before.)
            off_node = visible & (
                fails
                | (held & (pods.phase != PHASE_SUCCEEDED)
                   & (pods.phase != PHASE_REMOVED))
            )
        else:
            off_node = fails
        pod_node = jnp.where(off_node, -1, pod_node)
        restarts_arr = jnp.where(fails, new_restarts, pods.restarts)
        will_fail_arr = jnp.where(fails, False, pods.will_fail)
        n_fail_retries = retry.sum(axis=1, dtype=jnp.int32)
        n_perma = perma.sum(axis=1, dtype=jnp.int32)
        metrics = metrics._replace(
            pod_restarts=metrics.pod_restarts + n_fail_retries,
            pods_failed=metrics.pods_failed + n_perma,
            terminated_pods=metrics.terminated_pods + n_perma,
        )

    # Removed-while-running pods terminate as removed
    # (reference: api_server.rs PodRemovedFromNode removed=true accounting).
    n_removed_running = removed_running.sum(axis=1, dtype=jnp.int32)
    metrics = metrics._replace(
        pods_removed=metrics.pods_removed + n_removed_running,
        terminated_pods=metrics.terminated_pods + n_removed_running,
    )
    phase = jnp.where(removed_running, PHASE_REMOVED, phase)
    finish_time = t_where(removed_running, t_inf((C, P)), finish_time)
    if channel:
        finish_time = t_where(
            deferred,
            free_t,
            t_where(visible, t_inf((C, P)), finish_time),
        )

    # Removal of queued/unschedulable (or just-created) pods: dropped from the
    # queues with NO removed/terminated metrics (scalar parity: only
    # PodRemovedFromNode(removed=true) counts, reference: api_server.rs:345-368).
    removed_queued = (
        ((phase == PHASE_QUEUED) | (phase == PHASE_UNSCHEDULABLE))
        & (pod_removal < f32inf)
        & ~removed_running
    )
    phase = jnp.where(removed_queued, PHASE_REMOVED, phase)

    # Kill removed nodes AFTER pod resolution (resolution reads pre-window
    # alive only via pods.node indices, which is removal-independent).
    alive = alive & ~(node_removal < f32inf)

    any_created_node = created.any(axis=n_sum_ax)
    n_freed = n_done + n_removed_running
    if pod_faults:
        # Failing attempts free their resources too (scalar: the failure
        # handler wakes the unschedulable queue like a finish).
        n_freed = n_freed + n_fail_retries + n_perma
    metrics = metrics._replace(frees_total=metrics.frees_total + n_freed)
    if channel:
        any_freed = visible.any(axis=1)
        metrics = metrics._replace(
            frees_deferred=metrics.frees_deferred
            + (freed & deferred).sum(axis=1, dtype=jnp.int32)
        )
    else:
        any_freed = n_freed > 0

    # Conditional-move wake events (consumed by prepare_cycle's per-event
    # wake scans when enable_unscheduled_pods_conditional_move is on;
    # _conditional_wake_exact replays the scalar's one-scan-per-event
    # semantics): a new node contributes its full allocatable (= capacity at
    # creation, scheduler.rs:393), a finished/removed pod its freed requests
    # (scheduler.rs:366-380). Only built on the conditional-move path.
    if conditional_move:
        node_rel = jnp.where(created, node_create_rel, f32inf)
        if channel:  # a free wakes the queue when the scheduler hears of it
            freed_rel = jnp.where(
                visible, _rel_seconds(free_vis, base[:, None], interval), f32inf
            )
        else:
            freed_rel = jnp.where(
                finishes,
                _rel_seconds(pods.finish_time, base[:, None], interval),
                jnp.where(removed_running, pod_removal, f32inf),
            )
        wake_events = WakeEvents(
            # WakeEvents is row-major by contract (its consumer concatenates
            # the node and pod axes); transpose the lane-major accumulators
            # once here — conditional-move runs only.
            node_mask=created.T if lane_major else created,
            node_rel=node_rel.T if lane_major else node_rel,
            freed_mask=visible,
            freed_rel=freed_rel,
        )
    else:
        wake_events = None

    new_state = state._replace(
        nodes=nodes._replace(
            alive=alive,
            alloc_cpu=alloc_cpu,
            alloc_ram=alloc_ram,
            create_time=node_create_time,
            remove_time=node_remove_time,
        ),
        pods=pods._replace(
            phase=phase,
            queue_ts=queue_ts,
            queue_seq=queue_seq,
            initial_attempt_ts=initial_attempt_ts,
            attempts=attempts,
            node=pod_node,
            finish_time=finish_time,
            removal_time=pod_removal_time,
            restarts=restarts_arr,
            will_fail=will_fail_arr,
        ),
        metrics=metrics,
        event_cursor=event_cursor,
        queue_seq_counter=state.queue_seq_counter
        + n_creates
        + n_rescheds
        + n_fail_retries,
        # Events of interest wake the unschedulable queue (flush-all policy,
        # reference: scheduler.rs:391-410,435-440,445-473).
        requeue_signal=state.requeue_signal | any_created_node | any_freed,
        time=jnp.maximum(state.time, W),
    )
    return new_state, wake_events, event_chunks


class WakeEvents(NamedTuple):
    """This window's conditional-move wake events (intra-window lifetime:
    built by _apply_window_events, consumed by the same window's
    prepare_cycle). Rel times are float32 seconds from the window base."""

    node_mask: jnp.ndarray  # (C, N) nodes created this window
    node_rel: jnp.ndarray  # (C, N) creation effect rel seconds; +inf pad
    freed_mask: jnp.ndarray  # (C, P) pods freed (finish/removal)
    freed_rel: jnp.ndarray  # (C, P) free effect rel seconds; +inf pad


def _conditional_wake_exact(
    state: ClusterBatchState,
    pods,
    stale: jnp.ndarray,
    wake: "WakeEvents",
    lane_major: bool = False,
) -> jnp.ndarray:
    """Resource-aware unschedulable wakes for
    enable_unscheduled_pods_conditional_move, replicating the reference's
    one-greedy-scan-PER-EVENT semantics exactly: each node-add / freed event
    runs its own budget scan over the unschedulable queue in (insert_ts,
    name) order — here (queue_ts, queue_seq; park timestamps are distinct
    within a cycle, so seq ties cannot occur) — at the event's effect time,
    with pods moved by earlier events absent from later scans and pods
    parked after an event's time invisible to it:

    - Node added (reference: src/core/scheduler/scheduler.rs:391-409):
      budget = the new node's allocatable (= capacity); a pod that FITS
      consumes the budget and STAYS parked; a pod that does not fit moves to
      the active queue. (That inverted sense is the reference's actual
      behavior; preserved as-is.)
    - Resources freed by pod finish/removal (scheduler.rs:366-380,435-439,
      462-468): budget = that pod's freed requests; greedy first-fit — a pod
      that fits consumes the budget and MOVES.

    Cost: one P-length scan per wake event, gated to windows that have
    events and parked pods (rare outside contended conditional-move runs).
    """
    C, P = pods.phase.shape
    N = wake.node_mask.shape[1]
    rows = jnp.arange(C, dtype=jnp.int32)[:, None]
    rows1 = jnp.arange(C, dtype=jnp.int32)
    unsched = (pods.phase == PHASE_UNSCHEDULABLE) & ~stale

    u_t = t_where(unsched, pods.queue_ts, t_inf((C, P)))
    u_seq = jnp.where(unsched, pods.queue_seq, jnp.iinfo(jnp.int32).max)
    order = lexsort_time_i32(u_t, u_seq)  # (C, P) unschedulable first
    o_valid = unsched[rows, order]
    o_req_cpu = pods.req_cpu[rows, order]
    o_req_ram = pods.req_ram[rows, order]
    # (No park-time-vs-event-time gate: every parked pod present at this
    # window's prepare was parked microseconds after a PREVIOUS window
    # boundary, so it predates all of this window's events except
    # sub-microsecond pathologies.)

    # Combined event axis (N node slots + P pod slots), sorted by effect
    # time (stable; same-time events keep node-before-freed slab order —
    # same-timestamp interleavings are FIFO in the scalar queue and the
    # trace compiler emits creates before the finishes they enable).
    f32inf = jnp.float32(INF)
    ev_rel = jnp.concatenate([wake.node_rel, wake.freed_rel], axis=1)
    ev_valid = jnp.concatenate([wake.node_mask, wake.freed_mask], axis=1)
    ev_is_node = jnp.concatenate(
        [jnp.ones((C, N), bool), jnp.zeros((C, P), bool)], axis=1
    )
    cap_cpu = state.nodes.cap_cpu.T if lane_major else state.nodes.cap_cpu
    cap_ram = state.nodes.cap_ram.T if lane_major else state.nodes.cap_ram
    ev_cpu = jnp.concatenate([cap_cpu, pods.req_cpu], axis=1)
    ev_ram = jnp.concatenate([cap_ram, pods.req_ram], axis=1)
    key = jnp.where(ev_valid, ev_rel, f32inf)
    _, s_valid, s_is_node, s_cpu, s_ram = jax.lax.sort(
        (key, ev_valid, ev_is_node, ev_cpu, ev_ram),
        dimension=1, num_keys=1, is_stable=True,
    )
    n_ev = jnp.max(ev_valid.sum(axis=1, dtype=jnp.int32))

    def ev_body(carry):
        e, moved = carry
        v_valid = jax.lax.dynamic_index_in_dim(s_valid, e, 1, keepdims=False)
        v_is_node = jax.lax.dynamic_index_in_dim(s_is_node, e, 1, keepdims=False)
        v_cpu = jax.lax.dynamic_index_in_dim(s_cpu, e, 1, keepdims=False)
        v_ram = jax.lax.dynamic_index_in_dim(s_ram, e, 1, keepdims=False)

        def pod_scan(c2, xs):
            bud_cpu, bud_ram = c2
            p_valid, rcpu, rram, m = xs
            considered = p_valid & ~m & v_valid
            fits = considered & (rcpu <= bud_cpu) & (rram <= bud_ram)
            bud_cpu = bud_cpu - jnp.where(fits, rcpu, 0)
            bud_ram = bud_ram - jnp.where(fits, rram, 0)
            mv = jnp.where(v_is_node, considered & ~fits, fits)
            return (bud_cpu, bud_ram), mv

        (_, _), mv_sorted = jax.lax.scan(
            pod_scan,
            (v_cpu, v_ram),
            (o_valid.T, o_req_cpu.T, o_req_ram.T, moved.T),
        )
        return e + jnp.int32(1), moved | mv_sorted.T

    _, moved_sorted = jax.lax.while_loop(
        lambda carry: carry[0] < n_ev,
        ev_body,
        (jnp.int32(0), jnp.zeros((C, P), bool)),
    )
    # Scatter sorted-order decisions back to slot positions.
    return jnp.zeros((C, P), bool).at[rows, order].set(moved_sorted)


def spread_window_view(spread, pod_base: jnp.ndarray, P: int):
    """The device pod window's columns of the spread state's global pod
    planes: (workload, match bits, placed domain), (C, P) each. A build
    whose window is the whole trace holds planes of that width and reads
    them as they are; a sliding build cuts one run of P columns a cluster
    at `pod_base` (C row slices, not C x P indices)."""
    planes = (spread.pod_group, spread.pod_bits, spread.pod_zone)
    if planes[0].shape[1] == P:
        return planes
    cut = jax.vmap(lambda row, lo: jax.lax.dynamic_slice(row, (lo,), (P,)))
    return tuple(cut(x, pod_base) for x in planes)


def _spread_with_zone(spread, pod_base: jnp.ndarray, zone_w: jnp.ndarray):
    """The spread state with the window's placed-domain columns written
    back (spread_window_view's inverse for the one plane a cycle writes)."""
    if spread.pod_zone.shape[1] == zone_w.shape[1]:
        return spread._replace(pod_zone=zone_w)
    put = jax.vmap(lambda row, lo, new: jax.lax.dynamic_update_slice(row, new, (lo,)))
    return spread._replace(pod_zone=put(spread.pod_zone, pod_base, zone_w))


def affinity_window_view(affinity, pod_base: jnp.ndarray, P: int):
    """The device pod window's columns of the affinity state's global pod
    planes: (the term planes..., the untolerated-taint plane), (C, P) each,
    the order the kernels' wrappers take them in. As spread_window_view."""
    planes = tuple(affinity.pod_terms[:, t, :] for t in range(affinity.pod_terms.shape[1])) + (
        affinity.pod_forbid,
    )
    return _window_columns(planes, pod_base, P)


def _window_columns(planes, pod_base: jnp.ndarray, P: int):
    if planes[0].shape[1] == P:
        return planes
    cut = jax.vmap(lambda row, lo: jax.lax.dynamic_slice(row, (lo,), (P,)))
    return tuple(cut(x, pod_base) for x in planes)


def soft_window_view(affinity, pod_base: jnp.ndarray, P: int):
    """affinity_window_view for the score halves' planes: (the preferred-term
    planes..., the packed weights, the untolerated soft taints), (C, P) each,
    the order the kernels' wrappers take them in after the two capacity
    planes."""
    terms = affinity.pod_soft_terms
    planes = tuple(terms[:, t, :] for t in range(terms.shape[1])) + (
        affinity.pod_soft_weights,
        affinity.pod_soft_forbid,
    )
    return _window_columns(planes, pod_base, P)


@jax.named_scope("spread_counts")
def spread_counts(state: ClusterBatchState, consts: StepConstants, lane_major: bool = False):
    """What the PodTopologySpread filter knows at the start of a cycle
    (semantics: core/scheduler/plugins.PodTopologySpread): counts (C, G, Z)
    int32, the pods that hold the scheduler's allocatable (alloc_holders: so
    a free still on the pending-free channel counts, as it does in the
    scalar scheduler's cache) on nodes of domain z whose labels satisfy
    workload g's selector; and zone_alive (C, Z) bool, the domains of the
    nodes in the scheduler's cache. One masked reduction over the pod
    window: a holder's domain was written when it was placed
    (SpreadState.pod_zone), so nothing is gathered."""
    sp = state.spread
    _, G, Z = sp.max_skew.shape
    _, bits, zone = spread_window_view(sp, state.pod_base, state.pods.phase.shape[1])
    held = jnp.where(alloc_holders(state.pods, consts), zone, jnp.int32(-1))
    # ONE variadic reduce, G x Z sums off the same two planes: its producers
    # fuse into it, where one reduce of a (C, G, Z, P) broadcast leaves XLA
    # 28 MB of predicates between three fusions (compiled for a described
    # v5e: 32 MB a pass against 175).
    hits = tuple(
        ((((bits >> g) & 1) != 0) & (held == z)).astype(jnp.int32)
        for g in range(G)
        for z in range(Z)
    )
    sums = jax.lax.reduce(
        hits,
        (jnp.int32(0),) * len(hits),
        lambda a, b: tuple(x + y for x, y in zip(a, b)),
        (1,),
    )
    counts = jnp.stack(sums, axis=1).reshape(-1, G, Z)
    node_axis = 0 if lane_major else 1
    zone_alive = jnp.stack(
        [(state.nodes.alive & (sp.domain == z_)).any(axis=node_axis) for z_ in range(Z)],
        axis=1,
    )
    return counts, zone_alive


class CycleCandidates(NamedTuple):
    """One pass's scheduling candidates (K of the sorted queue);
    a pytree, so it composes with jit/scan like the rest of the state."""

    pods: "object"  # PodArrays with wake/flush moves applied
    last_flush_win: jnp.ndarray
    cand: jnp.ndarray  # (C, K) pod slots in queue order
    valid: jnp.ndarray  # (C, K)
    req_cpu: jnp.ndarray
    req_ram: jnp.ndarray
    # (C, K) float32 queue wait at cycle start: T - initial_attempt_ts.
    waited: jnp.ndarray
    # (C, K) the candidates' spread workload and match bits; None in a build
    # without the spread filter.
    spread_group: Optional[jnp.ndarray] = None
    spread_bits: Optional[jnp.ndarray] = None
    # (C, K) the candidates' node-affinity term masks and, last, their
    # untolerated-taint masks; None in a build without the label filters.
    affinity: Optional[Tuple[jnp.ndarray, ...]] = None
    # (C, K) the candidates' preferred-term masks, packed weights and
    # untolerated soft taints; None in a build without soft planes.
    soft: Optional[Tuple[jnp.ndarray, ...]] = None


def cycle_durations(pod_sched_time, K: int, first=0):
    """(cd_pre, cd_post), (C, K) float32: a cycle's simulated duration
    before and after the pod at positions first .. first + K - 1 of its
    queue order. The algorithm latency is one number a cluster a cycle, so
    they are the position, and the position + 1, times it: ONE float32
    product a value, no prefix sum whose rounding would depend on where a
    pass begins or how deep the cycle runs (the megakernel makes the same
    products in its loop). Values of their own, held apart from whatever is
    added to them: a compiler that fuses a multiply into an add (XLA on the
    CPU does) would round once where the megakernel, whose step adds the
    product of the step before, rounds twice."""
    pos = jnp.asarray(first, jnp.int32) + jnp.arange(K, dtype=jnp.int32)[None, :]
    return jax.lax.optimization_barrier(
        (
            pos.astype(jnp.float32) * pod_sched_time[:, None],
            (pos + 1).astype(jnp.float32) * pod_sched_time[:, None],
        )
    )


def cycle_timing(waited, pod_sched_time, consts: StepConstants, first=0):
    """(C, K) per-candidate timing mechanics of one pass of a cycle whose
    candidates stand at positions first .. of the WHOLE cycle's queue order:
    pod k's assignment effect time includes the algorithm latency of pods
    0..k of the cycle (reference: scheduler.rs:270-320; cycle_durations).
    Shared by the lax.scan, Pallas and RL paths; a single source is what
    keeps them bit-for-bit aligned.

    Returns (pod_queue_time (C,K), start_s (C,K), park_s (C,K)) — the
    latter two as float32 second offsets relative to the cycle time T."""
    cd_pre, cd_post = cycle_durations(pod_sched_time, waited.shape[1], first)
    pod_queue_time = waited + cd_pre
    start_s = cd_post + jnp.float32(consts.delta_bind_start)
    # Unschedulable park: new insert timestamp = T + cycle duration
    # (reference: scheduler.rs:282-306).
    park_s = cd_post
    return pod_queue_time, start_s, park_s


def decision_metrics(metrics, assign_k, pod_queue_time_k, pod_sched_time):
    """Fold one cycle's decisions into the (C,) metric accumulators
    (reference counters/estimators: scheduler.rs:322-329)."""
    C, K = assign_k.shape
    return metrics._replace(
        scheduling_decisions=metrics.scheduling_decisions
        + assign_k.sum(axis=1, dtype=jnp.int32),
        queue_time=_est_add_reduced(metrics.queue_time, pod_queue_time_k, assign_k),
        algo_latency=_est_add_reduced(
            metrics.algo_latency,
            jnp.broadcast_to(pod_sched_time[:, None], (C, K)),
            assign_k,
        ),
    )


class CycleTotals(NamedTuple):
    """What one DRAINED cycle adds to a cluster's metrics, (C,) each, however
    many passes made it up: the assignments, their queue-time samples' sum,
    sum of squares, least and largest, and the assignments a cycle bounded
    at max_pods_per_cycle would have put off to a later one."""

    assigned: jnp.ndarray  # int32
    q_total: jnp.ndarray  # float32
    q_total_sq: jnp.ndarray
    q_min: jnp.ndarray
    q_max: jnp.ndarray
    late: jnp.ndarray  # int32


def fold_cycle_totals(
    metrics, tot: CycleTotals, decided, pod_sched_time, consts: StepConstants, pass_size: int,
    compacted=None,
):
    """One cycle's totals into the (C,) metric accumulators (reference
    counters/estimators: scheduler.rs:322-329): ONCE a cycle, so that the
    sums do not depend on how the cycle was cut into passes. algo_latency
    takes the constant per-cluster pod_sched_time once an assignment.
    `decided` (C,): the pods the cycle assigned or parked, which is every pod
    eligible at its start. The
    drain counters (MetricArrays.cycle_*) count, a cluster: the passes of
    pass_size its cycle took, the cycles in which it had a pod to decide,
    the assignments after its first pass, its deepest cycle, the cycles
    whose simulated duration reached the interval (docs/PARITY.md: counted,
    not modelled), its cycles deeper than one pass, and those of them that
    `compacted` (C,) bool marks: drained by the megakernel's second launch
    (_launch_by_depth; None where the formulation has none)."""
    n = tot.assigned
    nf = n.astype(jnp.float32)
    has = n > 0
    qt, al = metrics.queue_time, metrics.algo_latency
    overrun = (decided > 0) & (
        decided.astype(jnp.float32) * pod_sched_time
        >= jnp.float32(consts.scheduling_interval)
    )
    return metrics._replace(
        scheduling_decisions=metrics.scheduling_decisions + n,
        queue_time=EstArrays(
            count=qt.count + n,
            total=qt.total + tot.q_total,
            total_sq=qt.total_sq + tot.q_total_sq,
            minimum=jnp.minimum(qt.minimum, tot.q_min),
            maximum=jnp.maximum(qt.maximum, tot.q_max),
        ),
        algo_latency=EstArrays(
            count=al.count + n,
            total=al.total + nf * pod_sched_time,
            total_sq=al.total_sq + nf * pod_sched_time * pod_sched_time,
            minimum=jnp.where(has, jnp.minimum(al.minimum, pod_sched_time), al.minimum),
            maximum=jnp.where(has, jnp.maximum(al.maximum, pod_sched_time), al.maximum),
        ),
        cycle_passes=metrics.cycle_passes
        + (decided + jnp.int32(pass_size - 1)) // jnp.int32(pass_size),
        cycle_count=metrics.cycle_count + (decided > 0).astype(jnp.int32),
        cycle_late_decisions=metrics.cycle_late_decisions + tot.late,
        cycle_deepest=jnp.maximum(metrics.cycle_deepest, decided),
        cycle_overruns=metrics.cycle_overruns + overrun.astype(jnp.int32),
        cycle_deep=metrics.cycle_deep + (decided > pass_size).astype(jnp.int32),
        cycle_compacted=metrics.cycle_compacted
        if compacted is None
        else metrics.cycle_compacted + compacted.astype(jnp.int32),
    )


def prepare_queue(
    state: ClusterBatchState,
    W: jnp.ndarray,
    consts: StepConstants,
    conditional_move: bool = False,
    wake=None,
    lane_major: bool = False,
):
    """Queue preamble shared by every cycle path (sorted-scan, Pallas
    candidate kernel, Pallas selection kernel, RL): unschedulable wake/flush
    moves and the eligibility mask. Returns (pods with moves applied,
    last_flush_win, eligible (C, P))."""
    C, P = state.pods.phase.shape
    pods = state.pods
    interval = jnp.float32(consts.scheduling_interval)
    Tpair = TPair(
        win=jnp.broadcast_to(W[:, None], (C, P)),
        off=jnp.zeros((C, P), jnp.float32),
    )

    # Unschedulable-leftover flush at the 30 s cadence
    # (reference: scheduler.rs:188-203).
    flush_now = (W - state.last_flush_win).astype(jnp.float32) * interval >= jnp.float32(
        consts.flush_interval
    )

    def wake_block():
        # Stale: T - queue_ts > max_stay, i.e. queue_ts + max_stay < T.
        stay_cut = t_norm(
            pods.queue_ts.win,
            pods.queue_ts.off + jnp.float32(consts.max_unschedulable_stay),
            interval,
        )
        stale = (
            (pods.phase == PHASE_UNSCHEDULABLE)
            & t_lt(stay_cut, Tpair)
            & flush_now[:, None]
        )
        if conditional_move:
            assert wake is not None, (
                "conditional_move prepare needs this window's WakeEvents"
            )
            moves = _conditional_wake_exact(
                state, pods, stale, wake, lane_major=lane_major
            )
        else:
            moves = state.requeue_signal[:, None] & (
                pods.phase == PHASE_UNSCHEDULABLE
            )
        to_move = stale | moves
        return (
            jnp.where(to_move, PHASE_QUEUED, pods.phase),
            pods.attempts + to_move.astype(jnp.int32),
        )

    # No parked pod anywhere -> nothing to wake or flush; skip the whole
    # (C, P) block (common case on uncontended batches).
    phase2, attempts2 = jax.lax.cond(
        (pods.phase == PHASE_UNSCHEDULABLE).any(),
        wake_block,
        lambda: (pods.phase, pods.attempts),
    )
    pods = pods._replace(phase=phase2, attempts=attempts2)
    last_flush_win = jnp.where(flush_now, W, state.last_flush_win)

    # Eligible = queued strictly before T — with pair times that is exactly
    # queue_ts.win < W.
    eligible = (pods.phase == PHASE_QUEUED) & (pods.queue_ts.win < W[:, None])
    return pods, last_flush_win, eligible


def candidates_from_slots(
    pods,
    last_flush_win: jnp.ndarray,
    cand: jnp.ndarray,
    valid: jnp.ndarray,
    W: jnp.ndarray,
    consts: StepConstants,
    spread_pods=None,
    affinity_pods=None,
    soft_pods=None,
) -> CycleCandidates:
    """Assemble CycleCandidates from chosen candidate slots — the gathers
    and the `waited` formula shared by the sorted path and the in-kernel
    selection path (ONE definition, so the paths cannot drift).
    `spread_pods`: the window's (workload, match bits) planes where the
    build runs the spread filter; `affinity_pods`: its term and
    untolerated-taint planes where it runs the label filters; `soft_pods`:
    its soft planes where it scores by them."""
    C = cand.shape[0]
    rows = jnp.arange(C, dtype=jnp.int32)[:, None]
    interval = jnp.float32(consts.scheduling_interval)
    init_win = pods.initial_attempt_ts.win[rows, cand]
    init_off = pods.initial_attempt_ts.off[rows, cand]
    waited = (W[:, None] - init_win).astype(jnp.float32) * interval - init_off
    return CycleCandidates(
        pods=pods,
        last_flush_win=last_flush_win,
        cand=cand,
        valid=valid,
        req_cpu=pods.req_cpu[rows, cand],
        req_ram=pods.req_ram[rows, cand],
        waited=waited,
        **(
            {}
            if spread_pods is None
            else dict(
                spread_group=spread_pods[0][rows, cand], spread_bits=spread_pods[1][rows, cand]
            )
        ),
        **(
            {}
            if affinity_pods is None
            else dict(affinity=tuple(x[rows, cand] for x in affinity_pods))
        ),
        **({} if not soft_pods else dict(soft=tuple(x[rows, cand] for x in soft_pods))),
    )


def queue_order(pods, eligible) -> jnp.ndarray:
    """(C, P) pod slots in queue order, (queue_ts, queue_seq), the eligible
    ones first."""
    C, P = eligible.shape
    sort_t = t_where(eligible, pods.queue_ts, t_inf((C, P)))
    sort_seq = jnp.where(eligible, pods.queue_seq, jnp.iinfo(jnp.int32).max)
    return lexsort_time_i32(sort_t, sort_seq)


def prepare_cycle(
    state: ClusterBatchState,
    W: jnp.ndarray,
    consts: StepConstants,
    K: int,
    conditional_move: bool = False,
    wake=None,
    lane_major: bool = False,
    spread_pods=None,
) -> CycleCandidates:
    """prepare_queue + queue sort + top-K compaction: the first K of the
    queue (the RL policy cycle's candidates; the kube-scheduler cycle takes
    the whole queue, K at a pass: _run_scheduling_cycle). W: (C,) int32
    window index (cycle time T = W * interval)."""
    C, P = state.pods.phase.shape
    rows = jnp.arange(C, dtype=jnp.int32)[:, None]
    pods, last_flush_win, eligible = prepare_queue(
        state, W, consts, conditional_move, wake, lane_major=lane_major
    )
    cand = queue_order(pods, eligible)[:, :K]
    return candidates_from_slots(
        pods, last_flush_win, cand, eligible[rows, cand], W, consts, spread_pods
    )


def commit_scattered_tail(
    state: ClusterBatchState,
    pods,
    last_flush_win,
    W: jnp.ndarray,
    consts: StepConstants,
    alloc_cpu,
    alloc_ram,
    metrics,
    phase,
    node,
    start_tmp,
    park_tmp,
    fault_params=None,
    shard_axis=None,
) -> ClusterBatchState:
    """Shared bottom half of the decision commit: reconstruct absolute
    start/finish/park pairs from the scattered float32 second offsets
    (+inf = untouched) and write the post-cycle state. Used by commit_cycle
    and by the megakernel path (whose kernel already produced the scattered
    phase/node/start/park arrays).

    With pod faults on, this is ALSO where every new attempt's failure draw
    happens: a counter-PRNG threefry on (seed, cluster, global plain pod
    slot, restarts) — identical bits to the scalar oracle's draw at
    assignment commit — decides whether the attempt fails and at what
    fraction of its duration; a failing attempt's finish_time becomes its
    fail time and will_fail is set for the finish resolution to dispose."""
    C, P = pods.phase.shape
    interval = jnp.float32(consts.scheduling_interval)
    f32inf = jnp.float32(INF)

    started = start_tmp < f32inf
    start_pair = t_norm(
        jnp.broadcast_to(W[:, None], (C, P)),
        jnp.where(started, start_tmp, 0.0),
        interval,
    )
    service = pods.duration.win < 0
    finish_pair = t_add(start_pair, pods.duration, interval)
    start_time = t_where(started, start_pair, pods.start_time)
    finish_val = t_where(service, t_inf((C, P)), finish_pair)
    pods_fault_fields = {}
    if fault_params is not None and fault_params.fail_prob > 0:
        from kubernetriks_tpu import chaos

        idx = jnp.broadcast_to(
            jnp.arange(P, dtype=jnp.int32)[None, :], (C, P)
        )
        # Device layout: [window over plain slots | resident ring tail];
        # plain device slot -> global slot via pod_base, resident via the
        # fixed shift. Only plain trace pods with finite durations draw
        # (ring replicas' identities are runtime-assigned and path-specific).
        plain_width = consts.trace_pod_bound - consts.resident_shift
        in_plain = idx < plain_width
        gslot = idx + jnp.where(
            in_plain, state.pod_base[:, None], jnp.int32(consts.resident_shift)
        )
        if consts.fault_seed is not None:
            # Scenario-vector fleet: per-lane seeds ride as traced (C,)
            # data and the cluster key pins to 0, so a lane's draws are a
            # pure function of its scenario seed — the same keying the
            # scalar oracle uses (PodFaultOracle keys cluster 0), which
            # makes lane placement permutation-invariant (fleet.py).
            seed_key = jnp.asarray(consts.fault_seed, jnp.uint32)[:, None]
            cid = jnp.zeros((C, P), jnp.uint32)
        else:
            seed_key = fault_params.seed
            # The draw keys on the cluster's index in the BUILD, whatever
            # shard holds it (sharding.cluster_ids).
            cid = jnp.broadcast_to(
                cluster_ids(C, shard_axis)[:, None], (C, P)
            ).astype(jnp.uint32)
        u_fail, u_frac = chaos.pod_attempt_uniforms(
            seed_key,
            cid,
            gslot.astype(jnp.uint32),
            pods.restarts.astype(jnp.uint32),
            xp=jnp,
        )
        faultable = started & in_plain & (pods.duration.win >= 0)
        wf = faultable & (u_fail < jnp.float32(fault_params.fail_prob))
        dur_s = t_seconds_f32(pods.duration, interval)
        fail_fin = t_norm(
            jnp.broadcast_to(W[:, None], (C, P)),
            jnp.where(wf, start_tmp + u_frac * dur_s, 0.0),
            interval,
        )
        finish_val = t_where(wf, fail_fin, finish_val)
        pods_fault_fields["will_fail"] = jnp.where(
            started, wf, pods.will_fail
        )
    finish_time = t_where(started, finish_val, pods.finish_time)
    parked = park_tmp < f32inf
    park_pair = t_norm(
        jnp.broadcast_to(W[:, None], (C, P)),
        jnp.where(parked, park_tmp, 0.0),
        interval,
    )
    queue_ts = t_where(parked, park_pair, pods.queue_ts)

    return state._replace(
        nodes=state.nodes._replace(alloc_cpu=alloc_cpu, alloc_ram=alloc_ram),
        pods=pods._replace(
            phase=phase,
            queue_ts=queue_ts,
            node=node,
            start_time=start_time,
            finish_time=finish_time,
            **pods_fault_fields,
        ),
        metrics=metrics,
        requeue_signal=jnp.zeros_like(state.requeue_signal),
        last_flush_win=last_flush_win,
        time=jnp.maximum(state.time, W),
    )


def scatter_decisions(
    phase,
    node,
    cand,
    assign_k,
    park_k,
    best_k,
    start_s_k,
    park_s_k,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
):
    """Scatter K per-cluster decisions into the (C, P) pod planes: returns
    (phase, node, start_tmp, park_tmp), the latter two float32 second offsets
    relative to the cycle time T with +inf where this pass touched nothing
    (64-bit value scatters are the slow path on TPU; the absolute pairs are
    rebuilt elementwise in commit_scattered_tail). With use_pallas, the four
    (C, K)-indexed scatters run as one Pallas one-hot kernel instead
    (ops/scheduler_kernel.fused_commit_scatter, bit-identical)."""
    C, P = phase.shape
    rows = jnp.arange(C, dtype=jnp.int32)[:, None]
    f32inf = jnp.float32(INF)

    from kubernetriks_tpu.ops.scheduler_kernel import (
        commit_kernel_fits,
        fused_commit_scatter,
    )

    if use_pallas and commit_kernel_fits(P, cand.shape[1]):
        phase_o, node_o, start_tmp, park_tmp = fused_commit_scatter(
            cand, assign_k, park_k, best_k, start_s_k, park_s_k, phase, node,
            interpret=pallas_interpret,
        )
        return phase_o.astype(phase.dtype), node_o.astype(node.dtype), start_tmp, park_tmp
    new_phase = jnp.where(
        assign_k,
        jnp.int32(PHASE_RUNNING),
        jnp.where(park_k, jnp.int32(PHASE_UNSCHEDULABLE), jnp.int32(-1)),
    ).astype(phase.dtype)
    touched = assign_k | park_k
    phase_o = phase.at[rows, jnp.where(touched, cand, P)].set(
        jnp.where(touched, new_phase, 0), mode="drop"
    )
    node_o = node.at[rows, jnp.where(assign_k, cand, P)].set(
        jnp.where(assign_k, best_k, 0), mode="drop"
    )
    start_tmp = (
        jnp.full((C, P), INF, jnp.float32)
        .at[rows, jnp.where(assign_k, cand, P)]
        .set(jnp.where(assign_k, start_s_k, f32inf), mode="drop")
    )
    park_tmp = (
        jnp.full((C, P), INF, jnp.float32)
        .at[rows, jnp.where(park_k, cand, P)]
        .set(jnp.where(park_k, park_s_k, f32inf), mode="drop")
    )
    return phase_o, node_o, start_tmp, park_tmp


def commit_cycle(
    state: ClusterBatchState,
    cc: CycleCandidates,
    W: jnp.ndarray,
    consts: StepConstants,
    alloc_cpu,
    alloc_ram,
    metrics,
    assign_k,
    park_k,
    best_k,
    start_s_k,
    park_s_k,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    fault_params=None,
    shard_axis=None,
) -> ClusterBatchState:
    """Scatter the K per-cluster decisions back into (C, P) state
    (scatter_decisions) and write the post-cycle state
    (commit_scattered_tail): a cycle of one pass, the RL policy cycle's."""
    phase, node, start_tmp, park_tmp = scatter_decisions(
        cc.pods.phase, cc.pods.node, cc.cand, assign_k, park_k, best_k,
        start_s_k, park_s_k, use_pallas, pallas_interpret,
    )
    return commit_scattered_tail(
        state, cc.pods, cc.last_flush_win, W, consts, alloc_cpu, alloc_ram,
        metrics, phase, node, start_tmp, park_tmp,
        fault_params=fault_params,
        shard_axis=shard_axis,
    )


def _lanes_to_move(n_eligible, K: int, R: int):
    """The clusters a second launch drains (_launch_by_depth), (C,) bool:
    those deeper than one pass, where they fit one tile of R lanes and where
    moving them pays. A tile runs as many steps as its deepest lane holds
    pods, so the single launch runs the sum of the tiles' deepest lanes, and
    the two launches the sum over the lanes that stayed plus the deepest of
    all: the move is made where that saves more steps than the selection and
    the put-back cost (CYCLE_COMPACT_PAYS a tile of the batch). A burst in
    every tile pays many times over; one deep cluster alone never does (its
    tile's steps only move to the second launch), nor do a few that pass K
    by little (a rack's re-queued pods on top of a cycle's arrivals)."""
    C = n_eligible.shape[0]
    tiles = -(-C // R)

    def steps(n):
        return jnp.pad(n, (0, tiles * R - C)).reshape(tiles, R).max(axis=1).sum()

    deep = n_eligible > K
    saved = steps(n_eligible) - steps(jnp.where(deep, 0, n_eligible)) - n_eligible.max()
    fits = deep.sum(dtype=jnp.int32) <= R
    return deep & fits & (saved > CYCLE_COMPACT_PAYS * tiles)


def _tile_slots(moved, R: int):
    """Where the clusters `moved` (C,) marks sit in a tile of R lanes of their
    own: (slot (C,), a moved cluster's place in the tile, the r-th moved one
    in slot r; index (R,), the cluster a slot holds, the batch's width (past
    the axis) for a slot nobody takes)."""
    count = jnp.cumsum(moved, dtype=jnp.int32)
    # A slot's cluster is the number of lanes with at most r moved ones up to
    # and with them.
    index = (count[None, :] <= jnp.arange(R, dtype=jnp.int32)[:, None]).sum(
        axis=1, dtype=jnp.int32
    )
    return count - 1, index


def _take_lanes(x, index):
    """The clusters `index` (R,) names out of x (..., C), cluster axis last;
    zeros in a slot whose index lies past the axis."""
    return jnp.take(x, index, axis=-1, mode="fill", fill_value=0)


def _put_lanes(full, part, slot, moved):
    """_take_lanes undone: `full` (..., C) with the clusters `moved` (C,)
    marks replaced by their slots `slot` (C,) of `part` (..., R); every other
    cluster keeps its own."""
    return jnp.where(moved, jnp.take(part, slot, axis=-1, mode="clip"), full)


def _launch_by_depth(
    launch, nodes, eligible, pod_planes, pod_time, spread, n_eligible, K, lane_major, affinity=None,
    kube=None,
):
    """The megakernel's launch, its lanes chosen by depth. A grid program of
    the kernel holds one tile of clusters (ops/scheduler_kernel._LANE) and
    loops to its deepest lane's queue, every lane computed at every step: a
    backlog's few deep clusters (a burst of a thousand pods among queues of
    twenty), spread over the batch, hold every tile to a thousand steps. So
    where moving them pays (_lanes_to_move, of `n_eligible` (C,) and the pass
    size K) the deep ones sit a first launch out, all their rows ineligible,
    which passes their rows through and leaves each tile the depth of the
    lanes that stayed; a second launch of the same wrapper drains them,
    brought together into one tile, from position 0 as a single launch does;
    and their rows replace the first launch's. The kernel's lanes never read
    one another (selection, placement, commit and the estimator fold are a
    lane's own; the trip count and the live row tiles only decide what is
    swept), so the state cannot tell which clusters shared a tile: every
    output is bit for bit a single launch's (tests/test_cycle_compact.py).

    The tile is a width, never a limit: if more clusters are deep than it
    holds, the one launch is the whole cycle, as it is where none is deep.
    One tile of clusters has nothing to choose and traces the single launch
    alone. Each chip of a mesh takes its own branch: no collective.

    Where the `cond` sits is what a window with no deep cluster pays, and
    was settled on the compiled text and the chip (PERF.md section 6,
    PR 45): round the kernel's OWN operands and results, the planes padded
    to whole lane tiles with the cluster axis last, which the launch
    materialises whatever this function does. Both arms launch on them (the
    wrapper's pads and slices then move nothing) and hand back the padded
    results, so no plane is passed through an arm, none goes from the branch
    into the loop's carry without the slice that follows, and whatever the
    compiler fused into the pads before and the slices after stays fused.

    nodes: (alive, alloc_cpu, alloc_ram); pod_planes: the eight (C, P)
    planes after `eligible` in the wrapper's order; pod_time (C,);
    spread: the wrapper's six operands or None; affinity: the label
    filters' operands (the node plane, then the pod planes) or None; kube:
    the integer scorers' (the two capacity planes, then the pod planes) or
    None. Returns
    (the wrapper's outputs, moved (C,) bool: the clusters a second launch
    drained)."""
    from kubernetriks_tpu.ops.scheduler_kernel import _LANE as R

    C = eligible.shape[0]
    if -(-C // R) == 1:
        return launch(nodes, eligible, pod_planes, pod_time, spread, affinity, kube), jnp.zeros(
            (C,), jnp.bool_
        )

    moved = _lanes_to_move(n_eligible, K, R)
    lanes = -(-C // R) * R
    n_axis = 1 if lane_major else 0

    def tiles(x, axis=0, fill=0):
        # Cluster axis last, padded to whole lane tiles with the wrapper's
        # own fill; a mask as the words the kernel reads.
        x = jnp.moveaxis(x.astype(jnp.int32) if x.dtype == jnp.bool_ else x, axis, -1)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - C)], constant_values=fill)

    def clusters_first(planes, axes):
        return tuple(jnp.moveaxis(x, -1, axis) for x, axis in zip(planes, axes))

    # The queue key's offset as the words the kernel compares (the wrapper
    # makes them of a float32 plane, and of words nothing: inside an arm that
    # cast would be a pass over the plane of its own).
    qwin, qoff, *others = pod_planes
    pod_planes = (qwin, jax.lax.bitcast_convert_type(qoff, jnp.int32), *others)
    in_axes = (n_axis,) * 3 + (0,) * (2 + len(pod_planes))
    spread_axes = (n_axis, 0, 0, 0, 0, 0)
    affinity_axes = (n_axis,) + (0,) * (len(affinity) - 1) if affinity else ()
    kube_axes = (n_axis,) * 2 + (0,) * (len(kube) - 2) if kube else ()
    # The pads are the wrapper's own, made a step early: the same device phase.
    with jax.named_scope("kernel_io"):
        operands = tuple(
            tiles(x, axis)
            for x, axis in zip((*nodes, eligible, *pod_planes, pod_time), in_axes)
        )
        spread_tiles = spread and tuple(
            tiles(x, axis, fill)
            for x, axis, fill in zip(spread, spread_axes, (-1, 0, 0, 0, -1, 0))
        )
        affinity_tiles = affinity and tuple(
            tiles(x, axis) for x, axis in zip(affinity, affinity_axes)
        )
        kube_tiles = kube and tuple(tiles(x, axis) for x, axis in zip(kube, kube_axes))

    def launch_on(operands, spread_tiles, affinity_tiles, kube_tiles):
        """The wrapper on planes that hold the cluster axis last; its outputs
        the same way."""
        ops = clusters_first(operands, in_axes)
        outputs = launch(
            ops[:3], ops[3], ops[4:-1], ops[-1],
            spread_tiles and clusters_first(spread_tiles, spread_axes),
            affinity_tiles and clusters_first(affinity_tiles, affinity_axes),
            kube_tiles and clusters_first(kube_tiles, kube_axes),
        )
        out_axes = (n_axis, n_axis) + (0,) * (len(outputs) - 2)
        return tuple(jnp.moveaxis(x, axis, -1) for x, axis in zip(outputs, out_axes))

    def split():
        moves = jnp.pad(moved, (0, lanes - C))
        first = launch_on(
            operands[:3] + (jnp.where(moves, 0, operands[3]),) + operands[4:],
            spread_tiles,
            affinity_tiles,
            kube_tiles,
        )
        slot, index = _tile_slots(moves, R)
        second = launch_on(
            tuple(_take_lanes(x, index) for x in operands),
            spread_tiles and tuple(_take_lanes(x, index) for x in spread_tiles),
            affinity_tiles and tuple(_take_lanes(x, index) for x in affinity_tiles),
            kube_tiles and tuple(_take_lanes(x, index) for x in kube_tiles),
        )
        return tuple(_put_lanes(a, b, slot, moves) for a, b in zip(first, second))

    outputs = jax.lax.cond(
        moved.any(), split, lambda: launch_on(operands, spread_tiles, affinity_tiles, kube_tiles)
    )
    out_axes = (n_axis, n_axis) + (0,) * (len(outputs) - 2)
    with jax.named_scope("kernel_io"):
        return clusters_first((x[..., :C] for x in outputs), out_axes), moved


def _flag_counts(flags_k: jnp.ndarray) -> jnp.ndarray:
    """(C, 2) how many of a pass's (C, K) decisions carry bit 0 and bit 1 of
    their flags (the spread filter's and the label filters' two facts)."""
    return jnp.stack(
        [(flags_k & 1).sum(axis=1, dtype=jnp.int32), (flags_k >> 1).sum(axis=1, dtype=jnp.int32)],
        axis=1,
    )


class _CyclePasses(NamedTuple):
    """What the passes of one cycle write, the carry of its loop: the
    allocatables, the four pod planes of the commit (start / park: second
    offsets from T, +inf untouched), the queue-time samples by position in
    the cycle (0 where nothing was assigned), the order-free folds, and with
    the spread filter the count table, the placed domains and the two
    counters, with the label filters theirs. `passes` is the loop's own count: it ends when passes * K
    covers the deepest queue."""

    passes: jnp.ndarray  # int32 scalar
    alloc_cpu: jnp.ndarray
    alloc_ram: jnp.ndarray
    phase: jnp.ndarray
    node: jnp.ndarray
    start_tmp: jnp.ndarray
    park_tmp: jnp.ndarray
    q_samples: jnp.ndarray  # (C, ceil(P / K) * K) float32
    q_min: jnp.ndarray
    q_max: jnp.ndarray
    assigned: jnp.ndarray
    late: jnp.ndarray
    spread: Optional[tuple] = None  # (counts (C, G, Z), zone_w (C, P), stats (C, 2))
    # (C, 2) the label filters' counters: attempts of pods that name their
    # nodes, and those of them the labels and taints alone refused.
    named: Optional[jnp.ndarray] = None
    # (C, 2) the label scorers' counters: decisions one of them had something
    # to normalise by, and those of them honoured.
    soft: Optional[jnp.ndarray] = None


@jax.named_scope("cycle")
def _run_scheduling_cycle(
    state: ClusterBatchState,
    W: jnp.ndarray,
    consts: StepConstants,
    max_pods_per_cycle: int,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    conditional_move: bool = False,
    use_pallas_select: bool = False,
    wake=None,
    use_megakernel: bool = True,
    fault_params=None,
    lane_major: bool = False,
    profile=None,
    shard_axis=None,
) -> Tuple[ClusterBatchState, Optional[jnp.ndarray]]:
    """One vectorized kube-scheduler cycle at window W for every cluster
    (scalar equivalent: reference scheduler.rs:246-333). Returns the state
    and, from the megakernel, its (C, 2) int32 sweep counter for the
    telemetry ring (row tiles swept, steps x the block's tiles; None on the
    other formulations, which sweep whole blocks).

    The cycle DRAINS, as the scalar scheduler's `while pop` does: every pod
    eligible at its start is assigned or parked in it, in queue order, pod k
    with the algorithm latency of pods 0..k of the whole cycle in its times.
    max_pods_per_cycle is the size of one PASS and changes no result: the
    megakernel runs its own loop to the deepest lane of a tile in one launch
    (it needs no passes; the number only feeds the drain counters), the
    other three formulations run passes of that many candidates in a
    `lax.while_loop` until no cluster has an eligible pod left, carrying
    only what a pass writes (_CyclePasses). A pass decides positions
    passes * K .. + K - 1 of every cluster that still has work (a cluster
    reaches pass i only by filling the i before it), the metrics fold once
    a cycle from values laid out by position (fold_cycle_totals), and so the
    state after the cycle is bit-identical for any pass size. What is not
    modelled: a cycle whose simulated duration reaches the interval delays
    the scalar scheduler's next cycle; here the windows are fixed, and such
    cycles are counted (MetricArrays.cycle_overruns).

    profile (pipeline.CompiledProfile, static; None = the reference
    default): the compiled scheduler profile whose filter-mask and
    weighted-score expressions the decision core runs — threaded to the
    lax.scan body and every Pallas kernel below, so all four formulations
    of the cycle execute the SAME configured profile (the scalar path's
    composable Filter/Score plugins, lowered; batched/pipeline.py).

    NOTE on a rejected optimization: skipping empty cycles behind a scalar
    lax.cond (predicate: no eligible/parked pod, no wake signal) is exact,
    but measured SLOWER end-to-end — on TPU the cond materializes the full
    state carry through both branches, costing more than the skipped sort.
    (The pass loop is a while_loop over the planes a pass writes anyway.)

    lane_major: the hot node leaves are (N, C) — the Pallas wrappers
    consume/return them without transposes (nodes_lane_major); the lax.scan
    fallback converts at its branch boundary (CPU-parity path only).
    """
    from kubernetriks_tpu.batched.pipeline import DEFAULT_PROFILE

    if profile is None:
        profile = DEFAULT_PROFILE
    C, P = state.pods.phase.shape
    K = min(max_pods_per_cycle, P)
    N = (
        state.nodes.alive.shape[0]
        if lane_major
        else state.nodes.alive.shape[1]
    )

    alive = state.nodes.alive
    alive_count = alive.sum(
        axis=0 if lane_major else 1, dtype=jnp.int32
    ).astype(jnp.float32)
    pod_sched_time = jnp.float32(consts.time_per_node) * alive_count  # (C,)

    # PodTopologySpread: the table the decision core carries through the
    # cycle's placements, counted once here, and the window's pod planes.
    sp = state.spread
    spread_pods = zone_w = counts0 = None
    if sp is not None:
        counts0, zone_alive = spread_counts(state, consts, lane_major)
        group_w, bits_w, zone_w = spread_window_view(sp, state.pod_base, P)
        spread_pods = (group_w, bits_w)

    def spread_nodes(counts):
        return (sp.domain, counts, sp.max_skew, zone_alive)

    # NodeAffinity / TaintToleration: the node plane and the window's pod
    # planes, integers interned at trace compile; nothing is carried.
    af = state.affinity
    affinity_pods = None if af is None else affinity_window_view(af, state.pod_base, P)
    affinity_all = None if af is None else (af.node_bits,) + affinity_pods
    # The integer scorers (a profile that ranks as kube-scheduler does): the
    # two capacity planes, and the window's soft planes where the build has
    # them (then `af` is there too: its node plane holds the bits).
    from kubernetriks_tpu.batched.pipeline import is_integer_profile

    kube_nodes = soft_pods = None
    if is_integer_profile(profile):
        kube_nodes = (state.nodes.cap_cpu, state.nodes.cap_ram)
        if af is not None and af.pod_soft_terms is not None:
            soft_pods = soft_window_view(af, state.pod_base, P)
    kube_all = kube_nodes and kube_nodes + (soft_pods or ())

    pods, last_flush_win, eligible = prepare_queue(
        state, W, consts, conditional_move, wake, lane_major=lane_major
    )
    # The cycle decides every one of them, whatever the formulation.
    n_eligible = eligible.sum(axis=1, dtype=jnp.int32)
    sweep = compacted = None

    if use_pallas and use_pallas_select and use_megakernel:
        # MEGAKERNEL path: queue selection (iterated 3-key argmin), the
        # fit/score/place cycle AND the decision commit run in ONE Pallas
        # launch that drains the queue; the queue-time estimator folds
        # in-kernel. Of timing it takes the per-pod time and makes
        # cycle_durations' products itself, at every position.
        from kubernetriks_tpu.ops.scheduler_kernel import (
            _row_tiles,
            fused_select_cycle_commit,
        )

        interval = jnp.float32(consts.scheduling_interval)
        waited_p = (
            W[:, None] - pods.initial_attempt_ts.win
        ).astype(jnp.float32) * interval - pods.initial_attempt_ts.off

        def launch(nodes, eligible, pod_planes, pod_time, spread, affinity, kube=None):
            # The wrapper reads column 0 of its last K-shaped operand alone.
            time_k = jnp.broadcast_to(pod_time[:, None], (pod_time.shape[0], K))
            return fused_select_cycle_commit(
                *nodes,
                eligible,
                *pod_planes,
                time_k,
                time_k,
                time_k,
                k_pods=K,
                interpret=pallas_interpret,
                nodes_lane_major=lane_major,
                profile=profile,
                spread=spread,
                affinity=affinity,
                kube=kube,
            )

        (
            (alloc_cpu, alloc_ram, phase, node, start_tmp, park_tmp, qstats, *placed),
            compacted,
        ) = _launch_by_depth(
            launch,
            (alive, state.nodes.alloc_cpu, state.nodes.alloc_ram),
            eligible,
            (
                pods.queue_ts.win,
                pods.queue_ts.off,
                pods.queue_seq,
                pods.req_cpu,
                pods.req_ram,
                waited_p,
                pods.phase,
                pods.node,
            ),
            pod_sched_time,
            None if sp is None else spread_nodes(counts0) + spread_pods,
            n_eligible,
            K,
            lane_major,
            affinity_all,
            kube=kube_all,
        )
        soft_out = placed.pop() if soft_pods else None
        named_out = placed.pop() if af is not None else None
        start_tmp = start_tmp + jnp.float32(consts.delta_bind_start)
        totals = CycleTotals(
            assigned=qstats[:, 0].astype(jnp.int32),
            q_total=qstats[:, 1],
            q_total_sq=qstats[:, 2],
            q_min=qstats[:, 3],
            q_max=qstats[:, 4],
            late=qstats[:, 7].astype(jnp.int32),
        )
        n_tiles = _row_tiles(-(-P // 8) * 8)[1]
        sweep = jnp.stack(
            [qstats[:, 5], qstats[:, 6] * jnp.float32(n_tiles)], axis=-1
        ).astype(jnp.int32)
        spread_out = None
        if placed:
            zone_new, stats = placed
            spread_out = (jnp.where(zone_new > jnp.int32(-2), zone_new, zone_w), stats)
    else:
        rows = jnp.arange(C, dtype=jnp.int32)[:, None]
        n_passes_max = -(-P // K)
        if not use_pallas_select:
            # The whole queue sorted once: pass i takes its columns
            # i * K .. + K - 1 (padded so that the last pass's slice holds).
            order = jnp.pad(queue_order(pods, eligible), ((0, 0), (0, n_passes_max * K - P)))

        def decide_select(acc, first):
            # Two-kernel path (KTPU_MEGAKERNEL=0, or a pod block past the
            # megakernel's VMEM gate): in-kernel selection + cycle over the
            # pods no earlier pass decided; the commit is a second kernel.
            from kubernetriks_tpu.ops.scheduler_kernel import (
                fused_select_schedule_cycle,
            )

            cand, valid, assign_k, fitany_k, best_k, alloc_cpu, alloc_ram, *placed_k = (
                fused_select_schedule_cycle(
                    alive,
                    acc.alloc_cpu,
                    acc.alloc_ram,
                    eligible & (acc.phase == PHASE_QUEUED),
                    pods.queue_ts.win,
                    pods.queue_ts.off,
                    pods.queue_seq,
                    pods.req_cpu,
                    pods.req_ram,
                    k_pods=K,
                    interpret=pallas_interpret,
                    nodes_lane_major=lane_major,
                    profile=profile,
                    spread=None if sp is None else spread_nodes(acc.spread[0]) + spread_pods,
                    affinity=affinity_all,
                    kube=kube_all,
                )
            )
            cc = candidates_from_slots(pods, last_flush_win, cand, valid, W, consts)
            return cc, assign_k, valid & ~fitany_k, best_k, alloc_cpu, alloc_ram, placed_k

        def sorted_candidates(first):
            cand = jax.lax.dynamic_slice_in_dim(order, first, K, axis=1)
            valid = (first + jnp.arange(K, dtype=jnp.int32))[None, :] < n_eligible[:, None]
            return candidates_from_slots(
                pods, last_flush_win, cand, valid, W, consts, spread_pods, affinity_pods, soft_pods
            )

        def decide_candidates(acc, first):
            # The (C, N)-heavy core runs as a fused VMEM kernel over the
            # pass's sorted candidates (see ops/scheduler_kernel.py).
            from kubernetriks_tpu.ops.scheduler_kernel import fused_schedule_cycle

            cc = sorted_candidates(first)
            assign_k, fitany_k, best_k, alloc_cpu, alloc_ram, *placed_k = fused_schedule_cycle(
                alive,
                acc.alloc_cpu,
                acc.alloc_ram,
                cc.valid,
                cc.req_cpu,
                cc.req_ram,
                interpret=pallas_interpret,
                nodes_lane_major=lane_major,
                profile=profile,
                spread=None
                if sp is None
                else spread_nodes(acc.spread[0]) + (cc.spread_group, cc.spread_bits),
                affinity=None if af is None else (af.node_bits,) + cc.affinity,
                kube=kube_nodes and kube_nodes + (cc.soft or ()),
            )
            return cc, assign_k, cc.valid & ~fitany_k, best_k, alloc_cpu, alloc_ram, placed_k

        def decide_scan(acc, first):
            # The scan fallback's body is (C, N)-row-major-shaped (per-row
            # scatter-adds, axis-1 argmax); under lane-major state it converts
            # at this branch boundary — the CPU-parity path, where XLA pays
            # layout copies either way.
            from kubernetriks_tpu.batched.pipeline import (
                NodeFacts,
                SoftFacts,
                affinity_names_nodes,
                affinity_node_masks,
                exact_best_node,
                exact_least_allocated_key,
                integer_best_node,
                integer_nodes,
                integer_scores,
                profile_fit_mask,
                soft_honoured,
                profile_fit_score,
                spread_alive_tile,
                spread_node_mask,
                spread_place,
                spread_tiles,
                spread_zone_ok,
            )

            cc = sorted_candidates(first)
            alive_x = alive.T if lane_major else alive
            acpu0 = acc.alloc_cpu.T if lane_major else acc.alloc_cpu
            aram0 = acc.alloc_ram.T if lane_major else acc.alloc_ram
            iota_n = jnp.arange(N, dtype=jnp.int32)[None, :]
            tiles0 = ()
            if sp is not None:
                # The kernels' layout (pipeline.spread_*): domains and nodes on
                # axis 0, clusters on axis 1; a workload's counts one (8, C) tile.
                domain_t = sp.domain if lane_major else sp.domain.T
                Z = counts0.shape[2]
                tiles0, limits = spread_tiles(acc.spread[0]), spread_tiles(sp.max_skew)
                zalive_t = spread_alive_tile(zone_alive)
            n_spread = 0 if sp is None else 2
            if af is not None:
                node_bits_x = af.node_bits.T if lane_major else af.node_bits
            n_affinity = 0 if af is None else len(cc.affinity)
            if kube_nodes is not None:
                caps = integer_nodes(
                    *(x.T if lane_major else x for x in kube_nodes), profile.units
                )

            def body(carry, xs):
                alloc_cpu, alloc_ram, tiles = carry
                valid, req_cpu, req_ram, *cand_planes = xs
                facts = None
                if sp is not None:
                    group, bits = (x[None, :] for x in cand_planes[:n_spread])
                    zone_ok, constrained, closed = spread_zone_ok(
                        list(tiles), limits, zalive_t, group, bits
                    )
                    facts = NodeFacts(
                        spread_ok=spread_node_mask(domain_t, zone_ok, constrained, Z).T
                    )

                # The compiled profile's filter mask + weighted score
                # (pipeline.py; default = Fit + LeastAllocatedResources,
                # reference: plugin.rs:33-63) — the SAME expressions the Pallas
                # kernels inline, so the scan oracle and the kernels cannot
                # drift per profile. Scores are float32 on BOTH batched paths
                # unless the build's requests call for the exact key
                # (pipeline.exact_score_bits); float32 only affects the argmax
                # between near-equal node scores, which lockstep requests
                # never produce.
                nodes_and_pod = (alloc_cpu, alloc_ram, req_cpu[:, None], req_ram[:, None])
                if af is not None:
                    # As the kernels' core (_fit_score_place): the chain
                    # without the two label filters, then with them.
                    *terms, forbid = (
                        x[:, None] for x in cand_planes[n_spread : n_spread + n_affinity]
                    )
                    rest = profile_fit_mask(profile, alive_x, *nodes_and_pod, facts)
                    affinity_ok, taints_ok = affinity_node_masks(node_bits_x, terms, forbid)
                    facts = (facts or NodeFacts())._replace(
                        affinity_ok=affinity_ok, taints_ok=taints_ok
                    )
                part = None
                if kube_nodes is not None:
                    # As the kernels' core: integer scores, the label scorers'
                    # normalised over the feasible nodes (reductions over the
                    # node axis), an integer argmax.
                    fit = profile_fit_mask(profile, alive_x, *nodes_and_pod, facts)
                    soft = None
                    if soft_pods:
                        *wants, weights, soft_forbid = (
                            x[:, None] for x in cand_planes[n_spread + n_affinity :]
                        )
                        soft = SoftFacts(
                            node_bits_x, tuple(wants), weights, soft_forbid, profile.soft_taints
                        )
                    total, part, soft_attempt = integer_scores(
                        profile, fit, *nodes_and_pod, caps, soft, axis=1
                    )
                    best = integer_best_node(total, True, iota_n, axis=1)[:, 0]
                elif profile.exact_bits:
                    fit = profile_fit_mask(profile, alive_x, *nodes_and_pod, facts)
                    hi, lo = exact_least_allocated_key(fit, *nodes_and_pod, profile.exact_bits)
                    best = exact_best_node(hi, lo, True, iota_n, axis=1)[:, 0]
                else:
                    fit, score = profile_fit_score(profile, alive_x, *nodes_and_pod, facts)
                    # Last-max-wins argmax, matching the reference's `>=` sweep
                    # over name-sorted nodes (kube_scheduler.rs:140-150).
                    best = jnp.int32(N - 1) - jax.lax.argmax(score[:, ::-1], 1, jnp.int32)
                any_fit = fit.any(axis=1)

                assign = valid & any_fit
                park = valid & ~any_fit
                rows1 = jnp.arange(C, dtype=jnp.int32)
                best_c = jnp.clip(best, 0, None)
                alloc_cpu = alloc_cpu.at[rows1, best_c].add(jnp.where(assign, -req_cpu, 0))
                alloc_ram = alloc_ram.at[rows1, best_c].add(jnp.where(assign, -req_ram, 0))
                outs = (assign, park, best)
                if sp is not None:
                    zbest = jnp.where(assign, domain_t[best_c, rows1], jnp.int32(-1))
                    tiles = tuple(spread_place(list(tiles), zbest[None, :], assign[None, :], bits))
                    flags = (assign & constrained[0]).astype(jnp.int32) + 2 * (
                        assign & closed[0]
                    ).astype(jnp.int32)
                    outs += (zbest, flags)
                if af is not None:
                    attempt = valid & affinity_names_nodes(forbid[:, 0])
                    outs += (
                        attempt.astype(jnp.int32)
                        + 2 * (attempt & ~any_fit & rest.any(axis=1)).astype(jnp.int32),
                    )
                if part is not None:
                    chosen = assign[:, None] & (iota_n == best[:, None])
                    soft_attempt = valid & soft_attempt[:, 0]
                    outs += (
                        soft_attempt.astype(jnp.int32)
                        + 2 * (soft_attempt & soft_honoured(part, fit, chosen, axis=1)[:, 0]).astype(jnp.int32),
                    )
                return (alloc_cpu, alloc_ram, tiles), outs

            xs = (cc.valid.T, cc.req_cpu.T, cc.req_ram.T)
            if sp is not None:
                xs += (cc.spread_group.T, cc.spread_bits.T)
            if af is not None:
                xs += tuple(x.T for x in cc.affinity)
            if soft_pods:
                xs += tuple(x.T for x in cc.soft)
            (alloc_cpu, alloc_ram, tiles), outs = jax.lax.scan(body, (acpu0, aram0, tiles0), xs)
            assign_k, park_k, best_k, *placed_k = (o.T for o in outs)
            if lane_major:
                alloc_cpu, alloc_ram = alloc_cpu.T, alloc_ram.T
            if sp is not None:
                # The table goes after the spread's two, before the label flags.
                placed_k.insert(2, jnp.stack([t[:Z].T for t in tiles], axis=1))
            return cc, assign_k, park_k, best_k, alloc_cpu, alloc_ram, placed_k

        decide = (
            decide_select if use_pallas and use_pallas_select
            else decide_candidates if use_pallas
            else decide_scan
        )

        def one_pass(acc: _CyclePasses) -> _CyclePasses:
            first = acc.passes * jnp.int32(K)
            cc, assign_k, park_k, best_k, alloc_cpu, alloc_ram, placed_k = decide(acc, first)
            # Timing mechanics: vectorized and shared by ALL THREE decision
            # cores above (and the RL path), so they stay the only divergence.
            q_k, start_s_k, park_s_k = cycle_timing(cc.waited, pod_sched_time, consts, first)
            phase, node, start_p, park_p = scatter_decisions(
                acc.phase, acc.node, cc.cand, assign_k, park_k, best_k, start_s_k, park_s_k,
                use_pallas=use_pallas and use_pallas_select,
                pallas_interpret=pallas_interpret,
            )
            n_assigned = assign_k.sum(axis=1, dtype=jnp.int32)
            soft_acc = named_acc = None
            if soft_pods:
                soft_acc = acc.soft + _flag_counts(placed_k.pop())
            if af is not None:
                named_acc = acc.named + _flag_counts(placed_k.pop())
            spread_acc = None
            if placed_k:
                # The placed domains go back into the pod plane as the
                # decisions' nodes do (candidate slots are unique).
                zbest_k, flags_k, counts = placed_k
                _, zone_acc, stats = acc.spread
                spread_acc = (
                    counts,
                    zone_acc.at[rows, jnp.where(assign_k, cc.cand, P)].set(zbest_k, mode="drop"),
                    stats + _flag_counts(flags_k),
                )
            return _CyclePasses(
                passes=acc.passes + jnp.int32(1),
                alloc_cpu=alloc_cpu,
                alloc_ram=alloc_ram,
                phase=phase,
                node=node,
                start_tmp=jnp.minimum(acc.start_tmp, start_p),
                park_tmp=jnp.minimum(acc.park_tmp, park_p),
                q_samples=jax.lax.dynamic_update_slice_in_dim(
                    acc.q_samples, jnp.where(assign_k, q_k, jnp.float32(0.0)), first, axis=1
                ),
                q_min=jnp.minimum(acc.q_min, jnp.where(assign_k, q_k, INF).min(axis=1)),
                q_max=jnp.maximum(acc.q_max, jnp.where(assign_k, q_k, -INF).max(axis=1)),
                assigned=acc.assigned + n_assigned,
                late=acc.late + jnp.where(acc.passes > 0, n_assigned, 0),
                spread=spread_acc,
                named=named_acc,
                soft=soft_acc,
            )

        zeros_c = jnp.zeros((C,), jnp.int32)
        acc = jax.lax.while_loop(
            lambda acc: (n_eligible > acc.passes * jnp.int32(K)).any(),
            one_pass,
            _CyclePasses(
                passes=jnp.int32(0),
                alloc_cpu=state.nodes.alloc_cpu,
                alloc_ram=state.nodes.alloc_ram,
                phase=pods.phase,
                node=pods.node,
                start_tmp=jnp.full((C, P), INF, jnp.float32),
                park_tmp=jnp.full((C, P), INF, jnp.float32),
                q_samples=jnp.zeros((C, n_passes_max * K), jnp.float32),
                q_min=jnp.full((C,), INF, jnp.float32),
                q_max=jnp.full((C,), -INF, jnp.float32),
                assigned=zeros_c,
                late=zeros_c,
                spread=None if sp is None else (counts0, zone_w, jnp.zeros((C, 2), jnp.int32)),
                named=None if af is None else jnp.zeros((C, 2), jnp.int32),
                soft=jnp.zeros((C, 2), jnp.int32) if soft_pods else None,
            ),
        )
        alloc_cpu, alloc_ram = acc.alloc_cpu, acc.alloc_ram
        phase, node, start_tmp, park_tmp = acc.phase, acc.node, acc.start_tmp, acc.park_tmp
        # The samples lie by position in the cycle, zeros between them: one
        # reduction over the same P columns whatever the pass size.
        q = acc.q_samples[:, :P]
        totals = CycleTotals(
            assigned=acc.assigned,
            q_total=q.sum(axis=1),
            q_total_sq=(q * q).sum(axis=1),
            q_min=acc.q_min,
            q_max=acc.q_max,
            late=acc.late,
        )
        spread_out = None if sp is None else acc.spread[1:]
        named_out = acc.named
        soft_out = acc.soft

    metrics = fold_cycle_totals(
        state.metrics, totals, n_eligible, pod_sched_time, consts, K, compacted
    )
    if soft_out is not None:
        metrics = metrics._replace(
            soft_attempts=metrics.soft_attempts + soft_out[:, 0],
            soft_honoured=metrics.soft_honoured + soft_out[:, 1],
        )
    new_state = commit_scattered_tail(
        state, pods, last_flush_win, W, consts, alloc_cpu, alloc_ram,
        metrics, phase, node, start_tmp, park_tmp,
        fault_params=fault_params,
        shard_axis=shard_axis,
    )
    if spread_out is not None:
        # stats: (C, 2) assignments of constrained pods, and those of them
        # for which the skew had closed a live domain.
        new_zone_w, stats = spread_out
        new_state = new_state._replace(
            spread=_spread_with_zone(sp, state.pod_base, new_zone_w)._replace(
                decisions=sp.decisions + stats[:, 0],
                decisions_bound=sp.decisions_bound + stats[:, 1],
            )
        )
    if named_out is not None:
        new_state = new_state._replace(
            affinity=af._replace(
                attempts=af.attempts + named_out[:, 0],
                attempts_refused=af.attempts_refused + named_out[:, 1],
            )
        )
    return new_state, sweep


@jax.named_scope("bookkeeping")
def _freeze_lanes(
    state: ClusterBatchState,
    state0: ClusterBatchState,
    active: jnp.ndarray,
    lane_major: bool = False,
) -> ClusterBatchState:
    """Lane-async clock protocol (DESIGN §13): revert every state leaf of
    INACTIVE lanes to its pre-window value, so a lane outside its
    [lane_clock, lane_clock + lane_horizon) span parks bit-exactly while
    neighbors keep stepping. `active` is the (C,) bool lane mask; the
    telemetry ring is excluded (inactive lanes still record their
    zero-delta row — the occupancy column needs it) and the hot node
    leaves mask along their own cluster axis (axis 1 inside lane-major
    programs — a bare leading-C broadcast would be the exact hazard the
    shapecontract pass patrols). Pure selects on values the body already
    holds: no reductions, no new syncs."""

    def keep(cur, prev, c_axis):
        shape = [1] * cur.ndim
        shape[c_axis] = active.shape[0]
        return jnp.where(active.reshape(shape), cur, prev)

    nodes = state.nodes
    frozen_nodes = nodes._replace(
        **{
            name: jax.tree.map(
                lambda cur, prev, ax=(
                    1 if (lane_major and name in NODE_HOT_LEAVES) else 0
                ): keep(cur, prev, ax),
                getattr(nodes, name),
                getattr(state0.nodes, name),
            )
            for name in nodes._fields
        }
    )
    # The spread filter's node plane never changes and is laid out like the
    # hot node leaves: it passes through.
    def rest_of(st):
        spread, affinity = st.spread, st.affinity
        return st._replace(
            nodes=None,
            telemetry=None,
            spread=spread if spread is None else spread._replace(domain=None),
            affinity=affinity if affinity is None else affinity._replace(node_bits=None),
        )

    rest = jax.tree.map(
        lambda cur, prev: keep(cur, prev, 0), rest_of(state), rest_of(state0)
    )
    if rest.spread is not None:
        rest = rest._replace(spread=rest.spread._replace(domain=state.spread.domain))
    if rest.affinity is not None:
        rest = rest._replace(
            affinity=rest.affinity._replace(node_bits=state.affinity.node_bits)
        )
    return rest._replace(nodes=frozen_nodes, telemetry=state.telemetry)


@jax.named_scope("bookkeeping")
def _telemetry_record(
    state: ClusterBatchState,
    m0,
    W: jnp.ndarray,
    consts: StepConstants,
    lane_major: bool = False,
    telem_window=None,
    lane_active=None,
    cycle_sweep=None,
    event_chunks=None,
    spread0=None,
):
    """Fold one per-window record row into the device telemetry ring:
    metric-counter deltas vs the window's incoming metrics `m0` plus queue
    depths / alive-node counts / reserve-occupancy gauges read straight
    off the post-window state, the megakernel's sweep counter
    (cycle_sweep, (C, 2); zeros without it) and the event loop's chunk
    count (event_chunks, (C,)). Pure bookkeeping — reads
    simulation state, writes only the ring — so telemetry-on runs are
    bit-identical to telemetry-off on every other leaf
    (tests/test_telemetry.py pins this).
    Cost: two (C, P) phase reductions, one (C, N) reduction, two tiny
    (C, G) occupancy sums and one (C, 1, K) scatter per window, only
    compiled in when the ring exists (state.telemetry is a structural
    static, like `auto`). The occupancy columns are derived from state
    the body already carries (auto counters, pod_base, static geometry) —
    no reductions over the slab or the pod axis beyond the record's own,
    and nothing here runs on the KTPU_WINDOW_RAZOR skip path (the record
    sits after the razor cond, once per executed window)."""
    from kubernetriks_tpu.batched.state import TelemetryRing

    ring = state.telemetry
    m1 = state.metrics
    pods, nodes = state.pods, state.nodes
    queued = (pods.phase == PHASE_QUEUED).sum(axis=1, dtype=jnp.int32)
    unsched = (pods.phase == PHASE_UNSCHEDULABLE).sum(axis=1, dtype=jnp.int32)
    alive = nodes.alive.sum(axis=0 if lane_major else 1, dtype=jnp.int32)
    # Reserve-occupancy gauges (capacity observatory): live HPA replicas
    # (tail - head over groups), consumed CA reserve slots (ca_cursor is
    # monotone — THE saturation driver of ROADMAP #2), and the remaining
    # plain-trace headroom of the sliding pod window. auto-off engines
    # record zeros (their programs never carry the auto pytree anyway).
    if state.auto is not None:
        hpa_used = (state.auto.hpa_tail - state.auto.hpa_head).sum(
            axis=1, dtype=jnp.int32
        )
        ca_used = state.auto.ca_cursor.sum(axis=1, dtype=jnp.int32)
    else:
        hpa_used = jnp.zeros_like(queued)
        ca_used = jnp.zeros_like(queued)
    # The device window covers plain_width plain-trace slots starting at
    # pod_base (plain_width = full device axis on non-segmented runs);
    # trace_pod_bound defaults to a huge sentinel there, so the headroom
    # column lands >= UNBOUNDED_SENTINEL and the observatory skips it.
    # Scalar int32 arithmetic on values the body already carries.
    plain_width = jnp.minimum(
        jnp.int32(pods.phase.shape[1]),
        consts.trace_pod_bound - consts.resident_shift,
    )
    headroom = jnp.maximum(
        consts.trace_pod_bound - state.pod_base - plain_width, 0
    )
    hpa = (m1.scaled_up_pods - m0.scaled_up_pods) + (
        m1.scaled_down_pods - m0.scaled_down_pods
    )
    ca = (m1.scaled_up_nodes - m0.scaled_up_nodes) + (
        m1.scaled_down_nodes - m0.scaled_down_nodes
    )
    faults = (
        (m1.node_crashes - m0.node_crashes)
        + (m1.node_recoveries - m0.node_recoveries)
        + (m1.pod_interruptions - m0.pod_interruptions)
        + (m1.pod_restarts - m0.pod_restarts)
        + (m1.pods_failed - m0.pods_failed)
    )
    # Lane-async mode: the window column records the GLOBAL window index
    # (telem_window) so it stays lane-uniform — ring.merge_snapshot keys
    # on buf[0, :, 0] — while every other column carries the lane's own
    # values; the lane_active bit is the occupancy observable. Outside
    # lane-async builds both default to the wave-aligned behavior
    # (window = W, active = 1 everywhere).
    row = jnp.stack(
        [
            telem_window if telem_window is not None else W,
            m1.scheduling_decisions - m0.scheduling_decisions,
            queued,
            unsched,
            hpa,
            ca,
            faults,
            alive,
            hpa_used,
            ca_used,
            headroom,
            (
                lane_active.astype(jnp.int32)
                if lane_active is not None
                else jnp.ones_like(W)
            ),
            *(
                (cycle_sweep[:, 0], cycle_sweep[:, 1])
                if cycle_sweep is not None
                else (jnp.zeros_like(W),) * 2
            ),
            event_chunks if event_chunks is not None else jnp.zeros_like(W),
            m1.frees_deferred - m0.frees_deferred,
            (
                state.spread.decisions_bound - spread0.decisions_bound
                if spread0 is not None
                else jnp.zeros_like(W)
            ),
        ],
        axis=-1,
    ).astype(jnp.int32)
    C, R = ring.buf.shape[0], ring.buf.shape[1]
    rows = jnp.arange(C, dtype=jnp.int32)
    buf = ring.buf.at[rows, jnp.mod(ring.cursor, R)].set(row)
    return TelemetryRing(buf=buf, cursor=ring.cursor + 1)


def _window_body(
    state: ClusterBatchState,
    slab: TraceSlab,
    W: jnp.ndarray,
    consts: StepConstants,
    max_events_per_window: int,
    max_pods_per_cycle: int,
    autoscale_statics=None,
    max_ca_pods_per_cycle: int = 64,
    max_pods_per_scale_down: int = 8,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    conditional_move: bool = False,
    use_pallas_select: bool = False,
    use_megakernel: bool = True,
    hpa_seg=None,
    fault_params=None,
    name_ranks=None,
    lane_major: bool = False,
    window_razor: bool = True,
    reclaim: bool = False,
    profile=None,
    freeze_lanes: bool = True,
    shard_axis=None,
) -> ClusterBatchState:
    with jax.named_scope("bookkeeping"):
        W = jnp.broadcast_to(jnp.asarray(W, jnp.int32), state.time.shape)
    # Lane-async clock protocol (engine lane_async=True, DESIGN §13): each
    # lane steps its VIRTUAL window W - lane_clock[c] — bit-identical to a
    # fresh run's window of that index — and is active only inside
    # [0, lane_horizon[c]). Inactive lanes still execute the body (the
    # clamp keeps the virtual index sane) and are reverted wholesale by
    # _freeze_lanes before the telemetry record, so a finished lane parks
    # at its exact final state until the host re-seeds it. lane_clock is
    # traced (C,) data: re-seeding never recompiles. freeze_lanes=False is
    # the ALL-ACTIVE fast path: the engine's host clock mirrors prove no
    # lane enters or leaves its span during the dispatched chunk, so the
    # state-wide revert selects (pure identities there) are compiled out —
    # bit-identical by construction, and the dominant per-window saving of
    # the lane-async executor (the freeze is O(state) every window).
    telem_W = W
    lane_active = None
    state0 = None
    if consts.lane_clock is not None:
        with jax.named_scope("bookkeeping"):
            rel = W - consts.lane_clock
            lane_active = (rel >= 0) & (rel < consts.lane_horizon)
            W = jnp.maximum(rel, 0)
        state0 = state if freeze_lanes else None
    # Telemetry ring (flight recorder): the window's incoming metric
    # counters, diffed at the end of the body into one per-window record.
    m0 = state.metrics
    spread0 = state.spread
    # CA slot reclaim (KTPU_RECLAIM): compaction runs FIRST — a clean
    # state boundary, and a scale-up later in this window then sees every
    # reclaimable slot (the loud starvation bound can only fire on true
    # live-demand exhaustion). See autoscale.ca_reclaim_pass.
    if reclaim and autoscale_statics is not None and state.auto is not None:
        from kubernetriks_tpu.batched.autoscale import ca_reclaim_pass

        state, auto_r = ca_reclaim_pass(
            state,
            state.auto,
            autoscale_statics,
            nodes_lane_major=lane_major,
        )
        state = state._replace(auto=auto_r)
    # Same-time reschedule/retry ordering needs lexicographic name ranks to
    # match the scalar's sorted-name walks; they come from the autoscale
    # statics when autoscalers are on, else from the engine's standalone
    # rank tables (built for fault-injection runs, where node crashes
    # produce large same-instant reschedule batches).
    if autoscale_statics is not None:
        node_name_rank = autoscale_statics.node_name_rank
        pod_name_rank = autoscale_statics.pod_name_rank
    elif name_ranks is not None:
        node_name_rank, pod_name_rank = name_ranks
    else:
        node_name_rank = pod_name_rank = None
    node_key_fn = None
    if (
        reclaim
        and autoscale_statics is not None
        and state.auto is not None
        and state.auto.ca_alloc is not None
    ):
        # Under reclaim the same-window reschedule batches order removed
        # CA nodes by their occupants' CURRENT names, not the slots'
        # static first-occupant names; the key derives from the
        # allocation indices and is only computed inside the (rare)
        # reschedule cond. auto is captured here — event application
        # never mutates it.
        from kubernetriks_tpu.batched.autoscale import ca_name_order

        auto0 = state.auto
        node_key_fn = lambda: ca_name_order(  # noqa: E731
            auto0, autoscale_statics
        )[1]

    state, wake, event_chunks = _apply_window_events(
        state,
        slab,
        W,
        consts,
        max_events_per_window,
        conditional_move,
        use_pallas,
        pallas_interpret,
        use_pallas_select,
        node_name_rank=node_name_rank,
        pod_name_rank=pod_name_rank,
        fault_params=fault_params,
        lane_major=lane_major,
        window_razor=window_razor,
        node_key_fn=node_key_fn,
    )
    # Pre-cycle shadows for the CA's early-snapshot case (a CA storage
    # snapshot landing before this window's commit-visibility time must not
    # see this cycle's assignments/parks — ca_pass docstring).
    pre_cycle = (
        state.pods.phase,
        state.pods.attempts,
        state.nodes.alloc_cpu,
        state.nodes.alloc_ram,
    )
    W_cycle = W
    if state0 is not None:
        # A lane outside its span still executes the window body on a
        # virtual clock that runs on past its horizon, and _freeze_lanes
        # then reverts it wholesale. Its due events pile up round after
        # round, and a cycle that drains would decide the whole pile each
        # window only to have it reverted (a what-if round took 73 ms where
        # it took 47: chip, PR 41). Such a lane's cycle runs at window 0,
        # before which no pod is queued: nothing to decide, and the same
        # state after the revert, bit for bit.
        W_cycle = jnp.where(lane_active, W, 0)
    state, cycle_sweep = _run_scheduling_cycle(
        state,
        W_cycle,
        consts,
        max_pods_per_cycle,
        use_pallas,
        pallas_interpret,
        conditional_move,
        use_pallas_select,
        wake=wake,
        use_megakernel=use_megakernel,
        fault_params=fault_params,
        lane_major=lane_major,
        profile=profile,
        shard_axis=shard_axis,
    )
    if autoscale_statics is not None:
        # Autoscaler ticks due by this window run after the scheduling cycle
        # (the scalar snapshot lands between cycles; SURVEY.md §3.5); their
        # effects land at composed future times via the pending-effect arrays.
        from kubernetriks_tpu.batched.autoscale import ca_pass, hpa_pass

        auto = state.auto
        # hpa_seg: STATIC (lo, hi) group-slot bounds (engine._hpa_seg) so
        # the HPA body and its not-due cond carry only the group slice;
        # (0, 0) = no group slots anywhere, skip the pass entirely.
        if hpa_seg != (0, 0):
            state, auto = hpa_pass(
                state, auto, autoscale_statics, W, consts, seg=hpa_seg
            )
        state, auto = ca_pass(
            state,
            auto,
            autoscale_statics,
            W,
            consts,
            max_ca_pods_per_cycle,
            max_pods_per_scale_down,
            pre=pre_cycle,
            # Each CA kernel gates on its own VMEM fits-check inside.
            use_pallas=use_pallas,
            pallas_interpret=pallas_interpret,
            nodes_lane_major=lane_major,
            reclaim=reclaim,
        )
        state = state._replace(auto=auto)
    if lane_active is not None and state0 is not None:
        # Freeze BEFORE the record: frozen lanes then diff m1 == m0 and
        # record zero-delta rows (their gauges re-read the parked state),
        # so the ring never carries phantom progress for an idle lane.
        state = _freeze_lanes(state, state0, lane_active, lane_major)
    if state.telemetry is not None:
        state = state._replace(
            telemetry=_telemetry_record(
                state,
                m0,
                W,
                consts,
                lane_major=lane_major,
                telem_window=telem_W,
                lane_active=lane_active,
                cycle_sweep=cycle_sweep,
                event_chunks=event_chunks,
                spread0=spread0,
            )
        )
    return state


@jax.named_scope("bookkeeping")
def gauge_snapshot(
    state: ClusterBatchState, lane_major: bool = False
) -> jnp.ndarray:
    """(C, 7) on-device gauge readings after a window: current nodes/pods,
    scheduling-queue length, node-average and cluster-total cpu/ram
    utilization (scalar equivalents: GaugeMetrics fields fed from
    collect_utilizations, reference: src/metrics/collector.rs:166-192,
    352-390). Utilization = requests / capacity over alive nodes."""
    if lane_major:
        state = swap_node_layout(state)
    nodes, pods = state.nodes, state.pods
    alive = nodes.alive
    alive_f = alive.astype(jnp.float32)
    n_alive = alive.sum(axis=1, dtype=jnp.int32)
    n_alive_f = jnp.maximum(n_alive, 1).astype(jnp.float32)

    live_pod = (
        (pods.phase == PHASE_QUEUED)
        | (pods.phase == PHASE_UNSCHEDULABLE)
        | (pods.phase == PHASE_RUNNING)
    )
    queued = (pods.phase == PHASE_QUEUED) | (pods.phase == PHASE_UNSCHEDULABLE)

    cap_cpu = jnp.maximum(nodes.cap_cpu, 1).astype(jnp.float32)
    cap_ram = jnp.maximum(nodes.cap_ram, 1).astype(jnp.float32)
    used_cpu = (nodes.cap_cpu - nodes.alloc_cpu).astype(jnp.float32) * alive_f
    used_ram = (nodes.cap_ram - nodes.alloc_ram).astype(jnp.float32) * alive_f

    node_avg_cpu = (used_cpu / cap_cpu).sum(axis=1) / n_alive_f
    node_avg_ram = (used_ram / cap_ram).sum(axis=1) / n_alive_f
    total_cap_cpu = jnp.maximum((cap_cpu * alive_f).sum(axis=1), 1.0)
    total_cap_ram = jnp.maximum((cap_ram * alive_f).sum(axis=1), 1.0)

    return jnp.stack(
        [
            n_alive.astype(jnp.float32),
            live_pod.sum(axis=1, dtype=jnp.int32).astype(jnp.float32),
            queued.sum(axis=1, dtype=jnp.int32).astype(jnp.float32),
            node_avg_cpu,
            node_avg_ram,
            used_cpu.sum(axis=1) / total_cap_cpu,
            used_ram.sum(axis=1) / total_cap_ram,
        ],
        axis=-1,
    )


_STEP_STATICS = (
    "max_events_per_window",
    "max_pods_per_cycle",
    "max_ca_pods_per_cycle",
    "max_pods_per_scale_down",
    "use_pallas",
    "pallas_interpret",
    "conditional_move",
    # sharding.ClusterShards (the build's mesh and cluster axis) or None;
    # read by the entry's over_clusters wrapper, which puts ONE shard_map
    # round the whole program. None = no mesh: the body runs as it is.
    "shards",
    "use_pallas_select",
    "use_megakernel",
    "hpa_seg",
    # chaos.FaultParams (hashable NamedTuple of scalars) or None; None
    # compiles programs textually identical to the pre-chaos build.
    "fault_params",
    # Perf statics, each with a flags.py A/B switch: lane-major hot node
    # state (KTPU_LANE_MAJOR) and the empty-window resolution razor
    # (KTPU_WINDOW_RAZOR). Both are bit-exact either way.
    "lane_major",
    "window_razor",
    # CA slot reclaim (KTPU_RECLAIM, r14): the compaction pass at the top
    # of the window body + allocation-index name orders in the CA passes.
    # Off compiles the pre-reclaim programs (the A/B bit-identity gate).
    "reclaim",
    # pipeline.CompiledProfile (hashable NamedTuple of plugin names +
    # weights) or None; the compiled scheduler profile whose filter/score
    # expressions the decision core runs. None compiles programs identical
    # to the pre-profile build (the reference default). Co-travels with
    # fault_params through every window-program entry (the ktpu-lint
    # jit-static pass enforces the pairing).
    "profile",
)


def _out_state(axis, _statics):
    """out_specs of a window program that returns the state alone: every
    leaf leads with the cluster axis."""
    return PartitionSpec(axis)


@partial(jax.jit, static_argnames=_STEP_STATICS)
@over_clusters(_STEP_STATICS, _out_state)
def window_step(
    state: ClusterBatchState,
    slab: TraceSlab,
    W: jnp.ndarray,
    consts: StepConstants,
    max_events_per_window: int,
    max_pods_per_cycle: int,
    autoscale_statics=None,
    max_ca_pods_per_cycle: int = 64,
    max_pods_per_scale_down: int = 8,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    conditional_move: bool = False,
    shards=None,
    use_pallas_select: bool = False,
    use_megakernel: bool = True,
    hpa_seg=None,
    fault_params=None,
    name_ranks=None,
    lane_major: bool = False,
    window_razor: bool = True,
    reclaim: bool = False,
    profile=None,
) -> ClusterBatchState:
    """Advance every cluster through scheduling-cycle window index W.

    Lane-major conversion happens at the jit boundary (state at rest is
    ALWAYS row-major — see state.swap_node_layout): two transposes per
    dispatch instead of two per kernel boundary."""
    if lane_major:
        state = swap_node_layout(state)
    state = _window_body(
        state,
        slab,
        W,
        consts,
        max_events_per_window,
        max_pods_per_cycle,
        autoscale_statics,
        max_ca_pods_per_cycle,
        max_pods_per_scale_down,
        use_pallas,
        pallas_interpret,
        conditional_move,
        use_pallas_select,
        use_megakernel=use_megakernel,
        hpa_seg=hpa_seg,
        fault_params=fault_params,
        name_ranks=name_ranks,
        lane_major=lane_major,
        window_razor=window_razor,
        reclaim=reclaim,
        profile=profile,
        shard_axis=shard_axis_of(shards),
    )
    if lane_major:
        state = swap_node_layout(state)
    return state


@jax.named_scope("bookkeeping")
def _next_interesting_window(
    state: ClusterBatchState,
    slab: TraceSlab,
    W: jnp.ndarray,
    consts: StepConstants,
    autoscale_statics,
    flush_windows: int,
    shard_axis=None,
) -> jnp.ndarray:
    """First window index > W whose body could change state (scalar, min
    over clusters). A window with none of the triggers below is PROVABLY the
    identity on all simulation state except the cadence bookkeeping that
    _catch_up_bookkeeping replays (last_flush_win, hpa_next/ca_next, time):
    no due trace events, no due pod finishes, no pending autoscaler effects,
    no eligible queued pod (an empty cycle assigns/parks/measures nothing
    and signals are already zeroed by the previous commit), no flush window
    while pods are parked, and no CA/HPA tick that could act.

    Every trigger is CONSERVATIVE (running a window early is always safe —
    window execution at any index is semantics-preserving); what is never
    allowed is skipping past a trigger. Under a mesh each shard computes
    its own candidate and ONE pmin at the end takes the least: a trigger is
    conservative for the shard's clusters, so the least is for all."""
    from kubernetriks_tpu.batched.timerep import INF_WIN

    pods, nodes = state.pods, state.nodes
    big = jnp.int32(INF_WIN)

    def amin(x):
        return jnp.min(x).astype(jnp.int32)

    # Next unapplied trace event (applied when stepping win+1); a cursor at
    # the end reads the slab's sentinel, INF_WIN.
    cand = amin(slab.win_at(state.event_cursor)) + 1

    # Pod finishes (resolved in the finish pair's window or the next; running
    # the earlier window is a harmless no-op when off > 0).
    # A free on the pending-free channel has its node-side time behind W,
    # so no window is skipped while one is held.
    holds = alloc_holders(pods, consts)
    cand = jnp.minimum(cand, amin(jnp.where(holds, pods.finish_time.win, big)))

    # Pending effect times (applied when stepping win+1): CA node
    # creations/removals, HPA pod removals.
    cand = jnp.minimum(cand, amin(nodes.create_time.win) + 1)
    cand = jnp.minimum(cand, amin(nodes.remove_time.win) + 1)
    cand = jnp.minimum(cand, amin(pods.removal_time.win) + 1)

    # Queued pods become eligible at queue_ts.win + 1.
    queued = pods.phase == PHASE_QUEUED
    cand = jnp.minimum(cand, amin(jnp.where(queued, pods.queue_ts.win, big)) + 1)

    # Parked pods: the flush cadence can wake them, and a due CA tick can
    # scale up from the unscheduled cache.
    parked_any = (pods.phase == PHASE_UNSCHEDULABLE).any()
    flush_next = jnp.min(state.last_flush_win) + jnp.int32(flush_windows)
    cand = jnp.minimum(cand, jnp.where(parked_any, flush_next, big))

    if autoscale_statics is not None and state.auto is not None:
        auto = state.auto
        # The CA cycle runs in the window containing its storage snapshot
        # (drifting cadence; autoscale.ca_pass docstring).
        ca_snap_t = t_add(
            auto.ca_next, autoscale_statics.ca_snap,
            jnp.float32(consts.scheduling_interval),
        )
        ca_tick = amin(ca_snap_t.win)
        hpa_tick = amin(auto.hpa_next.win)
        ca_can_act = parked_any | (auto.ca_count.sum() > 0)
        cand = jnp.minimum(cand, jnp.where(ca_can_act, ca_tick, big))
        # HPA ticks are interesting whenever a group could be active (the
        # engine parks hpa_next at +inf otherwise, making this a no-op).
        cand = jnp.minimum(cand, hpa_tick)
        if auto.col_next is not None:
            # HPA collection latch (r14 staleness fix): the 60 s metrics
            # collection snapshots the load curve AT its window — a skipped
            # collection would latch a different utilization later, so its
            # tick is a trigger like the HPA's own.
            cand = jnp.minimum(cand, amin(auto.col_next.win))

    if shard_axis is not None:
        cand = all_min(cand, shard_axis)
    return jnp.maximum(W + jnp.int32(1), cand)


@jax.named_scope("bookkeeping")
def _catch_up_bookkeeping(
    state: ClusterBatchState,
    from_w: jnp.ndarray,
    to_w: jnp.ndarray,
    consts: StepConstants,
    autoscale_statics,
) -> ClusterBatchState:
    """Replay the cadence bookkeeping of the skipped windows [from_w, to_w)
    with the SAME per-window arithmetic the window body uses, so a
    fast-forwarded run's state is bit-identical to continuous stepping:
    last_flush_win advances at the flush cadence, due autoscaler ticks
    advance hpa_next/ca_next once per window, and time tracks the last
    covered window. O(skipped windows) scalar work per cluster — ~10 tiny
    (C,)-shaped ops per window vs ~2k for a full body."""
    interval = jnp.float32(consts.scheduling_interval)
    has_auto = autoscale_statics is not None and state.auto is not None

    def body(carry):
        w, last_flush, hpa_next, ca_next = carry
        wc = jnp.broadcast_to(w, last_flush.shape)
        flush_now = (wc - last_flush).astype(jnp.float32) * interval >= jnp.float32(
            consts.flush_interval
        )
        last_flush = jnp.where(flush_now, wc, last_flush)
        if has_auto:
            T = TPair(win=wc, off=jnp.zeros_like(hpa_next.off))
            hpa_next = t_where(
                t_le(hpa_next, T),
                t_add(hpa_next, autoscale_statics.hpa_interval, interval),
                hpa_next,
            )
            # Same due/advance arithmetic as ca_pass: the cycle belongs to
            # the window containing its storage snapshot; the period is the
            # drifting round-trip + scan (autoscale.ca_pass docstring).
            T1 = TPair(win=wc + jnp.int32(1), off=jnp.zeros_like(ca_next.off))
            ca_due = t_lt(
                t_add(ca_next, autoscale_statics.ca_snap, interval), T1
            )
            ca_next = t_where(
                ca_due,
                t_add(ca_next, autoscale_statics.ca_period, interval),
                ca_next,
            )
        return (w + jnp.int32(1), last_flush, hpa_next, ca_next)

    if has_auto:
        hpa0, ca0 = state.auto.hpa_next, state.auto.ca_next
    else:
        dummy = TPair(
            win=jnp.zeros_like(state.last_flush_win),
            off=jnp.zeros(state.last_flush_win.shape, jnp.float32),
        )
        hpa0, ca0 = dummy, dummy
    _, last_flush, hpa_next, ca_next = jax.lax.while_loop(
        lambda carry: carry[0] < to_w,
        body,
        (jnp.asarray(from_w, jnp.int32), state.last_flush_win, hpa0, ca0),
    )
    state = state._replace(
        last_flush_win=last_flush,
        time=jnp.maximum(state.time, to_w - 1),
    )
    if has_auto:
        state = state._replace(
            auto=state.auto._replace(hpa_next=hpa_next, ca_next=ca_next)
        )
    return state


_SKIP_STATICS = _STEP_STATICS + ("flush_windows",)


@over_clusters(_SKIP_STATICS, _out_state)
def _run_windows_skip_impl(
    state: ClusterBatchState,
    slab: TraceSlab,
    first: jnp.ndarray,
    last: jnp.ndarray,
    consts: StepConstants,
    max_events_per_window: int,
    max_pods_per_cycle: int,
    autoscale_statics=None,
    max_ca_pods_per_cycle: int = 64,
    max_pods_per_scale_down: int = 8,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    conditional_move: bool = False,
    shards=None,
    use_pallas_select: bool = False,
    use_megakernel: bool = True,
    flush_windows: int = 3,
    hpa_seg=None,
    fault_params=None,
    name_ranks=None,
    lane_major: bool = False,
    window_razor: bool = True,
    reclaim: bool = False,
    profile=None,
):
    """run_windows with FAST-FORWARD over provably no-op windows: a dynamic
    while_loop executes only interesting windows (see
    _next_interesting_window) and replays the skipped windows' cadence
    bookkeeping exactly, so the final state is bit-identical to stepping
    every index in [first, last]. One compiled program serves any span
    (first/last are traced scalars). No per-window gauge collection — the
    engine falls back to run_windows when gauges are on. Under a mesh
    the next window is the earliest any shard needs (one scalar pmin a loop
    iteration), so every shard runs the same windows."""
    shard_axis = shard_axis_of(shards)
    if lane_major:
        # _next_interesting_window / _catch_up_bookkeeping read only
        # row-major leaves (pending pairs, pods), so the lane-major carry
        # flows through the whole skip loop untouched.
        state = swap_node_layout(state)

    def cond(carry):
        _, W = carry
        with jax.named_scope("bookkeeping"):
            return W <= last

    def body(carry):
        state, W = carry
        state = _window_body(
            state,
            slab,
            W,
            consts,
            max_events_per_window,
            max_pods_per_cycle,
            autoscale_statics,
            max_ca_pods_per_cycle,
            max_pods_per_scale_down,
            use_pallas,
            pallas_interpret,
            conditional_move,
            use_pallas_select,
            use_megakernel=use_megakernel,
            hpa_seg=hpa_seg,
            fault_params=fault_params,
            name_ranks=name_ranks,
            lane_major=lane_major,
            window_razor=window_razor,
            reclaim=reclaim,
            profile=profile,
            shard_axis=shard_axis,
        )
        with jax.named_scope("bookkeeping"):
            W_next = jnp.minimum(
                _next_interesting_window(
                    state, slab, W, consts, autoscale_statics, flush_windows,
                    shard_axis,
                ),
                last + jnp.int32(1),
            )
            state = _catch_up_bookkeeping(
                state, W + jnp.int32(1), W_next, consts, autoscale_statics
            )
        return state, W_next

    state, _ = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(first, jnp.int32))
    )
    if lane_major:
        state = swap_node_layout(state)
    return state


# Undonated (pure) and donated jit entries share one traced body. The engine's
# steady-state loop uses the DONATED variants: the full (C,N)/(C,P) state is
# consumed and updated in place instead of being re-materialized into fresh
# device buffers on every dispatch (the composed path dispatches popcount(span)
# chunks per slide span, so the per-dispatch allocate+copy of the whole state
# was pure overhead). Donated and undonated programs are bit-identical —
# tests/test_window_donation_dispatch.py pins it — but a donated call INVALIDATES its
# input state; callers that keep the input (tests, warm-up against a scratch
# copy) use the undonated names.
run_windows_skip = partial(jax.jit, static_argnames=_SKIP_STATICS)(
    _run_windows_skip_impl
)
run_windows_skip_donated = jax.jit(
    _run_windows_skip_impl,
    static_argnames=_SKIP_STATICS,
    donate_argnums=(0,),
)


_WINDOWS_STATICS = _STEP_STATICS + ("collect_gauges", "freeze_lanes")


def _out_state_gauges(axis, statics):
    """(state, (Wn, C, 7) gauges) with collect_gauges, else the state."""
    if statics["collect_gauges"]:
        return PartitionSpec(axis), PartitionSpec(None, axis)
    return PartitionSpec(axis)


@over_clusters(_WINDOWS_STATICS, _out_state_gauges, replicated=("window_idxs",))
def _run_windows_impl(
    state: ClusterBatchState,
    slab: TraceSlab,
    window_idxs: jnp.ndarray,
    consts: StepConstants,
    max_events_per_window: int,
    max_pods_per_cycle: int,
    autoscale_statics=None,
    max_ca_pods_per_cycle: int = 64,
    max_pods_per_scale_down: int = 8,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    conditional_move: bool = False,
    collect_gauges: bool = False,
    shards=None,
    use_pallas_select: bool = False,
    use_megakernel: bool = True,
    hpa_seg=None,
    fault_params=None,
    name_ranks=None,
    lane_major: bool = False,
    window_razor: bool = True,
    reclaim: bool = False,
    profile=None,
    freeze_lanes: bool = True,
):
    """Scan a whole sequence of scheduling-cycle windows on-device (the hot
    benchmark loop: no host round-trips between cycles). window_idxs: (Wn,)
    int32 consecutive window indices.

    With collect_gauges, returns (state, (Wn, C, 7) gauge time-series) — the
    batched analog of the scalar 5 s gauge CSV cycle (one sample per window,
    since batched state only changes at window boundaries)."""
    if lane_major:
        state = swap_node_layout(state)

    def body(carry, w):
        new = _window_body(
            carry,
            slab,
            w,
            consts,
            max_events_per_window,
            max_pods_per_cycle,
            autoscale_statics,
            max_ca_pods_per_cycle,
            max_pods_per_scale_down,
            use_pallas,
            pallas_interpret,
            conditional_move,
            use_pallas_select,
            use_megakernel=use_megakernel,
            hpa_seg=hpa_seg,
            fault_params=fault_params,
            name_ranks=name_ranks,
            lane_major=lane_major,
            window_razor=window_razor,
            reclaim=reclaim,
            profile=profile,
            freeze_lanes=freeze_lanes,
            shard_axis=shard_axis_of(shards),
        )
        return new, (
            gauge_snapshot(new, lane_major=lane_major)
            if collect_gauges
            else None
        )

    state, gauges = jax.lax.scan(body, state, jnp.asarray(window_idxs, jnp.int32))
    if lane_major:
        state = swap_node_layout(state)
    if collect_gauges:
        return state, gauges
    return state


run_windows = partial(jax.jit, static_argnames=_WINDOWS_STATICS)(
    _run_windows_impl
)
run_windows_donated = jax.jit(
    _run_windows_impl,
    static_argnames=_WINDOWS_STATICS,
    donate_argnums=(0,),
)


# --- sliding-window slide primitives ----------------------------------------
# Shared by the engine's two-dispatch slide path, the fused chunk+slide
# megastep (engine._fused_chunk_slide) and the superspan executor below.


@jax.named_scope("slide")
def _slide_shift_core(phase, create_win_pay, base, shard_axis=None):
    """The window-shift amount, computed ON DEVICE: the leading run of
    terminal-or-padding pod slots across every cluster (min over C of each
    row's first blocking slot; inside a window program's shard_map over
    every shard's, so pod_base stays uniform). Bit-identical to the host
    formulation in engine._advance_pod_window (same terminal set, same padding rule); only
    a 4-byte scalar reaches the host instead of the full (C, W) phase
    fetch. `base` indexes create_win_pay's columns — GLOBAL plain slots for
    the whole-trace payload, stage-relative under a bounded RefillStage."""
    C, W = phase.shape  # phase is pre-sliced to the plain window [0, W)
    no_create = jnp.int32(np.iinfo(np.int32).max)
    seg = jax.lax.dynamic_slice(create_win_pay, (jnp.int32(0), base), (C, W))
    terminal = (
        (phase == PHASE_SUCCEEDED)
        | (phase == PHASE_REMOVED)
        | (phase == PHASE_FAILED)
    )
    padding = (phase == PHASE_EMPTY) & (seg == no_create)
    blocking = ~(terminal | padding)
    first_live = jnp.where(
        blocking.any(axis=1),
        jnp.argmax(blocking, axis=1).astype(jnp.int32),
        jnp.int32(W),
    )
    return all_min(first_live, shard_axis).astype(jnp.int32)


@jax.named_scope("slide")
def _quantize_shift_device(s0, W: int):
    """Device mirror of _advance_pod_window's host shift quantization (same
    small set of slide amounts, so fused and unfused runs follow identical
    slide trajectories). s0 == 0 maps to 0 — the fused program's "no slide
    possible" flag, read back by the engine to trigger window growth."""
    quantum = max(W // 8, 1)
    # Largest power of two <= s0 (bit-smear; 0 for s0 == 0), the host path's
    # 1 << (s.bit_length() - 1) fallback.
    v = s0
    for sh in (1, 2, 4, 8, 16):
        v = v | (v >> sh)
    s = jnp.where(s0 >= quantum, jnp.int32(quantum), v - (v >> 1))
    if W // 4 > 0:
        s = jnp.where(s0 >= W // 4, jnp.int32(W // 4), s)
    if W // 2 > 0:
        s = jnp.where(s0 >= W // 2, jnp.int32(W // 2), s)
    return s.astype(jnp.int32)


@jax.named_scope("slide")
def _slide_apply_traced(pods, rank, pay, base, s, W: int):
    """Window slide with a TRACED shift amount (s == 0 is the identity): the
    block-move twin of engine._slide_apply_device, so ONE compiled program
    covers every quantized shift and the slide can fuse into the
    window-chunk program (engine._fused_chunk_slide) or the superspan loop
    (run_superspan). The shift and the base are ONE scalar each for the
    whole batch, so every plane moves as a dynamic_slice at that scalar: no
    gather. Bit-identical to the concat path: shifted window slots copy
    their source slot, refill slots combine the device payload with the SAME
    fresh-slot constructor init_state uses, and the resident pod-group tail
    (device slots >= W) is untouched. `base` is in the payload's own column
    coordinates (see _slide_shift_core).

    A dynamic_slice moves a start that would run off the end instead of
    failing, so both reads stay inside their operand by construction: the
    window is extended by W columns (s <= W), and the payload is read at
    base + s over W columns, whose last, base + s + W - 1, is the last a
    refill needs. The callers promise that one in range (the superspan's
    `exhausted` exit for a RefillStage; a slide only triggers at
    base + W < T and the whole-trace payload is padded to T + W)."""
    from kubernetriks_tpu.batched.state import fresh_pod_arrays

    C = pods.phase.shape[0]
    zero = jnp.int32(0)
    refill = jnp.arange(W, dtype=jnp.int32)[None, :] >= (jnp.int32(W) - s)

    def pg(a):
        # Window slot i's payload column is (base + s) + i.
        return jax.lax.dynamic_slice(a, (zero, base + s), (C, W))

    def move(old, fr):
        # Slot i takes slot i + s of the window; what the extension holds
        # is never kept (exactly the refill slots read it).
        ext = jnp.pad(old[:, :W], ((0, 0), (0, W)))
        shifted = jax.lax.dynamic_slice(ext, (zero, s), (C, W))
        return jnp.concatenate(
            [jnp.where(refill, fr, shifted), old[:, W:]], axis=1
        )

    fresh = fresh_pod_arrays(
        C,
        W,
        pg(pay["req_cpu"]),
        pg(pay["req_ram"]),
        TPair(win=pg(pay["dur_win"]), off=pg(pay["dur_off"])),
    )
    new_pods = jax.tree.map(move, pods, fresh)
    new_rank = None
    if rank is not None:
        new_rank = move(rank, pg(pay["rank"]))
    return new_pods, new_rank


# --- superspan executor ------------------------------------------------------

# Exit codes in the superspan progress vector (progress[3]):
SUPERSPAN_RUN = 0  # ran to the target / span budget; nothing blocked
SUPERSPAN_GROW = 1  # shift == 0: the live-pod span outgrew the window
SUPERSPAN_STAGE = 2  # next slide needs refill columns beyond the stage


_SUPERSPAN_STATICS = _STEP_STATICS + ("W", "K", "chunk")


def _out_superspan(axis, _statics):
    """(state, windowed pod-name ranks | None, the (4,) progress vector)."""
    return PartitionSpec(axis), PartitionSpec(axis), PartitionSpec()


@over_clusters(_SUPERSPAN_STATICS, _out_superspan, replicated=("progress",))
def _run_superspan_impl(
    state: ClusterBatchState,
    rank,
    progress,
    slab: TraceSlab,
    consts: StepConstants,
    stage,
    stage_lo,
    last,
    max_events_per_window: int,
    max_pods_per_cycle: int,
    autoscale_statics=None,
    max_ca_pods_per_cycle: int = 64,
    max_pods_per_scale_down: int = 8,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    conditional_move: bool = False,
    shards=None,
    use_pallas_select: bool = False,
    use_megakernel: bool = True,
    hpa_seg=None,
    fault_params=None,
    name_ranks=None,
    lane_major: bool = False,
    window_razor: bool = True,
    reclaim: bool = False,
    profile=None,
    W: int = 0,
    K: int = 16,
    chunk: int = 8,
):
    """Execute up to K consecutive slide-spans ENTIRELY on device: one
    while_loop whose body either advances a chunk of windows (while the
    next window's pod creations still fit the device window) or computes,
    quantizes and applies the pod-window slide — refill columns drawn from
    the device-resident RefillStage — carrying pod_base (in state) and the
    windowed pod-name ranks as traced loop state. The steady-state host
    boundary of the ladder path (one shift readback + refill bookkeeping
    per span) collapses to ONE progress readback per K spans.

    Arguments beyond the run_windows set:
    - rank: (C, P) windowed pod-name ranks carried through on-device slides
      (None without autoscale statics). The statics' own pod_name_rank leaf
      is ignored inside the loop (autoscale.statics_with_pod_rank rebinds
      the carried array for every window chunk).
    - progress: (4,) int32 [next_window, pod_base, spans, code]. The loop
      starts at progress[0] with progress[3] as the initial code — a
      non-RUN input code makes the whole call the identity, so callers can
      chain dispatches speculatively and resolve the codes later.
    - stage: state.RefillStage covering payload columns
      [stage_lo, stage_lo + L); the whole-trace payload is the L = T + W,
      stage_lo = 0 special case and never exhausts.
    - last: final window index (inclusive) this call may execute.
    - W/K/chunk (static): pod-window width, span budget, windows advanced
      per full-rate loop iteration.

    Exits (code in the returned progress vector): SUPERSPAN_RUN with
    next_window > last = target reached; SUPERSPAN_RUN with spans == K =
    span budget, redispatch; SUPERSPAN_GROW = no slide possible with the
    capacity column readable, the engine must grow the window;
    SUPERSPAN_STAGE = the pending slide's refill columns lie beyond the
    stage (or the slide is blocked with the capacity column itself beyond
    the stage, where growth cannot be trusted), the engine must install the
    next staging buffer. Blocking exits leave the slide UNAPPLIED (state as
    of the last completed window), so re-dispatching after the host fix is
    exact.

    Bit-identity with the ladder path: the same _window_body runs at the
    same window indices (chunking is associativity-free), slides trigger at
    exactly the capacity boundaries step_until_time uses (first overflow
    create across clusters), and shift/quantize/apply are the SAME traced
    formulations the fused megastep dispatches.
    """
    big = jnp.int32(np.iinfo(np.int32).max)
    from kubernetriks_tpu.batched.autoscale import statics_with_pod_rank

    # Under a mesh the three reads below that span every cluster (pod_base,
    # the capacity column, the slide's shift) are pmin'ned, so `bound`, the
    # branch taken, the loop's trip count and the exit code are the same on
    # every shard: the collectives sit where all shards meet.
    shard_axis = shard_axis_of(shards)

    if lane_major:
        # One conversion per superspan dispatch (covers up to K slide-spans
        # of windows); everything the loop touches outside _window_body —
        # pod_base, phases, the stage — is row-major / pod-side.
        state = swap_node_layout(state)

    L = stage.req_cpu.shape[1]
    stage_lo = jnp.asarray(stage_lo, jnp.int32)
    last = jnp.asarray(last, jnp.int32)
    pay = {
        "req_cpu": stage.req_cpu,
        "req_ram": stage.req_ram,
        "dur_win": stage.dur_win,
        "dur_off": stage.dur_off,
        "create_win": stage.create_win,
    }
    if stage.rank is not None:
        pay["rank"] = stage.rank

    def step_windows(state, rank, idxs):
        st = statics_with_pod_rank(autoscale_statics, rank)

        def body(carry, w):
            new = _window_body(
                carry,
                slab,
                w,
                consts,
                max_events_per_window,
                max_pods_per_cycle,
                st,
                max_ca_pods_per_cycle,
                max_pods_per_scale_down,
                use_pallas,
                pallas_interpret,
                conditional_move,
                use_pallas_select,
                use_megakernel=use_megakernel,
                hpa_seg=hpa_seg,
                fault_params=fault_params,
                name_ranks=name_ranks,
                lane_major=lane_major,
                window_razor=window_razor,
                reclaim=reclaim,
                profile=profile,
                shard_axis=shard_axis,
            )
            return new, None

        state, _ = jax.lax.scan(body, state, idxs)
        return state

    def cond(carry):
        _, _, w, spans, code = carry
        with jax.named_scope("bookkeeping"):
            return (w <= last) & (code == SUPERSPAN_RUN) & (spans < jnp.int32(K))

    def body(carry):
        state, rank, w, spans, code = carry
        # pod_base is uniform across clusters (slides shift every row
        # together); the min is its scalar read.
        # Capacity: the last window index dispatchable before a pod creation
        # would land beyond the device window — the create window of global
        # plain slot base + W (engine._pod_capacity_window's device twin).
        # Beyond the trace's plain segment capacity is unbounded; a stage
        # whose headroom is fully consumed reports capacity -1, forcing the
        # slide branch (which then exits SUPERSPAN_STAGE or GROW). The read
        # is the slide's trigger and carries its device phase.
        with jax.named_scope("slide"):
            base = all_min(state.pod_base, shard_axis)
            gcol = base + jnp.int32(W)
            col = gcol - stage_lo
            cap_read = all_min(
                jax.lax.dynamic_slice_in_dim(
                    stage.create_win, jnp.clip(col, 0, L - 1), 1, axis=1
                ),
                shard_axis,
            ).astype(jnp.int32)
            cap = jnp.where(
                gcol >= consts.trace_pod_bound,
                big,
                jnp.where(col < jnp.int32(L), cap_read, jnp.int32(-1)),
            )
            bound = jnp.minimum(cap, last)

        def run_branch(op):
            state, rank, w, spans = op
            with jax.named_scope("bookkeeping"):
                can_chunk = (w + jnp.int32(chunk - 1)) <= bound

            def run_n(n):
                def run(op2):
                    state, rank, w = op2
                    with jax.named_scope("bookkeeping"):
                        idxs = w + jnp.arange(n, dtype=jnp.int32)
                    state = step_windows(state, rank, idxs)
                    with jax.named_scope("bookkeeping"):
                        return state, rank, w + jnp.int32(n)

                return run

            state, rank, w = jax.lax.cond(
                can_chunk, run_n(chunk), run_n(1), (state, rank, w)
            )
            return state, rank, w, spans, jnp.int32(SUPERSPAN_RUN)

        @jax.named_scope("slide")
        def slide_branch(op):
            state, rank, w, spans = op
            s0 = _slide_shift_core(
                slide_phase(state.pods, consts)[:, :W], stage.create_win,
                base - stage_lo,
                shard_axis,
            )
            s = _quantize_shift_device(s0, W)
            blocked = s <= jnp.int32(0)
            # A blocked slide whose capacity column lies beyond the stage
            # (col >= L forced cap to -1 above) is staging exhaustion, not
            # growth: the TRUE capacity may still admit the next window, so
            # the engine must restage — GROW is only trustworthy when the
            # capacity read was in range.
            cap_unread = (col >= jnp.int32(L)) & (
                gcol < consts.trace_pod_bound
            )
            grow = blocked & ~cap_unread
            exhausted = (blocked & cap_unread) | (
                (~blocked)
                & ((base - stage_lo + jnp.int32(W) + s) > jnp.int32(L))
            )

            def apply(op2):
                state, rank = op2
                new_pods, new_rank = _slide_apply_traced(
                    state.pods, rank, pay, base - stage_lo, s, W
                )
                return (
                    state._replace(
                        pods=new_pods, pod_base=state.pod_base + s
                    ),
                    new_rank,
                )

            def skip(op2):
                return op2

            state, rank = jax.lax.cond(
                grow | exhausted, skip, apply, (state, rank)
            )
            code = jnp.where(
                grow,
                jnp.int32(SUPERSPAN_GROW),
                jnp.where(
                    exhausted,
                    jnp.int32(SUPERSPAN_STAGE),
                    jnp.int32(SUPERSPAN_RUN),
                ),
            )
            spans = spans + (code == SUPERSPAN_RUN).astype(jnp.int32)
            return state, rank, w, spans, code

        return jax.lax.cond(
            w <= bound, run_branch, slide_branch, (state, rank, w, spans)
        )

    with jax.named_scope("bookkeeping"):
        progress = jnp.asarray(progress, jnp.int32)
        carry = (state, rank, progress[0], jnp.int32(0), progress[3])
    state, rank, w, spans, code = jax.lax.while_loop(cond, body, carry)
    if lane_major:
        state = swap_node_layout(state)
    with jax.named_scope("bookkeeping"):
        progress_out = jnp.stack(
            [w, all_min(state.pod_base, shard_axis), spans, code]
        ).astype(jnp.int32)
    return state, rank, progress_out


run_superspan = partial(jax.jit, static_argnames=_SUPERSPAN_STATICS)(
    _run_superspan_impl
)
run_superspan_donated = jax.jit(
    _run_superspan_impl,
    static_argnames=_SUPERSPAN_STATICS,
    donate_argnums=(0,),
)
