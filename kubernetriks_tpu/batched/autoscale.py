"""Vectorized autoscaler passes for the batched backend.

The scalar HPA / cluster-autoscaler control loops (reference:
src/autoscalers/horizontal_pod_autoscaler/*.rs, cluster_autoscaler/*.rs)
become masked array passes over the dense cluster-batch state, run at their
scan cadence inside the window step:

- HPA: per-(cluster, pod-group) closed-form utilization from the compiled
  load curves, the k8s desired-replicas formula with tolerance band
  (reference: kube_horizontal_pod_autoscaler.rs:54-155), and head/tail
  activation windows over the group's reserved pod slots.
- CA: bounded-K first-fit bin-packing scale-up over the unscheduled-pod cache
  and a nested-scan scale-down with simulated re-placement over shared virtual
  allocatables (reference: kube_cluster_autoscaler.rs:55-307).

Times are the 32-bit (win, off) pairs of timerep.py; the only 64-bit math is
the load-curve elapsed-time evaluation (float64 on tiny (C, G) shapes — the
curves cycle over arbitrary-length periods, where float32 elapsed time at
Alibaba-scale timestamps would blur the curve position).

Round-4 exact-CA semantics (the old "one-window visibility shift" and
"fixed cadence" approximations are retired; tests/test_random_ca_equivalence
pins sample-for-sample trajectory equality, incl. conditional-move churn):
- ca_next carries the TRUE cycle fire time: the scalar re-arms
  scan_interval after the info round-trip returns
  (cluster_autoscaler.py on_response; reference
  cluster_autoscaler.rs:256-262 with delay 0 on overrun), so the period is
  round_trip + scan_interval and cycles DRIFT across windows. Cycle k runs
  in the window containing its storage-snapshot time s_k = fire + as_to_ca
  + as_to_ps; effects compose from the fire time.
- The decision reads the storage's view at s_k exactly: pre-cycle shadows
  when s_k precedes this window's commit visibility (ca_pass `pre`), and
  finish visibility read off the pending-free channel, whose holders the
  storage hears of one hop before the scheduler (_ca_scale_down vis_gone).
- Scale-down walks candidates and first-fits re-placements in NODE-NAME
  order (info.nodes is name-sorted); scale-up bin-packs the cache in
  POD-NAME order (scale_up_info sorts names) via the static name ranks.

Remaining bounded deviations:
- Scale-up considers at most K_up cache pods and scale-down at most K_sd pods
  per candidate node per cycle; overflow is deferred to the next cycle
  (scale-up) or conservatively skipped (scale-down).
- CA slot reserve: each group reserves slots ~ multiplier x max_count,
  mirroring the reference's pre-sized component pool
  (src/simulator.rs:212-230). Under reclaim (KTPU_RECLAIM, the r14
  endurance work) fully-retired slots are RETURNED to the reserve by a
  periodic in-trace compaction (ca_reclaim_pass) the way the reference's
  node_component_pool reuses components (node_component_pool.rs:60-77),
  so `ca_cursor` tracks LIVE reserve occupancy instead of cumulative
  allocations and sustained churn never exhausts the reserve; names stay
  scalar-exact because each allocation carries the scalar's monotone
  total_allocated index (auto.ca_alloc / ca_total — "{group}_{idx+1}")
  and every name-ordered walk derives its order from that index
  (ca_name_order). Without reclaim the cursor is monotone and the loud
  bound (engine.check_autoscaler_bounds) remains the only backstop.
- CA-cache name ORDER for HPA replicas whose slot has been ring-reused uses
  the slot's first occupant's static name rank (pod_name_rank); HPA
  scale-down victim IDENTITY is exact regardless (pods.hpa_idx stores the
  live occupant's replica index).
- Sub-scan-interval CA cadences (scan_interval < the window interval)
  degrade to one cycle per window.

Round-4 HPA identity semantics: scale-down pops the lexicographically
SMALLEST replica name from the group's live set exactly like the scalar's
BTreeSet (kube_horizontal_pod_autoscaler.rs:197-205) — victims are
scattered, so scale-up activates the first free slots of the reserve in
slot order and stores each occupant's replica index in pods.hpa_idx
("{group}_{idx}" naming, idx = total-created counter); hpa_head counts
total removals, keeping current = tail - head.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from kubernetriks_tpu.batched.sharding import over_clusters
from kubernetriks_tpu.batched.state import (
    ClusterBatchState,
    PHASE_EMPTY,
    PHASE_FAILED,
    PHASE_QUEUED,
    PHASE_REMOVED,
    PHASE_RUNNING,
    PHASE_SUCCEEDED,
    PHASE_UNSCHEDULABLE,
    StepConstants,
    held_frees,
    swap_node_layout,
)
from kubernetriks_tpu.batched.timerep import (
    TPair,
    is_inf,
    t_add,
    t_inf,
    t_le,
    t_lt,
    t_min,
    t_where,
    t_zeros,
)
from kubernetriks_tpu.batched.pipeline import DEVICE_FILTER_PLUGINS
from kubernetriks_tpu.core.scheduler.plugins import FIT

INF = jnp.inf
_BIG_I32 = jnp.iinfo(jnp.int32).max

# The Fit feasibility predicate, shared with the scheduler pipeline's
# device-plugin registry: CA placement simulation stays first-fit by
# reference semantics, but "fits" means the same thing everywhere.
def _fit_filter(cpu, ram, rc, rr):
    # The CA's what-if placements read resources alone (no spread table).
    return DEVICE_FILTER_PLUGINS[FIT](cpu, ram, rc, rr, None)


class AutoscaleStatics(NamedTuple):
    """Compile-time autoscaler tables (pytree of arrays; C-leading)."""

    # --- HPA pod groups: (C, Gp) ---
    pg_slot_start: jnp.ndarray  # int32 first reserved pod slot
    pg_slot_count: jnp.ndarray  # int32 reserved slots (cumulative creations cap)
    pg_initial: jnp.ndarray  # int32 initial replicas (created by the trace)
    pg_max_pods: jnp.ndarray  # int32 max simultaneous replicas
    pg_target_cpu: jnp.ndarray  # float32; <=0 means metric unset
    pg_target_ram: jnp.ndarray  # float32; <=0 means metric unset
    # First HPA tick that sees the group: creation + register delay (pair);
    # win=INF_WIN = padding / HPA disabled.
    pg_active_from: TPair
    # Absolute creation time in float64 seconds for load-curve elapsed math.
    pg_creation_s: jnp.ndarray
    # Piecewise-cyclic load curves, (C, Gp, U); duration 0 = padding unit.
    pg_cpu_dur: jnp.ndarray
    pg_cpu_load: jnp.ndarray
    pg_cpu_total: jnp.ndarray  # (C, Gp) cycle length; 0 = no model (util 0)
    pg_cpu_const: jnp.ndarray  # bool: constant model (load IS the utilization)
    pg_ram_dur: jnp.ndarray
    pg_ram_load: jnp.ndarray
    pg_ram_total: jnp.ndarray
    pg_ram_const: jnp.ndarray
    pod_group_id: jnp.ndarray  # (C, P) int32 group of pod slot; -1 = none
    # --- CA node groups: (C, Gn) ---
    ng_ca_start: jnp.ndarray  # int32 first CA-slot (in the compact CA axis)
    ng_slot_count: jnp.ndarray  # int32 reserved CA slots
    ng_max_count: jnp.ndarray  # int32; <0 = unbounded
    ng_tmpl_cpu: jnp.ndarray  # int32 template capacity
    ng_tmpl_ram: jnp.ndarray  # int32 (ram units)
    ca_max_nodes: jnp.ndarray  # (C,) int32 global CA node quota
    ca_slots: jnp.ndarray  # (C, S) int32 global node slot of CA slot; -1 pad
    ca_slot_group: jnp.ndarray  # (C, S) int32 owning group; -1 pad
    # --- per-lane control-law parameters: (C,) pairs / arrays -----------
    # Scenario-vector fleet (batched/fleet.py): every leaf below is
    # per-CLUSTER traced data composed by fleet.scenario_leaves — a fleet
    # of heterogeneous autoscaler configs runs under ONE compiled program
    # (scalar-config builds carry the base value replicated across C).
    hpa_interval: TPair  # (C,) per-lane HPA scan interval
    hpa_tolerance: jnp.ndarray  # (C,) f64 per-lane target tolerance
    ca_threshold: jnp.ndarray  # (C,) f64 per-lane scale-down threshold
    d_hpa_up: TPair  # (C,) HPA tick -> scaled-up pod enters scheduler queue
    d_hpa_down: TPair  # (C,) HPA tick -> pod removal effect at storage
    d_ca_up: TPair  # (C,) CA tick -> scaled-up node schedulable
    d_ca_down: TPair  # (C,) CA tick -> node removal effect at node
    # --- exact-CA cadence/visibility (r4; see ca_pass docstring) ---
    ca_period: TPair  # (C,) true cycle period: round-trip + scan (or just rt)
    ca_snap: TPair  # (C,) cycle fire -> storage snapshot (as_to_ca + as_to_ps)
    ca_finish_vis: TPair  # (C,) node finish -> storage visibility
    ca_commit_vis: TPair  # (C,) scheduler commit -> storage visibility
    pod_name_rank: jnp.ndarray  # (C, P) int32 lexicographic name rank; BIG = n/a
    node_name_rank: jnp.ndarray  # (C, N) int32 node-name rank (trace + CA slots)
    ca_sd_order: jnp.ndarray  # (C, S) CA slot indices in name order
    # --- HPA metrics-collection cadence (staleness fix, r14) -----------
    # The scalar HPA reads whatever the metrics collector's fixed 60 s
    # collection cycle last pulled (metrics/collector.py
    # COLLECTION_INTERVAL); this pair is that cadence as device time, so
    # hpa_pass can latch collection-window snapshots (AutoscaleState
    # col_*) instead of sampling the load curve at its own tick.
    col_interval: Optional[TPair] = None  # (C,) the 60 s collection cadence
    # --- reclaim name-order tables (r14; None = reclaim unsupported) ---
    # The scalar names every allocation "{group}_{total_allocated}"; with
    # slot reuse the name no longer equals the slot, so name-ordered
    # walks (scale-down candidates, re-placement first-fit, same-window
    # reschedule batches) derive their order from the occupant's
    # allocation index. Cross-CLASS order (trace node vs group name
    # family, family vs family) is static — verified non-interleaving at
    # build (engine._reclaim_class_tables) — and only the within-group
    # decimal-suffix order is dynamic.
    ca_slot_class: Optional[jnp.ndarray] = None  # (C, S) int32 class rank of slot's group
    ca_class_start: Optional[jnp.ndarray] = None  # (C, Gn) int32 first class-sorted slot pos
    node_class_key: Optional[jnp.ndarray] = None  # (C, N) int32 class_rank * (S + 1)


def statics_with_pod_rank(
    statics: Optional[AutoscaleStatics], rank
) -> Optional[AutoscaleStatics]:
    """Rebind the windowed pod-name ranks into the statics. The superspan
    executor (step.run_superspan) slides the pod window ON DEVICE, so the
    ranks become loop-carried state rather than a per-dispatch constant;
    every window chunk inside the loop reads its statics through this ONE
    rebinding point (the statics argument's own pod_name_rank leaf is never
    read there — it merely pins shape/sharding)."""
    if statics is None or rank is None:
        return statics
    return statics._replace(pod_name_rank=rank)


class AutoscaleState(NamedTuple):
    """Dynamic autoscaler state (lives inside ClusterBatchState.auto).

    The Optional leaves are structural statics in the `auto`/`telemetry`
    tradition: None compiles programs without the corresponding machinery
    (reclaim off / collection latch off), arrays arm it. ca_cursor under
    reclaim tracks LIVE reserve occupancy (compaction pulls it back);
    without reclaim it is the classic monotone next-slot cursor."""

    hpa_head: jnp.ndarray  # (C, Gp) int32 first live created offset
    hpa_tail: jnp.ndarray  # (C, Gp) int32 next creation offset (== total_created)
    ca_count: jnp.ndarray  # (C, Gn) int32 current CA nodes per group
    ca_cursor: jnp.ndarray  # (C, Gn) int32 next reserved slot offset
    hpa_next: TPair  # (C,) next HPA tick
    ca_next: TPair  # (C,) next CA tick
    # --- CA slot reclaim (r14; None = reclaim off) ---------------------
    ca_alloc: Optional[jnp.ndarray] = None  # (C, S) int32 occupant's allocation
    # index (the scalar's total_allocated - 1 at open time); -1 = free slot.
    # INVARIANT: occupied slots are exactly the per-group prefix
    # [ng_ca_start, ng_ca_start + ca_cursor) — allocation appends at the
    # cursor and compaction re-packs keepers stably, so slot order among
    # live CA nodes always equals allocation order (which keeps the
    # scheduler's slot-order tie-break identical to the no-reclaim path).
    ca_total: Optional[jnp.ndarray] = None  # (C, Gn) int32 monotone allocation
    # counter (the scalar's group.total_allocated; names are "{g}_{total}").
    ca_reclaimed: Optional[jnp.ndarray] = None  # (C,) int32 slots returned to
    # the reserve by compaction (the "reclaim actually fired" observable).
    # --- HPA collection latch (r14 staleness fix; None = legacy inline) ---
    col_next: Optional[TPair] = None  # (C,) next 60 s collection tick
    col_run: Optional[jnp.ndarray] = None  # (C, Gp) int32 running count at the
    # last collection (0 = group absent from the sample, like the scalar's
    # metrics dict missing the group).
    col_util_cpu: Optional[jnp.ndarray] = None  # (C, Gp) f32 latched utilization
    col_util_ram: Optional[jnp.ndarray] = None  # (C, Gp) f32


# --- contract-prover registries (ktpu-lint; see state.py's checklist) --------
# Leaf manifest of AutoscaleState — must equal the fields exactly
# (stateleaf pass); structural ca_* leaves additionally need a DESIGN §12
# entry and a CKPT_COVERED_LEAVES story (engine.py).
AUTOSCALE_STATE_LEAVES = (
    "hpa_head",
    "hpa_tail",
    "ca_count",
    "ca_cursor",
    "hpa_next",
    "ca_next",
    "ca_alloc",
    "ca_total",
    "ca_reclaimed",
    "col_next",
    "col_run",
    "col_util_cpu",
    "col_util_ram",
)

# AutoscaleStatics leaves that are per-lane TRACED scenario data — the
# fleet.scenario_leaves composition targets. The scenariotrace lint pass
# forbids them from flowing into Python control flow, host casts, jit
# statics or shape expressions: a what-if config must never shape a
# program (the fleet's compile-once guarantee, statically).
SCENARIO_TRACED_LEAVES = (
    "hpa_interval",
    "hpa_tolerance",
    "ca_threshold",
    "ca_max_nodes",
    "pg_active_from",
    "d_hpa_up",
    "d_hpa_down",
    "d_ca_up",
    "d_ca_down",
    "ca_period",
    "ca_snap",
    "ca_finish_vis",
    "ca_commit_vis",
)

# Declared axis signatures (shapecontract pass): the per-cluster "C" lane
# vectors are exactly the leaves whose broadcasts against per-object
# (C, G)/(C, P)/(C, S) planes MUST be explicit ([:, None]) — the PR 13
# tolerance/finish_vis bug class. "C,G,*" = the (C, G, U) curve tables.
AXIS_SIGNATURES = {
    # AutoscaleState
    "hpa_head": "C,G",
    "hpa_tail": "C,G",
    "ca_count": "C,G",
    "ca_cursor": "C,G",
    "ca_total": "C,G",
    "ca_alloc": "C,S",
    "ca_reclaimed": "C",
    "hpa_next": "C",
    "ca_next": "C",
    "col_next": "C",
    "col_run": "C,G",
    "col_util_cpu": "C,G",
    "col_util_ram": "C,G",
    # AutoscaleStatics per-lane control-law leaves
    "hpa_interval": "C",
    "hpa_tolerance": "C",
    "ca_threshold": "C",
    "ca_max_nodes": "C",
    "d_hpa_up": "C",
    "d_hpa_down": "C",
    "d_ca_up": "C",
    "d_ca_down": "C",
    "ca_period": "C",
    "ca_snap": "C",
    "ca_finish_vis": "C",
    "ca_commit_vis": "C",
    "col_interval": "C",
    # AutoscaleStatics tables
    "pg_slot_start": "C,G",
    "pg_slot_count": "C,G",
    "pg_initial": "C,G",
    "pg_max_pods": "C,G",
    "pg_target_cpu": "C,G",
    "pg_target_ram": "C,G",
    "pg_active_from": "C,G",
    "pg_creation_s": "C,G",
    "pg_cpu_dur": "C,G,*",
    "pg_cpu_load": "C,G,*",
    "pg_cpu_total": "C,G",
    "pg_cpu_const": "C,G",
    "pg_ram_dur": "C,G,*",
    "pg_ram_load": "C,G,*",
    "pg_ram_total": "C,G",
    "pg_ram_const": "C,G",
    "pod_group_id": "C,P",
    "ng_ca_start": "C,G",
    "ng_slot_count": "C,G",
    "ng_max_count": "C,G",
    "ng_tmpl_cpu": "C,G",
    "ng_tmpl_ram": "C,G",
    "ca_slots": "C,S",
    "ca_slot_group": "C,S",
    "ca_sd_order": "C,S",
    "ca_slot_class": "C,S",
    "ca_class_start": "C,G",
    "pod_name_rank": "C,P",
    "node_name_rank": "C,N",
    "node_class_key": "C,N",
}


def init_autoscale_state(
    statics: AutoscaleStatics,
    reclaim: bool = False,
    collect: bool = False,
) -> AutoscaleState:
    """reclaim arms the CA slot-reclaim leaves (requires the statics'
    name-order tables); collect arms the HPA collection latch (the engine
    sets it whenever real pod groups exist)."""
    C, Gp = statics.pg_slot_start.shape
    Gn = statics.ng_ca_start.shape[1]
    S = statics.ca_slots.shape[1]
    if reclaim and statics.ca_slot_class is None:
        raise ValueError(
            "init_autoscale_state(reclaim=True) needs the statics' reclaim "
            "name-order tables (ca_slot_class/ca_class_start/node_class_key) "
            "— built by engine.build_autoscale_statics when the name "
            "classes verify non-interleaving"
        )
    return AutoscaleState(
        hpa_head=jnp.zeros((C, Gp), jnp.int32),
        # The trace's initial pods count as created (the api-server expansion
        # seeds created_pods/total_created, reference: api_server.rs:405-455).
        hpa_tail=statics.pg_initial.astype(jnp.int32),
        ca_count=jnp.zeros((C, Gn), jnp.int32),
        ca_cursor=jnp.zeros((C, Gn), jnp.int32),
        hpa_next=t_zeros((C,)),
        ca_next=t_zeros((C,)),
        ca_alloc=jnp.full((C, S), -1, jnp.int32) if reclaim else None,
        ca_total=jnp.zeros((C, Gn), jnp.int32) if reclaim else None,
        ca_reclaimed=jnp.zeros((C,), jnp.int32) if reclaim else None,
        col_next=t_zeros((C,)) if collect else None,
        col_run=jnp.zeros((C, Gp), jnp.int32) if collect else None,
        col_util_cpu=jnp.zeros((C, Gp), jnp.float32) if collect else None,
        col_util_ram=jnp.zeros((C, Gp), jnp.float32) if collect else None,
    )


def _curve_load(dur, load, total, elapsed):
    """Piecewise-constant cyclic curve lookup (reference semantics:
    src/core/resource_usage/pod_group.rs:71-99). dur/load: (C, G, U);
    total/elapsed: (C, G). elapsed is float64 (see module docstring); the
    returned load is float32."""
    safe_total = jnp.maximum(total.astype(jnp.float64), 1e-9)
    pos = jnp.where(total > 0, jnp.mod(elapsed, safe_total), 0.0)
    ecs = jnp.cumsum(dur, axis=-1) - dur  # exclusive start of each unit
    in_unit = (ecs <= pos[..., None]) & (pos[..., None] < ecs + dur)
    return jnp.where(in_unit, load, 0.0).sum(axis=-1).astype(jnp.float32)


def _broadcast_pair(p: TPair, shape) -> TPair:
    return TPair(
        win=jnp.broadcast_to(p.win[..., None], shape),
        off=jnp.broadcast_to(p.off[..., None], shape),
    )


def _rows_at(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Row c of `x` (C, L) at the indices idx[c, :] ((C, M) int32): the
    value of `x[rows, idx]` / `take_along_axis(x, idx, 1)`, bit for bit, as
    a dense contraction — `where(idx == l, x[c, l], 0)` reduced over L —
    instead of an XLA gather. The TPU's gather costs per INDEX whatever it
    reads (4.5-10 ns each at the composed shapes, PERF.md section 5); the
    fused compare-and-reduce costs per ELEMENT (C x M x L of them at some
    0.4-1.8 ps), which is the cheaper side wherever L is a node or slot
    axis. Exact: each index matches one l, so the reduction adds zeros to
    one value (`any` for bools; floats travel as their int32 bit patterns,
    so -0.0, inf and nan payloads come back as they went in). With L == 1
    (one node group) the look-up is a broadcast.

    CONTRACT: 0 <= idx < L. Every call site clips its index first; an
    index outside the row matches nothing and reads 0 / False where the
    gather would have clamped."""
    L = x.shape[1]
    if L == 1:
        return jnp.broadcast_to(x, idx.shape)
    hit = idx[:, None, :] == jnp.arange(L, dtype=idx.dtype)[None, :, None]
    if x.dtype == jnp.bool_:
        return (hit & x[:, :, None]).any(axis=1)
    bits = x if x.dtype == jnp.int32 else jax.lax.bitcast_convert_type(x, jnp.int32)
    out = jnp.where(hit, bits[:, :, None], 0).sum(axis=1, dtype=jnp.int32)
    return out if x.dtype == jnp.int32 else jax.lax.bitcast_convert_type(out, x.dtype)


def _rows_put(idx: jnp.ndarray, v: jnp.ndarray, L: int) -> jnp.ndarray:
    """The other direction: out[c, l] = the `v[c, j]` whose idx[c, j] == l,
    combined over j — summed for int32 `v` (`zeros.at[rows, idx].add(v)`;
    equally `.set(v)` where no two j share a target, as through a
    permutation), or-ed for bool `v` (`zeros.at[rows, idx].set(True)` under
    the mask `v`). An index outside [0, L) matches no column and drops,
    like `mode="drop"`. Dense for the reason `_rows_at` is: the TPU's
    scatter sorts its indices and pays per index too."""
    hit = idx[:, :, None] == jnp.arange(L, dtype=idx.dtype)[None, None, :]
    if v.dtype == jnp.bool_:
        return (hit & v[:, :, None]).any(axis=1)
    return jnp.where(hit, v[:, :, None], 0).sum(axis=1, dtype=jnp.int32)


def _segment_sums(key: jnp.ndarray, N: int, *values: jnp.ndarray):
    """Per-segment totals of (C, P) rows grouped by an int32 key in [0, N]
    (N = in no segment), WITHOUT sorting. Returns (start (C, N): the keys
    below n, which is where segment n starts once the row is sorted by
    key; [for each value, (C, N): sum_p where(key == n, value, 0), a bool
    value counting]): `_rows_put` by the key, so XLA makes ONE (C, P, N)
    compare of it and reduces that over P every way asked. int32 adds in
    any order: equal, bit for bit, to the differences of the sorted row's
    cumulative sums at the segment's two boundaries, wrap-around included."""
    col = jnp.arange(N, dtype=key.dtype)[None, None, :]
    start = (key[:, :, None] < col).sum(axis=1, dtype=jnp.int32)
    return start, [_rows_put(key, v.astype(jnp.int32), N) for v in values]


def decimal_string_key(idx: jnp.ndarray) -> jnp.ndarray:
    """int32 key whose order equals the LEXICOGRAPHIC order of str(idx)
    for 0 <= idx < 10^8 ("g_10" < "g_2"): left-align the value to 8
    digits, tie-break shorter-first. Max key < 16 * 10^8 < 2^31. THE
    decimal-suffix ordering primitive shared by the HPA victim selection
    and the CA reclaim name orders — one implementation so the suffix
    rule can't drift."""
    idx = jnp.maximum(idx, 0)
    digits = (
        1
        + (idx >= 10).astype(jnp.int32)
        + (idx >= 100).astype(jnp.int32)
        + (idx >= 1_000).astype(jnp.int32)
        + (idx >= 10_000).astype(jnp.int32)
        + (idx >= 100_000).astype(jnp.int32)
        + (idx >= 1_000_000).astype(jnp.int32)
        + (idx >= 10_000_000).astype(jnp.int32)
    )
    pow10 = jnp.asarray(
        [0, 10_000_000, 1_000_000, 100_000, 10_000, 1_000, 100, 10, 1],
        jnp.int32,
    )
    # pow10[digits] as nine selects: a table look-up is an XLA gather, paid
    # per index on the TPU (_rows_at).
    scale = jnp.where(
        digits[..., None] == jnp.arange(9, dtype=jnp.int32), pow10, 0
    ).sum(axis=-1, dtype=jnp.int32)
    return idx * scale * jnp.int32(16) + digits


def ca_name_order(
    auto: AutoscaleState, st: AutoscaleStatics
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dynamic name orderings of the LIVE CA fleet under slot reclaim:
    (sd_order (C, S) — CA slot indices in current node-name order, the
    drop-in for the static st.ca_sd_order — and node_key (C, N) — an
    int32 key whose order over alive nodes equals node-name order, the
    drop-in for st.node_name_rank in re-placement first-fit and
    same-window reschedule ranking).

    An occupant's name is "{group}_{alloc+1}" (the scalar's
    total_allocated naming). Cross-class order (trace singleton vs group
    family, family vs family) is static — the build verified the classes
    non-interleaving — so the key decomposes as class_rank * (S + 1) +
    within-group rank, where the within-group rank comes from ONE stable
    (C, S) 2-key sort by (class, decimal-suffix key). Free slots sort
    after their group's occupants (suffix key BIG) and keep the class
    base key — they are dead, so every consumer masks them by liveness
    first. When no slot has ever been reused (alloc == slot offset) both
    orders coincide with the static tables exactly."""
    C, S = auto.ca_alloc.shape
    Gn = st.ca_class_start.shape[1]
    iota_s = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (C, S))
    occupied = auto.ca_alloc >= 0
    suffix = jnp.where(
        occupied, decimal_string_key(auto.ca_alloc + 1), _BIG_I32
    )
    _, _, sd_order = jax.lax.sort(
        (st.ca_slot_class, suffix, iota_s), dimension=1, num_keys=2,
        is_stable=True,
    )
    # Sorted position of each slot -> within-group rank (each group's
    # slots are contiguous in class order; ca_class_start is the static
    # first position of the group's segment). sd_order is a permutation:
    # its inverse is one dense put (_rows_put), no scatter.
    pos = _rows_put(sd_order, iota_s, S)
    gidc = jnp.clip(st.ca_slot_group, 0, Gn - 1)
    within = jnp.where(
        occupied, pos - _rows_at(st.ca_class_start, gidc), 0
    )
    N = st.node_class_key.shape[1]
    tgt = jnp.where(occupied & (st.ca_slots >= 0), st.ca_slots, N)
    node_key = st.node_class_key + _rows_put(tgt, within, N)
    return sd_order, node_key


@jax.named_scope("hpa_pass")
def hpa_pass(
    state: ClusterBatchState,
    auto: AutoscaleState,
    st: AutoscaleStatics,
    W: jnp.ndarray,
    consts: StepConstants,
    seg=None,
) -> Tuple[ClusterBatchState, AutoscaleState]:
    """One masked HPA cycle at window W for every due cluster
    (scalar equivalent: horizontal_pod_autoscaler.py run cycle +
    kube_horizontal_pod_autoscaler.py formula).

    seg: optional STATIC (lo, hi) device-slot bounds covering every pod-group
    slot (engine._hpa_seg). The pass only ever touches group slots, so the
    body — including its (C, P) victim sort — runs on the [lo, hi) slice,
    and the not-due `lax.cond` identity branch carries (C, hi-lo) slices
    instead of the full pod arrays (the cond materializes its carry through
    both branches; with the full state that copy cost more than the
    amortized body — the §3 "empty-cycle skip" lesson, docs/DESIGN.md)."""
    pods = state.pods
    C, P = pods.phase.shape
    lo, hi = (0, P) if seg is None else seg
    sliced = (lo, hi) != (0, P)
    sub = (
        jax.tree.map(lambda a: a[:, lo:hi], pods) if sliced else pods
    )
    T0 = TPair(win=W, off=jnp.zeros_like(auto.hpa_next.off))
    due_cycle = t_le(auto.hpa_next, T0).any()
    due_any = due_cycle
    if auto.col_next is not None:
        # The 60 s metrics collection (the latch) is part of the same
        # cond: a collection-only window updates the col_* leaves and
        # leaves everything else untouched (delta = 0 on every lane).
        due_any = due_any | t_le(auto.col_next, T0).any()

    zeros = jnp.zeros((C,), jnp.int32)
    if auto.col_next is None:
        body = lambda: _hpa_pass_body(
            sub, state.queue_seq_counter, auto, st, W, consts, lo
        )
    else:
        # Collection-only windows (scan_interval > 60: the 60 s tick fires
        # between HPA cycles) latch the sample WITHOUT paying the cycle
        # body — desired-replica math, the (C, P) victim sort and the
        # activation scatters all have delta 0 when no lane's cycle is
        # due, so the light branch is trajectory-exact by construction
        # (same sample expressions, same col_* writes).
        body = lambda: jax.lax.cond(
            due_cycle,
            lambda: _hpa_pass_body(
                sub, state.queue_seq_counter, auto, st, W, consts, lo
            ),
            lambda: (
                sub,
                _hpa_collect_only(sub, auto, st, W, consts, lo),
                zeros,
                zeros,
                zeros,
                zeros,
            ),
        )
    sub2, auto2, up_s, down_s, clamp_s, n_up = jax.lax.cond(
        due_any,
        body,
        lambda: (sub, auto, zeros, zeros, zeros, zeros),
    )
    if sliced:
        pods2 = jax.tree.map(
            lambda full, s: full.at[:, lo:hi].set(s), pods, sub2
        )
    else:
        pods2 = sub2
    metrics = state.metrics
    metrics = metrics._replace(
        scaled_up_pods=metrics.scaled_up_pods + up_s,
        scaled_down_pods=metrics.scaled_down_pods + down_s,
        hpa_reserve_clamped=metrics.hpa_reserve_clamped + clamp_s,
    )
    state = state._replace(
        pods=pods2,
        metrics=metrics,
        queue_seq_counter=state.queue_seq_counter + n_up,
    )
    return state, auto2


def _hpa_metrics_sample(pods, st: AutoscaleStatics, W, consts, lo):
    """The metrics collector's per-group sample at window W over the pod
    slice [lo, lo+P): (run_per_group (C,Gp) int32, util_cpu, util_ram
    (C,Gp) float32). ONE expression source for the cycle body and the
    collection-only latch branch, so the latched values can never depend
    on which branch took the sample."""
    C, P = pods.phase.shape
    Gp = st.pg_slot_start.shape[1]
    rows = jnp.arange(C, dtype=jnp.int32)[:, None]
    # Group membership and running counts (running = bound AND started by T,
    # mirroring node_component.running_pods at collection time).
    gid = st.pod_group_id[:, lo : lo + P]
    gid_c = jnp.where(gid >= 0, gid, Gp)
    started = t_le(
        pods.start_time,
        TPair(
            win=jnp.broadcast_to(W[:, None], (C, P)),
            off=jnp.zeros((C, P), jnp.float32),
        ),
    )
    running = (pods.phase == PHASE_RUNNING) & started
    run_per_group = (
        jnp.zeros((C, Gp + 1), jnp.int32)
        .at[rows, gid_c]
        .add(running.astype(jnp.int32))[:, :Gp]
    )
    runf = jnp.maximum(run_per_group, 1).astype(jnp.float32)

    # Elapsed time since group creation, float64 (curves cycle over arbitrary
    # periods; f32 elapsed at large absolute t would blur the curve position).
    T_s = W.astype(jnp.float64) * jnp.float64(consts.scheduling_interval)
    elapsed = T_s[:, None] - st.pg_creation_s
    cpu_load = _curve_load(st.pg_cpu_dur, st.pg_cpu_load, st.pg_cpu_total, elapsed)
    ram_load = _curve_load(st.pg_ram_dur, st.pg_ram_load, st.pg_ram_total, elapsed)
    util_cpu = jnp.where(
        st.pg_cpu_total > 0,
        jnp.where(st.pg_cpu_const, cpu_load, jnp.minimum(1.0, cpu_load / runf)),
        0.0,
    )
    util_ram = jnp.where(
        st.pg_ram_total > 0,
        jnp.where(st.pg_ram_const, ram_load, jnp.minimum(1.0, ram_load / runf)),
        0.0,
    )
    return run_per_group, util_cpu, util_ram


def _latch_collection(
    auto: AutoscaleState, st: AutoscaleStatics, W, interval,
    run_per_group, util_cpu, util_ram,
):
    """The collection-window latch writes — col_next advance + sample
    snapshot, gated on the collection being due — as (col_due, (col_next',
    col_run', col_util_cpu', col_util_ram')). ONE implementation consumed
    by both the cycle body and the collection-only branch, so the latched
    values cannot depend on which branch took the sample."""
    col_due = t_le(
        auto.col_next, TPair(win=W, off=jnp.zeros(W.shape, jnp.float32))
    )
    return col_due, (
        t_where(
            col_due,
            t_add(auto.col_next, st.col_interval, interval),
            auto.col_next,
        ),
        jnp.where(col_due[:, None], run_per_group, auto.col_run),
        jnp.where(col_due[:, None], util_cpu, auto.col_util_cpu),
        jnp.where(col_due[:, None], util_ram, auto.col_util_ram),
    )


def _hpa_collect_only(
    pods,
    auto: AutoscaleState,
    st: AutoscaleStatics,
    W: jnp.ndarray,
    consts: StepConstants,
    lo: int = 0,
) -> AutoscaleState:
    """The 60 s collection tick WITHOUT a due HPA cycle on any lane: latch
    the sample into the col_* leaves and advance col_next — exactly the
    col_state writes _hpa_pass_body would make (shared _hpa_metrics_sample
    + _latch_collection), skipping the cycle machinery (desired-replica
    math, the (C, P) victim sort, activation scatters) that is all
    delta-0 when no cycle is due."""
    interval = jnp.float32(consts.scheduling_interval)
    run_per_group, util_cpu, util_ram = _hpa_metrics_sample(
        pods, st, W, consts, lo
    )
    _, (col_next2, col_run2, col_ucpu2, col_uram2) = _latch_collection(
        auto, st, W, interval, run_per_group, util_cpu, util_ram
    )
    return auto._replace(
        col_next=col_next2,
        col_run=col_run2,
        col_util_cpu=col_ucpu2,
        col_util_ram=col_uram2,
    )


def _hpa_pass_body(
    pods,
    queue_seq_counter: jnp.ndarray,
    auto: AutoscaleState,
    st: AutoscaleStatics,
    W: jnp.ndarray,
    consts: StepConstants,
    lo: int = 0,
):
    """HPA cycle body over the pod-slot slice [lo, lo+P) of the device pod
    axis (P here = slice width; pod_group_id indexes align via lo). Returns
    (pods', auto', scaled_up (C,), scaled_down (C,), reserve_clamped (C,),
    n_activated (C,)) — the caller owns the metrics fold and writeback."""
    C, P = pods.phase.shape
    Gp = st.pg_slot_start.shape[1]
    interval = jnp.float32(consts.scheduling_interval)
    rows = jnp.arange(C, dtype=jnp.int32)[:, None]
    T = TPair(win=W, off=jnp.zeros((C,), jnp.float32))  # (C,)
    Tg = TPair(
        win=jnp.broadcast_to(W[:, None], (C, Gp)),
        off=jnp.zeros((C, Gp), jnp.float32),
    )

    due = t_le(auto.hpa_next, T)
    active = due[:, None] & t_le(st.pg_active_from, Tg)

    gid = st.pod_group_id[:, lo : lo + P]
    gid_c = jnp.where(gid >= 0, gid, Gp)
    run_per_group, util_cpu, util_ram = _hpa_metrics_sample(
        pods, st, W, consts, lo
    )
    present = run_per_group > 0  # group absent from metrics when nothing runs

    # HPA metrics-staleness fix (r14): the scalar HPA reads the metrics
    # collector's LAST 60 s collection sample, not a fresh evaluation at
    # its own tick (metrics/collector.py COLLECTION_INTERVAL; the
    # collection event precedes a same-instant HPA cycle, so a cycle at a
    # collection instant sees the fresh sample). With the latch armed
    # (col_* leaves present), a due collection snapshots (running count,
    # utilization) at this window, and the cycle consumes the latched
    # sample — the NEW one only when the collection time does not exceed
    # the cycle's fire time (both due in one window with the collection
    # later: the cycle still reads the previous sample, like the scalar).
    # At the default scan_interval 60 both cadences tick at the same
    # windows and the latched values equal the inline evaluation — the
    # pre-latch trajectories bit-exactly. Sub-window collection cadences
    # (interval > 60 s) degrade to one collection per window, mirroring
    # the documented CA cadence bound.
    col_state = None
    if auto.col_next is not None:
        col_due, col_state = _latch_collection(
            auto, st, W, interval, run_per_group, util_cpu, util_ram
        )
        # A cycle and a collection at the SAME instant order by the event
        # kernel's FIFO ids — i.e. by EMISSION time: the collection was
        # emitted 60 s before, the cycle scan_interval before, so the
        # collection fires first iff scan_interval <= 60 (at exactly 60
        # the tie breaks to the collection: its handler ran first at the
        # shared emission instant, all the way back to t = 0 where the
        # collector starts before the HPA).
        same_t = t_le(auto.col_next, auto.hpa_next) & t_le(
            auto.hpa_next, auto.col_next
        )
        col_first = t_le(st.hpa_interval, st.col_interval)
        use_new = col_due & (
            t_lt(auto.col_next, auto.hpa_next) | (same_t & col_first)
        )
        run_eff = jnp.where(use_new[:, None], run_per_group, auto.col_run)
        util_cpu = jnp.where(use_new[:, None], util_cpu, auto.col_util_cpu)
        util_ram = jnp.where(use_new[:, None], util_ram, auto.col_util_ram)
        present = run_eff > 0

    current = auto.hpa_tail - auto.hpa_head

    def desired_by(util, target):
        ratio = util / jnp.maximum(target, 1e-9)
        # (C,) per-lane tolerance against the (C, Gp) ratio.
        in_band = jnp.abs(ratio - 1.0) <= st.hpa_tolerance[:, None]
        # -1e-4 guards float32 products landing epsilon above an integer
        # (the scalar path computes the formula in f64).
        d = jnp.ceil(current.astype(jnp.float32) * ratio - 1e-4).astype(jnp.int32)
        return jnp.where(in_band, current, d)

    has_cpu = st.pg_target_cpu > 0
    has_ram = st.pg_target_ram > 0
    d_cpu = desired_by(util_cpu, st.pg_target_cpu)
    d_ram = desired_by(util_ram, st.pg_target_ram)
    desired = jnp.where(
        has_cpu & has_ram,
        jnp.maximum(d_cpu, d_ram),
        jnp.where(has_cpu, d_cpu, jnp.where(has_ram, d_ram, current)),
    )
    desired = jnp.minimum(desired, st.pg_max_pods)

    act = active & present
    delta = jnp.where(act, desired - current, 0)
    # head/tail are monotonic counters: tail = total replicas ever created
    # (the scalar's total_created naming counter), head = total removed, so
    # current = tail - head. Slots are REUSED: name-exact scale-down pops
    # scattered victims, so churn (repeated by the cyclic load curves) frees
    # arbitrary slots, and scale-up activates the first `up` reusable slots
    # of the reserve in slot-offset order; `up` is clamped to the reusable
    # count so the reserve can never be exceeded.
    count_g = jnp.maximum(st.pg_slot_count, 1)
    up0 = jnp.minimum(jnp.maximum(delta, 0), count_g - current)
    down = jnp.minimum(jnp.maximum(-delta, 0), current)

    # Group slot starts in SLICE coords ((C, P); garbage where gid<0).
    slot_start_p = st.pg_slot_start[rows, gid_c] - jnp.int32(lo)
    in_group = gid >= 0
    tail_p = auto.hpa_tail[rows, gid_c]

    # Scale-up activates the FIRST `up` reusable slots of the group's
    # reserve in slot-offset order (name-exact scale-down pops scattered
    # victims, so free slots are not ring-contiguous); the new occupant's
    # replica index idx = tail + rank is STORED in pods.hpa_idx — names are
    # "{group}_{idx}" exactly like the scalar's total_created naming.
    reusable = (
        (pods.phase == PHASE_EMPTY)
        | (pods.phase == PHASE_SUCCEEDED)
        | (pods.phase == PHASE_REMOVED)
        | (pods.phase == PHASE_FAILED)
    )
    if consts.delta_free_visible is not None:
        # A removed replica's slot is not reused while its free is still
        # on the pending-free channel (the new occupant would wipe it).
        reusable = reusable & ~held_frees(pods)
    reuse_in_g = in_group & reusable
    n_reusable = (
        jnp.zeros((C, Gp + 1), jnp.int32)
        .at[rows, gid_c]
        .add(reuse_in_g.astype(jnp.int32))[:, :Gp]
    )
    up = jnp.minimum(up0, n_reusable)
    up_p = up[rows, gid_c]
    down_p = down[rows, gid_c]

    # Rank among the group's reusable slots, slot-offset order (exclusive
    # running count minus its value at the group's first slot).
    cs_excl = (
        jnp.cumsum(reuse_in_g, axis=1, dtype=jnp.int32)
        - reuse_in_g.astype(jnp.int32)
    )
    start_cs = cs_excl[rows, jnp.clip(slot_start_p, 0, P - 1)]
    reuse_rank = cs_excl - start_cs
    activate = reuse_in_g & (reuse_rank < up_p)
    # Global activation rank for unique queue sequence numbers.
    rank = jnp.cumsum(activate, axis=1, dtype=jnp.int32) - 1
    n_up = activate.sum(axis=1, dtype=jnp.int32)
    enq = t_add(T, st.d_hpa_up, interval)  # (C,) pair
    enq_p = _broadcast_pair(enq, (C, P))
    phase = jnp.where(activate, PHASE_QUEUED, pods.phase)
    queue_ts = t_where(activate, enq_p, pods.queue_ts)
    queue_seq = jnp.where(
        activate, queue_seq_counter[:, None] + rank, pods.queue_seq
    )
    initial_attempt_ts = t_where(activate, enq_p, pods.initial_attempt_ts)
    attempts = jnp.where(activate, 1, pods.attempts)
    hpa_idx = jnp.where(activate, tail_p + reuse_rank, pods.hpa_idx)
    # Reset state left over from a previous occupant of a reused slot.
    node = jnp.where(activate, -1, pods.node)
    start_time = t_where(activate, t_zeros((C, P)), pods.start_time)
    finish_time = t_where(activate, t_inf((C, P)), pods.finish_time)

    # --- scale down: remove the lexicographically-smallest replica names --
    # The scalar pops the string-smallest name from the group's live set
    # (kube_horizontal_pod_autoscaler.rs:197-205, a BTreeSet of
    # "{group}_{idx}" names) — NOT FIFO: "g_10" < "g_2". The occupant index
    # lives in pods.hpa_idx (stored at activation); its decimal-string
    # order is a numeric key (left-aligned value, then digit count), and
    # the `down` smallest keys among live group members are the victims.
    # hpa_head stays the total-removed counter, so current = tail - head.
    live = (
        in_group
        & (
            (pods.phase == PHASE_QUEUED)
            | (pods.phase == PHASE_UNSCHEDULABLE)
            | (pods.phase == PHASE_RUNNING)
        )
        & is_inf(pods.removal_time)
        & ~activate
    )
    # Decimal-string order of "{group}_{idx}" names (shared primitive;
    # loud i32 bound at idx >= 10^8 via engine.check_autoscaler_bounds).
    name_key = decimal_string_key(pods.hpa_idx)
    big = jnp.int32(1 << 30)
    sort_gid = jnp.where(live, gid_c, Gp)
    sort_key = jnp.where(live, name_key, big)
    iota_p2 = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None, :], (C, P))
    s_gid, _, s_slot = jax.lax.sort(
        (sort_gid, sort_key, iota_p2), dimension=1, num_keys=2, is_stable=True
    )
    # Rank within group = sorted position - group's first sorted position.
    gseg_start = (
        jnp.full((C, Gp + 1), P, jnp.int32)
        .at[rows, s_gid]
        .min(iota_p2, mode="drop")
    )
    rank_sorted = iota_p2 - gseg_start[rows, s_gid]
    vrank = (
        jnp.zeros((C, P), jnp.int32)
        .at[rows, s_slot]
        .set(rank_sorted)
    )
    deactivate = live & (vrank < down_p)
    removal_time = t_where(activate, t_inf((C, P)), pods.removal_time)
    rem = t_add(T, st.d_hpa_down, interval)  # (C,) pair
    rem_p = _broadcast_pair(rem, (C, P))
    removal_time = t_where(
        deactivate, t_min(removal_time, rem_p), removal_time
    )

    auto = auto._replace(
        hpa_head=auto.hpa_head + down,
        hpa_tail=auto.hpa_tail + up,
        hpa_next=t_where(
            due, t_add(auto.hpa_next, st.hpa_interval, interval), auto.hpa_next
        ),
    )
    if col_state is not None:
        col_next2, col_run2, col_ucpu2, col_uram2 = col_state
        auto = auto._replace(
            col_next=col_next2,
            col_run=col_run2,
            col_util_cpu=col_ucpu2,
            col_util_ram=col_uram2,
        )
    pods = pods._replace(
        phase=phase,
        queue_ts=queue_ts,
        queue_seq=queue_seq,
        initial_attempt_ts=initial_attempt_ts,
        attempts=attempts,
        removal_time=removal_time,
        node=node,
        start_time=start_time,
        finish_time=finish_time,
        hpa_idx=hpa_idx,
    )
    return (
        pods,
        auto,
        up.sum(axis=1, dtype=jnp.int32),
        down.sum(axis=1, dtype=jnp.int32),
        # Replicas the formula wanted (delta, already clamped to the exact
        # scalar max_pod_count bound) but the reserve could not seat —
        # either up0's slot_count-current clamp or the no-reusable-slot
        # clamp. The scalar would have created them: nonzero = divergence,
        # surfaced loudly by engine.check_autoscaler_bounds().
        (jnp.maximum(delta, 0) - up).sum(axis=1, dtype=jnp.int32),
        n_up,
    )


@jax.named_scope("ca_scale_up")
def _ca_scale_up(
    state: ClusterBatchState,
    auto: AutoscaleState,
    st: AutoscaleStatics,
    branch: jnp.ndarray,
    K_up: int,
    phase_v: jnp.ndarray,
    attempts_v: jnp.ndarray,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
):
    """Bin-packing scale-up over the unscheduled-pod cache
    (reference: kube_cluster_autoscaler.rs:190-240). Returns
    (planned (C,S) bool, planned_per_group (C,Gn), reserve_starved (C,) —
    open attempts blocked ONLY by the consumed slot reserve, the
    silent-divergence case engine.check_autoscaler_bounds raises on).
    phase_v/attempts_v are the storage-visible views supplied by ca_pass."""
    pods = state.pods
    C, P = pods.phase.shape
    S = st.ca_slots.shape[1]
    Gn = st.ng_ca_start.shape[1]
    rows1 = jnp.arange(C, dtype=jnp.int32)

    # The storage unscheduled-pods cache: parked pods plus woken-but-unscheduled
    # pods (attempts>=2 after a wake, reference: persistent_storage.rs cache
    # removal only on assignment).
    in_cache = (phase_v == PHASE_UNSCHEDULABLE) | (
        (phase_v == PHASE_QUEUED) & (attempts_v >= 2)
    )

    from kubernetriks_tpu.ops.autoscale_kernel import (
        ca_up_kernel_fits,
        fused_ca_scale_up,
    )

    # NOTE (r5, measured dead end): moving the candidate ordering in-kernel
    # (an iterated 4-key argmin over (P, 128) VMEM pod tiles, mirroring the
    # scheduler's selection kernel) REGRESSED the composed bench 182k ->
    # 176k decisions/s: with a deep cache the loop runs all K_up=64 serial
    # sweeps of 7 (P, 128) tiles (~9.6 ms/window in the xplane profile)
    # while the XLA 4-key sort below costs ~0.06 ms — the scheduler kernel's
    # early-exit win does not transfer because CA backlogs keep k_bound
    # pegged at K_up. See docs/DESIGN.md §3.

    # The storage snapshot is NAME-sorted (scale_up_info, reference
    # persistent_storage.rs:137-146) and bin-packing consumes it in that
    # order. pod_name_rank carries the static lexicographic ranks (BIG for
    # slots whose names are runtime-assigned or shifted — those fall back
    # to queue order after every ranked pod, count-exact).
    name_key = jnp.where(in_cache, st.pod_name_rank, _BIG_I32)
    tie_win = jnp.where(in_cache, pods.queue_ts.win, _BIG_I32)
    tie_off = jnp.where(in_cache, pods.queue_ts.off, jnp.float32(jnp.inf))
    tie_seq = jnp.where(in_cache, pods.queue_seq, _BIG_I32)
    iota_p = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None, :], (C, P))
    _, _, _, _, order_full = jax.lax.sort(
        (name_key, tie_win, tie_off, tie_seq, iota_p), dimension=1,
        num_keys=4, is_stable=True,
    )
    order = order_full[:, :K_up]
    cvalid = _rows_at(in_cache, order) & branch[:, None]
    creq_cpu = _rows_at(pods.req_cpu, order)
    creq_ram = _rows_at(pods.req_ram, order)

    if use_pallas and ca_up_kernel_fits(S, Gn, K_up):
        core = partial(
            fused_ca_scale_up, n_slots=S, interpret=pallas_interpret
        )
        planned_k, g_planned_k, starved_k = core(
            st.ca_max_nodes[:, None],
            auto.ca_count,
            auto.ca_cursor,
            st.ng_max_count,
            st.ng_slot_count,
            st.ng_tmpl_cpu,
            st.ng_tmpl_ram,
            st.ng_ca_start,
            cvalid,
            creq_cpu,
            creq_ram,
        )
        return planned_k, g_planned_k, starved_k[:, 0]

    planned0 = jnp.zeros((C, S), bool)
    plan_seq0 = jnp.full((C, S), _BIG_I32, jnp.int32)
    palloc_cpu0 = jnp.zeros((C, S), jnp.int32)
    palloc_ram0 = jnp.zeros((C, S), jnp.int32)
    g_planned0 = jnp.zeros((C, Gn), jnp.int32)
    total0 = auto.ca_count.sum(axis=1)  # CA counts only (reference quirk:
    # max_node_count bounds CA-owned nodes, kube_cluster_autoscaler.rs:62-80)
    counter0 = jnp.zeros((C,), jnp.int32)
    starved0 = jnp.zeros((C,), jnp.int32)

    def body(carry, xs):
        (
            planned, plan_seq, palloc_cpu, palloc_ram, g_planned, total,
            counter, starved,
        ) = carry
        valid, rcpu, rram = xs

        # First-fit into already-planned nodes, in plan order; fitting pods
        # deduct from the virtual allocatable (reference :81-87). The
        # feasibility mask is the Fit device plugin — CA placement is
        # first-fit BY REFERENCE SEMANTICS regardless of the scheduler
        # profile, but the fit predicate itself is the one registry
        # definition (batched/pipeline.py).
        fit = planned & _fit_filter(
            palloc_cpu, palloc_ram, rcpu[:, None], rram[:, None]
        )
        any_fit = fit.any(axis=1)
        first = jax.lax.argmin(jnp.where(fit, plan_seq, _BIG_I32), 1, jnp.int32)
        use = valid & any_fit
        palloc_cpu = palloc_cpu.at[rows1, jnp.where(use, first, S)].add(
            -rcpu, mode="drop"
        )
        palloc_ram = palloc_ram.at[rows1, jnp.where(use, first, S)].add(
            -rram, mode="drop"
        )

        # Else open a node from the first fitting group (name-sorted at build).
        can_open = valid & ~any_fit & (total < st.ca_max_nodes)
        gcount = auto.ca_count + g_planned
        # Base eligibility (quota headroom + template fit); g_ok adds the
        # slot-reserve cursor bound. Deriving g_ok from the base keeps the
        # starvation counter's "blocked ONLY by the reserve" invariant in
        # lockstep with the actual open decision.
        g_ok_nc = (
            ((st.ng_max_count < 0) | (gcount < st.ng_max_count))
            & (rcpu[:, None] <= st.ng_tmpl_cpu)
            & (rram[:, None] <= st.ng_tmpl_ram)
        )
        g_ok = g_ok_nc & (auto.ca_cursor + g_planned < st.ng_slot_count)
        g_found = g_ok.any(axis=1)
        g = jax.lax.argmax(g_ok, 1, jnp.int32)
        open_ = can_open & g_found
        # Reserve starvation: a group would accept this pod (quota headroom
        # + template fit, with a real reserve) but its never-reclaimed slot
        # reserve is consumed (autoscale.py "Remaining bounded deviations")
        # — counted so the engine raises loudly instead of silently
        # diverging.
        starved = starved + (
            can_open
            & ~g_found
            & (g_ok_nc & (st.ng_slot_count > 0)).any(axis=1)
        ).astype(jnp.int32)
        s_new = (
            st.ng_ca_start[rows1, g]
            + auto.ca_cursor[rows1, g]
            + g_planned[rows1, g]
        )
        s_tgt = jnp.where(open_, s_new, S)
        planned = planned.at[rows1, s_tgt].set(True, mode="drop")
        plan_seq = plan_seq.at[rows1, s_tgt].set(counter, mode="drop")
        # The new node joins at FULL template allocatable: the triggering pod
        # is NOT packed into it (reference quirk, kube_cluster_autoscaler.rs:210-218).
        palloc_cpu = palloc_cpu.at[rows1, s_tgt].set(
            st.ng_tmpl_cpu[rows1, g], mode="drop"
        )
        palloc_ram = palloc_ram.at[rows1, s_tgt].set(
            st.ng_tmpl_ram[rows1, g], mode="drop"
        )
        g_planned = g_planned.at[rows1, jnp.where(open_, g, Gn)].add(1, mode="drop")
        total = total + open_.astype(jnp.int32)
        counter = counter + open_.astype(jnp.int32)
        return (
            planned, plan_seq, palloc_cpu, palloc_ram, g_planned, total,
            counter, starved,
        ), None

    carry0 = (
        planned0, plan_seq0, palloc_cpu0, palloc_ram0, g_planned0, total0,
        counter0, starved0,
    )
    # Early exit at the deepest lane's cache count: the bin-pack is
    # sequential over K_up candidate positions, but typical caches hold a
    # handful of pods — iterating all K_up steps cost ~K_up sequential
    # (C, S) passes per due window.
    k_bound = jnp.minimum(
        jnp.max(cvalid.sum(axis=1, dtype=jnp.int32)), jnp.int32(K_up)
    )

    def loop_body(lcarry):
        k, carry = lcarry
        xs_k = (cvalid[:, k], creq_cpu[:, k], creq_ram[:, k])
        carry, _ = body(carry, xs_k)
        return (k + jnp.int32(1), carry)

    _, (planned, _, _, _, g_planned, _, _, starved) = jax.lax.while_loop(
        lambda lc: lc[0] < k_bound, loop_body, (jnp.int32(0), carry0)
    )
    return planned, g_planned, starved


@jax.named_scope("ca_scale_down")
def _ca_scale_down(
    state: ClusterBatchState,
    auto: AutoscaleState,
    st: AutoscaleStatics,
    branch: jnp.ndarray,
    K_sd: int,
    phase_v: jnp.ndarray,
    alloc_cpu_v: jnp.ndarray,
    alloc_ram_v: jnp.ndarray,
    snap: TPair,
    interval,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    sd_order=None,
    node_rank=None,
):
    """Threshold + simulated-re-placement scale-down
    (reference: kube_cluster_autoscaler.rs:242-290). Returns
    (removed (C,S) bool, removed_per_group (C,Gn)).

    phase_v/alloc_*_v are the storage-visible views from ca_pass; on top of
    them the finish-visibility correction reconstructs what the storage
    knows at the snapshot time `snap`. The allocatable is the scheduler's
    and holds the requests of every running pod and of every pod on the
    pending-free channel (state.held_frees: off its node, the news still on
    its way to the scheduler). The storage lies one hop short of the
    scheduler on that way, so it is the channel's second reader, with its
    own chain: a holder whose finish reached the storage by snap
    (finish_time + ca_finish_vis) counts as gone, its resources freed; one
    whose finish has not still counts as running and still needs
    re-placement, whether the node has finished it or not.

    The correction segment-sum and the node grouping share a node key, so
    ONE 2-key sort serves the grouping (the secondary key puts each node's
    storage-RUNNING pods first in its segment, so the grouping tables slice
    a prefix of it) and ONE (C, P, N) compare of the key against the node
    axis gives every per-node total (_segment_sums: untouched rows carry 0,
    and integer sums are exact in any order).

    What this pass costs on the chip was measured op by op in PR 39's
    traced runs and read in PR 40 (PERF.md sections 5 and 6): of the
    parent's 12.6 ms `ca_pass` a stream window, 9.1 were XLA gathers (paid
    per index: the six boundary reads of the cumulative sums 3.2, the
    per-candidate pod table 2.1, every (C, S) row look-up 0.3-0.5) and 1.4
    the sorts; the (C, P, N) rank-count pair was 0.16. Gathers are paid per
    index on this chip: hence the dense forms (_rows_at,
    _rows_put, _segment_sums) at every look-up whose row is a node or slot
    axis: no XLA gather or scatter is left in the kernel path of this
    pass, in ca_pass or in ca_reclaim_pass. The XLA while_loop walk below
    the kernel branch keeps its per-step reads."""
    pods, nodes = state.pods, state.nodes
    C, P = pods.phase.shape
    N = nodes.alive.shape[1]
    S = st.ca_slots.shape[1]
    Gn = st.ng_ca_start.shape[1]
    rows1 = jnp.arange(C, dtype=jnp.int32)
    rows = rows1[:, None]
    col_n = jnp.arange(N, dtype=jnp.int32)[None, :]
    # Name orderings: the static build tables, or — under slot reclaim —
    # the dynamic orders derived from the occupants' allocation indices
    # (ca_name_order; bit-identical orders while no slot was ever reused).
    if sd_order is None:
        sd_order = st.ca_sd_order
    if node_rank is None:
        node_rank = st.node_name_rank

    snap_p = _broadcast_pair(snap, (C, P))
    # (C,) per-lane finish-visibility delay as a (C, 1) column against the
    # (C, P) pod pairs.
    finish_vis = TPair(
        win=st.ca_finish_vis.win[:, None], off=st.ca_finish_vis.off[:, None]
    )
    # The allocatable's holders: running (at the snapshot's side of the
    # cycle) or on the pending-free channel (the cycle moves no pod on or
    # off it).
    held = held_frees(pods)
    holds = (phase_v == PHASE_RUNNING) | held
    # Holder whose finish notification reached storage by snap: gone.
    vis_gone = holds & t_le(
        t_add(pods.finish_time, finish_vis, interval), snap_p
    )
    # Removals whose storage effect landed by snap: gone. An HPA removal
    # still pending is read from removal_time (already a storage-effect
    # time, d_hpa_down); a removed pod on the channel left the storage
    # before it left its node.
    vis_removed = (phase_v == PHASE_RUNNING) & t_le(pods.removal_time, snap_p)
    vis_gone = vis_gone | vis_removed | (held & (pods.phase == PHASE_REMOVED))

    # Virtual allocatables as the storage sees them: the per-node
    # correction sums are SEGMENT SUMS of the deltas by node, not a (C, P)
    # scatter-add (XLA's TPU scatter lowering costs per index:
    # xplane-measured ~1.1 ms/window at the composed shape). Integer adds,
    # so any summation order is exact.
    node_c = jnp.clip(pods.node, 0, N - 1)
    d_cpu = jnp.where(vis_gone, pods.req_cpu, 0)
    d_ram = jnp.where(vis_gone, pods.req_ram, 0)
    touched = vis_gone
    on_any = holds & ~vis_gone
    # One 2-key sort (node slot, then storage-running FIRST) serves the
    # grouping. on_any and touched pods are holders, which have node >= 0.
    in_seg = touched | on_any
    key_node = jnp.where(in_seg, node_c, jnp.int32(N))
    key2 = jnp.where(on_any, 0, 1).astype(jnp.int32)
    # Only the grouping rides the sort: the request values, which the
    # per-candidate tables slice by segment below.
    _, _, rc_sorted, rr_sorted = jax.lax.sort(
        (key_node, key2, pods.req_cpu, pods.req_ram),
        dimension=1,
        num_keys=2,
        is_stable=True,
    )
    # The per-node totals directly (_segment_sums), off the UNSORTED key:
    # where node n's segment starts in the sorted order, the freed cpu /
    # ram, and the storage-running count. Cumulative sums over the sorted
    # values read at both segment boundaries would be six (C, N) gathers,
    # 3.2 ms a stream window against 0.16 for the compare-and-sum pair
    # (PERF.md section 6, PR 40). Node n's segment LEADS with its on_any
    # pods in slot order (stable sort, key2): the prefix the grouping
    # tables slice.
    seg_start, (freed_cpu, freed_ram, seg_count) = _segment_sums(
        key_node, N, d_cpu, d_ram, on_any
    )
    alloc_cpu_v = alloc_cpu_v + freed_cpu
    alloc_ram_v = alloc_ram_v + freed_ram
    col_k = jnp.arange(K_sd, dtype=jnp.int32)[None, :]

    # Candidate walk order and liveness, shared by both paths: CA slots in
    # node-name order, alive where allocated (the kernel derives its walk
    # bound from cand_alive; the XLA path bounds its while_loop the same way).
    slot_perm = _rows_at(st.ca_slots, sd_order)
    slotc_perm = jnp.clip(slot_perm, 0, N - 1)
    cand_alive = (slot_perm >= 0) & _rows_at(nodes.alive, slotc_perm)

    from kubernetriks_tpu.ops.autoscale_kernel import (
        ca_down_kernel_fits,
        fused_ca_scale_down,
    )

    if use_pallas and ca_down_kernel_fits(N, S, K_sd):
        # Per-candidate pod tables in name order: each candidate's K_sd
        # slice of the sort-carried request values, S * K_sd look-ups a row
        # out of P. Dense too: XLA fuses the (C, P, S * K_sd) compare with
        # both reductions into one op with no temporary (0.93 ms a stream
        # window against the stacked gather's 2.14; faster in the what-if
        # as well: PERF.md section 6, PR 40).
        cnt_perm = jnp.where(
            slot_perm >= 0, _rows_at(seg_count, slotc_perm), 0
        )
        seg_pos = jnp.clip(_rows_at(seg_start, slotc_perm), 0, P - 1)  # (C, S)
        take = jnp.clip(
            seg_pos[:, :, None] + jnp.arange(K_sd, dtype=jnp.int32)[None, None, :],
            0,
            P - 1,
        ).reshape(C, S * K_sd)
        pr_cpu = _rows_at(rc_sorted, take)
        pr_ram = _rows_at(rr_sorted, take)
        pv0 = (
            jnp.arange(K_sd, dtype=jnp.int32)[None, None, :]
            < cnt_perm[:, :, None]
        ).reshape(C, S * K_sd)
        not_pending = is_inf(nodes.remove_time)
        thresh = jnp.broadcast_to(
            st.ca_threshold.astype(jnp.float32), (C,)
        )[:, None]

        core = partial(fused_ca_scale_down, k_sd=K_sd, interpret=pallas_interpret)
        removed_perm = core(
            branch[:, None],
            thresh,
            nodes.alive,
            not_pending,
            nodes.cap_cpu,
            nodes.cap_ram,
            alloc_cpu_v,
            alloc_ram_v,
            node_rank,
            slot_perm,
            cand_alive,
            cnt_perm,
            pr_cpu,
            pr_ram,
            pv0,
        )
        # Back from name-order positions to CA-slot indices (ca_sd_order is
        # a permutation, so each slot has exactly one source).
        removed = _rows_put(sd_order, removed_perm, S)
        return _per_group(removed, st, Gn)

    def outer(carry, s):
        valloc_cpu, valloc_ram = carry
        # The scalar walks candidates in NODE-NAME order (info.nodes is
        # name-sorted) and earlier candidates' committed re-placements are
        # visible to later ones — iterate CA slots through the name-order
        # permutation, (C,) per cluster.
        sidx = jax.lax.dynamic_index_in_dim(sd_order, s, 1, keepdims=False)
        # (C,) global node slot of this candidate.
        slot = st.ca_slots[rows1, sidx]
        slot_ok = (slot >= 0) & branch
        slotc = jnp.clip(slot, 0, N - 1)
        alive_here = nodes.alive[rows1, slotc] & slot_ok

        cap_cpu = jnp.maximum(nodes.cap_cpu[rows1, slotc], 1).astype(jnp.float32)
        cap_ram = jnp.maximum(nodes.cap_ram[rows1, slotc], 1).astype(jnp.float32)
        used_cpu = (nodes.cap_cpu[rows1, slotc] - valloc_cpu[rows1, slotc]).astype(
            jnp.float32
        )
        used_ram = (nodes.cap_ram[rows1, slotc] - valloc_ram[rows1, slotc]).astype(
            jnp.float32
        )
        util = jnp.maximum(used_cpu / cap_cpu, used_ram / cap_ram)
        # A node already pending removal (effect time beyond this window) must
        # not be re-selected: it would double-decrement ca_count.
        not_pending = is_inf(
            TPair(
                win=nodes.remove_time.win[rows1, slotc],
                off=nodes.remove_time.off[rows1, slotc],
            )
        )
        # f32 compare on both sides: the Mosaic kernel path has no f64, so
        # the XLA path casts the threshold down too — bit-identical paths.
        eligible = alive_here & not_pending & (
            util < st.ca_threshold.astype(jnp.float32)
        )

        # Pods assigned to this node (storage assignments include in-flight
        # bindings, matching PHASE_RUNNING): the K_sd-slice of this node's
        # segment in pod-slot order.
        cnt = seg_count[rows1, slotc] * slot_ok.astype(jnp.int32)
        attempt = eligible & (cnt <= K_sd)  # overflow: conservatively skip

        seg_pos = jnp.clip(seg_start[rows1, slotc], 0, P - 1)
        take = jnp.clip(seg_pos[:, None] + col_k, 0, P - 1)
        pvalid = (col_k < cnt[:, None]) & attempt[:, None]
        prcpu = rc_sorted[rows, take]
        prram = rr_sorted[rows, take]

        save_cpu, save_ram = valloc_cpu, valloc_ram

        def inner(icarry, ixs):
            vcpu, vram, ok = icarry
            pv, rcpu, rram = ixs
            fit = (
                nodes.alive
                & (col_n != slot[:, None])
                & _fit_filter(vcpu, vram, rcpu[:, None], rram[:, None])
            )
            any_fit = fit.any(axis=1)
            # First-fit in NODE-NAME order (the scalar iterates the
            # name-sorted info.nodes list; _node_fits_pod first match).
            tgt = jax.lax.argmin(
                jnp.where(fit, node_rank, _BIG_I32), 1, jnp.int32
            )
            place = pv & any_fit
            vcpu = vcpu.at[rows1, jnp.where(place, tgt, N)].add(-rcpu, mode="drop")
            vram = vram.at[rows1, jnp.where(place, tgt, N)].add(-rram, mode="drop")
            ok = ok & (~pv | any_fit)
            return (vcpu, vram, ok), None

        (vcpu, vram, all_ok), _ = jax.lax.scan(
            inner,
            (valloc_cpu, valloc_ram, jnp.ones((C,), bool)),
            (pvalid.T, prcpu.T, prram.T),
        )
        success = attempt & all_ok
        # Commit the re-placement on success, roll back otherwise
        # (reference :141-156); commits persist across later candidates.
        valloc_cpu = jnp.where(success[:, None], vcpu, save_cpu)
        valloc_ram = jnp.where(success[:, None], vram, save_ram)
        return valloc_cpu, valloc_ram, success

    def loop_body(carry):
        s, valloc_cpu, valloc_ram, removed = carry
        valloc_cpu, valloc_ram, success = outer((valloc_cpu, valloc_ram), s)
        sidx = jax.lax.dynamic_index_in_dim(sd_order, s, 1, keepdims=False)
        removed = removed.at[rows1, sidx].max(success)
        return (s + jnp.int32(1), valloc_cpu, valloc_ram, removed)

    # Name-order iteration: allocated slots are not a prefix of the name
    # permutation, so bound the walk by the LAST alive candidate's position
    # in permuted order (zero iterations before the first scale-up; dead /
    # unallocated slots inside the bound no-op through the alive_here gate).
    iota_s = jnp.arange(S, dtype=jnp.int32)[None, :]
    s_bound = jnp.max(jnp.where(cand_alive, iota_s + 1, 0)).astype(jnp.int32)
    _, _, _, removed = jax.lax.while_loop(
        lambda carry: carry[0] < s_bound,
        loop_body,
        (
            jnp.int32(0),
            alloc_cpu_v,
            alloc_ram_v,
            jnp.zeros((C, S), bool),
        ),
    )
    return _per_group(removed, st, Gn)


def _per_group(removed, st, Gn):
    """(removed (C, S) bool, per-group removal counts (C, Gn)) — the
    shared aggregation tail of both scale-down paths. A padding slot
    (group -1) is never removed and matches no group."""
    return removed, _rows_put(
        st.ca_slot_group, removed.astype(jnp.int32), Gn
    )


@jax.named_scope("ca_pass")
def ca_pass(
    state: ClusterBatchState,
    auto: AutoscaleState,
    st: AutoscaleStatics,
    W: jnp.ndarray,
    consts: StepConstants,
    K_up: int,
    K_sd: int,
    pre=None,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    nodes_lane_major: bool = False,
    reclaim: bool = False,
) -> Tuple[ClusterBatchState, AutoscaleState]:
    """One masked cluster-autoscaler cycle (scalar equivalent:
    cluster_autoscaler.py cycle; AUTO info policy: scale up iff the
    unscheduled cache is non-empty, reference: persistent_storage.rs:381-412).

    nodes_lane_major (KTPU_LANE_MAJOR): the hot node leaves arrive (N, C);
    the CA glue is (C, N)-oriented (name-order look-ups, grouping sorts), so
    it normalizes to row-major VIEWS here — a handful of transposes per
    window against the ~20 kernel-boundary transposes the mode removes in
    the base window (docs/DESIGN.md §"Lane-major hot state"). The pass only
    WRITES the pending pairs (create_time / remove_time — row-major
    always), so nothing converts back. _ca_scale_down's docstring says
    what the pass costs on the chip and why no look-up in it is an XLA gather or scatter (the
    slot-table touches and the allocation stamp below included: _rows_put,
    _rows_at; with one node group the stamp's three reads are broadcasts).

    Exact cadence + snapshot semantics (r4): `auto.ca_next` is the TRUE
    cycle-fire time c_k (the scalar re-arms scan_interval after the info
    round-trip returns, so the period drifts relative to windows); the
    storage snapshot the decision reads lands at s_k = c_k + ca_snap. Cycle
    k runs in the window W with W*iv <= s_k < (W+1)*iv, whose post-cycle
    state matches the snapshot up to two sub-window corrections:

    - pre-cycle shadows: if s_k precedes this window's commit-visibility
      time T + ca_commit_vis, the storage has not yet seen THIS cycle's
      assignments/parks — `pre` = (phase, attempts, alloc_cpu, alloc_ram)
      captured before the cycle supplies the storage's view.
    - finish visibility (handled inside _ca_scale_down): the storage learns
      a pod finish at F + ca_finish_vis, which can be on either side of s_k
      relative to the window boundary the arrays reflect.
    """
    pods, nodes, metrics = state.pods, state.nodes, state.metrics
    # ONE owner of the hot-leaf transpose set (state.swap_node_layout);
    # the pass reads through the row-major view and writes the pending
    # pairs back through the ORIGINAL `nodes`, so the hot leaves keep
    # their incoming layout.
    state_row = swap_node_layout(state) if nodes_lane_major else state
    nodes_row = state_row.nodes
    C = pods.phase.shape[0]
    interval = jnp.float32(consts.scheduling_interval)
    T = TPair(win=W, off=jnp.zeros((C,), jnp.float32))
    T_next = TPair(win=W + 1, off=jnp.zeros((C,), jnp.float32))

    c_k = auto.ca_next
    snap = t_add(c_k, st.ca_snap, interval)
    due = t_lt(snap, T_next)

    commit_vis = t_add(T, st.ca_commit_vis, interval)
    early_snap = due & t_lt(snap, commit_vis)
    if pre is not None:
        pre_phase, pre_attempts, pre_alloc_cpu, pre_alloc_ram = pre
        if nodes_lane_major:
            pre_alloc_cpu = pre_alloc_cpu.T
            pre_alloc_ram = pre_alloc_ram.T
        phase_v = jnp.where(early_snap[:, None], pre_phase, pods.phase)
        attempts_v = jnp.where(early_snap[:, None], pre_attempts, pods.attempts)
        alloc_cpu_v = jnp.where(
            early_snap[:, None], pre_alloc_cpu, nodes_row.alloc_cpu
        )
        alloc_ram_v = jnp.where(
            early_snap[:, None], pre_alloc_ram, nodes_row.alloc_ram
        )
    else:
        phase_v, attempts_v = pods.phase, pods.attempts
        alloc_cpu_v, alloc_ram_v = nodes_row.alloc_cpu, nodes_row.alloc_ram

    in_cache = (phase_v == PHASE_UNSCHEDULABLE) | (
        (phase_v == PHASE_QUEUED) & (attempts_v >= 2)
    )
    any_unsched = in_cache.any(axis=1)
    up_branch = due & any_unsched
    down_branch = due & ~any_unsched

    # Branch around the whole pass bodies: most windows have an empty
    # unscheduled cache (no scale-up work) and scale-down's pod grouping
    # ((C, P) sort) only matters once CA nodes exist. Under a mesh the
    # predicates are the SHARD's own (the whole program sits in one
    # shard_map): a shard whose clusters have no such work skips the body,
    # which for them is the identity, whatever another shard does.
    S = st.ca_slots.shape[1]
    Gn = st.ng_ca_start.shape[1]
    planned, planned_per_group, up_starved = jax.lax.cond(
        up_branch.any(),
        lambda: _ca_scale_up(
            state_row, auto, st, up_branch, K_up, phase_v, attempts_v,
            use_pallas=use_pallas,
            pallas_interpret=pallas_interpret,
        ),
        lambda: (
            jnp.zeros((C, S), bool),
            jnp.zeros((C, Gn), jnp.int32),
            jnp.zeros((C,), jnp.int32),
        ),
    )
    def _down_branch():
        # Under reclaim the candidate-walk and re-placement orders are
        # derived from the live occupants' allocation indices (the static
        # tables describe slot-index names, stale once a slot is reused);
        # computed inside the cond so quiet windows never pay the sort.
        sd_order = node_rank = None
        if reclaim and auto.ca_alloc is not None:
            sd_order, node_rank = ca_name_order(auto, st)
        return _ca_scale_down(
            state_row, auto, st, down_branch, K_sd,
            phase_v, alloc_cpu_v, alloc_ram_v, snap, interval,
            use_pallas=use_pallas,
            pallas_interpret=pallas_interpret,
            sd_order=sd_order,
            node_rank=node_rank,
        )

    removed, removed_per_group = jax.lax.cond(
        # ca_count (live CA nodes) rather than ca_cursor (ever allocated):
        # once everything scaled back down there is nothing to remove.
        down_branch.any() & (auto.ca_count.sum() > 0),
        _down_branch,
        lambda: (jnp.zeros((C, S), bool), jnp.zeros((C, Gn), jnp.int32)),
    )

    # Planned slots come alive at their effect time; removals likewise. The
    # effect-time value is one (C,) pair — put a boolean touch mask through
    # the slot table (_rows_put) and merge the pair elementwise.
    _, S = planned.shape
    N = nodes_row.alive.shape[1]
    touch_create = _rows_put(st.ca_slots, planned, N)
    eff_up = _broadcast_pair(t_add(c_k, st.d_ca_up, interval), (C, N))
    create_time = t_where(
        touch_create, t_min(nodes.create_time, eff_up), nodes.create_time
    )
    touch_remove = _rows_put(st.ca_slots, removed, N)
    eff_down = _broadcast_pair(t_add(c_k, st.d_ca_down, interval), (C, N))
    remove_time = t_where(
        touch_remove, t_min(nodes.remove_time, eff_down), nodes.remove_time
    )

    metrics = metrics._replace(
        scaled_up_nodes=metrics.scaled_up_nodes + planned.sum(axis=1, dtype=jnp.int32),
        scaled_down_nodes=metrics.scaled_down_nodes + removed.sum(axis=1, dtype=jnp.int32),
        ca_reserve_starved=metrics.ca_reserve_starved + up_starved,
    )
    new_auto = auto._replace(
        ca_count=auto.ca_count + planned_per_group - removed_per_group,
        ca_cursor=auto.ca_cursor + planned_per_group,
        ca_next=t_where(
            due, t_add(c_k, st.ca_period, interval), c_k
        ),
    )
    if reclaim and auto.ca_alloc is not None:
        # Stamp each opened slot's allocation index (the scalar's
        # total_allocated at open time; names are "{group}_{alloc+1}").
        # Scale-up opens the offsets [cursor, cursor + planned) of each
        # group's reserve in slot order, which is also allocation order,
        # so the index is cursor-relative arithmetic — no carry needed
        # through the bin-pack loop or the Pallas kernel.
        gidc = jnp.clip(st.ca_slot_group, 0, st.ng_ca_start.shape[1] - 1)
        iota_s = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], planned.shape
        )
        off_in_g = iota_s - _rows_at(st.ng_ca_start, gidc)
        alloc_new = (
            _rows_at(auto.ca_total, gidc)
            + off_in_g
            - _rows_at(auto.ca_cursor, gidc)
        )
        new_auto = new_auto._replace(
            ca_alloc=jnp.where(planned, alloc_new, auto.ca_alloc),
            ca_total=auto.ca_total + planned_per_group,
        )
    auto = new_auto
    state = state._replace(
        nodes=nodes._replace(create_time=create_time, remove_time=remove_time),
        metrics=metrics,
    )
    return state, auto


@jax.named_scope("ca_reclaim")
def ca_reclaim_pass(
    state: ClusterBatchState,
    auto: AutoscaleState,
    st: AutoscaleStatics,
    nodes_lane_major: bool = False,
) -> Tuple[ClusterBatchState, AutoscaleState]:
    """CA slot reclaim: return fully-RETIRED reserve slots to their group
    by a stable in-trace compaction, so ca_cursor tracks live occupancy
    and sustained churn never exhausts the reserve (the batched analog of
    the reference's node_component_pool reuse, node_component_pool.rs:60-77).

    Runs at the START of the window body — a clean state boundary, and it
    guarantees a scale-up later in the same window sees every slot that
    was reclaimable, so the loud starvation bound can only fire when the
    reserve is truly exhausted by LIVE demand.

    A slot is retired when its node's removal has fully drained:
    - the node is dead with no pending create/remove effect, and
    - no pod still binds it as RUNNING or from the pending-free channel
      (state.held_frees: its free is still owed to the slot, and a storage
      snapshot may still count the pod on it; pods past the channel
      contribute nothing to any later pass and their stale slot pointers
      are remapped along with the move).

    Compaction is STABLE per group (keepers pack to the group's reserve
    prefix in slot order), which preserves the two orderings exactness
    rests on: slot order among live CA nodes stays allocation order (the
    scheduler's slot-order tie-break is untouched), and names ride the
    occupants' allocation indices (ca_alloc), so every name-ordered walk
    (ca_name_order) is invariant under the move. When nothing retires the
    permutation is the identity and the pass is a bit-exact no-op; the
    whole body sits behind a cond on the cheap (C, S) dead-slot predicate
    so quiet windows pay only the predicate.
    """
    if auto is None or auto.ca_alloc is None:
        return state, auto
    nodes, pods = state.nodes, state.pods
    C = pods.phase.shape[0]
    S = auto.ca_alloc.shape[1]
    Gn = st.ng_ca_start.shape[1]
    alive_row = nodes.alive.T if nodes_lane_major else nodes.alive
    N = alive_row.shape[1]
    n_trace = N - S
    slots = st.ca_slots
    slotc = jnp.clip(slots, 0, N - 1)
    occupied = auto.ca_alloc >= 0

    # Cheap per-window predicate: an occupied slot whose node is dead
    # with no pending effects ((C, S) look-ups out of the node axis only).
    dead = (
        occupied
        & (slots >= 0)
        & ~_rows_at(alive_row, slotc)
        & is_inf(
            TPair(
                win=_rows_at(nodes.create_time.win, slotc),
                off=_rows_at(nodes.create_time.off, slotc),
            )
        )
        & is_inf(
            TPair(
                win=_rows_at(nodes.remove_time.win, slotc),
                off=_rows_at(nodes.remove_time.off, slotc),
            )
        )
    )
    do = dead.any()

    iota_s = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (C, S))
    grp = jnp.where(st.ca_slot_group >= 0, st.ca_slot_group, Gn)

    def _compact():
        # Row-major views of the hot node leaves (transposes only inside
        # this rare branch; the pending pairs are row-major by contract).
        alive_r = nodes.alive.T if nodes_lane_major else nodes.alive
        acpu_r = nodes.alloc_cpu.T if nodes_lane_major else nodes.alloc_cpu
        aram_r = nodes.alloc_ram.T if nodes_lane_major else nodes.alloc_ram
        capc_r = nodes.cap_cpu.T if nodes_lane_major else nodes.cap_cpu
        capr_r = nodes.cap_ram.T if nodes_lane_major else nodes.cap_ram

        # Retirement safety: pods still binding the node, which are the
        # allocatable's holders: RUNNING, or on the pending-free channel
        # (a free still owed to the slot, and a pod a storage snapshot
        # may still count on it, _ca_scale_down).
        blocking = (
            (pods.phase == PHASE_RUNNING) | held_frees(pods)
        ) & (pods.node >= 0)
        node_blocked = _rows_put(pods.node, blocking, N)
        retired = dead & ~_rows_at(node_blocked, slotc)
        keep = occupied & ~retired

        # Stable per-group partition: keepers first in slot order (slot
        # ranges per group are contiguous by construction).
        _, _, order = jax.lax.sort(
            (grp, jnp.where(keep, 0, 1).astype(jnp.int32), iota_s),
            dimension=1,
            num_keys=2,
            is_stable=True,
        )
        inv = _rows_put(order, iota_s, S)
        take = lambda a: _rows_at(a, order)  # noqa: E731

        # Permute the CA node segment (caps and crash payload are uniform
        # within a group / zero on CA slots — permutation-invariant, not
        # rewritten). Retired slots reset to pristine allocatable.
        seg = lambda a: a[:, n_trace:]  # noqa: E731
        retired_n = take(retired)
        alive_seg = take(seg(alive_r))
        acpu_seg = jnp.where(
            retired_n, seg(capc_r), take(seg(acpu_r))
        )
        aram_seg = jnp.where(
            retired_n, seg(capr_r), take(seg(aram_r))
        )
        ctw_seg = take(seg(nodes.create_time.win))
        cto_seg = take(seg(nodes.create_time.off))
        rtw_seg = take(seg(nodes.remove_time.win))
        rto_seg = take(seg(nodes.remove_time.off))

        cat = lambda full, s_: jnp.concatenate(  # noqa: E731
            [full[:, :n_trace], s_], axis=1
        )
        alive2 = cat(alive_r, alive_seg)
        acpu2 = cat(acpu_r, acpu_seg)
        aram2 = cat(aram_r, aram_seg)
        if nodes_lane_major:
            alive2, acpu2, aram2 = alive2.T, acpu2.T, aram2.T

        # Stale or live slot pointers follow the move (terminal pods past
        # the visibility horizon keep pointing at their retired slot's
        # new position; nothing ever reads them again).
        pn = pods.node
        ca_ptr = pn >= n_trace
        pn2 = jnp.where(
            ca_ptr,
            n_trace + _rows_at(inv, jnp.clip(pn - n_trace, 0, S - 1)),
            pn,
        )

        keep_cnt = _rows_put(grp, keep.astype(jnp.int32), Gn)
        return (
            alive2,
            acpu2,
            aram2,
            cat(nodes.create_time.win, ctw_seg),
            cat(nodes.create_time.off, cto_seg),
            cat(nodes.remove_time.win, rtw_seg),
            cat(nodes.remove_time.off, rto_seg),
            pn2,
            jnp.where(retired_n, -1, take(auto.ca_alloc)),
            keep_cnt,
            auto.ca_reclaimed + retired.sum(axis=1, dtype=jnp.int32),
        )

    def _identity():
        return (
            nodes.alive,
            nodes.alloc_cpu,
            nodes.alloc_ram,
            nodes.create_time.win,
            nodes.create_time.off,
            nodes.remove_time.win,
            nodes.remove_time.off,
            pods.node,
            auto.ca_alloc,
            auto.ca_cursor,
            auto.ca_reclaimed,
        )

    (
        alive2, acpu2, aram2, ctw2, cto2, rtw2, rto2, pn2,
        alloc2, cursor2, reclaimed2,
    ) = jax.lax.cond(do, _compact, _identity)
    state = state._replace(
        nodes=nodes._replace(
            alive=alive2,
            alloc_cpu=acpu2,
            alloc_ram=aram2,
            create_time=TPair(win=ctw2, off=cto2),
            remove_time=TPair(win=rtw2, off=rto2),
        ),
        pods=pods._replace(node=pn2),
    )
    auto = auto._replace(
        ca_alloc=alloc2, ca_cursor=cursor2, ca_reclaimed=reclaimed2
    )
    return state, auto


# Donated standalone entry points. Inside the window step the passes are
# already FUSED into the chunk program (step._window_body calls them in-trace,
# so there is no separate HPA/CA dispatch in the steady-state loop); these
# wrappers serve callers that drive a pass by itself (tests, exploratory
# tools) with the same in-place buffer reuse the donated window entries get.
# They take the full state ONLY — state.auto carries the AutoscaleState — so
# donation never sees the same buffer through two arguments (state and a
# separately-passed auto alias). Bit-identical to the plain calls
# (tests/test_window_donation_dispatch.py).
@partial(jax.jit, static_argnames=("seg",), donate_argnums=(0,))
def hpa_pass_donated(
    state: ClusterBatchState,
    st: AutoscaleStatics,
    W: jnp.ndarray,
    consts: StepConstants,
    seg=None,
) -> ClusterBatchState:
    state2, auto2 = hpa_pass(state, state.auto, st, W, consts, seg=seg)
    return state2._replace(auto=auto2)


_CA_PASS_STATICS = (
    "K_up", "K_sd", "use_pallas", "pallas_interpret", "shards", "reclaim",
)


@partial(jax.jit, static_argnames=_CA_PASS_STATICS, donate_argnums=(0,))
@over_clusters(_CA_PASS_STATICS, lambda axis, _statics: PartitionSpec(axis))
def ca_pass_donated(
    state: ClusterBatchState,
    st: AutoscaleStatics,
    W: jnp.ndarray,
    consts: StepConstants,
    K_up: int,
    K_sd: int,
    pre=None,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    shards=None,
    reclaim: bool = False,
) -> ClusterBatchState:
    state2, auto2 = ca_pass(
        state, state.auto, st, W, consts, K_up, K_sd, pre=pre,
        use_pallas=use_pallas, pallas_interpret=pallas_interpret,
        reclaim=reclaim,
    )
    return state2._replace(auto=auto2)
