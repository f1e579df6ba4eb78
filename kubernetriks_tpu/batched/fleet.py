"""Scenario-vector fleet: per-cluster config lanes over ONE compiled engine.

The cluster-batch axis C steps hundreds of clusters in lockstep, but until
this module every autoscaler parameter was a per-run scalar folded into the
`AutoscaleStatics` / `StepConstants` leaves at engine build — a parameter
sweep or what-if query paid a fresh engine, a full XLA compile and warm-up
per scenario. Here the scenario-bearing control-law parameters ride as
per-cluster (C,)-shaped TRACED arrays instead (ROADMAP #4: "per-cluster
config vectors instead of Python scalars"), so ONE compiled window /
superspan program serves any scenario mix, and this module supplies:

- `Scenario`: the per-lane config delta a what-if query carries. The
  vectorizable set is exactly the parameters that (a) do not shape
  programs and (b) enter ONLY the autoscaler chains, so a lane with
  overrides stays lane-by-lane equivalent to a scalar run with the same
  scalars (tests/test_fleet.py pins it):
    * HPA scan interval, target-threshold tolerance, per-lane enable
    * CA scan interval, scale-down utilization threshold, node quota
    * as_to_ca_network_delay (the one config delay that feeds ONLY the
      autoscaler chains: d_hpa_up/down, d_ca_up/down, ca_period, ca_snap)
    * the pod-fault PRNG seed (`fault_injection` already keys draws
      per-cluster; the fleet generalizes that to per-lane seeds keyed on
      cluster 0, making a lane's fault stream a pure function of its
      scenario — see StepConstants.fault_seed)
  Slot counts, reserve sizes, the scheduling interval and everything else
  shape- or program-bearing stays a build-time static.
- `scenario_leaves`: the ONE owner of the scalar->per-lane composition
  rules (the delay-chain formulas previously inlined in
  engine.build_autoscale_statics). Both the engine build and the fleet's
  between-query updates go through it, so the two can never drift.
- `ScenarioFleet`: a resident front-end that packs incoming what-if
  queries (config delta + horizon) into cluster lanes, resets the lanes'
  state columns in place (donation-friendly select re-init against the
  pristine build snapshot — no recompile, no re-warm), runs the resident
  composed engine, and reads per-lane results back at the horizon
  boundaries where the host already blocks (the telemetry-ring drain
  points — zero NEW syncs inside the dispatch loop). Compile and warm-up
  amortize across the whole query stream.

Lane reset protocol — two modes:

- WAVE-aligned (the default): the engine's window clock is fleet-global,
  so queries pack into C-lane waves — all lanes reset together at a wave
  boundary, then the wave runs to its queries' horizons (lanes whose
  horizon came early keep simulating idle until the wave drains).
- LANE-ASYNCHRONOUS (`lane_async=True`, DESIGN §13): the engine carries
  per-lane window clocks (StepConstants.lane_clock / lane_horizon —
  traced (C,) data), each lane steps its own virtual span inside the
  shared window programs, and a finished lane is reset + re-seeded IN
  PLACE while neighbors keep stepping. Queries flow through a continuous
  `submit()` / `pump()` / `poll()` engine (`run_async()` drains the
  queue); per-query results are bit-identical to the wave-aligned path
  on the same (scenario, horizon) mix (tests/test_fleet_async.py's A/B
  gate), per-lane completion is pure host arithmetic over the clock
  mirrors (zero new syncs), and the telemetry ring's lane_active column
  feeds the observatory's lane-occupancy gauge + idle-lane verdict.

A pump round's transport (PR 33, DESIGN §13.3): one packed readback of
every lane's result row (`_pack_lane_rows`, one blocking copy, rows sliced
on the host for the finished lanes; the wave path reads its horizons
through the same function) and one packed admission (`engine.admit_lanes`
-> `_admit_lanes`: scenario rows, pristine select and lane clocks of the
masked lanes from one host buffer), so the host-device round trips of a
round do not depend on how many lanes finished or were admitted. The
recorder's `pump_transfers_down` / `pump_transfers_up` count them.

Query observatory (PR 17, DESIGN §14): every query carries a host-side
lifecycle record (submitted → admitted-to-lane → first-dispatch →
horizon-drained → polled, all perf_counter_ns stamps — no device reads),
the tracer gets a queue-wait and a service span per query linked by a
submit→drain Chrome flow plus a per-lane swimlane event, and the latency
statistics live in bounded log-bucketed streaming histograms
(telemetry/histogram.py: O(buckets) forever, never O(queries)) with the
queue-wait (submit→admit) vs service (admit→drain) split.

Fault domains (PR 19, DESIGN §15): the unit of failure is a QUERY or a
LANE, never the fleet. Terminal failures are TYPED results
(batched/faults.py QueryError taxonomy) streamed through `poll()` under
the same stream-once contract as `FleetResult`s — every submitted qid
streams exactly one terminal outcome, so a client never hangs on a dead
query. A failing dispatch fails only the occupying lane's query
(`LaneFaultError`), the lane is crash-reset from the pristine snapshot
(the PR 13 donated-select machinery reused as recovery — pure data ops,
zero recompiles), and a lane faulting repeatedly inside a window is
QUARANTINED out of the admission rotation with exponential-backoff probe
re-admission (observatory `lane_state` gauge + `lane_quarantine`
verdict). `submit()` gains a bounded queue with reject/block
backpressure and per-query deadlines enforced at host boundaries the
pump already crosses; `close()` is a graceful drain (stop admitting,
finish in-flight, fail queued with `ShutdownError`). With
`KTPU_HOST_CHAOS` unset and no injector armed, every new path is gated
on `self._chaos is None` / empty fault ledgers — the layer is provably
free when quiet (per-query A/B bit-identity + dispatch_stats equality,
pinned in tests/test_fleet_async.py and
tests/test_fleet_faults.py::test_quiet_robustness_layer_is_free).
"""

from __future__ import annotations

import time
from collections import deque
from collections.abc import Mapping
from dataclasses import dataclass, fields
from functools import partial
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from kubernetriks_tpu.config import (
    KubeClusterAutoscalerConfig,
    KubeHorizontalPodAutoscalerConfig,
    SimulationConfig,
)
from kubernetriks_tpu.batched.faults import (
    DeadlineExceededError,
    HostChaos,
    InjectedFault,
    LaneFaultError,
    QueryError,
    RejectedError,
    ShutdownError,
)
from kubernetriks_tpu.telemetry.histogram import LatencyHistogram
from kubernetriks_tpu.telemetry.observatory import lane_windows_share
from kubernetriks_tpu.telemetry.tracer import (
    PH_LANE_DISPATCH,
    PH_LANE_QUARANTINE,
    PH_PUMP,
    PH_PUMP_ADMIT,
    PH_PUMP_DRAIN,
    PH_QUERY_FAIL,
    PH_QUERY_QUEUE,
    PH_QUERY_SERVICE,
    PH_RESULT_WAIT,
    build_span,
)

# Lifecycle records retired at poll() survive in a bounded trail (the
# most recent polled queries stay inspectable via query_lifecycle()).
_POLLED_LIFECYCLES_KEPT = 128
# Exact-sample cross-check window: the open-loop bench compares the
# histogram-derived p99 against the exact sorted-array p99 over this many
# most-recent latencies while both exist (bounded — the histogram is the
# statistic of record once the stream outgrows it).
_EXACT_LATENCY_WINDOW = 1024

# Scenario keys accepted as per-lane overrides (the vectorizable set).
SCENARIO_KEYS = (
    "hpa_scan_interval",
    "hpa_tolerance",
    "hpa_enabled",
    "ca_scan_interval",
    "ca_threshold",
    "ca_max_node_count",
    "as_to_ca_network_delay",
    "fault_seed",
)


@dataclass(frozen=True)
class Scenario:
    """One what-if query's config delta: every field is an override of the
    base SimulationConfig's value for ONE cluster lane (None = keep the
    base). `ca_max_node_count: 0` disables CA scale-up for the lane (quota
    0 plans nothing and counts no starvation); `hpa_enabled: False` parks
    the lane's pod groups (pg_active_from = +inf), matching a scalar run
    with the HPA off while the initial replicas still run."""

    hpa_scan_interval: Optional[float] = None
    hpa_tolerance: Optional[float] = None
    hpa_enabled: Optional[bool] = None
    ca_scan_interval: Optional[float] = None
    ca_threshold: Optional[float] = None
    ca_max_node_count: Optional[int] = None
    as_to_ca_network_delay: Optional[float] = None
    fault_seed: Optional[int] = None

    def overrides(self) -> Dict[str, object]:
        return {
            f.name: getattr(self, f.name)
            for f in fields(self)
            if getattr(self, f.name) is not None
        }


def _base_values(config: SimulationConfig) -> Dict[str, float]:
    """The base config's value for every scenario key — the scalar the
    per-lane vector is filled with where a lane has no override."""
    hpa = config.horizontal_pod_autoscaler
    ca = config.cluster_autoscaler
    hpa_tol = (
        hpa.kube_horizontal_pod_autoscaler_config
        or KubeHorizontalPodAutoscalerConfig()
    ).target_threshold_tolerance
    ca_thresh = (
        ca.kube_cluster_autoscaler or KubeClusterAutoscalerConfig()
    ).scale_down_utilization_threshold
    return {
        "hpa_scan_interval": float(hpa.scan_interval),
        "hpa_tolerance": float(hpa_tol),
        "hpa_enabled": bool(hpa.enabled),
        "ca_scan_interval": float(ca.scan_interval),
        "ca_threshold": float(ca_thresh),
        "ca_max_node_count": int(ca.max_node_count if ca.enabled else 0),
        "as_to_ca_network_delay": float(config.as_to_ca_network_delay),
        "fault_seed": int(
            config.fault_injection.seed
            if getattr(config, "fault_injection", None) is not None
            and config.fault_injection.seed is not None
            else config.seed
        ),
    }


def scenario_vectors(
    config: SimulationConfig,
    n_lanes: int,
    scenarios: Optional[Sequence[Optional[Scenario]]] = None,
    base_vectors: Optional[Dict[str, np.ndarray]] = None,
) -> Dict[str, np.ndarray]:
    """Materialize the per-lane (C,) scenario vectors: the base config's
    value everywhere (or a copy of `base_vectors` when given — the
    fleet's per-wave composition starts from its BUILD vectors, so a
    lane with no override keeps its build-time config, node-fault seeds
    included), each lane's Scenario overrides applied on top.
    scenarios: at most n_lanes entries (None entries keep the base)."""
    base = _base_values(config)
    out: Dict[str, np.ndarray] = {}
    for key in SCENARIO_KEYS:
        if base_vectors is not None and key in base_vectors:
            out[key] = base_vectors[key].copy()
        elif key == "hpa_enabled":
            out[key] = np.full((n_lanes,), bool(base[key]), bool)
        elif key in ("ca_max_node_count", "fault_seed"):
            out[key] = np.full((n_lanes,), int(base[key]), np.int64)
        else:
            out[key] = np.full((n_lanes,), float(base[key]), np.float64)
    if scenarios is not None:
        if len(scenarios) > n_lanes:
            raise ValueError(
                f"{len(scenarios)} scenarios do not fit {n_lanes} lanes"
            )
        for lane, scen in enumerate(scenarios):
            if scen is None:
                continue
            for key, val in scen.overrides().items():
                if key not in out:
                    raise KeyError(f"unknown scenario key {key!r}")
                out[key][lane] = val
    return out


def normalize_scenario(
    scenario: Optional[Dict[str, object]], n_lanes: int
) -> Optional[Dict[str, np.ndarray]]:
    """Validate a scenario-vector mapping: known keys only, every value
    broadcastable to (n_lanes,). Returns owned (C,) numpy arrays."""
    if scenario is None:
        return None
    out: Dict[str, np.ndarray] = {}
    for key, val in scenario.items():
        if key not in SCENARIO_KEYS:
            raise KeyError(
                f"unknown scenario key {key!r}; supported: {SCENARIO_KEYS}"
            )
        arr = np.asarray(val)
        if arr.ndim == 0:
            arr = np.full((n_lanes,), arr[()])
        if arr.shape != (n_lanes,):
            raise ValueError(
                f"scenario[{key!r}] must be scalar or shape ({n_lanes},), "
                f"got {arr.shape}"
            )
        out[key] = arr.copy()
    return out


def scenario_leaves(
    config: SimulationConfig,
    n_lanes: int,
    scenario: Optional[Dict[str, np.ndarray]] = None,
) -> Dict[str, np.ndarray]:
    """Compose the per-lane (C,)-shaped autoscaler-parameter leaves from
    the base config plus optional per-lane overrides — THE owner of the
    delay-chain composition rules (mirroring the scalar event chains;
    reference cluster_autoscaler.rs:256-262, SURVEY.md §3.2/3.4). Used by
    engine.build_autoscale_statics at build AND by
    engine.update_scenario between fleet queries, so the two sites can
    never drift. All values are float64 seconds (converted to device
    TPairs by the caller) except the bool/int control vectors."""
    scenario = dict(scenario or {})
    base = _base_values(config)
    C = n_lanes

    def vec(key, dtype=np.float64):
        val = scenario.get(key)
        out = np.full((C,), base[key], dtype)
        if val is not None:
            out[:] = np.asarray(val)
        return out

    hpa_scan = vec("hpa_scan_interval")
    hpa_tol = vec("hpa_tolerance")
    hpa_en = vec("hpa_enabled", bool) & bool(
        config.horizontal_pod_autoscaler.enabled
    )
    ca_scan = vec("ca_scan_interval")
    ca_thresh = vec("ca_threshold")
    ca_max = vec("ca_max_node_count", np.int64)
    if not config.cluster_autoscaler.enabled:
        ca_max[:] = 0
    as_to_ca = vec("as_to_ca_network_delay")
    fault_seed = vec("fault_seed", np.int64)

    as_to_ps = float(config.as_to_ps_network_delay)
    ps_to_sched = float(config.ps_to_sched_network_delay)
    sched_to_as = float(config.sched_to_as_network_delay)
    as_to_node = float(config.as_to_node_network_delay)
    d_pod_enqueue = as_to_ps + ps_to_sched

    # The CA's true cadence drifts: the scalar proxy re-arms scan_interval
    # AFTER the info round-trip returns (delay 0 on overrun), so the
    # period is round_trip + scan_interval (or just round_trip on
    # overrun) — composed per lane.
    ca_roundtrip = 2.0 * (as_to_ca + as_to_ps)
    ca_period_s = ca_roundtrip + np.where(
        ca_roundtrip <= ca_scan, ca_scan, 0.0
    )

    return {
        "hpa_interval_s": hpa_scan,
        "hpa_tolerance": hpa_tol,
        "hpa_enabled": hpa_en,
        "ca_threshold": ca_thresh,
        "ca_max_nodes": ca_max,
        "fault_seed": fault_seed,
        "d_hpa_up_s": as_to_ca + d_pod_enqueue,
        "d_hpa_down_s": as_to_ca + as_to_ps,
        "d_ca_up_s": 3.0 * as_to_ca + 5.0 * as_to_ps + ps_to_sched,
        "d_ca_down_s": 3.0 * as_to_ca + 4.0 * as_to_ps + as_to_node,
        "ca_period_s": ca_period_s,
        "ca_snap_s": as_to_ca + as_to_ps,
        "ca_finish_vis_s": np.full((C,), as_to_node + as_to_ps),
        "ca_commit_vis_s": np.full((C,), sched_to_as + as_to_ps),
    }


# --- fleet ------------------------------------------------------------------


@dataclass
class FleetResult:
    """One drained what-if query. Shares the `.ok` / `.kind`
    discrimination protocol with the `QueryError` taxonomy
    (batched/faults.py): a poll loop filters terminal outcomes with
    `outcome.ok` instead of isinstance ladders."""

    ok = True
    kind = "result"

    query: int
    wave: int
    lane: int
    horizon: float
    scenario: Scenario
    counters: Dict[str, float]
    hpa_replicas: Optional[Dict[str, int]]
    ca_nodes: Optional[List[int]]
    # Per-lane divergence counters (the loud-readout bounds of
    # engine.check_autoscaler_bounds, read per lane here): nonzero means
    # the lane's trajectory diverged from the scalar semantics.
    hpa_reserve_clamped: int = 0
    ca_reserve_starved: int = 0


# The per-lane counter rows a query reads back (MetricArrays fields).
_RESULT_COUNTERS = (
    "pods_succeeded",
    "pods_removed",
    "terminated_pods",
    "scheduling_decisions",
    "scaled_up_pods",
    "scaled_down_pods",
    "scaled_up_nodes",
    "scaled_down_nodes",
    "node_crashes",
    "node_recoveries",
    "pod_interruptions",
    "pod_restarts",
    "pods_failed",
)
# The columns of the packed readback (_pack_lane_rows) before the
# autoscaler's: the counters, then the two divergence counters.
_ROW_COUNTERS = _RESULT_COUNTERS + ("hpa_reserve_clamped", "ca_reserve_starved")


def jit_cache_sizes() -> Dict[str, int]:
    """Compiled-variant counts of every jit entry the dispatch loop can
    touch — the zero-recompile observable: capture after warm-up, compare
    after the query stream (tests/test_fleet.py::test_wave_reset_and_zero_recompiles
    asserts equality; a scenario update that silently became a jit-static
    shows up here loudly)."""
    from kubernetriks_tpu.batched import autoscale, engine, state, step

    entries = {
        "window_step": step.window_step,
        "run_windows": step.run_windows,
        "run_windows_donated": step.run_windows_donated,
        "run_windows_skip": step.run_windows_skip,
        "run_windows_skip_donated": step.run_windows_skip_donated,
        "run_superspan": step.run_superspan,
        "run_superspan_donated": step.run_superspan_donated,
        "fused_chunk_slide": engine._fused_chunk_slide,
        "fused_chunk_slide_donated": engine._fused_chunk_slide_donated,
        "hpa_pass_donated": autoscale.hpa_pass_donated,
        "ca_pass_donated": autoscale.ca_pass_donated,
        "tree_copy": state.tree_copy,
        "reset_lanes": _reset_lanes,
        "admit_lanes": _admit_lanes,
        "pack_lane_rows": _pack_lane_rows,
    }
    out = {}
    for name, fn in entries.items():
        try:
            out[name] = int(fn._cache_size())
        except AttributeError:  # pragma: no cover - jax version drift
            out[name] = -1
    return out


def _make_lane_programs():
    import jax
    import jax.numpy as jnp

    def select(mask, new, cur):
        return jnp.where(mask.reshape((-1,) + (1,) * (cur.ndim - 1)), new, cur)

    @partial(jax.jit, donate_argnums=(0,))
    def reset(state, pristine, mask):
        """Per-lane state re-init: lanes with mask True take the pristine
        build state's rows, everything else keeps the current buffers —
        donation reuses the live state's device buffers in place (no fresh
        full-state allocation per wave). Every state leaf leads with the
        cluster axis, so one broadcasted select covers the whole pytree."""
        return jax.tree.map(
            lambda cur, ini: select(mask, ini, cur), state, pristine
        )

    @partial(jax.jit, donate_argnums=(0,))
    def admit(state, pristine, live, buf):
        """A pump round's admission as one program over one buffer
        (`pack_admission`'s, put once by engine.admit_lanes): masked
        lanes take the pristine state's rows (reset's select) and the
        buffer's rows of every leaf of `live` (the scenario-bearing
        statics leaves, the fault seeds, the lane clocks); every other
        lane keeps what it has. One compiled shape whatever the lanes
        admitted: the mask is data."""
        mask = buf[:, 0] != 0
        state = jax.tree.map(
            lambda cur, ini: select(mask, ini, cur), state, pristine
        )
        leaves, treedef = jax.tree.flatten(live)
        out, col = [], 1
        for cur in leaves:
            width = cur.size // cur.shape[0]
            if cur.dtype.itemsize == 8:
                new = buf[:, col : col + width].reshape(cur.shape)
                out.append(select(mask, new, cur))
                col += width
                continue
            # Selected as WORDS: a float select flushes a subnormal on a
            # TPU, and every bit of a 32-bit leaf is the host's.
            high, low = (
                buf[:, at : at + width].astype(jnp.uint32).reshape(cur.shape)
                for at in (col, col + width)
            )
            words = jax.lax.bitcast_convert_type(cur, jnp.uint32)
            words = select(mask, (high << 16) | low, words)
            out.append(jax.lax.bitcast_convert_type(words, cur.dtype))
            col += 2 * width
        return state, jax.tree.unflatten(treedef, out)

    @jax.jit
    def pack_rows(counters, auto):
        """Everything a drained query returns, for ALL lanes, as one
        (C, R) integer array: the `_ROW_COUNTERS` columns, then (with
        autoscalers) each pod group's created replicas, hpa_tail -
        hpa_head, and each node group's ca_count. Every leaf is int32
        today; a wider one would widen the array, never narrow a leaf."""
        cols = [c[:, None] for c in counters]
        if auto is not None:
            hpa_head, hpa_tail, ca_count = auto
            cols += [hpa_tail - hpa_head, ca_count]
        dtype = jnp.result_type(*cols)
        return jnp.concatenate([c.astype(dtype) for c in cols], axis=1)

    return reset, admit, pack_rows


_reset_lanes, _admit_lanes, _pack_lane_rows = _make_lane_programs()


def pack_admission(mask: np.ndarray, rows) -> np.ndarray:
    """The one host buffer of a packed admission, `_admit_lanes`' `buf`:
    `(C, K)` float64, column 0 the lane mask, then the host rows of every
    leaf of the pytree `rows` in its own leaf order. A float64 leaf
    travels as it is (the device gets the value a put of the leaf alone
    would give it); a 32-bit leaf (int32, uint32, float32) as the high
    and the low 16 bits of its words, two columns an element, which the
    program shifts back together and bitcasts: whole numbers under 2**16
    are exact in whatever a backend makes of float64 (a TPU keeps a pair
    of float32, which holds neither every uint32 nor every int32)."""
    import jax

    cols = [mask.astype(np.float64)[:, None]]
    for leaf in jax.tree.leaves(rows):
        leaf = np.ascontiguousarray(leaf).reshape(len(mask), -1)
        if leaf.dtype.itemsize == 8:
            cols.append(leaf.astype(np.float64))
        else:
            words = leaf.view(np.uint32)
            cols += [words >> 16, words & 0xFFFF]
    return np.concatenate(cols, axis=1, dtype=np.float64)


class ScenarioFleet:
    """Resident what-if service over one compiled batched engine.

    Build once (compile + warm-up paid once), then `submit()` scenarios
    and `run()`: queries pack into C-lane waves; each wave resets the
    lanes in place, installs the wave's per-lane config vectors (traced
    data — zero recompiles), steps the resident engine to the wave's
    horizons and drains per-lane results at those existing host-block
    boundaries.
    """

    @build_span
    def __init__(
        self,
        config: SimulationConfig,
        cluster_events,
        workload_events,
        n_lanes: int,
        horizon: float,
        strict_divergence: bool = True,
        build_scenarios: Optional[Sequence[Optional[Scenario]]] = None,
        lane_async: bool = False,
        span_windows: Optional[int] = None,
        max_queue: Optional[int] = None,
        queue_policy: Optional[str] = None,
        quarantine_faults: int = 3,
        quarantine_window: int = 64,
        quarantine_backoff: int = 8,
        host_chaos: Optional[HostChaos] = None,
        **engine_kwargs,
    ) -> None:
        from kubernetriks_tpu.batched.engine import build_batched_from_traces
        from kubernetriks_tpu.flags import flag_int, flag_str

        if n_lanes < 1:
            raise ValueError("a fleet needs at least one lane")
        self.config = config
        self.n_lanes = int(n_lanes)
        self.default_horizon = float(horizon)
        self.strict_divergence = bool(strict_divergence)
        self.lane_async = bool(lane_async)
        if self.lane_async:
            engine_kwargs.setdefault("lane_async", True)
        if span_windows is None:
            span_windows = flag_int("KTPU_LANE_SPAN")
        self.span_windows = max(1, int(span_windows)) if span_windows else 8
        # Build WITH the scenario vectors so every scenario-bearing leaf
        # is (C,)-shaped traced data from the start (later updates are
        # pure data; in particular consts.fault_seed's pytree presence is
        # fixed at build — see engine.update_scenario). build_scenarios:
        # per-lane BUILD config (the wave default a query's overrides
        # apply on top of) — the one channel that reaches the host-
        # compiled node-fault crash chains, which live in the trace slab
        # and are fixed per lane at build (pod-fault seeds stay pure
        # traced data and re-seed per wave).
        self._vectors = scenario_vectors(config, self.n_lanes, build_scenarios)
        self.engine = build_batched_from_traces(
            config,
            cluster_events,
            workload_events,
            n_clusters=self.n_lanes,
            scenario=dict(self._vectors),
            **engine_kwargs,
        )
        self._queue: deque = deque()
        self._next_query = 0
        # Terminal outcome per qid: FleetResult (ok=True) or a typed
        # QueryError (ok=False) — both stream through poll() once.
        self.results: Dict[int, Union[FleetResult, QueryError]] = {}
        self.waves_run = 0
        # Wave 0 runs on the build-fresh engine; later waves reset first.
        self._dirty = False
        # Warm the lane-reset program now (an empty lane list is the same
        # compiled program — the mask is traced data), so the first REAL
        # reset at the wave-2 boundary is a cache hit and the sweep's
        # zero-recompiles-after-warm-up capture covers every program the
        # steady query stream can touch.
        self.engine.fleet_reset(lanes=[])
        # The same for the packed readback a drain makes and, on a
        # lane-async fleet, for the packed admission and the ring-preserving
        # crash-reset (with the device ring on a program of its own): an
        # empty lane list admits and resets nothing.
        self._pack_rows()
        if self.lane_async:
            self.engine.lane_reset([])
            self.engine.admit_lanes([], self._vectors, [])
        # KTPU_EXPLAIN_RECOMPILES=1: guard every post-warm-up wave with
        # the recompile sentinel — the runtime cross-check of the
        # scenariotrace lint pass's static compile-once guarantee. Wave 1
        # is warm-up (the window/superspan programs legitimately compile
        # there); any compilation inside a later wave raises, naming the
        # jit entry.
        from kubernetriks_tpu.recompile import maybe_sentinel

        self._sentinel = maybe_sentinel()
        # Lane-async bookkeeping (pump/poll, DESIGN §13). _live_vectors is
        # the CURRENT per-lane config row set: assignments rewrite only
        # the re-seeded lanes' rows, and engine.admit_lanes writes only
        # those lanes on the device, so in-flight trajectories are untouched.
        self._live_vectors = {k: v.copy() for k, v in self._vectors.items()}
        self._active: Dict[int, tuple] = {}  # lane -> (qid, scen, horizon)
        self._trace_rows: Dict[int, tuple] = {}  # qid -> (lo, hi)
        self._completed: deque = deque()
        # Query-observatory state (PR 17). _lifecycle holds one mutable
        # record per LIVE query (queued / in-flight / completed-unpolled):
        # perf_counter_ns stamps for submitted -> admitted ->
        # first_dispatch -> drained (-> polled at retirement), the
        # assigned lane, and the Chrome flow id linking submit to drain.
        # poll() retires records into the bounded _polled_lifecycles
        # trail, so the map's size tracks live queries, never the stream.
        self._lifecycle: Dict[int, Dict[str, int]] = {}
        self._polled_lifecycles: deque = deque(maxlen=_POLLED_LIFECYCLES_KEPT)
        # Latency statistics: bounded log-bucket histograms (O(buckets),
        # exact count/sum — the replacement for the PR 16-era unbounded
        # query_latency_s dict) + the bounded exact-sample window the
        # bench's histogram-vs-exact assert reads.
        self.latency_hist = LatencyHistogram()
        self.queue_wait_hist = LatencyHistogram()
        self.service_hist = LatencyHistogram()
        self.latency_exact_window: deque = deque(maxlen=_EXACT_LATENCY_WINDOW)
        self.pump_rounds = 0
        # True once a pump round has exercised the full program set
        # (assign + step + drain) — the sentinel guards rounds after that.
        self._async_warm_done = False
        # Span values whose window-program variants were AOT-warmed
        # (engine.precompile_lane_spans) — first drain alone cannot
        # prove the drain tail's freezing program compiled, because a
        # burst-submitted stream runs boundary-aligned (no-freeze)
        # chunks exclusively until the queue dries.
        self._warm_spans: set = set()
        self.lane_busy_windows = np.zeros((self.n_lanes,), np.int64)
        self.lane_total_windows = np.zeros((self.n_lanes,), np.int64)
        self.lane_windows_dispatched = 0  # every lane-window the device stepped
        # Fault-domain state (PR 19, DESIGN §15). Bounded admission:
        # queue depth + backpressure policy, flag defaults
        # (KTPU_FLEET_QUEUE / KTPU_FLEET_QUEUE_POLICY), unset = the
        # pre-fault-domain unbounded queue.
        if max_queue is None:
            max_queue = flag_int("KTPU_FLEET_QUEUE")
        self.max_queue = int(max_queue) if max_queue is not None else None
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                "max_queue must be >= 1 (or None for unbounded), "
                f"got {self.max_queue}"
            )
        policy = queue_policy or flag_str("KTPU_FLEET_QUEUE_POLICY") or "reject"
        if policy not in ("reject", "block"):
            raise ValueError(
                f"queue_policy must be 'reject' or 'block', got {policy!r}"
            )
        self.queue_policy = policy
        # Host-chaos injector: explicit arg wins, else the registered
        # flag. None = injection OFF — every chaos branch below is gated
        # on it, so an unset flag takes the exact pre-chaos code path.
        if host_chaos is None:
            host_chaos = HostChaos.from_flag(flag_str("KTPU_HOST_CHAOS"))
        self._chaos = host_chaos
        # Quarantine policy: a lane faulting `quarantine_faults` times
        # within `quarantine_window` pump rounds leaves the admission
        # rotation for `quarantine_backoff` rounds, then re-admits ONE
        # probe query; a faulting probe doubles the backoff, a completing
        # probe restores the lane and clears its fault history.
        self.quarantine_faults = max(1, int(quarantine_faults))
        self.quarantine_window = max(1, int(quarantine_window))
        self.quarantine_backoff = max(1, int(quarantine_backoff))
        self._lane_fault_rounds: Dict[int, deque] = {}
        self._quarantine: Dict[int, Dict] = {}
        self.quarantine_events = 0
        self.readmissions = 0
        self.failed_queries: Dict[str, int] = {}
        # True once any queued entry ever carried a deadline — the pump's
        # deadline sweep is skipped entirely (zero added host work) for
        # deadline-free streams.
        self._deadlines_ever = False
        self._closing = False
        self._closed = False

    # -- query intake --------------------------------------------------------

    # Scenario fields that must be finite and non-negative (seconds /
    # ratios); the remaining keys are bool/int control values.
    _NONNEG_KEYS = (
        "hpa_scan_interval",
        "hpa_tolerance",
        "ca_scan_interval",
        "ca_threshold",
        "as_to_ca_network_delay",
    )

    def _validate_scenario(self, scenario) -> Scenario:
        """Loud pre-admission validation: unknown keys and wrong axis
        shapes raise HERE (naming the field and the legal set) instead of
        becoming in-flight poison at a lane-reseed boundary."""
        if scenario is None:
            return Scenario()
        if isinstance(scenario, Scenario):
            overrides = scenario.overrides()
        elif isinstance(scenario, Mapping):
            overrides = dict(scenario)
            unknown = [k for k in overrides if k not in SCENARIO_KEYS]
            if unknown:
                raise ValueError(
                    f"submit(): unknown scenario key(s) {sorted(unknown)} "
                    f"— legal keys: {list(SCENARIO_KEYS)}"
                )
        else:
            raise ValueError(
                "submit(): scenario must be a Scenario or a mapping of "
                f"scenario keys, got {type(scenario).__name__}"
            )
        for key, val in overrides.items():
            arr = np.asarray(val)
            if arr.ndim != 0:
                raise ValueError(
                    f"submit(): scenario[{key!r}] must be a per-query "
                    f"SCALAR override (axis shape ()), got shape "
                    f"{arr.shape} — per-lane (C,) vectors belong to "
                    "build_scenarios / engine.update_scenario"
                )
            if key in self._NONNEG_KEYS:
                v = float(arr)
                if not np.isfinite(v) or v < 0:
                    raise ValueError(
                        f"submit(): scenario[{key!r}] must be a finite "
                        f"value >= 0, got {val!r}"
                    )
        if isinstance(scenario, Scenario):
            return scenario
        return Scenario(**overrides)

    @staticmethod
    def _validate_positive(name: str, value, unit: str) -> float:
        try:
            out = float(value)
        except (TypeError, ValueError):
            out = float("nan")
        if not np.isfinite(out) or out <= 0:
            raise ValueError(
                f"submit(): {name} must be a finite number > 0 "
                f"({unit}), got {value!r}"
            )
        return out

    def _retry_after_hint(self) -> Optional[float]:
        """Backpressure hint for RejectedError: the observed median
        service wall scaled by the queue depth ahead, None before any
        query completed."""
        if self.service_hist.count == 0:
            return None
        p50_s = self.service_hist.percentile(50.0)
        waves_ahead = (len(self._queue) + 1) / max(1, self.n_lanes)
        return round(p50_s * waves_ahead, 6)

    def submit(
        self,
        scenario: Optional[Union[Scenario, Mapping]] = None,
        horizon: Optional[float] = None,
        trace_rows: Optional[tuple] = None,
        deadline_s: Optional[float] = None,
    ) -> int:
        """Queue one what-if query; returns its id (the key into
        `results` after `run()` / the pump's drains). trace_rows:
        optional (lo, hi) workload row-range for the query's lane
        (lane-async builds only — engine.set_lane_trace installs it at
        the lane's reseed boundary). deadline_s: optional relative
        deadline (host seconds from now); a query still QUEUED past its
        deadline fails with DeadlineExceededError without ever occupying
        a lane (checked at pump boundaries — an admitted query always
        runs to its horizon).

        Validation happens BEFORE admission (loud ValueError naming the
        field); a full bounded queue applies the configured backpressure
        (reject: the query's qid streams a RejectedError through poll();
        block: pump inline until a slot frees). After close(), raises
        ShutdownError."""
        if self._closing:
            raise ShutdownError(
                -1,
                "submit() after close(): the fleet is draining/closed "
                "and admits no new queries",
            )
        scen = self._validate_scenario(scenario)
        h = (
            self._validate_positive("horizon", horizon, "simulated seconds")
            if horizon is not None
            else self.default_horizon
        )
        if deadline_s is not None:
            deadline_s = self._validate_positive(
                "deadline_s", deadline_s, "host seconds from submit"
            )
        if trace_rows is not None:
            if not self.lane_async:
                raise ValueError(
                    "trace_rows needs lane_async=True (the per-lane "
                    "trace multiplexer)"
                )
            lo, hi = trace_rows
            lo = int(lo)
            hi = None if hi is None else int(hi)
            if lo < 0 or (hi is not None and hi <= lo):
                raise ValueError(
                    "submit(): trace_rows must satisfy 0 <= lo < hi "
                    f"(hi=None = end of trace), got {trace_rows!r}"
                )
            trace_rows = (lo, hi)
        # Bounded admission: the queue depth check runs after validation
        # (a malformed query is a caller bug, not backpressure).
        if (
            self.max_queue is not None
            and len(self._queue) >= self.max_queue
            and self.queue_policy == "block"
        ):
            # Inline pump/run until a slot frees — the fleet is
            # single-threaded, so blocking IS making progress.
            while len(self._queue) >= self.max_queue:
                if self.lane_async:
                    self.pump()
                else:
                    self.run()
        qid = self._next_query
        self._next_query += 1
        t_submit = time.perf_counter_ns()
        # Lifecycle birth: host stamp + the submit->drain flow arrow's id
        # (all pure host, zero syncs).
        self._lifecycle[qid] = {
            "submitted_ns": t_submit,
            "flow_id": self.engine.tracer.flow_start(PH_QUERY_QUEUE),
            "lane": -1,
        }
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            # policy == "reject": the qid still streams exactly one
            # terminal outcome (a RejectedError via poll), preserving the
            # stream-once contract for refused work too.
            self._fail_query(
                qid,
                RejectedError(
                    qid,
                    f"query {qid} rejected at admission: queue full "
                    f"({len(self._queue)}/{self.max_queue} queued; "
                    "policy 'reject')",
                    retry_after_s=self._retry_after_hint(),
                    scenario=scen,
                    horizon=h,
                ),
            )
            return qid
        if trace_rows is not None:
            self._trace_rows[qid] = trace_rows
        deadline_ns = None
        if deadline_s is not None:
            deadline_ns = t_submit + int(deadline_s * 1e9)
            self._deadlines_ever = True
        self._queue.append((qid, scen, h, deadline_ns))
        return qid

    @property
    def pending(self) -> int:
        return len(self._queue)

    # -- fault delivery ------------------------------------------------------

    def _fail_query(self, qid: int, err: QueryError) -> None:
        """Deliver one terminal TYPED failure through the completion
        stream: same `results` + `_completed` path as a drained result,
        so poll() streams it exactly once and every counter/lifecycle
        readout stays coherent."""
        rec = self._lifecycle.get(qid)
        t_fail = time.perf_counter_ns()
        if rec is not None:
            rec["failed_ns"] = t_fail
            if err.lane >= 0:
                rec["lane"] = err.lane
            tracer = self.engine.tracer
            tracer.end(
                PH_QUERY_FAIL,
                rec["submitted_ns"],
                dur=t_fail - rec["submitted_ns"],
                ident=qid,
            )
            if rec["flow_id"]:
                tracer.flow_end(PH_QUERY_QUEUE, rec["flow_id"])
        self._trace_rows.pop(qid, None)
        self.results[qid] = err
        self._completed.append(qid)
        self.failed_queries[err.kind] = (
            self.failed_queries.get(err.kind, 0) + 1
        )

    def _expire_deadlines(self) -> None:
        """Fail queued-past-deadline queries WITHOUT occupying a lane —
        runs at pump/wave boundaries the host already crosses (pure
        queue arithmetic, zero new syncs), and only when a deadline was
        ever submitted."""
        if not self._deadlines_ever or not self._queue:
            return
        now = time.perf_counter_ns()
        keep: deque = deque()
        while self._queue:
            entry = self._queue.popleft()
            qid, scen, horizon, deadline_ns = entry
            if deadline_ns is not None and now >= deadline_ns:
                late_s = (now - deadline_ns) / 1e9
                self._fail_query(
                    qid,
                    DeadlineExceededError(
                        qid,
                        f"query {qid} deadline exceeded while queued "
                        f"({late_s:.3f}s late) — failed without "
                        "occupying a lane",
                        late_s=round(late_s, 6),
                        scenario=scen,
                        horizon=horizon,
                    ),
                )
            else:
                keep.append(entry)
        self._queue = keep

    # -- wave machinery ------------------------------------------------------

    def _lane_rows(self) -> np.ndarray:
        """Every lane's packed result row, `(C, R)` on the host: ONE
        small program over the state (_pack_lane_rows: the counters,
        created replicas and CA node counts in one integer array) and ONE
        blocking device-to-host copy a round or wave horizon, however
        many lanes finished; the caller takes the finished lanes' rows.
        The copy lands at a horizon boundary, where the host has nothing
        else to do until the device finishes the step: no new
        steady-state sync."""
        # result_wait: where the host waits for the device to finish what
        # the round (or wave) enqueued.
        tracer = self.engine.tracer
        t0 = tracer.begin(PH_RESULT_WAIT)
        packed = np.asarray(self._pack_rows())
        tracer.count("pump_transfers_down")
        tracer.end(PH_RESULT_WAIT, t0, ident=self.pump_rounds)
        return packed

    def _pack_rows(self):
        """Dispatch the packed readback's program; the rows, on the device."""
        st = self.engine.state
        auto = st.auto
        args = (
            tuple(getattr(st.metrics, name) for name in _ROW_COUNTERS),
            None if auto is None else (auto.hpa_head, auto.hpa_tail, auto.ca_count),
        )
        self.engine.tracer.program("pack_lane_rows", (), _pack_lane_rows, args, {})
        return _pack_lane_rows(*args)

    def _drain_lane(
        self,
        qid: int,
        lane: int,
        horizon: float,
        scen: Scenario,
        rows: np.ndarray,
        wave: Optional[int] = None,
    ) -> None:
        row = rows[lane].tolist()
        n = len(_RESULT_COUNTERS)
        clamped, starved = row[n], row[n + 1]
        if self.strict_divergence and (clamped > 0 or starved > 0):
            raise RuntimeError(
                f"fleet query {qid} (lane {lane}): autoscaler reserve "
                f"bound crossed (hpa_reserve_clamped={clamped}, "
                f"ca_reserve_starved={starved}) — the lane's trajectory "
                "diverged from the scalar semantics; widen the reserves "
                "or pass strict_divergence=False to read it anyway"
            )
        eng = self.engine
        hpa = None
        ca = None
        if eng.state.auto is not None:
            # The row's tail: a column a pod group (the lane's own names
            # cover the first of them), then a column a node group.
            groups = eng.state.auto.hpa_head.shape[1]
            hpa = dict(zip(eng.pod_group_names[lane], row[n + 2 :]))
            ca = row[n + 2 + groups :]
        self.results[qid] = FleetResult(
            query=qid,
            wave=self.waves_run if wave is None else wave,
            lane=lane,
            horizon=horizon,
            scenario=scen,
            counters=dict(zip(_RESULT_COUNTERS, row)),
            hpa_replicas=hpa,
            ca_nodes=ca,
            hpa_reserve_clamped=clamped,
            ca_reserve_starved=starved,
        )

    def _run_wave(self, wave) -> None:
        if self._sentinel is not None and self.waves_run >= 1:
            with self._sentinel.expect_none(
                f"fleet wave {self.waves_run + 1} (post-warm-up)"
            ):
                self._run_wave_inner(wave)
        else:
            self._run_wave_inner(wave)

    def _run_wave_inner(self, wave) -> None:
        eng = self.engine
        # Install the wave's per-lane config rows: base values everywhere,
        # each assigned lane's overrides on top. Idle lanes run the base
        # scenario (their work is discarded).
        vectors = scenario_vectors(
            self.config,
            self.n_lanes,
            [scen for _, scen, _, _ in wave],
            base_vectors=self._vectors,
        )
        eng.update_scenario(vectors)
        if self._dirty:
            eng.fleet_reset()
        self._dirty = True
        # Wave admission: every lane of the wave starts together, so the
        # whole wave shares one admission stamp (queue-wait on this path
        # is wave-packing delay, not lane contention).
        t_admit = time.perf_counter_ns()
        for lane, (qid, _, _, _) in enumerate(wave):
            rec = self._lifecycle.get(qid)
            if rec is not None:
                rec["admitted_ns"] = t_admit
                rec["lane"] = lane
        # Step to each distinct horizon once; lanes finishing there are
        # read back while the host is already blocked at the step exit.
        by_horizon: Dict[float, list] = {}
        for lane, (qid, scen, horizon, _) in enumerate(wave):
            by_horizon.setdefault(horizon, []).append((qid, lane, scen))
        tracer = eng.tracer
        for horizon in sorted(by_horizon):
            eng.step_until_time(horizon)
            rows = self._lane_rows()
            t_drain = time.perf_counter_ns()
            for qid, lane, scen in by_horizon[horizon]:
                self._drain_lane(qid, lane, horizon, scen, rows)
                # Retire the lifecycle record here (wave fleets read
                # results from `results`, not poll()) so the map stays
                # bounded by live queries on this path too.
                rec = self._lifecycle.pop(qid, None)
                if rec is not None:
                    rec["drained_ns"] = t_drain
                    if rec["flow_id"]:
                        tracer.flow_end(PH_QUERY_QUEUE, rec["flow_id"])
                    self._polled_lifecycles.append((qid, rec))
        self.waves_run += 1

    def run(self) -> Dict[int, FleetResult]:
        """Drain the queue: pack pending queries into C-lane waves and run
        each on the resident engine. Returns {query id: FleetResult} for
        everything drained (also accumulated in `self.results`)."""
        self._expire_deadlines()
        while self._queue:
            wave = [
                self._queue.popleft()
                for _ in range(min(self.n_lanes, len(self._queue)))
            ]
            self._run_wave(wave)
            self._expire_deadlines()
        return self.results

    # -- lane-async pump (continuous submit/poll, DESIGN §13) ----------------

    def pump(self, span_windows: Optional[int] = None) -> int:
        """One lane-async scheduling round: seed idle lanes from the
        queue, step up to `span_windows` global windows in power-of-two
        chunks clamped to the nearest lane-plan boundary (each chunk
        shape compiles once; boundary-aligned chunks run the no-freeze
        window program and never overshoot a horizon), then drain the
        lanes whose per-lane clock says their plan completed — pure host
        arithmetic over the clock mirrors, zero new device syncs. Returns
        the number of queries completed this round."""
        if not self.lane_async:
            raise ValueError(
                "pump() needs lane_async=True (wave-aligned fleets run())"
            )
        span = int(span_windows) if span_windows else self.span_windows
        if span not in self._warm_spans:
            self.engine.precompile_lane_spans(span)
            self._warm_spans.add(span)
        tracer = self.engine.tracer
        t_pump = tracer.begin(PH_PUMP)
        try:
            if self._sentinel is not None and self._async_warm_done:
                with self._sentinel.expect_none(
                    f"fleet pump round {self.pump_rounds + 1} (post-warm-up)"
                ):
                    drained = self._pump_inner(span)
            else:
                drained = self._pump_inner(span)
        finally:
            tracer.end(PH_PUMP, t_pump, ident=self.pump_rounds)
        self.pump_rounds += 1
        if drained and self.pump_rounds >= 1:
            # Assign + step + drain have all run at least once: every
            # program class the steady query stream touches is warm.
            self._async_warm_done = True
        return drained

    def _pump_inner(self, span: int) -> int:
        eng = self.engine
        tracer = eng.tracer
        # 0. Host-boundary deadline sweep: queued-past-deadline queries
        # fail here, before they can occupy a lane. No-op (one attribute
        # read) unless a deadline was ever submitted.
        self._expire_deadlines()
        # 1. Seed idle lanes: rewrite ONLY their _live_vectors rows (base
        # row + this query's overrides), reset their state in place, and
        # start their clocks at the engine's current global window.
        # Quarantined lanes sit out the rotation until their backoff
        # expires, then take ONE probe query; a closing fleet admits
        # nothing (graceful drain).
        assigned = []
        for lane in range(self.n_lanes):
            if lane in self._active or not self._queue or self._closing:
                continue
            q = self._quarantine.get(lane)
            if q is not None:
                if q["probing"] or self.pump_rounds < q["until_round"]:
                    continue
                q["probing"] = True
                self._push_lane_states()
            # Admission drops the deadline: an admitted query always
            # runs to its horizon (deadlines bound QUEUE time only —
            # enforcing them mid-flight would need new device syncs).
            assigned.append((lane, *self._queue.popleft()[:3]))
        if assigned:
            t_span = tracer.begin(PH_PUMP_ADMIT)
            for lane, qid, scen, horizon in assigned:
                for key in SCENARIO_KEYS:
                    self._live_vectors[key][lane] = self._vectors[key][lane]
                for key, val in scen.overrides().items():
                    self._live_vectors[key][lane] = val
            # One put and one program for the whole admission (scenario
            # rows, pristine select, lane clocks), whatever len(assigned).
            eng.admit_lanes(
                [lane for lane, _, _, _ in assigned],
                self._live_vectors,
                [eng.horizon_windows(h) for _, _, _, h in assigned],
            )
            tracer.count("pump_transfers_up")
            for lane, qid, _, _ in assigned:
                # Always (re)install the lane's workload range at the
                # reseed boundary: a previous query's mask must not leak
                # into this one (full range when the query carries none;
                # the mux skips the device write when nothing changed,
                # and a changed range is one more put).
                lo, hi = self._trace_rows.pop(qid, (0, None))
                if eng.set_lane_trace(lane, lo, hi):
                    tracer.count("pump_transfers_up")
            tracer.end(PH_PUMP_ADMIT, t_span, ident=self.pump_rounds)
            # Lifecycle: admitted-to-lane — close the queue-wait span
            # (submit -> here) on the tracer with an explicit duration.
            t_admit = time.perf_counter_ns()
            for lane, qid, scen, horizon in assigned:
                self._active[lane] = (qid, scen, horizon)
                rec = self._lifecycle.get(qid)
                if rec is not None:
                    rec["admitted_ns"] = t_admit
                    rec["lane"] = lane
                    tracer.end(
                        PH_QUERY_QUEUE,
                        rec["submitted_ns"],
                        dur=t_admit - rec["submitted_ns"],
                        ident=qid,
                    )
        if not self._active:
            return 0
        # Lifecycle: first-dispatch — the step block below is the first
        # device dispatch that can carry a freshly admitted lane's plan.
        t_dispatch = time.perf_counter_ns()
        for lane, (qid, _, _) in self._active.items():
            rec = self._lifecycle.get(qid)
            if rec is not None and "first_dispatch_ns" not in rec:
                rec["first_dispatch_ns"] = t_dispatch
        # 2. Dispatch, boundary-aligned: while every lane is mid-plan,
        # step power-of-two sub-spans clamped to the NEAREST lane
        # completion (ladder {span, span/2, ..., 1} — each shape compiles
        # once). Chunks then never cross a plan boundary, so (a) no lane
        # overshoots its horizon (zero occupancy waste while the queue
        # feeds) and (b) the engine's host-mirror proof selects the
        # no-freeze window program for every chunk — the lane-async
        # executor's per-window cost collapses to the wave-aligned
        # program's. Only the drain tail (queue dry, parked lanes riding
        # along) falls back to the fixed span + freezing program.
        remaining0 = eng.lane_windows_remaining()
        queue_fed = bool(self._queue)
        stepped = 0
        try:
            if len(self._active) == self.n_lanes:
                left = span
                remaining = remaining0.copy()
                while left > 0:
                    m = int(min(left, remaining.min()))
                    sub = 1 << (m.bit_length() - 1)
                    self._dispatch(sub)
                    stepped += sub
                    left -= sub
                    remaining = remaining - sub
                    if (remaining <= 0).any():
                        # A plan completed exactly at the chunk edge:
                        # stop the round so the drain/reseed below runs
                        # promptly.
                        break
            else:
                self._dispatch(span)
                stepped = span
        except Exception as exc:
            # FAULT DOMAIN: a failing dispatch kills the occupying
            # lane's query (or, unattributable, every active query) —
            # never the fleet. The lane is crash-reset below; neighbors
            # keep their trajectories (lanes are independent pure
            # functions of scenario + horizon). Recompile-sentinel and
            # strict-divergence errors are NOT lane faults and must stay
            # loud — they indicate a fleet-level contract break.
            from kubernetriks_tpu.recompile import RecompileError

            if isinstance(exc, RecompileError):
                raise
            self._on_dispatch_fault(exc)
            return 0
        # 3. Occupancy ledger (host ints): a lane is busy for
        # min(stepped, windows left on its plan). Idle lanes count as
        # wasted dispatch only while queries were WAITING (queue fed) —
        # parked lanes riding out the drain tail of a dried-up stream are
        # not the async executor's waste (an open-loop feed never dries).
        busy = 0
        for lane in range(self.n_lanes):
            if lane in self._active:
                lane_busy = min(stepped, int(remaining0[lane]))
                busy += lane_busy
                self.lane_busy_windows[lane] += lane_busy
                self.lane_total_windows[lane] += stepped
            elif queue_fed:
                self.lane_total_windows[lane] += stepped
        # The same round on the process-wide recorder, against what the
        # device stepped: every lane's `stepped` windows, busy or not.
        self.lane_windows_dispatched += stepped * self.n_lanes
        tracer.count("lane_windows_busy", busy)
        tracer.count("lane_windows_dispatched", stepped * self.n_lanes)
        # 4. Drain completed plans.
        done = eng.lane_windows_done()
        finished = [lane for lane in sorted(self._active) if done[lane]]
        if not finished:
            return 0
        t_pump_drain = tracer.begin(PH_PUMP_DRAIN)
        rows = self._lane_rows()
        t_drain = time.perf_counter_ns()
        obs = getattr(eng, "observatory", None)
        for lane in finished:
            qid, scen, horizon = self._active.pop(lane)
            self._drain_lane(
                qid, lane, horizon, scen, rows, wave=self.pump_rounds
            )
            q = self._quarantine.get(lane)
            if q is not None and q["probing"]:
                # Probe query COMPLETED: full re-admission — clear the
                # quarantine and the lane's fault history, close the
                # quarantine span (fire -> re-admission).
                del self._quarantine[lane]
                self._lane_fault_rounds.pop(lane, None)
                self.readmissions += 1
                tracer.end(
                    PH_LANE_QUARANTINE,
                    q["since_ns"],
                    dur=t_drain - q["since_ns"],
                    ident=lane,
                )
                if obs is not None:
                    obs.note_lane_readmitted(lane, probes=q["probes"] + 1)
                self._push_lane_states()
            # Lifecycle: horizon-drained — close the service span
            # (admit -> here), land the flow arrow, and draw the lane
            # swimlane interval; then fold the total / queue-wait /
            # service walls into the bounded histograms. All host
            # timestamps: telemetry armed or not, zero device reads.
            rec = self._lifecycle.get(qid)
            if rec is not None:
                rec["drained_ns"] = t_drain
                t_sub = rec["submitted_ns"]
                t_adm = rec.get("admitted_ns", t_sub)
                tracer.end(
                    PH_QUERY_SERVICE, t_adm, dur=t_drain - t_adm, ident=qid
                )
                if rec["flow_id"]:
                    tracer.flow_end(PH_QUERY_QUEUE, rec["flow_id"])
                tracer.lane_event(lane, qid, t_adm, t_drain - t_adm)
                lat = (t_drain - t_sub) / 1e9
                queue_wait = (t_adm - t_sub) / 1e9
                service = (t_drain - t_adm) / 1e9
            else:  # pragma: no cover - records exist for every submit
                lat = queue_wait = service = 0.0
            self.latency_hist.record(lat)
            self.queue_wait_hist.record(queue_wait)
            self.service_hist.record(service)
            self.latency_exact_window.append(lat)
            self._completed.append(qid)
            if obs is not None:
                obs.note_query(lat, queue_wait, service)
        tracer.end(PH_PUMP_DRAIN, t_pump_drain, ident=self.pump_rounds)
        return len(finished)

    # -- fault isolation + quarantine (lane-async) ---------------------------

    def _dispatch(self, n_windows: int) -> None:
        """One engine dispatch, with the host-chaos injection point: a
        stall sleeps before the dispatch (slow-lane latency, no failure),
        a dispatch fault raises InjectedFault in PLACE of the dispatch
        (the engine state is untouched — exactly like an XLA error
        surfacing before results land). Chaos off = straight call."""
        chaos = self._chaos
        if chaos is not None:
            stall = chaos.stall_s()
            if stall > 0.0:
                time.sleep(stall)
            victim = chaos.dispatch_fault(self._active)
            if victim is not None:
                raise InjectedFault(
                    f"host-chaos: injected dispatch fault on lane "
                    f"{victim} (seed {chaos.seed})",
                    lane=victim,
                )
        tracer = self.engine.tracer
        t0 = tracer.begin(PH_LANE_DISPATCH)
        self.engine.step_windows(n_windows)
        # The chunk's window-index vector: the one put a dispatch makes.
        tracer.count("pump_transfers_up")
        tracer.end(PH_LANE_DISPATCH, t0, ident=self.pump_rounds)

    def _on_dispatch_fault(self, exc: Exception) -> None:
        """Poison isolation: fail the victim lane's query (typed, via
        the completion stream), crash-reset the lane from the pristine
        snapshot, and zero its plan so the clock mirrors stay coherent.
        An exception that names no lane (no `.lane` attribute) is
        unattributable and fails every active query — still never the
        fleet."""
        eng = self.engine
        victim = getattr(exc, "lane", None)
        if victim is not None and victim in self._active:
            lanes = [int(victim)]
        else:
            lanes = sorted(self._active)
        for lane in lanes:
            qid, scen, horizon = self._active.pop(lane)
            self._fail_query(
                qid,
                LaneFaultError(
                    qid,
                    f"query {qid}: lane {lane} dispatch failed "
                    f"({type(exc).__name__}: {exc}) — lane crash-reset, "
                    "neighbors unaffected",
                    lane=lane,
                    cause=exc,
                    scenario=scen,
                    horizon=horizon,
                ),
            )
            self._note_lane_fault(lane)
        # Crash recovery = the donated-select lane reset (pure data ops,
        # no structure swap, no recompile) + a zero-window plan so the
        # lane reads as "done" to the host mirrors until re-seeded.
        eng.lane_reset(lanes)
        eng.set_lane_plan(lanes, eng.next_window_idx, [0] * len(lanes))

    def _note_lane_fault(self, lane: int) -> None:
        """Quarantine bookkeeping for one lane fault. A faulting PROBE
        doubles the backoff; `quarantine_faults` faults within
        `quarantine_window` pump rounds fire a fresh quarantine."""
        obs = getattr(self.engine, "observatory", None)
        q = self._quarantine.get(lane)
        if q is not None:
            q["backoff"] = min(q["backoff"] * 2, 1 << 16)
            q["until_round"] = self.pump_rounds + q["backoff"]
            q["probing"] = False
            q["probes"] += 1
            if obs is not None:
                obs.note_lane_quarantined(
                    lane, backoff_rounds=q["backoff"], probed=True
                )
            self._push_lane_states()
            return
        rounds = self._lane_fault_rounds.setdefault(
            lane, deque(maxlen=self.quarantine_faults)
        )
        rounds.append(self.pump_rounds)
        if (
            len(rounds) >= self.quarantine_faults
            and self.pump_rounds - rounds[0] <= self.quarantine_window
        ):
            self._quarantine[lane] = {
                "backoff": self.quarantine_backoff,
                "until_round": self.pump_rounds + self.quarantine_backoff,
                "probing": False,
                "probes": 0,
                "since_ns": time.perf_counter_ns(),
            }
            rounds.clear()
            self.quarantine_events += 1
            if obs is not None:
                obs.note_lane_quarantined(
                    lane,
                    backoff_rounds=self.quarantine_backoff,
                    probed=False,
                )
            self._push_lane_states()

    def lane_states(self) -> List[str]:
        """Per-lane admission state: 'active' (query in flight), 'idle'
        (admissible), 'quarantined' (out of rotation, backoff pending),
        'probe' (backoff expired — next admission is a probe, or the
        probe is in flight)."""
        out = []
        for lane in range(self.n_lanes):
            q = self._quarantine.get(lane)
            if q is not None:
                if q["probing"] or self.pump_rounds >= q["until_round"]:
                    out.append("probe")
                else:
                    out.append("quarantined")
            elif lane in self._active:
                out.append("active")
            else:
                out.append("idle")
        return out

    def _push_lane_states(self) -> None:
        obs = getattr(self.engine, "observatory", None)
        if obs is not None:
            obs.note_lane_states(self.lane_states())

    def arm_host_chaos(self, chaos: Optional[HostChaos]) -> None:
        """Attach (or detach, with None) the host-fault injector. Armed
        AFTER warm-up, a zero-recompile check runs under injection
        (tests/test_fleet_faults.py::test_fault_path_moves_no_jit_cache_count)."""
        self._chaos = chaos

    def fault_report(self) -> Dict:
        """Availability + fault-domain counters: completed/failed split by
        kind, quarantine activity,
        current lane states, injector event counts."""
        completed_ok = sum(
            1 for r in self.results.values() if getattr(r, "ok", True)
        )
        submitted = self._next_query
        return {
            "submitted": submitted,
            "completed": completed_ok,
            "failed": dict(self.failed_queries),
            "availability": (
                completed_ok / submitted if submitted else 1.0
            ),
            "quarantine_events": self.quarantine_events,
            "readmissions": self.readmissions,
            "lane_states": self.lane_states(),
            "chaos": (
                self._chaos.report() if self._chaos is not None else None
            ),
        }

    def _qid_inventory(self) -> str:
        """The known-qid inventory for loud lookup errors: what this
        fleet has seen, where everything currently is."""
        if self._next_query == 0:
            return "no queries have been submitted to this fleet yet"
        in_flight = sorted(q for q, _, _ in self._active.values())
        return (
            f"{self._next_query} submitted "
            f"(qids 0..{self._next_query - 1}), "
            f"{len(self.results)} completed "
            f"({len(self._completed)} unpolled), "
            f"in-flight qids {in_flight}, {len(self._queue)} queued"
        )

    def _retire_lifecycle(self, qid: int, t_poll_ns: int) -> None:
        rec = self._lifecycle.pop(qid, None)
        if rec is not None:
            rec["polled_ns"] = t_poll_ns
            self._polled_lifecycles.append((qid, rec))

    def poll(
        self, qid: Optional[int] = None
    ) -> List[Union[FleetResult, QueryError]]:
        """Terminal outcomes delivered since the last poll, in
        completion order — the read side of the continuous
        submit/pump/poll engine. Outcomes are FleetResults (ok=True) OR
        typed QueryErrors (ok=False: rejected / deadline_exceeded /
        lane_fault / feeder / shutdown) under ONE stream-once contract:
        every submitted qid streams exactly one terminal outcome, so a
        client never hangs on a dead query.

        ``poll(qid)`` narrows to one query: its outcome (as a
        one-element list) exactly once after it lands, ``[]`` while it
        is still queued/in-flight (or after its outcome was already
        streamed), and a loud ``KeyError`` carrying the known-qid
        inventory when the qid was never submitted here — silence is
        reserved for not-ready, never for a caller bug."""
        t_poll = time.perf_counter_ns()
        if qid is None:
            out = [self.results[q] for q in self._completed]
            for q in self._completed:
                self._retire_lifecycle(q, t_poll)
            self._completed.clear()
            return out
        qid = int(qid)
        if qid < 0 or qid >= self._next_query:
            raise KeyError(
                f"poll({qid}): query {qid} was never submitted to this "
                f"fleet — {self._qid_inventory()}"
            )
        if qid in self._completed:
            self._completed.remove(qid)
            self._retire_lifecycle(qid, t_poll)
            return [self.results[qid]]
        return []

    def query_lifecycle(self, qid: int) -> Dict[str, int]:
        """The host-side lifecycle record for one query: perf_counter_ns
        stamps (submitted_ns, admitted_ns, first_dispatch_ns, drained_ns,
        polled_ns — present once the stage happened), the assigned lane,
        and the trace flow id. Live queries read from the live map;
        recently polled ones from the bounded retirement trail. Raises
        the same loud KeyError as poll() for unknown qids (and for
        records that aged out of the bounded trail)."""
        qid = int(qid)
        if 0 <= qid < self._next_query:
            rec = self._lifecycle.get(qid)
            if rec is None:
                for old_qid, old_rec in reversed(self._polled_lifecycles):
                    if old_qid == qid:
                        rec = old_rec
                        break
            if rec is not None:
                return dict(rec)
        raise KeyError(
            f"query_lifecycle({qid}): no lifecycle record (never "
            f"submitted, or retired past the last "
            f"{_POLLED_LIFECYCLES_KEPT} polled queries) — "
            f"{self._qid_inventory()}"
        )

    def run_async(
        self, span_windows: Optional[int] = None
    ) -> Dict[int, FleetResult]:
        """Pump until the queue and every in-flight lane drain. The async
        counterpart of run(): same {query id: FleetResult} map, same
        per-query numbers (the A/B gate in tests/test_fleet_async.py),
        but a finished lane re-seeds immediately instead of idling to the
        wave boundary."""
        if not self.lane_async:
            raise ValueError(
                "run_async() needs lane_async=True (wave-aligned fleets run())"
            )
        while self._queue or self._active:
            self.pump(span_windows)
        return self.results

    def lane_occupancy(self) -> Dict[str, float]:
        """Busy fraction of dispatched lane-windows (the open-loop bench
        gate): per-lane busy/total from the pump ledger, reported as the
        across-lane mean and min (an idle lane counts as waste only while
        queries waited). 1.0 before any pump round. `share` is THE
        lane-occupancy gauge — busy over every lane-window the device
        stepped, idle lanes included — the number the observatory's
        `lane_occupancy` entry and the benchmark's `lane_busy_share` report
        from the recorder's `lane_windows_busy` / `lane_windows_dispatched`
        counters, which count the same rounds."""
        total = np.maximum(self.lane_total_windows, 1)
        frac = self.lane_busy_windows / total
        if not self.lane_total_windows.any():
            frac = np.ones_like(frac)
        busy = int(self.lane_busy_windows.sum())
        return {
            "mean": float(frac.mean()),
            "min": float(frac.min()),
            "share": lane_windows_share(busy, self.lane_windows_dispatched),
            "lane_windows_busy": busy,
            "lane_windows_total": int(self.lane_total_windows.sum()),
            "lane_windows_dispatched": self.lane_windows_dispatched,
        }

    def reset_query_stats(self) -> None:
        """Forget the latency histograms and the occupancy ledger (bench
        warm-up boundary: the reported percentiles/occupancy then
        reflect the resident steady state, not compile time). ATOMIC
        across both sides: the fleet's histograms and the engine
        observatory's query histograms/SLO window reset together, so the
        two can never report different streams."""
        self.latency_hist.reset()
        self.queue_wait_hist.reset()
        self.service_hist.reset()
        self.latency_exact_window.clear()
        self.lane_busy_windows[:] = 0
        self.lane_total_windows[:] = 0
        self.lane_windows_dispatched = 0
        obs = getattr(self.engine, "observatory", None)
        if obs is not None:
            obs.reset_query_stats()

    def query_latency_percentiles(self) -> Dict[str, float]:
        """Submit-to-drain wall latency percentiles (ms) over every
        completed query — derived from the bounded histogram (exact
        count, percentiles within one bucket width of exact) — exported
        next to queries/s in the open-loop bench record and the
        observatory report."""
        h = self.latency_hist
        if h.count == 0:
            return {"count": 0}
        out: Dict[str, float] = {"count": h.count}
        out.update(h.percentiles_ms())
        return out

    def query_latency_breakdown(self) -> Dict[str, object]:
        """The queue-wait (submit→admit) vs service (admit→drain) split
        plus the raw histogram dump: the open-loop bench embeds this in
        the SWEEP JSON and the Prometheus exporter renders the histogram
        natively (`_bucket`/`_sum`/`_count`)."""
        return {
            "queue_wait_ms": self.queue_wait_hist.percentiles_ms(),
            "service_ms": self.service_hist.percentiles_ms(),
            "histogram": self.latency_hist.to_dict(),
        }

    def sweep(
        self, scenarios: Sequence[Scenario], horizon: Optional[float] = None
    ) -> List[FleetResult]:
        """Convenience: submit + run a whole scenario list, results in
        submission order."""
        qids = [self.submit(s, horizon) for s in scenarios]
        self.run()
        return [self.results[q] for q in qids]

    def close(self, drain: bool = True) -> None:
        """Graceful shutdown: stop admitting (submit() now raises
        ShutdownError), finish in-flight queries (drain=True pumps the
        lane-async fleet until every active lane completes), then fail
        everything still queued with a typed ShutdownError through the
        completion stream — every submitted qid still streams exactly
        one terminal outcome, and poll() keeps working after close (the
        results are host state). drain=False fails in-flight queries
        too, without stepping the engine further."""
        if self._closed:
            return
        self._closing = True
        if self.lane_async and self._active:
            if drain:
                while self._active:
                    self.pump()
            else:
                for lane in sorted(self._active):
                    qid, scen, horizon = self._active.pop(lane)
                    self._fail_query(
                        qid,
                        ShutdownError(
                            qid,
                            f"query {qid} was in flight at "
                            "close(drain=False)",
                            lane=lane,
                            scenario=scen,
                            horizon=horizon,
                        ),
                    )
        while self._queue:
            qid, scen, horizon, _deadline = self._queue.popleft()
            self._fail_query(
                qid,
                ShutdownError(
                    qid,
                    f"query {qid} was still queued at close() — the "
                    "graceful drain finishes in-flight queries and "
                    "fails queued ones",
                    scenario=scen,
                    horizon=horizon,
                ),
            )
        self._closed = True
        if self._sentinel is not None:
            self._sentinel.uninstall()
            self._sentinel = None
        self.engine.close()
