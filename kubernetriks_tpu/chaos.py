"""Chaos engine: counter-based fault sampling shared by BOTH execution paths.

The simulator's first nondeterminism-bearing subsystem. Every random draw is
a pure function of a counter tuple — threefry2x32 on
(seed, stream, cluster, object, incarnation/attempt) — so the scalar
event-driven path and the batched array path consume IDENTICAL values with no
stream to keep in sync, batched runs stay order-independent (a dropped or
re-ordered draw cannot shift any other draw), and re-running any prefix of a
simulation replays the same faults. This is the template every future
stochastic workload should follow (see docs/DESIGN.md "Fault model").

Two fault channels:

- Node crashes (MTTF) with recovery (MTTR), sampled HOST-SIDE into concrete
  crash/recover events before either path runs: crash/recover chains depend
  only on the trace's node lifetimes, never on simulation state, so they
  compile exactly. A crash rides the planned node-removal chain (flagged
  `crashed`, carrying its pre-sampled downtime); a recovery is a fresh
  CreateNodeRequest (flagged `recovered`) — the node returns as fresh
  capacity (a new pool component on the scalar path, its own slot on the
  batched one: trace_compile), visible to the
  cluster autoscaler like any other capacity. TTF/TTR draws are clamped
  below at one scheduling interval so every crash->recover->crash transition
  lands in its own batched window (the bulk event application is
  window-granular).

- Pod failures (CrashLoopBackOff), drawn AT ATTEMPT COMMIT TIME in both
  paths from (cluster, global plain pod slot, restart count): a failing
  attempt runs for u_frac x duration then fails; the pod re-enters the
  scheduling queue after min(backoff_base * 2^k, backoff_cap) and is marked
  permanently failed once its restart count exceeds restart_limit. Only
  plain trace pods participate (HPA pod-group ring replicas and
  long-running services are exempt — their identities are runtime-assigned
  and path-specific).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

# Stream ids separating the fault channels in the counter space.
STREAM_NODE = 1
STREAM_GROUP = 2
STREAM_POD = 3


class FaultParams(NamedTuple):
    """Static (hashable) fault constants threaded into the batched step as a
    jit-static argument. None in its place = fault injection off — every
    compiled program is then textually identical to the pre-chaos build
    (the composed-path dispatch formula is untouched)."""

    seed: int
    fail_prob: float
    backoff_base: float
    backoff_cap: float
    restart_limit: int
    node_faults: bool  # slab may carry EV_NODE_CRASH / EV_NODE_RECOVER

    @property
    def pod_faults(self) -> bool:
        return self.fail_prob > 0.0


def has_node_faults(cfg) -> bool:
    """Whether a FaultInjectionConfig configures any node-level fault
    channel — the ONE owner of this predicate (the CLI's native-feeder
    guard, the engine's per-cluster compile decision and the jit-static
    FaultParams must never disagree)."""
    return (
        cfg is not None
        and cfg.enabled
        and (
            (cfg.node is not None and cfg.node.mttf > 0)
            or any(g.mttf > 0 for g in (cfg.failure_groups or []))
        )
    )


def make_fault_params(config, node_fault_events: bool = False) -> Optional[FaultParams]:
    """FaultParams from a SimulationConfig; None when fault injection is
    disabled or configured to do nothing. `node_fault_events`: the compiled
    traces carry crash / recover events (sampled from this config or handed
    to the build already sampled, with no `fault_injection` block at all);
    they switch the node channel on by themselves."""
    cfg = getattr(config, "fault_injection", None)
    if cfg is not None and not cfg.enabled:
        cfg = None
    node_faults = has_node_faults(cfg) or node_fault_events
    pod = cfg.pod if cfg is not None else None
    fail_prob = float(pod.fail_prob) if pod else 0.0
    if not node_faults and fail_prob <= 0:
        return None
    return FaultParams(
        seed=int(cfg.seed if cfg is not None and cfg.seed is not None else config.seed),
        fail_prob=fail_prob,
        backoff_base=float(pod.backoff_base) if pod else 10.0,
        backoff_cap=float(pod.backoff_cap) if pod else 300.0,
        restart_limit=int(pod.restart_limit) if pod else 5,
        node_faults=node_faults,
    )

_KS_PARITY = 0x1BD11BDA
_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)


def _threefry2x32(k0, k1, c0, c1, xp):
    """Threefry-2x32 (20 rounds). `xp` is numpy or jax.numpy; every
    intermediate is cast back to uint32 so both backends wrap identically.
    Returns two uint32 blocks."""
    u32 = xp.uint32

    def u(x):
        return xp.asarray(x).astype(u32)

    def rotl(x, r):
        return u(
            (x << u(np.uint32(r))) | (x >> u(np.uint32(32 - r)))
        )

    ks0, ks1 = u(k0), u(k1)
    ks2 = u(ks0 ^ ks1 ^ u(np.uint32(_KS_PARITY)))
    ks = (ks0, ks1, ks2)
    x0 = u(u(c0) + ks0)
    x1 = u(u(c1) + ks1)
    for chunk in range(5):
        rots = _ROT_A if chunk % 2 == 0 else _ROT_B
        for r in rots:
            x0 = u(x0 + x1)
            x1 = rotl(x1, r)
            x1 = u(x1 ^ x0)
        d = chunk + 1
        x0 = u(x0 + ks[d % 3])
        x1 = u(x1 + ks[(d + 1) % 3] + u(np.uint32(d)))
    return x0, x1


def _to_unit(bits, xp):
    """uint32 -> float32 uniform in [0, 1): top 24 bits scaled. (bits >> 8)
    < 2^24 is exactly representable in float32 and the 2^-24 scaling is a
    power of two, so the conversion is bit-identical on every backend."""
    f32 = xp.float32
    return (bits >> xp.uint32(8)).astype(f32) * f32(2.0**-24)


def object_uniforms(seed, stream, cluster, obj, counter, xp=np):
    """Two float32 uniforms for (seed, stream, cluster, obj, counter) via a
    two-level threefry chain: key = H(seed, stream | cluster, obj), then
    block (counter, 0). Vectorized: cluster/obj/counter broadcast. The ONE
    derivation both paths use (numpy host-side, jnp on device)."""
    h0, h1 = _threefry2x32(seed, stream, cluster, obj, xp)
    b0, b1 = _threefry2x32(h0, h1, counter, xp.uint32(0), xp)
    return _to_unit(b0, xp), _to_unit(b1, xp)


def pod_attempt_uniforms(seed, cluster, slot, attempt, xp=np):
    """(u_fail, u_frac) for one pod scheduling attempt; attempt = the pod's
    restart count when the attempt commits."""
    return object_uniforms(seed, STREAM_POD, cluster, slot, attempt, xp)


# --- node-fault compilation (host-side, shared by both paths) ---------------


def _sample_span(u: float, mean: float, distribution: str) -> float:
    if distribution == "fixed":
        return float(mean)
    if distribution != "exponential":
        # Config parsing validates too; this guards direct-API callers.
        raise ValueError(
            f"unknown fault distribution {distribution!r} "
            "(expected 'exponential' or 'fixed')"
        )
    # Exponential inverse CDF; u in [0, 1) so log(1-u) is finite.
    return float(-mean * np.log1p(-np.float64(u)))


def _sample_span_vec(
    u: np.ndarray, mean: float, distribution: str
) -> np.ndarray:
    """Vectorized _sample_span over a float32 uniform array: the SAME f64
    elementwise arithmetic (cast first, then -mean * log1p(-u)), so each
    lane is bit-identical to the scalar call on its element."""
    if distribution == "fixed":
        return np.full(np.shape(u), float(mean), np.float64)
    if distribution != "exponential":
        raise ValueError(
            f"unknown fault distribution {distribution!r} "
            "(expected 'exponential' or 'fixed')"
        )
    return -float(mean) * np.log1p(-np.asarray(u, np.float64))


def fault_horizon(cfg, cluster_events, workload_events) -> float:
    """Sampling horizon: explicit config value, else the latest finite trace
    timestamp (both paths hold the same traces, so both derive the same
    horizon)."""
    if cfg.horizon is not None:
        return float(cfg.horizon)
    last = 0.0
    for events in (cluster_events, workload_events):
        for ts, _ in events:
            if np.isfinite(ts):
                last = max(last, float(ts))
    return last


@dataclass
class _NodeLifetime:
    uid: int  # appearance index among the trace's CreateNode events
    name: str
    node: object  # core.types.Node template (capacity source)
    create_ts: float
    remove_ts: float  # +inf when never removed by the trace


def _node_lifetimes(cluster_events) -> List[_NodeLifetime]:
    from kubernetriks_tpu.core.events import CreateNodeRequest, RemoveNodeRequest

    lifetimes: List[_NodeLifetime] = []
    live: Dict[str, _NodeLifetime] = {}
    for ts, event in cluster_events:
        if isinstance(event, CreateNodeRequest):
            lt = _NodeLifetime(
                uid=len(lifetimes),
                name=event.node.metadata.name,
                node=event.node,
                create_ts=float(ts),
                remove_ts=np.inf,
            )
            lifetimes.append(lt)
            live[lt.name] = lt
        elif isinstance(event, RemoveNodeRequest):
            lt = live.pop(event.node_name, None)
            if lt is not None:
                lt.remove_ts = float(ts)
    return lifetimes


def _chain(
    seed: int,
    stream: int,
    cluster: int,
    uid: int,
    t0: float,
    end: float,
    horizon: float,
    mttf: float,
    mttr: float,
    distribution: str,
    interval: float,
) -> List[Tuple[float, float]]:
    """Crash/recover pairs for one failure process alive on [t0, end).
    Each incarnation k draws (u_ttf, u_ttr) from the counter PRNG; draws are
    clamped below at one scheduling interval so consecutive transitions land
    in distinct batched windows. A pair is emitted only when BOTH times fall
    before the node's planned removal (a crash whose recovery would outlive
    the node is dropped — the node stays up until its planned removal)."""
    pairs: List[Tuple[float, float]] = []
    t = t0
    k = 0
    while True:
        u1, u2 = object_uniforms(
            seed, stream, np.uint32(cluster), np.uint32(uid), np.uint32(k)
        )
        ttf = max(_sample_span(float(u1), mttf, distribution), interval)
        crash = t + ttf
        if crash >= min(horizon, end):
            break
        ttr = max(_sample_span(float(u2), mttr, distribution), interval)
        recover = crash + ttr
        if recover >= end:
            break
        pairs.append((crash, recover))
        t = recover
        k += 1
    return pairs


def _chains_batched(
    seed: int,
    stream: int,
    cluster: int,
    uids: Sequence[int],
    t0s: Sequence[float],
    ends: Sequence[float],
    horizon: float,
    mttf: float,
    mttr: float,
    distribution: str,
    interval: float,
) -> List[List[Tuple[float, float]]]:
    """Crash/recover chains for MANY failure processes at once — the
    vectorized twin of per-uid _chain calls, pinned bit-identical by
    tests/test_chaos.py. The counter PRNG is order-independent, so one
    threefry call per incarnation index draws (u_ttf, u_ttr) for EVERY
    process; only the tiny incarnation loop stays sequential (chain times
    accumulate), and each lane's float arithmetic is the scalar loop's
    exact sequence (elementwise f64 adds in the same association). Draws
    for already-terminated processes are computed and dropped — dropped
    draws desync nothing by construction.

    Replaces the host-side compile bottleneck for node-fault traces: the
    loop version hashed 2 x incarnations x lifetimes blocks one scalar
    threefry at a time through Python."""
    U = len(uids)
    pairs: List[List[Tuple[float, float]]] = [[] for _ in range(U)]
    if U == 0:
        return pairs
    uid_arr = np.asarray(uids, np.uint32)
    t = np.asarray(t0s, np.float64).copy()
    end_arr = np.asarray(ends, np.float64)
    cutoff = np.minimum(np.float64(horizon), end_arr)  # crash must stay below
    active = np.ones(U, bool)
    k = 0
    while active.any():
        u1, u2 = object_uniforms(
            seed, stream, np.uint32(cluster), uid_arr, np.uint32(k)
        )
        ttf = np.maximum(_sample_span_vec(u1, mttf, distribution), interval)
        crash = t + ttf
        active &= crash < cutoff
        ttr = np.maximum(_sample_span_vec(u2, mttr, distribution), interval)
        recover = crash + ttr
        active &= recover < end_arr
        for i in np.nonzero(active)[0]:
            pairs[i].append((float(crash[i]), float(recover[i])))
        t = np.where(active, recover, t)
        k += 1
    return pairs


def inject_node_faults(
    cluster_events,
    cfg,
    seed: int,
    cluster_idx: int,
    horizon: float,
    interval: float,
):
    """Return a NEW cluster-event list: the original events (order
    preserved) plus sampled crash/recover events appended in time order.
    Crash = RemoveNodeRequest(crashed=True, downtime_s=sampled TTR);
    recover = CreateNodeRequest(recovered=True) with the node's original
    capacity. Deterministic in
    (cfg, seed, cluster_idx, trace)."""
    from kubernetriks_tpu.core.events import CreateNodeRequest, RemoveNodeRequest

    lifetimes = _node_lifetimes(cluster_events)
    by_name: Dict[str, List[_NodeLifetime]] = {}
    for lt in lifetimes:
        by_name.setdefault(lt.name, []).append(lt)

    fault_events: List[Tuple[float, object]] = []
    # Emitted downtime spans per lifetime uid. The per-node and group chains
    # are sampled independently, so without mutual exclusion a group crash
    # could land while its member is already down (double-remove -> KeyError
    # at trace compile). Channels are applied in a fixed order (per-node
    # first, then groups in config order) and a pair is dropped for any
    # member already down — or within one scheduling interval of another
    # transition, keeping every slot's create/remove in distinct batched
    # windows. Host-side and order-deterministic, so both paths agree.
    downtime: Dict[int, List[Tuple[float, float]]] = {}

    def clear_of_existing(lt: _NodeLifetime, crash: float, recover: float) -> bool:
        return all(
            recover + interval <= start or crash >= end + interval
            for start, end in downtime.get(lt.uid, [])
        )

    def emit_pair(lt: _NodeLifetime, crash: float, recover: float) -> None:
        downtime.setdefault(lt.uid, []).append((crash, recover))
        ttr = recover - crash
        fault_events.append(
            (
                crash,
                RemoveNodeRequest(
                    node_name=lt.name, crashed=True, downtime_s=float(ttr)
                ),
            )
        )
        fresh = lt.node.copy()
        fresh.status.allocatable = fresh.status.capacity.copy()
        fault_events.append(
            (recover, CreateNodeRequest(node=fresh, recovered=True))
        )

    # Chain sampling is BATCHED across lifetimes (_chains_batched draws one
    # threefry block per incarnation index for every process at once);
    # emission order is unchanged — lifetimes in uid order, each chain in
    # incarnation order — so the event stream is bit-identical to the
    # per-lifetime loop (pinned in tests/test_chaos.py).
    if cfg.node is not None and cfg.node.mttf > 0:
        chains = _chains_batched(
            seed,
            STREAM_NODE,
            cluster_idx,
            [lt.uid for lt in lifetimes],
            [lt.create_ts for lt in lifetimes],
            [lt.remove_ts for lt in lifetimes],
            horizon,
            cfg.node.mttf,
            cfg.node.mttr,
            cfg.node.distribution,
            interval,
        )
        for lt, chain in zip(lifetimes, chains):
            for crash, recover in chain:
                emit_pair(lt, crash, recover)

    # Correlated failure groups: one shared crash process per group; every
    # member whose lifetime covers the full (crash, recover) span goes down
    # and comes back together (blast radius). Groups carry their own
    # mttf/mttr, so each is its own (single-process) batched call.
    for gi, group in enumerate(cfg.failure_groups or []):
        for crash, recover in _chains_batched(
            seed,
            STREAM_GROUP,
            cluster_idx,
            [gi],
            [0.0],
            [np.inf],
            horizon,
            group.mttf,
            group.mttr,
            group.distribution,
            interval,
        )[0]:
            for name in group.members:
                for lt in by_name.get(name, []):
                    if (
                        lt.create_ts <= crash
                        and recover < lt.remove_ts
                        and clear_of_existing(lt, crash, recover)
                    ):
                        emit_pair(lt, crash, recover)

    fault_events.sort(key=lambda item: item[0])
    return list(cluster_events) + fault_events


# --- pod-fault oracle (scalar path) -----------------------------------------


def plain_pod_slot_map(workload_events) -> Dict[str, int]:
    """name -> global plain pod slot, replicating the batched trace
    compiler's numbering: CreatePodRequest events stably sorted by
    timestamp, ranked among plain pods (pod-group ring slots are renumbered
    past every plain pod by segment_pod_slots, so the plain rank IS the
    global slot in both the segmented and unsegmented layouts)."""
    from kubernetriks_tpu.core.events import CreatePodRequest

    creates = [
        (float(ts), i, event.pod.metadata.name)
        for i, (ts, event) in enumerate(workload_events)
        if isinstance(event, CreatePodRequest)
    ]
    creates.sort(key=lambda item: (item[0], item[1]))
    return {name: slot for slot, (_, _, name) in enumerate(creates)}


class PodFaultOracle:
    """Scalar-path pod failure oracle: draws the SAME counter-PRNG values
    the batched commit draws on device, tracks per-pod restart counts, and
    answers the retry/perma/backoff questions the control-plane components
    ask. Pods without a plain trace slot (HPA ring replicas) and
    long-running services are exempt."""

    def __init__(self, cfg, seed: int, cluster_idx: int, workload_events) -> None:
        pod = cfg.pod
        self.fail_prob = np.float32(pod.fail_prob if pod else 0.0)
        self.backoff_base = float(pod.backoff_base) if pod else 10.0
        self.backoff_cap = float(pod.backoff_cap) if pod else 300.0
        self.restart_limit = int(pod.restart_limit) if pod else 5
        self.seed = int(seed)
        self.cluster_idx = int(cluster_idx)
        self.slot_map = plain_pod_slot_map(workload_events)
        self.restarts: Dict[str, int] = {}

    def attempt(
        self, pod_name: str, pod_duration: Optional[float]
    ) -> Optional[float]:
        """Draw for one scheduling attempt at commit: returns fail_after
        seconds (the attempt fails that long after its start) or None (the
        attempt runs to completion)."""
        if self.fail_prob <= 0 or pod_duration is None:
            return None
        slot = self.slot_map.get(pod_name)
        if slot is None:
            return None
        k = self.restarts.get(pod_name, 0)
        u_fail, u_frac = pod_attempt_uniforms(
            self.seed,
            np.uint32(self.cluster_idx),
            np.uint32(slot),
            np.uint32(k),
        )
        if not bool(np.float32(u_fail) < self.fail_prob):
            return None
        # f32 product mirrors the batched path's u_frac * duration_seconds.
        return float(np.float32(u_frac) * np.float32(pod_duration))

    def record_failure(self, pod_name: str) -> int:
        """Increment and return the pod's restart count (called once per
        failure, by the api server — the first component on the failure
        chain)."""
        k = self.restarts.get(pod_name, 0) + 1
        self.restarts[pod_name] = k
        return k

    def is_permanently_failed(self, pod_name: str) -> bool:
        return self.restarts.get(pod_name, 0) > self.restart_limit

    def backoff_after_failure(self, pod_name: str) -> float:
        """Backoff of the pod's LAST recorded failure: min(base * 2^k, cap)
        with k = the restart count before that failure (0-based). float32
        arithmetic so the value matches the batched path bit-for-bit."""
        k = max(self.restarts.get(pod_name, 1) - 1, 0)
        return float(
            np.minimum(
                np.float32(self.backoff_base) * np.exp2(np.float32(k)),
                np.float32(self.backoff_cap),
            )
        )
