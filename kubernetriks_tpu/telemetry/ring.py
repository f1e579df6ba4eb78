"""Device-side telemetry ring: build, drain, merge (the flight recorder's
sim-time half).

The ring itself (state.TelemetryRing) is carried INSIDE ClusterBatchState
and written on-device by the window body (step._telemetry_record) — one
(C, TELEMETRY_COLS) int32 row per executed window, scattered at
cursor % R. This module owns everything host-side:

- `init_ring` builds the empty ring the engine attaches at construction;
- `snapshot` drains it to host arrays. The engine calls this ONLY at
  boundaries where the host already blocks — step_until_time exit (where
  bench span fetches land) and readout — NEVER inside the dispatch loop,
  so telemetry-on adds zero new host syncs there and the dispatch-count
  regression gate (tests/test_telemetry.py) holds. Unlike tracer.py,
  this module deliberately opts OUT of the lint pass's hot-path pragma:
  it is the cold drain side, and the one device fetch below is its whole
  purpose.
- `series` merges drained snapshots into one (windows, (Wn, C, K)) view,
  deduped by window index (overlapping snapshots of a wrapping ring
  re-observe the same rows bit-identically).
- `counter_events` renders the merged series as Chrome trace counter
  ("C") tracks on a sim-time process, so the Perfetto view shows queue
  depth / autoscaler actions / fault events against the host span
  timeline.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from kubernetriks_tpu.batched.state import TELEMETRY_COLS, TelemetryRing

# Column names, indexed by the TELEM_* constants in batched/state.py.
RING_COLUMNS = (
    "window",
    "decisions",
    "queued",
    "unschedulable",
    "hpa_pod_actions",
    "ca_node_actions",
    "fault_events",
    "alive_nodes",
    # Capacity-observatory occupancy gauges (telemetry/observatory.py):
    # live HPA replicas vs the pod-group slot reserve, consumed CA node
    # slots (monotone — the ROADMAP #2 saturation driver), and the
    # remaining plain-trace columns ahead of the sliding pod window.
    "hpa_reserve_used",
    "ca_reserve_used",
    "pod_headroom",
    # Lane-async fleet occupancy bit (state.TELEM_LANE_ACTIVE): 1 when the
    # lane's per-lane clock made it active for the window, constant 1
    # outside lane-async builds. The observatory's lane-occupancy gauge
    # and idle-lane-waste verdict fold this column.
    "lane_active",
    # The megakernel's sweep counter (state.TELEM_CYCLE_TILES_SWEPT /
    # _TILE_STEPS): pod-block row tiles its steps swept, and steps x the
    # block's tiles. telemetry_report()["ring"]["cycle_rows_swept_share"]
    # is the ratio of their totals.
    "cycle_tiles_swept",
    "cycle_tile_steps",
    # The event loop's chunks a cluster (state.TELEM_EVENT_CHUNKS); a
    # window's maximum over clusters is the slab reads it paid.
    "event_chunks",
    # Frees the pending-free channel carried past the window's cycle
    # (state.TELEM_FREES_DEFERRED).
    "frees_deferred",
    # Assignments of pods under a topology-spread constraint for which the
    # skew had closed a live domain (state.TELEM_SPREAD_BOUND).
    "spread_bound",
)
assert len(RING_COLUMNS) == TELEMETRY_COLS

# Gauges are POINT-IN-TIME readings: summing them across windows (the way
# the per-window action deltas sum into ring totals) is meaningless, so
# report consumers track their high-water mark instead.
GAUGE_COLUMNS = frozenset(
    {
        "queued",
        "unschedulable",
        "alive_nodes",
        "hpa_reserve_used",
        "ca_reserve_used",
        "pod_headroom",
        "lane_active",
    }
)


def init_ring(n_clusters: int, capacity: int) -> TelemetryRing:
    """Empty ring: window column -1 marks unwritten rows (the drain
    filters on it), cursor 0."""
    return TelemetryRing(
        buf=jnp.full(
            (n_clusters, capacity, TELEMETRY_COLS), -1, jnp.int32
        ),
        cursor=jnp.zeros((n_clusters,), jnp.int32),
    )


def snapshot(telem: TelemetryRing) -> Tuple[np.ndarray, int]:
    """Drain the ring to host: ((C, R, K) buffer copy, total windows
    recorded). Blocking device fetch — callers sit at an existing host
    sync boundary (readout / step_until_time exit), outside the
    sanitizer's transfer-guard region. np.array (owned COPY, not a view):
    on the CPU backend device_get can alias the device buffer, and the
    next DONATED dispatch would mutate the buffer — and the snapshot —
    in place."""
    from kubernetriks_tpu.parallel.multihost import to_host

    buf = np.array(to_host(telem.buf))
    cursor = int(np.asarray(to_host(telem.cursor)).max())
    return buf, cursor


def merge_snapshot(seen: dict, buf: np.ndarray) -> None:
    """Fold one drained buffer into the window->row accumulator (keys:
    window index, values: (C, K) rows). Overlapping snapshots of a
    wrapping ring re-observe the same rows bit-identically, so last-write
    dedupe is exact; the dict keeps memory bounded by DISTINCT windows,
    not drain count."""
    wins = buf[0, :, 0]  # (R,) window column, uniform across clusters
    for slot in np.nonzero(wins >= 0)[0]:
        seen[int(wins[slot])] = buf[:, slot, :]


def series(seen: dict, n_clusters: int) -> Tuple[np.ndarray, np.ndarray]:
    """Accumulated records as (windows (Wn,), data (Wn, C, K)), sorted by
    window index."""
    if not seen:
        return (
            np.zeros((0,), np.int32),
            np.zeros((0, n_clusters, TELEMETRY_COLS), np.int32),
        )
    order = sorted(seen)
    wins = np.asarray(order, np.int32)
    data = np.stack([seen[w] for w in order], axis=0)  # (Wn, C, K)
    return wins, data


def counter_events(
    wins: np.ndarray, data: np.ndarray, interval: float, pid: int = 1
) -> list:
    """Chrome trace counter tracks from the merged ring series, on a
    sim-time process (ts = window * interval in sim-µs): cross-cluster
    sums per window for each ring column past the window index."""
    ev = [
        {
            "ph": "M",
            "name": "process_name",
            "pid": pid,
            "tid": 0,
            "args": {"name": "ktpu-device-ring (sim time)"},
        }
    ]
    if len(wins) == 0:
        return ev
    totals = data.sum(axis=1)  # (Wn, K) summed over clusters
    for i, w in enumerate(wins.tolist()):
        ts = w * interval * 1e6
        for col in range(1, TELEMETRY_COLS):
            ev.append(
                {
                    "ph": "C",
                    "name": RING_COLUMNS[col],
                    "pid": pid,
                    "ts": ts,
                    "args": {RING_COLUMNS[col]: int(totals[i, col])},
                }
            )
    return ev
