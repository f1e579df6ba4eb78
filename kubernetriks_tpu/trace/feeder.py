"""ctypes binding for the native C++ trace feeder (native/trace_feeder.cc).

The feeder is the framework's native host-side data loader: it parses the
Alibaba v2017 CSVs (batch_instance joined to batch_task; machine_events),
applies the reference's validity filters (reference:
src/trace/alibaba_cluster_trace_v2017/workload.rs:56-120, cluster.rs:55-105)
and returns dense, time-sorted numpy arrays ready to be compiled into device
tensors. The pure-Python pipeline in kubernetriks_tpu.trace.alibaba has
identical semantics and serves as both fallback and oracle.

The shared library is built on demand with g++ (cached next to the source,
keyed on a hash of the source — a copied tree does not keep mtimes, and a
stray library built from other source must never load); if no toolchain is
available the callers fall back to the Python path.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SOURCE = os.path.join(_REPO_ROOT, "native", "trace_feeder.cc")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _build_library() -> Tuple[Optional[str], Optional[str]]:
    """Compile the feeder unless the library for exactly this source is
    already built. Returns (library path, None) or (None, error string)."""
    try:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        if not os.path.exists(_SOURCE):
            return None, f"feeder source not found: {_SOURCE}"
        with open(_SOURCE, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()[:16]
        lib = os.path.join(_BUILD_DIR, f"libtrace_feeder.{digest}.so")
        if os.path.exists(lib):
            return lib, None
    except OSError as exc:
        return None, f"cannot stage native build dir: {exc}"
    # Build to a per-process temp path, then rename into place: concurrent
    # builders (pytest workers, parallel CLI runs) must never dlopen a
    # half-written .so.
    tmp = f"{lib}.tmp.{os.getpid()}"
    cmd = [
        "g++", "-O2", "-std=c++17", "-shared", "-fPIC",
        _SOURCE, "-o", tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        if proc.returncode != 0:
            return None, f"g++ failed: {proc.stderr[-2000:]}"
        os.replace(tmp, lib)
    except (OSError, subprocess.TimeoutExpired) as exc:
        return None, f"g++ invocation failed: {exc}"
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return lib, None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        path, err = _build_library()
        if err is not None:
            _build_error = err
            return None
        lib = ctypes.CDLL(path)
        lib.feeder_parse_workload.restype = ctypes.c_void_p
        lib.feeder_parse_workload.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.feeder_parse_machines.restype = ctypes.c_void_p
        lib.feeder_parse_machines.argtypes = [ctypes.c_char_p]
        lib.feeder_error.restype = ctypes.c_char_p
        lib.feeder_error.argtypes = [ctypes.c_void_p]
        lib.feeder_workload_count.restype = ctypes.c_int64
        lib.feeder_workload_count.argtypes = [ctypes.c_void_p]
        lib.feeder_workload_rows_read.restype = ctypes.c_int64
        lib.feeder_workload_rows_read.argtypes = [ctypes.c_void_p]
        lib.feeder_machine_count.restype = ctypes.c_int64
        lib.feeder_machine_count.argtypes = [ctypes.c_void_p]
        f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        lib.feeder_workload_fill.restype = None
        lib.feeder_workload_fill.argtypes = [
            ctypes.c_void_p, f64p, i64p, i64p, f64p, i64p, i64p, i64p,
        ]
        lib.feeder_workload_fill_range.restype = None
        lib.feeder_workload_fill_range.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            f64p, i64p, i64p, f64p, i64p, i64p, i64p,
        ]
        lib.feeder_machine_fill.restype = None
        lib.feeder_machine_fill.argtypes = [ctypes.c_void_p, f64p, i32p, i64p, i64p, i64p]
        lib.feeder_free.restype = None
        lib.feeder_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def native_build_error() -> Optional[str]:
    _load()
    return _build_error


@dataclass
class WorkloadArrays:
    """Dense pod-creation events, stably sorted by start timestamp."""

    start_ts: np.ndarray       # (P,) float64 seconds
    cpu_millicores: np.ndarray  # (P,) int64
    ram_bytes: np.ndarray       # (P,) int64
    duration: np.ndarray        # (P,) float64 seconds
    job_id: np.ndarray          # (P,) int64; -1 encodes a missing job id
    task_id: np.ndarray         # (P,) int64
    pod_no: np.ndarray          # (P,) int64 per-trace running pod counter
    # Data rows of batch_instance the parse read (len(start_ts) of them passed
    # the validity filter); None on a row-range segment.
    rows_read: Optional[int] = None

    def pod_name(self, i: int) -> str:
        # Mirrors the Python path's f"{job_id}_{task_id}_{n}" naming, where a
        # missing job id renders as the literal "None".
        jid = "None" if self.job_id[i] == -1 else str(int(self.job_id[i]))
        return f"{jid}_{int(self.task_id[i])}_{int(self.pod_no[i])}"


@dataclass
class ClusterArrays:
    """Dense node lifecycle events (kind 0 = create, 1 = remove), sorted."""

    ts: np.ndarray             # (M,) float64 seconds
    kind: np.ndarray           # (M,) int32
    cpu_millicores: np.ndarray  # (M,) int64 (creates only)
    ram_bytes: np.ndarray       # (M,) int64 (creates only)
    machine_id: np.ndarray      # (M,) int64

    def node_name(self, i: int) -> str:
        return f"alibaba_node_{int(self.machine_id[i])}"


def _take_handle(lib: ctypes.CDLL, handle: int) -> int:
    if not handle:
        raise RuntimeError("native feeder returned a null handle")
    err = lib.feeder_error(ctypes.c_void_p(handle)).decode()
    if err:
        lib.feeder_free(ctypes.c_void_p(handle))
        raise ValueError(err)
    return handle


def load_workload_arrays(
    batch_instance_path: str, batch_task_path: str
) -> WorkloadArrays:
    """Parse + join + filter the workload CSVs natively."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native feeder unavailable: {_build_error}")
    handle = _take_handle(
        lib,
        lib.feeder_parse_workload(
            batch_instance_path.encode(), batch_task_path.encode()
        ),
    )
    try:
        n = lib.feeder_workload_count(ctypes.c_void_p(handle))
        out = WorkloadArrays(
            start_ts=np.empty(n, np.float64),
            cpu_millicores=np.empty(n, np.int64),
            ram_bytes=np.empty(n, np.int64),
            duration=np.empty(n, np.float64),
            job_id=np.empty(n, np.int64),
            task_id=np.empty(n, np.int64),
            pod_no=np.empty(n, np.int64),
            rows_read=int(lib.feeder_workload_rows_read(ctypes.c_void_p(handle))),
        )
        if n:
            lib.feeder_workload_fill(
                ctypes.c_void_p(handle),
                out.start_ts, out.cpu_millicores, out.ram_bytes,
                out.duration, out.job_id, out.task_id, out.pod_no,
            )
        return out
    finally:
        lib.feeder_free(ctypes.c_void_p(handle))


class WorkloadSegmentReader:
    """Keep-alive handle over the natively parsed workload: pulls sorted
    rows [lo, lo + n) as bounded WorkloadArrays segments instead of
    materializing every column Python-side at once — the TRACE half of
    the streaming ingestion pipeline (batched/stream.py stages payload
    segments; this is the seam that feeds them for multi-million-row
    Alibaba replays: the compact parsed representation stays native-side,
    and the Python working set is one segment).

    Usage:
        with WorkloadSegmentReader(bi_path, bt_path) as r:
            for seg in r.iter_segments(rows_per_segment=1_000_000):
                ...  # seg is a WorkloadArrays over one row range

    Segment reads are pure slices of the one stable time-sort the parse
    performed, so concatenating every segment reproduces
    load_workload_arrays exactly (pinned in tests/test_native_feeder.py).
    """

    def __init__(self, batch_instance_path: str, batch_task_path: str):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native feeder unavailable: {_build_error}")
        self._lib = lib
        self._handle: Optional[int] = _take_handle(
            lib,
            lib.feeder_parse_workload(
                batch_instance_path.encode(), batch_task_path.encode()
            ),
        )
        self._count = int(
            lib.feeder_workload_count(ctypes.c_void_p(self._handle))
        )

    def __len__(self) -> int:
        return self._count

    def read(self, lo: int, n: int) -> WorkloadArrays:
        """Rows [lo, lo + n) of the sorted workload (clamped to the end)."""
        if self._handle is None:
            raise ValueError("WorkloadSegmentReader is closed")
        if lo < 0:
            raise ValueError(f"segment lo must be >= 0, got {lo}")
        n = max(0, min(n, self._count - lo))
        out = WorkloadArrays(
            start_ts=np.empty(n, np.float64),
            cpu_millicores=np.empty(n, np.int64),
            ram_bytes=np.empty(n, np.int64),
            duration=np.empty(n, np.float64),
            job_id=np.empty(n, np.int64),
            task_id=np.empty(n, np.int64),
            pod_no=np.empty(n, np.int64),
        )
        if n:
            self._lib.feeder_workload_fill_range(
                ctypes.c_void_p(self._handle), lo, n,
                out.start_ts, out.cpu_millicores, out.ram_bytes,
                out.duration, out.job_id, out.task_id, out.pod_no,
            )
        return out

    def iter_segments(self, rows_per_segment: int):
        """Yield (lo, WorkloadArrays) covering the whole workload in order."""
        if rows_per_segment <= 0:
            raise ValueError("rows_per_segment must be positive")
        lo = 0
        while lo < self._count:
            yield lo, self.read(lo, rows_per_segment)
            lo += rows_per_segment

    def close(self) -> None:
        if self._handle is not None:
            self._lib.feeder_free(ctypes.c_void_p(self._handle))
            self._handle = None

    def __enter__(self) -> "WorkloadSegmentReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass


class WorkloadArraysReader:
    """Python-oracle random-access reader over a materialized
    WorkloadArrays: the same (lo, n) -> WorkloadArrays contract as
    WorkloadSegmentReader.read, for callers (payload sources, tests)
    that need row ranges without the native toolchain. Views, no copies."""

    def __init__(self, arrays: WorkloadArrays) -> None:
        self.arrays = arrays
        self._count = len(arrays.start_ts)

    def __len__(self) -> int:
        return self._count

    def read(self, lo: int, n: int) -> WorkloadArrays:
        if lo < 0:
            raise ValueError(f"segment lo must be >= 0, got {lo}")
        hi = min(lo + max(n, 0), self._count)
        a = self.arrays
        return WorkloadArrays(
            start_ts=a.start_ts[lo:hi],
            cpu_millicores=a.cpu_millicores[lo:hi],
            ram_bytes=a.ram_bytes[lo:hi],
            duration=a.duration[lo:hi],
            job_id=a.job_id[lo:hi],
            task_id=a.task_id[lo:hi],
            pod_no=a.pod_no[lo:hi],
        )


def iter_workload_segments(
    arrays: WorkloadArrays, rows_per_segment: int
):
    """Python-oracle mirror of WorkloadSegmentReader.iter_segments over an
    already-materialized WorkloadArrays (the fallback path when no native
    toolchain exists): yields (lo, WorkloadArrays) row-range views with
    identical semantics, so callers of either source see the same segment
    stream."""
    if rows_per_segment <= 0:
        raise ValueError("rows_per_segment must be positive")
    total = len(arrays.start_ts)
    lo = 0
    while lo < total:
        hi = min(lo + rows_per_segment, total)
        yield lo, WorkloadArrays(
            start_ts=arrays.start_ts[lo:hi],
            cpu_millicores=arrays.cpu_millicores[lo:hi],
            ram_bytes=arrays.ram_bytes[lo:hi],
            duration=arrays.duration[lo:hi],
            job_id=arrays.job_id[lo:hi],
            task_id=arrays.task_id[lo:hi],
            pod_no=arrays.pod_no[lo:hi],
        )
        lo = hi


def load_cluster_arrays(machine_events_path: str) -> ClusterArrays:
    """Parse + dedup the machine-events CSV natively."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native feeder unavailable: {_build_error}")
    handle = _take_handle(
        lib, lib.feeder_parse_machines(machine_events_path.encode())
    )
    try:
        n = lib.feeder_machine_count(ctypes.c_void_p(handle))
        out = ClusterArrays(
            ts=np.empty(n, np.float64),
            kind=np.empty(n, np.int32),
            cpu_millicores=np.empty(n, np.int64),
            ram_bytes=np.empty(n, np.int64),
            machine_id=np.empty(n, np.int64),
        )
        if n:
            lib.feeder_machine_fill(
                ctypes.c_void_p(handle),
                out.ts, out.kind, out.cpu_millicores, out.ram_bytes,
                out.machine_id,
            )
        return out
    finally:
        lib.feeder_free(ctypes.c_void_p(handle))


def workload_events_from_arrays(arrays: WorkloadArrays) -> List[Tuple[float, object]]:
    """Materialize the dense arrays back into CreatePodRequest trace events
    (object form used by the scalar path and the batched trace compiler)."""
    from kubernetriks_tpu.core.events import CreatePodRequest
    from kubernetriks_tpu.core.types import Pod

    events = []
    for i in range(len(arrays.start_ts)):
        pod = Pod.new(
            arrays.pod_name(i),
            int(arrays.cpu_millicores[i]),
            int(arrays.ram_bytes[i]),
            float(arrays.duration[i]),
        )
        events.append((float(arrays.start_ts[i]), CreatePodRequest(pod=pod)))
    return events


def cluster_events_from_arrays(arrays: ClusterArrays) -> List[Tuple[float, object]]:
    from kubernetriks_tpu.core.events import CreateNodeRequest, RemoveNodeRequest
    from kubernetriks_tpu.core.types import Node

    events = []
    for i in range(len(arrays.ts)):
        name = arrays.node_name(i)
        if int(arrays.kind[i]) == 0:
            events.append(
                (
                    float(arrays.ts[i]),
                    CreateNodeRequest(
                        node=Node.new(
                            name,
                            int(arrays.cpu_millicores[i]),
                            int(arrays.ram_bytes[i]),
                        )
                    ),
                )
            )
        else:
            events.append((float(arrays.ts[i]), RemoveNodeRequest(node_name=name)))
    return events


def iter_time_slabs(
    arrays: WorkloadArrays, slab_seconds: float
) -> List[Tuple[float, float, slice]]:
    """Index the sorted workload into [t0, t0+slab) windows for streaming:
    host->device transfer happens one slab at a time so multi-million-row
    traces never need to sit in HBM whole (SURVEY §5.8 'host/device
    streaming'). Returns (slab_start, slab_end, index_slice) triples."""
    if len(arrays.start_ts) == 0:
        return []
    t0 = float(arrays.start_ts[0])
    t_end = float(arrays.start_ts[-1])
    slabs = []
    lo = 0
    slab_start = t0
    while slab_start <= t_end:
        slab_end = slab_start + slab_seconds
        hi = int(np.searchsorted(arrays.start_ts, slab_end, side="left"))
        if hi > lo:
            slabs.append((slab_start, slab_end, slice(lo, hi)))
        lo = hi
        slab_start = slab_end
    return slabs


class NativeAlibabaWorkloadTrace:
    """Trace-interface adapter over the native workload arrays: drop-in for
    AlibabaWorkloadTraceV2017 when the C++ feeder is available."""

    def __init__(self, arrays: WorkloadArrays) -> None:
        self.arrays: Optional[WorkloadArrays] = arrays

    @staticmethod
    def from_files(
        batch_instance_trace_path: str, batch_task_trace_path: str
    ) -> "NativeAlibabaWorkloadTrace":
        return NativeAlibabaWorkloadTrace(
            load_workload_arrays(batch_instance_trace_path, batch_task_trace_path)
        )

    def convert_to_simulator_events(self):
        arrays, self.arrays = self.arrays, None
        if arrays is None:
            return []
        return workload_events_from_arrays(arrays)

    def event_count(self) -> int:
        return 0 if self.arrays is None else len(self.arrays.start_ts)


class NativeAlibabaClusterTrace:
    """Trace-interface adapter over the native machine-event arrays."""

    def __init__(self, arrays: ClusterArrays) -> None:
        self.arrays: Optional[ClusterArrays] = arrays

    @staticmethod
    def from_file(machine_events_trace_path: str) -> "NativeAlibabaClusterTrace":
        return NativeAlibabaClusterTrace(load_cluster_arrays(machine_events_trace_path))

    def convert_to_simulator_events(self):
        arrays, self.arrays = self.arrays, None
        if arrays is None:
            return []
        return cluster_events_from_arrays(arrays)

    def event_count(self) -> int:
        return 0 if self.arrays is None else len(self.arrays.ts)
