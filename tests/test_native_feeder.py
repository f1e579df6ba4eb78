"""Native C++ trace feeder == pure-Python oracle, row for row.

The feeder (native/trace_feeder.cc via kubernetriks_tpu.trace.feeder) must
reproduce the Python pipeline's join/filter/convert semantics exactly
(reference: src/trace/alibaba_cluster_trace_v2017/{workload,cluster}.rs), so
every test here runs both implementations on the same CSVs and diffs events.
"""

import numpy as np
import pytest

from kubernetriks_tpu.core.events import CreateNodeRequest, CreatePodRequest, RemoveNodeRequest
from kubernetriks_tpu.trace import feeder
from kubernetriks_tpu.trace.alibaba import (
    AlibabaClusterTraceV2017,
    AlibabaWorkloadTraceV2017,
    read_batch_instances,
    read_batch_tasks,
    read_machine_events,
)

pytestmark = pytest.mark.skipif(
    not feeder.native_available(),
    reason=f"native feeder unavailable: {feeder.native_build_error()}",
)


WORKLOAD_TASKS = (
    # create, end, job, task, n_inst, status, cpus(santicores), norm_mem
    "100,200,1,10,2,Terminated,50,0.015625\n"     # 500 mcpu, 2 GiB
    "100,300,1,11,1,Terminated,100,0.25\n"        # 1000 mcpu, 32 GiB
    "100,300,1,12,1,Terminated,,\n"               # missing resources -> filtered
    "100,300,1,13,1,Terminated,64,0.5\n"
)
WORKLOAD_INSTANCES = (
    "41562,41618,1,10,299,Terminated,1,2\n"   # valid
    "41563,41619,1,10,300,Terminated,2,2\n"   # valid (same task, 2nd instance)
    ",41618,1,10,299,Interrupted,1,2\n"       # no start -> filtered
    "41562,,1,10,299,Interrupted,1,2\n"       # no end -> filtered
    "41562,41618,1,,299,Failed,1,2\n"         # no task id -> filtered
    "41562,41618,1,99,299,Terminated,1,2\n"   # unknown task -> filtered
    "41562,41618,1,12,299,Terminated,1,2\n"   # task lacks resources -> filtered
    "0,41618,1,11,299,Terminated,1,2\n"       # start <= 0 -> filtered
    "41618,41618,1,11,299,Terminated,1,2\n"   # start >= end -> filtered
    "41000,41001,1,11,299,Terminated,1,2\n"   # valid
    "41000,41100,,13,1,Terminated,1,1\n"      # valid, missing job id
)
MACHINE_EVENTS = (
    "10,1,add,,64,0.69\n"
    "10,2,add,,32,0.5\n"
    "50,1,softerror,links_broken,,\n"
    "60,1,harderror,,,\n"        # re-removal -> deduped
    "70,3,softerror,,,\n"        # ghost node -> deduped
    "80,2,harderror,,,\n"
    "90,4,add,,8,0.125\n"
)


def _python_workload_events(instances_text, tasks_text):
    trace = AlibabaWorkloadTraceV2017(
        read_batch_instances(instances_text), read_batch_tasks(tasks_text)
    )
    return trace.convert_to_simulator_events()


def _python_cluster_events(machines_text):
    return AlibabaClusterTraceV2017(
        read_machine_events(machines_text)
    ).convert_to_simulator_events()


def _write(tmp_path, name, text):
    path = tmp_path / name
    path.write_text(text)
    return str(path)


def test_workload_native_matches_python(tmp_path):
    inst = _write(tmp_path, "batch_instance.csv", WORKLOAD_INSTANCES)
    task = _write(tmp_path, "batch_task.csv", WORKLOAD_TASKS)

    arrays = feeder.load_workload_arrays(inst, task)
    native = feeder.workload_events_from_arrays(arrays)
    python = _python_workload_events(WORKLOAD_INSTANCES, WORKLOAD_TASKS)

    assert len(native) == len(python) == 4
    for (nts, nev), (pts, pev) in zip(native, python):
        assert nts == pts
        assert isinstance(nev, CreatePodRequest)
        assert nev.pod.metadata.name == pev.pod.metadata.name
        assert nev.pod.spec.resources.requests.cpu == pev.pod.spec.resources.requests.cpu
        assert nev.pod.spec.resources.requests.ram == pev.pod.spec.resources.requests.ram
        assert nev.pod.spec.running_duration == pev.pod.spec.running_duration
    # The missing-job-id row renders like the Python f-string.
    assert any(ev.pod.metadata.name.startswith("None_13_") for _, ev in native)


def test_cluster_native_matches_python(tmp_path):
    path = _write(tmp_path, "machine_events.csv", MACHINE_EVENTS)

    arrays = feeder.load_cluster_arrays(path)
    native = feeder.cluster_events_from_arrays(arrays)
    python = _python_cluster_events(MACHINE_EVENTS)

    assert len(native) == len(python) == 5
    for (nts, nev), (pts, pev) in zip(native, python):
        assert nts == pts
        assert type(nev) is type(pev)
        if isinstance(nev, CreateNodeRequest):
            assert nev.node.metadata.name == pev.node.metadata.name
            assert nev.node.status.capacity.cpu == pev.node.status.capacity.cpu
            assert nev.node.status.capacity.ram == pev.node.status.capacity.ram
        else:
            assert isinstance(nev, RemoveNodeRequest)
            assert nev.node_name == pev.node_name


def test_duplicate_task_id_raises(tmp_path):
    inst = _write(tmp_path, "i.csv", WORKLOAD_INSTANCES)
    task = _write(tmp_path, "t.csv", "1,2,3,64,1,T,50,0.5\n1,2,3,64,1,T,50,0.5\n")
    with pytest.raises(ValueError, match="duplicated task id: 64"):
        feeder.load_workload_arrays(inst, task)


def test_add_without_resources_raises(tmp_path):
    path = _write(tmp_path, "m.csv", "10,1,add,,,\n")
    with pytest.raises(ValueError, match="lacks cpu/memory"):
        feeder.load_cluster_arrays(path)


def test_unknown_machine_event_raises(tmp_path):
    path = _write(tmp_path, "m.csv", "10,1,add,,64,0.5\n20,1,frobnicate,,,\n")
    with pytest.raises(ValueError, match="Unsupported operation"):
        feeder.load_cluster_arrays(path)


def test_native_matches_python_on_random_trace(tmp_path):
    """Fuzz: a few thousand random rows with every failure mode mixed in."""
    rng = np.random.default_rng(7)
    n_tasks, n_inst = 200, 4000
    task_lines = []
    for tid in range(n_tasks):
        if rng.random() < 0.1:
            cpu, mem = "", ""
        else:
            cpu, mem = str(rng.integers(10, 640)), f"{rng.random():.6f}"
        task_lines.append(f"1,2,{rng.integers(1, 50)},{tid},1,Terminated,{cpu},{mem}")
    inst_lines = []
    for _ in range(n_inst):
        start = rng.integers(-10, 5000)
        end = start + rng.integers(-5, 500)
        tid = rng.integers(0, int(n_tasks * 1.1))  # some unknown tasks
        s = "" if rng.random() < 0.05 else str(start)
        e = "" if rng.random() < 0.05 else str(end)
        t = "" if rng.random() < 0.05 else str(tid)
        j = "" if rng.random() < 0.05 else str(rng.integers(1, 50))
        inst_lines.append(f"{s},{e},{j},{t},1,Terminated,1,1")
    inst_text = "\n".join(inst_lines) + "\n"
    task_text = "\n".join(task_lines) + "\n"

    inst = _write(tmp_path, "bi.csv", inst_text)
    task = _write(tmp_path, "bt.csv", task_text)

    arrays = feeder.load_workload_arrays(inst, task)
    native = feeder.workload_events_from_arrays(arrays)
    python = _python_workload_events(inst_text, task_text)

    assert len(native) == len(python)
    for (nts, nev), (pts, pev) in zip(native, python):
        assert nts == pts
        assert nev.pod.metadata.name == pev.pod.metadata.name
        assert nev.pod.spec.resources.requests.cpu == pev.pod.spec.resources.requests.cpu
        assert nev.pod.spec.resources.requests.ram == pev.pod.spec.resources.requests.ram
        assert nev.pod.spec.running_duration == pev.pod.spec.running_duration


def test_time_slab_iteration(tmp_path):
    inst = _write(tmp_path, "bi.csv", WORKLOAD_INSTANCES)
    task = _write(tmp_path, "bt.csv", WORKLOAD_TASKS)
    arrays = feeder.load_workload_arrays(inst, task)

    slabs = feeder.iter_time_slabs(arrays, slab_seconds=100.0)
    # Slabs cover every event exactly once, in order.
    covered = []
    for t0, t1, idx in slabs:
        chunk = arrays.start_ts[idx]
        assert ((chunk >= t0) & (chunk < t1)).all()
        covered.extend(chunk.tolist())
    assert covered == arrays.start_ts.tolist()


def test_workload_segment_reader_matches_whole_fill(tmp_path):
    """Segment-at-a-time iteration (the streaming pipeline's trace-side
    seam): WorkloadSegmentReader pulls bounded row ranges of the natively
    parsed + sorted workload, and concatenating every segment must
    reproduce the whole-trace fill bit for bit — same sort, same filters,
    only the Python-side working set changes. The pure-Python oracle
    iterator (iter_workload_segments) must yield the identical stream."""
    import numpy as np

    inst = _write(tmp_path, "bi.csv", WORKLOAD_INSTANCES)
    task = _write(tmp_path, "bt.csv", WORKLOAD_TASKS)
    whole = feeder.load_workload_arrays(inst, task)

    with feeder.WorkloadSegmentReader(inst, task) as reader:
        assert len(reader) == len(whole.start_ts) == 4
        # Odd segment size: the final segment is a ragged remainder.
        native_segs = list(reader.iter_segments(rows_per_segment=3))
        # Out-of-range reads clamp (never over-read the native buffers).
        tail = reader.read(3, 100)
        assert len(tail.start_ts) == 1
        assert reader.read(4, 5).start_ts.size == 0
    oracle_segs = list(feeder.iter_workload_segments(whole, 3))

    assert [lo for lo, _ in native_segs] == [lo for lo, _ in oracle_segs]
    for (_, n_seg), (_, o_seg) in zip(native_segs, oracle_segs):
        for field in (
            "start_ts", "cpu_millicores", "ram_bytes", "duration",
            "job_id", "task_id", "pod_no",
        ):
            np.testing.assert_array_equal(
                getattr(n_seg, field), getattr(o_seg, field), err_msg=field
            )
    cat = np.concatenate([s.start_ts for _, s in native_segs])
    np.testing.assert_array_equal(cat, whole.start_ts)


def test_compile_from_arrays_matches_event_compile(tmp_path):
    """Dense-array fast path == compile_cluster_trace over the event objects."""
    from kubernetriks_tpu.batched.trace_compile import (
        compile_cluster_trace,
        compile_from_arrays,
    )
    from kubernetriks_tpu.test_util import default_test_simulation_config

    inst = _write(tmp_path, "bi.csv", WORKLOAD_INSTANCES)
    task = _write(tmp_path, "bt.csv", WORKLOAD_TASKS)
    machines = _write(tmp_path, "me.csv", MACHINE_EVENTS)

    w_arrays = feeder.load_workload_arrays(inst, task)
    c_arrays = feeder.load_cluster_arrays(machines)
    config = default_test_simulation_config()

    fast = compile_from_arrays(c_arrays, w_arrays, config)
    slow = compile_cluster_trace(
        feeder.cluster_events_from_arrays(c_arrays),
        feeder.workload_events_from_arrays(w_arrays),
        config,
    )

    np.testing.assert_array_equal(fast.ev_time, slow.ev_time)
    np.testing.assert_array_equal(fast.ev_kind, slow.ev_kind)
    np.testing.assert_array_equal(fast.ev_slot, slow.ev_slot)
    np.testing.assert_array_equal(fast.node_cap_cpu, slow.node_cap_cpu)
    np.testing.assert_array_equal(fast.node_cap_ram, slow.node_cap_ram)
    np.testing.assert_array_equal(fast.pod_req_cpu, slow.pod_req_cpu)
    np.testing.assert_array_equal(fast.pod_req_ram, slow.pod_req_ram)
    np.testing.assert_array_equal(fast.pod_duration, slow.pod_duration)
    assert fast.node_names == slow.node_names
    assert fast.pod_names == slow.pod_names


def test_batched_sim_runs_from_native_arrays(tmp_path):
    """End to end: native feeder -> compile_from_arrays -> BatchedSimulation."""
    from kubernetriks_tpu.batched.engine import BatchedSimulation
    from kubernetriks_tpu.batched.trace_compile import compile_from_arrays
    from kubernetriks_tpu.test_util import default_test_simulation_config

    # One 64-core node, two pods that fit.
    machines = _write(tmp_path, "me.csv", "1,1,add,,64,0.5\n")
    task = _write(tmp_path, "bt.csv", "100,200,1,10,2,Terminated,50,0.015625\n")
    inst = _write(
        tmp_path, "bi.csv",
        "100,150,1,10,1,Terminated,1,2\n200,260,1,10,2,Terminated,2,2\n",
    )
    config = default_test_simulation_config()
    compiled = compile_from_arrays(
        feeder.load_cluster_arrays(machines),
        feeder.load_workload_arrays(inst, task),
        config,
    )
    sim = BatchedSimulation(config, [compiled] * 2)
    sim.run_to_completion()
    counters = sim.metrics_summary()["counters"]
    assert counters["pods_succeeded"] == 2 * 2
    assert counters["processed_nodes"] == 1 * 2


def test_same_tick_create_remove_with_asymmetric_shifts(tmp_path):
    """A same-timestamp add+softerror pair must keep create-before-remove
    ordering even when shift_create_node > shift_remove_node (regression:
    the remove used to sort first, crashing one compiler and silently
    diverging in the other)."""
    from kubernetriks_tpu.batched.state import EV_CREATE_NODE, EV_REMOVE_NODE
    from kubernetriks_tpu.batched.trace_compile import (
        compile_cluster_trace,
        compile_from_arrays,
    )
    from kubernetriks_tpu.test_util import default_test_simulation_config

    machines = _write(
        tmp_path, "me.csv", "100,1,add,,64,0.5\n100,1,softerror,,,\n"
    )
    inst = _write(tmp_path, "bi.csv", "100,150,1,10,1,Terminated,1,1\n")
    task = _write(tmp_path, "bt.csv", "1,2,1,10,1,Terminated,50,0.015625\n")

    config = default_test_simulation_config()
    # Make the create shift strictly larger than the remove shift.
    config.ps_to_sched_network_delay = 1.0
    config.as_to_node_network_delay = 0.0

    c_arrays = feeder.load_cluster_arrays(machines)
    w_arrays = feeder.load_workload_arrays(inst, task)
    fast = compile_from_arrays(c_arrays, w_arrays, config)
    slow = compile_cluster_trace(
        feeder.cluster_events_from_arrays(c_arrays),
        feeder.workload_events_from_arrays(w_arrays),
        config,
    )
    for compiled in (fast, slow):
        kinds = list(compiled.ev_kind)
        assert kinds.index(EV_CREATE_NODE) < kinds.index(EV_REMOVE_NODE)
    np.testing.assert_array_equal(fast.ev_time, slow.ev_time)
    np.testing.assert_array_equal(fast.ev_kind, slow.ev_kind)
    np.testing.assert_array_equal(fast.ev_slot, slow.ev_slot)


def test_native_rejects_malformed_required_fields(tmp_path):
    """Field-validation parity: the native parser must reject the
    same malformed rows the Python parser raises on, even for columns the
    simulation never reads."""
    import pytest

    from kubernetriks_tpu.trace import feeder

    if not feeder.native_available():
        pytest.skip("no native toolchain")

    tasks = tmp_path / "batch_task.csv"
    instances = tmp_path / "batch_instance.csv"

    # Garbage in batch_task.number_of_instances (field 4).
    tasks.write_text("10,100,1,7,garbage,Terminated,100,0.5\n")
    instances.write_text("10,100,1,7,0,Terminated,0,1\n")
    with pytest.raises(ValueError, match="number_of_instances"):
        feeder.load_workload_arrays(str(instances), str(tasks))

    # Garbage in batch_instance.sequence_number (field 6).
    tasks.write_text("10,100,1,7,1,Terminated,100,0.5\n")
    instances.write_text("10,100,1,7,0,Terminated,oops,1\n")
    with pytest.raises(ValueError, match="sequence_number"):
        feeder.load_workload_arrays(str(instances), str(tasks))


def test_native_rejects_malformed_machine_id(tmp_path):
    import pytest

    from kubernetriks_tpu.trace import feeder

    if not feeder.native_available():
        pytest.skip("no native toolchain")
    tasks = tmp_path / "batch_task.csv"
    instances = tmp_path / "batch_instance.csv"
    tasks.write_text("10,100,1,7,1,Terminated,100,0.5\n")
    instances.write_text("10,100,1,7,garbage,Terminated,0,1\n")
    with pytest.raises(ValueError, match="machine_id"):
        feeder.load_workload_arrays(str(instances), str(tasks))


# --- opt-in real-trace tier -------------------------------------------------
# Mirrors the reference's #[ignore]d real-CSV tests
# (/root/reference/src/trace/alibaba_cluster_trace_v2017/workload.rs:206-219):
# with KUBERNETRIKS_ALIBABA_DIR pointing at a directory holding the real
# Alibaba v2017 machine_events.csv / batch_task.csv / batch_instance.csv,
# the C++ feeder and the Python oracle must agree row for row at full scale.

import os

from kubernetriks_tpu.flags import flag_str

_REAL_DIR = flag_str("KUBERNETRIKS_ALIBABA_DIR")


def _real_path(name):
    path = os.path.join(_REAL_DIR, name)
    assert os.path.exists(path), f"KUBERNETRIKS_ALIBABA_DIR lacks {name}"
    return path


@pytest.mark.skipif(
    not _REAL_DIR, reason="set KUBERNETRIKS_ALIBABA_DIR to the real v2017 CSVs"
)
def test_real_alibaba_workload_native_matches_python():
    inst = _real_path("batch_instance.csv")
    task = _real_path("batch_task.csv")

    arrays = feeder.load_workload_arrays(inst, task)
    python = AlibabaWorkloadTraceV2017.from_files(inst, task).convert_to_simulator_events()

    n = len(arrays.start_ts)
    assert len(python) == n > 0
    p_ts = np.fromiter((ts for ts, _ in python), np.float64, count=n)
    p_cpu = np.fromiter(
        (ev.pod.spec.resources.requests.cpu for _, ev in python), np.int64, count=n
    )
    p_ram = np.fromiter(
        (ev.pod.spec.resources.requests.ram for _, ev in python), np.int64, count=n
    )
    p_dur = np.fromiter(
        (ev.pod.spec.running_duration for _, ev in python), np.float64, count=n
    )
    np.testing.assert_array_equal(arrays.start_ts, p_ts)
    np.testing.assert_array_equal(arrays.cpu_millicores.astype(np.int64), p_cpu)
    np.testing.assert_array_equal(arrays.ram_bytes.astype(np.int64), p_ram)
    np.testing.assert_array_equal(arrays.duration, p_dur)
    # Names spot-check across the span (full string compare of 4M rows is
    # pointless once the numeric join keys match).
    for i in np.linspace(0, n - 1, 997).astype(int):
        assert arrays.pod_name(int(i)) == python[int(i)][1].pod.metadata.name


@pytest.mark.skipif(
    not _REAL_DIR, reason="set KUBERNETRIKS_ALIBABA_DIR to the real v2017 CSVs"
)
def test_real_alibaba_cluster_native_matches_python():
    machines = _real_path("machine_events.csv")

    arrays = feeder.load_cluster_arrays(machines)
    native = feeder.cluster_events_from_arrays(arrays)
    python = _python_cluster_events(open(machines).read())

    assert len(native) == len(python) > 0
    for (nts, nev), (pts, pev) in zip(native, python):
        assert nts == pts
        assert type(nev) is type(pev)
        if isinstance(nev, CreateNodeRequest):
            assert nev.node.metadata.name == pev.node.metadata.name
            assert nev.node.status.capacity.cpu == pev.node.status.capacity.cpu
            assert nev.node.status.capacity.ram == pev.node.status.capacity.ram
        else:
            assert nev.node_name == pev.node_name


# ---------------------------------------------------------------------------
# Real-format CSV quirks (CRLF endings, RFC4180-quoted fields, optional
# header): the native feeder's SplitCsv/IsHeaderRow must match the Python
# oracle's csv-module + _data_rows behavior on the same quirked files.
# ---------------------------------------------------------------------------

from kubernetriks_tpu.test_util import (
    ALIBABA_INSTANCE_HEADER as INSTANCE_HEADER,
    ALIBABA_TASK_HEADER as TASK_HEADER,
    ALIBABA_MACHINE_HEADER as MACHINE_HEADER,
    quirkify_csv as _quirkify,
)


def _assert_workload_matches(native, python):
    assert len(native) == len(python)
    for (nts, nev), (pts, pev) in zip(native, python):
        assert nts == pts
        assert nev.pod.metadata.name == pev.pod.metadata.name
        assert nev.pod.spec.resources.requests.cpu == pev.pod.spec.resources.requests.cpu
        assert nev.pod.spec.resources.requests.ram == pev.pod.spec.resources.requests.ram
        assert nev.pod.spec.running_duration == pev.pod.spec.running_duration


QUIRK_CASES = [
    dict(crlf=True),
    dict(quote=True),
    dict(crlf=True, quote=True),
    dict(header=True),
    dict(header=True, crlf=True, quote=True),
]


@pytest.mark.parametrize("quirk", QUIRK_CASES, ids=str)
def test_workload_csv_quirks_native_matches_python(tmp_path, quirk):
    kw = dict(quirk)
    use_header = kw.pop("header", False)
    inst_text = _quirkify(
        WORKLOAD_INSTANCES, header=INSTANCE_HEADER if use_header else None, **kw
    )
    task_text = _quirkify(
        WORKLOAD_TASKS, header=TASK_HEADER if use_header else None, **kw
    )
    inst = _write(tmp_path, "bi.csv", inst_text)
    task = _write(tmp_path, "bt.csv", task_text)

    native = feeder.workload_events_from_arrays(
        feeder.load_workload_arrays(inst, task)
    )
    python = _python_workload_events(inst_text, task_text)
    assert len(native) == 4  # quirks change NOTHING about the join/filter
    _assert_workload_matches(native, python)


@pytest.mark.parametrize("quirk", QUIRK_CASES, ids=str)
def test_cluster_csv_quirks_native_matches_python(tmp_path, quirk):
    kw = dict(quirk)
    use_header = kw.pop("header", False)
    text = _quirkify(
        MACHINE_EVENTS, header=MACHINE_HEADER if use_header else None, **kw
    )
    path = _write(tmp_path, "me.csv", text)

    native = feeder.cluster_events_from_arrays(feeder.load_cluster_arrays(path))
    python = _python_cluster_events(text)
    assert len(native) == len(python) == 5
    for (nts, nev), (pts, pev) in zip(native, python):
        assert nts == pts
        assert type(nev) is type(pev)


def test_native_quoted_field_with_embedded_comma(tmp_path):
    """RFC4180: commas inside quotes are field content ("" is a literal
    quote) — the machine event_detail free-text column is where real dumps
    use both."""
    text = '10,1,add,,64,0.69\n50,1,softerror,"links, ""b"" broken",,\n'
    path = _write(tmp_path, "me.csv", text)
    native = feeder.cluster_events_from_arrays(feeder.load_cluster_arrays(path))
    python = _python_cluster_events(text)
    assert len(native) == len(python) == 2
    assert isinstance(native[1][1], RemoveNodeRequest)


def test_native_first_row_empty_leading_field_is_data(tmp_path):
    """An empty first field on row one is DATA (batch_instance's optional
    start_ts), not a header — the row must flow through the join/filter
    exactly as the Python oracle drops it (no start -> filtered), without
    desyncing the rows behind it."""
    inst_text = (
        ",41618,1,10,299,Interrupted,1,2\n"       # empty start: data, filtered
        "41562,41618,1,10,299,Terminated,1,2\n"   # survives
    )
    task_text = "100,200,1,10,2,Terminated,50,0.015625\n"
    inst = _write(tmp_path, "bi.csv", inst_text)
    task = _write(tmp_path, "bt.csv", task_text)
    native = feeder.workload_events_from_arrays(
        feeder.load_workload_arrays(inst, task)
    )
    python = _python_workload_events(inst_text, task_text)
    assert len(native) == 1
    _assert_workload_matches(native, python)


def test_native_non_ascii_digit_first_row_is_header_on_both_sides(tmp_path):
    """The header rule's integer test is the ASCII subset on BOTH sides: a
    first row leading with full-width digits (which Python's bare int()
    would happily parse, but a byte-level C scan cannot) is a header for
    the Python oracle AND the native feeder, so the two parses never desync
    by a row. Pins the _ASCII_INT_RE / LooksLikePythonInt equivalence at
    its one divergence-prone edge."""
    inst_text = (
        "４１５６２,41618,1,10,299,Terminated,1,2\n"
        "41562,41618,1,10,299,Terminated,1,2\n"   # survives on both sides
    )
    task_text = "100,200,1,10,2,Terminated,50,0.015625\n"
    inst = _write(tmp_path, "bi.csv", inst_text)
    task = _write(tmp_path, "bt.csv", task_text)
    native = feeder.workload_events_from_arrays(
        feeder.load_workload_arrays(inst, task)
    )
    python = _python_workload_events(inst_text, task_text)
    assert len(native) == len(python) == 1
    _assert_workload_matches(native, python)
