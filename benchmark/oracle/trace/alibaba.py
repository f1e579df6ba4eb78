"""Plain parser of Alibaba cluster-trace-v2017 CSVs for the benchmark's
reference: straightforward Python over the `csv` module, written for the
benchmark and importing nothing of the program (whose own parsers are
kubernetriks_tpu/trace/alibaba.py and native/trace_feeder.cc).

It follows the reference simulator's workload.rs:48-147 and cluster.rs:55-105:
`batch_instance` joined to `batch_task` on the task id, the validity filter,
one pod an instance named `{job}_{task}_{n}` with n counting the kept
instances in file order, cpus in santicores x 10 = millicores, normalized
memory x 128 GiB truncated to bytes, duration end - start, pods stably sorted
by start; one node a machine `add` row, named `alibaba_node_{machine id}`,
cores x 1000, normalized memory x 128 GiB. Departures, each because the
deployment has no use for it: the result is the benchmark's neutral records
(benchmark/traffic_gen.py), not event objects; a machine row other than
`add` is an error (the replayed trace is the reference's modified, add-only
one; cluster.rs turns soft and hard errors into node removals).

Tolerated in the files, as the circulating dumps have them: CRLF line ends,
quoted fields, and one optional header line (the first row is a header iff
its first field is non-empty and not an integer: every data row starts with a
timestamp or with nothing).
"""

from __future__ import annotations

import csv
from typing import Dict, Iterator, List, Optional, Tuple

NORMALIZED_MEMORY_BASE_BYTES = 128 * 1024**3
MILLICORES_PER_SANTICORE = 10
MILLICORES_PER_CORE = 1000


def _is_integer(text: str) -> bool:
    """An ASCII integer literal as Python's int() reads one (a sign, digits,
    single underscores between digits)."""
    if not text.isascii():
        return False
    try:
        int(text)
    except ValueError:
        return False
    return True


def data_rows(path: str) -> Iterator[List[str]]:
    with open(path, newline="") as fh:
        first = True
        for row in csv.reader(fh):
            if not row:
                continue
            if first:
                first = False
                head = row[0].strip(" \t\f\v")
                if head and not _is_integer(head):
                    continue
            yield row


def _optional(text: str, kind):
    return kind(text) if text != "" else None


def read_tasks(path: str) -> Dict[int, Tuple[Optional[int], Optional[float]]]:
    """task id -> (santicores an instance, normalized memory an instance)."""
    tasks: Dict[int, Tuple[Optional[int], Optional[float]]] = {}
    for row in data_rows(path):
        task_id = int(row[3])
        if task_id in tasks:
            raise ValueError(f"duplicated task id: {task_id}")
        cpus = _optional(row[6], int) if len(row) > 6 else None
        memory = _optional(row[7], float) if len(row) > 7 else None
        tasks[task_id] = (cpus, memory)
    return tasks


def workload_records(batch_instance_path: str, batch_task_path: str) -> Tuple[List[Tuple], Dict[str, int]]:
    """(`create_pod` records sorted by time, {"rows", "dropped"})."""
    tasks = read_tasks(batch_task_path)
    records, rows = [], 0
    for row in data_rows(batch_instance_path):
        rows += 1
        start, end = _optional(row[0], int), _optional(row[1], int)
        job_id, task_id = _optional(row[2], int), _optional(row[3], int)
        if start is None or end is None or task_id is None or task_id not in tasks:
            continue
        cpus, memory = tasks[task_id]
        if cpus is None or memory is None:
            continue
        if start <= 0 or end <= 0 or start >= end:
            continue
        name = f"{job_id}_{task_id}_{len(records)}"
        records.append(
            (
                float(start), "create_pod", name, cpus * MILLICORES_PER_SANTICORE,
                int(memory * NORMALIZED_MEMORY_BASE_BYTES), float(end - start),
            )
        )
    kept = len(records)
    records.sort(key=lambda rec: rec[0])  # stable: file order at equal starts
    return records, {"rows": rows, "dropped": rows - kept}


def cluster_records(machine_events_path: str) -> List[Tuple]:
    records = []
    for row in data_rows(machine_events_path):
        timestamp, machine_id, kind = int(row[0]), int(row[1]), row[2]
        if kind != "add":
            raise ValueError(
                f"machine {machine_id}: event {kind!r}; the benchmark's reference parses the add-only trace"
            )
        cores = _optional(row[4], int) if len(row) > 4 else None
        memory = _optional(row[5], float) if len(row) > 5 else None
        if cores is None or memory is None:
            raise ValueError(f"machine {machine_id}: an add row without cpus or memory")
        records.append(
            (
                float(timestamp), "create_node", f"alibaba_node_{machine_id}",
                cores * MILLICORES_PER_CORE, int(memory * NORMALIZED_MEMORY_BASE_BYTES),
            )
        )
    records.sort(key=lambda rec: rec[0])
    return records
