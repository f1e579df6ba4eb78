"""Seeded Alibaba cluster-trace-v2017 CSVs for the `alibaba1313` deployment.

The real trace may not be shipped, so the benchmark writes files in its column
format (machine_events: timestamp, machine id, event type, event detail, cpus,
normalized memory; batch_task: create, end, job id, task id, instances,
status, cpus in santicores, normalized memory; batch_instance: start, end,
job id, task id, machine id, status, sequence number, total sequence number)
from the sizes benchmark/configs/alibaba1313.json states. The program reads
the files through its normal path (native feeder) and the reference through
benchmark/oracle/trace/alibaba.py; neither sees this module's values any
other way. Nothing here is imported from the program.

Every count is fixed by the parameters, and the seed only draws values and
places: the number of tasks, of instances (1, 2 and 3 an instance apportioned
in equal thirds), of heavy tasks and of each kind of row the validity filter
drops is the same for every seed, so the engine's shapes and a job's work do
not move with the seed (PERF.md, finding 1).
"""

from __future__ import annotations

import os
import random
from typing import Dict, List, Tuple

from benchmark.traffic_gen import _apportion, derive_seed

NORMALIZED_MEMORY_BASE_MIB = 128 * 1024  # normalized memory 1.0 = 128 GiB
JOB_ID_BASE, TASK_ID_BASE = 1_000_000, 2_000_000
TASKS_PER_JOB = 4

FILES = ("machine_events.csv", "batch_task.csv", "batch_instance.csv")


def machine_rows(deployment: Dict) -> List[Tuple]:
    """One `add` row a machine at t = 0, ids 1..n as the trace counts them:
    the reference's modified trace keeps only the adds."""
    return [
        (0, m, "add", "", int(deployment["machine_cores"]), float(deployment["machine_normalized_memory"]))
        for m in range(1, int(deployment["machines"]) + 1)
    ]


def workload_rows(trace: Dict, machines: int, seed: int) -> Tuple[List[Tuple], List[Tuple]]:
    """(batch_task rows, batch_instance rows in file order)."""
    rng = random.Random(derive_seed(seed, "alibaba", "workload"))
    n = int(trace["tasks"])
    span = int(trace["span_s"])
    per_task = [int(k) for k in trace["instances_per_task"]]
    instances: List[int] = []
    for k, count in zip(per_task, _apportion([1.0] * len(per_task), n)):
        instances += [k] * count
    rng.shuffle(instances)
    heavy = [True] * int(round(float(trace["heavy_share"]) * n))
    heavy += [False] * (n - len(heavy))
    rng.shuffle(heavy)
    creates = sorted(rng.randint(1, span) for _ in range(n))
    cpu_lo, cpu_hi = trace["cpu_santicores"]
    heavy_lo, heavy_hi = trace["heavy_cpu_santicores"]
    mem_lo, mem_hi = trace["memory_mib"]
    dur_lo, dur_hi = trace["duration_s"]
    within = int(trace["instance_start_within_s"])

    task_rows, instance_rows = [], []
    for i, create in enumerate(creates):
        job, task = JOB_ID_BASE + i // TASKS_PER_JOB, TASK_ID_BASE + i
        cpus = rng.randint(heavy_lo, heavy_hi) if heavy[i] else rng.randint(cpu_lo, cpu_hi)
        memory = rng.randint(mem_lo, mem_hi) / NORMALIZED_MEMORY_BASE_MIB  # exact in binary
        duration = rng.randint(dur_lo, dur_hi)
        task_rows.append((create, create + duration, job, task, instances[i], "Terminated", cpus, repr(memory)))
        for seq in range(instances[i]):
            start = create + rng.randint(0, within)
            instance_rows.append(
                (start, start + duration, job, task, rng.randint(1, machines), "Terminated", seq, instances[i])
            )

    # Rows the validity filter must drop, a fixed count of each kind (the real
    # tables carry waiting and failed instances, and tasks without a plan).
    dropped = trace["dropped_rows"]
    next_task = TASK_ID_BASE + n
    for _ in range(int(dropped["task_without_plan"])):
        create = rng.randint(1, span)
        task_rows.append((create, create + 100, JOB_ID_BASE + n, next_task, 1, "Waiting", "", ""))
        instance_rows.append((create + 5, create + 105, JOB_ID_BASE + n, next_task, 1, "Terminated", 0, 1))
        next_task += 1
    for _ in range(int(dropped["instance_without_times"])):
        task = TASK_ID_BASE + rng.randrange(n)
        instance_rows.append(("", "", JOB_ID_BASE, task, "", "Waiting", 0, 1))
    for _ in range(int(dropped["instance_ending_before_start"])):
        task, start = TASK_ID_BASE + rng.randrange(n), rng.randint(1, span)
        instance_rows.append((start, start - rng.randint(0, 30), JOB_ID_BASE, task, 1, "Failed", 0, 1))
    for _ in range(int(dropped["instance_of_unknown_task"])):
        start = rng.randint(1, span)
        instance_rows.append((start, start + 100, JOB_ID_BASE, next_task + rng.randrange(1000), 1, "Terminated", 0, 1))
    # the tables come sorted by their first timestamp, rows without one first
    instance_rows.sort(key=lambda row: row[0] if row[0] != "" else 0)
    task_rows.sort(key=lambda row: row[0])
    return task_rows, instance_rows


def dropped_instance_rows(trace: Dict) -> int:
    return sum(int(v) for v in trace["dropped_rows"].values())


def valid_instances(trace: Dict) -> int:
    """Pods a job creates: the same for every seed."""
    per_task = [int(k) for k in trace["instances_per_task"]]
    counts = _apportion([1.0] * len(per_task), int(trace["tasks"]))
    return sum(k * c for k, c in zip(per_task, counts))


def write_trace(out_dir: str, deployment: Dict, trace: Dict, seed: int) -> Dict[str, str]:
    """The three CSVs under `out_dir`; {"machine_events", "batch_task",
    "batch_instance"} -> path."""
    os.makedirs(out_dir, exist_ok=True)
    tasks, instances = workload_rows(trace, int(deployment["machines"]), seed)
    tables = dict(zip(FILES, (machine_rows(deployment), tasks, instances)))
    paths = {}
    for name, rows in tables.items():
        path = os.path.join(out_dir, name)
        with open(path, "w") as fh:
            fh.writelines(",".join(str(x) for x in row) + "\n" for row in rows)
        paths[name[: -len(".csv")]] = path
    return paths
