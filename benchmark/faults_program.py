"""What the `batch_jobs_faults` driver takes from the program, beside
benchmark/program.py (which no later PR edits): whether the program can be
given a fault schedule that is already sampled and puts a recovered node back
where name order has it, its object types with the node removal among them,
the pool that compiles every cluster's trace with its schedule
(program.TracePool compiles traffic_gen's fault-free records by name), and
the three fault counters."""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace
from typing import Dict, List, Optional

from benchmark import program

FAULT_COUNTERS = ("node_crashes", "node_recoveries", "pod_interruptions")  # leaves of the metrics state, by name


def program_api() -> SimpleNamespace:
    from kubernetriks_tpu.core.events import RemoveNodeRequest

    api = program.program_api()
    api.RemoveNodeRequest = RemoveNodeRequest
    return api


def why_not() -> Optional[str]:
    """None where the program can run a node-fault cell, else why not. Host
    Python only (the trace compiler is numpy): run before JAX reaches for the
    chip. Two things are asked. The build takes crash and recovery events it
    did not sample (no `fault_injection` block in the config), switching the
    node-fault channel on from the traces alone. And a node that recovers
    under its own name returns to its own slot: on identical nodes, where
    ties decide nearly every placement, a recovery on a fresh, later slot
    breaks them another way than the reference's sorted-name walk."""
    import inspect

    try:
        from kubernetriks_tpu import chaos
        from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
    except ImportError as e:
        return f"the program has no chaos engine ({e})"
    if "node_fault_events" not in inspect.signature(chaos.make_fault_params).parameters:
        return "the build cannot be given a fault schedule that is already sampled (only `config.fault_injection` switches node faults on)"
    api = program_api()
    config = api.SimulationConfig.from_yaml(
        "sim_name: probe\nseed: 1\nscheduling_cycle_interval: 10.0\n"
        + "".join(
            f"{hop}_network_delay: 0.0\n"
            for hop in ("as_to_ps", "ps_to_sched", "sched_to_as", "as_to_node", "as_to_ca", "as_to_hpa")
        )
    )
    node = lambda name: api.Node.new(name, 1000, 1 << 30)  # noqa: E731
    trace = compile_cluster_trace(
        [
            (0.0, api.CreateNodeRequest(node=node("n0"))),
            (0.0, api.CreateNodeRequest(node=node("n1"))),
            (15.0, api.RemoveNodeRequest(node_name="n0", crashed=True, downtime_s=20.0)),
            (35.0, api.CreateNodeRequest(node=node("n0"), recovered=True)),
        ],
        [],
        config,
    )
    if trace.n_nodes != 2:
        return f"a recovered node comes back on a fresh slot ({trace.n_nodes} slots for 2 nodes)"
    return None


def _compile_chunk(job):
    """Pool worker (program._compile_chunk over a cluster's own node events):
    runs in a child that never needs the chip. Every trace is padded with
    sentinel events to the mix's `event_capacity`, and its crashes' downtime
    spans with zeros to `crash_capacity`: how many faults a cluster draws
    moves with the seed, and the shapes of the slab and of its downtime
    table, which every window program is compiled for, must not (a new seed
    would compile again)."""
    config_text, config, traffic, seed, clusters = job
    import numpy as np

    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace

    from benchmark import faults_gen, traffic_gen

    api = program_api()
    sim_config = api.SimulationConfig.from_yaml(config_text)
    capacity = int(traffic["faults"]["event_capacity"])
    crash_capacity = int(traffic["faults"]["crash_capacity"])
    out = []
    for c in clusters:
        trace = compile_cluster_trace(
            faults_gen.to_events(faults_gen.cluster_records(config, seed, c), api, flagged=True),
            traffic_gen.to_events(traffic_gen.workload_records(traffic, seed, c), api),
            sim_config,
        )
        spans = np.zeros(0) if trace.crash_downtime_s is None else trace.crash_downtime_s
        pad = capacity - trace.n_events
        if pad < 0 or len(spans) > crash_capacity:
            raise ValueError(
                f"faults_program: cluster {c} holds {trace.n_events} events and {len(spans)} crashes, over the "
                f"mix's event_capacity {capacity} or crash_capacity {crash_capacity}"
            )
        out.append(
            dataclasses.replace(
                trace,
                ev_time=np.concatenate([trace.ev_time, np.full(pad, np.inf)]),
                ev_kind=np.concatenate([trace.ev_kind, np.zeros(pad, np.int32)]),
                ev_slot=np.concatenate([trace.ev_slot, np.zeros(pad, np.int32)]),
                crash_downtime_s=np.concatenate([spans, np.zeros(crash_capacity - len(spans))]),
            )
        )
    return out


class FaultsTracePool(program.TracePool):
    """program.TracePool with this module's worker: the same chunking, the
    same spawned pool held to the CPU, every worker ended when `result()` or
    `cancel()` returns. Its jobs carry the whole configuration (the fault
    processes are beside the deployment, not in it)."""

    def start(self) -> "FaultsTracePool":
        import concurrent.futures
        import multiprocessing

        if self.workers > 1:
            self.pool = concurrent.futures.ProcessPoolExecutor(
                max_workers=self.workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=program._hold_to_cpu,
            )
            self.futures = [self.pool.submit(_compile_chunk, job) for job in self.jobs]
        return self

    def result(self) -> List:
        if self.pool is None:
            return [trace for job in self.jobs for trace in _compile_chunk(job)]
        return super().result()


def cluster_counters(sim, cluster: int) -> Dict[str, int]:
    """program.cluster_counters with the three fault counters."""
    m = sim.state.metrics
    out = program.cluster_counters(sim, cluster)
    out.update({name: int(getattr(m, name)[cluster]) for name in FAULT_COUNTERS})
    return out


def fault_counters() -> Dict[str, int]:
    """The batch's fault counters as the program's last `metrics_summary()`
    left them on its recorder ({} where it publishes none)."""
    from benchmark import program_spans

    found = program_spans._program()
    if found is None:
        return {}
    counters = found[0].counters
    return {k: int(counters[k]) for k in FAULT_COUNTERS if k in counters}
