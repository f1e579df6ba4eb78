"""Interleaved A/B of the 128-aligned pod axis at the headline shape
(1024 x 256-node clusters): aligned (P -> 2048) vs exact-width (P=2026)
builds alternate chunks in ONE process.

Usage: python scripts/profile_align_ab.py [rounds]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build():
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )

    config = SimulationConfig.from_yaml(
        "sim_name: bench\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(256, cpu=64000, ram=128 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=2.0, horizon=1000.0, seed=3, cpu=4000,
        ram=8 * 1024**3, duration_range=(30.0, 120.0),
    )
    return build_batched_from_traces(
        config, cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=1024, max_pods_per_cycle=64,
    )


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    os.environ["KTPU_ALIGN_PODS"] = "1"
    simA = build()
    os.environ["KTPU_ALIGN_PODS"] = "0"
    simB = build()
    print(f"A P={simA.n_pods} B P={simB.n_pods}", flush=True)

    for sim in (simA, simB):
        sim.step_until_time(190.0)
        _ = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())

    resA, resB = [], []
    end = 390.0
    for _ in range(rounds):
        for sim, res in ((simA, resA), (simB, resB)):
            before = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())
            t0 = time.perf_counter()
            sim.step_until_time(end)
            d = int(np.asarray(sim.state.metrics.scheduling_decisions).sum()) - before
            res.append(d / (time.perf_counter() - t0))
        end += 200.0
    print("A (aligned) Mdec/s:", " ".join(f"{x/1e6:.2f}" for x in resA), flush=True)
    print("B (exact)   Mdec/s:", " ".join(f"{x/1e6:.2f}" for x in resB), flush=True)


if __name__ == "__main__":
    main()
