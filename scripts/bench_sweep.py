"""Parametrized perf sweep over (n_clusters, n_nodes, pallas on/off).

Usage: python scripts/bench_sweep.py [C:N:pallas ...]
Each spec runs the bench.py scenario scaled to that shape and prints one JSON
line per spec with decisions/s.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def run_spec(n_clusters: int, n_nodes: int, use_pallas):
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )

    config = SimulationConfig.from_yaml(
        "sim_name: bench\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(n_nodes, cpu=64000, ram=128 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=2.0,
        horizon=1000.0,
        seed=3,
        cpu=4000,
        ram=8 * 1024**3,
        duration_range=(30.0, 120.0),
    )
    sim = build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=n_clusters,
        max_pods_per_cycle=64,
        use_pallas=use_pallas,
    )

    def decisions_now() -> int:
        # Host fetch = real sync (see bench.py).
        import numpy as np

        return int(np.asarray(sim.state.metrics.scheduling_decisions).sum())

    sim.step_until_time(190.0)
    decisions_before = decisions_now()

    t0 = time.perf_counter()
    end = 390.0
    while end <= 1200.0:
        sim.step_until_time(end)
        end += 200.0
    decisions = decisions_now() - decisions_before
    elapsed = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "C": n_clusters,
                "N": n_nodes,
                "pallas": sim.use_pallas,
                "decisions_per_s": round(decisions / elapsed),
                "elapsed_s": round(elapsed, 2),
                "decisions": int(decisions),
            }
        ),
        flush=True,
    )


def main() -> None:
    for spec in sys.argv[1:]:
        c, n, p = spec.split(":")
        pallas = {"auto": None, "on": True, "off": False}[p]
        run_spec(int(c), int(n), pallas)


if __name__ == "__main__":
    main()
