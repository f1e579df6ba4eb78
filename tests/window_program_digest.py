"""Digests of the window programs the accepted benchmark cells' rehearsal
builds lower, debug locations stripped: the yardstick of "this PR did not
change what a cell compiles". Imports nothing newer than the PR that added
the cell (PR 50 for `sched1k-kubescore.montecarlo`, PR 47 for
`sched1k-pools.montecarlo`, PR 43 for
`sched1k-faults.montecarlo`, PR 41 for `sched1k-backlog.bursts`, PR 37 for
`sched1k-spread.montecarlo`, PR 33 for the six before it), so the same file
runs on an older checkout over the cells that checkout has:

    python tests/window_program_digest.py --write   # on the tree to pin

writes tests/data/window_program_digests.json;
tests/test_topology_spread.py::test_accepted_cells_lower_the_programs_they_lowered
compares a tree against it.
"""

import hashlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
DIGESTS = os.path.join(HERE, "data", "window_program_digests.json")
SEED = 7

CELLS = [
    "sched1k.montecarlo",
    "sched1k.montecarlo-x4",
    "sched1k-netdelay.montecarlo",
    "autoscaled.stream",
    "autoscaled.whatif",
    "alibaba1313.replay",
    "sched1k-spread.montecarlo",
    "sched1k-backlog.bursts",
    "sched1k.saturated",
    "sched1k-faults.montecarlo",
    "sched1k-pools.montecarlo",
    "sched1k-kubescore.montecarlo",
]

# `loc(...)` trailers and `#loc` lines: where in the source an op was traced.
_LOCATION = re.compile(r"\s*loc\([^\n]*\)$|^#loc[^\n]*$", re.M)


def lowered_window_program(sim) -> str:
    """The StableHLO of the engine's window program (step.run_windows over
    one window, with the statics the engine dispatches it with), debug
    locations stripped."""
    import jax.numpy as jnp

    from kubernetriks_tpu.batched import step

    lowered = step.run_windows.lower(
        sim.state,
        sim.slab,
        jnp.asarray([1], jnp.int32),
        sim.consts,
        collect_gauges=False,
        freeze_lanes=True,
        **sim._window_call_kwargs(),
    )
    return _LOCATION.sub("", lowered.as_text(debug_info=False))


def rehearsal_engine(cell_name: str):
    """The engine the cell's driver builds under its rehearsal overrides."""
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    from benchmark import deployment, program
    from benchmark.harness import Cell, load_json

    manifest = load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    rehearsal = load_json(os.path.join(CHECKOUT, "benchmark", "rehearsal", cell_name + ".json"))
    cell = Cell(manifest, cell_name, rehearsal)
    driver = cell.traffic["driver"]
    config_text = deployment.config_yaml(cell.config_name, cell.config["deployment"])
    if driver == "batch_jobs":
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from benchmark.drivers import batch_jobs

        mesh = None
        if cell.chips > 1:
            mesh = Mesh(np.array(jax.devices()[: cell.chips]), ("clusters",))
        compiled = batch_jobs.prepare(cell, SEED).result()
        return program.build_engine(
            config_text, compiled, resettable=True, mesh=mesh, **batch_jobs._engine_kwargs(cell)
        )
    if driver in (
        "batch_jobs_labelled", "batch_jobs_bursts", "batch_jobs_faults", "batch_jobs_pools",
        "batch_jobs_kubescore",
    ):
        import importlib

        from benchmark.drivers import batch_jobs

        compiled = importlib.import_module("benchmark.drivers." + driver).prepare(cell, SEED).result()
        return program.build_engine(
            config_text, compiled, resettable=True, **batch_jobs._engine_kwargs(cell)
        )
    if driver == "served_open_loop":
        from benchmark.drivers import served_open_loop

        _, _, cluster_events, workload_events = served_open_loop._base_workload(cell, SEED)
        horizon = max(float(h) for h in cell.traffic["queries"]["horizons_s"])
        fleet = served_open_loop._build_fleet(
            cell, config_text, cluster_events, workload_events, int(cell.traffic["lanes"]), horizon
        )
        return fleet.engine
    if driver == "trace_replay":
        from benchmark import replay_program
        from benchmark.drivers import trace_replay

        files = trace_replay.prepare(cell, SEED)
        try:
            n_clusters = int(cell.traffic["clusters_per_chip"]) * cell.chips
            return replay_program.build_engine(
                config_text, files.result(), n_clusters, **trace_replay._engine_kwargs(cell)
            )
        finally:
            files.cancel()
    raise ValueError(f"no rehearsal build for driver {driver!r}")


def digest(cell_name: str) -> str:
    sim = rehearsal_engine(cell_name)
    try:
        return hashlib.sha256(lowered_window_program(sim).encode()).hexdigest()
    finally:
        sim.close()


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    have = {w["name"] for w in json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))["workloads"]}
    digests = {cell: digest(cell) for cell in CELLS if cell in have}
    print(json.dumps(digests, indent=1))
    if "--write" in sys.argv:
        with open(DIGESTS, "w") as fh:
            json.dump(
                {
                    "what": "sha256 of each accepted cell's rehearsal window program (StableHLO of "
                    "step.run_windows, locations stripped); tests/window_program_digest.py --write",
                    "digests": digests,
                },
                fh,
                indent=1,
            )
            fh.write("\n")
