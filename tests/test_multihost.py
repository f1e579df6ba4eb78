"""Multi-host placement/readout helpers: single-process degenerate semantics
on the suite's 8-device virtual mesh, plus a REAL two-process
jax.distributed harness (test_two_process_cross_process_branches) that
executes the cross-process branches of put_global/to_host — gloo CPU
collectives standing in for DCN — and steps a BatchedSimulation SPMD on the
cross-process mesh."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubernetriks_tpu.parallel.multihost import (
    global_mesh,
    is_cross_process,
    put_global,
    to_host,
)


def test_initialize_from_env_is_noop_without_coordinator():
    """Unconditional initialize_from_env on a plain single-process run must
    return False instead of raising — checked in a fresh interpreter because
    jax.distributed.initialize only works before the backend starts."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        f"import sys; sys.path.insert(0, {root!r});\n"
        "from kubernetriks_tpu.parallel.multihost import initialize_from_env\n"
        "assert initialize_from_env() is False\n"
    )
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_COORD")}
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run([sys.executable, "-c", code], env=env, check=True, timeout=120)


def test_two_process_cross_process_branches():
    """Two jax.distributed CPU processes (4 virtual devices each, one
    8-device world): put_global assembles global arrays from per-process
    shards, to_host allgathers non-addressable arrays, and the engine steps
    on the cross-process mesh end to end (tests/multihost_worker.py)."""
    import os
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)), "multihost_worker.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(port)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-4000:]}"
        assert f"ROUNDTRIP_OK {i}" in out
        assert f"ENGINE_OK {i}" in out
        assert f"SLIDING_OK {i}" in out
    # Both processes computed identical global metrics, and the sliding
    # window grew/slid identically on both.
    for tag in ("ENGINE_OK", "SLIDING_OK"):
        l0 = [l for l in outs[0].splitlines() if l.startswith(tag)][0].split()[2:]
        l1 = [l for l in outs[1].splitlines() if l.startswith(tag)][0].split()[2:]
        assert l0 == l1, (tag, l0, l1)


def test_put_global_matches_device_put():
    mesh = Mesh(np.array(jax.devices()[:8]), ("clusters",))
    tree = {
        "a": jnp.arange(32, dtype=jnp.int32).reshape(8, 4),
        "b": jnp.ones((16, 2, 3), jnp.float32),
    }
    shardings = {
        "a": NamedSharding(mesh, PartitionSpec("clusters", None)),
        "b": NamedSharding(mesh, PartitionSpec("clusters", None, None)),
    }
    got = put_global(tree, shardings)
    want = jax.device_put(tree, shardings)
    for k in tree:
        assert got[k].sharding == want[k].sharding
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_to_host_and_cross_process_detection():
    mesh = global_mesh()
    assert not is_cross_process(mesh)  # single process in tests
    x = jax.device_put(
        jnp.arange(16, dtype=jnp.float32),
        NamedSharding(mesh, PartitionSpec("clusters")),
    )
    np.testing.assert_array_equal(to_host(x), np.arange(16, dtype=np.float32))


def test_engine_on_global_mesh_reads_metrics():
    """BatchedSimulation on the all-device mesh steps and reduces metrics
    through the multihost readout path."""
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )

    config = SimulationConfig.from_yaml(
        "sim_name: mh\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(4, cpu=16000, ram=32 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=0.5, horizon=60.0, seed=2, cpu=2000,
        ram=4 * 1024**3, duration_range=(10.0, 30.0),
    )
    sim = build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=16,
        max_pods_per_cycle=8,
        mesh=global_mesh(),
    )
    sim.step_until_time(100.0)
    counters = sim.metrics_summary()["counters"]
    assert counters["processed_nodes"] == 4 * 16
    assert counters["scheduling_decisions"] > 0
