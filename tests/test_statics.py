"""The engine statics table (kubernetriks_tpu/batched/statics.py).

- Precedence, one case a row: platform default on a CPU and on a stand-in
  accelerator backend, the row's flag beats the default, the kwarg beats
  the flag.
- The table, the constructor's signature, `resolved_statics()` and the
  flag registry name the same statics.
- An illegal value or an unmet `requires` raises naming the static(s).
- A lane-async engine refuses each global-clock static by name.
- A default CPU build resolves the table's CPU defaults.
"""

import inspect

import pytest

from kubernetriks_tpu import flags
from kubernetriks_tpu.batched.engine import (
    BatchedSimulation,
    build_batched_from_traces,
)
from kubernetriks_tpu.batched.fleet import ScenarioFleet
from kubernetriks_tpu.batched.statics import (
    ACCELERATOR,
    NAMES,
    TABLE,
    EngineStatics,
    resolve,
)
from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.trace.generator import (
    PoissonWorkloadTrace,
    UniformClusterTrace,
)

# What each row turns on with it when asked for by kwarg.
REQUIRED = {row.name: row.requires for row in TABLE if row.kind == "tristate"}
FLAG_OF = {row.name: row.flag for row in TABLE}


@pytest.fixture(autouse=True)
def _no_static_flags(monkeypatch):
    for row in TABLE:
        if row.flag:
            monkeypatch.delenv(row.flag, raising=False)


def _default(row, backend):
    return backend != "cpu" if row.default is ACCELERATOR else row.default


def _other(row, value):
    """A legal value of the row that differs from `value`."""
    if row.kind == "tristate":
        return not value
    return 5 if value != 5 else 7


def _with_required(name, value):
    kwargs = {name: value}
    if value is True and REQUIRED.get(name):
        kwargs[REQUIRED[name]] = True
    return kwargs


@pytest.mark.parametrize("row", TABLE, ids=NAMES)
def test_precedence(row, monkeypatch):
    for backend in ("cpu", "tpu"):
        st = resolve({}, backend)
        want = _default(row, backend)
        if row.requires and row.kind == "tristate":
            want = want and getattr(st, row.requires)
        assert getattr(st, row.name) == want, backend
        own = row.flag and flags.REGISTRY[row.flag].default is not None
        assert st.source[row.name] == ("flag" if own else "default")
    # The flag beats the platform default, on the backend whose default
    # is the other value.
    backend = "tpu"
    from_default = getattr(resolve({}, backend), row.name)
    flagged = _other(row, from_default)
    if row.flag:
        monkeypatch.setenv(row.flag, str(int(flagged)))
        required = REQUIRED.get(row.name) if flagged is True else None
        st = resolve({required: True} if required else {}, backend)
        assert getattr(st, row.name) == flagged
        assert st.source[row.name] == "flag"
    # The kwarg beats the flag (or, for a row without one, the default).
    asked = _other(row, flagged)
    st = resolve(_with_required(row.name, asked), backend)
    assert getattr(st, row.name) == asked
    assert st.source[row.name] == "kwarg"


def test_table_covers_every_engine_static(tiny_sim):
    params = inspect.signature(BatchedSimulation.__init__).parameters
    assert set(NAMES) <= set(params), set(NAMES) - set(params)
    assert all(params[name].default is None for name in NAMES)
    # Every other None-default kwarg is accounted for by name: geometry,
    # a kernel fit gate, or a debug / telemetry plane. A new one lands in
    # the table or here.
    not_statics = {
        "max_events_per_window", "max_pods_per_cycle", "mesh", "pod_window",
        "scenario", "scheduler_profile",  # geometry and semantics
        "use_pallas", "fast_forward",  # decided by fit / trace density
        "sanitize_mode", "telemetry", "watchdog",  # debug planes
    }
    none_default = {n for n, p in params.items() if p.default is None}
    assert none_default == set(NAMES) | not_statics
    assert list(tiny_sim.resolved_statics()) == list(NAMES)
    assert [f.name for f in EngineStatics.__dataclass_fields__.values()] == [
        *NAMES, "source",
    ]
    kinds = {"tristate": "tristate", "int": "int", "optional_int": "int"}
    for row in TABLE:
        assert row.requires is None or row.requires in NAMES
        if row.flag is None:
            continue
        flag = flags.REGISTRY[row.flag]
        assert flag.type == kinds[row.kind], row.name
        # A flag with a default of its own agrees with the row's.
        assert flag.default in (None, row.default), row.name


@pytest.mark.parametrize(
    "kwargs, named",
    [
        ({"superspan_k": -1}, "superspan_k"),
        ({"stream_depth": 2.5}, "stream_depth"),
        ({"stream_segment": True}, "stream_segment"),
        ({"donate": 1}, "donate"),
        ({"lane_major": "yes"}, "lane_major"),
    ],
    ids=["negative", "float", "bool-for-int", "int-for-bool", "str-for-bool"],
)
def test_illegal_value_names_the_static(kwargs, named):
    with pytest.raises(ValueError, match=repr(named)):
        resolve(kwargs, "cpu")


@pytest.mark.parametrize(
    "kwargs, backend",
    [
        ({"stream": True}, "cpu"),
        ({"stream": True, "superspan": False}, "tpu"),
    ],
    ids=["cpu-default-off", "superspan-off-by-name"],
)
def test_unmet_requires_names_both(kwargs, backend, monkeypatch):
    with pytest.raises(ValueError, match="stream=True requires superspan"):
        resolve(kwargs, backend)
    # From its flag or the platform default the rider resolves off.
    monkeypatch.setenv("KTPU_STREAM", "1")
    assert resolve({"superspan": False}, backend).stream is False


def test_int_statics_normalise():
    st = resolve({"superspan_k": 0, "stream_segment": 0}, "cpu")
    assert st.superspan_k == 1 and st.stream_segment == 0
    assert st.superspan_stage_cols is None


TINY_YAML = "sim_name: statics\nseed: 1\nscheduling_cycle_interval: 10.0"


@pytest.fixture(scope="module")
def tiny_traces():
    config = SimulationConfig.from_yaml(TINY_YAML)
    cluster = UniformClusterTrace(4, cpu=64000, ram=128 * 1024**3)
    wl = PoissonWorkloadTrace(
        rate_per_second=0.2,
        horizon=200.0,
        seed=3,
        cpu=16000,
        ram=32 * 1024**3,
        duration_range=(30.0, 90.0),
        name_prefix="p",
    )
    return (
        config,
        cluster.convert_to_simulator_events(),
        wl.convert_to_simulator_events(),
    )


@pytest.fixture(scope="module")
def tiny_sim(tiny_traces):
    config, cev, wev = tiny_traces
    sim = build_batched_from_traces(
        config, cev, wev, n_clusters=4, use_pallas=False, fast_forward=False
    )
    yield sim
    sim.close()


def test_resolved_statics_of_a_default_cpu_build(tiny_sim):
    """No kwarg, no flag: the table's CPU defaults (accelerator tristates
    off), and the attributes the engine reads agree."""
    want = {row.name: _default(row, "cpu") for row in TABLE}
    assert tiny_sim.resolved_statics() == want
    assert tiny_sim.statics == resolve({}, "cpu")
    assert tiny_sim.donate is False and tiny_sim.window_razor is False
    assert tiny_sim._superspan_k == 16 and tiny_sim._stream_depth == 3
    assert tiny_sim._reclaim_requested is None


@pytest.mark.parametrize("name", ["superspan", "stream", "fuse_slide"])
def test_lane_async_refuses_global_clock_statics(name, tiny_traces, monkeypatch):
    config, cev, wev = tiny_traces

    def fleet(**kwargs):
        return ScenarioFleet(
            config, cev, wev, n_lanes=2, horizon=100.0, use_pallas=False,
            lane_async=True, **kwargs,
        )

    with pytest.raises(ValueError, match=f"lane_async.*{name}=True"):
        fleet(**_with_required(name, True))
    # Its flag, like its accelerator default, is turned off instead.
    monkeypatch.setenv("KTPU_SUPERSPAN", "1")
    monkeypatch.setenv(FLAG_OF[name], "1")
    built = fleet()
    assert built.engine.resolved_statics()[name] is False
    built.close()
