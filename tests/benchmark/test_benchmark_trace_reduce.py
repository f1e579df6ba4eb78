"""trace_reduce on a small recorded trace (the first 30 ms of a traced
`sched1k.montecarlo` window on a TPU v5 lite, PR 25, in the neutral form) and
on hand-made events, so that every PR computes the same numbers the same way."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "sched1k_montecarlo_v5e.trace.json")) as fh:
        return tr.TraceEvents.from_json(json.load(fh))


def test_recorded_trace_reduces_to_known_numbers(recorded):
    assert len(recorded.devices) == 1 and len(recorded.devices[0]) == 3377
    s = tr.reduce_events(recorded)
    assert s.window_s == pytest.approx(0.03, abs=1e-9)
    assert s.busy_s == pytest.approx(0.02760793, rel=1e-9)
    # the one megakernel launch in these 30 ms, found by the match table
    assert s.kernel_events == {"cycle": 1, "ca_up": 0, "ca_down": 0}
    assert s.kernel_s["cycle"] == pytest.approx(0.000243466, rel=1e-9)
    # the device waited 2.4 ms for the first dispatch, and hardly for the reset
    assert s.idle_gaps_s["dispatch"] == pytest.approx(0.002391963, rel=1e-6)
    assert s.idle_gaps_s["reset"] < 1e-6
    assert s.busy_s + sum(s.idle_gaps_s.values()) == pytest.approx(s.window_s, rel=1e-9)
    top = s.breakdown()["device_ops"]
    assert top[0][0] == "fusion.83" and top[0][1] == pytest.approx(0.011400535, rel=1e-9)
    assert len(top) == 10 and all(len(name) < 100 for name, _ in top)
    # self times partition the busy time: the job-long `while` counts its body once
    assert sum(s.op_self_s.values()) == pytest.approx(0.027761536, rel=1e-9)


def test_json_round_trip(recorded):
    again = tr.TraceEvents.from_json(json.loads(json.dumps(recorded.to_json())))
    assert again == recorded


def test_union_self_time_and_gap_labels_on_hand_made_events():
    ms = 1e6
    device = [
        ("while.1", 10 * ms, 30 * ms),  # parent
        ("kernel_a.3", 12 * ms, 10 * ms),  # child
        ("fusion.2", 25 * ms, 5 * ms),  # child
        ("kernel_a.3", 60 * ms, 10 * ms),  # a second launch, after a gap
    ]
    spans = [("dispatch", 0.0, 20 * ms), ("fetch", 20 * ms, 60 * ms)]
    s = tr.reduce_events(tr.TraceEvents([device], spans), {"a": ["^kernel_a"], "none": ["^zzz"]})
    assert s.window_s == pytest.approx(0.080)
    assert s.busy_s == pytest.approx(0.040)  # 10..40 and 60..70
    assert s.op_self_s == pytest.approx({"while.1": 0.015, "kernel_a.3": 0.020, "fusion.2": 0.005})
    assert s.kernel_s == pytest.approx({"a": 0.020, "none": 0.0}) and s.kernel_events == {"a": 2, "none": 0}
    # 0..10 falls under `dispatch`; 40..60 and 70..80 under `fetch`
    assert s.idle_gaps_s == pytest.approx({"dispatch": 0.010, "fetch": 0.030})


def test_two_devices_are_averaged_and_an_empty_trace_is_an_error():
    ms = 1e6
    events = tr.TraceEvents([[("op", 0.0, 10 * ms)], [("op", 0.0, 30 * ms)]], [("dispatch", 0.0, 40 * ms)])
    s = tr.reduce_events(events, {})
    assert s.n_devices == 2 and s.busy_s == pytest.approx(0.020) and s.window_s == pytest.approx(0.040)
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce_events(tr.TraceEvents([[]], []), {})


def test_short_name_cuts_the_hlo_text():
    long = "%fused_select_cycle_commit.8 = (s32[1000,1280]{1,0:T(8,128)}, ...) custom-call(...)"
    assert tr.short_name(long) == "fused_select_cycle_commit.8"
    assert tr.short_name("fusion.83") == "fusion.83"
