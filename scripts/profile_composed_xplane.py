"""Device-op anatomy of the composed-flagship window from an xplane profile.

Builds the composed bench scenario (profile_autoscale_cost.build), warms
past the compile/HPA-burst region, captures a jax.profiler trace of a
steady-state span, then aggregates the TPU device plane's op durations by
HLO op name prefix — the measured structure the optimization work starts
from (the r4 dense-window anatomy in docs/DESIGN.md was produced the same
way).

Usage: python scripts/profile_composed_xplane.py [pod_window] [span_s]
"""

import collections
import glob
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import numpy as np

from profile_autoscale_cost import build


def capture(pod_window=512, span=200.0, outdir="/tmp/ktpu_xplane"):
    # Host spans over every dispatch phase are recorded alongside the
    # xplane capture, and while the profiler session is live they ALSO
    # land in the xplane as `ktpu:<phase>` TraceAnnotations, so the
    # aggregation below can be correlated with the engine phases directly
    # instead of re-derived from HLO op names. KTPU_TRACE adds the device
    # ring the report's per-window line reads.
    os.environ.setdefault("KTPU_TRACE", "1")
    sim = build(pod_window, True)
    sim.step_until_time(590.0)
    _ = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())
    os.makedirs(outdir, exist_ok=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(outdir):
        sim.step_until_time(590.0 + span)
        _ = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())
    wall = time.perf_counter() - t0
    n_windows = span / 10.0
    print(f"captured {n_windows:.0f} windows in {wall:.2f}s "
          f"({wall / n_windows * 1e3:.2f} ms/window wall)")
    rep = sim.telemetry_report()
    print("host-span anatomy of the captured region "
          "(same spans appear as TraceAnnotations in the xplane):")
    for name, s in sorted(
        rep["spans"].items(), key=lambda kv: -kv[1]["total_ms"]
    ):
        print(f"{s['total_ms']:9.2f} ms  {name} (x{s['count']})")
    print("sync budget:", rep["sync_budget"])
    return outdir, n_windows


def aggregate(outdir, n_windows):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(glob.glob(outdir + "/**/*.xplane.pb", recursive=True))
    assert paths, f"no xplane under {outdir}"
    space = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as fh:
        space.ParseFromString(fh.read())

    for plane in space.planes:
        if "TPU" not in plane.name and "/device" not in plane.name.lower():
            continue
        ev_names = dict(plane.event_metadata.items())
        per_op = collections.Counter()
        total_ps = 0
        for line in plane.lines:
            for ev in line.events:
                md = ev_names.get(ev.metadata_id)
                name = md.name if md else f"id{ev.metadata_id}"
                per_op[name] += ev.duration_ps
                total_ps += ev.duration_ps
        print(f"\n== plane: {plane.name} "
              f"(device total {total_ps / 1e12 * 1e3:.2f} ms, "
              f"{total_ps / 1e12 / n_windows * 1e3:.3f} ms/window) ==")
        # Group by cleaned op-name prefix (fusion groups, kernel names).
        groups = collections.Counter()
        for name, ps in per_op.items():
            key = name.split(".")[0].split("(")[0]
            groups[key] += ps
        for key, ps in groups.most_common(28):
            print(f"{ps / 1e12 / n_windows * 1e3:9.4f} ms/win  {key}")


def main():
    pod_window = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    span = float(sys.argv[2]) if len(sys.argv) > 2 else 200.0
    outdir, n_windows = capture(pod_window, span)
    aggregate(outdir, n_windows)


if __name__ == "__main__":
    main()
