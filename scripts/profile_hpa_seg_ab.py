"""Interleaved A/B of the HPA segment-sliced pass vs the full-width pass
on the composed scenario (same process, alternating chunks).

A: engine default (_hpa_seg = (lo, hi) group-slot slice)
B: _hpa_seg = None (hpa_pass full-width path, the pre-slice structure)

Usage: python scripts/profile_hpa_seg_ab.py [rounds]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from profile_autoscale_cost import build  # noqa: E402 (same scenario)


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    simA = build(512, True)
    print("A seg:", simA._hpa_seg, flush=True)
    simB = build(512, True)
    simB._hpa_seg = None

    for sim in (simA, simB):
        sim.step_until_time(590.0)
        _ = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())

    spans = []
    end = 790.0
    for _ in range(rounds):
        spans.append(end)
        end += 200.0
    resA, resB = [], []
    for until in spans:
        for sim, res in ((simA, resA), (simB, resB)):
            t0 = time.perf_counter()
            sim.step_until_time(until)
            _ = int(np.asarray(sim.state.metrics.scheduling_decisions).sum())
            res.append((time.perf_counter() - t0) / 20 * 1e3)  # ms/window
    print("A (seg)  ms/win:", " ".join(f"{x:.2f}" for x in resA), flush=True)
    print("B (full) ms/win:", " ".join(f"{x:.2f}" for x in resB), flush=True)


if __name__ == "__main__":
    main()
