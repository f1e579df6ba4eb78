"""One run of one cell: `python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`.

Everything is found by name. BENCHMARK.json's `workloads` entry names the
configuration (benchmark/configs/<config>.json) and the traffic mix
(benchmark/traffic/<traffic>.json); the mix names its driver
(benchmark/drivers/<driver>.py); each per-layer metric has a reader
(benchmark/metrics/<name>.py). A later PR adds files and entries and edits
none of these.

The run fails (non-zero exit, no result line) unless JAX finds a TPU with as
many chips as the cell asks for. `--rehearsal <file>` is the one way round
that: toy sizes from the named override file, Pallas interpreted, for debugging
the harness off the chip. Its result line says `"rehearsal": true` and is never
a device number.

The last line on stdout is the result object; every line before it is a JSON
object too (checks with their limits, the CA trajectory, counters).
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.harness import Cell, Harness, load_json, read_per_layer, say  # noqa: E402


def open_harness(args):
    """The cell found by name and a harness on the devices it asks for, or
    None where JAX finds no TPU (or too few chips) and no rehearsal is asked."""
    manifest = load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    rehearsal = load_json(args.rehearsal) if args.rehearsal else None
    cell = Cell(manifest, args.workload, rehearsal)
    driver = importlib.import_module("benchmark.drivers." + cell.traffic["driver"])
    prepared = driver.prepare(cell, int(args.seed)) if hasattr(driver, "prepare") else None

    def refuse(message: str):
        if prepared is not None:
            prepared.cancel()
        print(message, file=sys.stderr)

    import jax

    from kubernetriks_tpu.compile_cache import place_compile_cache

    from benchmark import peaks

    cache_dir = place_compile_cache()
    # No size cap: under JAX's LRU mode (JAX_COMPILATION_CACHE_MAX_SIZE, which
    # the chip machine sets to 192 MiB) the window programs of one autoscaled
    # cell evict each other and large entries fail to be written at all, so
    # every run would compile again (PERF.md, findings).
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not cell.rehearsal:
        if device["platform"] != "tpu":
            refuse(f"run.py: needs a TPU, JAX found {device}")
            return None
        peaks.for_device(device["kind"])  # an unknown device kind is an error
    if len(devices) < cell.chips:
        refuse(f"run.py: {cell.name} needs {cell.chips} chips, JAX found {device}")
        return None
    say(
        line="start", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=cell.rehearsal, device=device,
        compile_cache=cache_dir, jax=jax.__version__,
        since_process_start_s=time.perf_counter() - _PROCESS_T0,
    )
    harness = Harness(cell, args, device, devices[: cell.chips], _PROCESS_T0)
    harness.driver, harness.prepared = driver, prepared
    return harness


def add_arguments(parser) -> None:
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearsal",
        help="override file (benchmark/rehearsal/*.json): toy sizes, Pallas "
        "interpreted, any platform; debugs the harness, measures nothing",
    )
    parser.add_argument(
        "--control", type=int, choices=(0, 1), default=0,
        help="also put the control in the program's place (the same answers in "
        "the next lower precision, or with one stated guarantee broken) and "
        "print its numbers on `control` lines; the driver's runs never ask for it",
    )
    parser.add_argument(
        "--keep-trace", help="directory for a one-second record of the trace and its plane listing"
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_arguments(parser)
    args = parser.parse_args(argv)
    harness = open_harness(args)
    if harness is None:
        return 2
    cell, device = harness.cell, harness.device
    harness.driver.run(harness)
    device["memory_peak_bytes"] = harness.counters["memory_peak_bytes"]
    result = {
        "correct": all(c.ok for c in harness.checks) and bool(harness.checks),
        "attempted": harness.attempted,
        "failed": harness.failed,
    }
    for check in harness.checks:
        say(line="check", **check.row())
    for check in harness.control_checks:
        say(line="control", **check.row())
    if harness.control:
        result["control_correct"] = all(c.ok for c in harness.control_checks)
    if harness.tracing:
        harness.reduce_trace()
        device["busy_s"] = harness.trace.busy_s
        device["window_s"] = harness.trace.window_s
        result["metrics"] = read_per_layer(harness)
        result["breakdown"] = harness.trace.breakdown()
    else:
        harness.end_to_end["setup_s"] = harness.setup_s
        result["metrics"] = {
            m["name"]: {"value": float(harness.end_to_end[m["name"]]), "unit": m["unit"]}
            for m in cell.metrics("end_to_end")
        }
    result["device"] = device
    if cell.rehearsal:
        result["rehearsal"] = True
    say(line="counters", **{k: v for k, v in harness.counters.items()})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
