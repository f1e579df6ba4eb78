"""Central registry of KTPU_* / KUBERNETRIKS_* environment flags.

Every environment flag the framework reads is declared here — name, type,
default, and documentation — and every read goes through the typed helpers
below. This is enforced by the env-flag lint pass
(kubernetriks_tpu/lint/envflags.py): an `os.environ` / `os.getenv` read of a
KTPU_*/KUBERNETRIKS_* name anywhere outside this module is a lint violation,
and a helper read of an unregistered name raises here at runtime.

Why a registry: before PR 6, `"0"` / empty-string / unset truthiness was
decided ad hoc at each read site (`env != "0"`, `== "1"`,
`bool(os.environ.get(...))` — three different rules, one of which made
a flag set to `0` truthy). The registry gives every flag ONE
parser, one default, and one greppable declaration.

Truthiness rule (flag_bool / flag_tristate): unset -> default (or None for
tristate); `"0"`, `""`, `"false"`, `"no"`, `"off"` (case-insensitive) ->
False; anything else -> True.
"""

from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional


class Flag(NamedTuple):
    name: str
    type: str  # "bool" | "tristate" | "str" | "int"
    default: object
    doc: str


_FLAGS = [
    Flag(
        "KTPU_DONATE",
        "tristate",
        None,
        "Buffer donation for the steady-state dispatch loop (donated jit "
        "entries consume the input state in place). Unset: on for "
        "accelerator backends, off on CPU hosts.",
    ),
    Flag(
        "KTPU_FUSED_SLIDE",
        "tristate",
        None,
        "Fused chunk+slide megastep: the last ladder chunk of a slide span "
        "also computes and applies the window slide on device. Unset: on "
        "for accelerator backends, off on CPU hosts.",
    ),
    Flag(
        "KTPU_SUPERSPAN",
        "tristate",
        None,
        "Superspan executor: one jitted while_loop retires up to K "
        "consecutive slide-spans per dispatch. Unset: on for accelerator "
        "backends, off on CPU hosts.",
    ),
    Flag(
        "KTPU_STREAM",
        "tristate",
        None,
        "Streaming trace-ingestion pipeline (batched/stream.py): a feeder "
        "thread compiles trace segments into a bounded ring of K "
        "device-resident staging slabs, running ahead of the superspan "
        "executor so stage-exhaustion exits find the next slab already "
        "uploaded and the whole-trace device slide payload is never "
        "materialized. Rides the superspan executor (inactive when "
        "KTPU_SUPERSPAN is off). Unset: on for accelerator backends, off "
        "on CPU hosts — the same platform default as KTPU_SUPERSPAN.",
    ),
    Flag(
        "KTPU_STREAM_DEPTH",
        "int",
        3,
        "Ring depth K of the streaming feeder: at most K staging slabs "
        "live on device at once (the memory bound). K = 1 degenerates to "
        "synchronous-but-off-thread staging and stays exact.",
    ),
    Flag(
        "KTPU_STREAM_SEGMENT",
        "int",
        None,
        "Staging-segment width (payload columns) of the streaming "
        "feeder's slabs. Unset: the superspan stage default (4x the pod "
        "window, clamped to [W + W/2, whole payload]). Width is a jit "
        "static — changing it recompiles the superspan program.",
    ),
    Flag(
        "KTPU_LANE_MAJOR",
        "tristate",
        None,
        "Lane-major hot node state: inside every window program the hot "
        "(C, N) node leaves (alive, caps, allocatables, crash payload) are "
        "carried TRANSPOSED (N, C) — the layout the Pallas kernels consume "
        "— so the event/free/cycle kernel wrappers skip their per-boundary "
        "transposes and the XLA glue runs elementwise on the kernel "
        "layout. Bit-identical to the row-major path (float metric sums "
        "within the documented docs/PARITY.md tolerance). Unset: on for "
        "accelerator backends, off on CPU hosts (where XLA pays the "
        "transposes anyway and the row-major path avoids the extra "
        "program variants). A mesh build follows the same rule: the swap "
        "happens on the shard, inside the window program's shard_map.",
    ),
    Flag(
        "KTPU_WINDOW_RAZOR",
        "tristate",
        None,
        "Window-cost razor: gate the per-window event-resolution soup "
        "(event application, pending-effect merge, finish/interrupt "
        "resolution, free/reschedule bookkeeping) behind a cheap due-ness "
        "predicate, so empty and near-empty windows in dense traces skip "
        "the masked elementwise passes entirely. Bit-exact: the skip "
        "branch fires only when the soup is provably the identity. Unset: "
        "on for accelerator backends; off on CPU hosts, where the cond "
        "adds compile time to every window program. 0/1 force for A/B "
        "measurement.",
    ),
    Flag(
        "KTPU_RECLAIM",
        "tristate",
        None,
        "CA slot reclaim (batched/autoscale.py ca_reclaim_pass): a "
        "periodic in-trace compaction returns fully-retired CA reserve "
        "slots to their group, so ca_cursor tracks LIVE occupancy and "
        "sustained churn never exhausts the reserve (the ROADMAP #2 "
        "endurance blocker). Trajectories stay scalar-exact: allocations "
        "carry the scalar's total_allocated naming index and every "
        "name-ordered walk derives its order from it. 0 compiles the "
        "pre-reclaim programs (the A/B bit-identity gate; the loud "
        "reserve bound is then the only backstop). Unset: on for "
        "accelerator backends, off on CPU hosts — tests and endurance "
        "runs opt in explicitly. Forced off (warning) when the trace's "
        "node-name classes interleave; an explicit 1 raises there.",
    ),
    Flag(
        "KTPU_ALIGN_PODS",
        "bool",
        True,
        "128-align the pod axis of full-resident runs so Pallas block pads "
        "are no-ops.",
    ),
    Flag(
        "KTPU_MEGAKERNEL",
        "bool",
        True,
        "Fused selection+cycle+commit Pallas megakernel on the dense path "
        "(0 selects the two-kernel path for A/B measurement). Read at "
        "engine build time and threaded as a jit-static.",
    ),
    Flag(
        "KTPU_DEBUG_FINITE",
        "bool",
        False,
        "Guard mode: host-side NaN/inf sweep over every float state leaf "
        "after each dispatched chunk, naming the offending field. Keeps "
        "the ladder path (per-chunk localization).",
    ),
    Flag(
        "KTPU_SANITIZE",
        "bool",
        False,
        "Runtime sanitizer: the engine's steady-state dispatch region runs "
        "under jax.transfer_guard('disallow_explicit') for device-to-host "
        "transfers (waived syncs carry explicit allow scopes), donated "
        "inputs are force-deleted after donated calls so read-after-donate "
        "crashes even on CPU (where XLA donation is a no-op), and the "
        "KTPU_DEBUG_FINITE state sweep runs at every dispatch boundary.",
    ),
    Flag(
        "KTPU_PROFILE",
        "str",
        None,
        "Named scheduler profile for batched engines that were not handed "
        "an explicit profile (CLI selection): a key of "
        "core.scheduler.kube_scheduler.NAMED_PROFILE_SPECS ('default', "
        "'best_fit', 'balanced_packing'). Compiled into the scan and "
        "Pallas kernel paths at engine build (batched/pipeline.py); an "
        "unknown name or un-lowerable plugin raises at construction "
        "instead of silently running the default pipeline. Unset: the "
        "config's scheduler_profile, else the reference default.",
    ),
    Flag(
        "KTPU_EXPLAIN_RECOMPILES",
        "tristate",
        None,
        "Recompile sentinel (kubernetriks_tpu/recompile.py): a "
        "jax.log_compiles-based monitor that raises RecompileError "
        "naming the jit entry on any post-warm-up XLA compilation — the "
        "runtime cross-check of the fleet's compile-once guarantee (the "
        "scenariotrace lint pass is the static half). 1: ScenarioFleet "
        "guards every post-warm-up wave; unset or 0: it arms none.",
    ),
    Flag(
        "KTPU_TRACE",
        "bool",
        False,
        "Flight recorder: the device-side per-window metrics ring carried "
        "in ClusterBatchState and the capacity observatory (the host-side "
        "span recorder is always on). Read out via engine.telemetry_report() / "
        "write_chrome_trace(); cli.py prints the report and writes the "
        "trace. Off by default (telemetry-on is bit-identical and "
        "gated <3% overhead, but the ring costs device memory).",
    ),
    Flag(
        "KTPU_TRACE_PATH",
        "str",
        None,
        "Output path stem for the Chrome trace-event JSON cli.py writes "
        "when the flight recorder is armed (Perfetto-loadable). Unset: "
        "ktpu_trace under the working directory.",
    ),
    Flag(
        "KTPU_WATCHDOG",
        "tristate",
        None,
        "Saturation watchdog (telemetry/observatory.py): at every "
        "telemetry-ring drain, fit the reserve-occupancy trajectories "
        "(CA node-slot reserve, HPA pod-reserve, pod-window headroom) and "
        "emit SaturationWarning with an estimated time-to-exhaustion "
        "BEFORE the loud reserve bound fires; also flags feeder "
        "starvation and sync-budget violations. Unset: armed exactly when "
        "the flight recorder is (KTPU_TRACE / telemetry=True) — it reads "
        "the ring's occupancy columns, so it rides telemetry; an explicit "
        "1 with telemetry off raises at engine build instead of silently "
        "watching nothing.",
    ),
    Flag(
        "KTPU_LANE_SPAN",
        "int",
        None,
        "Pump span (windows per round) of the lane-asynchronous fleet's "
        "continuous submit/poll engine (batched/fleet.py pump()): every "
        "round steps ALL lanes this many global windows through one "
        "compiled fixed-span program, then re-seeds the lanes whose "
        "per-lane clock finished. Smaller spans cut completion latency "
        "and idle-lane waste at more dispatch overhead. Unset: 8.",
    ),
    Flag(
        "KTPU_HOST_CHAOS",
        "str",
        None,
        "Deterministic HOST-fault injection for the serving fleet "
        "(batched/faults.py HostChaos): counter-seeded threefry draws "
        "inject dispatch exceptions (victim lane cycles round-robin), "
        "stream-feeder producer kills, and slow-lane stalls, so the "
        "fault-domain machinery (typed QueryError results, lane_reset "
        "crash recovery, quarantine, feeder supervisor) is provable in "
        "CI. '1' selects the documented defaults "
        "(seed=7,dispatch=0.04,feeder=0.05,stall=0.03,stall_ms=2.0); a "
        "'k=v,...' spec overrides them. Unset: injection OFF — the fleet "
        "runs the exact pre-chaos code path (per-query bit-identity and "
        "dispatch_stats equality, tests/test_fleet_faults.py).",
    ),
    Flag(
        "KTPU_FLEET_QUEUE",
        "int",
        None,
        "Bounded admission queue depth for ScenarioFleet.submit(): at "
        "most this many queries may be QUEUED (in-flight lanes excluded). "
        "A full queue applies the KTPU_FLEET_QUEUE_POLICY backpressure. "
        "Unset: unbounded (the pre-fault-domain behavior).",
    ),
    Flag(
        "KTPU_FLEET_QUEUE_POLICY",
        "str",
        "reject",
        "Backpressure policy when the bounded admission queue is full: "
        "'reject' streams a RejectedError (with a retry_after_s hint "
        "derived from the observed service rate) through poll() for the "
        "refused query; 'block' makes submit() pump the fleet inline "
        "until a queue slot frees. Ignored while KTPU_FLEET_QUEUE is "
        "unset.",
    ),
    Flag(
        "KTPU_SLO_MS",
        "int",
        None,
        "Latency-SLO target in milliseconds (submit-to-drain wall) for "
        "lane-async fleet queries: arms the capacity observatory's SLO "
        "burn-rate verdicts (telemetry/observatory.py) — fast/slow "
        "error-budget burn alerting with hysteresis, windowed by "
        "KTPU_SLO_BURN_WINDOW. Unset: SLO verdicts disarmed.",
    ),
    Flag(
        "KTPU_SLO_BURN_WINDOW",
        "int",
        60,
        "Fast burn-rate window (wall seconds) for the SLO verdict; the "
        "slow-burn window is 12x this. Default: 60.",
    ),
    Flag(
        "KUBERNETRIKS_PALLAS",
        "tristate",
        None,
        "Force the Pallas scheduling-cycle kernels on (1) or off (0). "
        "Unset: auto — on for TPU backends whose blocks fit VMEM.",
    ),
    Flag(
        "KUBERNETRIKS_LOG",
        "str",
        "INFO",
        "CLI logging level (DEBUG/INFO/WARNING/ERROR).",
    ),
    Flag(
        "KUBERNETRIKS_ALIBABA_DIR",
        "str",
        None,
        "Directory holding the real Alibaba v2017 trace CSVs; enables the "
        "real-trace feeder tests when set.",
    ),
]

REGISTRY: Dict[str, Flag] = {f.name: f for f in _FLAGS}

_FALSY = frozenset({"0", "", "false", "no", "off"})


def _lookup(name: str, expected: str) -> Flag:
    flag = REGISTRY.get(name)
    if flag is None:
        raise KeyError(
            f"environment flag {name!r} is not registered in "
            "kubernetriks_tpu.flags — declare it (name, type, default, doc) "
            "before reading it"
        )
    if flag.type != expected:
        raise TypeError(
            f"environment flag {name!r} is registered as {flag.type!r}, "
            f"read as {expected!r}"
        )
    return flag


def parse_bool(raw: str) -> bool:
    """THE truthiness rule for flag strings (see module docstring)."""
    return raw.strip().lower() not in _FALSY


def flag_bool(name: str) -> bool:
    """Boolean flag: unset -> registered default; else parse_bool."""
    flag = _lookup(name, "bool")
    raw = os.environ.get(name)
    if raw is None:
        return bool(flag.default)
    return parse_bool(raw)


def flag_tristate(name: str) -> Optional[bool]:
    """Tri-state flag: None when unset (caller picks a platform default),
    else parse_bool."""
    _lookup(name, "tristate")
    raw = os.environ.get(name)
    if raw is None:
        return None
    return parse_bool(raw)


def flag_str(name: str) -> Optional[str]:
    """String flag: unset -> registered default (may be None)."""
    flag = _lookup(name, "str")
    raw = os.environ.get(name)
    if raw is None:
        return flag.default  # type: ignore[return-value]
    return raw


def flag_int(name: str) -> Optional[int]:
    """Integer flag: unset or empty -> registered default (may be None);
    anything else must parse as a base-10 integer (a typo'd value raises
    here, at the registry, instead of silently selecting a default)."""
    flag = _lookup(name, "int")
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return flag.default  # type: ignore[return-value]
    try:
        return int(raw.strip(), 10)
    except ValueError as exc:
        raise ValueError(
            f"environment flag {name!r} must be an integer, got {raw!r}"
        ) from exc
