"""The one place an engine static is decided.

A static is a `BatchedSimulation` build kwarg that selects a compiled
program variant or a host dispatch policy and never changes a result
(each is bit-identity gated by its own test). TABLE has one row a
static; `resolve` applies, for every row in one loop,

    explicit kwarg  >  the static's own KTPU_* flag  >  platform default

and returns a frozen record the constructor reads. What depends on the
build's geometry (reclaim's auto-off, a lane-async engine refusing the
global-clock statics, a cross-process mesh) stays in the engine and reads
the request from the record. Adding or deleting a static is one row here,
its kwarg and its flag (docs/DESIGN.md section 16).

Cold-path host code: no jit, no device work.
"""

from __future__ import annotations

import dataclasses
import numbers
from typing import Dict, Mapping, NamedTuple, Optional

from kubernetriks_tpu.flags import flag_int, flag_tristate

# Platform default of the accelerator tristates: on for accelerator
# backends, where the win is device-side (buffer reuse, fewer dispatches
# and syncs, no layout copies); off on CPU hosts, where each would add a
# program variant to compile for a neutral-at-best effect, so tier-1
# tests opt in explicitly.
ACCELERATOR = "accelerator"


class Static(NamedTuple):
    name: str  # the BatchedSimulation build kwarg
    # Legal values by kind. "tristate": True or False (its flag may be
    # unset). "int": an integer >= 0, raised to at least 1.
    # "optional_int": None (the engine's own geometry rule decides) or an
    # integer >= 0.
    kind: str
    flag: Optional[str]  # its flag in flags.py, or None
    default: object  # a value, or ACCELERATOR
    # The static this one rides. An on/off static asked for by kwarg while
    # that one is off raises; from a flag or the platform default it
    # resolves off. An int static is merely inert without it.
    requires: Optional[str]
    doc: str


TABLE = (
    Static("donate", "tristate", "KTPU_DONATE", ACCELERATOR, None,
           "The dispatch loop consumes its input state buffers in place "
           "(donated jit variants)."),
    Static("fuse_slide", "tristate", "KTPU_FUSED_SLIDE", ACCELERATOR, None,
           "The last ladder chunk of a slide span also computes and applies "
           "the window slide on device. Inert under the superspan executor, "
           "which slides in-program."),
    Static("superspan", "tristate", "KTPU_SUPERSPAN", ACCELERATOR, None,
           "One while_loop program retires up to K slide-spans a dispatch, "
           "instead of ladder chunks plus a shift readback a span."),
    Static("superspan_k", "int", None, 16, "superspan",
           "Max slide-spans a superspan dispatch retires (the while_loop's "
           "trip bound; one progress readback amortises over K spans)."),
    Static("superspan_chunk", "int", None, 8, "superspan",
           "Windows advanced per inner iteration of the superspan body."),
    Static("superspan_stage_cols", "optional_int", None, None, "superspan",
           "Width (payload columns) of the superspan refill stage; None: "
           "4x the pod window, clamped to [W + W/2, whole payload]."),
    Static("stream", "tristate", "KTPU_STREAM", ACCELERATOR, "superspan",
           "A feeder thread stages trace segments into a bounded ring of "
           "device slabs ahead of the superspan executor; the whole-trace "
           "slide payload is never materialised."),
    Static("stream_depth", "int", "KTPU_STREAM_DEPTH", 3, "stream",
           "Feeder ring depth: at most this many staging slabs live on "
           "device at once. Host policy, no recompile."),
    Static("stream_segment", "optional_int", "KTPU_STREAM_SEGMENT", None,
           "stream",
           "Width of the feeder's slabs (a jit static); None: the "
           "superspan stage rule."),
    Static("lane_major", "tristate", "KTPU_LANE_MAJOR", ACCELERATOR, None,
           "Window programs carry the hot node leaves transposed (N, C), "
           "the Pallas kernels' layout; state at rest stays row-major."),
    Static("window_razor", "tristate", "KTPU_WINDOW_RAZOR", ACCELERATOR, None,
           "Gate the per-window resolution soup behind a cheap due-ness "
           "predicate, so empty windows skip it."),
    Static("reclaim", "tristate", "KTPU_RECLAIM", ACCELERATOR, None,
           "Periodic in-trace compaction returns retired CA reserve slots. "
           "The record holds the REQUEST: the engine turns it off (or "
           "raises, if it was asked for by name or flag) where the trace's "
           "node-name classes interleave or there is no CA."),
)

NAMES = tuple(row.name for row in TABLE)
_READ_FLAG = {
    "tristate": flag_tristate,
    "int": flag_int,
    "optional_int": flag_int,
}


def _as_dict(self) -> Dict[str, object]:
    return {name: getattr(self, name) for name in NAMES}


# One field a row, plus `source`: which level decided each static,
# "kwarg" | "flag" | "default" (a flag with a default of its own in
# flags.py reads as "flag" whether or not it is set).
EngineStatics = dataclasses.make_dataclass(
    "EngineStatics",
    [(name, object) for name in NAMES] + [("source", Mapping[str, str])],
    frozen=True,
    namespace={"as_dict": _as_dict},
)


def _normalise(row: Static, value: object) -> object:
    if row.kind == "tristate":
        if not isinstance(value, bool):
            raise ValueError(
                f"engine static {row.name!r}: {value!r} is not True or False"
            )
        return value
    if (
        isinstance(value, bool)
        or not isinstance(value, numbers.Integral)
        or value < 0
    ):
        raise ValueError(
            f"engine static {row.name!r}: {value!r} is not an integer >= 0"
        )
    return max(1, int(value)) if row.kind == "int" else int(value)


def resolve(kwargs: Mapping[str, object], backend: str) -> "EngineStatics":
    """Decide every static of TABLE for one build. `kwargs`: the build
    kwargs by name (None or absent: not given); `backend`:
    jax.default_backend(). Raises ValueError naming the static on an
    illegal value or an unmet `requires`."""
    values: Dict[str, object] = {}
    source: Dict[str, str] = {}
    for row in TABLE:
        value, level = kwargs.get(row.name), "kwarg"
        if value is None and row.flag is not None:
            value, level = _READ_FLAG[row.kind](row.flag), "flag"
        if value is None:
            level = "default"
            value = backend != "cpu" if row.default is ACCELERATOR else row.default
        if value is not None:
            value = _normalise(row, value)
        rides = row.requires if row.kind == "tristate" and value else None
        if rides and not values[rides]:
            if level == "kwarg":
                raise ValueError(
                    f"engine static {row.name}=True requires {rides}=True: "
                    f"{row.name} rides {rides}"
                )
            value = False
        values[row.name], source[row.name] = value, level
    return EngineStatics(**values, source=source)
