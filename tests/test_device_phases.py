"""Device phases (PR 39): every device op of a window program names the part
of the simulator it belongs to, and the program exports the map.

(a) the lowered window program of each accepted cell's rehearsal build:
    at least 95% of its ops carry a phase of the closed set
    (telemetry/tracer.py DEVICE_PHASES) in their location, and every scope a
    location names is a phase, a known sub-scope or a kernel's name: a
    misspelt scope fails here;
(b) `recorder().program_phases()` on a compiled toy window program: the
    instruction names are the compiled module's own, a kernel's ops map to
    `cycle` / `events` and a wrapper's pad to `kernel_io`, and nothing is
    compiled until it is called (nor when it is: jax finds the dispatch's
    own executable again);
(c) a second engine in the process adds its programs and drops none;
(d) the census of XLA gathers and scatters under `ca_pass` / `ca_reclaim` in
    the two autoscaled cells' lowered programs (PR 40), and the same count,
    a phase, in `telemetry_report()["device_phases"]["gathers"]`;
(e) the same census under `slide` in the lowered superspan program of the two
    cells whose pod window slides (PR 42): block moves, no gather.

The scopes are location metadata: that they change no program by a byte is
tests/test_topology_spread.py::test_accepted_cells_lower_the_programs_they_lowered.
"""

import collections
import functools
import re
import time

import jax
import jax.numpy as jnp
import pytest

import window_program_digest as wpd
from kubernetriks_tpu.recompile import RecompileSentinel
from kubernetriks_tpu.telemetry.tracer import (
    DEVICE_PHASES,
    gather_instructions,
    instruction_phases,
    phase_of,
    recorder,
)

from test_telemetry import _build_plain

# Scopes that nest inside a phase and name none (batched/step.py,
# batched/autoscale.py).
SUB_SCOPES = ("spread_counts", "ca_scale_up", "ca_scale_down")
# What jax itself puts into a location's path: transforms and control flow.
# A Pallas kernel is there under its `name=` (`fused_...`).
STRUCTURAL = re.compile(
    r"^(jit|pjit|vmap|shard_map|checkpoint|custom_jvp|custom_vjp)\(.*\)$"
    r"|^(while|body|cond|scan|closed_call|branch_\d+_fun|jit|shard_map)$"
    r"|^fused_\w+$"
)
_LOC_NAME = re.compile(r'^loc\("([^"]*)"')
# Ops that stand for no work of their own.
_NO_WORK = ("func.return", "stablehlo.return", "stablehlo.constant", "sdy.return")


def lowered_op_paths(lowered):
    """[(op, [its full scope paths])] over the ops of a lowered program. An
    op of a private function (an inner `jit`) carries a path relative to the
    function: it is joined to the path of every call site, as XLA joins them
    when it inlines the call."""
    funcs = {}

    def walk(op, func):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    o = inner.operation
                    if o.name == "func.func":
                        name = str(o.attributes["sym_name"]).strip('"')
                        funcs[name] = []
                        walk(o, name)
                        continue
                    if func is None:
                        continue
                    named = _LOC_NAME.match(str(o.location))
                    callee = (
                        str(o.attributes["callee"]).lstrip("@")
                        if o.name == "func.call"
                        else None
                    )
                    funcs[func].append((o.name, named.group(1) if named else "", callee))
                    walk(o, func)

    walk(lowered.compiler_ir("stablehlo").operation, None)
    contexts = collections.defaultdict(set)
    contexts["main"].add("")
    changed = True
    while changed:
        changed = False
        for func, ops in funcs.items():
            for _, path, callee in ops:
                if callee is None:
                    continue
                for ctx in list(contexts[func]):
                    full = (ctx + "/" + path).strip("/")
                    if full not in contexts[callee]:
                        contexts[callee].add(full)
                        changed = True
    return [
        (name, [(ctx + "/" + path).strip("/") for ctx in contexts[func]])
        for func, ops in funcs.items()
        for name, path, _ in ops
        if name not in _NO_WORK
    ]


def strangers_of(path):
    """The scopes on a path that the tree does not know: not jax's own, not
    a phase, a sub-scope or a kernel. The last component is the primitive."""
    return [
        part
        for part in path.split("/")[:-1]
        if part not in DEVICE_PHASES and part not in SUB_SCOPES and not STRUCTURAL.match(part)
    ]


def lowered_window_program(sim):
    from kubernetriks_tpu.batched import step

    return step.run_windows.lower(
        sim.state,
        sim.slab,
        jnp.asarray([1], jnp.int32),
        sim.consts,
        collect_gauges=False,
        freeze_lanes=True,
        **sim._window_call_kwargs(),
    )


# --- (a) the lowered window programs of the accepted cells ------------------


@functools.lru_cache(maxsize=None)
def rehearsal_op_paths(cell):
    """(`lowered_op_paths` of a cell's rehearsal window program, whether the
    build carries the autoscalers): built and lowered once a worker, for
    every test of this file that reads it."""
    sim = wpd.rehearsal_engine(cell)
    try:
        return lowered_op_paths(lowered_window_program(sim)), sim.autoscale_statics is not None
    finally:
        sim.close()


@pytest.mark.parametrize("cell", wpd.CELLS)
def test_lowered_window_program_names_its_phases(cell):
    ops, autoscaled = rehearsal_op_paths(cell)
    assert len(ops) > 500, "not a window program"
    scoped = sum(1 for _, paths in ops if paths and all(phase_of(p) for p in paths))
    assert scoped >= 0.95 * len(ops), (
        f"{cell}: {scoped} of {len(ops)} lowered ops carry a device phase; without one: "
        f"{collections.Counter(p for _, paths in ops for p in paths if not phase_of(p)).most_common(12)}"
    )
    # Every scope on a path is one the tree knows.
    strangers = collections.Counter(
        part for _, paths in ops for path in paths for part in strangers_of(path)
    )
    assert not strangers, f"{cell}: scopes outside the closed set: {dict(strangers)}"
    # The phases a cell must show: all of them the event application and the
    # cycle; the autoscaled ones the three autoscaler phases.
    seen = {phase_of(p)[0] for _, paths in ops for p in paths if phase_of(p)}
    wanted = {"events", "cycle"}
    if autoscaled:
        wanted |= {"hpa_pass", "ca_pass"}
    assert wanted <= seen, (cell, seen)


def test_a_misspelt_scope_is_a_stranger():
    """The check of (a) on a path with a scope one letter off."""
    path = "jit(_run_windows_impl)/while/body/closed_call/evnets/cond/branch_1_fun/add"
    assert strangers_of(path) == ["evnets"] and phase_of(path) is None


@pytest.mark.parametrize(
    "op_name, phases",
    [
        ("jit(_run_windows_impl)/while/body/closed_call/events/cond/branch_1_fun/while/body/add", ("events", "events")),
        ("jit(_run_windows_impl)/while/body/closed_call/cycle/kernel_io/jit(_pad)/pad", ("cycle", "kernel_io")),
        ("jit(_run_windows_impl)/while/body/closed_call/cycle/fused_select_cycle_commit", ("cycle", "cycle")),
        ("jit(_run_windows_impl)/while/body/closed_call/events/cond/branch_1_fun/kernel_io/transpose", ("events", "kernel_io")),
        ("jit(_run_windows_impl)/while/body/closed_call/cycle/spread_counts/reduce", ("cycle", "cycle")),
        ("jit(_run_windows_impl)/while/body/closed_call/ca_pass/cond/branch_1_fun/ca_scale_down/kernel_io/pad", ("ca_pass", "kernel_io")),
        ("jit(_run_superspan_impl)/while/body/cond/branch_0_fun/slide/slide/dynamic_slice", ("slide", "slide")),
        ("jit(_run_superspan_impl)/while/cond/bookkeeping/lt", ("bookkeeping", "bookkeeping")),
        ("jit(_run_windows_impl)/while/body/dynamic_slice", None),
        ("state.pods.phase", None),
    ],
)
def test_phase_of_reads_the_first_and_the_last_phase_of_a_path(op_name, phases):
    assert phase_of(op_name) == phases


# --- (d) the census of per-index look-ups in the cluster autoscaler ----------

# `stablehlo.gather` / `stablehlo.scatter` ops whose location names `ca_pass`
# or `ca_reclaim` in the lowered rehearsal program (the kernel path, as on the
# chip). At the parent (4597efb) each cell lowered 26 gathers (`ca_scale_down`
# 11, `ca_scale_up` 3, `ca_pass` itself 5, `ca_reclaim` 7) and 9 scatters
# (2, 0, 4, 3); on the chip the 26 were 132 timed ops of the stream's
# superspan and 75.7% of its device time went to gathers (PERF.md section 5).
# PR 40 left none: a look-up whose row is a node, slot or group axis is a
# dense contraction (autoscale._rows_at / _rows_put / _segment_sums). A new
# one here is paid per index on the TPU: 4.5-10 ns each, 0.3-0.5 ms a window
# for a (C, S) read.
CA_CENSUS = {
    "autoscaled.stream": {"stablehlo.gather": 0, "stablehlo.scatter": 0},
    "autoscaled.whatif": {"stablehlo.gather": 0, "stablehlo.scatter": 0},
}


@pytest.mark.parametrize("cell", sorted(CA_CENSUS))
def test_the_cluster_autoscaler_lowers_no_per_index_look_up(cell):
    found = collections.Counter()
    everywhere = collections.Counter()
    for op, paths in rehearsal_op_paths(cell)[0]:
        if op in ("stablehlo.gather", "stablehlo.scatter"):
            everywhere[op] += 1
            tops = {(phase_of(path) or ("",))[0] for path in paths}
            if tops & {"ca_pass", "ca_reclaim"}:
                found[op] += 1
    assert dict(found) == {op: n for op, n in CA_CENSUS[cell].items() if n}
    # The census sees the ops it is there to see: the passes of the window
    # program PR 40 left alone (the HPA, the event application, the cycle's
    # queue) still gather: 19 in each cell's program as PR 42 read them
    # (events 9, hpa_pass 6, cycle 4). The slide is no part of `run_windows`:
    # its census is below.
    assert everywhere["stablehlo.gather"] >= 10


# --- (e) the census of per-index look-ups in the slide -------------------------

# The two cells whose pod window slides, both through `step.run_superspan`.
# At the parent (a205d3b) the slide branch lowered 27 gathers in the stream's
# program (21 pod planes and the name ranks by `take_along_axis` over (C, P)
# indices, 5 payload planes the same way) and 25 in the replay's (no name
# ranks): 4.17 of the stream's 10.8 ms a window on the chip (PERF.md section
# 6, PR 42). Shift and base are one scalar each for the batch, so PR 42 moves
# every plane with a `dynamic_slice` at that scalar.
SLIDE_CELLS = ("autoscaled.stream", "alibaba1313.replay")


def lowered_superspan_program(sim, last=40):
    """The superspan program the engine would dispatch from where it stands,
    lowered (tests/test_batched_sharding.py compiles the same)."""
    from kubernetriks_tpu.batched import step

    stage, lo = sim._current_stage()
    rank = None if sim.autoscale_statics is None else sim.autoscale_statics.pod_name_rank
    return step.run_superspan.lower(
        sim.state,
        rank,
        jnp.asarray([0, sim._pod_base, 0, step.SUPERSPAN_RUN], jnp.int32),
        sim.slab,
        sim.consts,
        stage,
        jnp.int32(lo),
        jnp.int32(last),
        W=sim.pod_window,
        K=sim._superspan_k,
        chunk=sim._superspan_chunk,
        **sim._window_call_kwargs(),
    )


@pytest.mark.parametrize("cell", SLIDE_CELLS)
def test_the_slide_lowers_no_per_index_look_up(cell):
    sim = wpd.rehearsal_engine(cell)
    try:
        assert sim._superspan_ok(), "the cell's rehearsal build does not dispatch superspans"
        ops = lowered_op_paths(lowered_superspan_program(sim))
        planes = len(jax.tree.leaves(sim.state.pods)) + (sim.autoscale_statics is not None)
    finally:
        sim.close()
    slide = collections.Counter()
    for op, paths in ops:
        if {(phase_of(path) or ("",))[0] for path in paths} == {"slide"}:
            slide[op] += 1
    assert slide["stablehlo.gather"] == 0 and slide["stablehlo.scatter"] == 0, slide
    # Not vacuous: the branch is there, a block move a pod plane and a block
    # read a payload plane (and the shift's and the capacity read's slices).
    assert slide["stablehlo.dynamic_slice"] >= planes + 4, slide
    assert slide["stablehlo.case"] >= 1  # the slide branch's apply / skip


def test_gather_instructions_reads_the_primitive_off_the_op_name():
    text = """HloModule m
ENTRY %main (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p), kind=kCustom, calls=%fc, metadata={op_name="jit(f)/ca_pass/gather"}
  %reshape.2 = s32[8]{0} reshape(%fusion.1), metadata={op_name="jit(f)/ca_pass/cond/branch_1_fun/gather"}
  %fusion.3 = s32[8]{0} fusion(%reshape.2), kind=kLoop, calls=%fd, metadata={op_name="jit(f)/ca_pass/gather_like/add"}
  ROOT %copy.4 = s32[8]{0} copy(%fusion.3)
}
"""
    assert gather_instructions(text) == {"fusion.1", "reshape.2"}


# --- (b) the map of a compiled program ---------------------------------------

# Optimized HLO as the TPU compiler prints it (cut down from a chip run of
# `sched1k.montecarlo`): a Mosaic kernel is a custom call of its own under
# the scope that launched it, its wrapper's pads are ops of `kernel_io`, a
# fusion's inner instructions are not device ops, the copies the compiler
# lays operands out with have no `op_name` (or only the loop's).
HLO_TEXT = """\
HloModule jit__run_windows_impl, is_scheduled=true, entry_computation_layout={(s32[4]{0})->s32[4]{0}}

FileNames
1 "/root/repo/kubernetriks_tpu/batched/step.py"

%fused_computation.7 (param_0.1: s32[4]) -> s32[4] {
  %param_0.1 = s32[4]{0} parameter(0)
  %constant.9 = s32[] constant(1), metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/events/add"}
  %broadcast.3 = s32[4]{0} broadcast(%constant.9), dimensions={}
  ROOT %add.12 = s32[4]{0} add(%param_0.1, %broadcast.3), metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/events/add"}
}

%region_add.4 (a.1: s32[], b.1: s32[]) -> s32[] {
  %a.1 = s32[] parameter(0)
  %b.1 = s32[] parameter(1)
  ROOT %add.13 = s32[] add(%a.1, %b.1), metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/cycle/reduce_sum"}
}

%branch_run.2 (arg.5: s32[4]) -> s32[4] {
  %arg.5 = s32[4]{0} parameter(0)
  %pad.180 = s32[8]{0} pad(%arg.5, %constant.2), padding=0_4, metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/events/cond/branch_1_fun/kernel_io/jit(_pad)/pad" stack_frame_id=4}
  %fused_free_resources.1 = s32[8]{0:T(1024)} custom-call(%pad.180), custom_call_target="tpu_custom_call", metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/events/cond/branch_1_fun/fused_free_resources"}
  ROOT %slice.31 = s32[4]{0} slice(%fused_free_resources.1), slice={[0:4]}, metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/events/cond/branch_1_fun/kernel_io/slice"}
}

%branch_skip.3 (arg.6: s32[4]) -> s32[4] {
  %arg.6 = s32[4]{0} parameter(0)
  ROOT %copy.402 = s32[4]{0} copy(%arg.6), metadata={op_name="jit(_run_windows_impl)/while/body/closed_call"}
}

%body.10 (loop.1: (s32[], s32[4])) -> (s32[], s32[4]) {
  %loop.1 = (s32[], s32[4]{0}) parameter(0)
  %get-tuple-element.5 = s32[4]{0} get-tuple-element(%loop.1), index=1
  %fusion.79 = s32[4]{0} fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/events/add"}
  %copy.401 = s32[4]{0} copy(%get-tuple-element.5)
  %conditional.4 = s32[4]{0} conditional(%pred.1, %fusion.79, %copy.401), true_computation=%branch_run.2, false_computation=%branch_skip.3, metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/events/cond"}
  %copy-start.7 = (s32[4]{0}, s32[4]{0}, u32[]) copy-start(%conditional.4)
  %copy-done.7 = s32[4]{0} copy-done(%copy-start.7)
  %copy.333 = s32[4]{0} copy(%copy-done.7), metadata={op_name="jit(_run_windows_impl)/while/body/closed_call"}
  %copy.400 = s32[4]{0} copy(%fusion.79)
  %pad.185 = s32[8]{0} pad(%copy.333, %constant.2), padding=0_4, metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/cycle/kernel_io/jit(_pad)/pad"}
  %fused_select_cycle_commit.2 = (s32[8]{0}, s32[8]{0}) custom-call(%pad.185), custom_call_target="tpu_custom_call", metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/cycle/fused_select_cycle_commit"}
  %reduce.6 = s32[] reduce(%pad.185, %copy.401), dimensions={0}, to_apply=%region_add.4, metadata={op_name="jit(_run_windows_impl)/while/body/closed_call/cycle/reduce_sum"}
  ROOT %tuple.8 = (s32[], s32[4]{0}, s32[4]{0}) tuple(%reduce.6, %copy.333, %copy.400)
}

%cond.11 (loop.2: (s32[], s32[4])) -> pred[] {
  %loop.2 = (s32[], s32[4]{0}) parameter(0)
  ROOT %compare.3 = pred[] compare(%get-tuple-element.9, %constant.4), direction=LT, metadata={op_name="jit(_run_windows_impl)/while/cond/lt"}
}

ENTRY %main.20 (state.1: s32[4]) -> s32[4] {
  %state.1 = s32[4]{0} parameter(0), metadata={op_name="state.time"}
  %transpose.2 = s32[4]{0} transpose(%state.1), dimensions={0}, metadata={op_name="jit(_run_windows_impl)/bookkeeping/transpose"}
  %while.193 = (s32[], s32[4]{0}) while(%tuple.1), condition=%cond.11, body=%body.10, metadata={op_name="jit(_run_windows_impl)/while"}
  ROOT %get-tuple-element.11 = s32[4]{0} get-tuple-element(%while.193), index=1
}
"""


def test_instruction_phases_reads_optimized_hlo_text():
    phases = instruction_phases(HLO_TEXT)
    # A kernel's custom call goes to the phase that launched it, its
    # wrapper's pads and slices to `kernel_io` under that phase.
    assert phases["fused_select_cycle_commit.2"] == ("cycle", "cycle", "scope")
    assert phases["fused_free_resources.1"] == ("events", "events", "scope")
    assert phases["pad.185"] == ("cycle", "kernel_io", "scope")
    assert phases["pad.180"] == ("events", "kernel_io", "scope")
    assert phases["slice.31"] == ("events", "kernel_io", "scope")
    assert phases["fusion.79"] == ("events", "events", "scope")
    assert phases["reduce.6"] == ("cycle", "cycle", "scope")
    assert phases["transpose.2"] == ("bookkeeping", "bookkeeping", "scope")
    # An instruction the compiler made without a phase takes its consumers':
    # the copies that lay out the cycle kernel's operand, followed through
    # each other to the pad that reads them ...
    assert phases["copy.333"] == ("cycle", "kernel_io", "consumer")
    assert phases["copy-done.7"] == phases["copy-start.7"] == ("cycle", "kernel_io", "consumer")
    # ... or, where only the loop's carry reads it, its producers' ...
    assert phases["copy.400"] == ("events", "events", "producer")
    # ... and none where its consumers disagree (the branch and the reduce)
    # or nothing near it names a phase.
    assert phases["copy.401"] is None
    # A branch that only hands its operand on in another layout names no
    # phase and has no neighbour that does: the conditional that runs it does.
    assert phases["copy.402"] == phases["arg.6"] == ("events", "events", "caller")
    assert phases["while.193"] is None and phases["compare.3"] is None
    # What runs inside another op is no device op of its own: a fusion's
    # and a reduce's computation.
    assert not {"add.12", "broadcast.3", "add.13", "param_0.1"} & set(phases)
    # Loop body, loop condition and both branches are.
    assert {"conditional.4", "arg.6", "get-tuple-element.5", "tuple.8"} <= set(phases)
    assert all(p is None or (p[:2] == phase_of("/".join(p[:2])) and len(p) == 3) for p in phases.values())


@pytest.fixture(scope="module")
def toy():
    """A toy engine on the dense kernel set (interpreted), stepped, under a
    sentinel sealed after the dispatches; its handle's programs, and the
    instant before its build (the recorder is the worker's: it holds the
    programs of every earlier test's engines, whose executables jax may have
    dropped since)."""
    sentinel = RecompileSentinel(mode="warn").install()
    before = set(recorder()._programs)
    since = time.perf_counter_ns()
    sim = _build_plain(use_pallas=True, pallas_interpret=True)
    sim.use_pallas_select = True
    sim.use_megakernel = True
    assert sim.kernel_formulation()["cycle"] == "megakernel"
    sim.step_until_time(150.0)
    sentinel.seal("dispatched")
    yield sim, sentinel, [key for key in recorder()._programs if key not in before], since
    sentinel.uninstall()
    sim.close()


def test_noting_a_program_compiles_nothing_and_reports_nothing(toy):
    sim, sentinel, keys, _ = toy
    assert [key[1] for key in keys] == ["run_windows"]
    program = recorder()._programs[keys[0]]
    if program.phases is None:  # nobody has asked yet
        assert sim.telemetry_report()["device_phases"] == {
            "phases": list(DEVICE_PHASES),
            "programs": {},
            "gathers": {},
        }
        # What is kept is shapes: no array, no device memory.
        import jax

        kept = jax.tree.leaves((program.args, program.kwargs))
        assert kept and not any(isinstance(leaf, jax.Array) for leaf in kept)
    assert sentinel.post_seal_events() == []


def test_program_phases_of_a_compiled_toy_window_program(toy):
    sim, sentinel, keys, since = toy
    program = recorder()._programs[keys[0]]
    fn, args, kwargs = program.fn, program.args, program.kwargs
    programs = recorder().program_phases(since_ns=since)
    # On demand, and then jax hands back the executable the dispatch ran:
    # no compilation, no load from the persistent cache.
    assert sentinel.post_seal_events() == []
    (label,) = [name for name in programs if name.endswith(f"@engine{keys[0][0]}")]
    assert label.startswith("run_windows[")
    phases = programs[label]
    # The instruction names are the compiled module's own.
    if fn is not None:
        text = fn.lower(*args, **kwargs).compile().as_text()
        own = set(re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s=\s", text, re.M))
        assert set(phases) <= own and len(phases) > 300
    counts = collections.Counter(phases.values())
    # The interpreted kernels' ops sit under the phase that launches them,
    # the wrappers' pads and slices under `kernel_io` inside it.
    assert counts[("cycle", "cycle", "scope")] > 50 and counts[("events", "events", "scope")] > 50
    assert counts[("cycle", "kernel_io", "scope")] > 0 and counts[("events", "kernel_io", "scope")] > 0
    assert any(name.startswith("pad") or "pad" in name for name, p in phases.items() if p and p[1] == "kernel_io")
    assert {p[0] for p in phases.values() if p} <= set(DEVICE_PHASES) - {"kernel_io"}
    # Kept: a second call reads nothing again.
    assert recorder().program_phases(since_ns=since)[label] is phases
    report = sim.telemetry_report()["device_phases"]
    assert report["phases"] == list(DEVICE_PHASES)
    assert report["programs"][label]["cycle"] == sum(
        n for p, n in counts.items() if p and p[0] == "cycle"
    )
    assert report["programs"][label]["unscoped"] == counts[None]
    assert report["programs"][label]["inherited"] == sum(n for p, n in counts.items() if p and p[2] != "scope") > 0
    # Beside them, a phase, the instructions that are XLA gathers (PR 40):
    # the compiled module's own, by the primitive their `op_name` ends in.
    gathered = report["gathers"][label]
    assert set(gathered) <= set(report["programs"][label]) - {"inherited"}
    assert all(0 < n <= report["programs"][label][phase] for phase, n in gathered.items())
    if fn is not None:
        wanted = collections.Counter(
            phases[name][0] if phases[name] else "unscoped" for name in gather_instructions(text) if name in phases
        )
        assert gathered == dict(wanted) and sum(wanted.values()) > 0


# --- (c) two engines ----------------------------------------------------------


def test_a_second_engine_adds_its_programs_and_drops_none(toy):
    sim, _, keys, toy_since = toy
    before = dict(recorder()._programs)
    other = _build_plain()
    try:
        other.step_until_time(150.0)
        after = recorder()._programs
        added = [key for key in after if key not in before]
        assert [key[1] for key in added] == ["run_windows"]
        assert added[0][0] != keys[0][0]  # another engine's handle
        assert all(after[key] is program for key, program in before.items())
        # Each engine reports its own programs, the recorder all of them.
        recorder().program_phases(since_ns=toy_since)
        mine = sim.telemetry_report()["device_phases"]["programs"]
        theirs = other.telemetry_report()["device_phases"]["programs"]
        assert mine and theirs and not set(mine) & set(theirs)
        # An engine's handle reads its own programs alone (the CLI's report).
        assert set(other.tracer.program_phases()) == set(theirs)
        everyone = recorder().report()["device_phases"]["programs"]
        assert set(mine) | set(theirs) <= set(everyone)
        # A later dispatch of a noted program (the same 16-window chunk) is
        # one lookup: nothing new. It leaves its time, so a reader can ask
        # for the programs that ran since: the other engine's, not the toy's.
        n = len(recorder()._programs)
        since = time.perf_counter_ns()
        other.step_until_time(310.0)
        assert len(recorder()._programs) == n
        assert set(recorder().program_phases(since_ns=since)) == set(theirs)
        # ... and until: nothing was first dispatched before the toy was.
        first = min(program.first_ns for program in recorder()._programs.values())
        assert recorder().program_phases(until_ns=first) == {}
        until = after[added[0]].first_ns  # before the other engine's first dispatch
        assert set(recorder().program_phases(since_ns=toy_since, until_ns=until)) == set(mine)
    finally:
        other.close()
