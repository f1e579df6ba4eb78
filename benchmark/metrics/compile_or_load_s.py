"""Seconds the program spent compiling or loading programs from the
persistent cache before the window opened: the sum of the recorder's `compile`
rows (one per 'Finished XLA compilation' line of jax's log while the run's
recompile sentinel was installed, duration = the logged seconds). The ten
largest are printed by name on a `compiles` line."""

from benchmark import program_spans
from benchmark.harness import say


def read(run):
    rows = program_spans.setup_rows(run)
    if rows is None:
        return None
    compiles = rows.of("compile")
    if not len(compiles):
        return None
    names = program_spans.compile_names(compiles)
    largest = sorted(zip(compiles[:, program_spans.DUR].tolist(), names), reverse=True)[:10]
    total_s = float(compiles[:, program_spans.DUR].sum()) / 1e9
    say(line="compiles", programs=len(compiles), seconds=total_s,
        largest=[[name, ns / 1e9] for ns, name in largest])
    return total_s
