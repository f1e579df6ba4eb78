"""XLA compilations the recompile sentinel saw before it was sealed (hit or
miss of the persistent cache alike)."""


def read(run):
    return run.counters.get("compiles_in_setup")
