"""Seconds in the engine (or fleet) build and the first dispatch after it,
where every program compiles or loads from the cache: harness spans."""


def read(run):
    spans = run.spans
    return spans.total("engine_build") + spans.total("first_dispatch")
