"""Host dispatches per whole job: the delta of engine.dispatch_stats
(window_chunks + superspans + stage_refills) over the window's jobs."""


def read(run):
    return run.counters.get("dispatches_per_job")
