"""Of the last job's scheduling decisions, the share that placed a pod again
after its node crashed under it, in percent: the program's
`pod_interruptions` over its decisions, as `metrics_summary()` published them
after the window (the driver copies them into its counters). The fault path's
own reading: 0 where no crash ever meets a running pod, and nothing to read
where the program publishes no such counter."""


def read(run):
    interrupted = run.counters.get("pod_interruptions")
    decisions = run.counters.get("decisions_last_job")
    if interrupted is None or not decisions:
        return None
    return 100.0 * interrupted / decisions
