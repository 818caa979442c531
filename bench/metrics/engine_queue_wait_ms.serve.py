"""Median queue wait of the engine, in ms: ``ServingStats``' queue-wait
p50 (submit to dispatch, from its reservoir sample) over the window."""


def read(summary, counters, readings, peaks):
    return counters.get("queue_wait_ms_p50")
