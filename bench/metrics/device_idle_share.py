"""Idle share of the chips in the window, in %: one minus the
union of device op intervals over the traced window, averaged over the
chips that ran anything (each chip's own share is printed earlier)."""
from benchlib import trace


def read(summary, counters, readings, peaks):
    share = trace.idle_share(summary)
    return None if share is None else 100.0 * share
