"""Mean device time of one fleet forward dispatch, in ms: the ``XLA
Modules`` events of the fleet's labels program (``FleetMachine._labels``)
in the traced window."""
from benchlib import trace


def read(summary, counters, readings, peaks):
    s = trace.module_mean_s(summary, "jit__labels")
    return None if s is None else 1e3 * s
