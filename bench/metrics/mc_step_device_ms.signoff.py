"""Mean device time of one streaming Monte-Carlo step program, in ms.

Read from the trace: the ``XLA Modules`` events of the engine's chunk step
(``StreamingMCMachine._step``), averaged over the steps and the chips
that ran them.
"""
from benchlib import trace


def read(summary, counters, readings, peaks):
    s = trace.module_mean_s(summary, readings["step_program"])
    return None if s is None else 1e3 * s
