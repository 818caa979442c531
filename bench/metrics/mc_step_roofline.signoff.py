"""Share of the chip's roofline reached by one Monte-Carlo step, in %.

``max(ops / peak FLOP/s, bytes / peak B/s)`` over the step's mean device
time, with ops and bytes counted by ``benchlib.counts.signoff_step`` for
the work the signed-off assignment needs (its analog pairs at their true
support counts, its linear pairs, the recombination and the fold).
"""
import sys

from benchlib import counts, trace


def read(summary, counters, readings, peaks):
    t = trace.module_mean_s(summary, readings["step_program"])
    if t is None:
        return None
    share, bound = counts.roofline_share(
        readings["step_ops"], readings["step_bytes"], t, peaks)
    print(f"[roofline] mc step: {readings['step_ops']:.6e} ops, "
          f"{readings['step_bytes']:.6e} bytes in {t:.6f} s: "
          f"{share:.6e} % ({bound} bound)", file=sys.stderr)
    return share
