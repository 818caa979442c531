"""Sequential slices the analog variant lanes of one streaming Monte-Carlo
step run in: the engine's ``lane_slices_run`` over its ``steps_run`` in
the window (1 where the lanes are one ``vmap``)."""


def read(summary, counters, readings, peaks):
    if "lane_slices_run" not in counters or not counters.get("steps_run"):
        return None
    return counters["lane_slices_run"] / counters["steps_run"]
