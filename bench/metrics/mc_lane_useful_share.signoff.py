"""Share of the analog lane cells the streaming Monte-Carlo steps computed
that the signed-off assignment needs, in %.

Needed: the assignment's analog pairs at their true support counts
(``needed_cells`` per variant-row, from the plain reference's deployment)
times the variant-rows of the window (``variants_run`` x ``rows``).
Computed: the engine's ``lane_cells_run``, every step's chunk x rows x
padded ``(P, M)`` slots of its candidate banks.
"""


def read(summary, counters, readings, peaks):
    cells = counters.get("lane_cells_run")
    if not cells or "variants_run" not in counters \
            or "needed_cells" not in readings:
        return None
    needed = readings["needed_cells"] * counters["variants_run"] \
        * readings["rows"]
    return 100.0 * needed / cells
