"""Yield signoff with the program's own counters beside it.

The traffic, the end-to-end metric, the check and the control are those of
``signoff.py``, imported through the registry's loader and re-exported
unchanged.  Two functions add to them:

* ``run`` runs ``signoff.run`` and adds to its ``WindowOut.counters`` the
  window's deltas of the engine machine's work totals (``steps_run``,
  ``variants_run``, ``lane_cells_run``, ``lane_slices_run``) and of
  ``repro.obs.snapshot()``;
* ``readings`` adds ``needed_cells``, the analog cells of the signed-off
  assignment at their true support counts per variant-row (from the plain
  reference's deployment), and the rows of a job (``rows``).

A counter the program does not keep is left out, so the cell also runs on
a program that lacks it, and the readers of such a counter read nothing.
"""
from __future__ import annotations

import os

from benchlib import registry

signoff = registry.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "signoff.py"),
    "generator")

setup = signoff.setup
check = signoff.check
control = signoff.control
release = signoff.release

#: Work totals of ``StreamingMCMachine`` read around the window.
TOTALS = ("steps_run", "variants_run", "lane_cells_run", "lane_slices_run")


def _totals(s) -> dict:
    sm = s.est.stream_machine(s.key, method="iid", mc_chunk=s.chunk)
    out = {k: getattr(sm, k) for k in TOTALS if hasattr(sm, k)}
    try:
        from repro import obs
    except ImportError:
        return out
    out.update(obs.snapshot())
    return out


def run(s, seconds: float):
    before = _totals(s)
    out = signoff.run(s, seconds)
    after = _totals(s)
    out.counters.update({k: after[k] - before[k] for k in after
                         if k in before})
    return out


def readings(s) -> dict:
    from reference.circuit import Machine

    ref = Machine(s.ctx.config, s.ctx.config["_dir"])
    needed = sum(a["m"] for a, kind in zip(ref.analog, ref.kernel_map)
                 if kind == "rbf")
    return dict(signoff.readings(s), needed_cells=needed,
                rows=int(s.ctx.traffic["rows"]))
