"""The counted signoff generator and the readers of its counters: the
check and the metric are ``signoff.py``'s own, and a counter the program
lacks leaves its reader silent."""
from benchlib import registry

reg = registry.Registry()


def test_counted_signoff_reexports_signoff():
    counted, signoff = reg.generator("signoff_counted"), \
        reg.generator("signoff")
    for fn in ("setup", "check", "control", "release"):
        assert getattr(counted, fn) is getattr(signoff, fn)


def test_slices_per_step():
    read = reg.reader("mc_lane_slices.signoff").read
    assert read(None, {"lane_slices_run": 48, "steps_run": 4}, {}, {}) \
        == 12.0
    # a program without the counter (the parent of the change adding it)
    assert read(None, {"steps_run": 4}, {}, {}) is None


def test_useful_share_of_lane_cells():
    read = reg.reader("mc_lane_useful_share.signoff").read
    counters = {"variants_run": 1024, "lane_cells_run": 4 * 256 * 128 * 300}
    readings = {"needed_cells": 75, "rows": 128}
    assert read(None, counters, readings, {}) == 25.0
    assert read(None, {"variants_run": 1024}, readings, {}) is None
