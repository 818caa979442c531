"""Tests of the benchmark's own code; they run on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

HELD = os.path.join(BENCH, "tests", "data", "held_cells.json")


def with_held(spec: dict) -> dict:
    """``spec`` with the cells held out of BENCHMARK.json added back, as a
    later PR would add them: entries only."""
    with open(HELD) as f:
        held = json.load(f)
    for key in ("workloads", "end_to_end", "per_layer"):
        spec[key] = spec[key] + held[key]
    return spec


@pytest.fixture(scope="session")
def held_root(tmp_path_factory):
    """A checkout (the program and the benchmark, linked) whose
    BENCHMARK.json also holds the held cells."""
    root = tmp_path_factory.mktemp("held")
    for name in ("bench", "src"):
        os.symlink(os.path.join(ROOT, name), root / name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = with_held(json.load(f))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)
