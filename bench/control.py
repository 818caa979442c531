"""Readings the correctness limits are set from, at a cell's own size.

    python3 bench/control.py --workload signoff.balance --seeds 1 2 3

(A cell held out of ``BENCHMARK.json``, such as ``serve.balance``, needs its
entries from ``bench/tests/data/held_cells.json`` added first.)

Prints, as JSON lines, the numbers the cell's check compares, read for the
program (the lower reading) and for the control, the plain reference
computed in bfloat16 in the program's place (the upper reading).  What is
read is the cell's generator's own ``control`` function
(``bench/generators/<generator>.py``), found by the name in its traffic
file, as ``run.py`` finds the generator.  Exits non-zero without a TPU
unless ``--no-gate`` (tests only).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def readings(workload: str, seeds, seconds: float, emit,
             gate: bool = True, root: str = None) -> None:
    from benchlib import device, harness, registry

    reg = registry.Registry(root or registry.ROOT)
    cell = reg.cell(workload)
    harness.setup_jax(reg.root)
    devs = device.gate(cell["chips"]) if gate else None
    traffic = reg.traffic(cell["traffic"])
    generator = reg.generator(traffic["generator"])

    def ctx_for(seed):
        return harness.Ctx(cell=cell, config=reg.config(cell["config"]),
                           traffic=traffic, seed=seed, devices=devs)

    generator.control(ctx_for, list(seeds), seconds, emit)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--no-gate", action="store_true")
    args = ap.parse_args(argv)

    def emit(row):
        print(json.dumps(row), flush=True)

    readings(args.workload, args.seeds, args.seconds, emit,
             gate=not args.no_gate)
    return 0


if __name__ == "__main__":
    sys.exit(main())
