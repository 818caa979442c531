"""Run one benchmark cell once on the chips of this host.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are data: see
``BENCHMARK.json`` and ``bench/benchlib/registry.py``.  With no TPU, or
fewer chips than the cell asks for, the run exits non-zero and prints no
result.  The last line of standard output is the result object; the last
lines of standard error are the numbers compared with the reference, each
with its limit.  ``--trace-out PATH`` also writes the normalised profiler
trace of a ``--trace 1`` run to ``PATH``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args(argv)
    from benchlib import harness

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), trace_out=args.trace_out,
                              t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
