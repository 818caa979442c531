"""Sweep offered load on a serving cell to find its knee, once, on the chip.

    python3 bench/knee.py --workload serve.balance --rates 1000 2000 4000 \
        --seconds 10 --seed 7

The cell must be listed in ``BENCHMARK.json`` (``serve.balance`` is held:
add its entries from ``bench/tests/data/held_cells.json``).  One process
sets the cell up once, then offers each rate for ``--seconds``
with the cell's own generator and engine settings.  For each rate it
prints one JSON line: latency percentiles from the due time, the rate
completed, and whether the backlog grew (the p95 of the last third of the
window over that of the first third).  The knee is the highest rate that
completes what it is offered without a growing backlog; the cell's rate
is set at about four fifths of it.  The sweep stops at the first rate
whose completed rate falls under four fifths of what was offered.
"""
import argparse
import copy
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from benchlib import device, harness, registry

    reg = registry.Registry()
    cell = reg.cell(args.workload)
    harness.setup_jax(reg.root)
    devs = device.gate(cell["chips"])
    traffic = copy.deepcopy(reg.traffic(cell["traffic"]))
    generator = reg.generator(traffic["generator"])
    ctx = harness.Ctx(cell=cell, config=reg.config(cell["config"]),
                      traffic=traffic, seed=args.seed, devices=devs)
    state = generator.setup(ctx)
    gc.collect()
    gc.freeze()                    # as a run does after its set-up
    for rate in args.rates:
        traffic["rate_per_s"] = rate
        state.engine.stats.reset()
        out = generator.run(state, args.seconds)
        lat = state.last_latency_ms
        third = max(1, len(lat) // 3)
        first = float(np.percentile(lat[:third], 95))
        last = float(np.percentile(lat[-third:], 95))
        print(json.dumps({
            "rate_per_s": rate, "requests": out.attempted,
            "failed": out.failed,
            "completed_per_s": state.last_completed_per_s,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": out.e2e["serve_p95_ms"],
            "p99_ms": float(np.percentile(lat, 99)),
            "p95_first_third_ms": first, "p95_last_third_ms": last,
            "lateness_p95_ms": out.counters["lateness_p95_ms"],
            "rows_per_batch": (out.counters["n_queries"]
                               / max(out.counters["n_batches"], 1))}),
            flush=True)
        if state.last_completed_per_s < 0.8 * rate:
            break                      # past the knee: the backlog grows
    generator.release(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
