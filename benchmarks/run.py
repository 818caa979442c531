"""Benchmark orchestrator: one entry per paper table/figure + kernels +
roofline.  Prints ``name,us_per_call,derived`` style CSV blocks.

``--json PATH`` additionally aggregates every machine-readable sub-result
(currently fig4, svm_infer, svm_train, serving, scale, pareto and
montecarlo — including the streaming V=64..1e6 scaling curve; more as
benchmarks grow JSON output) into one file suitable for BENCH_*.json
trajectory tracking.

Table2 / fig5 / pareto share per-dataset Algorithm-1 fits through
``benchmarks._fit_cache`` — each dataset is fitted once per process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Script-mode robustness: `python benchmarks/run.py` puts benchmarks/ (not
# the repo root) on sys.path, breaking the `from benchmarks import ...`
# imports that `python -m benchmarks.run` resolves fine.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write aggregated machine-readable results here")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    results: dict[str, dict] = {}

    print("== Fig. 4: analog behavioral-model fidelity ==")
    from benchmarks import fig4
    results["fig4"] = fig4.run(n_variation=32 if args.json else 0)

    print("\n== Table II: accuracy / area / power ==")
    from benchmarks import table2
    table2.run()

    print("\n== Fig. 5: analog/digital breakdown ==")
    from benchmarks import fig5
    fig5.run()

    print("\n== Pareto: kernel-assignment design-space exploration ==")
    from benchmarks import pareto
    results["pareto"] = pareto.run()

    print("\n== Monte-Carlo: variation-aware yield sweep ==")
    from benchmarks import montecarlo
    results["montecarlo"] = montecarlo.run()
    if args.json:
        # Trajectory files record the full streaming signoff curve
        # (DESIGN.md §10); interactive runs skip the ~15 min V=1e6 leg.
        print("\n== Monte-Carlo: streaming scaling curve V=64..1e6 ==")
        results["montecarlo"]["scaling"] = montecarlo.run_scaling()

    print("\n== SVM inference: object path vs compiled machine ==")
    from benchmarks import svm_infer
    results["svm_infer"] = svm_infer.run()

    print("\n== SVM training: sequential loop vs batched engine ==")
    from benchmarks import svm_train
    results["svm_train"] = svm_train.run()

    print("\n== Serving: streaming engine vs naive per-request dispatch ==")
    from benchmarks import serving
    results["serving"] = serving.run()

    print("\n== Scale-out: K=12 DAG front, lane ladder, portfolio DSE ==")
    from benchmarks import scale
    results["scale"] = scale.run()

    print("\n== Kernel micro-bench (Pallas interpret vs jnp oracle) ==")
    from benchmarks import kernelbench
    kernelbench.run()

    if os.path.isdir("runs/dryrun") and os.listdir("runs/dryrun"):
        print("\n== Roofline (single-pod 16x16) ==")
        from benchmarks import roofline
        roofline.run()
    else:
        print("\n(roofline skipped: run `python -m repro.launch.dryrun "
              "--all --mesh both` first)")
    total = time.time() - t0
    print(f"\ntotal_bench_seconds,{total:.1f}")

    if args.json:
        payload = {"machine": _machine_note(),
                   "total_bench_seconds": round(total, 1), **results}
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"aggregated JSON -> {args.json}")


def _machine_note() -> dict:
    """Reproducibility header for BENCH_*.json trajectory files: where the
    numbers came from and the seed policy.  Every sub-benchmark uses fixed
    seeds internally (RandomState(0)/PRNGKey(0) unless its JSON record
    says otherwise), so a trajectory diff isolates code changes."""
    import platform

    import jax

    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "seed_policy": "fixed seeds (0) per sub-benchmark; explicit "
                       "seeds/keys recorded in each record",
    }


if __name__ == "__main__":
    main()
