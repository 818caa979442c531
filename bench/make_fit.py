"""Make a configuration's data on one chip: the fitted machine and the test
split.

    python3 bench/make_fit.py --config balance --out out/balance

Reads the configuration's ``recipe`` (the dataset and the estimator's
arguments), fits ``MixedKernelSVM(**estimator)`` by Algorithm 1 on the
training split of ``repro.data.datasets.load(dataset)`` and saves it with
``save()`` as ``<out>_fit.npz`` / ``<out>_fit.json``; writes the test
split as ``<out>_test.npz`` (features float32, labels int32); prints the
sha256 of each file.  Copy the files to ``bench/configs/`` and their
sha256 into the configuration file, whose ``provenance`` names the run
that made the committed ones.
"""
import argparse
import hashlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from benchlib import device, harness, registry

    reg = registry.Registry()
    recipe = reg.config(args.config)["recipe"]
    harness.setup_jax(reg.root)
    device.gate(1)
    import numpy as np

    from repro.api import MixedKernelSVM
    from repro.data import datasets

    ds = datasets.load(recipe["dataset"])
    t0 = time.perf_counter()
    est = MixedKernelSVM(**recipe["estimator"]).fit(ds.x_train, ds.y_train)
    print(f"fit: {time.perf_counter() - t0:.1f} s, K={est.n_classes_} "
          f"P={len(est.pairs_)} kernel map {est.kernel_map_}", flush=True)
    print(est.deploy("circuit").describe(), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    est.save(args.out + "_fit")
    np.savez(args.out + "_test.npz", x=ds.x_test.astype(np.float32),
             y=ds.y_test.astype(np.int32))
    for suffix in ("_fit.npz", "_fit.json", "_test.npz"):
        with open(args.out + suffix, "rb") as f:
            print(f"sha256 {os.path.basename(args.out + suffix)} "
                  f"{hashlib.sha256(f.read()).hexdigest()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
