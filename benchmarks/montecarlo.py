"""Monte-Carlo variation benchmark: the variant axis end-to-end (§6).

For each paper dataset, runs the variation-aware kernel-assignment sweep
(``MixedKernelSVM.pareto(n_variants=...)``) — every assignment scored with
mean/std/worst-case accuracy and yield over V sampled fabricated instances
— and reports:

* **variants/s** — throughput of the one-jitted-forward
  ``MonteCarloMachine`` at the reference V,
* **compile budget** — the variant axis must cost at most 2 additional jit
  compiles over the nominal DSE path (the MC forward + the batched
  recombination); ``--assert-compiles`` turns the measurement into a gate,
* **nominal bit-identity** — variant 0 (zero offsets) must reproduce the
  nominal ``CandidateMachine`` bits AND scores bit-exactly
  (``--assert-nominal`` gates it; DESIGN.md §6.3),
* **yield-vs-sigma** — the Algorithm-1 circuit's accuracy distribution and
  yield as the process sigmas scale jointly (0.5x .. 4x),
* **nominal vs robust vertex** — where the Algorithm-1 design sits in
  mean/worst/yield terms, and what the robust rule
  (``select(yield_floor=...)``) deploys instead.

All mismatch is drawn from explicit jax PRNG keys derived from
``--mc-seed``; the seed is recorded in the JSON for reproducibility.

  PYTHONPATH=src python benchmarks/montecarlo.py [--out montecarlo.json]
                                                 [--assert-nominal]
                                                 [--assert-compiles]

The streaming scaling leg (``--scaling``; DESIGN.md §10) sweeps the
flat-memory engine from V = 64 to ``--v-max`` (default 10^6) on one
dataset and records variants/s, the streamed yield + its confidence
interval, and the XLA ``memory_analysis`` of the one compiled chunk
step.  ``--assert-flat-memory`` gates that every ladder point ran
through that SAME program (zero extra compiles, identical peak temp
bytes); ``--assert-ci-width`` gates the final yield CI width.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

try:
    from benchmarks import _fit_cache
    from benchmarks.svm_train import count_compiles
except ImportError:  # run as a script: benchmarks/ itself is sys.path[0]
    import _fit_cache
    from svm_train import count_compiles

#: Reference variant count (the acceptance setting) and sigma ladder.
N_VARIANTS = 64
SIGMA_SCALES = (0.5, 1.0, 2.0, 4.0)

#: The variant axis may cost at most this many extra jit compiles.
MAX_MC_COMPILES = 2

#: Yield floors the robust deployment rule is probed at.
YIELD_FLOORS = (0.5, 0.9)

#: Streaming scaling-ladder defaults: V multiplies by 16 from 64 up to
#: --v-max; the chunk step is compiled ONCE for every ladder point.
SCALING_DATASET = "balance"
SCALING_CHUNK = 2048
SCALING_X = 64


def run(n_epochs: int = 120, seed: int = 0, mc_seed: int = 0,
        n_variants: int = N_VARIANTS,
        sigma_scales: tuple = SIGMA_SCALES,
        verbose: bool = True) -> dict:
    import jax

    from repro.core import dse
    from repro.data import datasets

    cm = _fit_cache.calibrated_cost_model(n_epochs=n_epochs, seed=seed)
    results = {}
    for name in datasets.DATASETS:
        ds, est = _fit_cache.fitted(name, n_epochs=n_epochs, seed=seed)
        key = jax.random.PRNGKey(mc_seed)
        nominal_acc = est.score(ds.x_test, ds.y_test, target="circuit")
        floor = round(nominal_acc - 0.02, 6)

        # Warm the nominal DSE path, then lower the MC machine OUTSIDE the
        # counted block (lowering runs eager sampling/interp ops); the
        # counted sweep may then add at most the MC forward + the batched
        # recombination program.
        est.pareto(ds.x_test, ds.y_test, cm=cm)
        machine = est.monte_carlo_machine(n_variants, key)
        with count_compiles() as cc:
            sweep = est.pareto(ds.x_test, ds.y_test, cm=cm,
                               n_variants=n_variants, key=key,
                               accuracy_floor=floor)
        mc_compiles = cc.count()

        # Nominal bit-identity: variant 0 vs the nominal candidate machine.
        nominal_machine = est.design_space(cm).machine
        bits_exact = bool(np.array_equal(
            machine.pair_bits(ds.x_test)[0],
            nominal_machine.pair_bits(ds.x_test)))
        scores_exact = bool(np.array_equal(
            machine.pair_scores(ds.x_test)[0],
            nominal_machine.pair_scores(ds.x_test)))

        # Throughput of the jitted MC forward (already warm).
        reps, t0 = 10, time.perf_counter()
        for _ in range(reps):
            machine.pair_bits(ds.x_test)
        per_call = (time.perf_counter() - t0) / reps
        variants_per_s = n_variants / per_call

        # Algorithm-1 vertex: nominal vs robust statistics.
        alg1 = dse.assignment_from_kernel_map(est.kernel_map_)
        i = sweep.find(alg1)
        vertex = {
            "kernel_map": est.kernel_map_,
            "accuracy_nominal": float(sweep.accuracy[i]),
            "acc_mean": float(sweep.acc_mean[i]),
            "acc_std": float(sweep.acc_std[i]),
            "acc_worst": float(sweep.acc_worst[i]),
            "yield_frac": float(sweep.yield_[i]),
            "on_robust_front": bool(i in set(sweep.robust_front.tolist())),
        }

        # Robust deployment at reference yield floors.
        robust_deploys = {}
        for yf in YIELD_FLOORS:
            try:
                j = sweep.select(yield_floor=yf)
                robust_deploys[str(yf)] = {
                    "kernel_map": sweep.kernel_map(j),
                    "acc_mean": float(sweep.acc_mean[j]),
                    "yield_frac": float(sweep.yield_[j]),
                    "area_mm2": float(sweep.area[j]),
                    "power_mw": float(sweep.power[j]),
                }
            except ValueError:
                robust_deploys[str(yf)] = None

        # Yield-vs-sigma: the Algorithm-1 circuit under scaled mismatch.
        sigma_curve = []
        for s in sigma_scales:
            mc = est.monte_carlo(ds.x_test, ds.y_test,
                                 n_variants=n_variants, key=key,
                                 sigma_scale=float(s))
            sigma_curve.append({
                "sigma_scale": float(s),
                "acc_mean": round(mc.mean, 6),
                "acc_std": round(mc.std, 6),
                "acc_worst": round(mc.worst, 6),
                "yield_frac": round(mc.yield_at(floor), 6),
            })

        results[name] = {
            "n_pairs": sweep.n_pairs,
            "n_assignments": int(sweep.assignments.shape[0]),
            "n_variants": int(n_variants),
            "accuracy_floor": floor,
            "mc_compiles": int(mc_compiles),
            "mc_compile_names": cc.names,
            "nominal_bits_exact": bits_exact,
            "nominal_scores_exact": scores_exact,
            "mc_forward_s": round(per_call, 6),
            "variants_per_s": round(variants_per_s, 1),
            "sweep_s": round(sweep.elapsed_s, 4),
            "alg1": vertex,
            "robust_deploys": robust_deploys,
            "robust_front": sweep.front_points(robust=True),
            "yield_vs_sigma": sigma_curve,
        }
        # The yield deploy mutates assignment_; keep the cached fit clean
        # for any benchmark sharing it through _fit_cache.
        est.assignment_ = None

    if verbose:
        print("dataset,mc_compiles,nominal_bits_exact,nominal_scores_exact,"
              "variants_per_s,alg1_yield,alg1_worst")
        for name, r in results.items():
            a = r["alg1"]
            print(f"{name},{r['mc_compiles']},{r['nominal_bits_exact']},"
                  f"{r['nominal_scores_exact']},{r['variants_per_s']},"
                  f"{a['yield_frac']:.3f},{a['acc_worst']:.3f}")
        for name, r in results.items():
            print(f"-- {name} yield vs sigma (floor {r['accuracy_floor']}):")
            for p in r["yield_vs_sigma"]:
                print(f"   x{p['sigma_scale']}: mean {p['acc_mean']:.3f}, "
                      f"worst {p['acc_worst']:.3f}, "
                      f"yield {p['yield_frac']:.3f}")
    return {"benchmark": "montecarlo", "n_epochs": n_epochs, "seed": seed,
            "mc_seed": mc_seed, "n_variants": n_variants,
            "datasets": results}


def assert_nominal(result: dict) -> None:
    """Hard CI gate: the zero-offset variant IS the nominal compiled path."""
    bad = {
        name: {"bits": r["nominal_bits_exact"],
               "scores": r["nominal_scores_exact"]}
        for name, r in result["datasets"].items()
        if not (r["nominal_bits_exact"] and r["nominal_scores_exact"])
    }
    print(f"nominal-variant bit-identity assertion: "
          f"{'FAIL ' + str(bad) if bad else 'OK'}")
    if bad:
        raise AssertionError(
            f"zero-offset Monte-Carlo variant drifted from the nominal "
            f"compiled path on {bad} — the §6.3 bit-identity contract "
            "(structural nominal-subgraph reuse) regressed")


def assert_compiles(result: dict,
                    budget: int = MAX_MC_COMPILES) -> None:
    """Hard CI gate: the variant axis costs <= `budget` extra compiles."""
    bad = {
        name: r["mc_compile_names"]
        for name, r in result["datasets"].items()
        if r["mc_compiles"] > budget
    }
    print(f"mc-compile budget assertion (<= {budget}): "
          f"{'FAIL ' + str(bad) if bad else 'OK'}")
    if bad:
        raise AssertionError(
            f"Monte-Carlo sweep compiled more than {budget} extra "
            f"programs: {bad} — the variant axis is leaking shapes")


def run_scaling(n_epochs: int = 120, seed: int = 0, mc_seed: int = 0,
                v_max: int = 1_000_000, method: str = "sobol",
                mc_chunk: int = SCALING_CHUNK, n_x: int = SCALING_X,
                dataset: str = SCALING_DATASET,
                verbose: bool = True) -> dict:
    """Variants/s scaling curve of the streaming engine, V = 64 -> v_max.

    One donated fixed-shape chunk program serves every ladder point, so
    peak temp memory is V-independent; the record carries the compile
    count across the ladder and the step's XLA memory analysis so
    ``assert_flat_memory`` can gate both.
    """
    import jax

    from repro.core import dse

    ds, est = _fit_cache.fitted(dataset, n_epochs=n_epochs, seed=seed)
    x = np.asarray(ds.x_test[:n_x])
    y = np.asarray(ds.y_test[:n_x])
    key = jax.random.PRNGKey(mc_seed)
    floor = round(est.score(x, y, target="circuit") - 0.02, 6)
    a = dse.assignment_from_kernel_map(est.kernel_map_)[None, :]

    sm = est.stream_machine(key, method=method, mc_chunk=mc_chunk)
    ladder = [64]
    while ladder[-1] * 16 < v_max:
        ladder.append(ladder[-1] * 16)
    if ladder[-1] != v_max:
        ladder.append(int(v_max))

    # Streamed-vs-dense parity oracle at V = 64: the SAME 64 variants
    # through the dense bit tensor + batched recombination.
    bits64 = sm.pair_bits_dense(x, np.arange(64), a)
    acc64 = dse.assignment_accuracies_mc(bits64, a, y, est.n_classes_)
    warm = sm.stream(x, y, a, n_variants=64, accuracy_floor=floor)
    parity = {
        "mean_abs_err": float(abs(warm["mean"][0] - acc64.mean())),
        "std_abs_err": float(abs(warm["std"][0] - acc64.std())),
        "worst_exact": bool(warm["worst"][0] == acc64.min()),
        "yield_exact": bool(
            warm["yield"][0] == (acc64 >= floor).mean()),
    }

    mem = sm.step_memory_analysis(n_x, assignments=a)
    mem_rec = None if mem is None else {
        "temp_bytes": int(mem.temp_size_in_bytes),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
    }
    dense_temp = None
    try:  # dense V=64 forward, for contrast with the flat streamed step
        m64 = est.monte_carlo_machine(64, jax.random.fold_in(key, 1))
        dm = jax.jit(m64._forward).lower(x).compile().memory_analysis()
        if dm is not None:
            dense_temp = int(dm.temp_size_in_bytes)
    except Exception:
        pass

    points = []
    with count_compiles() as cc:
        for v in ladder:
            t0 = time.perf_counter()
            out = sm.stream(x, y, a, n_variants=v, accuracy_floor=floor)
            dt = time.perf_counter() - t0
            points.append({
                "n_variants": int(v),
                "seconds": round(dt, 4),
                "variants_per_s": round(v / dt, 1),
                "acc_mean": round(float(out["mean"][0]), 6),
                "acc_worst": round(float(out["worst"][0]), 6),
                "yield_frac": round(float(out["yield"][0]), 6),
                "yield_lo": round(float(out["yield_lo"][0]), 6),
                "yield_hi": round(float(out["yield_hi"][0]), 6),
                "ci_width": round(float(out["yield_hi"][0]
                                        - out["yield_lo"][0]), 6),
                "step_temp_bytes": (None if mem_rec is None
                                    else mem_rec["temp_bytes"]),
            })
    result = {
        "dataset": dataset, "method": method, "mc_chunk": int(mc_chunk),
        "n_x": int(n_x), "mc_seed": int(mc_seed),
        "accuracy_floor": floor,
        "parity_vs_dense64": parity,
        "step_memory": mem_rec,
        "dense_v64_temp_bytes": dense_temp,
        "ladder_extra_compiles": cc.count(),
        "ladder_compile_names": cc.names,
        "points": points,
    }
    if verbose:
        print(f"-- streaming scaling ({dataset}, {method}, "
              f"chunk {mc_chunk}, floor {floor}):")
        print("V,seconds,variants_per_s,yield,ci_width")
        for p in points:
            print(f"{p['n_variants']},{p['seconds']},"
                  f"{p['variants_per_s']},{p['yield_frac']:.4f},"
                  f"{p['ci_width']:.5f}")
        print(f"   step temp bytes: "
              f"{None if mem_rec is None else mem_rec['temp_bytes']}"
              f" (dense V=64 forward: {dense_temp}); "
              f"extra compiles across ladder: {cc.count()}")
    return result


def assert_flat_memory(scaling: dict) -> None:
    """Hard CI gate: V = 64 -> v_max reuses ONE fixed-shape chunk step.

    Two checks: the ladder added zero jit compiles after the warm-up
    stream (no V-dependent shapes leak into the step), and every ladder
    point records the same peak temp bytes as the first.
    """
    extra = scaling["ladder_extra_compiles"]
    temps = {p["step_temp_bytes"] for p in scaling["points"]}
    ok = extra == 0 and len(temps) == 1
    print(f"flat-memory assertion: {'OK' if ok else 'FAIL'} "
          f"(extra compiles {extra}, temp bytes {sorted(temps)})")
    if not ok:
        raise AssertionError(
            f"streaming scaling is not flat: {extra} extra compiles "
            f"across the V ladder, temp bytes {sorted(temps)} — the "
            "chunk step's shapes depend on n_variants")


def assert_ci_width(scaling: dict, max_width: float) -> None:
    """Hard CI gate: the final ladder point's yield CI is tight enough."""
    p = scaling["points"][-1]
    ok = p["ci_width"] <= max_width
    print(f"ci-width assertion (<= {max_width} at V={p['n_variants']}): "
          f"{'OK' if ok else 'FAIL'} ({p['ci_width']})")
    if not ok:
        raise AssertionError(
            f"yield CI width {p['ci_width']} at V={p['n_variants']} "
            f"exceeds {max_width} — the streamed exceedance counts (or "
            "the IS effective sample size) regressed")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="write JSON here as well")
    ap.add_argument("--n-epochs", type=int, default=120)
    ap.add_argument("--n-variants", type=int, default=N_VARIANTS)
    ap.add_argument("--mc-seed", type=int, default=0)
    ap.add_argument("--assert-nominal", action="store_true",
                    help="fail unless the zero-offset variant is "
                         "bit-identical to the nominal compiled path")
    ap.add_argument("--assert-compiles", action="store_true",
                    help="fail if the variant axis costs more than "
                         f"{MAX_MC_COMPILES} extra jit compiles")
    ap.add_argument("--scaling", action="store_true",
                    help="also run the streaming V=64..--v-max scaling "
                         "curve (DESIGN.md §10)")
    ap.add_argument("--v-max", type=int, default=1_000_000)
    ap.add_argument("--method", default="sobol",
                    help="streaming sampler: iid | sobol | stratified | is")
    ap.add_argument("--mc-chunk", type=int, default=SCALING_CHUNK)
    ap.add_argument("--assert-flat-memory", action="store_true",
                    help="fail unless the whole V ladder reuses one "
                         "fixed-shape chunk step (implies --scaling)")
    ap.add_argument("--assert-ci-width", type=float, default=None,
                    metavar="W",
                    help="fail if the final yield CI is wider than W "
                         "(implies --scaling)")
    args = ap.parse_args()
    result = run(n_epochs=args.n_epochs, mc_seed=args.mc_seed,
                 n_variants=args.n_variants)
    scaling = None
    if args.scaling or args.assert_flat_memory \
            or args.assert_ci_width is not None:
        scaling = run_scaling(n_epochs=args.n_epochs, mc_seed=args.mc_seed,
                              v_max=args.v_max, method=args.method,
                              mc_chunk=args.mc_chunk)
        result["scaling"] = scaling
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    if args.assert_nominal:
        assert_nominal(result)
    if args.assert_compiles:
        assert_compiles(result)
    if args.assert_flat_memory:
        assert_flat_memory(scaling)
    if args.assert_ci_width is not None:
        assert_ci_width(scaling, args.assert_ci_width)


if __name__ == "__main__":
    main()
