"""Scale-out benchmark: K >= 10 OvO machines (DESIGN.md §11).

Three records, appended to the BENCH trajectory:

  * **dag_vs_votes** — warm predict throughput of the O(K) DDAG decision
    front vs the dense votes path on the har12 test split (K = 12,
    P = 66), plus their label agreement.  ``--assert-scaling`` gates
    DAG >= 2x votes queries/s and agreement >= 0.99.  A synthetic
    K-ladder (K in {5, 10, 12} float-bit machines) shows how the gap
    opens with P = K(K-1)/2.

  * **lane_ladder** — the size-sharded trainer layout
    (``trainer.shard_lane_layout`` + per-device programs trimmed to their
    shard max) against the seed's global-``n_max`` program, at
    D in {1, 2, 4, 8} shards on 8 virtual CPU devices (one subprocess
    per rung, each pinned to ``JAX_PLATFORMS=cpu`` so ``XLA_FLAGS`` never
    leaks and a rung never contends for a chip; these are never chip
    numbers).  Throughput is
    TRUE lane work per second — sum over pairs of ``n_i^2 * G * C``
    solver-units, identical across rungs — so rung ratios measure
    exactly the padded-work this layout removes.  ``--assert-scaling``
    gates the 8-shard rung >= 3x the 1-shard rung.

    Honesty note: this host pins to ONE physical core, so the 8 virtual
    devices serialize and the >= 3x comes from shard-local padding
    (har12's 198..1582 subset-size spread makes the global-pad layout do
    ~3.9x more solver work than the size-sharded one), not from
    parallel silicon.  On a real multi-core/TPU mesh the same layout
    additionally overlaps shards; the record stores the decomposition
    (``padded_work_units``) so both effects stay separable.

  * **dse_k12** — the portfolio search (greedy/flip + annealing + front
    polish) on a synthetic K = 12, P = 66 space: elapsed, evaluated
    assignments, front size — no 2^P anywhere — plus the small-P oracle
    check: at P = 10 (K = 5) the forced portfolio front must contain
    every exhaustive-front point.

Fit policy: the har12 machine is fitted on a per-class subsample
(``HAR12_FIT_PER_CLASS`` rows/class, ``n_epochs=60``, seed 0) — a
single-core container cannot run 66 pairs x 7 gammas x 6 Cs x 5 folds at
n_max = 1582 in benchmark time; the subsample keeps the full K = 12 /
P = 66 decision structure that this benchmark measures.  All seeds are
in the JSON record.

  PYTHONPATH=src python benchmarks/scale.py --out runs/scale.json \
      --assert-scaling
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HAR12_FIT_PER_CLASS = 150
HAR12_FIT_EPOCHS = 60
SEED = 0
TRIALS = 3
#: Ladder subsample fraction: keeps har12's relative subset-size spread
#: (the padding-waste ratio) while bounding single-core runtime.
LADDER_FRACTION = 0.25
LADDER_GAMMAS = (0.5, 2.0)
LADDER_CS = (1.0, 10.0)
#: Enough solver epochs that per-rung fixed costs (dispatch, result
#: collection) are amortized against the n^2-scaling lane work the rung
#: ratios are meant to measure.
LADDER_EPOCHS = 30


def _har12_subsample(per_class: int, seed: int = SEED):
    from repro.data import datasets

    ds = datasets.load("har12")
    rng = np.random.default_rng(seed)
    keep = np.concatenate([
        rng.choice(np.flatnonzero(ds.y_train == k),
                   size=min(per_class, int((ds.y_train == k).sum())),
                   replace=False)
        for k in range(ds.n_classes)])
    return ds, ds.x_train[keep], ds.y_train[keep]


def _har12_fraction(fraction: float, seed: int = SEED):
    """Stratified FRACTION subsample of the har12 train split.

    Unlike the per-class cap of :func:`_har12_subsample` (which flattens
    the class-size spread), keeping ``fraction`` of every class preserves
    har12's ~9x spread of OvO pair-subset sizes — the padding-waste
    profile the size-sharded lane layout exists to remove.
    """
    from repro.data import datasets

    ds = datasets.load("har12")
    rng = np.random.default_rng(seed)
    keep = np.concatenate([
        rng.choice(np.flatnonzero(ds.y_train == k),
                   size=max(2, int(round(fraction *
                                         int((ds.y_train == k).sum())))),
                   replace=False)
        for k in range(ds.n_classes)])
    return ds, ds.x_train[keep], ds.y_train[keep]


def _best_of(fn, trials: int = TRIALS) -> float:
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        fn()
        wall = time.perf_counter() - t0
        best = wall if best is None else min(best, wall)
    return best


def _float_bit_machine(k, n=400, seed=SEED, **kw):
    """Synthetic deployed machine — decision-path cost without a fit."""
    from repro.api import compile_machine
    from repro.core import ovo, svm as svm_mod

    rng = np.random.RandomState(seed)
    x = rng.rand(n, 3)
    y = rng.randint(0, k, n)
    clfs = []
    for (ci, cj) in ovo.class_pairs(k):
        mask = (y == ci) | (y == cj)
        yy = np.where(y[mask] == ci, 1.0, -1.0)
        m = svm_mod.train_binary(x[mask], yy, "linear", c=1.0, n_epochs=40)
        clfs.append(ovo.FloatBitClassifier(m))
    return compile_machine(clfs, n_classes=k, **kw), x


def run_dag_vs_votes() -> dict:
    """Warm predict throughput: DAG front vs dense votes, K=12 har12."""
    from repro.api import MixedKernelSVM

    ds, xs, ys = _har12_subsample(HAR12_FIT_PER_CLASS)
    t0 = time.perf_counter()
    est = MixedKernelSVM(n_epochs=HAR12_FIT_EPOCHS, seed=SEED).fit(xs, ys)
    fit_s = time.perf_counter() - t0
    xq, yq = ds.x_test, ds.y_test

    m_votes = est.deploy("circuit")
    m_dag = est.deploy("circuit", decider="dag")
    m_votes.predict(xq[:8])                                  # compile
    m_dag.predict(xq[:8])
    t_votes = _best_of(lambda: m_votes.predict(xq))
    t_dag = _best_of(lambda: m_dag.predict(xq))
    lv, ld = m_votes.predict(xq), m_dag.predict(xq)
    agreement = float(np.mean(lv == ld))
    rec = {
        "benchmark": "scale_dag_vs_votes",
        "dataset": "har12",
        "seed": SEED,
        "fit_config": {"per_class": HAR12_FIT_PER_CLASS,
                       "n_epochs": HAR12_FIT_EPOCHS, "fit_s": round(fit_s, 1)},
        "n_queries": int(len(xq)),
        "k": int(ds.n_classes),
        "p": len(est.pairs_),
        "votes_qps": round(len(xq) / t_votes, 1),
        "dag_qps": round(len(xq) / t_dag, 1),
        "dag_speedup": round(t_votes / t_dag, 2),
        "agreement": round(agreement, 4),
        "votes_accuracy": round(float(np.mean(lv == yq)), 4),
        "dag_accuracy": round(float(np.mean(ld == yq)), 4),
        "trials": TRIALS,
    }

    # Table-II-style row for the scale workload (accuracy / area / power
    # per design).  Costs use the DEFAULT cost-model units — the
    # calibrated Table-II units need the three UCI fits, which belong to
    # benchmarks/table2.py; ratios between designs are unit-free anyway.
    from repro.core import hwcost

    cm = hwcost.CostModel()
    row = []
    for design, target in (("linear", "linear"), ("rbf", "rbf"),
                           ("mixed", "circuit")):
        acc = est.score(xq, yq, target=target)
        cost = hwcost.system_cost(est.bank(target), cm)
        row.append({"design": design,
                    "accuracy_pct": round(100 * float(acc), 2),
                    "area_mm2": round(float(cost.area_mm2), 4),
                    "power_mw": round(float(cost.power_mw), 4)})
    rec["table2_row"] = {"dataset": "har12", "designs": row,
                         "cost_model": "default units (uncalibrated)",
                         "fit": rec["fit_config"]}

    # End-to-end closure at K=12 / P=66: pareto (portfolio path — no
    # 2^66 anywhere) and both Monte-Carlo engines (dense + streaming
    # pair-chunked votes fold) on the same fitted estimator.
    xv, yv = xq[:400], yq[:400]
    t0 = time.perf_counter()
    sw = est.pareto(xv, yv)
    pareto_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    mc = est.monte_carlo(xv, yv, n_variants=16)
    stream = est.monte_carlo(xv, yv, n_variants=64, method="iid",
                             mc_chunk=16)
    mc_s = time.perf_counter() - t0
    rec["e2e_k12"] = {
        "pareto_exhaustive": bool(sw.exhaustive),
        "pareto_evaluated": int(sw.assignments.shape[0]),
        "pareto_front_size": int(len(sw.front)),
        "pareto_s": round(pareto_s, 1),
        "mc_dense_mean_acc": round(float(np.mean(mc.accuracy)), 4),
        "mc_stream_mean_acc": round(float(stream.mean), 4),
        "mc_stream_yield": round(float(stream.yield_), 4),
        "mc_s": round(mc_s, 1),
    }

    ladder = []
    for k in (5, 10, 12):
        mv, x = _float_bit_machine(k)
        md, _ = _float_bit_machine(k, decider="dag")
        xq_s = np.tile(x, (4, 1))[:1024]
        mv.predict(xq_s[:8]); md.predict(xq_s[:8])
        tv = _best_of(lambda: mv.predict(xq_s))
        td = _best_of(lambda: md.predict(xq_s))
        ladder.append({"k": k, "p": k * (k - 1) // 2,
                       "votes_qps": round(len(xq_s) / tv, 1),
                       "dag_qps": round(len(xq_s) / td, 1),
                       "dag_speedup": round(tv / td, 2)})
    rec["k_ladder"] = ladder
    return rec


_LADDER_BODY = """
    import os, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    import sys
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {root!r})
    from repro.core import trainer
    from benchmarks.scale import _har12_fraction, LADDER_FRACTION, \\
        LADDER_GAMMAS, LADDER_CS, LADDER_EPOCHS, SEED

    d = {d}
    _, xs, ys = _har12_fraction(LADDER_FRACTION, SEED)
    padded = trainer.pad_pairs(xs, ys, 12, n_folds=5, seed=SEED)
    g = np.asarray(LADDER_GAMMAS); c = np.asarray(LADDER_CS)
    devices = jax.devices()[:d]
    shards = trainer.shard_lane_layout(padded.n_true, d)
    padded_units = sum(
        len(s) * int(max(np.asarray(padded.n_true)[s])) ** 2
        for s in shards) * len(g) * len(c)
    true_units = sum(n * n for n in padded.n_true) * len(g) * len(c)

    def grid():
        return trainer.family_cv_grid_size_sharded(
            padded, "rbf", g, c, LADDER_EPOCHS, devices=devices)

    ref = grid()                                    # compile (per shard)
    best = None
    for _ in range(2):
        t0 = time.perf_counter(); grid()
        w = time.perf_counter() - t0
        best = w if best is None else min(best, w)
    print("RESULT " + json.dumps({{
        "d": d, "n_shards": len(shards), "wall_s": round(best, 3),
        "n_max_global": padded.n_max,
        "shard_maxes": [int(max(np.asarray(padded.n_true)[s]))
                        for s in shards],
        "padded_work_units": int(padded_units),
        "true_work_units": int(true_units),
        "lane_units_per_s": round(true_units / best, 1),
    }}))
"""


def run_lane_ladder() -> dict:
    """D in {1, 2, 4, 8} size-sharded rungs, one subprocess each."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    # Virtual CPU devices: the child never reaches for a chip the parent
    # process may already hold.
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    rungs = []
    for d in (1, 2, 4, 8):
        body = textwrap.dedent(_LADDER_BODY).format(src=src, root=root, d=d)
        res = subprocess.run([sys.executable, "-c", body], env=env,
                             capture_output=True, text=True, timeout=3600)
        if res.returncode != 0:
            raise RuntimeError(
                f"ladder rung d={d} failed:\n{res.stdout}\n{res.stderr}")
        line = [ln for ln in res.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        rungs.append(json.loads(line[len("RESULT "):]))
        print(f"  d={d}: wall {rungs[-1]['wall_s']}s, "
              f"lane units/s {rungs[-1]['lane_units_per_s']}, "
              f"padded units {rungs[-1]['padded_work_units']}")
    base = rungs[0]["lane_units_per_s"]
    return {
        "benchmark": "scale_lane_ladder",
        "dataset": "har12",
        "seed": SEED,
        "fraction": LADDER_FRACTION,
        "gammas": list(LADDER_GAMMAS), "cs": list(LADDER_CS),
        "n_epochs": LADDER_EPOCHS,
        "devices_virtual": 8,
        "physical_cores": os.cpu_count(),
        "rungs": rungs,
        "speedup_8v1": round(rungs[-1]["lane_units_per_s"] / base, 2),
        "padding_waste_1shard": round(
            rungs[0]["padded_work_units"] / rungs[0]["true_work_units"], 3),
        "padding_waste_8shard": round(
            rungs[-1]["padded_work_units"] / rungs[-1]["true_work_units"], 3),
        "note": "single physical core: virtual devices serialize; the "
                "speedup is the padded-work reduction of shard-local "
                "trimming (see padded_work_units), which composes with "
                "real device parallelism on multi-core hosts",
    }


def run_dse_k12() -> dict:
    """Portfolio DSE at K=12 (P=66) + the P=10 exhaustive-coverage oracle."""
    from repro.core import dse, hwcost, ovo, trainer
    from repro.core.analog import AnalogBinaryClassifier
    from repro.core.ovo import DigitalLinearClassifier
    from repro.core.svm import SVMModel

    def synthetic_space(k, n_val, seed=SEED):
        rng = np.random.RandomState(seed)
        hw = trainer.default_hw(0)
        gamma = float(trainer.hw_gamma_grid(hw)[3])
        d, m = 3, 6
        cands = []
        for _ in ovo.class_pairs(k):
            w = rng.randn(d)
            lin = SVMModel(kind="linear", support_x=np.zeros((1, d)),
                           support_y=np.ones(1), alpha=np.zeros(1),
                           bias=float(-w.sum() / 2), gamma=1.0, c=1.0, w=w)
            sv = rng.rand(m, d)
            yv = np.where(rng.rand(m) > 0.5, 1.0, -1.0)
            rbf = SVMModel(kind="hw", support_x=sv, support_y=yv,
                           alpha=rng.rand(m) + 0.1,
                           bias=float(rng.randn() * 0.1),
                           gamma=gamma, c=1.0, kernel_fn=hw.kernel_response)
            cands.append((DigitalLinearClassifier.deploy(lin),
                          AnalogBinaryClassifier.deploy(rbf, hw)))
        space = dse.DesignSpace.from_candidates(cands, k, hwcost.CostModel())
        x = rng.rand(n_val, d)
        y = rng.randint(0, k, n_val)
        return space, x, y

    space, x, y = synthetic_space(12, 400)
    t0 = time.perf_counter()
    sw = space.sweep(x, y)
    elapsed = time.perf_counter() - t0
    assert not sw.exhaustive

    space10, x10, y10 = synthetic_space(5, 200)
    ex = space10.sweep(x10, y10)
    po = space10.sweep(x10, y10, max_exhaustive=0)
    ex_front = {tuple(a) for a in np.asarray(ex.assignments[ex.front], bool)}
    po_front = {tuple(a) for a in np.asarray(po.assignments[po.front], bool)}
    covered = not (ex_front - po_front)
    return {
        "benchmark": "scale_dse_k12",
        "seed": SEED,
        "k": 12, "p": 66,
        "evaluated_assignments": int(sw.assignments.shape[0]),
        "front_size": int(len(sw.front)),
        "elapsed_s": round(elapsed, 1),
        "assignments_per_s": round(sw.assignments_per_s, 1),
        "oracle_p10": {
            "exhaustive_front": len(ex_front),
            "portfolio_front": len(po_front),
            "portfolio_covers_exhaustive": bool(covered),
        },
    }


def run(assert_scaling: bool = False) -> dict:
    print("scale: DAG vs votes (K=12 har12 fit + synthetic K ladder)")
    dag = run_dag_vs_votes()
    print(f"  votes {dag['votes_qps']} q/s, dag {dag['dag_qps']} q/s "
          f"({dag['dag_speedup']}x), agreement {dag['agreement']}")
    print("scale: size-sharded lane ladder (8 virtual devices)")
    ladder = run_lane_ladder()
    print(f"  8-shard vs 1-shard lane throughput: {ladder['speedup_8v1']}x")
    print("scale: K=12 portfolio DSE + P=10 oracle coverage")
    k12 = run_dse_k12()
    print(f"  {k12['evaluated_assignments']} assignments in "
          f"{k12['elapsed_s']}s; P=10 oracle covered: "
          f"{k12['oracle_p10']['portfolio_covers_exhaustive']}")
    out = {"dag_vs_votes": dag, "lane_ladder": ladder, "dse_k12": k12}
    if assert_scaling:
        assert_gates(out)
    return out


def assert_gates(out: dict) -> None:
    dag, ladder, k12 = out["dag_vs_votes"], out["lane_ladder"], out["dse_k12"]
    assert dag["dag_speedup"] >= 2.0, \
        f"DAG speedup {dag['dag_speedup']} < 2x"
    assert dag["agreement"] >= 0.99, \
        f"DAG/votes agreement {dag['agreement']} < 0.99"
    assert ladder["speedup_8v1"] >= 3.0, \
        f"8-shard ladder speedup {ladder['speedup_8v1']} < 3x"
    assert k12["oracle_p10"]["portfolio_covers_exhaustive"], \
        "portfolio front missed exhaustive-front points at P=10"
    print("scale: all scaling gates passed")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, metavar="PATH")
    ap.add_argument("--assert-scaling", action="store_true",
                    help="gate DAG >=2x + agreement >=0.99 + ladder >=3x "
                         "+ P=10 oracle coverage")
    args = ap.parse_args()
    res = run()
    if args.out:
        # Written before the gates so a failed run still leaves the
        # numbers behind for diagnosis.
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)
        print(f"JSON -> {args.out}")
    if args.assert_scaling:
        assert_gates(res)


if __name__ == "__main__":
    main()
