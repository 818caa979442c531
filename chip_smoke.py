"""Chip smoke: the mixed-kernel SVM main path, once, on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the mesh paths, on a 4-chip host

One process, phases in order; any failure raises and exits non-zero.
The device gate runs first: with no TPU in ``jax.devices()`` the script
exits non-zero before any phase, and nothing runs on the CPU in its place.

One chip: Algorithm-1 fit of ``har12`` at full width (K = 12, P = 66,
d = 5, all 4375 training rows, the default (C, gamma) grid, 5 folds)
with the Pallas solver compiled by Mosaic, plus ``balance`` for a
two-tenant fleet; the fused solver against the blocked XLA oracle on
har12 lanes; the ``float``, ``circuit`` and ``rbf`` machines against
the object path (``rbf`` is the target whose banks run the Mosaic
kernel ``ops.rbf_matrix``; ``float`` lowers rbf-selected pairs to the
measured-curve ``hw`` bank); a fleet behind ``SVMEngine`` against its
members; streaming Monte-Carlo signoff and the P = 66 portfolio sweep.

``--four-chips``: only the data-parallel fleet forward on
``make_serving_mesh(4)`` and streaming Monte-Carlo on
``make_variant_mesh(4)``, each against the one-device result, with the
full-size har12 machine: fitted again, or loaded from the file a
one-chip run saved (``--har12-model PATH`` on both runs).

Comparisons follow the comparator tie rule of DESIGN.md §1.4: outputs
must agree wherever the deciding score has |score| > ``TIE_EPS``.
Timings printed here are smoke timings (compile included), not metrics.
The last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: Comparator tie epsilon (DESIGN.md §1.4, ``TIE_EPS`` in tests/test_api.py).
TIE_EPS = 1e-5
#: Algorithm-1 epochs of the smoke fit (the estimator default is 200).
N_EPOCHS = 60
#: Solver-vs-oracle check: epochs, and tolerances of
#: tests/test_solver_pallas.py (scaled by max(C, 1)).
SOLVER_EPOCHS = 20
ALPHA_ATOL, F_ATOL, RTOL = 5e-4, 5e-3, 1e-3
#: Engine phase: requests submitted across the fleet.
N_REQUESTS = 512
#: Portfolio-search budget of the P = 66 sweep (``DesignSpace.sweep``).
SWEEP_KW = dict(n_random=4, max_rounds=16, n_anneal=4, anneal_steps=32)
#: Streaming Monte-Carlo signoff: one chunk of the engine's default size
#: (``MC_STREAM_CHUNK`` variants) over ``MC_ROWS`` validation rows, and
#: the zero-variation stream over ``DEPLOY_ROWS`` rows in chunks of
#: ``NOMINAL_CHUNK``.  har12's 66 analog candidates span ~1e6 mismatch
#: dimensions: past scipy's Sobol' table (``mcstream.SOBOL_MAX_DIM``),
#: and too many for host-side QMC draws, so the draws are the engine's
#: on-device iid ones.  Each variant and row costs 66 x M x d
#: measured-curve lookups; their rate on the chip (PERF.md) bounds the
#: rows a smoke can afford, not the engine's memory.
MC_ROWS = 64
NOMINAL_CHUNK = 4
MC_METHOD = "iid"
#: ``--four-chips``: validation rows of the variant-mesh stream.
FOUR_CHIP_MC_ROWS = 32
#: Test rows the deployed machines are checked on against the object path.
DEPLOY_ROWS = 512


def log(msg: str) -> None:
    print(msg, flush=True)


def device_gate(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees {len(devs)} "
                 f"{devs[0].platform!r} device(s)); nothing runs in its "
                 f"place")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, found {len(devs)}")
    return devs


def stable_rows(scores, eps: float = TIE_EPS):
    """Rows whose every pair score is clear of the comparator tie."""
    import numpy as np

    s = np.abs(np.asarray(scores))
    return s.reshape(s.shape[0], -1).min(axis=1) > eps


def fit(name: str, model_path=None):
    """Algorithm 1 on all of ``name``'s training rows, or the estimator a
    one-chip run saved to ``model_path``."""
    from repro.api import MixedKernelSVM
    from repro.data import datasets

    ds = datasets.load(name)
    t0 = time.perf_counter()
    if model_path:
        est = MixedKernelSVM.load(model_path)
        how = f"loaded from {model_path}"
    else:
        est = MixedKernelSVM(n_epochs=N_EPOCHS).fit(ds.x_train, ds.y_train)
        how = f"n_epochs={N_EPOCHS}"
    log(f"[fit] {name}: K={est.n_classes_} P={len(est.pairs_)} "
        f"d={ds.x_train.shape[1]} n_train={len(ds.y_train)} "
        f"rbf_pairs={est.n_rbf_} {how}; {since(t0)}")
    return ds, est


def since(t0: float) -> str:
    return (f"smoke timing, not a metric: {time.perf_counter() - t0:.1f}s "
            f"wall, compile included")


# -- one chip ----------------------------------------------------------------


def phase_solver(ds) -> None:
    """Fused Pallas lanes (Mosaic-compiled) vs the blocked XLA oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kernels as kern
    from repro.core import trainer
    from repro.kernels import ops

    padded = trainer.pad_pairs(ds.x_train, ds.y_train, 12, n_folds=5)
    pick = [int(np.argmax(padded.n_true)), int(np.argmin(padded.n_true))]
    sub = padded.take(pick)
    cs = trainer.DEFAULT_CS[:4]
    n_f = sub.fold_masks.shape[1]
    c_box = (np.repeat(cs, n_f)[None, :, None]
             * np.tile(sub.fold_masks, (1, len(cs), 1))
             * sub.valid[:, None, :]).astype(np.float32)   # (P, C*F, n)
    scale = max(float(cs.max()), 1.0)
    oracle = jax.jit(jax.vmap(
        lambda kp, y, cb: trainer.dual_coordinate_ascent_blocked(
            kp, y, cb, SOLVER_EPOCHS), in_axes=(None, None, 0)))
    x, y = jnp.asarray(sub.x), jnp.asarray(sub.y)
    t0 = time.perf_counter()
    for kind, gammas in (("rbf", trainer.DEFAULT_RBF_GAMMAS[::3]),
                         ("linear", np.array([1.0]))):
        g_pg = np.broadcast_to(gammas, (len(pick), len(gammas)))
        a_pl, f_pl = ops.solve_lanes(
            x, y, jnp.asarray(c_box), jnp.asarray(g_pg, jnp.float32),
            kind=kind, n_epochs=SOLVER_EPOCHS, interpret=False)
        a_pl, f_pl = np.asarray(a_pl), np.asarray(f_pl)
        da = df = 0.0
        for p in range(len(pick)):
            for g, gamma in enumerate(gammas):
                kp = kern.kernel_matrix(kind, x[p], x[p], gamma) + 1.0
                a_or = oracle(kp, y[p], jnp.asarray(c_box[p]))
                f_or = np.asarray(kern.matmul(a_or * y[p], kp.T))
                a_or = np.asarray(a_or)
                np.testing.assert_allclose(a_pl[p, g], a_or,
                                           atol=ALPHA_ATOL * scale, rtol=RTOL)
                np.testing.assert_allclose(f_pl[p, g], f_or,
                                           atol=F_ATOL * scale, rtol=RTOL)
                da = max(da, float(np.abs(a_pl[p, g] - a_or).max()))
                df = max(df, float(np.abs(f_pl[p, g] - f_or).max()))
        log(f"[solver] {kind}: pairs {pick} (n={[padded.n_true[i] for i in pick]}"
            f", n_max={sub.n_max}) x {len(gammas)} gamma x {c_box.shape[1]} "
            f"lanes, {SOLVER_EPOCHS} epochs: max |d alpha| {da:.3e} "
            f"(atol {ALPHA_ATOL * scale:.1e}), max |d f| {df:.3e} "
            f"(atol {F_ATOL * scale:.1e}) vs dual_coordinate_ascent_blocked; "
            f"{since(t0)}")


def check_machine(est, target: str, x, y):
    """Compiled machine vs the object path under the tie rule."""
    import numpy as np

    from repro.core.ovo import decide_votes

    t0 = time.perf_counter()
    m = est.deploy(target)
    b_obj = est.bank(target).predict_bits(x)        # the slow object path
    lab_obj = decide_votes(b_obj, est.n_classes_)
    scores = m.decision_scores(x)
    b_cmp, lab_cmp = m.predict_bits(x), m.predict(x)
    ok = np.abs(scores) > TIE_EPS
    np.testing.assert_array_equal(b_cmp[ok], b_obj[ok])
    rows = stable_rows(scores)
    np.testing.assert_array_equal(lab_cmp[rows], lab_obj[rows])
    log(f"[deploy] {target}: {m.describe().splitlines()[1:]} use_pallas="
        f"{m.use_pallas} interpret={m.interpret}; bits agree on "
        f"{int(ok.sum())}/{ok.size} stable scores, labels on "
        f"{int(rows.sum())}/{len(rows)} stable rows "
        f"({int((lab_cmp == lab_obj).sum())}/{len(rows)} overall); accuracy "
        f"{float(np.mean(lab_cmp == y)):.4f} (object path "
        f"{float(np.mean(lab_obj == y)):.4f}); {since(t0)}")
    return m


def phase_deploy(ds, est):
    import jax.numpy as jnp

    x, y = ds.x_test[:DEPLOY_ROWS], ds.y_test[:DEPLOY_ROWS]
    check_machine(est, "float", x, y)
    circuit_m = check_machine(est, "circuit", x, y)
    rbf_m = check_machine(est, "rbf", x, y)
    if not rbf_m.use_pallas or rbf_m.interpret is True:
        raise AssertionError(
            f"rbf machine must run the compiled kernel: use_pallas="
            f"{rbf_m.use_pallas}, interpret={rbf_m.interpret}")
    hlo = rbf_m._forward_jit.lower(
        jnp.zeros((256, rbf_m.n_features), jnp.float32)).as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("rbf machine HLO has no tpu_custom_call: its "
                             "banks did not lower to the Mosaic kernel")
    log("[deploy] rbf machine HLO holds tpu_custom_call: its banks run the "
        "Mosaic-compiled Pallas kernel, not the interpreter")
    return rbf_m, circuit_m


def fleet_reference(fleet, x, names):
    """Per-row member labels and stability under the tie rule."""
    import numpy as np

    want = np.zeros(len(x), np.int64)
    ok = np.zeros(len(x), bool)
    for name in set(names):
        rows = np.flatnonzero(np.asarray(names) == name)
        m = fleet.member(name)
        xm = x[rows, : m.n_features]
        want[rows] = m.predict(xm)
        ok[rows] = stable_rows(m.decision_scores(xm))
    return want, ok


def fleet_requests(pools, n: int, seed: int = 0):
    import numpy as np

    rng = np.random.RandomState(seed)
    ids = list(pools)
    names = [ids[i] for i in rng.randint(len(ids), size=n)]
    d = max(p.shape[1] for p in pools.values())
    x = np.zeros((n, d), np.float32)
    for i, name in enumerate(names):
        row = pools[name][rng.randint(len(pools[name]))]
        x[i, : row.shape[0]] = row
    return x, names


def phase_serve(members, pools) -> None:
    import numpy as np

    from repro.api import compile_fleet
    from repro.serving import SVMEngine

    fleet = compile_fleet(members)
    x, names = fleet_requests(pools, N_REQUESTS)
    t0 = time.perf_counter()
    with SVMEngine(fleet) as eng:
        eng.warmup()
        futs = [eng.submit(x[i, : fleet.member(n).n_features], n)
                for i, n in enumerate(names)]
        got = np.asarray([f.result(timeout=120.0) for f in futs])
    want, ok = fleet_reference(fleet, x, names)
    np.testing.assert_array_equal(got[ok], want[ok])
    log(f"[serve] SVMEngine over fleet {fleet.model_ids} "
        f"(use_pallas={fleet.use_pallas}): {len(futs)} requests resolved, "
        f"labels equal members' predict on {int(ok.sum())}/{len(ok)} stable "
        f"rows ({int((got == want).sum())}/{len(ok)} overall); "
        f"{since(t0)}")


def tie_tolerance(machine, x) -> int:
    """How many validation rows sit on a comparator tie."""
    return int((~stable_rows(machine.decision_scores(x))).sum())


def phase_signoff(ds, est, circuit_m) -> None:
    import math

    import jax

    from repro.api.compiled import MC_STREAM_CHUNK

    x, y = ds.x_test[:DEPLOY_ROWS], ds.y_test[:DEPLOY_ROWS]
    acc = circuit_m.accuracy(x, y)
    n_tie = tie_tolerance(circuit_m, x)
    t0 = time.perf_counter()
    nom = est.monte_carlo(x, y, n_variants=NOMINAL_CHUNK, sigma_scale=0.0,
                          method=MC_METHOD, mc_chunk=NOMINAL_CHUNK)
    if nom.worst != nom.best or abs(nom.mean - acc) > n_tie / len(y) + 1e-6:
        raise AssertionError(
            f"zero-variation stream {nom.worst}..{nom.best} (mean "
            f"{nom.mean}) != circuit machine accuracy {acc} "
            f"(tie rows {n_tie})")
    log(f"[signoff] zero-variation stream on {len(y)} rows: accuracy "
        f"{nom.mean:.6f} == machine.accuracy {acc:.6f} (tie rows {n_tie}); "
        f"{since(t0)}")
    x, y = x[:MC_ROWS], y[:MC_ROWS]
    t0 = time.perf_counter()
    res = est.monte_carlo(x, y, n_variants=MC_STREAM_CHUNK,
                          method=MC_METHOD)
    sm = est.stream_machine(jax.random.PRNGKey(est.seed), method=MC_METHOD)
    vals = [res.mean, res.std, res.worst, res.best, res.yield_,
            res.yield_lo, res.yield_hi]
    if not (all(math.isfinite(v) for v in vals)
            and 0.0 <= res.worst <= res.mean <= res.best <= 1.0
            and 0.0 <= res.yield_lo <= res.yield_ <= res.yield_hi <= 1.0):
        raise AssertionError(f"malformed streaming result {vals}")
    log(f"[signoff] streaming {MC_METHOD} MC on the har12 circuit machine "
        f"({sm.mismatch_dim} mismatch dims), default chunk "
        f"{sm.mc_chunk}: V={res.n_variants} over {len(y)} rows: mean "
        f"{res.mean:.4f} std {res.std:.4f} worst {res.worst:.4f} yield "
        f"{res.yield_:.4f} [{res.yield_lo:.4f}, {res.yield_hi:.4f}] at "
        f"floor {res.accuracy_floor:.4f}; {since(t0)}")


def phase_sweep(ds, est) -> None:
    t0 = time.perf_counter()
    sw = est.pareto(ds.x_test, ds.y_test, **SWEEP_KW)
    if sw.n_pairs != 66 or sw.exhaustive or len(sw.front) == 0:
        raise AssertionError(
            f"P=66 portfolio sweep: pairs={sw.n_pairs} exhaustive="
            f"{sw.exhaustive} front={len(sw.front)}")
    log(f"[sweep] portfolio DSE at P={sw.n_pairs}: {len(sw.accuracy)} "
        f"assignments evaluated, front of {len(sw.front)} (best accuracy "
        f"{float(sw.accuracy[sw.front].max()):.4f}); {since(t0)}")


def one_chip(har12_model=None) -> None:
    from repro.core.svm import resolve_use_pallas

    if resolve_use_pallas(None) is not True:
        raise AssertionError("use_pallas does not resolve to True on the TPU")
    ds12, est12 = fit("har12")
    if har12_model:
        est12.save(har12_model)
        log(f"[fit] har12 estimator saved to {har12_model}")
    phase_solver(ds12)
    rbf_m, circuit_m = phase_deploy(ds12, est12)
    dsb, estb = fit("balance")
    phase_serve({"har12": rbf_m, "balance": estb.deploy("circuit")},
                {"har12": ds12.x_test, "balance": dsb.x_test})
    phase_signoff(ds12, est12, circuit_m)
    phase_sweep(ds12, est12)


# -- four chips --------------------------------------------------------------


def four_chips(har12_model=None) -> None:
    import numpy as np

    from repro.api import compile_fleet
    from repro.api.compiled import MC_STREAM_CHUNK
    from repro.launch.mesh import make_serving_mesh, make_variant_mesh

    ds12, est12 = fit("har12", har12_model)
    dsb, estb = fit("balance")
    fleet = compile_fleet({"har12": est12.deploy("rbf"),
                           "balance": estb.deploy("circuit")})
    pools = {"har12": ds12.x_test, "balance": dsb.x_test}
    x, names = fleet_requests(pools, 4 * 1024)
    idx = np.asarray([fleet.model_index(n) for n in names], np.int32)
    t0 = time.perf_counter()

    mesh = make_serving_mesh(4)
    out = fleet.shard(mesh)(x, idx.copy())
    shards = out.addressable_shards
    devs = {s.device for s in shards}
    if len(devs) != 4 or any(s.data.shape[0] != len(x) // 4 for s in shards):
        raise AssertionError(
            f"sharded labels landed on {len(devs)} device(s): "
            f"{[(str(s.device), s.data.shape) for s in shards]}")
    got = np.asarray(out)
    one = fleet.predict(x, names)
    want, ok = fleet_reference(fleet, x, names)
    np.testing.assert_array_equal(got[ok], one[ok])
    np.testing.assert_array_equal(got[ok], want[ok])
    log(f"[4-chip serve] fleet.shard(make_serving_mesh(4)): {len(x)} rows "
        f"in 4 slices of {len(x) // 4} on {sorted(str(d) for d in devs)}; "
        f"labels equal the one-device forward on {int(ok.sum())}/{len(ok)} "
        f"stable rows ({int((got == one).sum())}/{len(ok)} overall); "
        f"{since(t0)}")

    vmesh = make_variant_mesh(4)
    if len(set(vmesh.devices.ravel().tolist())) != 4:
        raise AssertionError(f"variant mesh devices {vmesh.devices}")
    x, y = ds12.x_test[:FOUR_CHIP_MC_ROWS], ds12.y_test[:FOUR_CHIP_MC_ROWS]
    kw = dict(n_variants=MC_STREAM_CHUNK, method=MC_METHOD)
    t0 = time.perf_counter()
    r4 = est12.monte_carlo(x, y, mesh=vmesh, **kw)
    r1 = est12.monte_carlo(x, y, **kw)
    # Exact counts and extrema; the mean only to f32 summation order (the
    # mesh adds four partial sums), far below one flipped sample's
    # 1 / (rows x V).
    got = (round(r4.yield_ * r4.n_variants), r4.worst, r4.best)
    want = (round(r1.yield_ * r1.n_variants), r1.worst, r1.best)
    if got != want or abs(r4.mean - r1.mean) > 1e-6:
        raise AssertionError(
            f"variant mesh (in-spec, worst, best) {got}, mean {r4.mean} "
            f"!= one device {want}, mean {r1.mean}")
    log(f"[4-chip signoff] monte_carlo(mesh=make_variant_mesh(4)), V="
        f"{r4.n_variants} {MC_METHOD} over {len(y)} rows: in-spec variants "
        f"{got[0]} vs {want[0]}, worst {r4.worst:.4f} vs {r1.worst:.4f}, "
        f"best {r4.best:.4f} vs {r1.best:.4f}, mean {r4.mean:.7f} vs "
        f"{r1.mean:.7f} on one device; {since(t0)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh paths")
    ap.add_argument("--har12-model", metavar="PATH",
                    help="one chip: save the har12 fit to PATH; with "
                         "--four-chips: load it from PATH instead of "
                         "fitting har12 again")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = device_gate(n_chips)
    log(f"[setup] {len(devs)} x {devs[0].device_kind}; compile cache at "
        f"{cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.har12_model)
    else:
        one_chip(args.har12_model)
    log(f"[done] all phases passed; smoke timing "
        f"{time.perf_counter() - t0:.1f}s")
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
