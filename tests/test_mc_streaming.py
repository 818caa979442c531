"""Tests for the streaming tail-yield Monte-Carlo engine (DESIGN.md §10).

Covers the tentpole guarantees:

  * streamed mean/std/min/yield match the dense oracle — the SAME
    fold_in-keyed variants through ``pair_bits_dense`` + the batched
    recombination — at small V (documented tolerance: 1e-4 moments,
    exact extrema/exceedance);
  * chunk edges: V = 1, V = chunk, V = chunk + 1, and chunk-size
    invariance of the whole statistics dict;
  * scrambled-Sobol determinism from the stored key (+ chunk-size
    invariance of the fast-forwarded sequence);
  * importance sampling: ``is_scale = 1`` degenerates to the iid stream
    with unit weights, and the self-normalized streamed yield equals the
    brute-force weighted estimate from the dense oracle;
  * the Wilson / Clopper-Pearson bounds and the fixed-grid quantile
    sketch against closed-form references;
  * the ``shard_map`` leg over ``make_variant_mesh`` reproduces the
    single-device stream (8 fake devices, subprocess);
  * the assignment-chunked recombination (``mc_chunk=``) is a pure
    program-shape knob.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api import compiled as api
from repro.core import dse, mcstream, trainer
from repro.core.analog import AnalogBinaryClassifier, variant_dim
from repro.core.svm import SVMModel

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")

#: Moment tolerance of the streamed-vs-dense parity contract (f32
#: accumulation order differs between the two programs).
MOMENT_TOL = 1e-4


def _tiny_candidates(m: int = 6, d: int = 3):
    rng = np.random.default_rng(0)
    sx = rng.normal(size=(m, d)).astype(np.float32)
    sy = (np.arange(m) % 2 * -2 + 1).astype(np.float32)
    alpha = (np.abs(rng.normal(size=m)) + 0.1).astype(np.float32)
    w = ((alpha * sy) @ sx).astype(np.float32)
    lin = SVMModel(kind="linear", support_x=sx, support_y=sy, alpha=alpha,
                   bias=0.1, gamma=1.0, c=1.0, w=w)
    rbf = SVMModel(kind="rbf", support_x=sx, support_y=sy, alpha=alpha,
                   bias=-0.05, gamma=0.7, c=1.0)
    hw_clf = AnalogBinaryClassifier.deploy(rbf, trainer.default_hw(0))
    hw_small = AnalogBinaryClassifier.deploy(
        SVMModel(kind="rbf", support_x=sx[:4], support_y=sy[:4],
                 alpha=alpha[:4], bias=0.02, gamma=0.9, c=1.0),
        trainer.default_hw(0))
    return [(lin, rbf), (lin, hw_clf), (lin, hw_small)]


@pytest.fixture(scope="module")
def tiny():
    rng = np.random.default_rng(1)
    cands = _tiny_candidates()
    x = rng.normal(size=(40, 3)).astype(np.float32)
    y = rng.integers(0, 3, size=40).astype(np.int32)
    a = np.ones((1, 3), bool)
    return cands, x, y, a


def _dense_stats(sm, x, y, a, n_variants, floor, chunk=8):
    """Brute-force oracle: the machine's own dense bits, recombined."""
    bits = np.concatenate([
        sm.pair_bits_dense(x, np.arange(s, min(s + chunk, n_variants)))
        for s in range(0, n_variants, chunk)])
    acc = dse.assignment_accuracies_mc(bits, a, y, 3)
    return acc


# -- streamed vs dense parity -------------------------------------------------


def test_streamed_matches_dense_oracle(tiny):
    cands, x, y, a = tiny
    sm = api.compile_mc_stream(cands, n_classes=3,
                               key=jax.random.PRNGKey(0), mc_chunk=8)
    floor = 0.6
    out = sm.stream(x, y, a, n_variants=21, accuracy_floor=floor)
    acc = _dense_stats(sm, x, y, a, 21, floor)
    assert abs(out["mean"][0] - acc.mean()) < MOMENT_TOL
    assert abs(out["std"][0] - acc.std()) < MOMENT_TOL
    assert out["worst"][0] == acc.min()
    assert out["best"][0] == acc.max()
    assert out["yield"][0] == (acc >= floor).mean()
    assert out["count"] == 21.0 and out["n_eff"] == pytest.approx(21.0)


def test_multi_assignment_columns(tiny):
    cands, x, y, _ = tiny
    sm = api.compile_mc_stream(cands, n_classes=3,
                               key=jax.random.PRNGKey(0), mc_chunk=8)
    a = np.array([[1, 1, 1], [0, 0, 0], [1, 0, 1]], bool)
    out = sm.stream(x, y, a, n_variants=17, accuracy_floor=0.5)
    acc = _dense_stats(sm, x, y, a, 17, 0.5)
    np.testing.assert_allclose(out["mean"], acc.mean(0), atol=MOMENT_TOL)
    np.testing.assert_array_equal(out["worst"], acc.min(0))
    np.testing.assert_array_equal(out["yield"], (acc >= 0.5).mean(0))


# -- chunk edges and invariance ----------------------------------------------


@pytest.mark.parametrize("n_variants", [1, 8, 9])
def test_chunk_edges(tiny, n_variants):
    """V = 1, V = chunk, V = chunk + 1: the padded tail stays inert."""
    cands, x, y, a = tiny
    sm = api.compile_mc_stream(cands, n_classes=3,
                               key=jax.random.PRNGKey(0), mc_chunk=8)
    out = sm.stream(x, y, a, n_variants=n_variants, accuracy_floor=0.6)
    acc = _dense_stats(sm, x, y, a, n_variants, 0.6)
    assert out["count"] == float(n_variants)
    assert abs(out["mean"][0] - acc.mean()) < MOMENT_TOL
    assert out["worst"][0] == acc.min()
    assert out["yield"][0] == (acc >= 0.6).mean()


def test_chunk_size_invariance(tiny):
    """The whole statistics dict is a pure function of (key, V)."""
    cands, x, y, a = tiny
    outs = []
    for chunk in (5, 8, 32):
        sm = api.compile_mc_stream(cands, n_classes=3,
                                   key=jax.random.PRNGKey(0),
                                   mc_chunk=chunk)
        outs.append(sm.stream(x, y, a, n_variants=21, accuracy_floor=0.6))
    for out in outs[1:]:
        for k in ("mean", "std", "worst", "best", "yield", "yield_lo"):
            np.testing.assert_allclose(out[k], outs[0][k], atol=2e-6)
        np.testing.assert_allclose(out["hist"], outs[0]["hist"])


@pytest.mark.parametrize("batch", [1, 3])
def test_variant_slices_match_one_pass(tiny, monkeypatch, batch):
    """Past ``MC_LANE_BYTES`` the analog lanes take a chunk's variants in
    sequential slices (``_variant_batch``; 3 leaves a ragged last slice
    of 8): the bits and the stream equal those of one pass."""
    cands, x, y, a = tiny

    def run():
        sm = api.compile_mc_stream(cands, n_classes=3,
                                   key=jax.random.PRNGKey(0), mc_chunk=8)
        return (sm.pair_bits_dense(x, np.arange(8)),
                sm.stream(x, y, a, n_variants=21, accuracy_floor=0.6))

    bits, out = run()
    monkeypatch.setattr(api, "_variant_batch", lambda *shape: batch)
    bits_b, out_b = run()
    np.testing.assert_array_equal(bits_b, bits)
    assert abs(out_b["mean"][0] - out["mean"][0]) < MOMENT_TOL
    assert out_b["worst"][0] == out["worst"][0]
    assert out_b["yield"][0] == out["yield"][0]


def test_stream_rejects_bad_config(tiny):
    cands, x, y, a = tiny
    with pytest.raises(ValueError, match="method"):
        api.compile_mc_stream(cands, n_classes=3,
                              key=jax.random.PRNGKey(0), method="mcmc")
    with pytest.raises(ValueError, match="mc_chunk"):
        api.compile_mc_stream(cands, n_classes=3,
                              key=jax.random.PRNGKey(0), mc_chunk=0)
    sm = api.compile_mc_stream(cands, n_classes=3,
                               key=jax.random.PRNGKey(0), mc_chunk=8)
    with pytest.raises(ValueError, match="n_variants"):
        sm.stream(x, y, a, n_variants=0, accuracy_floor=0.5)


# -- QMC ---------------------------------------------------------------------


def test_sobol_deterministic_from_key(tiny):
    cands, x, y, a = tiny
    mk = lambda key, chunk: api.compile_mc_stream(
        cands, n_classes=3, key=key, method="sobol", mc_chunk=chunk)
    out1 = mk(jax.random.PRNGKey(7), 8).stream(
        x, y, a, n_variants=24, accuracy_floor=0.6)
    out2 = mk(jax.random.PRNGKey(7), 8).stream(
        x, y, a, n_variants=24, accuracy_floor=0.6)
    np.testing.assert_array_equal(out1["hist"], out2["hist"])
    assert out1["mean"][0] == out2["mean"][0]
    # fast_forward makes the sequence chunk-size invariant
    out3 = mk(jax.random.PRNGKey(7), 16).stream(
        x, y, a, n_variants=24, accuracy_floor=0.6)
    np.testing.assert_allclose(out3["mean"], out1["mean"], atol=2e-6)
    # a different key scrambles differently
    out4 = mk(jax.random.PRNGKey(8), 8).stream(
        x, y, a, n_variants=24, accuracy_floor=0.6)
    assert not np.array_equal(out4["hist"], out1["hist"])


def test_sobol_dense_oracle_parity(tiny):
    """pair_bits_dense replays the SAME Sobol draws as the stream."""
    cands, x, y, a = tiny
    sm = api.compile_mc_stream(cands, n_classes=3,
                               key=jax.random.PRNGKey(3), method="sobol",
                               mc_chunk=8)
    out = sm.stream(x, y, a, n_variants=16, accuracy_floor=0.6)
    acc = _dense_stats(sm, x, y, a, 16, 0.6)
    assert abs(out["mean"][0] - acc.mean()) < MOMENT_TOL
    assert out["worst"][0] == acc.min()
    assert out["yield"][0] == (acc >= 0.6).mean()


# -- importance sampling ------------------------------------------------------


def test_is_scale_one_degenerates_to_iid(tiny):
    """is_scale = 1: identical draws to the iid stream, unit weights."""
    cands, x, y, a = tiny
    iid = api.compile_mc_stream(cands, n_classes=3,
                                key=jax.random.PRNGKey(0), mc_chunk=8)
    is1 = api.compile_mc_stream(cands, n_classes=3,
                                key=jax.random.PRNGKey(0), method="is",
                                is_scale=1.0, mc_chunk=8)
    np.testing.assert_allclose(is1.chunk_weights(np.arange(8)), 1.0,
                               atol=1e-5)
    o1 = iid.stream(x, y, a, n_variants=20, accuracy_floor=0.6)
    o2 = is1.stream(x, y, a, n_variants=20, accuracy_floor=0.6)
    np.testing.assert_allclose(o2["mean"], o1["mean"], atol=2e-6)
    np.testing.assert_array_equal(o2["worst"], o1["worst"])
    assert o2["n_eff"] == pytest.approx(o1["n_eff"], rel=1e-4)


def test_is_yield_matches_brute_force_weighted_estimate(tiny):
    """Self-normalized streamed yield == sum(w 1[acc >= floor]) / sum(w)
    with the weights and accuracies both read back densely."""
    cands, x, y, a = tiny
    sm = api.compile_mc_stream(cands, n_classes=3,
                               key=jax.random.PRNGKey(0), method="is",
                               is_scale=1.3, mc_chunk=8)
    floor, v = 0.6, 24
    out = sm.stream(x, y, a, n_variants=v, accuracy_floor=floor)
    acc = np.asarray(_dense_stats(sm, x, y, a, v, floor)[:, 0], np.float64)
    w = np.concatenate([np.asarray(sm.chunk_weights(np.arange(s, s + 8)),
                                   np.float64)
                        for s in range(0, v, 8)])
    assert np.isfinite(w).all() and w.min() > 0
    expect_yield = float((w * (acc >= floor)).sum() / w.sum())
    expect_mean = float((w * acc).sum() / w.sum())
    expect_neff = float(w.sum() ** 2 / (w * w).sum())
    assert out["yield"][0] == pytest.approx(expect_yield, abs=1e-5)
    assert out["mean"][0] == pytest.approx(expect_mean, abs=1e-4)
    assert out["n_eff"] == pytest.approx(expect_neff, rel=1e-3)


# -- the accumulator / bound / sketch layer ----------------------------------


def test_update_stream_matches_numpy_weighted_moments():
    rng = np.random.default_rng(0)
    acc = rng.uniform(0.3, 1.0, size=(48, 2)).astype(np.float32)
    w = rng.uniform(0.1, 3.0, size=48).astype(np.float32)
    state = mcstream.init_stream(2, mcstream.hist_bins(100))
    for s in range(0, 48, 16):
        state = mcstream.update_stream(
            state, acc[s:s + 16], w[s:s + 16],
            np.ones(16, np.float32), np.float32(0.6))
    out = mcstream.finalize(state)
    wm = (w[:, None] * acc).sum(0) / w.sum()
    wv = (w[:, None] * (acc - wm) ** 2).sum(0) / w.sum()
    np.testing.assert_allclose(out["mean"], wm, atol=1e-5)
    np.testing.assert_allclose(out["std"], np.sqrt(wv), atol=1e-5)
    np.testing.assert_allclose(
        out["yield"], (w[:, None] * (acc >= 0.6)).sum(0) / w.sum(),
        atol=1e-5)
    np.testing.assert_allclose(out["worst"], acc.min(0), atol=0)


def test_wilson_and_clopper_pearson_bounds():
    lo, hi = mcstream.wilson_bounds(1.0, 64)
    assert hi == pytest.approx(1.0)
    assert lo == pytest.approx(0.9434, abs=2e-4)  # 3.84/(64+3.84)
    lo0, hi0 = mcstream.wilson_bounds(0.0, 64)
    assert lo0 == 0.0 and hi0 == pytest.approx(1 - 0.9434, abs=2e-4)
    lo5, hi5 = mcstream.wilson_bounds(0.5, 100)
    assert lo5 == pytest.approx(0.404, abs=2e-3)
    assert hi5 == pytest.approx(0.596, abs=2e-3)
    scipy_stats = pytest.importorskip("scipy.stats")
    clo, chi = mcstream.clopper_pearson_bounds(0.9, 100)
    assert clo == pytest.approx(scipy_stats.beta.ppf(0.025, 90, 11),
                                abs=1e-6)
    assert chi == pytest.approx(scipy_stats.beta.ppf(0.975, 91, 10),
                                abs=1e-6)
    clo1, chi1 = mcstream.clopper_pearson_bounds(1.0, 64)
    assert chi1 == 1.0 and clo1 == pytest.approx(0.025 ** (1 / 64),
                                                 abs=1e-4)


@pytest.mark.parametrize("n", [64, 256, 1000, 16384])
def test_wilson_bounds_hold_the_point_estimate(n):
    """lo <= p <= hi exactly, also where rounding would put the bound of
    p = 1 one ulp below 1 (n = 256)."""
    p = np.array([0.0, 0.3, 1.0])
    lo, hi = mcstream.wilson_bounds(p, n)
    assert np.all(lo <= p) and np.all(p <= hi)
    assert lo[0] == 0.0 and hi[2] == 1.0


def test_hist_quantiles_exact_on_grid():
    """n_bins = n_val + 1 puts every attainable accuracy on a bin center,
    so the sketch's type-1 quantiles are exact."""
    n_val = 20
    acc = np.array([[5, 10, 10, 15, 18]], np.float32).T / n_val  # (5, 1)
    state = mcstream.init_stream(1, mcstream.hist_bins(n_val))
    state = mcstream.update_stream(
        state, acc, np.ones(5, np.float32), np.ones(5, np.float32),
        np.float32(0.5))
    qs = mcstream.hist_quantiles(np.asarray(state.hist),
                                 np.array([0.0, 0.2, 0.5, 1.0]))
    np.testing.assert_allclose(qs[:, 0],
                               [5 / 20, 5 / 20, 10 / 20, 18 / 20],
                               atol=1e-6)


# -- the sharded leg ----------------------------------------------------------


@pytest.mark.parametrize("assignment,restricted", [
    ([1, 1, 1], 0), ([1, 1, 0], 1)], ids=["candidate", "restricted"])
def test_sharded_stream_matches_local(assignment, restricted):
    """shard_map over the variants axis reproduces the single-device
    stream (8 fake devices, subprocess so XLA_FLAGS doesn't leak): with
    every pair selected (the candidate step) and with one of the two
    analog pairs (the restricted step, DESIGN.md §10.6)."""
    body = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax
        import sys
        sys.path.insert(0, os.path.join(%r, "tests"))
        from test_mc_streaming import _tiny_candidates
        from repro.api import compiled as api
        from repro.launch import mesh as mesh_mod

        rng = np.random.default_rng(1)
        x = rng.normal(size=(40, 3)).astype(np.float32)
        y = rng.integers(0, 3, size=40).astype(np.int32)
        a = np.array([%r], bool)
        cands = _tiny_candidates()
        sm = api.compile_mc_stream(cands, n_classes=3,
                                   key=jax.random.PRNGKey(0), mc_chunk=12)
        mesh = mesh_mod.make_variant_mesh()
        assert mesh.shape["variants"] == 8
        lo = sm.stream(x, y, a, n_variants=37, accuracy_floor=0.6)
        sh = sm.stream(x, y, a, n_variants=37, accuracy_floor=0.6,
                       mesh=mesh)
        assert sm.restricted_steps_run == %d * sm.steps_run
        assert sh["count"] == 37.0
        for k in ("mean", "std", "worst", "best", "yield"):
            np.testing.assert_allclose(sh[k], lo[k], atol=1e-5), k
        np.testing.assert_allclose(sh["hist"], lo["hist"], atol=1e-3)
        print("OK")
    """) % (os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir)), assignment, restricted)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, \
        f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "OK" in res.stdout


# -- the assignment-chunk knob (satellite: host loop now in-graph) ------------


def test_assignment_chunk_knob_is_pure_shape(tiny):
    cands, x, y, _ = tiny
    mcm = api.compile_variants(cands, n_classes=3,
                               key=jax.random.PRNGKey(0), n_variants=4)
    bits3 = mcm.pair_bits(x)
    a = np.array([[1, 1, 1], [0, 1, 0], [1, 0, 1], [0, 0, 1],
                  [1, 1, 0]], bool)
    ref = dse.assignment_accuracies_mc(bits3, a, y, 3)
    for chunk in (1, 2, 5, 16):
        got = dse.assignment_accuracies_mc(bits3, a, y, 3, mc_chunk=chunk)
        np.testing.assert_array_equal(got, ref)
    with pytest.raises(ValueError, match="mc_chunk"):
        dse.assignment_accuracies_mc(bits3, a, y, 3, mc_chunk=0)


def test_variant_dim_layout():
    """The flat QMC layout and the fold_in draw agree on the dim count."""
    assert variant_dim(6, 3) == 6 * 3 * 4 + 6 * 2 + 1
    sm = api.compile_mc_stream(_tiny_candidates(), n_classes=3,
                               key=jax.random.PRNGKey(0))
    # two analog banks: one m=6 pair and one m=4 pair padded to m_max
    assert sm.true_dim == variant_dim(6, 3) + variant_dim(4, 3)
    assert sm.mismatch_dim >= sm.true_dim
