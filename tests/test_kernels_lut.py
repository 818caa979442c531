"""The gather-free measured-curve lookup is the gather, bit for bit.

``core.kernels.curve_pair_mxu`` (the TPU's lookup: a bf16 one-hot
contracted against the curve split into three bf16 parts) must return
exactly what ``curve_pair_gather`` (``jnp.take``) returns, and
``_uniform_interp`` built on either must give the same bits, inside the
grid, below it, above it and exactly at its upper end.  Runs on the CPU:
the contraction is the same ``dot_general`` the TPU lowers to the MXU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import kernels as kern
from repro.core import selection


def _curve(n: int, seed: int) -> np.ndarray:
    """Entries of both signs over 1e-30 .. 1e3, with a tiny, a negative,
    a zero and values at and just under 1.0 in fixed places."""
    rng = np.random.default_rng(seed)
    c = rng.choice([-1.0, 1.0], n) * 10.0 ** rng.uniform(-30, 3, n)
    c[:5] = [1e-30, -0.75, 0.0, 1.0, np.nextafter(np.float32(1), 0)]
    return c.astype(np.float32)


def _bits(a) -> np.ndarray:
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("n", [257, 33, 1025])
def test_mxu_lookup_is_the_gather(n):
    curve = jnp.asarray(_curve(n, n))
    n_seg = n - 1
    rng = np.random.default_rng(n + 1)
    idx = np.concatenate([[0, n_seg - 1], np.arange(n_seg),
                          rng.integers(0, n_seg, 4096)]).astype(np.int32)
    idx = np.resize(idx, 4 * -(-idx.size // 4))
    # the leading axes of the lanes: (variants, pairs, rows, slots)
    i = jnp.asarray(idx.reshape(2, -1, 2))
    f0, f1 = jax.jit(kern.curve_pair_mxu)(curve, i)
    np.testing.assert_array_equal(_bits(f0), _bits(jnp.take(curve, i)))
    np.testing.assert_array_equal(_bits(f1), _bits(jnp.take(curve, i + 1)))


def _queries(lo: float, hi: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    span = hi - lo
    return np.concatenate([
        [lo - 1.0, lo - 1e-6, lo, hi, hi + 1e-6, hi + 1.0],
        rng.uniform(lo, hi, 2000),
        rng.uniform(lo - span, lo, 50), rng.uniform(hi, hi + span, 50),
    ]).astype(np.float32)


def _hw_curve():
    hw = selection.default_hw(0, None)
    return (np.asarray(hw.dv_grid, np.float32),
            np.asarray(hw.kernel_curve, np.float32))


@pytest.mark.parametrize("which", ["hw", "random"])
def test_uniform_interp_same_on_either_lookup(which, monkeypatch):
    if which == "hw":
        grid, curve = _hw_curve()
    else:
        curve = _curve(257, 7)
        grid = np.linspace(-0.4, 0.4, 257).astype(np.float32)
    fp = kern._grid_fast_path(grid)
    lo, hi = float(grid[0]), float(grid[-1])
    v = jnp.asarray(_queries(lo, hi, 3).reshape(-1, 2))

    def interp():  # a fresh function each time: jit caches traces by it
        return jax.jit(lambda q: kern._uniform_interp(
            q, jnp.asarray(curve), lo, hi, float(curve[0]), float(curve[-1]),
            jnp.float32(fp["inv_step"])))

    want = interp()(v)
    # The CPU lowers the default branch: put the MXU lookup there.
    monkeypatch.setattr(kern, "curve_pair_gather", kern.curve_pair_mxu)
    before = obs.snapshot()["lut_mxu_traced"]
    got = interp()(v)
    assert obs.snapshot()["lut_mxu_traced"] == before + 1
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(np.asarray(want)[v < lo], curve[0])
    np.testing.assert_array_equal(np.asarray(want)[v > hi], curve[-1])


def test_cpu_lowers_the_gather_alone():
    """On the CPU the program holds the gather, and only the gather."""
    grid, curve = _hw_curve()
    fp = kern._grid_fast_path(grid)
    before = obs.snapshot()

    def cell(v):
        return kern.measured_cell(v, jnp.asarray(grid), jnp.asarray(curve),
                                  float(curve[0]), float(curve[-1]), True,
                                  jnp.float32(fp["inv_step"]))

    text = jax.jit(cell).lower(
        jnp.zeros((3, 5), jnp.float32)).compile().as_text()
    after = obs.snapshot()
    assert after["lut_gather_traced"] == before["lut_gather_traced"] + 1
    assert after["lut_mxu_traced"] == before["lut_mxu_traced"]
    assert "/hw.lut/" in text
    assert " gather(" in text and " convolution(" not in text
