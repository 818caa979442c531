"""AOT compiles of the main path's device programs for a TPU v5e chip.

No chip is needed: the TPU compiler ships with jaxlib and compiles for a
*described* ``v5e:2x2`` topology, so what Mosaic or XLA:TPU would refuse
on the chip — unaligned blocks, unsupported kernel ops, a program larger
than HBM — fails here.  Arguments are shapes only (``ShapeDtypeStruct``
on one described chip); nothing runs.

The topology is described inside a module fixture, never at import, and
every test of this kind lives in this one file: only one process at a
time may load the TPU library, and it keeps it until it exits.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

#: One v5e chip's HBM.
V5E_HBM_BYTES = 16 * 1024 ** 3
#: har12 at full training size: P = 66 OvO pairs over n_max = 1582 rows,
#: d = 5, the default 7 x 7 (gamma, C) grid x 5 folds = 35 C-lanes.
P, N_MAX, D, G, L = 66, 1582, 5, 7, 35
#: Serving bucket of the deployed forwards.
BUCKET = 256
#: Streaming Monte-Carlo at har12 size: the default 256-variant chunk
#: over 512 validation rows, the 66 analog candidates of one bank, up to
#: M = 745 support vectors each.
MC_CHUNK, MC_ROWS, MC_M = 256, 512, 745


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # Described-chip compiles cannot be read back from the persistent
        # cache without a chip: keep them out of it.
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this jaxlib
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
    return compiled


def test_rbf_matrix_vmapped_deploy_shapes(one_chip):
    """A deployed rbf bank (``deploy("rbf")``): ``ops.rbf_matrix`` vmapped
    over 66 pairs, bucket 256 x a support count as large as a pair's
    whole training subset (the worst case), d = 5."""
    from repro.kernels import ops

    def bank(x, sv, gamma):
        return jax.vmap(lambda s, g: ops.rbf_matrix(
            x, s, g, v_scale=1.0, interpret=False))(sv, gamma)

    compiled = _compile(bank, _spec(one_chip, (BUCKET, D)),
                        _spec(one_chip, (P, N_MAX, D)),
                        _spec(one_chip, (P,)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind,n_gamma", [("rbf", G), ("linear", 1)])
def test_solve_lanes_har12_full_size(one_chip, kind, n_gamma):
    """The fused solver at har12's full CV-grid lane shapes."""
    from repro.kernels import ops

    def solve(x, y, c_box, gamma):
        return ops.solve_lanes(x, y, c_box, gamma, kind=kind, n_epochs=2,
                               interpret=False)

    compiled = _compile(solve, _spec(one_chip, (P, N_MAX, D)),
                        _spec(one_chip, (P, N_MAX)),
                        _spec(one_chip, (P, L, N_MAX)),
                        _spec(one_chip, (P, n_gamma)))
    assert "tpu_custom_call" in compiled.as_text()


def test_circuit_fleet_forward_bucket(one_chip):
    """A two-tenant circuit fleet (har12 at full width K = 12, P = 66 —
    trained on a per-class subsample — and balance): its jitted labels
    program at one serving bucket."""
    from repro.api import MixedKernelSVM, compile_fleet
    from repro.data import datasets

    members = {}
    for name, per_class in (("har12", 12), ("balance", None)):
        ds = datasets.load(name)
        keep = np.arange(len(ds.y_train))
        if per_class is not None:
            keep = np.concatenate([np.flatnonzero(ds.y_train == c)[:per_class]
                                   for c in range(ds.n_classes)])
        est = MixedKernelSVM(n_epochs=5).fit(ds.x_train[keep],
                                             ds.y_train[keep])
        members[name] = est.deploy("circuit")
    fleet = compile_fleet(members)
    assert fleet.n_pairs_total == 66 + 3
    _compile(fleet._labels, _spec(one_chip, (BUCKET, fleet.n_features)),
             _spec(one_chip, (BUCKET,), jnp.int32))


#: The benchmark's balance signoff step: a 256-variant chunk over 128
#: rows, 3 analog candidates of up to M = 65 support vectors, d = 4.
BAL_CHUNK, BAL_ROWS, BAL_P, BAL_M, BAL_D = 256, 128, 3, 65, 4


def _curve_gathers(hlo: str, n: int) -> list:
    """The optimized HLO's gathers whose operand is an ``f32[n]`` curve."""
    shapes = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo))
    return [line for line in hlo.splitlines()
            if (m := re.search(r" gather\(%([\w.\-]+)", line))
            and shapes.get(m.group(1)) == f"f32[{n}]"]


def _compile_mc_lanes(one_chip, B, n, p, m, d):
    """``_bank_scores_mc`` for B variants over n rows of p analog pairs of
    M = m support vectors in d inputs, on the default measured curve;
    checks that every curve lookup went to the MXU, none to a gather."""
    from repro import obs
    from repro.api import compiled as api
    from repro.core import selection

    hw = selection.default_hw(0, None)
    grid = np.asarray(hw.dv_grid, np.float32)
    curve = np.asarray(hw.kernel_curve, np.float32)

    def lanes(x, sv, cpos, cneg, vshift, vgain, vcpos, vcneg, voff):
        z = jnp.zeros((p,), jnp.float32)
        bank = api._KernelBank(
            kind="hw", input_bits=0, pair_idx=np.arange(p), sv=sv,
            coef_pos=cpos, coef_neg=cneg, bias_pos=z, bias_neg=z, offset=z,
            gamma=z + 1.0, scale=z + 1.0, shift=z, grid=jnp.asarray(grid),
            curve=jnp.asarray(curve), **api._grid_fast_path(grid))
        bv = api._BankVariants(shift=vshift, gain=vgain, coef_pos=vcpos,
                               coef_neg=vcneg, offset=voff)
        return api._bank_scores_mc(bank, bv, x, use_pallas=False,
                                   include_nominal=False)

    before = obs.snapshot()["lut_mxu_traced"]
    compiled = _compile(
        lanes, _spec(one_chip, (n, d)), _spec(one_chip, (p, m, d)),
        _spec(one_chip, (p, m)), _spec(one_chip, (p, m)),
        _spec(one_chip, (B, p, m, d)), _spec(one_chip, (B, p, m, d)),
        _spec(one_chip, (B, p, m)), _spec(one_chip, (B, p, m)),
        _spec(one_chip, (B, p)))
    assert obs.snapshot()["lut_mxu_traced"] > before
    hlo = compiled.as_text()
    assert not _curve_gathers(hlo, curve.size)
    return compiled


def test_mc_analog_lanes_har12_chunk(one_chip):
    """The streaming engine's analog variant lanes at har12 size: without
    the variant slicing of ``MC_LANE_BYTES`` the lookups' temporaries ask
    for 412 GB on a 16 GB chip."""
    from repro.api import compiled as api

    assert api._variant_batch(MC_ROWS, P, MC_M) < MC_CHUNK
    _compile_mc_lanes(one_chip, MC_CHUNK, MC_ROWS, P, MC_M, D)


def test_mc_analog_lanes_balance_chunk(one_chip):
    """The benchmark's signoff lanes: one slice of the whole chunk, every
    curve lookup a one-hot contraction on the MXU."""
    from repro.api import compiled as api

    assert api._variant_batch(BAL_ROWS, BAL_P, BAL_M) >= BAL_CHUNK
    compiled = _compile_mc_lanes(one_chip, BAL_CHUNK, BAL_ROWS, BAL_P,
                                 BAL_M, BAL_D)
    assert " convolution(" in compiled.as_text()


#: The benchmark's ten-class signoff lanes (``signoff.digits``): a
#: 256-variant chunk over 128 rows, the 45 analog candidates of the
#: optdigits machine padded to M = 165, d = 5.
DIG_CHUNK, DIG_ROWS, DIG_P, DIG_M, DIG_D = 256, 128, 45, 165, 5


def test_mc_analog_lanes_digits_slices(one_chip):
    """The ten-class signoff lanes run in several slices, and one slice's
    temporaries fit ``MC_LANE_BYTES`` without falling far below it: the
    per-cell count of ``_variant_batch`` matches what the TPU compile
    holds, every lookup on the MXU."""
    from repro.api import compiled as api

    assert api._lane_slices(DIG_CHUNK, DIG_ROWS, DIG_P, DIG_M) > 1
    compiled = _compile_mc_lanes(one_chip, DIG_CHUNK, DIG_ROWS, DIG_P,
                                 DIG_M, DIG_D)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert api.MC_LANE_BYTES / 2 <= temp <= api.MC_LANE_BYTES
