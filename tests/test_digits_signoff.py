"""A ten-class machine (K = 10, P = 45 > MAX_TABLE_BITS) through the
streaming Monte-Carlo signoff: the votes recombination, the sliced analog
lanes and their counters, held to the benchmark's plain reference; and the
optdigits dataset that backs the benchmark's ten-class configuration.

The machines are seeded random pair models written in the estimator's
save format (``<name>.npz`` + ``<name>.json``), so the system loads them
through ``MixedKernelSVM.load`` and the reference reads the same files.
"""
import hashlib
import itertools
import json
import os
import shutil
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import MixedKernelSVM
from repro.api import compiled as api
from repro.core import dse
from repro.core.ovo import MAX_TABLE_BITS
from repro.data import datasets

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import registry  # noqa: E402
from reference.circuit import Machine  # noqa: E402

signoff = registry.Registry().generator("signoff")

D = 5
V, CHUNK, ROWS = 32, 16, 24
KEY, FLOOR = 13, 0.3
TIE_EPS = {"linear": 1e-4, "rbf": 1e-3}


def _kernel_map(n_pairs: int) -> list:
    """A fixed mix: every third pair digital linear, the rest analog."""
    return ["linear" if p % 3 == 0 else "rbf" for p in range(n_pairs)]


def _write_fit(path: str, k: int, seed: int = 0) -> None:
    """A seeded random K-class machine in the fit file format: per pair a
    linear model, an ideal RBF model and a hardware (``hw``) model of a
    few support vectors, and the kernel Algorithm 1 'chose'."""
    rng = np.random.default_rng(seed)
    pairs = list(itertools.combinations(range(k), 2))
    kmap = _kernel_map(len(pairs))
    arrays, meta_pairs = {}, []
    for i, (pair, kind) in enumerate(zip(pairs, kmap)):
        m = int(rng.integers(3, 9))
        sx = rng.uniform(0.0, 1.0, (m, D)).astype(np.float32)
        sy = np.where(np.arange(m) % 2 == 0, 1.0, -1.0).astype(np.float32)
        alpha = rng.uniform(0.2, 1.0, m).astype(np.float32)
        w = rng.normal(0.0, 1.0, D).astype(np.float32)
        models = {}
        for slot, mkind in (("model_linear", "linear"),
                            ("model_rbf", "rbf"), ("model_hw", "hw")):
            has_w = mkind == "linear"
            models[slot] = {
                "kind": mkind, "bias": float(rng.normal(0.0, 0.3)),
                "gamma": float(rng.uniform(0.5, 3.0)), "c": 10.0,
                "has_w": has_w}
            if has_w:
                arrays[f"p{i}.{slot}.w"] = w
            else:
                arrays[f"p{i}.{slot}.support_x"] = sx
                arrays[f"p{i}.{slot}.support_y"] = sy
                arrays[f"p{i}.{slot}.alpha"] = alpha
        meta_pairs.append({"pair": list(pair), "kernel": kind,
                           "acc_linear": 0.5, "acc_rbf": 0.5,
                           "models": models})
    meta = {"format": "repro.api.MixedKernelSVM", "version": 1,
            "n_classes": k,
            "config": {"weight_bits": 8, "input_bits": 4, "n_epochs": 1,
                       "seed": 0, "tie_margin": 0.005,
                       "alpha_floor_rel": 0.00390625, "cv_epochs": None,
                       "hw_all": True, "circuit": None},
            "assignment": None, "monte_carlo": None, "pairs": meta_pairs}
    np.savez(path + ".npz", **arrays)
    with open(path + ".json", "w") as f:
        json.dump(meta, f)


def _reference(path: str) -> Machine:
    """The plain reference of the fit at ``path``, deployed with the
    benchmark configurations' circuit and deployment settings."""
    with open(os.path.join(BENCH, "configs", "balance.json")) as f:
        cfg = json.load(f)
    cfg["data"] = {"fit": os.path.basename(path)}
    return Machine(cfg, os.path.dirname(path))


@pytest.fixture(scope="module")
def ten_class(tmp_path_factory):
    """(fit path, x, y): the K = 10 machine and 24 rows to sign off."""
    path = str(tmp_path_factory.mktemp("k10") / "k10_fit")
    _write_fit(path, 10)
    rng = np.random.default_rng(1)
    x = rng.uniform(0.0, 1.0, (ROWS, D)).astype(np.float32)
    y = rng.integers(0, 10, ROWS).astype(np.int32)
    return path, x, y


def _signoff(path, x, y):
    """A fresh load of the machine, one streaming job; (answer, engine):
    the engine is the one streaming machine the job cached."""
    est = MixedKernelSVM.load(path)
    r = est.monte_carlo(x, y, n_variants=V, method="iid", mc_chunk=CHUNK,
                        key=jax.random.PRNGKey(KEY), accuracy_floor=FLOOR)
    got = dict(signoff.answer(r.mean, r.worst, r.best,
                              r.yield_ * r.n_variants, r.hist), n=len(y))
    (sm,) = est._stream_machines.values()
    return got, sm


def test_ten_class_signoff_agrees_with_reference(ten_class):
    """Past the encoder table (P = 45), the streamed signoff of a mixed
    linear/analog assignment reads 0 and 0 against the reference."""
    path, x, y = ten_class
    assert len(_kernel_map(45)) > MAX_TABLE_BITS
    got, sm = _signoff(path, x, y)
    want = signoff.reference_answer(_reference(path), x, y, KEY, V, FLOOR,
                                    TIE_EPS)
    g = signoff.gaps(got, want, FLOOR)
    assert g["variants_unexplained"] == 0.0
    # The program's mean is a float32 fold: V x n x its rounding is far
    # below the one-row resolution the limit (0.5) is set at.
    assert g["fold_gap"] <= 1e-4
    assert (sm.steps_run, sm.variants_run) == (V // CHUNK, V)


def test_sliced_lanes_match_one_slice(ten_class, monkeypatch):
    """With ``MC_LANE_BYTES`` cut so the largest bank of the signoff's
    (restricted) step runs a chunk's variant lanes in 4 slices (3 of 5
    variants and 1 of 1), every variant's correct-row count and the bits
    are those of one slice, and ``lane_slices_run`` counts the slices of
    every step."""
    path, x, y = ten_class
    a = dse.assignment_from_kernel_map(_kernel_map(45))[None, :]
    got, sm = _signoff(path, x, y)
    assert sm.lane_slices_run == sm.steps_run == sm.restricted_steps_run
    bits = sm.pair_bits_dense(x, np.arange(CHUNK), a)
    grid = max(sm.lane_grids(a), key=lambda g: g[0] * g[1])
    cells = ROWS * grid[0] * grid[1]
    monkeypatch.setattr(api, "MC_LANE_BYTES", 5 * api._LANE_CELL_BYTES * cells)
    assert api._variant_batch(ROWS, *grid) == 5
    got_s, sm_s = _signoff(path, x, y)
    assert sm_s.lane_slices_run == 4 * sm_s.steps_run
    np.testing.assert_array_equal(got_s["rows"], got["rows"])
    np.testing.assert_array_equal(
        sm_s.pair_bits_dense(x, np.arange(CHUNK), a), bits)


def test_lane_cell_bytes_cover_the_step(ten_class, monkeypatch):
    """The per-cell count of ``_variant_batch`` covers the compiled chunk
    step's temporaries (CPU ``memory_analysis``): at one ``vmap`` of the
    whole chunk they stay within ``_LANE_CELL_BYTES`` a lane cell, and
    slices of 4 variants never ask for more than one pass.  (How a slice's
    temporaries grow on the TPU is held in ``test_tpu_compile.py``.)"""
    path, x, y = ten_class
    temp = {}
    for batch in (CHUNK, 4):
        sm = MixedKernelSVM.load(path).stream_machine(
            jax.random.PRNGKey(KEY), method="iid", mc_chunk=CHUNK)
        (grid,) = sm.lane_grids()
        cells = ROWS * grid[0] * grid[1]
        monkeypatch.setattr(api, "MC_LANE_BYTES",
                            batch * api._LANE_CELL_BYTES * cells)
        assert sm._step_slices(CHUNK, ROWS) == CHUNK // batch
        temp[batch] = sm.compiled_step(ROWS).memory_analysis() \
            .temp_size_in_bytes
    assert 0 < temp[CHUNK] <= api._LANE_CELL_BYTES * CHUNK * cells
    assert temp[4] <= temp[CHUNK]


def test_recombination_path_is_counted(ten_class, tmp_path):
    """Lowering the step counts the recombination it holds: the votes past
    ``MAX_TABLE_BITS`` pairs (K = 10), the encoder table below (K = 3)."""
    path, x, y = ten_class
    three = str(tmp_path / "k3_fit")
    _write_fit(three, 3, seed=3)
    for fit, k, rose, still in ((path, 10, "recombine_votes_traced",
                                 "recombine_table_traced"),
                                (three, 3, "recombine_table_traced",
                                 "recombine_votes_traced")):
        sm = MixedKernelSVM.load(fit).stream_machine(
            jax.random.PRNGKey(KEY), method="iid", mc_chunk=CHUNK)
        before = obs.snapshot()
        hlo = sm.compiled_step(ROWS).as_text()
        after = obs.snapshot()
        assert after[rose] > before[rose], k
        assert after[still] == before[still], k
        scope = rose.split("_")[1]
        assert f"mc.recombine/mc.recombine.{scope}/" in hlo, k


def test_digits_dataset():
    """The committed optdigits test part: checked by sha256, 1,797 rows of
    64 pixel counts and 10 digits, split 1,258/539, 5 selected inputs."""
    with open(datasets.DIGITS_FILE, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == datasets.DIGITS_SHA256
    x, y = datasets._digits_raw()
    assert x.shape == (1797, 64) and x.min() == 0 and x.max() == 16
    assert np.bincount(y).min() == 174 and np.bincount(y).max() == 183
    ds = datasets.load("digits")
    assert ds.n_classes == 10
    assert (len(ds.y_train), len(ds.y_test)) == (1258, 539)
    assert ds.n_features == 5 and len(ds.feature_idx) == 5
    assert ds.x_train.min() >= 0.0 and ds.x_train.max() <= 1.0
    assert "digits" in datasets.SCALE_DATASETS
    assert "digits" not in datasets.DATASETS


def test_digits_file_is_checked(tmp_path, monkeypatch):
    """A changed byte in the data file is refused, not read."""
    bad = tmp_path / "optdigits_test.csv.gz"
    shutil.copy(datasets.DIGITS_FILE, bad)
    blob = bytearray(bad.read_bytes())
    blob[-9] ^= 1
    bad.write_bytes(bytes(blob))
    monkeypatch.setattr(datasets, "DIGITS_FILE", str(bad))
    with pytest.raises(ValueError, match="sha256"):
        datasets.load("digits")
