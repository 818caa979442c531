"""Measurement inside the streaming Monte-Carlo path.

* the chunk step's device ops carry the six named scopes;
* ``repro.obs`` counts compiles: a second job on a cached machine adds
  none, a new key (a new machine) adds some;
* a profiler capture of one job holds the ``repro.mc.stream`` span with
  its host phases nested inside it, and capturing changes no result;
* the machine's work totals follow the steps it dispatched.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import MixedKernelSVM
from repro.api import compiled as api
from repro.data import datasets
from test_mc_streaming import _tiny_candidates

SCOPES = ("mc.draw", "mc.lanes.linear", "mc.lanes.rbf", "mc.lanes.hw",
          "mc.recombine", "mc.fold")
PHASES = ("repro.mc.prep", "repro.mc.dispatch", "repro.mc.wait",
          "repro.mc.finalize")


@pytest.fixture(scope="module")
def job():
    """A small fitted machine and one signoff job's arguments, run once so
    that everything the job compiles is compiled."""
    ds = datasets.load("balance")
    keep = np.arange(120)
    est = MixedKernelSVM(n_epochs=5).fit(ds.x_train[keep], ds.y_train[keep])
    kw = dict(x=ds.x_test[:32], y=ds.y_test[:32], n_variants=40,
              method="iid", mc_chunk=16, key=jax.random.PRNGKey(3),
              accuracy_floor=0.5)
    return est, kw, est.monte_carlo(**kw)


def _same(a, b) -> None:
    for f in ("mean", "std", "worst", "best", "yield_", "n_eff"):
        assert getattr(a, f) == getattr(b, f), f
    np.testing.assert_array_equal(a.hist, b.hist)


def test_step_hlo_carries_every_scope():
    sm = api.compile_mc_stream(_tiny_candidates(), 3, jax.random.PRNGKey(0),
                               mc_chunk=4)
    hlo = sm.compiled_step(8).as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in hlo, scope


def test_compiles_counted_per_key(job):
    est, kw, want = job
    before = obs.snapshot()
    _same(est.monte_carlo(**kw), want)
    again = obs.snapshot()
    assert again["backend_compile_n"] == before["backend_compile_n"]
    assert again["trace_n"] == before["trace_n"]
    est.monte_carlo(**dict(kw, key=jax.random.PRNGKey(4)))
    after = obs.snapshot()
    assert after["backend_compile_n"] >= again["backend_compile_n"] + 1
    assert after["backend_compile_s"] > again["backend_compile_s"]


def _host_spans(log_dir: str) -> list:
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_capture_nests_host_phases_in_the_stream_span(job, tmp_path):
    est, kw, want = job
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got = est.monte_carlo(**kw)
    finally:
        jax.profiler.stop_trace()
    _same(got, want)
    spans = _host_spans(str(tmp_path))
    (t0, t1, _, args), = [s for s in spans if s[2] == "repro.mc.stream"]
    assert {k: int(v) for k, v in args.items()} == {
        "n_variants": 40, "mc_chunk": 16, "steps": 3}
    inner = [s for s in spans if s[2] != "repro.mc.stream"]
    assert {s[2] for s in inner} == set(PHASES)
    assert all(t0 <= a <= b <= t1 for a, b, _, _ in inner)
    firsts = [min(a for a, _, n, _ in inner if n == p) for p in PHASES]
    assert firsts == sorted(firsts)


def test_work_totals_follow_dispatched_steps():
    rng = np.random.default_rng(1)
    sm = api.compile_mc_stream(_tiny_candidates(), 3, jax.random.PRNGKey(0),
                               mc_chunk=16)
    x = rng.normal(size=(10, 3)).astype(np.float32)
    y = rng.integers(0, 3, size=10).astype(np.int32)
    a = np.ones((1, 3), bool)
    sm.stream(x, y, a, n_variants=40, accuracy_floor=0.5)
    # 3 steps of 16 variants; one variant bank of 2 pairs padded to M = 6.
    assert (sm.steps_run, sm.variants_run) == (3, 40)
    assert sm.lane_cells_run == 3 * 16 * 10 * 2 * 6
    sm.stream(x, y, a, n_variants=16, accuracy_floor=0.5)
    assert (sm.steps_run, sm.variants_run) == (4, 56)
    assert sm.lane_cells_run == 4 * 16 * 10 * 2 * 6
