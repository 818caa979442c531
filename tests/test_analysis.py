"""Static analyzer rule fixtures (DESIGN.md §8).

Each rule gets a known-bad snippet proving it fires exactly there, a
known-good twin proving it stays quiet on the repo's sanctioned idioms,
and the whole-repo run must come back clean modulo the committed
baseline — the same invocation CI gates on.
"""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ast_lint, jaxpr_checks, pallas_budget
from repro.analysis.report import (
    Finding,
    Report,
    Waiver,
    dump_baseline,
    load_baseline,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


def lint_snippet(tmp_path, source: str):
    p = tmp_path / "snippet.py"
    p.write_text(source)
    findings, _ = ast_lint.lint_files([str(p)], str(tmp_path))
    return findings


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# KEY-REUSE
# ---------------------------------------------------------------------------


def test_key_reuse_fires_on_double_draw(tmp_path):
    # the exact PR 4 bug shape: one key feeding two sweeps
    findings = lint_snippet(tmp_path, """
import jax

def from_circuit(params, key):
    dv, curve = dc_sweep_gaussian(params, key=key)
    dva, ratio = dc_sweep_alpha(params, key=key)
    return curve, ratio
""")
    assert [f.rule for f in findings] == ["KEY-REUSE"]
    assert findings[0].symbol == "from_circuit"


def test_key_reuse_fires_on_loop_without_rotation(tmp_path):
    findings = lint_snippet(tmp_path, """
import jax

def sample(key, n):
    out = []
    for i in range(n):
        out.append(jax.random.normal(key, (4,)))
    return out
""")
    assert rules_of(findings) == {"KEY-REUSE"}


def test_key_reuse_quiet_on_split_and_rotate(tmp_path):
    findings = lint_snippet(tmp_path, """
import jax

def ok_split(key):
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, (4,))
    b = jax.random.normal(k2, (4,))
    return a + b

def ok_rotate(key, n):
    out = []
    for i in range(n):
        key, sub = jax.random.split(key)
        out.append(jax.random.normal(sub, (4,)))
    return out

def ok_fold(base, n):
    return [jax.random.normal(jax.random.fold_in(base, i), (4,))
            for i in range(n)]

def ok_branches(key, flag):
    if flag:
        return jax.random.normal(key, (4,))
    else:
        return jax.random.uniform(key, (4,))
""")
    assert findings == []


# ---------------------------------------------------------------------------
# INTERPRET-THREAD
# ---------------------------------------------------------------------------


def test_interpret_thread_fires_on_unthreaded_call(tmp_path):
    findings = lint_snippet(tmp_path, """
from repro.kernels import ops

def score(x, z, gamma):
    return ops.rbf_matrix(x, z, gamma, kind="rbf")
""")
    assert [f.rule for f in findings] == ["INTERPRET-THREAD"]
    assert findings[0].symbol == "score"


def test_interpret_thread_fires_on_unthreadable_forward(tmp_path):
    # passes interpret= but has no parameter to thread it from
    findings = lint_snippet(tmp_path, """
from repro.kernels import ops

def score(x, z, gamma):
    return ops.rbf_matrix(x, z, gamma, interpret=interpret)
""")
    assert [f.rule for f in findings] == ["INTERPRET-THREAD"]


def test_interpret_thread_quiet_on_threaded_and_local_names(tmp_path):
    findings = lint_snippet(tmp_path, """
from repro.kernels import ops

def score(x, z, gamma, interpret=None):
    return ops.rbf_matrix(x, z, gamma, interpret=interpret)

def rbf_matrix(x, z, gamma):   # local jnp oracle shadows the entry name
    return x @ z.T

def uses_local(x, z, gamma):
    return rbf_matrix(x, z, gamma)
""")
    assert findings == []


# ---------------------------------------------------------------------------
# PYTREE-REG
# ---------------------------------------------------------------------------


def test_pytree_reg_fires_on_unregistered_dataclass(tmp_path):
    findings = lint_snippet(tmp_path, """
import dataclasses
import jax.numpy as jnp

@dataclasses.dataclass
class Bank:
    w: jnp.ndarray
    b: jnp.ndarray
""")
    assert [f.rule for f in findings] == ["PYTREE-REG"]
    assert findings[0].symbol == "Bank"


def test_pytree_reg_quiet_when_registered(tmp_path):
    findings = lint_snippet(tmp_path, """
import dataclasses
import jax
import jax.numpy as jnp

@dataclasses.dataclass
class Bank:
    w: jnp.ndarray

jax.tree_util.register_dataclass(Bank, data_fields=("w",), meta_fields=())
""")
    assert findings == []


# ---------------------------------------------------------------------------
# BANNED-IN-HOT
# ---------------------------------------------------------------------------


def test_banned_in_hot_fires_on_all_three_classes(tmp_path):
    findings = lint_snippet(tmp_path, """
import time
import jax
import numpy as np

@jax.jit
def hot(x):
    noise = np.random.normal(size=4)
    t0 = time.time()
    s = x.sum().item()
    return x + noise + t0 + s
""")
    assert [f.rule for f in findings] == ["BANNED-IN-HOT"] * 3
    msgs = " ".join(f.message for f in findings)
    assert "np.random" in msgs and "time.time" in msgs and ".item()" in msgs


def test_banned_in_hot_quiet_outside_jit(tmp_path):
    findings = lint_snippet(tmp_path, """
import time
import numpy as np

def host_bench(x):
    t0 = time.time()
    return np.random.normal(size=4), time.time() - t0
""")
    assert findings == []


# ---------------------------------------------------------------------------
# Pass 1: F64-IN-JIT / HOST-CALLBACK / CONST-BAKE / DONATION-DROPPED
# ---------------------------------------------------------------------------


def test_f64_leak_fires():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x * np.float64(2.0))(jnp.zeros(3, jnp.float32))
    findings, _ = jaxpr_checks.check_jaxpr(closed, path="fixture",
                                           symbol="f64_leak")
    assert "F64-IN-JIT" in rules_of(findings)


def test_f64_clean_repo_default():
    # with x64 disabled (the repo default) the same program stays f32
    closed = jax.make_jaxpr(
        lambda x: x * np.float64(2.0))(jnp.zeros(3, jnp.float32))
    findings, _ = jaxpr_checks.check_jaxpr(closed, path="fixture",
                                           symbol="f32_ok")
    assert findings == []


def test_host_callback_fires():
    def noisy(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1.0

    closed = jax.make_jaxpr(noisy)(jnp.zeros(3, jnp.float32))
    findings, _ = jaxpr_checks.check_jaxpr(closed, path="fixture",
                                           symbol="noisy")
    assert "HOST-CALLBACK" in rules_of(findings)


def test_const_bake_fires_above_threshold():
    big = jnp.zeros((512, 1024), jnp.float32)   # 2 MiB closed-over weight

    closed = jax.make_jaxpr(lambda x: x @ big)(jnp.zeros(512, jnp.float32))
    findings, _ = jaxpr_checks.check_jaxpr(closed, path="fixture",
                                           symbol="capture")
    assert "CONST-BAKE" in rules_of(findings)
    findings, _ = jaxpr_checks.check_jaxpr(
        closed, path="fixture", symbol="capture",
        max_const_bytes=4 << 20)
    assert findings == []


def test_donation_honored_and_dropped():
    good_j = jax.jit(lambda y: y * 2.0, donate_argnums=(0,))
    findings, info = jaxpr_checks.check_donation(
        good_j, (jnp.ones((64,), jnp.float32),), {},
        path="fixture", symbol="good")
    assert findings == [] and info["honored"] is True

    # nothing the donated i32 buffer can alias: output is a bigger f32
    bad_j = jax.jit(lambda y: jnp.zeros((128,), jnp.float32) + y.sum(),
                    donate_argnums=(0,))
    findings, info = jaxpr_checks.check_donation(
        bad_j, (jnp.ones((4,), jnp.int32),), {},
        path="fixture", symbol="bad")
    assert [f.rule for f in findings] == ["DONATION-DROPPED"]
    assert info["honored"] is False


# ---------------------------------------------------------------------------
# Pass 3: VMEM-BUDGET / GRID-DIVISIBLE / FUSED-VS-ORACLE
# ---------------------------------------------------------------------------


def _record_one(shape, block, budget):
    from jax.experimental import pallas as pl

    with pallas_budget.record_pallas_calls() as recs:
        pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(max(1, shape[0] // block[0]),),
            in_specs=[pl.BlockSpec(block, lambda i: (i, 0))],
            out_specs=pl.BlockSpec(block, lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        )(jnp.zeros(shape, jnp.float32))
    assert len(recs) == 1
    return pallas_budget.analyze_record(recs[0], path="fixture",
                                        symbol="toy", vmem_budget=budget)


def test_vmem_budget_fires_on_oversized_blocks():
    # 2 operands x double-buffered 32x32 f32 blocks = 16 KiB > 1 KiB budget
    info, findings = _record_one((64, 32), (32, 32), budget=1024)
    assert [f.rule for f in findings] == ["VMEM-BUDGET"]
    assert info["vmem_bytes"] == 2 * 2 * 32 * 32 * 4


def test_grid_divisible_fires_on_ragged_shape():
    info, findings = _record_one((100, 32), (16, 32), budget=1 << 30)
    # one finding per ragged operand: the input and the output block spec
    assert rules_of(findings) == {"GRID-DIVISIBLE"} and len(findings) == 2


def test_repo_kernels_within_budget_and_fused_below_oracle():
    findings, info = pallas_budget.check_kernels()
    assert findings == []
    names = {p["symbol"] for p in info["programs"]}
    assert {"dual_ascent_lanes_pallas", "flash_attention",
            "ssd_scan_pallas"} <= names
    contract = info["fused_vs_oracle"]
    assert contract["holds"] is True
    # PR 5's ordering: the fused solver's whole working set is orders of
    # magnitude below the (lanes, n, n) Gram it replaces
    assert contract["fused_vmem_bytes"] < contract["oracle_gram_bytes"] / 100


def test_fused_vs_oracle_gate_fails_on_seeded_regression():
    # shrink the oracle below the fused footprint: the gate must fire
    findings, info = pallas_budget.check_kernels(oracle_bytes=1)
    assert "FUSED-VS-ORACLE" in rules_of(findings)
    assert info["fused_vs_oracle"]["holds"] is False


# ---------------------------------------------------------------------------
# Baseline / report machinery
# ---------------------------------------------------------------------------


def test_waiver_requires_reason(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({
        "format": "repro.analysis.baseline", "version": 1,
        "waivers": [{"rule": "KEY-REUSE", "match": "x.py::f",
                     "reason": "  "}]}))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(p))


def test_waiver_rejects_todo_placeholder(tmp_path):
    # an unedited placeholder reason must fail loudly, not pass review
    p = tmp_path / "baseline.json"
    for reason in ("TODO: justify this waiver", "todo later"):
        p.write_text(json.dumps({
            "format": "repro.analysis.baseline", "version": 1,
            "waivers": [{"rule": "KEY-REUSE", "match": "x.py::f",
                         "reason": reason}]}))
        with pytest.raises(ValueError, match="placeholder"):
            load_baseline(str(p))


def test_update_baseline_requires_real_reason(tmp_path, monkeypatch):
    # --update-baseline on a new finding must demand --reason and reject
    # TODO placeholders; with a real reason the waiver records it.
    from repro.analysis.__main__ import main

    src = tmp_path / "src"
    src.mkdir()
    (src / "bad.py").write_text(
        "import jax\n\n"
        "def f(key):\n"
        "    a = jax.random.normal(key)\n"
        "    b = jax.random.normal(key)\n"
        "    return a + b\n")
    baseline = tmp_path / "baseline.json"
    args = ["--root", str(tmp_path), "--no-jaxpr", "--no-pallas",
            "--baseline", str(baseline), "--update-baseline"]
    with pytest.raises(SystemExit):
        main(args)                                   # no --reason
    with pytest.raises(SystemExit):
        main(args + ["--reason", "TODO: fill in"])   # placeholder reason
    assert not baseline.exists()
    rc = main(args + ["--reason", "intentional correlated draws"])
    assert rc == 0
    waivers = load_baseline(str(baseline))
    assert waivers and all(
        w.reason == "intentional correlated draws" for w in waivers)
    # prior waivers keep their own justification on re-update
    rc = main(args + ["--reason", "a different reason"])
    assert rc == 0
    assert [w.reason for w in load_baseline(str(baseline))] == \
        [w.reason for w in waivers]


def test_waiver_glob_and_unused_tracking(tmp_path):
    f = Finding(rule="KEY-REUSE", path="benchmarks/fig4.py", symbol="run",
                message="m")
    report = Report(findings=[f], waivers=[
        Waiver(rule="KEY-REUSE", match="benchmarks/*", reason="r"),
        Waiver(rule="VMEM-BUDGET", match="never/*", reason="r"),
    ])
    assert report.new_findings == []
    assert len(report.waived_findings) == 1
    assert [w.rule for w in report.unused_waivers()] == ["VMEM-BUDGET"]
    # round-trip
    p = tmp_path / "b.json"
    dump_baseline(str(p), report.waivers)
    assert [dataclasses.asdict(w) for w in load_baseline(str(p))] == \
        [dataclasses.asdict(w) for w in report.waivers]


def test_finding_key_is_line_stable():
    a = Finding(rule="R", path="p.py", symbol="f", message="m", line=10)
    b = Finding(rule="R", path="p.py", symbol="f", message="m", line=99)
    assert a.key == b.key


# ---------------------------------------------------------------------------
# The CI gate: repo is clean modulo the committed baseline
# ---------------------------------------------------------------------------


def test_repo_clean_modulo_baseline(tmp_path):
    from repro.analysis.__main__ import main

    out = tmp_path / "report.json"
    rc = main(["--root", str(REPO),
               "--baseline", str(REPO / "analysis_baseline.json"),
               "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["summary"]["new"] == 0
    assert report["summary"]["unused_waivers"] == []
    # the report carries the regression-gate payloads
    contract = report["info"]["pallas_budget"]["fused_vs_oracle"]
    assert contract["holds"] is True
    donations = [e.get("donation") for e in
                 report["info"]["jaxpr_checks"]["entrypoints"]
                 if e.get("donation")]
    assert donations and all(d["honored"] for d in donations)


def test_gate_fails_without_baseline():
    from repro.analysis.__main__ import build_report

    report = build_report(str(REPO), run_jaxpr=False, run_pallas=False)
    # the deliberate exceptions exist, so an empty baseline must gate
    assert len(report.new_findings) > 0
