"""The lower-precision control fails each cell's comparison, and the
program passes it, at the cells' own sizes on the CPU (one seed each; the
chip readings the limits were set from are in PERF.md)."""
import control
from benchlib import registry


def _readings(root, workload, seed, seconds=5.0):
    rows = []
    control.readings(workload, [seed], seconds, rows.append, gate=False,
                     root=root)
    reg = registry.Registry(root)
    return rows, reg.traffic(reg.cell(workload)["traffic"])


def test_signoff_control_fails_program_passes(held_root):
    rows, traffic = _readings(held_root, "signoff.balance", 2**31 + 3)
    lim = traffic["limits"]
    prog = [r for r in rows if r["side"] == "program"]
    ctrl = [r for r in rows if r["side"] == "control"]
    # the program at the traffic's key, and at its second key
    assert {r["key"] for r in prog} == {traffic["mc_key"],
                                        traffic["control_key"]}
    for r in prog:
        assert all(r[k] <= lim[k] for k in lim), r
    assert any(ctrl[0][k] > lim[k] for k in lim)


def test_serve_control_fails_program_passes(held_root):
    rows, traffic = _readings(held_root, "serve.balance", 2**31 + 5)
    prog = [r for r in rows if r["side"] == "program"][0]
    ctrl = [r for r in rows if r["side"] == "control"][0]
    for kind, gap in prog["max_score_gap"].items():
        assert gap < traffic["tie_eps"][kind]
    assert ctrl["wrong_labels"] > traffic["limits"]["wrong_labels"]
