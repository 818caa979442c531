"""The roofline counts charge only the work the result needs."""
from benchlib import counts

KMAP = ["linear", "rbf", "linear", "rbf"]
M_TRUE = [0, 40, 0, 7]


def test_unused_candidates_add_no_work():
    base = counts.signoff_step(KMAP, M_TRUE, 5, 256, 2, 4)
    # analog candidates of the pairs deployed linear: any support count
    with_unused = counts.signoff_step(KMAP, [745, 40, 391, 7], 5, 256, 2, 4)
    assert with_unused == base


def test_padding_adds_no_work():
    """The count takes true support counts only: padding every analog pair
    to the bank's M would be a different (larger) count, never the one the
    benchmark charges."""
    base = counts.signoff_step(KMAP, M_TRUE, 5, 256, 2, 4)
    padded = counts.signoff_step(KMAP, [0, 40, 0, 40], 5, 256, 2, 4)
    assert padded[0] > base[0]
    assert counts.signoff_step(KMAP, M_TRUE, 5, 256, 2, 4) == base


def test_work_scales_with_what_is_needed():
    one = counts.signoff_step(KMAP, M_TRUE, 5, 256, 1, 4)[0]
    two = counts.signoff_step(KMAP, M_TRUE, 5, 256, 2, 4)[0]
    assert two == 2 * one
    all_linear = counts.signoff_step(["linear"] * 4, M_TRUE, 5, 256, 1, 4)[0]
    assert all_linear < one


def test_roofline_share_names_its_bound():
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    share, bound = counts.roofline_share(1e12, 1e6, 2.0, peaks)
    assert (share, bound) == (50.0, "compute")
    share, bound = counts.roofline_share(1.0, 1e9, 1.0, peaks)
    assert (share, bound) == (100.0, "memory")
