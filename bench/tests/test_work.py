"""Operation and byte counts against hand counts, and the peaks table."""
import pytest

from bench import work


@pytest.mark.parametrize("n,m,d", [(256, 3, 34), (256, 100, 18)])
def test_score_work_matches_hand_count(n, m, d):
    flops, nbytes = work.kernel_work("score", n, m, d)
    # distance matmul 2nmd, the x2 + c2 - 2xc combine and min 4nm, divide n
    assert flops == 2 * n * m * d + 4 * n * m + n
    # read x and c, write distance, index and score
    assert nbytes == 4 * (n * d + m * d + 3 * n)


def test_hand_count_values():
    assert work.kernel_work("score", 256, 3, 34) == (55_552.0, 38_296.0)
    assert work.kernel_work("score", 256, 100, 18) == (1_024_256.0,
                                                       28_704.0)


def test_roofline_names_the_bound():
    t, bound = work.roofline_seconds("score", 256, 3, 34, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(38_296 / 819e9)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99 imaginary")
    with pytest.raises(ValueError):
        work.kernel_work("no_such_kernel", 1, 1, 1)
