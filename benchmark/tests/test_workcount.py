import pytest

import workcount


def test_wave_work_by_hand():
    # 4 nodes, 10 pods, no counted terms, one simulation:
    # node state read 4 * (2*3) * 4 B = 96, written 4 * 3 * 4 B = 48,
    # pods 10 * 2 * 4 B = 80 -> 224 B; ops 4 * 18 + 10 = 82
    assert workcount.work(4, [10]) == (82.0, 224.0)


def test_affinity_work_by_hand():
    # 2 terms add 2 words per node read: 4 * (6 + 4) * 4 = 160 read
    assert workcount.work(4, [10], terms=2) == (82.0, 160.0 + 48.0 + 80.0)


def test_two_simulations_count_node_state_twice():
    ops, nb = workcount.work(4, [10], sims=2)
    assert (ops, nb) == (2 * 72.0 + 10, 2 * 144.0 + 80.0)


@pytest.mark.parametrize("split", [[150], [100, 50], [1] * 150, [8, 142]])
def test_segmentation_does_not_change_the_count(split):
    assert workcount.work(5000, split, terms=2) == workcount.work(5000, [150], terms=2)


def test_roofline_bound_and_unknown_device():
    ops, nb = workcount.work(5120, [1000])
    pct, bound = workcount.roofline_pct(ops, nb, 1e-3, "TPU v5 lite")
    assert bound == "bytes"
    assert pct == pytest.approx(100 * nb / 819e9 / 1e-3)
    with pytest.raises(KeyError):
        workcount.roofline_pct(ops, nb, 1e-3, "no such chip")
