"""The work counters against counts made by hand."""
import numpy as np
import pytest

from bench import work

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_histogram_has_k_plus_one_bins():
    assert work.histogram([0, 2, 2, 4], 4).tolist() == [1, 0, 2, 0, 1]


def test_train_counts_by_hand():
    # 10 ratings at pair rank 2: (2 + 6 + 14) FLOPs per factor
    assert work.train_flops(10, 2) == 10 * 2 * 22
    # rows p, q, acc_p, acc_q read and written over 2 factors, 4 bytes each,
    # plus user id, item id and rating
    assert work.train_bytes(10, 2) == 10 * (2 * 2 * 4 * 4 + 12)


def test_score_counts_by_hand():
    h_item = work.histogram([0, 1, 3], 3)   # items of rank 0, 1, 3
    users = np.array([2, 3])
    # user rank 2: min(2,0)+min(2,1)+min(2,3) = 3; rank 3: 0+1+3 = 4
    assert work.score_flops(users, h_item) == 2 * (3 + 4)
    # catalog (0 + 1 + 3) factors, users (2 + 3) factors, 2 users x top-5
    # (score, index) pairs
    assert work.score_bytes(users, h_item, 5) == 4 * 4 + 5 * 4 + 2 * 5 * 8


def test_roofline_takes_the_larger_bound():
    assert work.roofline_s(200.0, 10.0, PEAK) == 2.0     # compute-bound
    assert work.roofline_s(100.0, 50.0, PEAK) == 5.0     # memory-bound
