"""Seeded inputs."""

import numpy as np

from benchmarks import traffic

BIG = 2**31 + 12345   # seeds may exceed 32 signed bits


def test_token_block_is_seeded():
    a = traffic.token_block(BIG, 2, 8, 100)
    assert (a == traffic.token_block(BIG, 2, 8, 100)).all()
    assert (a != traffic.token_block(BIG + 1, 2, 8, 100)).any()
    assert a.dtype == np.int32 and a.shape == (2, 8)
    assert a.min() >= 0 and a.max() < 100
