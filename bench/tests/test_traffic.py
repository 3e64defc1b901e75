"""The traffic generator: the same seed gives the same rounds, every seed
gives rounds of the same shapes, and the rows of the first rounds all
differ."""
import numpy as np

from conftest import tiny_traffic
from traffic.generator import Rounds


def rounds(seed, n):
    r = Rounds(tiny_traffic(1), 512, 4, seed)
    return [r.next() for _ in range(n)]


def test_same_seed_same_rounds_and_shapes():
    a, b = rounds(2 ** 31 + 3, 5), rounds(2 ** 31 + 3, 5)
    c = rounds(11, 5)
    for (xa, ya), (xb, yb), (xc, yc) in zip(a, b, c):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert xa.shape == xc.shape == (4, 2, 16) and ya.shape == (4, 2)
        assert xa.dtype == np.int32 and ya.dtype == np.int32


def test_first_rounds_rows_all_differ():
    spec = tiny_traffic(1)
    rows = [tuple(x) for tokens, _ in rounds(7, spec["first_rounds"])
            for x in tokens.reshape(-1, spec["seq_len"])]
    assert len(set(rows)) == len(rows)
