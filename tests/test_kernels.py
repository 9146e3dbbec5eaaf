"""Pallas kernels vs ref.py oracles — shape/dtype sweeps, interpret mode."""

import numpy as np
import pytest

from repro.core import build_forest, query_host
from repro.core.reachability import pack_rows, unpack_rows
from repro.kernels.bitset_mm.ops import bitset_mm, bitset_mm_mxu
from repro.kernels.range_query.ops import range_query_forest


# ---------------------------------------------------------------- range_query
@pytest.mark.parametrize("dim", [2, 3])
@pytest.mark.parametrize("P,T,B", [(0, 1, 8), (7, 1, 3), (130, 4, 33),
                                   (513, 7, 64)])
def test_range_query_sweep(dim, P, T, B):
    rng = np.random.default_rng(P * 31 + T * 7 + B + dim)
    lo = rng.random((P, dim)).astype(np.float32) * 10
    hi = lo + (0 if dim == 2 else rng.random((P, dim)).astype(np.float32))
    boxes = np.concatenate([lo, hi], axis=1)
    tree_of = rng.integers(0, T, size=P)
    forest = build_forest(boxes, np.arange(P, dtype=np.int32), tree_of, T)
    tids = rng.integers(-1, T, size=B)
    c = rng.random((B, dim)).astype(np.float32) * 10
    r = rng.random((B, dim)).astype(np.float32) * 3
    rects = np.concatenate([c - r, c + r], axis=1)
    want = query_host(forest, tids, rects)
    got_k = range_query_forest(forest, tids, rects, interpret=True)
    got_r = range_query_forest(forest, tids, rects, use_ref=True)
    assert (got_k == want).all()
    assert (got_r == want).all()


# ---------------------------------------------------------------- bitset_mm
@pytest.mark.parametrize("d,dj,p", [(1, 1, 1), (8, 32, 128), (33, 40, 70),
                                    (65, 128, 257)])
def test_bitset_mm_sweep(d, dj, p):
    rng = np.random.default_rng(d * 131 + dj + p)
    A = rng.random((d, dj)) < 0.15
    R = rng.random((dj, p)) < 0.25
    want = pack_rows((A.astype(np.int64) @ R.astype(np.int64)) > 0)
    a_bits, r_bits = pack_rows(A), pack_rows(R)
    got = bitset_mm(a_bits, r_bits, interpret=True)
    ref = bitset_mm(a_bits, r_bits, use_ref=True)
    assert np.array_equal(got, want)
    assert np.array_equal(ref, want)
    # MXU path needs R padded to the word boundary of A's columns
    rpad = np.zeros((a_bits.shape[1] * 32, r_bits.shape[1]), np.uint32)
    rpad[:dj] = r_bits
    got_mxu = bitset_mm_mxu(a_bits, rpad)[:d]
    assert np.array_equal(got_mxu, want)

