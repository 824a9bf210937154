"""The reference scan and the check that decides ``correct``."""
import numpy as np

from bench import reference as ref
from bench.corpus import Generator
from bench.tests.tiny import TINY

K = 3


def _truth(gen, q):
    whole = np.asarray(gen.rows(np.arange(gen.n)), np.float64)
    d2 = ((q[:, None, :].astype(np.float64) - whole[None]) ** 2).sum(-1)
    return np.sort(d2, 1)[:, :K], np.argsort(d2, 1)[:, :K]


def test_exact_knn_matches_a_float64_scan():
    gen = Generator(TINY, 21)
    q = gen.queries(12)
    dist, ids = ref.exact_knn(gen, q, K, rows_per_block=200)
    want_d, want_i = _truth(gen, q)
    np.testing.assert_allclose(dist, want_d, rtol=1e-4)
    np.testing.assert_array_equal(np.sort(ids, 1), np.sort(want_i, 1))


def test_check_passes_a_true_topk_and_flags_a_wrong_id():
    gen = Generator(TINY, 22)
    q = gen.queries(10)
    _, ids = _truth(gen, q)
    cert = np.ones(10, bool)
    res = ref.check(gen, q, ids, cert, K)
    assert not res["wrong"].any()
    bad = ids.copy()
    bad[4, 1] = ids[4, 0] + 1 if ids[4, 0] + 1 not in ids[4] else 0
    far = np.argmax(((np.asarray(gen.rows(np.arange(gen.n)))
                      - q[4]) ** 2).sum(-1))
    bad[4, 1] = far
    res = ref.check(gen, q, bad, cert, K)
    assert list(np.nonzero(res["wrong"])[0]) == [4]
    # a duplicate id, an id out of range and an uncertified row are wrong
    dup = ids.copy()
    dup[2, 2] = dup[2, 0]
    dup[3, 0] = gen.n + 5
    uncert = cert.copy()
    uncert[7] = False
    res = ref.check(gen, q, dup, uncert, K)
    assert list(np.nonzero(res["wrong"])[0]) == [2, 3, 7]


def test_value_gap_reads_the_served_distances():
    gen = Generator(TINY, 23)
    q = gen.queries(6)
    d, ids = _truth(gen, q)
    scale = float(TINY["d_pad"])
    served_d = ref.served_dists(gen, q, ids)
    gaps = ref.value_gaps(served_d, d / scale, scale)
    assert gaps.max() < 1e-5
    gaps = ref.value_gaps(served_d, d * (1 + 1e-3) / scale, scale)
    assert np.all(gaps > 9e-4)


def test_bf16_control_answers_in_lower_precision():
    gen = Generator(TINY, 24)
    q = gen.queries(32)
    dists, rows = ref.Bf16Scan(gen, K, rows_per_block=256).query(q)
    served_d = ref.served_dists(gen, q, rows)
    gaps = ref.value_gaps(served_d, dists / TINY["d_pad"], TINY["d_pad"])
    assert gaps.max() > 10 * TINY["limits"]["value_gap"]
