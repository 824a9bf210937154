"""Per-kernel validation: Pallas (interpret mode = kernel body on CPU)
against the pure-jnp ref.py oracles, swept over shapes and dtypes, and the
XLA Hadamard rotation against the explicit Hadamard matrix."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# FWHT
# ---------------------------------------------------------------------------

def _hadamard(d):
    H = np.array([[1.0]])
    while H.shape[0] < d:
        H = np.block([[H, H], [H, -H]])
    return H / np.sqrt(d)


@pytest.mark.parametrize("d", [2, 8, 64, 256, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_matches_ref(rng, d, dtype):
    """The butterfly equals the explicit normalized Hadamard product."""
    x = jnp.asarray(rng.normal(size=(5, d)).astype(np.float32)).astype(dtype)
    got = ops.fwht(x)
    want = np.asarray(x, np.float64) @ _hadamard(d).T
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol, rtol=tol)


def test_fwht_matches_explicit_hadamard(rng):
    d = 32
    x = rng.normal(size=(7, d)).astype(np.float32)
    got = np.asarray(ops.fwht(jnp.asarray(x)))
    np.testing.assert_allclose(got, x @ _hadamard(d).T, atol=1e-5)


def test_fwht_preserves_l2_distances(rng):
    x = jnp.asarray(rng.normal(size=(6, 128)).astype(np.float32))
    y = ops.fwht(x)
    dx = np.asarray(ref.pairwise_dist_ref(x, x))
    dy = np.asarray(ref.pairwise_dist_ref(y, y))
    np.testing.assert_allclose(dx, dy, atol=1e-3, rtol=1e-4)


def test_fwht_row_padding(rng):
    """Leading batch axes and an odd row count transform row by row."""
    x = jnp.asarray(rng.normal(size=(3, 5, 64)).astype(np.float32))
    got = ops.fwht(x)
    want = ops.fwht(x.reshape(15, 64)).reshape(3, 5, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]),
                               np.asarray(x[1]) @ _hadamard(64).T, atol=1e-5)


# ---------------------------------------------------------------------------
# block_pull
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,block,B,P", [
    (16, 256, 128, 4, 2),
    (32, 512, 64, 8, 3),
    (8, 1024, 256, 8, 1),
    (64, 384, 128, 16, 5),
])
@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_block_pull_matches_ref(rng, n, d, block, B, P, metric):
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    arm = jnp.asarray(rng.integers(0, n, B), jnp.int32)
    blk = jnp.asarray(rng.integers(0, d // block, (B, P)), jnp.int32)
    got = ops.block_pull(X, q, arm, blk, block=block, metric=metric, impl="interpret")
    want = ops.block_pull(X, q, arm, blk, block=block, metric=metric, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_pull_dtypes(rng, dtype):
    X = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32)).astype(dtype)
    q = jnp.asarray(rng.normal(size=(256,)).astype(np.float32)).astype(dtype)
    arm = jnp.arange(4, dtype=jnp.int32)
    blk = jnp.zeros((4, 2), jnp.int32)
    got = ops.block_pull(X, q, arm, blk, block=128, metric="l2", impl="interpret")
    want = ops.block_pull(X, q, arm, blk, block=128, metric="l2", impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("Q,n,d,block,B,P", [
    (3, 16, 256, 128, 4, 2),
    (5, 32, 512, 64, 8, 3),
    (2, 8, 1024, 256, 8, 1),
])
@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_block_pull_multi_matches_ref(rng, Q, n, d, block, B, P, metric):
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(Q, d)).astype(np.float32))
    arm = jnp.asarray(rng.integers(0, n, (Q, B)), jnp.int32)
    blk = jnp.asarray(rng.integers(0, d // block, (Q, B, P)), jnp.int32)
    got = ops.block_pull_multi(X, qs, arm, blk, block=block, metric=metric,
                               impl="interpret")
    want = ops.block_pull_multi(X, qs, arm, blk, block=block, metric=metric,
                                impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    # row q of the multi-query pull == the single-query pull for that query
    for qidx in range(Q):
        single = ops.block_pull(X, qs[qidx], arm[qidx], blk[qidx],
                                block=block, metric=metric, impl="ref")
        np.testing.assert_allclose(np.asarray(got[qidx]), np.asarray(single),
                                   rtol=1e-5)


def test_block_pull_full_coverage_equals_exact(rng):
    """Pulling every block once averages to the exact θ."""
    n, d, block = 6, 512, 128
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    nb = d // block
    blk = jnp.broadcast_to(jnp.arange(nb)[None], (n, nb)).astype(jnp.int32)
    pulls = ops.block_pull(X, q, jnp.arange(n, dtype=jnp.int32), blk,
                           block=block, metric="l2", impl="interpret")
    theta = np.asarray(ref.pairwise_dist_ref(q[None], X))[0] / d
    np.testing.assert_allclose(np.asarray(pulls).mean(1), theta, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused_epoch_pull (round-fused racing kernel, DESIGN.md §4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q,n,d,block,B,T", [
    (3, 16, 256, 128, 4, 6),     # T = R·P for (R, P) = (3, 2)
    (5, 32, 512, 64, 8, 2),      # single-round epoch (R = 1)
    (2, 8, 1024, 256, 6, 12),
    (4, 64, 384, 128, 16, 9),    # odd T, d_pad not a power of two
])
@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_fused_epoch_pull_matches_ref(rng, Q, n, d, block, B, T, metric):
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(Q, d)).astype(np.float32))
    arm = jnp.asarray(rng.integers(0, n, (Q, B)), jnp.int32)
    blk = jnp.asarray(rng.integers(0, d // block, (Q, B, T)), jnp.int32)
    got = ops.fused_epoch_pull(X, qs, arm, blk, block=block, metric=metric,
                               impl="interpret")
    want = ops.fused_epoch_pull(X, qs, arm, blk, block=block, metric=metric,
                                impl="ref")
    assert got.shape == (Q, B, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("Q,n,d_pad,block,B,T,max_chunk,smem_words,metric", [
    # 15 pairs in lane-bound chunks of 4: a padded remainder chunk; 13 rows
    # and 192 lanes pad the store to whole (8, 128) tiles
    (3, 13, 192, 64, 5, 3, 4, 1024, "l2"),
    # SMEM-bound chunks of 8 // 3 = 2 pairs; only the rows are ragged
    (3, 10, 256, 128, 5, 3, 2048, 8, "l1"),
    # a store narrower than one lane tile, 7 chunks of 3 pairs
    (2, 6, 96, 32, 10, 2, 3, 1024, "l2"),
])
@pytest.mark.parametrize("stats", [False, True], ids=["pulls", "stats"])
def test_pull_kernel_chunks_and_ragged_stores(rng, monkeypatch, Q, n, d_pad,
                                              block, B, T, max_chunk,
                                              smem_words, metric, stats):
    """The launch split into several index chunks (ragged remainder
    included) and a store that is not whole tiles agree with the refs."""
    import functools

    from repro.kernels import block_pull
    monkeypatch.setattr(block_pull, "_MAX_CHUNK", max_chunk)
    monkeypatch.setattr(block_pull, "_SMEM_WORDS", smem_words)
    X = jnp.asarray(rng.normal(size=(n, d_pad)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(Q, d_pad)).astype(np.float32))
    arm = jnp.asarray(rng.integers(0, n, (Q, B)), jnp.int32)
    arm = arm.at[0, 0].set(n - 1)               # the last, padded row tile
    blk = jnp.asarray(rng.integers(0, d_pad // block, (Q, B, T)), jnp.int32)
    blk = blk.at[0, 0, 0].set(d_pad // block - 1)
    got = jax.jit(functools.partial(
        block_pull.pull_pallas, block=block, metric=metric, stats=stats,
        interpret=True))(X, qs, arm, blk)
    want_fn = ref.fused_epoch_pull_ref if stats else ref.block_pull_multi_ref
    want = want_fn(X, qs, arm, blk, block, metric)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-5)


def test_fused_epoch_pull_stats_match_raw_pulls(rng):
    """The kernel's on-chip (mean, M2) reduction over T pulls must merge
    into running state exactly like feeding the T raw per-round pull values
    through the per-round Welford update."""
    from repro.core import confidence as conf
    Q, n, d, block, B, T = 2, 16, 512, 64, 4, 8
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(Q, d)).astype(np.float32))
    arm = jnp.asarray(rng.integers(0, n, (Q, B)), jnp.int32)
    blk = jnp.asarray(rng.integers(0, d // block, (Q, B, T)), jnp.int32)
    raw = ops.block_pull_multi(X, qs, arm, blk, block=block, impl="ref")
    stats = ops.fused_epoch_pull(X, qs, arm, blk, block=block,
                                 impl="interpret")

    mean0 = jnp.asarray(rng.normal(size=(Q * B,)).astype(np.float32))
    count0 = jnp.asarray(rng.integers(2, 10, (Q * B,)).astype(np.float32))
    m20 = jnp.abs(jnp.asarray(rng.normal(size=(Q * B,)).astype(np.float32)))
    mask = jnp.ones((Q * B,), jnp.float32)
    want = conf.welford_batch_update(mean0, count0, m20,
                                     raw.reshape(Q * B, T), mask)
    got = conf.welford_merge(mean0, count0, m20,
                             stats[..., 0].reshape(-1), float(T),
                             stats[..., 1].reshape(-1), mask)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# pairwise_dist
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q,n,d", [(4, 16, 64), (9, 50, 300), (8, 128, 512),
                                   (1, 7, 1000)])
@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_pairwise_matches_ref(rng, Q, n, d, metric):
    qs = jnp.asarray(rng.normal(size=(Q, d)).astype(np.float32))
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    got = ops.pairwise_dist(qs, X, metric=metric, impl="interpret")
    want = ops.pairwise_dist(qs, X, metric=metric, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_pairwise_l2_dot_variant(rng):
    """The MXU (−2qxᵀ + norms) form agrees with the elementwise form."""
    from repro.kernels.pairwise_dist import pairwise_dist_pallas
    qs = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    X = jnp.asarray(rng.normal(size=(32, 256)).astype(np.float32))
    a = pairwise_dist_pallas(qs, X, metric="l2", interpret=True)
    b = pairwise_dist_pallas(qs, X, metric="l2_dot", interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-3)


def test_pairwise_zero_distance(rng):
    X = jnp.asarray(rng.normal(size=(5, 128)).astype(np.float32))
    d = np.asarray(ops.pairwise_dist(X, X, metric="l2", impl="interpret"))
    np.testing.assert_allclose(np.diag(d), 0.0, atol=1e-3)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["pallas", "xla", "cuda"])
def test_unknown_impl_is_rejected(impl):
    x = jnp.zeros((2, 8), jnp.float32)
    with pytest.raises(ValueError, match="unknown impl"):
        ops.pairwise_dist(x, x, impl=impl)
