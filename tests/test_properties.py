"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.kernels import ops


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 6), st.integers(1, 6))
def test_fwht_involution(log_d, n):
    """H is orthonormal and symmetric → FWHT is its own inverse."""
    d = 1 << log_d
    rng = np.random.default_rng(log_d * 7 + n)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = ops.fwht(ops.fwht(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 5), st.integers(1, 64))
def test_fwht_preserves_norm(n, seed):
    d = 256
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = ops.fwht(x)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=1),
                               np.linalg.norm(np.asarray(x), axis=1),
                               rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(4, 64), st.integers(2, 16), st.integers(1, 4),
       st.integers(2, 10))
def test_moe_dispatch_no_collisions(T, E, k, cap):
    """Every kept token-slot maps to a unique buffer slot in its expert's
    capacity range; dropped slots map out of bounds."""
    from repro.models.moe import _dispatch_indices
    k = min(k, E)
    rng = np.random.default_rng(T * 100 + E)
    expert_ids = jnp.asarray(rng.integers(0, E, T * k), jnp.int32)
    dest, order, keep = map(np.asarray, _dispatch_indices(
        jnp.asarray(expert_ids), E, cap))
    kept = dest[keep]
    assert len(set(kept.tolist())) == len(kept)          # no collisions
    assert (kept < E * cap).all()
    assert (dest[~keep] == E * cap).all()                # dropped → sentinel
    # each kept slot's expert bucket matches its expert id
    sorted_e = np.asarray(expert_ids)[order]
    assert ((kept // cap) == sorted_e[keep]).all()
    # per-expert kept count ≤ cap and = min(count, cap)
    for e in range(E):
        cnt = int((sorted_e == e).sum())
        kept_e = int(((kept // cap) == e).sum())
        assert kept_e == min(cnt, cap)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 100), st.floats(0.01, 100.0))
def test_quantize_roundtrip_error(seed, scale):
    from repro.optim.compress import _quantize
    rng = np.random.default_rng(seed)
    g = jnp.asarray((rng.normal(size=64) * scale).astype(np.float32))
    q, s = _quantize(g)
    err = np.abs(np.asarray(g) - np.asarray(q, np.float32) * float(s))
    assert err.max() <= float(s) / 2 + 1e-6
    assert np.abs(np.asarray(q)).max() <= 127


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 32), st.integers(2, 8))
def test_race_topk_identifies_separated_arms(n, k):
    """With well-separated deterministic arms, racing must return the true
    top-k (pull noise ~ tiny)."""
    from repro.configs.base import BMOConfig
    from repro.core.ucb import race_topk
    k = min(k, n - 1)
    rng = np.random.default_rng(n * 17 + k)
    means = np.sort(rng.uniform(0, 1, n)).astype(np.float32)
    means = means + np.arange(n, dtype=np.float32)  # gaps ≥ ~1

    def pull(arm_idx, key):
        noise = jax.random.normal(key, (arm_idx.shape[0], 2)) * 0.01
        return jnp.asarray(means)[arm_idx][:, None] + noise

    def exact(arm_idx):
        return jnp.asarray(means)[arm_idx]

    cfg = BMOConfig(k=k, delta=0.05, batch_arms=min(8, n), pulls_per_round=2)
    res = race_topk(pull, exact, n=n, max_pulls=64, pull_cost=1.0,
                    exact_cost=64.0, cfg=cfg, rng=jax.random.PRNGKey(0))
    assert set(np.asarray(res.topk).tolist()) == set(range(k))


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 6), st.integers(8, 40))
def test_sparse_dataset_roundtrip(n, d):
    from repro.core.datasets import SparseDataset
    rng = np.random.default_rng(n * d)
    mask = rng.random((n, d)) < 0.3
    x = np.where(mask, rng.normal(size=(n, d)), 0).astype(np.float32)
    ds = SparseDataset.build(x)
    dense = np.zeros((n, d), np.float32)
    idx, vals = np.asarray(ds.indices), np.asarray(ds.values)
    for i in range(n):
        real = idx[i] < d
        dense[i, idx[i][real]] = vals[i][real]
    np.testing.assert_array_equal(dense, x)
    # indices sorted with sentinel padding
    assert (np.diff(idx, axis=1) >= 0).all()
