"""BMO-NN (paper Algorithm 2): k-nearest neighbours via BMO-UCB, for the
three Monte-Carlo boxes of the paper:

  * dense   (§III):   uniform coordinate/block sampling, ℓ1 or ℓ2²,
  * rotated (§IV-B):  dense box on x' = H D x (ℓ2 only; the rotation makes
                      coordinates exchangeable — which also justifies the
                      TPU block sampling, see DESIGN.md §2),
  * sparse  (§IV-A):  support-union importance sampling (Eq. 12), ℓ1.

θ_i = ρ(q, x_i)/d throughout (the paper's mean normalization).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BMOConfig
from repro.core.datasets import DenseDataset, SparseDataset, hadamard_rotate
from repro.core.ucb import RaceResult, race_topk
from repro.kernels import ops as kops


class KNNResult(NamedTuple):
    indices: jax.Array     # (Q, k)
    values: jax.Array      # (Q, k) θ estimates (ρ/d)
    coord_ops: jax.Array   # (Q,) coordinate-wise distance computations
    rounds: jax.Array      # (Q,)
    n_exact: jax.Array     # (Q,)


# ---------------------------------------------------------------------------
# dense / rotated boxes
# ---------------------------------------------------------------------------


def _dense_pull_fn(ds: DenseDataset, q: jax.Array, cfg: BMOConfig, impl: str):
    nb = ds.n_blocks

    def pull(arm_idx, rng):
        B = arm_idx.shape[0]
        blk = jax.random.randint(rng, (B, cfg.pulls_per_round), 0, nb)
        return kops.block_pull(ds.x, q, arm_idx, blk, block=ds.block,
                               metric=cfg.metric, impl=impl)

    return pull


def _dense_exact_fn(ds: DenseDataset, q: jax.Array, cfg: BMOConfig, impl: str):
    def exact(arm_idx):
        rows = ds.x[arm_idx]                       # (B, d_pad)
        dist = kops.pairwise_dist(q[None], rows, metric=cfg.metric, impl=impl)
        return dist[0] / ds.d_pad   # θ units: the mean a block pull estimates

    return exact


def query_dense(ds: DenseDataset, q: jax.Array, cfg: BMOConfig, rng: jax.Array,
                *, impl: str = "auto", eliminate: bool = True) -> RaceResult:
    """k-NN of one query against a dense corpus. ``q`` already padded."""
    max_pulls = ds.d_pad // ds.block               # = d/B blocks ≙ d coords
    return race_topk(
        _dense_pull_fn(ds, q, cfg, impl),
        _dense_exact_fn(ds, q, cfg, impl),
        n=ds.n,
        max_pulls=max_pulls,
        pull_cost=float(ds.block),
        exact_cost=float(ds.d),
        cfg=cfg, rng=rng, eliminate=eliminate,
    )


# ---------------------------------------------------------------------------
# sparse box (§IV-A, Eq. 12)
# ---------------------------------------------------------------------------


def _sparse_lookup(indices_row, values_row, t):
    """value of the row at coordinate t (0 if absent) + membership flag."""
    pos = jnp.searchsorted(indices_row, t)
    pos = jnp.clip(pos, 0, indices_row.shape[0] - 1)
    found = indices_row[pos] == t
    return jnp.where(found, values_row[pos], 0.0), found


def sparse_pull_one(ds: SparseDataset, q_idx, q_val, q_nnz, arm, key):
    """One Eq. 12 sample of θ̂ for (query, arm). Module-level so both the
    per-query racer here and index.batched_race can vmap over it."""
    d = ds.d
    k1, k2, k3 = jax.random.split(key, 3)
    ai, av, an = ds.indices[arm], ds.values[arm], ds.nnz[arm]
    tot = (q_nnz + an).astype(jnp.float32)
    from_query = jax.random.uniform(k1) < q_nnz / jnp.maximum(tot, 1.0)
    # sample a support coordinate from the chosen side
    tq = q_idx[jax.random.randint(k2, (), 0, jnp.maximum(q_nnz, 1))]
    ta = ai[jax.random.randint(k3, (), 0, jnp.maximum(an, 1))]
    t = jnp.where(from_query, tq, ta)
    # both sides' values at t
    va, found_a = _sparse_lookup(ai, av, t)
    vq, found_q = _sparse_lookup(q_idx, q_val, t)
    in_other = jnp.where(from_query, found_a, found_q)
    mult = tot / (2.0 * d) * (1.0 + (~in_other).astype(jnp.float32))
    # Eq. 12 value (ℓ1 coordinate distance), θ normalized by d already
    val = mult * jnp.abs(vq - va)
    # degenerate both-sides-empty case (tombstoned/zero rows racing a zero
    # query): the support union is empty so θ = 0 exactly; the sampled
    # coordinate above came from padding and must not contribute
    return jnp.where(tot > 0, val, 0.0)


def _sparse_pull_fn(ds: SparseDataset, q_idx, q_val, q_nnz, cfg: BMOConfig):
    def pull(arm_idx, rng):
        B = arm_idx.shape[0]
        P = cfg.pulls_per_round
        keys = jax.random.split(rng, B * P).reshape(B, P, 2)
        return jax.vmap(lambda a, ks: jax.vmap(
            lambda kk: sparse_pull_one(ds, q_idx, q_val, q_nnz, a, kk))(ks))(
            arm_idx, keys).astype(jnp.float32)

    return pull


def sparse_exact_theta(ds: SparseDataset, q_idx, q_val, arm_idx):
    """θ_i = ‖q − x_i‖₁ / d via support-merge lookups: Σ_{t∈Sq}|q_t − x_t| +
    Σ_{t∈Si, t∉Sq} |x_t|.  Cost ≈ n_q + n_i lookups (the paper's
    sparsity-aware exact baseline)."""

    def one(arm):
        ai, av = ds.indices[arm], ds.values[arm]
        xa_at_q, _ = jax.vmap(lambda t: _sparse_lookup(ai, av, t))(q_idx)
        term1 = jnp.sum(jnp.abs(q_val - xa_at_q) * (q_idx < ds.d))
        _, in_q = jax.vmap(lambda t: _sparse_lookup(q_idx, q_val, t))(ai)
        term2 = jnp.sum(jnp.abs(av) * (~in_q) * (ai < ds.d))
        return (term1 + term2) / ds.d

    return jax.vmap(one)(arm_idx)


def query_sparse(ds: SparseDataset, q_idx, q_val, q_nnz, cfg: BMOConfig,
                 rng: jax.Array, *, eliminate: bool = True) -> RaceResult:
    """k-NN of one sparse query (padded index/value rows) — ℓ1 only."""
    exact_cost = (ds.nnz + q_nnz).astype(jnp.float32)
    # an arm is 'exactly evaluable' after ~support-size pulls (cost parity
    # with the sparse exact computation), min 8 to keep CIs meaningful
    max_pulls = jnp.maximum(exact_cost, 8.0)
    return race_topk(
        _sparse_pull_fn(ds, q_idx, q_val, q_nnz, cfg),
        lambda arm_idx: sparse_exact_theta(ds, q_idx, q_val, arm_idx),
        n=ds.n,
        max_pulls=max_pulls,
        pull_cost=1.0,
        exact_cost=exact_cost,
        cfg=cfg, rng=rng, eliminate=eliminate,
        max_pulls_static=int(ds.m + q_idx.shape[0]),
    )


# ---------------------------------------------------------------------------
# multi-query drivers (Algorithm 2 iterates queries; embarrassingly parallel)
# ---------------------------------------------------------------------------


def knn(corpus, queries, cfg: BMOConfig, rng: jax.Array, *,
        impl: str = "auto", eliminate: bool = True,
        exclude_self: Optional[jax.Array] = None) -> KNNResult:
    """k-NN of each query row against the corpus.

    corpus: (n, d) array (dense/rotated) or SparseDataset (sparse box).
    queries: (Q, d) array, or (q_idx, q_val, q_nnz) padded triplet for sparse.
    ``cfg.rotate`` applies the §IV-B Hadamard rotation to corpus+queries
    (ℓ2 only; distances preserved).
    """
    if cfg.sparse:
        assert isinstance(corpus, SparseDataset)
        q_idx, q_val, q_nnz = queries

        def run_one(args):
            qi, qv, qn, key = args
            r = query_sparse(corpus, qi, qv, qn, cfg, key, eliminate=eliminate)
            return KNNResult(r.topk, r.topk_values, r.coord_ops, r.rounds, r.n_exact)

        Q = q_idx.shape[0]
        keys = jax.random.split(rng, Q)
        return jax.lax.map(run_one, (q_idx, q_val, q_nnz, keys))

    x = jnp.asarray(corpus, jnp.float32)
    qs = jnp.asarray(queries, jnp.float32)
    if cfg.rotate:
        assert cfg.metric == "l2", "rotation preserves only ℓ2"
        rng, sub = jax.random.split(rng)
        both, _ = hadamard_rotate(jnp.concatenate([x, qs], 0), sub)
        x, qs = both[: x.shape[0]], both[x.shape[0]:]
    ds = DenseDataset.build(x, block=cfg.block)
    qs = ds.pad_query(qs)

    def run_one(args):
        q, key = args
        r = query_dense(ds, q, cfg, key, impl=impl, eliminate=eliminate)
        return KNNResult(r.topk, r.topk_values, r.coord_ops, r.rounds, r.n_exact)

    Q = qs.shape[0]
    keys = jax.random.split(rng, Q)
    return jax.lax.map(run_one, (qs, keys))


def knn_graph(x, cfg: BMOConfig, rng: jax.Array, *, impl: str = "auto",
              eliminate: bool = True) -> KNNResult:
    """Algorithm 2 proper: k-NN of every point among the others. Implemented
    as knn() with k+1 then dropping self-matches."""
    cfg1 = dataclasses.replace(cfg, k=cfg.k + 1)
    res = knn(x, x, cfg1, rng, impl=impl, eliminate=eliminate)
    Q = res.indices.shape[0]
    self_row = jnp.arange(Q)[:, None]
    is_self = res.indices == self_row
    # keep k non-self entries per row (self, when found, is dropped;
    # otherwise drop the worst)
    rank = jnp.argsort(jnp.where(is_self, jnp.inf, res.values), axis=1)[:, : cfg.k]
    take = jnp.take_along_axis
    return KNNResult(take(res.indices, rank, 1), take(res.values, rank, 1),
                     res.coord_ops, res.rounds, res.n_exact)
