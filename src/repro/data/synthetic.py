"""Synthetic data generators.

LM side: deterministic Zipf-ish token streams keyed by (seed, step, shard) —
reproducible across restarts and elastic re-sharding.

kNN side: generators matched to the paper's two datasets in (n, d, sparsity,
coordinate-distance tail). Tiny-ImageNet-like data is a clustered heavy-tail
mixture (Fig. 4c shows rapidly-decaying but heavy-ish coordinate-distance
tails); the 10x-genomics-like data is ~7% dense non-negative with
exponential magnitudes.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# LM tokens
# ---------------------------------------------------------------------------


def lm_batch(vocab: int, batch: int, seq: int, *, seed: int, step: int,
             shard: int = 0, n_shards: int = 1) -> Dict[str, np.ndarray]:
    """Deterministic (tokens, labels) batch; labels are next-token shifted."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, step, shard, n_shards]))
    # Zipf-ish marginal over the vocab with short-range repetition structure
    ranks = rng.zipf(1.3, size=(batch, seq + 1)) % vocab
    rep = rng.random((batch, seq + 1)) < 0.3
    ranks[:, 1:][rep[:, 1:]] = ranks[:, :-1][rep[:, 1:]]
    toks = ranks.astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}


# ---------------------------------------------------------------------------
# kNN corpora
# ---------------------------------------------------------------------------

_ROWS = 4096   # rows per draw of the dense generator


def clustered_dense(n: int, d: int, *, n_clusters: int = 64,
                    noise: float = 0.15, heavy_tail: float = 1.0,
                    seed: int = 0) -> np.ndarray:
    """Image-like corpus: cluster centers with per-point heavy-tailed scale.
    Most inter-point gaps are large (cheap to race); same-cluster points are
    the hard arms — matching the paper's Tiny-ImageNet behaviour."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, n)
    scale = (1.0 + heavy_tail * rng.exponential(1.0, size=(n, 1))).astype(np.float32)
    # drawn in row chunks — the same stream as one (n, d) draw, without its
    # float64 temporaries at paper scale (100k × 12288)
    pts = np.empty((n, d), np.float32)
    for s in range(0, n, _ROWS):
        e = min(n, s + _ROWS)
        pts[s:e] = centers[assign[s:e]] + noise * scale[s:e] * rng.normal(
            size=(e - s, d)).astype(np.float32)
    return pts


def clustered_sparse(n: int, d: int, *, sparsity: float = 0.07,
                     n_clusters: int = 32, seed: int = 0) -> np.ndarray:
    """RNA-seq-like corpus: ~sparsity fraction nonzero, non-negative,
    exponential magnitudes, cluster-structured supports."""
    rng = np.random.default_rng(seed)
    out = np.zeros((n, d), np.float32)
    # each cluster has a preferred support
    supports = [rng.choice(d, size=int(d * sparsity * 1.5), replace=False)
                for _ in range(n_clusters)]
    for i in range(n):
        c = rng.integers(0, n_clusters)
        sup = supports[c]
        keep = rng.random(len(sup)) < (sparsity / (sparsity * 1.5))
        idx = sup[keep]
        out[i, idx] = rng.exponential(2.0, size=len(idx)).astype(np.float32)
    return out


def make_knn_benchmark_data(kind: str, n: int, d: int, n_queries: int,
                            seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """(corpus, queries): queries are perturbed corpus points (paper queries
    points of the dataset itself)."""
    rng = np.random.default_rng(seed + 1)
    if kind == "sparse":
        corpus = clustered_sparse(n, d, seed=seed)
        qidx = rng.integers(0, n, n_queries)
        queries = corpus[qidx].copy()
        return corpus, queries
    corpus = clustered_dense(n, d, seed=seed)
    qidx = rng.integers(0, n, n_queries)
    queries = corpus[qidx] + 0.05 * rng.normal(size=(n_queries, d)).astype(np.float32)
    return corpus, queries.astype(np.float32)
