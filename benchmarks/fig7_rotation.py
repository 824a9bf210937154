"""Paper Fig. 7 / Lemma 3: the randomized Hadamard rotation lightens the
coordinate-distance tails (smaller ‖x−y‖∞²·d / ‖x−y‖₂² ratio → smaller
sub-Gaussian constant → fewer pulls)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, set_accuracy
from repro.configs.base import BMOConfig
from repro.core import bmo_nn, oracle
from repro.core.datasets import hadamard_rotate
from repro.data.synthetic import make_knn_benchmark_data


def tail_ratio(x: np.ndarray, pairs: int = 64, seed: int = 0) -> float:
    """E[ d·max_j (x_a−x_b)_j² / ‖x_a−x_b‖₂² ] over random pairs — the
    Lemma 3 improvement factor proxy (1 = perfectly flat coordinates)."""
    rng = np.random.default_rng(seed)
    n, d = x.shape
    vals = []
    for _ in range(pairs):
        a, b = rng.integers(0, n, 2)
        diff2 = (x[a] - x[b]) ** 2
        denom = diff2.sum()
        if denom > 0:
            vals.append(d * diff2.max() / denom)
    return float(np.mean(vals))


def spiky(n: int, d: int, seed: int = 0) -> np.ndarray:
    """Image-like coordinate structure: per-coordinate scales are lognormal
    (a few coordinates carry most of the pairwise distance — the regime
    Lemma 3 targets; i.i.d. Gaussian coordinates are already flat and show
    no rotation benefit)."""
    rng = np.random.default_rng(seed)
    scales = rng.lognormal(0.0, 1.6, size=(1, d)).astype(np.float32)
    centers = rng.normal(size=(16, d)).astype(np.float32) * scales
    assign = rng.integers(0, 16, n)
    pts = centers[assign] + 0.2 * scales * rng.normal(size=(n, d)).astype(np.float32)
    return pts.astype(np.float32)


def main(n: int = 1000, d: int = 4096, Q: int = 6, k: int = 5):
    rng = np.random.default_rng(51)
    corpus = spiky(n, d, seed=51)
    qidx = rng.integers(0, n, Q)
    queries = corpus[qidx] + 0.02 * rng.normal(size=(Q, d)).astype(np.float32)
    both = jnp.concatenate([jnp.asarray(corpus), jnp.asarray(queries)], 0)
    rot, _ = hadamard_rotate(both, jax.random.PRNGKey(0))
    rot = np.asarray(rot)
    r_before = tail_ratio(corpus)
    r_after = tail_ratio(rot[:n])
    emit("fig7_tail_before", 0.0, f"dmax/l2={r_before:.1f}")
    emit("fig7_tail_after", 0.0, f"dmax/l2={r_after:.1f} "
         f"improvement={r_before / r_after:.1f}x")

    ex = oracle.exact_knn(corpus, queries, k, "l2")
    for rotate in (False, True):
        cfg = BMOConfig(k=k, delta=0.01, block=128, batch_arms=32,
                        metric="l2", rotate=rotate)
        res = bmo_nn.knn(corpus, queries, cfg, jax.random.PRNGKey(1))
        acc = set_accuracy(res.indices, ex.indices)
        gain = float(Q * n * d / np.sum(np.asarray(res.coord_ops)))
        emit(f"fig7_knn_rotate{int(rotate)}", 0.0,
             f"gain={gain:.1f}x acc={acc:.3f}")


if __name__ == "__main__":
    main()
