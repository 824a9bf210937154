"""The plain reference and the check that decides ``correct``.

The reference is an exact ℓ2 scan of the unrotated corpus, redrawn from
the configuration's data block by block (``corpus.row_blocks``), so it needs neither the
program's store nor the whole corpus resident. It imports nothing of the
program.

A served row is right when it names ``k`` distinct corpus rows, its race
certified it, and each row's exact distance is within the tie tolerance of
the true k-th smallest. Two numbers are compared:

* ``wrong_share``: wrong rows over rows checked (an answer that never came
  counts as wrong). Its limit is the configuration's own failure
  probability δ, the guarantee the race states per query.
* ``value_gap``: over the right rows, the widest relative gap between a
  served θ (the race's exact mean over the padded width, ``d_pad``) times
  ``d_pad`` and the reference's distance of the same row. Only answers
  whose values are exact carry it (the plane's certified prefixes).

``Bf16Scan`` is the control: the same scan put in the program's place with
the corpus and the queries rounded to bfloat16, the nearest precision below
the float32 the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.corpus import Generator, row_blocks

#: tie tolerance on squared distances, as the program's own δ-auditor uses
RTOL, ATOL = 1e-4, 1e-5
ROWS_PER_BLOCK = 4096
QUERY_CHUNK = 1024
#: candidates kept past k by the matmul-form scan, re-summed exactly
SPARE = 16


@functools.partial(jax.jit, static_argnames=("k",))
def _scan_block(best_d, best_i, x, q, start, *, k: int):
    """Fold one corpus block into the running k smallest distances."""
    xx = jnp.sum(x * x, axis=1)
    qq = jnp.sum(q * q, axis=1)
    d = (qq[:, None] - 2.0 * jnp.dot(q, x.T, precision="highest")
         + xx[None, :])
    ids = start + jnp.arange(x.shape[0], dtype=jnp.int32)
    cat_d = jnp.concatenate([best_d, d], axis=1)
    cat_i = jnp.concatenate(
        [best_i, jnp.broadcast_to(ids[None], d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


@jax.jit
def _row_dists(x, q):
    """Squared ℓ2 distance of row i of ``x`` to row i of ``q``, summed
    from the differences (no cancellation)."""
    diff = x - q
    return jnp.sum(diff * diff, axis=1)


def exact_knn(gen: Generator, queries: np.ndarray, k: int,
              rows_per_block: int = ROWS_PER_BLOCK):
    """(dists, ids), each (Q, k) ascending: the true k nearest corpus rows
    of every query. One pass over the redrawn corpus keeps ``k + SPARE``
    candidates by the matmul form of the distance; their distances are
    then summed again from the differences, which the matmul form's
    cancellation cannot blur."""
    Q, m = queries.shape[0], k + SPARE
    chunks = [(s, min(Q, s + QUERY_CHUNK)) for s in range(0, Q, QUERY_CHUNK)]
    qs = [jnp.asarray(queries[s:e]) for s, e in chunks]
    best = [(jnp.full((e - s, m), jnp.inf, jnp.float32),
             jnp.full((e - s, m), -1, jnp.int32)) for s, e in chunks]
    for start, x in row_blocks(gen, rows_per_block):
        best = [_scan_block(bd, bi, x, q, jnp.int32(start), k=m)
                for (bd, bi), q in zip(best, qs)]
    cand = np.concatenate([np.asarray(bi) for _, bi in best])
    dist = served_dists(gen, queries, cand)
    order = np.argsort(dist, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(dist, order, axis=1),
            np.take_along_axis(cand, order, axis=1))


def served_dists(gen: Generator, queries: np.ndarray,
                 rows: np.ndarray) -> np.ndarray:
    """(Q, k) exact distances of the served corpus rows (inf where a row
    id is out of range)."""
    Q, k = rows.shape
    ok = (rows >= 0) & (rows < gen.n)
    flat = np.where(ok, rows, 0).reshape(-1)
    qrep = np.repeat(queries, k, axis=0)
    out = np.empty(flat.shape[0], np.float32)
    for s in range(0, flat.shape[0], ROWS_PER_BLOCK):
        e = min(flat.shape[0], s + ROWS_PER_BLOCK)
        out[s:e] = np.asarray(_row_dists(gen.rows(flat[s:e]),
                                         jnp.asarray(qrep[s:e])))
    return np.where(ok, out.reshape(Q, k), np.inf)


def wrong_rows(served_d: np.ndarray, rows: np.ndarray, certified: np.ndarray,
               kth: np.ndarray) -> np.ndarray:
    """(Q,) bool: rows that are not a certified true top-k up to ties."""
    k = rows.shape[1]
    distinct = np.array([len(np.unique(r)) == k for r in rows], bool)
    within = np.all(served_d <= (kth + ATOL + RTOL * np.abs(kth))[:, None],
                    axis=1)
    return ~(np.asarray(certified, bool) & distinct & within)


def value_gaps(served_d: np.ndarray, values: np.ndarray,
               theta_scale: float) -> np.ndarray:
    """(Q,) widest relative gap between the served θ, scaled to a squared
    distance, and the exact distance of the row it names."""
    exact = np.maximum(served_d, 1e-30)
    return np.max(np.abs(values * theta_scale - served_d) / exact, axis=1)


def check(gen: Generator, queries: np.ndarray, rows: np.ndarray,
          certified: np.ndarray, k: int) -> dict:
    """Compare served rows (corpus row ids, -1 where none) with the
    reference: per row, whether it is wrong, and the exact distance of
    each row it names."""
    true_d, _ = exact_knn(gen, queries, k)
    served_d = served_dists(gen, queries, rows)
    return {"wrong": wrong_rows(served_d, rows, certified, true_d[:, k - 1]),
            "served_d": served_d}


@functools.partial(jax.jit, static_argnames=("k",))
def _bf16_block(best_d, best_i, x, xx, q, start, *, k: int):
    qq = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=1)
    d = (qq[:, None] - 2.0 * jnp.dot(q, x.T,
                                      preferred_element_type=jnp.float32)
         + xx[None, :])
    ids = start + jnp.arange(x.shape[0], dtype=jnp.int32)
    cat_d = jnp.concatenate([best_d, d], axis=1)
    cat_i = jnp.concatenate(
        [best_i, jnp.broadcast_to(ids[None], d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


@jax.jit
def _to_bf16(x):
    xb = x.astype(jnp.bfloat16)
    return xb, jnp.sum(jnp.square(xb.astype(jnp.float32)), axis=1)


class Bf16Scan:
    """The control: an exact scan over the corpus and queries rounded to
    bfloat16 (float32 accumulation), answering in the program's place."""

    def __init__(self, gen: Generator, k: int,
                 rows_per_block: int = ROWS_PER_BLOCK):
        self.k = k
        self.blocks = [(start, *_to_bf16(x))
                       for start, x in row_blocks(gen, rows_per_block)]

    def query(self, queries: np.ndarray):
        """(dists, rows), each (Q, k): the scan's k nearest corpus rows."""
        q = jnp.asarray(queries, jnp.bfloat16)
        bd = jnp.full((q.shape[0], self.k), jnp.inf, jnp.float32)
        bi = jnp.full((q.shape[0], self.k), -1, jnp.int32)
        for start, x, xx in self.blocks:
            bd, bi = _bf16_block(bd, bi, x, xx, q, jnp.int32(start), k=self.k)
        return np.asarray(bd), np.asarray(bi)
