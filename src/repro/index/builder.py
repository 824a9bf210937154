"""One-time index preprocessing (DESIGN.md §3.1): corpus → IndexStore.

Everything the per-call ``bmo_nn.knn`` path recomputes per query batch is
done once here and amortized across the index's lifetime:

  * dense:   blocked/padded corpus layout,
  * rotated: the §IV-B Hadamard rotation is *cached* — the sign vector and
    the pre-rotated corpus are stored, so serving only rotates the (Q, d)
    query batch (O(Q d log d)) instead of corpus + queries every call,
  * sparse:  padded-CSR layout (§IV-A box),
  * per-arm block statistics (mean/variance of each row's block values),
    the warm-start priors for the racing CIs.

Persistence goes through checkpoint/manager.py's atomic save, so an index
directory is bit-compatible with the training checkpoints' tooling.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BMOConfig
from repro.core.datasets import SparseDataset, next_pow2
from repro.index.store import IndexStore
from repro.utils import get_logger

log = get_logger("repro.index")

#: corpus rows laid out per build step: the build's transient device memory
#: is a few such chunks on top of the capacity-padded store
BUILD_ROWS = 4096


def _row_block_stats(x: jax.Array, block: int, metric: str):
    """Per-arm variance across blocks of the row's block values — the
    query-independent component of the pull-value variance (the pull is the
    block mean of |x_t − q_t|^p; its spread across blocks is bounded below
    by the spread of the row's own block energies)."""
    n, d_pad = x.shape
    xb = x.reshape(n, d_pad // block, block)
    v = jnp.mean(jnp.abs(xb) if metric == "l1" else xb * xb, axis=-1)  # (n, nb)
    return jnp.var(v, axis=-1)


def _sparse_prior(values: jax.Array, nnz: jax.Array, d: int):
    """Eq. 12 pull values are (tot/2d)·(1+…)·|v|: scale the per-row value
    variance by the squared support mass so empty/light rows start tight."""
    m = values.shape[1]
    mask = jnp.arange(m)[None, :] < nnz[:, None]
    cnt = jnp.maximum(nnz.astype(jnp.float32), 1.0)
    mean = jnp.sum(jnp.abs(values) * mask, 1) / cnt
    var = jnp.sum(jnp.square(jnp.abs(values) - mean[:, None]) * mask, 1) / cnt
    scale = (nnz.astype(jnp.float32) / d) ** 2
    return var * scale


def prepare_rows(rows: jax.Array, d_pad: int, signs: Optional[jax.Array],
                 block: int, metric: str):
    """Dense (B, d) rows → the store's blocked (B, d_pad) layout, rotated
    with the cached ``signs`` in the rotated box, and their block-statistics
    priors (B,). Shared by the build and by online inserts (mutable.py)."""
    x = jnp.asarray(rows, jnp.float32)
    pad = d_pad - x.shape[1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    if signs is not None:
        from repro.kernels import ops as kops
        x = kops.fwht(x * signs[None, :])
    return x, _row_block_stats(x, block, metric)


@functools.partial(jax.jit, static_argnames=("block", "metric"),
                   donate_argnums=(0, 1))
def _write_rows(x, prior_var, rows, signs, start, *, block: int, metric: str):
    """Lay out one row chunk into the (donated) padded store in place."""
    xr, pv = prepare_rows(rows, x.shape[1], signs, block, metric)
    return (jax.lax.dynamic_update_slice(x, xr, (start, 0)),
            jax.lax.dynamic_update_slice(prior_var, pv, (start,)))


def build_index(corpus, cfg: BMOConfig, rng: jax.Array, *,
                capacity: Optional[int] = None) -> IndexStore:
    """Preprocess ``corpus`` into an IndexStore ready for batched serving.

    corpus: (n, d) array (dense; also the input for the rotated/sparse boxes
    — ``cfg.rotate`` / ``cfg.sparse`` select the §IV box exactly like
    ``bmo_nn.knn``). ``capacity``: total slots (≥ n); defaults to the next
    power of two so early inserts don't force a growth.

    Dense/rotated rows are moved to the device and laid out ``BUILD_ROWS``
    at a time into the preallocated store, so a host-side (numpy) corpus is
    never resident on the device in full beside it.
    """
    if cfg.sparse:
        return _build_sparse(corpus, cfg, capacity)
    if not hasattr(corpus, "shape"):
        corpus = np.asarray(corpus, np.float32)
    n, d = corpus.shape
    kind = "rotated" if cfg.rotate else "dense"
    signs = None
    d_pad = d + (-d) % cfg.block
    if cfg.rotate:
        assert cfg.metric == "l2", "rotation preserves only ℓ2"
        assert cfg.block & (cfg.block - 1) == 0, \
            "rotated box needs a power-of-two block"
        d_pad = max(next_pow2(d), cfg.block)
        signs = jax.random.rademacher(rng, (d_pad,), jnp.float32)
    cap = capacity or next_pow2(n)
    assert cap >= n
    x = jnp.zeros((cap, d_pad), jnp.float32)
    prior_var = jnp.zeros((cap,), jnp.float32)
    for start in range(0, n, BUILD_ROWS):
        rows = jnp.asarray(corpus[start:start + BUILD_ROWS], jnp.float32)
        x, prior_var = _write_rows(x, prior_var, rows, signs,
                                   jnp.int32(start), block=cfg.block,
                                   metric=cfg.metric)
    alive = jnp.arange(cap) < n
    log.info("built %s index: n=%d cap=%d d=%d d_pad=%d block=%d",
             kind, n, cap, d, d_pad, cfg.block)
    return IndexStore(kind=kind, cfg=cfg, d=d, alive=alive, x=x,
                      block=cfg.block, signs=signs, prior_var=prior_var)


def _build_sparse(corpus, cfg: BMOConfig, capacity: Optional[int]) -> IndexStore:
    ds = corpus if isinstance(corpus, SparseDataset) else SparseDataset.build(
        np.asarray(corpus))
    n, m, d = ds.n, ds.m, ds.d
    cap = capacity or next_pow2(n)
    assert cap >= n
    indices = jnp.pad(ds.indices, ((0, cap - n), (0, 0)), constant_values=d)
    values = jnp.pad(ds.values, ((0, cap - n), (0, 0)))
    nnz = jnp.pad(ds.nnz, (0, cap - n))
    alive = jnp.arange(cap) < n
    prior_var = _sparse_prior(values, nnz, d)
    log.info("built sparse index: n=%d cap=%d d=%d m=%d", n, cap, d, m)
    return IndexStore(kind="sparse", cfg=cfg, d=d, alive=alive,
                      indices=indices, values=values, nnz=nnz,
                      prior_var=prior_var)


# ---------------------------------------------------------------------------
# persistence (checkpoint/manager.py)
# ---------------------------------------------------------------------------


def save_index(store: IndexStore, path: str, *, extra=None) -> None:
    """Atomic write of the store's arrays + meta (checkpoint layout).
    ``extra(tmpdir)``: optional callback staging sidecars (payload, tuned
    config) into the same all-or-nothing publish — a crash mid-save can
    never leave an index without its sidecars (or vice versa)."""
    from repro import checkpoint
    checkpoint.manager.save(path, store.arrays(), meta=store.meta(),
                            extra=extra)


def load_index(path: str) -> IndexStore:
    from repro import checkpoint
    arrays = checkpoint.manager.load_arrays(path)
    meta = checkpoint.manager.read_meta(path)
    return IndexStore.from_arrays(arrays, meta)
