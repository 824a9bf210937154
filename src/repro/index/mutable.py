"""Online mutation of an IndexStore (DESIGN.md §3.3): the serving datastore
can grow during decode — true kNN-LM behaviour — without a rebuild.

  * ``insert`` writes new rows into free (tombstoned or never-used) slots,
    doubling capacity only when none are free; slot ids are returned so the
    caller can keep side payloads (e.g. next-token ids) aligned,
  * ``delete`` is an O(1) tombstone flip — dead slots enter every subsequent
    race pre-rejected (batched_race ``dead`` mask), so queries never pay for
    them beyond the mask itself,
  * ``compact`` rebuilds a dense slot layout once tombstones accumulate,
    returning the old→new slot mapping for payload reindexing.

All mutation is host-side/eager: shapes change only on growth or compaction,
so the jitted batched-race executables stay warm across steady-state
insert/delete traffic.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.datasets import next_pow2
from repro.index.builder import _sparse_prior, prepare_rows
from repro.index.store import IndexStore, free_slots
from repro.utils import get_logger

log = get_logger("repro.index")


def _grow(store: IndexStore, need: int) -> IndexStore:
    cap = store.capacity
    new_cap = max(2 * cap, next_pow2(cap + need))
    extra = new_cap - cap
    log.info("growing index capacity %d -> %d", cap, new_cap)
    kw = dict(alive=jnp.pad(store.alive, (0, extra)),
              prior_var=jnp.pad(store.prior_var, (0, extra)))
    if store.kind == "sparse":
        kw.update(indices=jnp.pad(store.indices, ((0, extra), (0, 0)),
                                  constant_values=store.d),
                  values=jnp.pad(store.values, ((0, extra), (0, 0))),
                  nnz=jnp.pad(store.nnz, (0, extra)))
    else:
        kw.update(x=jnp.pad(store.x, ((0, extra), (0, 0))))
    return dataclasses.replace(store, **kw)


def insert(store: IndexStore, rows) -> Tuple[IndexStore, np.ndarray]:
    """Insert (B, d) dense rows (all kinds take dense input; the rotated box
    rotates with the *cached* signs, the sparse box re-compresses). Returns
    (new store, slot ids (B,))."""
    rows = np.asarray(rows, np.float32)
    if rows.ndim == 1:
        rows = rows[None]
    bsz = rows.shape[0]
    free = free_slots(store)
    if len(free) < bsz:
        store = _grow(store, bsz - len(free))
        free = free_slots(store)
    slots = free[:bsz]
    sl = jnp.asarray(slots)
    alive = store.alive.at[sl].set(True)

    if store.kind == "sparse":
        nnz = (rows != 0).sum(axis=1).astype(np.int32)
        m_new = int(max(nnz.max(initial=0), 1))
        store = _widen_sparse(store, m_new)
        m = store.m
        idx = np.full((bsz, m), store.d, np.int32)
        val = np.zeros((bsz, m), np.float32)
        for i in range(bsz):
            nz = np.nonzero(rows[i])[0]
            idx[i, : len(nz)] = nz
            val[i, : len(nz)] = rows[i, nz]
        indices = store.indices.at[sl].set(jnp.asarray(idx))
        values = store.values.at[sl].set(jnp.asarray(val))
        nnz_arr = store.nnz.at[sl].set(jnp.asarray(nnz))
        prior = store.prior_var.at[sl].set(
            _sparse_prior(jnp.asarray(val), jnp.asarray(nnz), store.d))
        return dataclasses.replace(store, alive=alive, indices=indices,
                                   values=values, nnz=nnz_arr,
                                   prior_var=prior), slots

    x_rows, row_prior = prepare_rows(
        rows, store.d_pad, store.signs if store.kind == "rotated" else None,
        store.block, store.cfg.metric)
    x = store.x.at[sl].set(x_rows)
    prior = store.prior_var.at[sl].set(row_prior)
    return dataclasses.replace(store, alive=alive, x=x, prior_var=prior), slots


def _widen_sparse(store: IndexStore, m_new: int) -> IndexStore:
    if m_new <= store.m:
        return store
    extra = m_new - store.m
    log.info("widening sparse index m %d -> %d", store.m, m_new)
    return dataclasses.replace(
        store,
        indices=jnp.pad(store.indices, ((0, 0), (0, extra)),
                        constant_values=store.d),
        values=jnp.pad(store.values, ((0, 0), (0, extra))))


def delete(store: IndexStore, slot_ids) -> IndexStore:
    """Tombstone slots (O(1)); data stays until ``compact``."""
    sl = jnp.asarray(np.atleast_1d(np.asarray(slot_ids, np.int64)))
    return dataclasses.replace(store, alive=store.alive.at[sl].set(False))


def tombstone_fraction(store: IndexStore) -> float:
    """Fraction of capacity occupied by dead slots (tombstones + never-used
    tail): the state every race still pays a mask for."""
    return 1.0 - store.n_live / max(store.capacity, 1)


def maybe_compact(store: IndexStore, *, threshold: float = 0.5,
                  ) -> Tuple[IndexStore, Optional[np.ndarray]]:
    """Auto-compaction policy (ROADMAP): rebuild the dense slot layout once
    the tombstone fraction crosses ``threshold``. Returns
    ``(store, old_ids)`` — ``old_ids`` is None when no compaction ran, else
    the old→new slot map for payload reindexing (see ``compact``).

    Only worthwhile when it actually shrinks capacity: with a power-of-two
    slot layout, dropping tombstones pays off (smaller race buffers, a fresh
    jit specialization) only once live < capacity/2, so thresholds below 0.5
    would trigger rebuilds into the *same* capacity — the shrink check runs
    on plain ints BEFORE the O(capacity·d) gather, so an over-eager
    threshold costs nothing per call. Callers amortize this into mutation
    traffic (serve/engine.py folds it into the per-step index append)."""
    if (store.capacity and tombstone_fraction(store) > threshold
            and next_pow2(max(store.n_live, 1)) < store.capacity):
        return compact(store)
    return store, None


def compact(store: IndexStore) -> Tuple[IndexStore, np.ndarray]:
    """Rebuild a dense slot layout dropping tombstones. Returns (new store,
    old_ids (new_cap,)) with ``old_ids[j]`` = previous slot of new slot j
    (−1 for empty slots) — reindex side payloads with it."""
    alive_np = np.asarray(store.alive)
    live = np.nonzero(alive_np)[0]
    n = len(live)
    cap = max(next_pow2(max(n, 1)), 1)
    old_ids = np.full((cap,), -1, np.int64)
    old_ids[:n] = live
    sl = jnp.asarray(live)
    alive = jnp.arange(cap) < n
    kw = dict(alive=alive,
              prior_var=_take_pad(store.prior_var, sl, cap))
    if store.kind == "sparse":
        kw.update(indices=_take_pad(store.indices, sl, cap, fill=store.d),
                  values=_take_pad(store.values, sl, cap),
                  nnz=_take_pad(store.nnz, sl, cap))
    else:
        kw.update(x=_take_pad(store.x, sl, cap))
    log.info("compacted index: %d live slots, capacity %d -> %d",
             n, store.capacity, cap)
    return dataclasses.replace(store, **kw), old_ids


def _take_pad(arr, sl, cap: int, fill=0):
    taken = arr[sl]
    pad = cap - taken.shape[0]
    if pad:
        widths = ((0, pad),) + ((0, 0),) * (arr.ndim - 1)
        taken = jnp.pad(taken, widths, constant_values=fill)
    return taken
