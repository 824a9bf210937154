"""Survivor-compacted racing frontier (DESIGN.md §4.2).

The PR-1 batched driver keeps (Q, n) state for the whole race: every round's
CI radii, top-k selection and acceptance masks traverse all n arms even when
all but a handful are long rejected — the per-round cost is flat in how hard
the instance actually is. The paper's O((n+d)·log²) bound only materializes
if per-round work tracks the *surviving* arms.

This module keeps the racing state in *bucketed dense buffers* instead:
after each epoch the still-alive entries (accepted + candidates) are
gathered to the front and the buffer width W shrinks along a power-of-two
schedule n → n/2 → n/4 → … (each width is one extra XLA specialization of
the epoch step — a bounded, ~log₂(n)-sized compile cache, amortized across
the index's serving lifetime). All bookkeeping from then on is O(Q·W).

Invariant (tested): compaction only ever drops rejected or padding entries
and preserves per-entry statistics exactly, so the race's accept/reject
decisions are *identical* with and without compaction. The CI variance pool
is defined over survivors (not all alive arms as in the PR-1 driver)
precisely so this invariance holds — see ``batched_race`` for the radius.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.datasets import next_pow2


class FrontierState(NamedTuple):
    """Bucketed racing state: (Q, W) buffers over the survivor frontier.

    ``ids`` maps buffer positions to original arm/slot ids; ``valid`` marks
    real entries (padding and — after compaction — nothing else is invalid;
    dead/tombstoned slots enter as invalid + rejected). Per-query scalars
    mirror the PR-1 ``BatchedRaceState``.
    """
    ids: jax.Array        # (Q, W) int32 arm/slot ids
    mean: jax.Array       # (Q, W) running θ̂
    count: jax.Array      # (Q, W) pulls so far
    m2: jax.Array         # (Q, W) Welford M2
    prior: jax.Array      # (Q, W) warm-start variance prior (gathered)
    exact: jax.Array      # (Q, W) bool — mean is exact, CI = 0
    accepted: jax.Array   # (Q, W) bool
    rejected: jax.Array   # (Q, W) bool
    valid: jax.Array      # (Q, W) bool — False for padding entries
    coord_ops: jax.Array  # (Q,) coordinate-op counter
    n_exact: jax.Array    # (Q,) int32 arms exactly evaluated — a running
                          # counter, NOT derived from the buffers: compaction
                          # may drop exact-then-rejected entries
    rounds: jax.Array     # (Q,) int32 equivalent pull-rounds while active
    done: jax.Array       # (Q,) bool
    rng: jax.Array

    @property
    def width(self) -> int:
        return self.ids.shape[1]


def survivors(st: FrontierState) -> jax.Array:
    """(Q, W) bool — entries the race still owes work or an answer for."""
    return st.valid & ~st.rejected


@functools.partial(jax.jit, static_argnames=("W_new",))
def compact_frontier(st: FrontierState, *, W_new: int) -> FrontierState:
    """Gather each query's surviving entries into the first ``W_new``
    positions and drop the rest of the buffer.

    Priority: accepted < candidate < (rejected | padding), stably — so a
    finished query's k accepted arms survive any truncation, and for active
    queries the caller guarantees W_new ≥ survivor count (nothing live is
    ever dropped). Statistics ride along untouched.
    """
    with jax.named_scope("repro.compact_frontier"):
        key = jnp.where(st.accepted, 0, jnp.where(survivors(st), 1, 2))
        order = jnp.argsort(key, axis=1)[:, :W_new]
        take = lambda a: jnp.take_along_axis(a, order, axis=1)
        return st._replace(
            ids=take(st.ids), mean=take(st.mean), count=take(st.count),
            m2=take(st.m2), prior=take(st.prior), exact=take(st.exact),
            accepted=take(st.accepted), rejected=take(st.rejected),
            valid=take(st.valid) & ~take(st.rejected),
        )


def bucket_width(need: int, *, floor: int, current: int) -> int:
    """Next buffer width: power-of-two cover of ``need`` (the max survivor
    count over still-active queries), floored to keep selection/acceptance
    shapes sane, and never growing back above ``current``."""
    w = max(next_pow2(max(int(need), 1)), floor)
    return min(w, current)


def floor_width(cfg, n: int, *, B0: int = 0) -> int:
    """Smallest bucket width the shrink schedule may reach for an
    ``n``-wide frontier. ``cfg.frontier_floor`` (a ``repro.tune`` knob)
    overrides the derived default of max(racing batch, 2k, 32); either
    way the result is pow2-quantized and capped at ``n`` so the compile
    cache stays on the n → n/2 → … chain."""
    if not B0:
        B0 = min(cfg.batch_arms, n)
    base = cfg.frontier_floor if cfg.frontier_floor > 0 \
        else max(B0, 2 * cfg.k, 32)
    return min(n, bucket_width(base, floor=1, current=n))


def pow2_floor(m: int) -> int:
    """Largest power of two ≤ max(m, 1). The epoch drivers quantize the
    adaptive rounds-per-launch multiplier through this so T = R·P (a
    static jit arg of the fused step) takes values only on a ~log-sized
    chain — one warm race precompiles every specialization mid-traffic
    requests can reach (guarded by the repro_xla_compiles_total test)."""
    return 1 << (max(int(m), 1).bit_length() - 1)
