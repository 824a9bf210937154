"""Cross-query batched racing (DESIGN.md §3.2/§4) — the index-serving
drivers that replace per-query ``jax.lax.map`` over ``core.ucb.race_topk``.

Two drivers share this module:

``batched_race_topk`` (PR-1, DESIGN.md §3.2) races one ``(Q, B)`` arm
frontier with one ``block_pull_multi`` launch *per round*: wall-clock is the
MAX of per-query rounds instead of the SUM, but every round still pays one
launch plus O(Q·n) bookkeeping (CI radii, top-k selection, acceptance) even
late in the race when nearly every arm is rejected.

The *epoch-fused* driver (``fused_race_topk`` + ``index/frontier.py``,
DESIGN.md §4) restructures that loop into a two-level epoch loop: the inner
R pull-rounds are fused into one ``kernels/ops.fused_epoch_pull`` launch
(on-chip Welford, double-buffered corpus DMA), acceptance runs only at epoch
boundaries, and between epochs the still-candidate arms are gathered into
shrinking power-of-two buckets so bookkeeping scales with *survivors*
instead of n. It serves the dense/rotated boxes; the sparse box stays on the
per-round driver.

Correctness is the per-query algorithm's, unchanged: selection, Welford
updates, CI radii, and the Alg. 1 acceptance/rejection step
(``core.ucb.acceptance_step``) are applied per query via ``vmap``; the only
coupling across queries is the shared kernel launch. Warm-start priors from
the IndexStore enter through ``confidence.empirical_sigma_sq_prior`` —
variance estimates only, never CI sample counts.

Tombstoned (dead) slots enter the race pre-rejected (mutable.py): they are
never selected, never pulled, and can never be returned.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BMOConfig
from repro.core import confidence as conf
from repro.core.bmo_nn import KNNResult, sparse_exact_theta, sparse_pull_one
from repro.core.datasets import SparseDataset
from repro.core.ucb import (INF, acceptance_step, acceptance_step_masked,
                            topk_from_state, topk_from_state_masked)
from repro.obs import get_obs
from repro.utils.hostsync import host_fetch
from repro.index.frontier import (FrontierState, bucket_width,
                                  compact_frontier, floor_width, pow2_floor,
                                  survivors)
from repro.kernels import ops as kops


class BatchedRaceState(NamedTuple):
    mean: jax.Array        # (Q, n)
    count: jax.Array       # (Q, n)
    m2: jax.Array          # (Q, n)
    exact: jax.Array       # (Q, n) bool
    accepted: jax.Array    # (Q, n) bool
    rejected: jax.Array    # (Q, n) bool
    coord_ops: jax.Array   # (Q,)
    rounds: jax.Array      # (Q,) rounds spent while the query was active
    done: jax.Array        # (Q,) bool
    round_no: jax.Array    # () int32
    rng: jax.Array


class RoundsRaceFns(NamedTuple):
    """The per-round driver's pieces, exposed so callers can drive the race
    in bounded chunks (the anytime request plane, ``index/anytime.py``)
    instead of one run-to-certification ``while_loop``. All members are
    trace-compatible closures over the box's pull/exact functions."""
    init: Callable        # rng -> BatchedRaceState
    body: Callable        # state -> state (one racing round)
    active: Callable      # state -> bool (queries left AND round cap unhit)
    ci_radius: Callable   # state -> (Q, n) CI half-widths
    exact_fn: Callable    # (sel (Q, B)) -> (Q, B) exact θ
    exact_cost: jax.Array  # (Q, n) coordinate-op cost of an exact eval
    max_rounds: int


def make_rounds_race(
    pull_fn: Callable,          # (sel (Q, B), rng) -> (Q, B, P) samples
    exact_fn: Callable,         # (sel (Q, B)) -> (Q, B) exact θ
    n: int,
    Q: int,
    max_pulls,                  # scalar, (n,) or (Q, n)
    pull_cost: float,
    exact_cost,                 # scalar, (n,) or (Q, n)
    cfg: BMOConfig,
    *,
    eliminate: bool = True,
    dead: Optional[jax.Array] = None,       # (n,) bool tombstones
    prior_var: Optional[jax.Array] = None,  # (n,) warm-start variance prior
    prior_weight: float = 0.0,
    max_pulls_static: int = 0,
) -> RoundsRaceFns:
    k = cfg.k
    B = min(cfg.batch_arms, n)
    P = cfg.pulls_per_round
    max_pulls_arr = jnp.broadcast_to(
        jnp.asarray(max_pulls, jnp.float32), (Q, n))
    exact_cost_arr = jnp.broadcast_to(
        jnp.asarray(exact_cost, jnp.float32), (Q, n))
    max_pulls_hi = max_pulls_static or int(np.max(np.asarray(max_pulls)))
    log_term = float(np.log(2.0 / conf.delta_prime(cfg.delta, n, max_pulls_hi)))
    max_rounds = cfg.max_rounds or int(
        2 * math.ceil(n * max_pulls_hi / max(B * P, 1)) + n + 16)

    alive = jnp.ones((n,), bool) if dead is None else ~dead
    alive_f = alive.astype(jnp.float32)
    n_alive = jnp.sum(alive_f)
    if prior_var is None:
        prior_var = jnp.zeros((n,), jnp.float32)
        prior_weight = 0.0
    # priors may be per-arm (n,) — the build-time block statistics — or
    # per-query (Q, n) when the caller seeds them (near-repeat warm starts)
    prior2 = (jnp.broadcast_to(prior_var[None], (Q, n))
              if prior_var.ndim == 1 else prior_var)
    prior_pool = jnp.sum(prior2 * alive_f[None], 1) / jnp.maximum(n_alive, 1.0)
    qi = jnp.arange(Q)[:, None]

    def ci_radius(st: BatchedRaceState) -> jax.Array:
        if cfg.sigma is not None:
            sig_sq = jnp.full((Q, n), float(cfg.sigma) ** 2, jnp.float32)
        else:
            # per-query pooled variance, warm-started by the build-time prior
            num = jnp.sum(st.m2 * alive_f, 1) + prior_weight * prior_pool
            den = (jnp.sum(jnp.maximum(st.count - 1.0, 0.0) * alive_f, 1)
                   + prior_weight)
            global_var = num / jnp.maximum(den, 1.0)         # (Q,)
            sig_sq = conf.empirical_sigma_sq_prior(
                st.m2, st.count, 1e-12, global_var[:, None],
                prior2, prior_weight)
        c = conf.hoeffding_radius(sig_sq, st.count, log_term)
        return jnp.where(st.exact, 0.0, c)

    def init_state(rng):
        # wide init (paper App. D-A): every alive arm of every query gets
        # init_pulls samples, as reps of ONE (Q, n, P) launch
        n_init = max(cfg.init_pulls, 2)
        reps = max(1, n_init // P)
        mean = jnp.zeros((Q, n), jnp.float32)
        count = jnp.zeros((Q, n), jnp.float32)
        m2 = jnp.zeros((Q, n), jnp.float32)
        all_arms = jnp.broadcast_to(jnp.arange(n)[None], (Q, n))
        mask = jnp.broadcast_to(alive_f[None], (Q, n)).reshape(-1)

        def rep_body(carry, _):
            mean, count, m2, rng = carry
            rng, sub = jax.random.split(rng)
            vals = pull_fn(all_arms, sub)                    # (Q, n, P)
            nm, nc, n2 = conf.welford_batch_update(
                mean.reshape(-1), count.reshape(-1), m2.reshape(-1),
                vals.reshape(Q * n, P), mask)
            return (nm.reshape(Q, n), nc.reshape(Q, n), n2.reshape(Q, n),
                    rng), None

        (mean, count, m2, rng), _ = jax.lax.scan(
            rep_body, (mean, count, m2, rng), None, length=reps)
        return BatchedRaceState(
            mean=mean, count=count, m2=m2,
            exact=jnp.zeros((Q, n), bool),
            accepted=jnp.zeros((Q, n), bool),
            rejected=jnp.broadcast_to(~alive[None], (Q, n)),
            coord_ops=jnp.full((Q,), float(reps * P * pull_cost)) * n_alive,
            rounds=jnp.zeros((Q,), jnp.int32),
            done=jnp.zeros((Q,), bool),
            round_no=jnp.zeros((), jnp.int32),
            rng=rng,
        )

    def cond(st: BatchedRaceState):
        return (~jnp.all(st.done)) & (st.round_no < max_rounds)

    def body(st: BatchedRaceState):
        ci = ci_radius(st)
        lcb = st.mean - ci
        candidate = ~st.accepted & ~st.rejected
        need = candidate & ~st.exact & ~st.done[:, None]

        # ---- selection: per query, B lowest-LCB candidates ---------------
        sel_score = jnp.where(need, lcb, INF)
        _, sel = jax.lax.top_k(-sel_score, B)                # (Q, B)
        sel_valid = jnp.take_along_axis(need, sel, axis=1)   # (Q, B)

        rng, sub = jax.random.split(st.rng)
        vals = pull_fn(sel, sub)                             # (Q, B, P)
        cm, cc, c2 = st.mean[qi, sel], st.count[qi, sel], st.m2[qi, sel]
        nm, nc, n2 = conf.welford_batch_update(
            cm.reshape(-1), cc.reshape(-1), c2.reshape(-1),
            vals.reshape(Q * B, P), sel_valid.reshape(-1).astype(jnp.float32))
        mean = st.mean.at[qi, sel].set(nm.reshape(Q, B))
        count = st.count.at[qi, sel].set(nc.reshape(Q, B))
        m2 = st.m2.at[qi, sel].set(n2.reshape(Q, B))
        coord_ops = st.coord_ops + jnp.sum(sel_valid, 1) * P * pull_cost

        # ---- lazy exact evaluation for arms that crossed MAX_PULLS -------
        crossed = ((count[qi, sel] >= max_pulls_arr[qi, sel])
                   & sel_valid & ~st.exact[qi, sel])
        exact_vals = jax.lax.cond(
            jnp.any(crossed),
            lambda s: exact_fn(s),
            lambda s: jnp.zeros((Q, B), jnp.float32),
            sel)
        mean = mean.at[qi, sel].set(
            jnp.where(crossed, exact_vals, mean[qi, sel]))
        exact = st.exact.at[qi, sel].set(st.exact[qi, sel] | crossed)
        coord_ops = coord_ops + jnp.sum(crossed * exact_cost_arr[qi, sel], 1)

        st2 = st._replace(mean=mean, count=count, m2=m2, exact=exact,
                          coord_ops=coord_ops, rng=rng)

        # ---- per-query acceptance / rejection (shared Alg. 1 step) -------
        ci2 = ci_radius(st2)
        accept_new, rejected = jax.vmap(
            lambda m, c, e, a, r: acceptance_step(
                m, c, e, a, r, k, epsilon=cfg.epsilon, eliminate=eliminate)
        )(st2.mean, ci2, st2.exact, st2.accepted, st2.rejected)
        accepted = st2.accepted | accept_new
        # freeze finished queries
        frozen = st.done[:, None]
        accepted = jnp.where(frozen, st.accepted, accepted)
        rejected = jnp.where(frozen, st.rejected, rejected)

        # a query is finished when it has its k certified arms — or when no
        # candidate is left at all, which a full-corpus race can only reach
        # *after* k acceptances (elimination keeps ≥ k arms non-rejected) but
        # a sharded shard-local race with fewer than k live slots reaches
        # with every live arm certified (sharded.py races such shards for
        # their entire live set; the cross-shard merge tops it back up).
        no_candidates = jnp.sum(~accepted & ~rejected, 1) == 0
        done = st.done | (jnp.sum(accepted, 1) >= k) | no_candidates
        rounds = jnp.where(st.done, st.rounds, st.rounds + 1)
        return st2._replace(accepted=accepted, rejected=rejected,
                            rounds=rounds, done=done,
                            round_no=st.round_no + 1)

    return RoundsRaceFns(init=init_state, body=body, active=cond,
                         ci_radius=ci_radius, exact_fn=exact_fn,
                         exact_cost=exact_cost_arr, max_rounds=max_rounds)


def run_to_certification(fns: RoundsRaceFns, rng: jax.Array,
                         k: int) -> KNNResult:
    """Drive a rounds race to completion in one ``while_loop`` — the
    blocking twin of the chunked sessions in ``index/anytime.py``."""
    st = fns.init(rng)
    st = jax.lax.while_loop(fns.active, fns.body, st)
    ci = fns.ci_radius(st)
    topk, topk_vals = jax.vmap(
        lambda m, c, a, r: topk_from_state(m, c, a, r, k)
    )(st.mean, ci, st.accepted, st.rejected)
    return KNNResult(indices=topk, values=topk_vals, coord_ops=st.coord_ops,
                     rounds=st.rounds, n_exact=jnp.sum(st.exact, 1))


def batched_race_topk(
    pull_fn: Callable,          # (sel (Q, B), rng) -> (Q, B, P) samples
    exact_fn: Callable,         # (sel (Q, B)) -> (Q, B) exact θ
    n: int,
    Q: int,
    max_pulls,                  # scalar, (n,) or (Q, n)
    pull_cost: float,
    exact_cost,                 # scalar, (n,) or (Q, n)
    cfg: BMOConfig,
    rng: jax.Array,
    *,
    eliminate: bool = True,
    dead: Optional[jax.Array] = None,       # (n,) bool tombstones
    prior_var: Optional[jax.Array] = None,  # (n,) warm-start variance prior
    prior_weight: float = 0.0,
    max_pulls_static: int = 0,
) -> KNNResult:
    fns = make_rounds_race(
        pull_fn, exact_fn, n, Q, max_pulls, pull_cost, exact_cost, cfg,
        eliminate=eliminate, dead=dead, prior_var=prior_var,
        prior_weight=prior_weight, max_pulls_static=max_pulls_static)
    return run_to_certification(fns, rng, cfg.k)


# ---------------------------------------------------------------------------
# Epoch-fused driver (DESIGN.md §4): R rounds per launch, survivor-compacted
# bookkeeping. Dense/rotated boxes only — the pulls are corpus-block reads.
# ---------------------------------------------------------------------------


def draw_blocks(key: jax.Array, shape, nb: int) -> jax.Array:
    """Uniform block indices in [0, nb) of ``shape``. Drawn flat and then
    reshaped (the same values as a shaped draw): the TPU lays a shaped
    draw out with its short trailing pull axis padded to 128 lanes, which
    at the wide init's (Q, capacity, T0) is gigabytes of HBM."""
    return jax.random.randint(key, (math.prod(shape),), 0, nb).reshape(shape)


def _dense_exact_theta(x, qs, sel, metric: str):
    """Exact θ for selected slots (the Alg. 1 lazy exact evaluation both
    dense drivers share). sel (Q, B) → (Q, B). The distance is divided by
    the store's padded width d_pad, the mean a block pull estimates: the
    Hadamard rotation spreads a row over all d_pad coordinates, so dividing
    by the unpadded d would put exact arms on a scale d_pad/d above the
    sampled ones and misorder them."""
    rows = x[sel]                                            # (Q, B, d_pad)
    diff = rows - qs[:, None, :]
    if metric == "l1":
        dist = jnp.sum(jnp.abs(diff), -1)
    else:
        dist = jnp.sum(diff * diff, -1)
    return dist / x.shape[-1]


def _frontier_ci(st: FrontierState, cfg: BMOConfig, log_term: float,
                 prior_pool, prior_weight: float) -> jax.Array:
    """Masked CI radii over the compacted frontier. The variance pool is
    taken over *survivors* (not all alive arms as in the PR-1 driver) so the
    radii — and therefore every accept/reject decision — are invariant under
    frontier compaction, which only ever removes rejected entries."""
    Q, W = st.mean.shape
    if cfg.sigma is not None:
        sig_sq = jnp.full((Q, W), float(cfg.sigma) ** 2, jnp.float32)
    else:
        pool_f = survivors(st).astype(jnp.float32)
        num = jnp.sum(st.m2 * pool_f, 1) + prior_weight * prior_pool
        den = (jnp.sum(jnp.maximum(st.count - 1.0, 0.0) * pool_f, 1)
               + prior_weight)
        global_var = num / jnp.maximum(den, 1.0)              # (Q,)
        sig_sq = conf.empirical_sigma_sq_prior(
            st.m2, st.count, 1e-12, global_var[:, None], st.prior,
            prior_weight)
    c = conf.hoeffding_radius_masked(sig_sq, st.count, log_term, st.valid)
    return jnp.where(st.exact, 0.0, c)


@functools.partial(jax.jit, static_argnames=("cfg", "block", "impl",
                                             "prior_weight"))
def _fused_init(x, qs, alive, prior_var, rng, *, cfg: BMOConfig, block: int,
                impl: str, prior_weight: float):
    """Full-width frontier after the paper's wide init: every alive arm of
    every query gets ``init_pulls`` samples from ONE fused launch. Returns
    (state, prior_pool) — the pool term is frozen here so it stays invariant
    across compactions."""
    n = x.shape[0]
    Q = qs.shape[0]
    nb = x.shape[1] // block
    P = cfg.pulls_per_round
    T0 = max(1, max(cfg.init_pulls, 2) // P) * P

    alive_f = alive.astype(jnp.float32)
    n_alive = jnp.sum(alive_f)
    # (n,) build-time priors or (Q, n) per-query seeded priors (near-repeat
    # warm starts) — the pool term is per query either way
    prior2 = (jnp.broadcast_to(prior_var[None], (Q, n))
              if prior_var.ndim == 1 else prior_var)
    prior_pool = jnp.sum(prior2 * alive_f[None], 1) / jnp.maximum(n_alive, 1.0)

    rng, sub = jax.random.split(rng)
    all_arms = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (Q, n))
    blk = draw_blocks(sub, (Q, n, T0), nb)
    with jax.named_scope("repro.fused_init_pull"):
        stats = kops.fused_epoch_pull(x, qs, all_arms, blk, block=block,
                                      metric=cfg.metric, impl=impl,
                                      n_buf=cfg.kernel_buffers)
    zeros = jnp.zeros((Q, n), jnp.float32)
    mask = jnp.broadcast_to(alive_f[None], (Q, n))
    mean, count, m2 = conf.welford_merge(
        zeros, zeros, zeros, stats[..., 0], float(T0), stats[..., 1], mask)
    st = FrontierState(
        ids=all_arms,
        mean=mean, count=count, m2=m2,
        prior=prior2,
        exact=jnp.zeros((Q, n), bool),
        accepted=jnp.zeros((Q, n), bool),
        rejected=jnp.broadcast_to(~alive[None], (Q, n)),
        valid=jnp.broadcast_to(alive[None], (Q, n)),
        coord_ops=jnp.full((Q,), float(T0 * block)) * n_alive,
        n_exact=jnp.zeros((Q,), jnp.int32),
        rounds=jnp.zeros((Q,), jnp.int32),
        done=jnp.zeros((Q,), bool),
        rng=rng,
    )
    return st, prior_pool


@functools.partial(jax.jit, static_argnames=(
    "cfg", "block", "d", "impl", "eliminate", "prior_weight", "log_term",
    "T"))
def _fused_epoch_step(x, qs, st: FrontierState, prior_pool, *,
                      cfg: BMOConfig, block: int, d: int, impl: str,
                      eliminate: bool, prior_weight: float, log_term: float,
                      T: int):
    """One epoch: select B lowest-LCB candidates per query, pull each T
    times in one fused launch, merge the on-chip Welford stats, lazily
    exact-evaluate arms that crossed MAX_PULLS, then run acceptance ONCE.
    Everything is O(Q·W) with W the current bucket width."""
    Q, W = st.mean.shape
    k = cfg.k
    B = min(cfg.batch_arms, W)
    nb = x.shape[1] // block
    max_pulls = float(nb)
    qi = jnp.arange(Q)[:, None]

    ci = _frontier_ci(st, cfg, log_term, prior_pool, prior_weight)
    need = (st.valid & ~st.accepted & ~st.rejected & ~st.exact
            & ~st.done[:, None])

    # ---- selection: per query, B lowest-LCB candidates -------------------
    sel_score = jnp.where(need, st.mean - ci, INF)
    _, sel = jax.lax.top_k(-sel_score, B)                    # (Q, B) positions
    sel_valid = jnp.take_along_axis(need, sel, axis=1)
    slot = jnp.take_along_axis(st.ids, sel, axis=1)
    slot_safe = jnp.where(sel_valid, slot, 0)

    # ---- one fused launch: T pulls per selected arm, reduced on-chip -----
    rng, sub = jax.random.split(st.rng)
    blk = draw_blocks(sub, (Q, B, T), nb)
    with jax.named_scope("repro.fused_epoch_pull"):
        stats = kops.fused_epoch_pull(x, qs, slot_safe, blk, block=block,
                                      metric=cfg.metric, impl=impl,
                                      n_buf=cfg.kernel_buffers)
    cm = jnp.take_along_axis(st.mean, sel, axis=1)
    cc = jnp.take_along_axis(st.count, sel, axis=1)
    c2 = jnp.take_along_axis(st.m2, sel, axis=1)
    nm, nc, n2 = conf.welford_merge(
        cm, cc, c2, stats[..., 0], float(T), stats[..., 1],
        sel_valid.astype(jnp.float32))
    coord_ops = st.coord_ops + jnp.sum(sel_valid, 1) * float(T * block)

    # ---- lazy exact evaluation for arms that crossed MAX_PULLS -----------
    crossed = ((nc >= max_pulls) & sel_valid
               & ~jnp.take_along_axis(st.exact, sel, axis=1))
    exact_vals = jax.lax.cond(
        jnp.any(crossed),
        lambda s: _dense_exact_theta(x, qs, s, cfg.metric),
        lambda s: jnp.zeros((Q, B), jnp.float32), slot_safe)
    nm = jnp.where(crossed, exact_vals, nm)
    mean = st.mean.at[qi, sel].set(nm)
    count = st.count.at[qi, sel].set(nc)
    m2 = st.m2.at[qi, sel].set(n2)
    exact = st.exact.at[qi, sel].set(
        jnp.take_along_axis(st.exact, sel, axis=1) | crossed)
    coord_ops = coord_ops + jnp.sum(crossed, 1) * float(d)

    st2 = st._replace(mean=mean, count=count, m2=m2, exact=exact,
                      coord_ops=coord_ops,
                      n_exact=st.n_exact + jnp.sum(crossed, 1, dtype=jnp.int32),
                      rng=rng)

    # ---- acceptance / rejection, ONCE per epoch --------------------------
    ci2 = _frontier_ci(st2, cfg, log_term, prior_pool, prior_weight)
    accept_new, rejected = jax.vmap(
        lambda m, c, e, a, r, v: acceptance_step_masked(
            m, c, e, a, r, v, k, epsilon=cfg.epsilon, eliminate=eliminate)
    )(st2.mean, ci2, st2.exact, st2.accepted, st2.rejected, st2.valid)
    accepted = st2.accepted | accept_new
    frozen = st.done[:, None]
    accepted = jnp.where(frozen, st.accepted, accepted)
    rejected = jnp.where(frozen, st.rejected, rejected)

    # done at k certified arms — or at candidate exhaustion, reachable only
    # in shard-local races over fewer than k live slots (see the per-round
    # driver's note; full-corpus races certify k first)
    no_candidates = jnp.sum(st2.valid & ~accepted & ~rejected, 1) == 0
    done = st.done | (jnp.sum(accepted, 1) >= k) | no_candidates
    # a finished query owes its unresolved candidates nothing: retire them
    # so its survivor set is exactly its k accepted arms — without this a
    # done query could freeze a large candidate set and either pin the
    # bucket width or (worse) have compaction truncate it, breaking the
    # compaction-invariance guarantee.
    rejected = jnp.where(done[:, None], rejected | ~accepted, rejected)
    R = max(1, T // cfg.pulls_per_round)
    rounds = jnp.where(st.done, st.rounds, st.rounds + R)
    st2 = st2._replace(accepted=accepted, rejected=rejected,
                       rounds=rounds, done=done)
    n_surv = jnp.sum((st2.valid & ~st2.rejected & ~st2.done[:, None]), 1)
    return st2, n_surv, done


@functools.partial(jax.jit, static_argnames=("cfg", "log_term",
                                             "prior_weight"))
def _fused_finalize(st: FrontierState, prior_pool, *, cfg: BMOConfig,
                    log_term: float, prior_weight: float):
    ci = _frontier_ci(st, cfg, log_term, prior_pool, prior_weight)
    topk, topk_vals = jax.vmap(
        lambda m, c, a, r, v, i: topk_from_state_masked(
            m, c, a, r, v, i, cfg.k)
    )(st.mean, ci, st.accepted, st.rejected, st.valid, st.ids)
    return topk, topk_vals, st.n_exact


def fused_race_topk(x, qs, alive, prior_var, rng, *, cfg: BMOConfig,
                    block: int, d: int, impl: str, eliminate: bool,
                    prior_weight: float, compaction: bool = True,
                    _return_state: bool = False):
    """Epoch-fused, survivor-compacted dense/rotated race (DESIGN.md §4).

    Two-level loop: the *host* iterates epochs (re-jitted per bucket width —
    a bounded, ~log₂ n-sized specialization cache), each epoch running R
    fused pull-rounds in one kernel launch and one acceptance pass. Pulls
    per epoch are reallocated adaptively: as the frontier shrinks by c×, R
    scales up by c× (capped at MAX_PULLS worth), so stragglers drain in a
    handful of launches instead of hundreds of rounds.

    ``compaction=False`` keeps the full-width buffers (used by the
    invariance tests — decisions must match exactly).
    ``_return_state`` additionally returns the final FrontierState.
    """
    n = x.shape[0]
    Q = qs.shape[0]
    k = cfg.k
    P = cfg.pulls_per_round
    nb = x.shape[1] // block
    B0 = min(cfg.batch_arms, n)
    # host-sync: python-float math on cfg.delta, no device value
    log_term = float(np.log(2.0 / conf.delta_prime(cfg.delta, n, nb)))
    max_rounds = cfg.max_rounds or int(
        2 * math.ceil(n * nb / max(B0 * P, 1)) + n + 16)
    R0 = max(cfg.epoch_rounds, 1)
    R_cap = max(1, -(-nb // P))          # one epoch never overshoots exact
    floor_w = floor_width(cfg, n, B0=B0)

    st, prior_pool = _fused_init(x, qs, alive, prior_var, rng, cfg=cfg,
                                 block=block, impl=impl,
                                 prior_weight=prior_weight)
    W0 = st.width
    rounds_spent = 0
    n_surv = np.full((Q,), n)
    done = np.zeros((Q,), bool)
    obs = get_obs()
    prev_coord = float(np.sum(host_fetch(st.coord_ops)))
    epoch_ms = obs.registry.histogram(
        "repro_race_epoch_ms", "wall time of one race epoch (ms)",
        kind="fused_blocking")
    coord_total = obs.registry.counter(
        "repro_race_coord_ops_total", "coordinate reads paid by race epochs",
        kind="fused_blocking")
    while not done.all() and rounds_spent < max_rounds:
        # adaptive reallocation (Neufeld et al. style): as the candidate
        # frontier shrinks by c×, fuse c× more rounds into the next launch —
        # the same pull budget per epoch, concentrated on the survivors.
        # Keyed off the *survivor count*, not the buffer width, so the pull
        # schedule is identical with compaction on or off (tested).
        need = int(n_surv[~done].max(initial=1))
        if compaction:
            W_new = bucket_width(need, floor=floor_w, current=st.width)
            if W_new < st.width:
                st = compact_frontier(st, W_new=W_new)
        R = min(R0 * pow2_floor(W0 // max(need, 1)), R_cap)
        t0 = time.perf_counter()
        with obs.tracer.annotate("race.epoch.fused_blocking"):
            with obs.tracer.span("race.launch"):
                st, n_surv_d, done_d = _fused_epoch_step(
                    x, qs, st, prior_pool, cfg=cfg, block=block, d=d,
                    impl=impl, eliminate=eliminate,
                    prior_weight=prior_weight, log_term=log_term, T=R * P)
            rounds_spent += R
            # the per-epoch boundary: survivor count, done flags and the
            # coordinates paid cross to host in one fetch, to drive the
            # Python reallocation loop and the epoch's counters
            with obs.tracer.span("race.sync"):
                n_surv, done, coord_ops = host_fetch(
                    (n_surv_d, done_d, st.coord_ops))
        epoch_ms.observe((time.perf_counter() - t0) * 1e3)
        coord = float(np.sum(coord_ops))  # host-sync: fetched above
        coord_total.inc(max(coord - prev_coord, 0.0))
        prev_coord = coord

    topk, topk_vals, n_exact = _fused_finalize(
        st, prior_pool, cfg=cfg, log_term=log_term, prior_weight=prior_weight)
    res = KNNResult(indices=topk, values=topk_vals, coord_ops=st.coord_ops,
                    rounds=st.rounds, n_exact=n_exact)
    if _return_state:
        return res, st
    return res


# ---------------------------------------------------------------------------
# IndexStore front-ends
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "block", "d", "impl",
                                             "eliminate", "prior_weight"))
def _dense_index_knn(x, qs, alive, prior_var, rng, *, cfg: BMOConfig,
                     block: int, d: int, impl: str, eliminate: bool,
                     prior_weight: float) -> KNNResult:
    n, d_pad = x.shape
    Q = qs.shape[0]
    nb = d_pad // block

    def pull(sel, key):
        blk = draw_blocks(key, sel.shape + (cfg.pulls_per_round,), nb)
        with jax.named_scope("repro.block_pull_multi"):
            return kops.block_pull_multi(x, qs, sel, blk, block=block,
                                         metric=cfg.metric, impl=impl)

    def exact(sel):
        return _dense_exact_theta(x, qs, sel, cfg.metric)

    return batched_race_topk(
        pull, exact, n=n, Q=Q,
        max_pulls=float(d_pad // block),
        pull_cost=float(block),
        exact_cost=float(d),
        cfg=cfg, rng=rng, eliminate=eliminate,
        dead=~alive, prior_var=prior_var, prior_weight=prior_weight,
    )


def make_sparse_rounds_race(indices, values, nnz, alive, prior_var,
                            q_idx, q_val, q_nnz, *, cfg: BMOConfig, d: int,
                            eliminate: bool, prior_weight: float
                            ) -> RoundsRaceFns:
    """Assemble the §IV-A sparse box's per-round race pieces (shared by the
    blocking driver below and the resumable sessions in index/anytime.py)."""
    n, m = indices.shape
    Q, mq = q_idx.shape
    ds = SparseDataset(indices=indices, values=values, nnz=nnz, d=d)
    P = cfg.pulls_per_round

    def pull(sel, key):
        B = sel.shape[1]
        keys = jax.random.split(key, Q * B * P).reshape(Q, B, P, 2)
        per_pull = lambda qi_, qv_, qn_, a, kk: sparse_pull_one(
            ds, qi_, qv_, qn_, a, kk)
        over_p = jax.vmap(per_pull, in_axes=(None, None, None, None, 0))
        over_b = jax.vmap(over_p, in_axes=(None, None, None, 0, 0))
        over_q = jax.vmap(over_b, in_axes=(0, 0, 0, 0, 0))
        return over_q(q_idx, q_val, q_nnz, sel, keys).astype(jnp.float32)

    def exact(sel):
        return jax.vmap(lambda qi_, qv_, s: sparse_exact_theta(ds, qi_, qv_, s))(
            q_idx, q_val, sel)

    exact_cost = (nnz[None, :] + q_nnz[:, None]).astype(jnp.float32)  # (Q, n)
    max_pulls = jnp.maximum(exact_cost, 8.0)
    return make_rounds_race(
        pull, exact, n=n, Q=Q,
        max_pulls=max_pulls, pull_cost=1.0, exact_cost=exact_cost,
        cfg=cfg, eliminate=eliminate,
        dead=~alive, prior_var=prior_var, prior_weight=prior_weight,
        max_pulls_static=int(m + mq),
    )


@functools.partial(jax.jit, static_argnames=("cfg", "d", "eliminate",
                                             "prior_weight"))
def _sparse_index_knn(indices, values, nnz, alive, prior_var,
                      q_idx, q_val, q_nnz, rng, *, cfg: BMOConfig, d: int,
                      eliminate: bool, prior_weight: float) -> KNNResult:
    fns = make_sparse_rounds_race(
        indices, values, nnz, alive, prior_var, q_idx, q_val, q_nnz,
        cfg=cfg, d=d, eliminate=eliminate, prior_weight=prior_weight)
    return run_to_certification(fns, rng, cfg.k)


def index_knn(store, queries, rng: jax.Array, *, k=None, impl: str = "auto",
              eliminate: bool = True, warm_start: bool = True,
              mode: str = "auto", prior_hint=None) -> KNNResult:
    """Batched k-NN against an IndexStore (slot indices; tombstones are
    excluded). Drop-in for ``bmo_nn.knn`` on the serving path — same
    KNNResult fields, one batched race instead of Q sequential ones.

    ``mode``: "fused" — the epoch-fused, survivor-compacted driver
    (DESIGN.md §4; dense/rotated only); "rounds" — the PR-1 one-launch-per-
    round driver; "auto" — fused where available, rounds for sparse.

    ``prior_hint``: optional (Q, capacity) per-query CI variance priors
    replacing the store's build-time per-arm priors — the near-repeat
    warm-start path (serve/engine.py) seeds these from a cached neighbour's
    result. A ``ShardedIndexStore`` (DESIGN.md §5) dispatches to the
    mesh-spanning driver in ``index/sharded.py``.
    """
    if hasattr(store, "shards"):      # ShardedIndexStore — mesh present
        from repro.index.sharded import sharded_index_knn
        return sharded_index_knn(store, queries, rng, k=k, impl=impl,
                                 eliminate=eliminate, warm_start=warm_start,
                                 mode=mode, prior_hint=prior_hint)
    cfg = store.cfg if k is None else dataclasses.replace(store.cfg, k=k)
    n_live = store.n_live
    if cfg.k > n_live:
        raise ValueError(
            f"k={cfg.k} exceeds the index's {n_live} live slots — "
            "tombstoned slots can never be returned")
    if mode not in ("auto", "fused", "rounds"):
        raise ValueError(f"unknown mode {mode!r}")
    w = store.prior_weight if warm_start else 0.0
    prior = store.prior_var if prior_hint is None else jnp.asarray(
        prior_hint, jnp.float32)
    if prior_hint is not None:
        w = store.prior_weight        # a seeded prior implies warm start
    if store.kind == "sparse":
        if mode == "fused":
            raise ValueError("the fused epoch driver pulls corpus blocks — "
                             "sparse boxes race on the per-round driver")
        q_idx, q_val, q_nnz = queries
        return _sparse_index_knn(
            store.indices, store.values, store.nnz, store.alive,
            prior, q_idx, q_val, q_nnz, rng,
            cfg=cfg, d=store.d, eliminate=eliminate, prior_weight=w)
    qs = store.prepare_queries(queries)
    if mode == "rounds":
        return _dense_index_knn(
            store.x, qs, store.alive, prior, rng,
            cfg=cfg, block=store.block, d=store.d, impl=impl,
            eliminate=eliminate, prior_weight=w)
    return fused_race_topk(
        store.x, qs, store.alive, prior, rng,
        cfg=cfg, block=store.block, d=store.d, impl=impl,
        eliminate=eliminate, prior_weight=w)
