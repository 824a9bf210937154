"""Epoch-granular resumable races — the anytime engine under the request
plane (DESIGN.md §7.1).

The bandit race is naturally an *anytime* algorithm: at every epoch boundary
each query holds a partial top-k with per-arm confidence intervals. The
blocking drivers (``batched_race.py``, ``sharded.py``) run that loop to full
certification inside one call; this module re-exposes the SAME loop as a
``RaceSession`` the scheduler can drive one epoch at a time:

    sess = make_session(store, queries, rng, cfg=cfg)
    while sess.step():
        partial = sess.snapshot          # host-side anytime view
        ...                              # serve it, check deadlines, retire

Correctness of the partial view (the *certified-prefix* contract, tested):

  * After every epoch the ≤ k **accepted** arms of each query are lazily
    exact-evaluated in place (mean ← exact θ, CI ← 0; Welford pool stats
    untouched so the survivor-pooled CI variance is unchanged). Accepted
    arms are never pulled again, so this is a one-time O(k·d) cost per
    query, the same O(d) term the paper's bound already pays — and the
    sharded merge already required it (DESIGN.md §5.3).
  * ``snapshot.acc_count`` leading entries are accepted arms sorted by
    exact θ. An entry is *order-certified* at position i iff its exact θ is
    below the minimum LCB over every remaining candidate
    (``snapshot.cand_lcb_min``): w.h.p. 1 − δ no candidate — and hence no
    later-accepted arm — can end below it, so the certified prefix of any
    partial answer equals the full-certification answer's prefix.
  * A ``done`` query's accepted set IS its certificate (the acceptance rule
    already beat every candidate), so its ``cand_lcb_min`` is +inf and the
    whole prefix certifies.

Sessions exist for all four store boxes: single-shard dense/rotated (the
epoch-fused frontier driver), single-shard sparse (the per-round driver in
bounded-round chunks), and their sharded twins (shard-local state stepped
under ``shard_map``, merged on host per snapshot).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import BMOConfig
from repro.obs import get_obs, new_trace_id
from repro.core import confidence as conf
from repro.core.ucb import INF
from repro.utils.hostsync import host_fetch
from repro.index.batched_race import (BatchedRaceState, RoundsRaceFns,
                                      _dense_exact_theta, _frontier_ci,
                                      _fused_epoch_step, _fused_init,
                                      make_sparse_rounds_race)
from repro.index.frontier import (FrontierState, bucket_width,
                                  compact_frontier, floor_width, pow2_floor)
from repro.index.sharded import (AXIS, _ST_SPEC, ShardedIndexStore,
                                 _compact_stacked, _fused_init_fn,
                                 _fused_step_fn, _shard_delta, _squeeze,
                                 _unsqueeze)

_BIG = 1e9


class RaceSummary(NamedTuple):
    """Device-side anytime view of one race batch, refreshed per epoch."""
    ids: jax.Array          # (Q, k) slot ids, accepted-first then best cands
    values: jax.Array       # (Q, k) exact θ for accepted, estimates after
    ci: jax.Array           # (Q, k) CI half-widths (0 where exact)
    acc_count: jax.Array    # (Q,) leading accepted (certification-ready)
    cand_lcb_min: jax.Array  # (Q,) min LCB over remaining candidates
    done: jax.Array         # (Q,) race finished (k certified / exhausted)
    coord_ops: jax.Array    # (Q,)
    rounds: jax.Array       # (Q,)
    n_exact: jax.Array      # (Q,)


class Partial(NamedTuple):
    """Host-side (numpy) RaceSummary — sharded sessions merge S of them."""
    ids: np.ndarray
    values: np.ndarray
    ci: np.ndarray
    acc_count: np.ndarray
    cand_lcb_min: np.ndarray
    done: np.ndarray
    coord_ops: np.ndarray
    rounds: np.ndarray
    n_exact: np.ndarray


def _to_host(summ: RaceSummary) -> Partial:
    # THE per-epoch device->host boundary: one deliberate fetch of the
    # whole summary; everything downstream is host-resident numpy. The
    # fused session's epoch hands it arrays its one packed fetch already
    # brought over (``_unpack_epoch``), which pass through untouched.
    return Partial(*host_fetch(tuple(summ)))


#: host dtype of each ``RaceSummary`` field in the fused epoch's packed
#: buffer, then the survivor count (int32); ids, values and ci are (Q, k),
#: the rest (Q,): (Q, 3k + 7) int32 words in all
_PACKED = RaceSummary(ids=np.int32, values=np.float32, ci=np.float32,
                      acc_count=np.int32, cand_lcb_min=np.float32,
                      done=np.bool_, coord_ops=np.float32, rounds=np.int32,
                      n_exact=np.int32)


def _pack_epoch(summ: RaceSummary, n_surv) -> jax.Array:
    """On the device: the summary and the survivor count as one (Q, 3k + 7)
    int32 array, float32 fields bit-cast, so the host fetches them in one
    transfer."""
    cols = []
    for a, dt in zip((*summ, n_surv), (*_PACKED, np.int32)):
        a = a.astype(dt).reshape(a.shape[0], -1)
        cols.append(jax.lax.bitcast_convert_type(a, jnp.int32)
                    if dt == np.float32 else a.astype(jnp.int32))
    return jnp.concatenate(cols, axis=1)


def _unpack_epoch(buf: np.ndarray, k: int):
    """On the host: ``(RaceSummary of numpy arrays, n_surv)`` from a fetched
    ``_pack_epoch`` buffer, each array as ``_to_host`` would have fetched it
    on its own."""
    out, at = [], 0
    for i, dt in enumerate((*_PACKED, np.int32)):
        w = k if i < 3 else 1
        col = np.ascontiguousarray(buf[:, at:at + w])
        at += w
        col = col.view(np.float32) if dt == np.float32 else col.astype(dt)
        out.append(col if i < 3 else col[:, 0])
    return RaceSummary(*out[:-1]), out[-1]


def _summarize(ids, mean, ci, exact, accepted, rejected, valid, done,
               coord_ops, rounds, n_exact, k: int) -> RaceSummary:
    """Rank the race state into the anytime view: accepted arms first
    (ascending exact θ), then the best candidates by current estimate.
    Junk picks (a query with < k rankable entries) surface as +inf values
    so downstream merges drop them."""
    acc = accepted & valid
    cand = valid & ~accepted & ~rejected
    score = jnp.where(acc, mean - _BIG, jnp.where(cand, mean, INF))
    _, pos = jax.lax.top_k(-score, k)
    take = lambda a: jnp.take_along_axis(a, pos, axis=1)
    picked = take(score)
    out_vals = jnp.where(picked == INF, INF, take(mean))
    out_ci = jnp.where(take(exact) | (picked == INF), 0.0, take(ci))
    # the − BIG class offset exceeds f32 resolution, so accepted picks tie
    # on score and arrive in arbitrary order — re-sort them by exact θ
    # (stable, so the candidate tail keeps its ascending-estimate order)
    order = jnp.argsort(jnp.where(take(acc), out_vals, INF), axis=1)
    reorder = lambda a: jnp.take_along_axis(a, order, axis=1)
    pos = reorder(pos)
    out_vals, out_ci = reorder(out_vals), reorder(out_ci)
    take = lambda a: jnp.take_along_axis(a, pos, axis=1)
    cand_min = jnp.min(jnp.where(cand, mean - ci, INF), axis=1)
    return RaceSummary(
        ids=take(ids),
        values=out_vals,
        ci=out_ci,
        acc_count=jnp.minimum(jnp.sum(acc, 1), k).astype(jnp.int32),
        cand_lcb_min=jnp.where(done, INF, cand_min),
        done=done,
        coord_ops=coord_ops,
        rounds=rounds,
        n_exact=n_exact,
    )


def _exactify_frontier(x, qs, st: FrontierState, *, k: int, metric: str,
                       d: int) -> FrontierState:
    """Exact-evaluate the ≤ k accepted arms that still carry estimates.
    Means and the ``exact`` flag change; Welford count/m2 stay, so the
    survivor-pooled CI variance — and hence every pending accept/reject
    decision's radius — is untouched."""
    Q = st.mean.shape[0]
    qi = jnp.arange(Q)[:, None]
    acc = st.accepted & st.valid
    sel_score = jnp.where(acc & ~st.exact, st.mean, INF)
    with jax.named_scope("repro.exactify"):
        _, pos = jax.lax.top_k(-sel_score, k)
        need = jnp.take_along_axis(acc & ~st.exact, pos, axis=1)
        slots = jnp.where(need, jnp.take_along_axis(st.ids, pos, axis=1), 0)
        vals = jax.lax.cond(
            jnp.any(need),
            lambda s: _dense_exact_theta(x, qs, s, metric),
            lambda s: jnp.zeros(s.shape, jnp.float32), slots)
        cur = jnp.take_along_axis(st.mean, pos, axis=1)
        mean = st.mean.at[qi, pos].set(jnp.where(need, vals, cur))
        exact = st.exact.at[qi, pos].set(
            jnp.take_along_axis(st.exact, pos, axis=1) | need)
    return st._replace(
        mean=mean, exact=exact,
        coord_ops=st.coord_ops + jnp.sum(need, 1) * float(d),
        n_exact=st.n_exact + jnp.sum(need, 1, dtype=jnp.int32))


def _rounds_partial(fns: RoundsRaceFns, st: BatchedRaceState, k: int,
                    gid_base=0):
    """Exactify accepted arms of the per-round driver's state (via the
    box's own exact_fn, at its honest coordinate cost) and summarize."""
    Q, n = st.mean.shape
    qi = jnp.arange(Q)[:, None]
    acc = st.accepted
    sel_score = jnp.where(acc & ~st.exact, st.mean, INF)
    _, pos = jax.lax.top_k(-sel_score, k)
    need = jnp.take_along_axis(acc & ~st.exact, pos, axis=1)
    vals = jax.lax.cond(
        jnp.any(need), fns.exact_fn,
        lambda s: jnp.zeros(s.shape, jnp.float32), pos)
    cur = jnp.take_along_axis(st.mean, pos, axis=1)
    mean = st.mean.at[qi, pos].set(jnp.where(need, vals, cur))
    exact = st.exact.at[qi, pos].set(
        jnp.take_along_axis(st.exact, pos, axis=1) | need)
    coord_ops = st.coord_ops + jnp.sum(
        need * jnp.take_along_axis(fns.exact_cost, pos, axis=1), 1)
    st = st._replace(mean=mean, exact=exact, coord_ops=coord_ops)
    ci = fns.ci_radius(st)
    ids = gid_base + jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[None], (Q, n))
    valid = jnp.ones((Q, n), bool)
    summ = _summarize(ids, st.mean, ci, st.exact, st.accepted, st.rejected,
                      valid, st.done, st.coord_ops, st.rounds,
                      jnp.sum(st.exact, 1), k)
    return st, summ


# ---------------------------------------------------------------------------
# single-shard jitted entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "d", "log_term",
                                             "prior_weight"))
def _fused_partial(x, qs, st: FrontierState, prior_pool, *, cfg: BMOConfig,
                   d: int, log_term: float, prior_weight: float):
    st = _exactify_frontier(x, qs, st, k=cfg.k, metric=cfg.metric, d=d)
    ci = _frontier_ci(st, cfg, log_term, prior_pool, prior_weight)
    summ = _summarize(st.ids, st.mean, ci, st.exact, st.accepted,
                      st.rejected, st.valid, st.done, st.coord_ops,
                      st.rounds, st.n_exact, cfg.k)
    return st, summ


@functools.partial(jax.jit, static_argnames=(
    "step", "cfg", "block", "d", "impl", "eliminate", "prior_weight",
    "log_term", "T"))
def _fused_epoch_snapshot(x, qs, st: FrontierState, prior_pool, *, step,
                          cfg: BMOConfig, block: int, d: int, impl: str,
                          eliminate: bool, prior_weight: float,
                          log_term: float, T: int):
    """One epoch of the fused session in one launch: ``step`` (the epoch
    step, ``_fused_epoch_step``), then the snapshot's exactify and summary
    (``_fused_partial``) on the state it produced. Returns the exactified
    state, the summary and survivor count packed by ``_pack_epoch``, and
    the survivor count. ``step`` is static, so a launch traces whatever
    step the module holds when it launches."""
    st, n_surv, _ = step(x, qs, st, prior_pool, cfg=cfg, block=block, d=d,
                         impl=impl, eliminate=eliminate,
                         prior_weight=prior_weight, log_term=log_term, T=T)
    st, summ = _fused_partial(x, qs, st, prior_pool, cfg=cfg, d=d,
                              log_term=log_term, prior_weight=prior_weight)
    return st, _pack_epoch(summ, n_surv), n_surv


@functools.partial(jax.jit, static_argnames=("cfg", "d", "eliminate",
                                             "prior_weight"))
def _sparse_sess_init(indices, values, nnz, alive, prior, q_idx, q_val,
                      q_nnz, rng, *, cfg: BMOConfig, d: int, eliminate: bool,
                      prior_weight: float):
    fns = make_sparse_rounds_race(
        indices, values, nnz, alive, prior, q_idx, q_val, q_nnz, cfg=cfg,
        d=d, eliminate=eliminate, prior_weight=prior_weight)
    return _rounds_partial(fns, fns.init(rng), cfg.k)


@functools.partial(jax.jit, static_argnames=("cfg", "d", "eliminate",
                                             "prior_weight", "rounds"))
def _sparse_sess_chunk(indices, values, nnz, alive, prior, q_idx, q_val,
                       q_nnz, st: BatchedRaceState, *, cfg: BMOConfig,
                       d: int, eliminate: bool, prior_weight: float,
                       rounds: int):
    fns = make_sparse_rounds_race(
        indices, values, nnz, alive, prior, q_idx, q_val, q_nnz, cfg=cfg,
        d=d, eliminate=eliminate, prior_weight=prior_weight)
    limit = st.round_no + rounds
    st = jax.lax.while_loop(
        lambda s: fns.active(s) & (s.round_no < limit), fns.body, st)
    return _rounds_partial(fns, st, cfg.k)


def _force_done(st, mask):
    """Freeze rows (plane retire): drivers never pull / mutate done rows."""
    done = st.done
    mask = jnp.asarray(mask)
    if done.ndim == mask.ndim + 1:          # (S, Q) sharded-stacked state
        mask = mask[None]
    return st._replace(done=done | mask)


# ---------------------------------------------------------------------------
# sharded jitted entry points (shard-local bodies under shard_map)
# ---------------------------------------------------------------------------

_SUMM_SPEC = RaceSummary(*([P(AXIS)] * len(RaceSummary._fields)))
_BR_SPEC = BatchedRaceState(*([P(AXIS)] * len(BatchedRaceState._fields)))


@functools.lru_cache(maxsize=None)
def _sharded_fused_partial_fn(mesh, cfg, d, log_term, prior_weight, stride):
    def body(x, qs, st, pool):
        st = _squeeze(st)
        st = _exactify_frontier(x, qs, st, k=cfg.k, metric=cfg.metric,
                                d=d)
        ci = _frontier_ci(st, cfg, log_term, pool[0], prior_weight)
        gids = jax.lax.axis_index(AXIS) * stride + st.ids
        summ = _summarize(gids, st.mean, ci, st.exact, st.accepted,
                          st.rejected, st.valid, st.done, st.coord_ops,
                          st.rounds, st.n_exact, cfg.k)
        return (_unsqueeze(st),
                jax.tree_util.tree_map(lambda a: a[None], summ))

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(), _ST_SPEC, P(AXIS)),
        out_specs=(_ST_SPEC, _SUMM_SPEC), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_sparse_init_fn(mesh, cfg, d, eliminate, prior_weight, stride):
    def body(idx, val, nnz, alive, prior, qi, qv, qn, rng):
        fns = make_sparse_rounds_race(
            idx[0], val[0], nnz[0], alive[0], prior[0], qi, qv, qn, cfg=cfg,
            d=d, eliminate=eliminate, prior_weight=prior_weight)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(AXIS))
        st, summ = _rounds_partial(
            fns, fns.init(rng), cfg.k,
            gid_base=jax.lax.axis_index(AXIS) * stride)
        return (_unsqueeze(st),
                jax.tree_util.tree_map(lambda a: a[None], summ))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS),
                  P(), P(), P(), P()),
        out_specs=(_BR_SPEC, _SUMM_SPEC), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_sparse_chunk_fn(mesh, cfg, d, eliminate, prior_weight, stride,
                             rounds):
    def body(idx, val, nnz, alive, prior, qi, qv, qn, st):
        fns = make_sparse_rounds_race(
            idx[0], val[0], nnz[0], alive[0], prior[0], qi, qv, qn, cfg=cfg,
            d=d, eliminate=eliminate, prior_weight=prior_weight)
        st = _squeeze(st)
        limit = st.round_no + rounds
        st = jax.lax.while_loop(
            lambda s: fns.active(s) & (s.round_no < limit), fns.body, st)
        st, summ = _rounds_partial(
            fns, st, cfg.k, gid_base=jax.lax.axis_index(AXIS) * stride)
        return (_unsqueeze(st),
                jax.tree_util.tree_map(lambda a: a[None], summ))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS),
                  P(), P(), P(), _BR_SPEC),
        out_specs=(_BR_SPEC, _SUMM_SPEC), check_vma=False))


def _merge_shard_partials(p: Partial) -> Partial:
    """Merge S per-shard partial views into one global view (host-side;
    Q and k are serving-small). Accepted entries — already exact — are
    merged by (θ, gid); the best-effort tail interleaves the shards'
    candidate estimates."""
    S, Q, k = p.ids.shape
    ids = np.full((Q, k), -1, np.int64)
    vals = np.full((Q, k), np.inf, np.float32)
    ci = np.zeros((Q, k), np.float32)
    acc_count = np.zeros((Q,), np.int32)
    for q in range(Q):
        accepted, cands = [], []
        for s in range(S):
            a = int(p.acc_count[s, q])
            for i in range(k):
                # host-sync: p is the host-side per-shard Partial
                v = float(p.values[s, q, i])
                if not np.isfinite(v):
                    continue
                entry = (v, int(p.ids[s, q, i]),
                         float(p.ci[s, q, i]))  # host-sync: host Partial
                (accepted if i < a else cands).append(entry)
        accepted.sort(key=lambda e: (e[0], e[1]))
        cands.sort(key=lambda e: (e[0], e[1]))
        merged = (accepted + cands)[:k]
        for i, (v, g, c) in enumerate(merged):
            vals[q, i], ids[q, i], ci[q, i] = v, g, c
        acc_count[q] = min(len(accepted), k)
    return Partial(
        ids=ids, values=vals, ci=ci, acc_count=acc_count,
        cand_lcb_min=np.min(p.cand_lcb_min, axis=0),
        done=np.all(p.done, axis=0),
        coord_ops=np.sum(p.coord_ops, axis=0),
        rounds=np.max(p.rounds, axis=0),
        n_exact=np.sum(p.n_exact, axis=0),
    )


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------


class RaceSession:
    """One resumable race batch. ``step()`` advances one epoch and refreshes
    ``snapshot``; ``retire(mask)`` freezes rows whose ticket left the plane
    (deadline/budget) so the remaining rows get their pull budget.

    The base ``step()`` owns the epoch boundary: it times the concrete
    driver's ``_step_impl()``, then records — entirely host-side, from the
    snapshot arrays the drivers already transferred — the epoch's pull /
    coord-op deltas, frontier width, survivors, the CI radius of the worst
    uncertified position, the time blocked on the device (``wait_ms``, every
    fetch goes through ``_fetch``), the number of those fetches
    (``fetches``) and the rest (``host_ms``), and (sharded)
    the per-shard straggler split, as a ``race.epoch`` span under the
    session's ``sid`` trace id plus registry metrics (DESIGN.md §8.3). The
    session's start is a ``race.init`` span. Jitted code is untouched.
    """

    kind = "base"

    def __init__(self, Q: int, k: int, *, obs=None, sid: Optional[str] = None):
        self.Q = Q
        self.k = k
        self.epochs = 0
        self.obs = obs if obs is not None else get_obs()
        self.sid = sid if sid is not None else new_trace_id("s")
        self.last_epoch: Optional[dict] = None
        self.race_s = 0.0     # wall time inside race.init and race.epoch
        self._wait_s = 0.0    # this epoch's time blocked in _fetch
        self._fetches = 0     # this epoch's _fetch calls
        self.shard_coord_ops: Optional[np.ndarray] = None
        self.shard_rounds: Optional[np.ndarray] = None
        self._snap: Optional[Partial] = None
        self._retired = np.zeros((Q,), bool)
        self._prev_coord_ops: Optional[float] = None
        self._prev_rounds = 0
        self._prev_shard_coord_ops: Optional[np.ndarray] = None
        self._prev_shard_rounds: Optional[np.ndarray] = None
        self._deadline_t: Optional[float] = None
        self._round_ms = 0.0

    def set_deadline(self, deadline_ms: Optional[float],
                     round_ms: Optional[float] = None) -> None:
        """Deadline-aware fused-round selection (DESIGN.md §9.7): with a
        wall-clock budget and a measured per-round cost estimate (the
        tuned config's ``round_ms``), the fused drivers cap the rounds
        fused into the NEXT launch so one epoch never overshoots the
        deadline — the plane harvests a certified prefix at the boundary
        instead of blocking an extra launch past expiry."""
        self._deadline_t = (None if deadline_ms is None
                            else time.perf_counter() + deadline_ms / 1e3)
        self._round_ms = float(round_ms or 0.0)

    def _deadline_R(self, R: int) -> int:
        """Cap the adaptive R by the rounds the remaining wall budget can
        pay for, quantized DOWN the warm R0·2^j chain — an off-chain R is
        a fresh T specialization whose XLA compile costs far more wall
        time than the rounds it would save."""
        if self._deadline_t is None or self._round_ms <= 0.0:
            return R
        left_ms = (self._deadline_t - time.perf_counter()) * 1e3
        cap = int(left_ms / self._round_ms)
        R0 = getattr(self, "_R0", 1)
        if cap <= R0:
            return min(R, R0)     # never below the chain's smallest rung
        return min(R, R0 * pow2_floor(cap // R0))

    @property
    def snapshot(self) -> Partial:
        return self._snap

    @property
    def done(self) -> np.ndarray:
        # host-sync: _snap crossed at the _fetch boundary (numpy)
        return np.asarray(self._snap.done) | self._retired

    @property
    def exhausted(self) -> bool:
        """Round cap hit with rows unresolved — the driver's safety net."""
        return not self.done.all() and self._rounds_spent >= self._max_rounds

    def retire(self, mask: np.ndarray) -> None:
        mask = np.asarray(mask, bool)  # host-sync: caller-side numpy mask
        self._retired |= mask
        self._apply_force_done(jnp.asarray(self._retired))

    def _fetch(self, value, fetch=host_fetch):
        """``fetch(value)`` (a ``host_fetch``) inside a ``race.sync`` span.
        Every blocking fetch of a session goes through here; its time is
        the epoch's ``wait_ms``."""
        t0 = time.perf_counter()
        with self.obs.tracer.span("race.sync", trace=self.sid):
            value = fetch(value)
        self._wait_s += time.perf_counter() - t0
        self._fetches += 1
        return value

    @contextlib.contextmanager
    def _race_init(self, width: int):
        """The ``race.init`` span around a session's start: the wide init's
        dispatch and the first snapshot fetch. ``coord_ops`` is what the
        init paid, read from that snapshot."""
        t0 = time.perf_counter()
        with self.obs.tracer.annotate("race.init"):
            yield
        dur = time.perf_counter() - t0
        self.race_s += dur
        self.obs.tracer.complete(
            "race.init", t0, dur, trace=self.sid, dur_ms=dur * 1e3, Q=self.Q,
            width=width, coord_ops=float(np.sum(self._snap.coord_ops)))

    def step(self) -> bool:
        if self.done.all() or self._rounds_spent >= self._max_rounds:
            return False
        if self._prev_coord_ops is None:     # baseline excludes init pulls
            # host-sync: _snap/shard stats are post-boundary numpy
            self._prev_coord_ops = float(np.sum(self._snap.coord_ops))
            self._prev_rounds = int(np.max(self._snap.rounds, initial=0))
            if self.shard_coord_ops is not None:
                # host-sync: post-boundary numpy
                self._prev_shard_coord_ops = np.array(self.shard_coord_ops,
                                                      float)
                # host-sync: post-boundary numpy
                self._prev_shard_rounds = np.array(self.shard_rounds, float)
        self._wait_s, self._fetches = 0.0, 0
        t0 = time.perf_counter()
        with self.obs.tracer.annotate(f"race.epoch.{self.kind}"):
            alive = self._step_impl()
        dur = time.perf_counter() - t0
        self.race_s += dur
        self._record_epoch(t0, dur)
        return alive

    def _record_epoch(self, t0: float, dur: float) -> None:
        snap = self._snap  # host-sync: numpy snapshot, whole method is host math
        coord = float(np.sum(snap.coord_ops))  # host-sync: numpy
        rounds = int(np.max(snap.rounds, initial=0))
        d_coord = max(coord - self._prev_coord_ops, 0.0)
        d_rounds = max(rounds - self._prev_rounds, 0)
        self._prev_coord_ops, self._prev_rounds = coord, rounds
        finite_ci = np.where(np.isfinite(snap.ci), snap.ci, 0.0)
        info = {
            "epoch": self.epochs,
            "kind": self.kind,
            "coord_ops": d_coord,
            "rounds": d_rounds,
            "worst_ci": float(finite_ci.max(initial=0.0)),  # host-sync: numpy
            "active": int(np.sum(~self.done)),
            "done": int(np.sum(self.done)),
        }
        info.update(self._epoch_extra())
        if self.shard_coord_ops is not None:
            cur_c = np.asarray(self.shard_coord_ops, float)  # host-sync: numpy
            cur_r = np.asarray(self.shard_rounds, float)  # host-sync: numpy
            prev_c = (self._prev_shard_coord_ops
                      if self._prev_shard_coord_ops is not None
                      else np.zeros_like(cur_c))
            prev_r = (self._prev_shard_rounds
                      if self._prev_shard_rounds is not None
                      else np.zeros_like(cur_r))
            info["shard_coord_ops"] = [float(v)  # host-sync: numpy
                                       for v in cur_c - prev_c]
            info["shard_rounds"] = [float(v)  # host-sync: numpy
                                    for v in cur_r - prev_r]
            self._prev_shard_coord_ops = cur_c
            self._prev_shard_rounds = cur_r
        self.last_epoch = info
        reg = self.obs.registry
        reg.counter("repro_race_epochs_total",
                    "race epochs stepped", kind=self.kind).inc()
        reg.counter("repro_race_coord_ops_total",
                    "coordinate reads paid by race epochs",
                    kind=self.kind).inc(d_coord)
        reg.histogram("repro_race_epoch_ms",
                      "wall time of one race epoch (ms)",
                      kind=self.kind).observe(dur * 1e3)
        self.obs.tracer.complete("race.epoch", t0, dur, trace=self.sid,
                                 dur_ms=dur * 1e3, wait_ms=self._wait_s * 1e3,
                                 host_ms=(dur - self._wait_s) * 1e3,
                                 fetches=self._fetches, **info)

    def _epoch_extra(self) -> dict:
        """Per-box epoch attributes (frontier width, survivors, R)."""
        return {}

    def _step_impl(self) -> bool:
        raise NotImplementedError

    def _epoch_launch(self, R: int):
        """``(fn, args, kwargs)``: the jitted program one epoch of R rounds
        launches from the current state — what ``_step_impl`` calls."""
        raise NotImplementedError(f"{self.kind} sessions expose no epoch launch")

    def epoch_hlo(self, R: Optional[int] = None) -> str:
        """Compiled HLO text of the next epoch's launch, built by the same
        ``_epoch_launch`` that ``step()`` calls (R defaults to the first
        rung of the round chain)."""
        fn, args, kwargs = self._epoch_launch(R or getattr(self, "_R0", 1))
        return fn.lower(*args, **kwargs).compile().as_text()

    def _apply_force_done(self, mask) -> None:
        raise NotImplementedError


class FusedSession(RaceSession):
    """Single-shard dense/rotated: the §4 epoch-fused survivor-compacted
    driver, host loop exposed one epoch at a time (same compaction schedule
    and adaptive-R rule as the blocking ``fused_race_topk``). An epoch is
    one launch (``_fused_epoch_snapshot``: the step, the exactify and the
    summary) and one fetch of one packed array; ``_refresh`` serves the
    session's start."""

    kind = "fused"

    def __init__(self, store, queries, rng, *, cfg: BMOConfig,
                 impl: str = "auto", eliminate: bool = True,
                 prior=None, prior_weight: float = 0.0,
                 obs=None, sid: Optional[str] = None):
        x, qs = store.x, store.prepare_queries(queries)
        n = x.shape[0]
        super().__init__(qs.shape[0], cfg.k, obs=obs, sid=sid)
        nb = x.shape[1] // store.block
        B0 = min(cfg.batch_arms, n)
        P_ = cfg.pulls_per_round
        self._cfg, self._x, self._qs = cfg, x, qs
        self._block, self._d, self._impl = store.block, store.d, impl
        self._eliminate, self._prior_weight = eliminate, prior_weight
        self._log_term = float(
            np.log(2.0 / conf.delta_prime(cfg.delta, n, nb)))
        self._max_rounds = cfg.max_rounds or int(
            2 * math.ceil(n * nb / max(B0 * P_, 1)) + n + 16)
        self._R0 = max(cfg.epoch_rounds, 1)
        self._R_cap = max(1, -(-nb // P_))
        self._floor_w = floor_width(cfg, n, B0=B0)
        prior = store.prior_var if prior is None else jnp.asarray(
            prior, jnp.float32)
        self._rounds_spent = 0
        self._last_R = 0
        self._n_surv = np.full((self.Q,), n)
        with self._race_init(width=n):
            st, self._pool = _fused_init(
                x, qs, store.alive, prior, rng, cfg=cfg, block=store.block,
                impl=impl, prior_weight=prior_weight)
            self._W0 = st.width
            self._refresh(st)

    def _refresh(self, st) -> None:
        with self.obs.tracer.span("race.summary", trace=self.sid):
            self._st, summ = _fused_partial(
                self._x, self._qs, st, self._pool, cfg=self._cfg, d=self._d,
                log_term=self._log_term, prior_weight=self._prior_weight)
            self._snap = self._fetch(summ, _to_host)

    def _apply_force_done(self, mask) -> None:
        self._st = _force_done(self._st, mask)
        self._n_surv = np.where(np.asarray(self._retired), 0, self._n_surv)

    def _epoch_extra(self) -> dict:
        return {"width": int(self._st.width),
                "n_surv": int(self._n_surv.max(initial=0)),
                "R": self._last_R}

    def _epoch_launch(self, R: int):
        return _fused_epoch_snapshot, \
            (self._x, self._qs, self._st, self._pool), \
            dict(step=_fused_epoch_step, cfg=self._cfg, block=self._block,
                 d=self._d, impl=self._impl, eliminate=self._eliminate,
                 prior_weight=self._prior_weight, log_term=self._log_term,
                 T=R * self._cfg.pulls_per_round)

    def _step_impl(self) -> bool:
        need = int(self._n_surv[~self.done].max(initial=1))
        # halve the buffer at most once per epoch (unlike the blocking
        # driver's jump-to-cover): every session then walks the SAME
        # descending width chain, so one warm full-certification race
        # pre-compiles every (Q, W) specialization a serving race can hit —
        # no mid-traffic XLA compiles on the request plane's hot path
        W_new = max(bucket_width(need, floor=self._floor_w,
                                 current=self._st.width),
                    self._st.width // 2)
        R = min(self._R0 * pow2_floor(self._W0 // max(need, 1)), self._R_cap)
        R = self._deadline_R(R)
        with self.obs.tracer.span("race.launch", trace=self.sid):
            if W_new < self._st.width:
                self._st = compact_frontier(self._st, W_new=W_new)
            fn, args, kwargs = self._epoch_launch(R)
            self._st, packed, _ = fn(*args, **kwargs)
        self._rounds_spent += R
        self._last_R = R
        # one fetch an epoch: the snapshot and the next epoch's survivors
        summ, self._n_surv = _unpack_epoch(self._fetch(packed), self.k)
        self._snap = _to_host(summ)
        self.epochs += 1
        return not self.done.all()


class SparseRoundsSession(RaceSession):
    """Single-shard sparse: the §3.2 per-round driver in bounded-round
    chunks (one chunk = one scheduler epoch)."""

    kind = "sparse"

    def __init__(self, store, queries, rng, *, cfg: BMOConfig,
                 eliminate: bool = True, prior=None,
                 prior_weight: float = 0.0, chunk_rounds: int = 0,
                 obs=None, sid: Optional[str] = None):
        q_idx, q_val, q_nnz = (jnp.asarray(a) for a in queries)
        super().__init__(q_idx.shape[0], cfg.k, obs=obs, sid=sid)
        self._args = (store.indices, store.values, store.nnz, store.alive,
                      store.prior_var if prior is None
                      else jnp.asarray(prior, jnp.float32),
                      q_idx, q_val, q_nnz)
        self._cfg, self._d = cfg, store.d
        self._eliminate, self._prior_weight = eliminate, prior_weight
        self._chunk = chunk_rounds or 2 * max(cfg.epoch_rounds, 1)
        n, m = store.indices.shape
        B0 = min(cfg.batch_arms, n)
        mp = int(m + q_idx.shape[1])
        self._max_rounds = cfg.max_rounds or int(
            2 * math.ceil(n * mp / max(B0 * cfg.pulls_per_round, 1)) + n + 16)
        self._rounds_spent = 0
        with self._race_init(width=n):
            self._st, summ = _sparse_sess_init(
                *self._args, rng, cfg=cfg, d=store.d, eliminate=eliminate,
                prior_weight=prior_weight)
            self._snap = self._fetch(summ, _to_host)

    def _apply_force_done(self, mask) -> None:
        self._st = _force_done(self._st, mask)

    def _epoch_extra(self) -> dict:
        return {"R": self._chunk}

    def _step_impl(self) -> bool:
        with self.obs.tracer.span("race.launch", trace=self.sid):
            self._st, summ = _sparse_sess_chunk(
                *self._args, self._st, cfg=self._cfg, d=self._d,
                eliminate=self._eliminate, prior_weight=self._prior_weight,
                rounds=self._chunk)
        self._rounds_spent += self._chunk
        self._snap = self._fetch(summ, _to_host)
        self.epochs += 1
        return not self.done.all()


class ShardedFusedSession(RaceSession):
    """Sharded dense/rotated: the §5.2 shard-local fused race with the
    shared host epoch loop — including the cross-shard pull-budget
    reallocator — stepped one epoch at a time; snapshots merge the
    shards' certified/accepted frontiers on host."""

    kind = "sharded_fused"

    def __init__(self, store: ShardedIndexStore, queries, rng, *,
                 cfg: BMOConfig, impl: str = "auto", eliminate: bool = True,
                 prior_st=None, prior_weight: float = 0.0,
                 obs=None, sid: Optional[str] = None):
        qs = store.prepare_queries(queries)
        super().__init__(qs.shape[0], cfg.k, obs=obs, sid=sid)
        self._store, self._qs, self._cfg = store, qs, cfg
        self._S, self._stride, self._mesh = (store.n_shards, store.stride,
                                             store.mesh)
        dev = store.device_arrays()
        self._x_st, alive_st = dev["x"], dev["alive"]
        if prior_st is None:
            prior_st = dev["prior_var"]
        self._impl, self._eliminate = impl, eliminate
        self._prior_weight = prior_weight
        nb = self._x_st.shape[1] // store.block
        P_ = cfg.pulls_per_round
        self._log_term = float(np.log(
            2.0 / conf.delta_prime(cfg.delta, self._S * self._stride, nb)))
        B0 = min(cfg.batch_arms, self._stride)
        self._R0 = max(cfg.epoch_rounds, 1)
        self._R_cap = max(1, -(-nb // P_))
        self._floor_w = floor_width(cfg, self._stride, B0=B0)
        self._max_rounds = cfg.max_rounds or int(
            2 * math.ceil(self._stride * nb / max(B0 * P_, 1))
            + self._stride + 16)
        self._rounds_spent = 0
        self._last_R = 0
        self._n_surv = np.full((self._S, self.Q), self._stride)
        with self._race_init(width=self._stride):
            st, self._pool = _fused_init_fn(
                self._mesh, cfg, store.block, impl, prior_weight)(
                self._x_st, qs, alive_st, prior_st, rng)
            self._W0 = st.ids.shape[2]
            self._refresh(st)

    def _refresh(self, st) -> None:
        with self.obs.tracer.span("race.summary", trace=self.sid):
            self._st, summ = _sharded_fused_partial_fn(
                self._mesh, self._cfg, self._store.d, self._log_term,
                self._prior_weight, self._stride)(
                self._x_st, self._qs, st, self._pool)
            per_shard = self._fetch(summ, _to_host)
        self.shard_coord_ops = per_shard.coord_ops.sum(axis=1)
        self.shard_rounds = per_shard.rounds.max(axis=1)
        self._snap = _merge_shard_partials(per_shard)

    def _apply_force_done(self, mask) -> None:
        self._st = _force_done(self._st, mask)
        self._n_surv = np.where(np.asarray(self._retired)[None], 0,
                                self._n_surv)

    def _epoch_extra(self) -> dict:
        return {"width": int(self._st.ids.shape[2]),
                "n_surv": int(self._n_surv.max(initial=0)),
                "R": self._last_R, "shards": self._S}

    def _epoch_launch(self, R: int):
        fn = _fused_step_fn(
            self._mesh, self._cfg, self._store.block, self._store.d,
            self._impl, self._eliminate, self._prior_weight, self._log_term,
            R * self._cfg.pulls_per_round)
        return fn, (self._x_st, self._qs, self._st, self._pool), {}

    def _step_impl(self) -> bool:
        active_q = ~self.done
        need = int(self._n_surv[:, active_q].max(initial=1))
        # at-most-halving schedule — see FusedSession.step
        W_new = max(bucket_width(need, floor=self._floor_w,
                                 current=self._st.ids.shape[2]),
                    self._st.ids.shape[2] // 2)
        total_need = int(
            np.sum(self._n_surv[:, active_q].max(axis=1, initial=0)))
        R = min(self._R0 * pow2_floor((self._S * self._W0)
                                      // max(total_need, 1)), self._R_cap)
        R = self._deadline_R(R)
        with self.obs.tracer.span("race.launch", trace=self.sid):
            if W_new < self._st.ids.shape[2]:
                self._st = _compact_stacked(self._st, W_new=W_new)
            fn, args, kwargs = self._epoch_launch(R)
            st, n_surv, _ = fn(*args, **kwargs)
        self._rounds_spent += R
        self._last_R = R
        self._n_surv = self._fetch(n_surv)
        self.epochs += 1
        self._refresh(st)
        return not self.done.all()


class ShardedSparseSession(RaceSession):
    """Sharded sparse: the per-round driver chunked shard-locally under
    ``shard_map`` (each chunk one collective program), merged per snapshot."""

    kind = "sharded_sparse"

    def __init__(self, store: ShardedIndexStore, queries, rng, *,
                 cfg: BMOConfig, eliminate: bool = True, prior_st=None,
                 prior_weight: float = 0.0, chunk_rounds: int = 0,
                 obs=None, sid: Optional[str] = None):
        q_idx, q_val, q_nnz = (jnp.asarray(a) for a in queries)
        super().__init__(q_idx.shape[0], cfg.k, obs=obs, sid=sid)
        cfg = _shard_delta(cfg, store.n_shards)
        self._cfg, self._d = cfg, store.d
        self._S, self._stride, self._mesh = (store.n_shards, store.stride,
                                             store.mesh)
        dev = store.device_arrays()
        if prior_st is None:
            prior_st = dev["prior_var"]
        self._args = (dev["indices"], dev["values"], dev["nnz"],
                      dev["alive"], prior_st, q_idx, q_val, q_nnz)
        self._eliminate, self._prior_weight = eliminate, prior_weight
        self._chunk = chunk_rounds or 2 * max(cfg.epoch_rounds, 1)
        m = int(dev["indices"].shape[2])
        B0 = min(cfg.batch_arms, self._stride)
        mp = m + int(q_idx.shape[1])
        self._max_rounds = cfg.max_rounds or int(
            2 * math.ceil(self._stride * mp
                          / max(B0 * cfg.pulls_per_round, 1))
            + self._stride + 16)
        self._rounds_spent = 0
        with self._race_init(width=self._stride):
            self._st, summ = _sharded_sparse_init_fn(
                self._mesh, cfg, store.d, eliminate, prior_weight,
                self._stride)(*self._args, rng)
            self._ingest(summ)

    def _ingest(self, summ) -> None:
        per_shard = self._fetch(summ, _to_host)
        self.shard_coord_ops = per_shard.coord_ops.sum(axis=1)
        self.shard_rounds = per_shard.rounds.max(axis=1)
        self._snap = _merge_shard_partials(per_shard)

    def _apply_force_done(self, mask) -> None:
        self._st = _force_done(self._st, mask)

    def _epoch_extra(self) -> dict:
        return {"R": self._chunk, "shards": self._S}

    def _step_impl(self) -> bool:
        with self.obs.tracer.span("race.launch", trace=self.sid):
            self._st, summ = _sharded_sparse_chunk_fn(
                self._mesh, self._cfg, self._d, self._eliminate,
                self._prior_weight, self._stride, self._chunk)(
                *self._args, self._st)
        self._rounds_spent += self._chunk
        self.epochs += 1
        self._ingest(summ)
        return not self.done.all()


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------


def make_session(store, queries, rng, *, cfg: Optional[BMOConfig] = None,
                 impl: str = "auto", eliminate: bool = True,
                 warm_start: bool = True, prior_hint=None,
                 chunk_rounds: int = 0, obs=None,
                 sid: Optional[str] = None,
                 deadline_ms: Optional[float] = None,
                 round_ms: Optional[float] = None) -> RaceSession:
    """Build the right resumable session for ``store``'s box and layout —
    the anytime twin of ``index_knn`` (same priors, same δ accounting).
    ``obs``/``sid`` select the observability context and trace id the
    session records epoch spans under (default: process obs, fresh id).
    ``deadline_ms`` (wall budget) + ``round_ms`` (the tuned per-round cost
    estimate, ``repro.tune``) turn on deadline-aware fused-round selection
    — see ``RaceSession.set_deadline``."""
    cfg = cfg if cfg is not None else store.cfg
    if cfg.k > store.n_live:
        raise ValueError(
            f"k={cfg.k} exceeds the index's {store.n_live} live slots — "
            "tombstoned slots can never be returned")
    sharded = hasattr(store, "shards")
    w = store.prior_weight if (warm_start or prior_hint is not None) else 0.0
    if sharded:
        S, stride = store.n_shards, store.stride
        if prior_hint is not None:
            Q = (queries[0] if isinstance(queries, tuple)
                 else jnp.asarray(queries)).shape[0]
            prior_st = jnp.asarray(prior_hint, jnp.float32).reshape(
                Q, S, stride).transpose(1, 0, 2)
        else:
            prior_st = None
        if store.kind == "sparse":
            sess = ShardedSparseSession(
                store, queries, rng, cfg=cfg, eliminate=eliminate,
                prior_st=prior_st, prior_weight=w, chunk_rounds=chunk_rounds,
                obs=obs, sid=sid)
        else:
            sess = ShardedFusedSession(
                store, queries, rng, cfg=cfg, impl=impl, eliminate=eliminate,
                prior_st=prior_st, prior_weight=w, obs=obs, sid=sid)
    else:
        prior = None if prior_hint is None else jnp.asarray(prior_hint,
                                                            jnp.float32)
        if store.kind == "sparse":
            sess = SparseRoundsSession(
                store, queries, rng, cfg=cfg, eliminate=eliminate,
                prior=prior, prior_weight=w, chunk_rounds=chunk_rounds,
                obs=obs, sid=sid)
        else:
            sess = FusedSession(store, queries, rng, cfg=cfg, impl=impl,
                                eliminate=eliminate, prior=prior,
                                prior_weight=w, obs=obs, sid=sid)
    if deadline_ms is not None:
        sess.set_deadline(deadline_ms, round_ms)
    return sess
