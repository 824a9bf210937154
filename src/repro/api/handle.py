"""``Index`` — the one handle in front of the index subsystem
(DESIGN.md §6.1).

PRs 1–3 grew three parallel surfaces for the same paper technique: the
``IndexStore`` free functions, their ``sharded_*`` twins, and the
cache/prior plumbing private to ``ServeEngine``. This handle collapses the
single-shard/sharded split: ``Index.build/load/open`` return one object
whose ``query/insert/delete/compact/save`` dispatch internally on the store
type, queries go through the typed ``QuerySpec`` protocol (spec.py), the
query LRU + near-repeat warm starts live behind ``CachePolicy``, and
tombstone debt behind ``CompactionPolicy``. Admin operations — **live**
elastic re-sharding and read-replica fan-out — are first-class methods
(admin.py) instead of a save/load cycle.

Side payloads (e.g. kNN-LM next-token ids) attach to the handle and ride
every slot-remapping event (growth, compaction, re-shard) automatically:
``payload[result.indices]`` is always aligned.

The handle is *mutable* (unlike the immutable stores underneath): every
mutation swaps in a fresh store and bumps ``epoch``, which fences the query
cache and the replica fan-out — the invalidation contract callers can rely
on instead of store identity.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Optional

import jax
import numpy as np

from repro.api.cache import QueryCache
from repro.api.spec import (CachePolicy, CompactionPolicy, KNNResult,
                            QuerySpec, ServeStats)
from repro.core.datasets import next_pow2
from repro.index import mutable
from repro.index.batched_race import index_knn as _index_knn
from repro.index.builder import build_index, load_index, save_index
from repro.index.sharded import (ShardedIndexStore, build_sharded_index,
                                 is_sharded_index_dir, load_sharded_index,
                                 save_sharded_index, sharded_delete,
                                 sharded_insert, sharded_maybe_compact)
from repro.utils import get_logger

log = get_logger("repro.api")

PAYLOAD_FILE = "payload.npy"


def _with_cfg(store, cfg):
    """Rebind the racing config (δ / budget overrides) on a store without
    touching its arrays. Off the fast path: a sharded store loses its cached
    device placement and re-places on the next launch."""
    if hasattr(store, "shards"):
        return dataclasses.replace(
            store, shards=[dataclasses.replace(s, cfg=cfg)
                           for s in store.shards])
    return dataclasses.replace(store, cfg=cfg)


class Index:
    """One handle over a single-shard or mesh-spanning racing index.

    Construct through ``Index.build`` (from a corpus), ``Index.load`` (from
    a saved directory, optionally re-sharded on the way in), or
    ``Index.open`` (around an existing store object). All query/mutation/
    admin traffic then goes through the handle; the underlying store is
    reachable read-only as ``handle.store``.
    """

    def __init__(self, store, *, payload: Optional[np.ndarray] = None,
                 build_gids: Optional[np.ndarray] = None,
                 cache: Optional[CachePolicy] = None,
                 compaction: Optional[CompactionPolicy] = None):
        self._store = store
        self._base_cfg = store.cfg    # pre-tuning config: the use_tuned=False
                                      # contract races exactly this
        self._tuned = None            # active repro.tune.TunedConfig (or None)
        self._force_untuned = False   # recall-guard fallback: serve every
                                      # query on build-time defaults
        self._retune_reason = None    # pending re-tune request (or None)
        self.cache_policy = cache if cache is not None else CachePolicy()
        self.compaction_policy = (compaction if compaction is not None
                                  else CompactionPolicy())
        self._cache = (QueryCache(self.cache_policy.capacity)
                       if self.cache_policy.capacity > 0 else None)
        self._cache_ns: Optional[str] = None  # fleet-set namespace label; a
                                              # shared cache keys/fences on it
        self._payload = payload
        self._build_gids = build_gids
        self._epoch = 0
        self._admin_active: Optional[str] = None
        self._n_replicas = 1
        self._replica_stores = None
        self._rr = 0
        self._races = 0
        self._raced_queries = 0
        self._near_hits = 0
        self._compactions = 0
        self._reshards = 0
        self._shard_coord_ops = None
        self._shard_rounds = None
        self._auto_rng = 0
        self._reset_shard_telemetry()

    # -- constructors -------------------------------------------------------

    @classmethod
    def build(cls, corpus, cfg, rng=None, *, shards: int = 1,
              placement: str = "round_robin", capacity: Optional[int] = None,
              payload=None,
              cache: Optional[CachePolicy] = None,
              compaction: Optional[CompactionPolicy] = None) -> "Index":
        """Preprocess ``corpus`` (n, d) into a served index. ``shards > 1``
        spans it over that many mesh devices (DESIGN.md §5). ``payload``:
        optional (n,)-row-aligned side values (e.g. next-token ids) attached
        slot-aligned — the handle keeps them aligned through every remap."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if shards > 1:
            store, gids = build_sharded_index(
                np.asarray(corpus), cfg, rng, shards=shards,
                placement=placement, capacity=capacity)
        else:
            store = build_index(corpus, cfg, rng, capacity=capacity)
            gids = np.arange(store.n_live, dtype=np.int64)
        handle = cls(store, build_gids=gids, cache=cache,
                     compaction=compaction)
        if payload is not None:
            handle.attach_payload(payload, gids=gids)
        return handle

    @classmethod
    def open(cls, store, *, payload=None, payload_gids=None,
             cache: Optional[CachePolicy] = None,
             compaction: Optional[CompactionPolicy] = None) -> "Index":
        """Wrap an existing ``IndexStore`` / ``ShardedIndexStore``.

        ``payload`` without ``payload_gids`` is taken slot-aligned: it must
        cover every live slot, and a sharded store (whose live global ids
        are non-contiguous) needs the full capacity length."""
        handle = cls(store, cache=cache, compaction=compaction)
        if payload is not None:
            handle.attach_payload(payload, gids=payload_gids)
        return handle

    @classmethod
    def load(cls, path: str, *, shards: Optional[int] = None,
             cache: Optional[CachePolicy] = None,
             compaction: Optional[CompactionPolicy] = None) -> "Index":
        """Load a saved index directory (either layout); ``shards=S'``
        re-shards on the way in. A ``payload.npy`` sidecar (written by
        ``save`` when a payload is attached) is restored and remapped."""
        from repro.index.sharded import reshard as _reshard
        old_ids = None
        if is_sharded_index_dir(path):
            store, old_ids = load_sharded_index(path, shards=shards)
        else:
            store = load_index(path)
            if shards is not None and shards > 1:
                store, old_ids = _reshard(ShardedIndexStore([store]), shards)
        handle = cls(store, cache=cache, compaction=compaction)
        ppath = os.path.join(path, PAYLOAD_FILE)
        if os.path.exists(ppath):
            saved = np.load(ppath)
            buf = np.zeros((store.capacity,) + saved.shape[1:], saved.dtype)
            if old_ids is None:
                buf[: len(saved)] = saved
            else:
                live = old_ids >= 0
                buf[live] = saved[old_ids[live]]
            handle._payload = buf
        # tuned.json sidecar (repro.tune): apply only when its signature
        # still matches the store as reloaded — re-sharded / re-typed /
        # grown-past-bucket stores fall back to defaults bit-compatibly.
        from repro.tune import cache_put, load_tuned, signature_of
        tuned, _why = load_tuned(path, store)
        if tuned is not None:
            handle._apply_tuned(tuned, swap=False)
            cache_put(signature_of(store), tuned)
        return handle

    # -- store-shape properties --------------------------------------------

    @property
    def store(self):
        """The underlying (immutable) store — read-only access; mutate
        through the handle so the epoch fence stays truthful."""
        return self._store

    @property
    def sharded(self) -> bool:
        return hasattr(self._store, "shards")

    @property
    def n_shards(self) -> int:
        return self._store.n_shards if self.sharded else 1

    @property
    def capacity(self) -> int:
        return self._store.capacity

    @property
    def n_live(self) -> int:
        return self._store.n_live

    @property
    def kind(self) -> str:
        return self._store.kind

    @property
    def cfg(self):
        return self._store.cfg

    @property
    def k(self) -> int:
        return self._store.cfg.k

    @property
    def epoch(self) -> int:
        """Bumped on every mutation/admin swap — the cache/replica fence."""
        return self._epoch

    @property
    def tuned(self):
        """The active ``repro.tune.TunedConfig`` (None = build-time
        defaults). Set by ``tune()`` or a valid ``tuned.json`` sidecar at
        ``load``; cleared only by tuning again."""
        return self._tuned

    @property
    def serving_fallback(self) -> bool:
        """True while the recall guard has forced ``use_tuned=False`` for
        ALL queries (``force_untuned``) — the spec's own ``use_tuned`` is
        then ignored until the fallback is lifted."""
        return self._force_untuned

    @property
    def retune_requested(self) -> bool:
        """True while a re-tune has been flagged (``request_retune``) and
        not yet serviced by ``tune()``."""
        return self._retune_reason is not None

    @property
    def retune_reason(self) -> Optional[str]:
        return self._retune_reason

    def force_untuned(self, on: bool = True) -> None:
        """Recall-guard fallback (DESIGN.md §10.3): serve EVERY query on
        the pre-tuning build config until lifted. Cost-only, not an epoch
        event — the tuned config changes racing knobs, never which
        neighbors are correct, so certified cached results stay valid."""
        if on != self._force_untuned:
            log.warning("serving fallback %s: %s the tuned config",
                        "ENGAGED" if on else "lifted",
                        "bypassing" if on else "restoring")
        self._force_untuned = bool(on)

    def request_retune(self, reason: str = "") -> None:
        """Flag that the active tuning is suspect and should be re-raced
        (``tune(force=True)`` clears the flag). Advisory — the launcher or
        an operator decides when to pay the re-race."""
        self._retune_reason = reason or "requested"

    def _serving_tuned(self, spec: QuerySpec) -> bool:
        """Whether THIS query races the tuned config: needs an active
        tuning, the spec opting in, and no recall-guard fallback."""
        return (self._tuned is not None and spec.use_tuned
                and not self._force_untuned)

    @property
    def payload(self) -> Optional[np.ndarray]:
        """(capacity,)+ global-id-aligned side values; index with
        ``KNNResult.indices``."""
        return self._payload

    @property
    def build_gids(self) -> Optional[np.ndarray]:
        """Global slot of each original corpus row (−1 once deleted or
        displaced), maintained through every remap — the row-accuracy hook
        for benches and parity tests."""
        return self._build_gids

    @property
    def stats(self) -> ServeStats:
        cache = self._cache      # NB: an *empty* QueryCache is falsy (__len__)
        return ServeStats(
            races=self._races,
            raced_queries=self._raced_queries,
            cache_hits=cache.hits if cache is not None else 0,
            cache_misses=cache.misses if cache is not None else 0,
            cache_entries=len(cache) if cache is not None else 0,
            near_hits=self._near_hits,
            compactions=self._compactions,
            reshards=self._reshards,
            replicas=self._n_replicas,
            shard_coord_ops=(self._shard_coord_ops.tolist()
                             if self._shard_coord_ops is not None else None),
            shard_rounds=(self._shard_rounds.tolist()
                          if self._shard_rounds is not None else None),
            serving_fallback=self._force_untuned,
            retune_requested=self._retune_reason is not None,
        )

    # -- internal plumbing --------------------------------------------------

    def _reset_shard_telemetry(self) -> None:
        if self.sharded:
            self._shard_coord_ops = np.zeros(self.n_shards)
            self._shard_rounds = np.zeros(self.n_shards)
        else:
            self._shard_coord_ops = self._shard_rounds = None

    def _swap(self, store) -> None:
        """Epoch fence: install a new store, invalidate the query cache and
        the replica fan-out (both re-derive from the new store lazily)."""
        old_shards = self.n_shards if self.sharded else None
        self._store = store
        self._epoch += 1
        if self._cache is not None:
            # a standalone handle (_cache_ns=None) owns the whole cache; a
            # fleet-owned handle shares it and may only fence its own slice
            self._cache.clear(self._cache_ns)
        self._replica_stores = None
        new_shards = store.n_shards if hasattr(store, "shards") else None
        if new_shards != old_shards:
            self._reset_shard_telemetry()

    def _apply_tuned(self, tuned, *, swap: bool = True) -> None:
        """Install a ``TunedConfig``: rebind every shard onto the tuned
        racing knobs (k/δ/metric stay the store's own). ``swap=True`` goes
        through the epoch fence — live re-tunes must invalidate the query
        cache and replica fan-out; ``swap=False`` is the load-time path
        (fresh handle, nothing to fence)."""
        new = _with_cfg(self._store, tuned.bind(self._store.cfg))
        if swap:
            self._swap(new)
        else:
            # load-time: handle not yet published, nothing observes it
            self._store = new  # repro-lint: allow[epoch-fence]
        self._tuned = tuned

    def _remap(self, old_ids: np.ndarray) -> None:
        """Reindex payload + build-row map through an old→new global-id map
        (the ``mutable.compact`` contract). Call BEFORE ``_swap``."""
        old_ids = np.asarray(old_ids)
        live = old_ids >= 0
        if self._payload is not None:
            remapped = np.zeros((len(old_ids),) + self._payload.shape[1:],
                                self._payload.dtype)
            remapped[live] = self._payload[old_ids[live]]
            self._payload = remapped
        if self._build_gids is not None:
            lookup = np.full((self.capacity,), -1, np.int64)
            lookup[old_ids[live]] = np.nonzero(live)[0]
            bg = self._build_gids
            ok = bg >= 0
            self._build_gids = np.where(ok, lookup[np.where(ok, bg, 0)], -1)

    def _grow_payload(self, new_capacity: int) -> None:
        if self._payload is not None and new_capacity > len(self._payload):
            grown = np.zeros((new_capacity,) + self._payload.shape[1:],
                             self._payload.dtype)
            grown[: len(self._payload)] = self._payload
            self._payload = grown

    @contextlib.contextmanager
    def _admin_op(self, name: str):
        """Quiesce fence for admin swaps: mutations attempted while the op
        is in flight fail loudly instead of racing the swap."""
        if self._admin_active is not None:
            raise RuntimeError(
                f"admin op {name!r} while {self._admin_active!r} is in "
                "flight")
        self._admin_active = name
        try:
            yield
        finally:
            self._admin_active = None

    def _check_mutable(self, what: str) -> None:
        if self._admin_active is not None:
            raise RuntimeError(
                f"{what} rejected: index is quiesced for admin op "
                f"{self._admin_active!r}")

    def _route(self):
        """Round-robin the query over the replica fan-out (admin.py)."""
        if self._n_replicas <= 1:
            return self._store
        if self._replica_stores is None:
            from repro.api.admin import materialize_replicas
            self._replica_stores = materialize_replicas(
                self._store, self._n_replicas)
        store = self._replica_stores[self._rr % len(self._replica_stores)]
        self._rr += 1
        return store

    def _query_cfg(self, spec: QuerySpec):
        """The config a spec binds against: the served (tuned) config on
        the fast path, the pre-tuning build config under
        ``use_tuned=False`` or a recall-guard ``force_untuned`` fallback."""
        base = self.cfg if (self._tuned is None
                            or self._serving_tuned(spec)) \
            else self._base_cfg
        return spec.bind(base)

    def _race(self, store, queries, rng, cfg, spec: QuerySpec, prior_hint):
        want = dataclasses.replace(cfg, k=store.cfg.k)
        if want != store.cfg:     # δ / budget / tuning-opt-out overrides
            store = _with_cfg(store, want)
        mode = spec.mode
        if mode == "auto" and self._serving_tuned(spec):
            mode = self._tuned.mode       # tuned fused-vs-rounds dispatch
        return _index_knn(store, queries, rng, k=cfg.k, impl=spec.impl,
                          eliminate=spec.eliminate,
                          warm_start=spec.warm_start, mode=mode,
                          prior_hint=prior_hint)

    def _record_race(self, raw, n_queries: int) -> None:
        self._races += 1
        self._raced_queries += n_queries
        if self._shard_coord_ops is not None and \
                hasattr(raw, "shard_coord_ops"):
            self._shard_coord_ops += np.asarray(raw.shard_coord_ops)
            self._shard_rounds = np.maximum(self._shard_rounds,
                                            np.asarray(raw.shard_rounds))

    def _seeded_priors(self, hid: np.ndarray, miss):
        """Near-repeat warm starts: per-query CI variance priors for missed
        rows, tightened on the cached neighbour's top-k arms. Priors shape
        the variance estimate only — the race stays a fresh δ-PAC race."""
        pol = self.cache_policy
        if (self._cache is None or pol.near_threshold <= 0
                or len(self._cache) == 0):
            return None
        base = np.asarray(self._store.prior_var, np.float32)
        rows, found = [], False
        for i in miss:
            near = self._cache.get_near(hid[i], pol.near_threshold,
                                        self._cache_ns)
            if near is None:
                rows.append(base)
            else:
                seeded = base.copy()
                seeded[near[0]] *= pol.near_prior_scale
                rows.append(seeded)
                found = True
                self._near_hits += 1
        return np.stack(rows) if found else None

    # -- query --------------------------------------------------------------

    def query(self, queries, rng=None, *, spec: Optional[QuerySpec] = None,
              **overrides) -> KNNResult:
        """Batched k-NN with the typed query protocol (spec.py): pass a
        ``QuerySpec``, keyword overrides (``k=``, ``delta=``, ``mode=``, …),
        or both (kwargs refine the spec). Dense queries are a (Q, d) array;
        the sparse box takes the (q_idx, q_val, q_nnz) padded triplet.

        Returns the stable ``KNNResult`` schema with GLOBAL slot ids.
        Exact-repeat rows are served from the query LRU at zero
        coordinate-ops (unless the spec bypasses it); near-repeats race with
        seeded CI priors."""
        if spec is None:
            spec = QuerySpec(**overrides)
        elif overrides:
            spec = dataclasses.replace(spec, **overrides)
        cfg = self._query_cfg(spec)
        if rng is None:
            rng = jax.random.PRNGKey(self._auto_rng)
            self._auto_rng += 1
        is_sparse_q = isinstance(queries, tuple)
        use_cache = (self._cache is not None and spec.cacheable
                     and spec.cache != "bypass" and not is_sparse_q)
        if not use_cache:
            raw = self._race(self._route(), queries, rng, cfg, spec,
                             spec.prior_hint)
            Q = int(np.asarray(raw.indices).shape[0])
            self._record_race(raw, Q)
            return self._result(raw)

        hid = np.asarray(queries, np.float32)
        Q, k = hid.shape[0], cfg.k
        idx = np.zeros((Q, k), np.int64)
        vals = np.zeros((Q, k), np.float32)
        coord_ops = np.zeros((Q,), np.float32)
        rounds = np.zeros((Q,), np.int32)
        n_exact = np.zeros((Q,), np.int32)
        keys = [QueryCache.key(row, self._cache_ns) for row in hid]
        miss = []
        for i in range(Q):
            got = None if spec.cache == "refresh" else self._cache.get(keys[i])
            if got is None:
                miss.append(i)
            else:
                idx[i], vals[i] = got
        raw = None
        if miss:
            sub = hid[miss]
            prior_hint = self._seeded_priors(hid, miss)
            # pad to a power-of-two sub-batch so the jitted executables
            # stay warm across varying miss counts
            pad = next_pow2(len(miss)) - len(miss)
            if pad:
                sub = np.concatenate([sub, np.repeat(sub[:1], pad, 0)], 0)
                if prior_hint is not None:
                    prior_hint = np.concatenate(
                        [prior_hint, np.repeat(prior_hint[:1], pad, 0)], 0)
            raw = self._race(self._route(), sub, rng, cfg, spec, prior_hint)
            r_idx = np.asarray(raw.indices)
            r_vals = np.asarray(raw.values)
            r_ops = np.asarray(raw.coord_ops)
            r_rounds = np.asarray(raw.rounds)
            r_exact = np.asarray(raw.n_exact)
            for j, i in enumerate(miss):
                idx[i], vals[i] = r_idx[j], r_vals[j]
                coord_ops[i] = r_ops[j]
                rounds[i] = r_rounds[j]
                n_exact[i] = r_exact[j]
                self._cache.put(keys[i], (idx[i].copy(), vals[i].copy()),
                                vec=hid[i], namespace=self._cache_ns)
            self._record_race(raw, len(miss))
        return self._result(raw, indices=idx, values=vals,
                            coord_ops=coord_ops, rounds=rounds,
                            n_exact=n_exact, cache_hits=Q - len(miss))

    def race(self, queries, rng=None, *, spec: Optional[QuerySpec] = None,
             raced_queries: Optional[int] = None, chunk_rounds: int = 0,
             obs=None, sid=None, deadline_ms: Optional[float] = None,
             **overrides):
        """Epoch-granular resumable race — the anytime twin of ``query``
        (DESIGN.md §7.1). Returns a ``repro.index.anytime.RaceSession``:
        ``step()`` advances one epoch, ``snapshot`` is the partial top-k
        with CI radii and the certified-prefix length. The request plane
        (``repro.serve.plane``) drives this to implement deadlines, effort
        budgets and anytime streaming; it never touches the query LRU
        (partial results must not poison the cache).

        ``raced_queries`` overrides the row count recorded in ``stats``
        (the plane pads coalesced batches to powers of two).
        ``obs``/``sid`` select the observability context / trace id the
        session's per-epoch spans record under (DESIGN.md §8.3).

        ``deadline_ms``: remaining wall budget for this race — with a
        tuned per-round cost estimate on file (``repro.tune``), the
        session caps each epoch's fused round count R to what the budget
        can still pay (DESIGN.md §9.7). Defaults to ``spec.deadline``'s
        full allowance; the request plane passes the group's tightest
        remaining budget explicitly."""
        from repro.index.anytime import make_session
        if spec is None:
            spec = QuerySpec(**overrides)
        elif overrides:
            spec = dataclasses.replace(spec, **overrides)
        cfg = self._query_cfg(spec)
        if rng is None:
            rng = jax.random.PRNGKey(self._auto_rng)
            self._auto_rng += 1
        if spec.mode == "fused" and self.kind == "sparse":
            raise ValueError("the fused epoch driver pulls corpus blocks — "
                             "sparse boxes race on the per-round driver")
        if spec.mode == "rounds" and self.kind != "sparse":
            raise ValueError(
                "anytime sessions drive dense/rotated boxes through the "
                "epoch-fused driver; mode='rounds' is blocking-query only")
        if deadline_ms is None and spec.deadline is not None:
            deadline_ms = spec.deadline.ms
        round_ms = (self._tuned.round_ms if self._serving_tuned(spec)
                    else 0.0)
        session = make_session(
            self._route(), queries, rng, cfg=cfg, impl=spec.impl,
            eliminate=spec.eliminate, warm_start=spec.warm_start,
            prior_hint=spec.prior_hint, chunk_rounds=chunk_rounds,
            obs=obs, sid=sid, deadline_ms=deadline_ms, round_ms=round_ms)
        self._races += 1
        self._raced_queries += int(raced_queries if raced_queries is not None
                                   else session.Q)
        return session

    def _record_session_telemetry(self, session) -> None:
        """Fold a finished RaceSession's per-shard counters into stats
        (the plane calls this when it drops a race group)."""
        if (self._shard_coord_ops is not None
                and session.shard_coord_ops is not None
                and len(session.shard_coord_ops) == len(self._shard_coord_ops)):
            self._shard_coord_ops += np.asarray(session.shard_coord_ops)
            self._shard_rounds = np.maximum(
                self._shard_rounds, np.asarray(session.shard_rounds))

    def _result(self, raw, **overrides) -> KNNResult:
        kw = dict(
            shard_coord_ops=(np.asarray(raw.shard_coord_ops).tolist()
                             if raw is not None
                             and hasattr(raw, "shard_coord_ops") else None),
            shard_rounds=(np.asarray(raw.shard_rounds).tolist()
                          if raw is not None
                          and hasattr(raw, "shard_rounds") else None),
        )
        if "indices" not in overrides:
            kw.update(indices=np.asarray(raw.indices),
                      values=np.asarray(raw.values),
                      coord_ops=np.asarray(raw.coord_ops),
                      rounds=np.asarray(raw.rounds),
                      n_exact=np.asarray(raw.n_exact))
        kw.update(overrides)
        return KNNResult(**kw)

    # -- mutation ------------------------------------------------------------

    def attach_payload(self, values, *, gids=None) -> None:
        """Attach (or replace) the slot-aligned side payload. ``gids``
        places row i of ``values`` at global slot ``gids[i]``; without it
        the values are taken slot-aligned from 0 (and must cover every live
        slot — a sharded store needs the full capacity length, since its
        live global ids are non-contiguous)."""
        values = np.asarray(values)
        if gids is None:
            if len(values) > self.capacity:
                raise ValueError(
                    f"payload ({len(values)}) exceeds index capacity "
                    f"({self.capacity}) — wrong index for this datastore?")
            if len(values) < self.n_live:
                raise ValueError(
                    f"payload ({len(values)}) does not cover the index's "
                    f"{self.n_live} live slots — uncovered slots would "
                    "silently serve zeros")
            if self.sharded and len(values) != self.capacity:
                raise ValueError(
                    f"a sharded index needs a capacity-length "
                    f"({self.capacity}) global-id-aligned payload, got "
                    f"{len(values)} (or pass gids=)")
        buf = np.zeros((self.capacity,) + values.shape[1:], values.dtype)
        if gids is None:
            buf[: len(values)] = values
        else:
            buf[np.asarray(gids)] = values
        self._payload = buf

    def insert(self, rows, *, payload=None) -> np.ndarray:
        """Insert (B, d) dense rows; returns their GLOBAL slot ids.
        ``payload``: per-row side values written into the attached payload
        at those slots."""
        self._check_mutable("insert")
        if self.sharded:
            store, gids, grow_ids = sharded_insert(self._store, rows)
            if grow_ids is not None:      # stride grew → global ids shifted
                self._remap(grow_ids)
        else:
            store, gids = mutable.insert(self._store, rows)
        self._grow_payload(store.capacity)
        if payload is not None:
            if self._payload is None:
                payload = np.asarray(payload)
                self._payload = np.zeros(
                    (store.capacity,) + payload.shape[1:], payload.dtype)
            self._payload[np.asarray(gids)] = payload
        self._swap(store)
        return np.asarray(gids, np.int64)

    def delete(self, global_ids) -> None:
        """Tombstone global slots (O(1)); data stays until compaction."""
        self._check_mutable("delete")
        if self.sharded:
            store = sharded_delete(self._store, global_ids)
        else:
            store = mutable.delete(self._store, global_ids)
        if self._build_gids is not None:
            # honour the build_gids contract (−1 once deleted): a later
            # insert may reuse the freed slot, which would otherwise be
            # silently attributed to the original corpus row
            dead = np.atleast_1d(np.asarray(global_ids, np.int64))
            self._build_gids = np.where(
                np.isin(self._build_gids, dead), -1, self._build_gids)
        self._swap(store)

    def compact(self) -> np.ndarray:
        """Rebuild the slot layout dropping tombstones; payload and build
        map are remapped in place. Returns the old→new global-id map for
        any *external* side state."""
        self._check_mutable("compact")
        if self.sharded:
            from repro.index.sharded import sharded_compact
            store, old_ids = sharded_compact(self._store)
        else:
            store, old_ids = mutable.compact(self._store)
        self._remap(old_ids)
        self._swap(store)
        self._compactions += 1
        return old_ids

    def maybe_compact(self, *, threshold: Optional[float] = None
                      ) -> Optional[np.ndarray]:
        """Apply the handle's ``CompactionPolicy`` (or an explicit
        threshold): compact only when tombstone debt crosses it AND capacity
        would shrink. Returns the remap when a compaction ran, else None."""
        self._check_mutable("compact")
        thr = threshold if threshold is not None \
            else self.compaction_policy.threshold
        if self.sharded:
            store, old_ids = sharded_maybe_compact(self._store, threshold=thr)
        else:
            store, old_ids = mutable.maybe_compact(self._store, threshold=thr)
        if old_ids is None:
            return None
        self._remap(old_ids)
        self._swap(store)
        self._compactions += 1
        return old_ids

    # -- persistence ---------------------------------------------------------

    def save(self, path: str) -> None:
        """Persist through the checkpoint layer (per-shard checkpoints +
        manifest when sharded); an attached payload is written as a
        ``payload.npy`` sidecar that ``Index.load`` restores and remaps.

        Crash-safe: the sidecars are staged INSIDE the checkpoint layer's
        all-or-nothing directory publish, so ``path`` only ever holds a
        complete index (arrays + manifest + payload + tuned config) — a
        kill at any byte leaves the previous version untouched."""
        def _sidecars(tmp: str) -> None:
            if self._payload is not None:
                np.save(os.path.join(tmp, PAYLOAD_FILE), self._payload)
            if self._tuned is not None:
                from repro.tune import save_tuned, signature_of
                save_tuned(tmp, signature_of(self._store), self._tuned,
                           measured={"epoch_ms": self._tuned.epoch_ms,
                                     "round_ms": self._tuned.round_ms})

        if self.sharded:
            save_sharded_index(self._store, path, extra=_sidecars)
        else:
            save_index(self._store, path, extra=_sidecars)

    # -- admin ops (admin.py) ------------------------------------------------

    def reshard(self, n_shards: int) -> np.ndarray:
        """LIVE elastic re-shard to ``n_shards`` — no checkpoint round-trip;
        see ``repro.api.admin.live_reshard`` for the fence protocol."""
        from repro.api.admin import live_reshard
        return live_reshard(self, n_shards)

    def tune(self, queries=None, rng=None, *, levels: int = 2,
             reps: int = 1, force: bool = False, apply: bool = True,
             **kw) -> dict:
        """Autotune the serving config for THIS store (repro.tune,
        DESIGN.md §9): enumerate the (R, P, B, floor, buffers, mode)
        candidate grid, prune it with the roofline cost model, and race
        the survivors with successive halving on measured wall time.

        Runs as an admin op — serving traffic is quiesced for the race
        and the winner is installed through the epoch fence, never under
        live queries. An equal-signature tuning from earlier in the
        process is reused without re-racing unless ``force``. ``queries``
        defaults to a synthetic batch drawn from the corpus (sparse boxes
        must pass real queries). ``apply=False`` measures without
        installing. ``save()`` persists the active tuning as a
        ``tuned.json`` sidecar; ``load()`` re-applies it while the store
        signature still matches. Returns the tuning report dict."""
        from repro.tune import tune_store
        with self._admin_op("tune"):
            tuned, report = tune_store(self._store, queries, rng,
                                       levels=levels, reps=reps,
                                       force=force, **kw)
            report = dict(report, applied=bool(apply))
            if apply:
                self._apply_tuned(tuned)
                # a fresh tuning services any pending recall-guard state:
                # the suspect config is gone, so the fallback lifts and
                # the re-tune request is satisfied
                if self._force_untuned:
                    self.force_untuned(False)
                self._retune_reason = None
        return report

    def add_replicas(self, n_replicas: int) -> int:
        """Set the read fan-out to ``n_replicas`` (1 = primary only);
        queries round-robin across replica meshes. Returns the fan-out."""
        from repro.api.admin import add_replicas
        return add_replicas(self, n_replicas)

    def __repr__(self) -> str:
        return (f"Index(kind={self.kind!r}, shards={self.n_shards}, "
                f"live={self.n_live}/{self.capacity}, k={self.k}, "
                f"epoch={self._epoch}, replicas={self._n_replicas})")
