"""Typed query protocol for the unified index surface (DESIGN.md §6.2).

Three frozen/typed records replace the ad-hoc kwargs and stringly-keyed
dicts that accumulated across PRs 1–3:

  * ``QuerySpec`` — everything a caller may vary per query batch (k, racing
    mode/impl, a δ override, a pull-budget cap, per-query CI variance
    priors, cache policy), validated ONCE at construction instead of
    per-call inside every driver. A default-constructed spec is the serving
    fast path and is the only spec the query cache serves.
  * ``KNNResult`` — the stable result schema of ``Index.query``: host-side
    arrays with GLOBAL slot ids, per-query cost counters, and (behind a
    sharded store) per-shard load telemetry.
  * ``ServeStats`` — the typed replacement for ``engine.stats``'s dict
    (LeJeune et al. 2019 / Mason et al. 2021 treat per-query budgets and
    priors as part of the query contract; so does this surface).

Plus the two pluggable policy objects lifted out of ``ServeEngine``:
``CachePolicy`` (query LRU + near-repeat warm starts) and
``CompactionPolicy`` (tombstone-debt threshold).
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

from repro.kernels import IMPLS

MODES = ("auto", "fused", "rounds")
CACHE_POLICIES = ("use", "bypass", "refresh")

#: schema version of KNNResult / ServeStats.as_dict() — bump on any field
#: change so downstream JSON consumers (benchmarks, dashboards) can gate.
#: v2 (PR 5): QuerySpec gained deadline/budget; ServeStats gained the
#: request-plane queue/latency fields (DESIGN.md §7.4).
#: v3 (PR 6): ServeStats gained the obs_* observability fields; the plane
#: latency percentiles became plain floats (0.0 on an empty window, never
#: None/NaN) so autoscaling policies can compare them unconditionally
#: (DESIGN.md §8.6).
#: v4 (PR 7): QuerySpec gained ``use_tuned`` — per-query opt-out of the
#: autotuned serving config (DESIGN.md §9.6).
#: v5 (PR 8): ServeStats gained the audit_*/slo_alerts/serving_fallback/
#: retune_requested fields — the online δ-audit and SLO burn-rate state
#: (DESIGN.md §10).
#: v6 (PR 9): ServeStats gained the fleet rollup fields — per-namespace
#: residency/eviction/reload counters and live per-namespace queue depths
#: (``fleet_namespaces_resident/evicted``, ``fleet_reloads``,
#: ``ns_queue_depth``) so autoscaling can see namespace pressure
#: (DESIGN.md §11).
SCHEMA_VERSION = 6


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """Per-query-batch contract, validated at the boundary.

    ``None`` means "use the index's build-time default" for the overridable
    fields; a default-constructed ``QuerySpec()`` is the cacheable serving
    fast path.
    """

    k: Optional[int] = None            # top-k override (None = store cfg.k)
    mode: str = "auto"                 # auto | fused | rounds driver
    impl: str = "auto"                 # kernel impl (IMPLS; see kernels/ops)
    delta: Optional[float] = None      # failure-probability override
    max_rounds: Optional[int] = None   # pull-budget cap (racing rounds)
    eliminate: bool = True             # Alg. 1 elimination on/off
    warm_start: bool = True            # build-time CI variance priors
    prior_hint: Optional[Any] = None   # (Q, capacity) per-query variance
                                       # priors (near-repeat warm starts)
    cache: str = "use"                 # use | bypass | refresh the query LRU
    deadline: Optional[Any] = None     # stream.Deadline — wall-clock cap;
                                       # the request plane returns the
                                       # certified prefix at expiry
    budget: Optional[Any] = None       # stream.EffortBudget — pull-budget
                                       # cap (epochs / coord_ops)
    use_tuned: bool = True             # serve on the autotuned config
                                       # (repro.tune) when one is active;
                                       # False races on build-time defaults

    def __post_init__(self):
        from repro.api.stream import Deadline, EffortBudget
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r} (want one of {MODES})")
        if self.impl not in IMPLS:
            raise ValueError(f"unknown impl {self.impl!r} (want one of {IMPLS})")
        if self.cache not in CACHE_POLICIES:
            raise ValueError(f"unknown cache policy {self.cache!r} "
                             f"(want one of {CACHE_POLICIES})")
        if self.k is not None and self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.delta is not None and not (0.0 < self.delta < 1.0):
            raise ValueError(f"delta must be in (0, 1), got {self.delta}")
        if self.max_rounds is not None and self.max_rounds < 1:
            raise ValueError(f"max_rounds must be >= 1, got {self.max_rounds}")
        if self.deadline is not None and not isinstance(self.deadline,
                                                        Deadline):
            raise ValueError(
                f"deadline must be a repro.api.Deadline, got "
                f"{type(self.deadline).__name__}")
        if self.budget is not None and not isinstance(self.budget,
                                                      EffortBudget):
            raise ValueError(
                f"budget must be a repro.api.EffortBudget, got "
                f"{type(self.budget).__name__}")

    def bind(self, cfg):
        """Apply the spec's overrides to the store's build-time BMOConfig."""
        kw = {}
        if self.k is not None:
            kw["k"] = self.k
        if self.delta is not None:
            kw["delta"] = self.delta
        if self.max_rounds is not None:
            kw["max_rounds"] = self.max_rounds
        return dataclasses.replace(cfg, **kw) if kw else cfg

    @property
    def cacheable(self) -> bool:
        """Only default-contract races may hit or fill the query LRU: a k /
        δ / budget override, a seeded prior, or an anytime early-exit
        contract (deadline / effort budget — the result may be partial)
        changes what the cached result would certify."""
        return (self.k is None and self.delta is None
                and self.max_rounds is None and self.prior_hint is None
                and self.eliminate and self.warm_start
                and self.deadline is None and self.budget is None
                and self.use_tuned)


@dataclasses.dataclass(frozen=True)
class KNNResult:
    """Stable result schema of ``Index.query`` (host-side numpy).

    ``indices`` are GLOBAL slot ids (shard·stride + local behind a sharded
    store) — feed them to ``Index.payload`` lookups or ``Index.delete``.
    Cache-served rows report zero ``coord_ops``/``rounds``.
    """

    indices: Any                       # (Q, k) int   — global slot ids
    values: Any                        # (Q, k) float — ascending θ
    coord_ops: Any                     # (Q,) coordinate reads paid
    rounds: Any                        # (Q,) racing rounds paid
    n_exact: Any                       # (Q,) lazy exact evaluations
    cache_hits: int = 0                # rows served from the query LRU
    shard_coord_ops: Optional[List[float]] = None   # (S,) per-shard reads
    shard_rounds: Optional[List[float]] = None      # (S,) per-shard rounds

    def as_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["schema_version"] = SCHEMA_VERSION
        return out


@dataclasses.dataclass
class ServeStats:
    """Typed serving counters (the ``engine.stats`` contract since PR 4).

    ``as_dict()`` is the stable JSON schema benchmarks emit; ``__getitem__``
    additionally accepts the pre-PR-4 stringly keys (``knn_cache_hits``, …)
    so downstream dict-style consumers keep working.
    """

    races: int = 0             # batched races launched
    raced_queries: int = 0     # cache misses that actually paid a race
    cache_hits: int = 0
    cache_misses: int = 0
    cache_entries: int = 0
    near_hits: int = 0         # near-repeat CI warm starts
    compactions: int = 0
    reshards: int = 0          # live re-shard admin ops
    replicas: int = 1          # read replicas serving the fan-out
    shard_coord_ops: Optional[List[float]] = None  # cumulative per shard
    shard_rounds: Optional[List[float]] = None     # max per shard
    # -- request-plane telemetry (schema v2, DESIGN.md §7.4) ---------------
    plane_submitted: int = 0   # tickets submitted
    plane_admitted: int = 0    # tickets admitted into a race group
    plane_completed: int = 0   # tickets finished (any terminal reason)
    plane_shed: int = 0        # tickets shed at admission (backpressure)
    plane_deadline_exits: int = 0   # terminated at the wall-clock deadline
    plane_budget_exits: int = 0     # terminated at the effort budget
    plane_readmitted: int = 0  # tickets re-raced after a mutation fence
    plane_epochs: int = 0      # scheduler epochs run
    plane_queue_depth: int = 0      # tickets waiting for admission (now)
    plane_active: int = 0      # tickets racing (now)
    # 0.0 (never None/NaN) when no terminal latency landed in the window yet
    plane_latency_p50_ms: float = 0.0   # terminal latency percentiles
    plane_latency_p95_ms: float = 0.0
    plane_latency_p99_ms: float = 0.0
    # -- observability (schema v3, DESIGN.md §8) ---------------------------
    obs_events: int = 0        # trace events recorded (ring-buffer total)
    obs_event_drops: int = 0   # events overwritten before export
    obs_epoch_ms: Optional[dict] = None    # race-epoch histogram snapshot
    obs_latency_ms: Optional[dict] = None  # ticket-latency histogram snap
    # -- δ-audit / SLO (schema v5, DESIGN.md §10) --------------------------
    audit_sampled: int = 0     # query rows shadow-audited so far
    audit_mismatches: int = 0  # audited rows violating the 1-δ contract
    # 1.0 = "no claim yet": the Wilson bound carries no evidence until
    # rows have actually been audited (and is 1.0 with auditing off)
    audit_err_upper: float = 1.0
    audit_pending: int = 0     # sampled tickets awaiting the oracle
    slo_alerts: int = 0        # burn-rate alerts fired (lifetime)
    serving_fallback: bool = False  # tuned config forced off (recall guard)
    retune_requested: bool = False  # an Index.tune() re-race is flagged
    # -- fleet rollup (schema v6, DESIGN.md §11) ---------------------------
    fleet_namespaces_resident: int = 0  # namespaces open in memory (now)
    fleet_namespaces_evicted: int = 0   # namespaces checkpointed cold (now)
    fleet_reloads: int = 0              # cold reloads paid (lifetime)
    ns_queue_depth: Optional[dict] = None  # namespace -> waiting tickets

    _LEGACY = {
        "knn_races": "races",
        "knn_raced_queries": "raced_queries",
        "knn_cache_hits": "cache_hits",
        "knn_cache_misses": "cache_misses",
        "knn_cache_entries": "cache_entries",
        "knn_near_hits": "near_hits",
        "index_compactions": "compactions",
        "knn_shard_coord_ops": "shard_coord_ops",
        "knn_shard_rounds": "shard_rounds",
    }

    def as_dict(self) -> dict:
        out = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self)}
        out["schema_version"] = SCHEMA_VERSION
        return out

    def __getitem__(self, key: str):
        name = self._LEGACY.get(key, key)
        if name.startswith("_") or not hasattr(self, name):
            raise KeyError(key)
        return getattr(self, name)

    def __contains__(self, key) -> bool:
        try:
            self[key]
        except (KeyError, TypeError):
            return False
        return True


@dataclasses.dataclass(frozen=True)
class CachePolicy:
    """Query-LRU policy (lifted out of ServeEngine): exact-byte repeats are
    served from memory; a *near* repeat (cosine ≥ ``near_threshold``) still
    races but has its CI variance priors seeded from the cached neighbour.
    ``capacity=0`` disables caching entirely."""

    capacity: int = 256
    near_threshold: float = 0.95     # 0 disables near-repeat warm starts
    near_prior_scale: float = 0.25   # variance tightening on seeded arms

    def __post_init__(self):
        if self.capacity < 0:
            raise ValueError(f"capacity must be >= 0, got {self.capacity}")
        if self.near_threshold > 1.0:
            raise ValueError("near_threshold is a cosine similarity; "
                             f"got {self.near_threshold}")


@dataclasses.dataclass(frozen=True)
class CompactionPolicy:
    """Tombstone-debt policy (lifted out of ServeEngine): rebuild the slot
    layout when the dead fraction crosses ``threshold`` AND capacity would
    actually shrink. ``threshold >= 1`` disables auto-compaction."""

    threshold: float = 0.5

    def __post_init__(self):
        if self.threshold <= 0:
            raise ValueError(f"threshold must be > 0, got {self.threshold}")
