"""``Fleet`` — many named namespaces, one mesh, one request plane
(DESIGN.md §11.1).

A namespace is one ``repro.api.Index`` (single-shard or mesh-spanning)
plus its durable state under ``<root>/ns/<name>/`` (checkpoint, payload,
tuned sidecar). The fleet owns the routing table, an LRU residency set
(at most ``max_resident`` namespaces materialized; the rest live as
checkpoints and reload transparently on next touch), the shared
namespace-keyed ``QueryCache``, and the placement plan that bin-packs
sharded namespaces onto the device mesh.

Serving goes through ONE shared ``RequestPlane``: construct it with
``fleet.serve()`` (or ``RequestPlane(router=fleet)``) and submit tickets
with a ``namespace=`` label — admission fairness, per-namespace
``max_queue`` quota and shed all ride the existing per-tenant machinery
at ``(tenant, namespace)`` granularity, and the plane's ``namespace_load``
guard keeps the fleet from evicting a namespace with in-flight tickets.

Durability contract: ``create`` checkpoints the namespace eagerly and
every eviction re-checkpoints iff the epoch moved since the last save
(both through the crash-safe staged-directory publish), the manifest
(``fleet.json``) is rewritten atomically after every membership/placement
change, and ``Fleet.open(root)`` recovers the whole fleet — namespaces,
placements, tuned sidecars, payloads — without materializing any index.
"""
from __future__ import annotations

import dataclasses
import os
import re
import shutil
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.api import Index
from repro.api.cache import QueryCache
from repro.fleet.manifest import load_manifest, save_manifest
from repro.fleet.placement import plan_placement
from repro.utils import get_logger

log = get_logger("repro.fleet")

#: filesystem- and metric-label-safe namespace names (no NUL — the cache
#: key prefix relies on that — no separators, no dot-prefixed traversal)
_NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,127}$")

NS_SUBDIR = "ns"


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet-level knobs (per-namespace overrides ride ``create``)."""

    max_resident: int = 8          # namespaces materialized at once
    cache_capacity: int = 1024     # shared namespace-keyed query LRU
    default_max_queue: Optional[int] = None  # per-namespace admission bound
                                   # (None = the plane's own max_queue)

    def __post_init__(self):
        if self.max_resident < 1:
            raise ValueError(
                f"max_resident must be >= 1, got {self.max_resident}")
        if self.cache_capacity < 0:
            raise ValueError(
                f"cache_capacity must be >= 0, got {self.cache_capacity}")


class _NsState(object):
    """Routing-table row: the (maybe materialized) index + its metadata."""

    def __init__(self, name: str, meta: dict,
                 index: Optional[Index] = None):
        self.name = name
        self.meta = meta          # shards/device_offset/max_queue/n_live/kind
        self.index = index        # None while evicted (checkpoint on disk)
        self.last_used = 0        # fleet touch counter (LRU recency)
        self.saved_epoch = -1     # index epoch at the last checkpoint


class Fleet:
    """The namespace fleet handle. See the module docstring; construct
    with ``Fleet(root)`` (fresh or adopt an existing root) or
    ``Fleet.open(root)`` (strict: the manifest must exist)."""

    def __init__(self, root: str, config: Optional[FleetConfig] = None):
        self.root = root
        self.config = config if config is not None else FleetConfig()
        os.makedirs(os.path.join(root, NS_SUBDIR), exist_ok=True)
        self._ns: Dict[str, _NsState] = {}
        self._cache = (QueryCache(self.config.cache_capacity)
                       if self.config.cache_capacity > 0 else None)
        self._clock = 0           # monotone touch counter
        self._reloads = 0
        self._evictions = 0
        self.plane = None         # attached by RequestPlane(router=self)
        doc = load_manifest(root)
        if doc is not None:
            for name, rec in doc["namespaces"].items():
                self._ns[name] = _NsState(name, dict(rec))

    # -- constructors --------------------------------------------------------

    @classmethod
    def open(cls, root: str,
             config: Optional[FleetConfig] = None) -> "Fleet":
        """Recover a fleet from its root. Strict: a missing/invalid
        manifest raises instead of silently starting an empty fleet over
        data it cannot see. Namespaces materialize lazily on first touch."""
        if load_manifest(root) is None:
            raise FileNotFoundError(
                f"no fleet manifest at {root!r} — is this a fleet root?")
        return cls(root, config)

    # -- plumbing ------------------------------------------------------------

    def _dir(self, name: str) -> str:
        return os.path.join(self.root, NS_SUBDIR, name)

    def _check_name(self, name: str) -> None:
        if not _NAME_RE.match(name):
            raise ValueError(
                f"bad namespace name {name!r} (want {_NAME_RE.pattern})")

    def _state(self, name: str) -> _NsState:
        st = self._ns.get(name)
        if st is None:
            raise KeyError(f"unknown namespace {name!r} "
                           f"(have {sorted(self._ns)})")
        return st

    def _touch(self, st: _NsState) -> None:
        self._clock += 1
        st.last_used = self._clock

    def _adopt(self, st: _NsState, index: Index) -> None:
        """Wire a materialized index into the fleet: the SHARED namespace-
        keyed query cache replaces the handle's private one, so exact/near
        repeats stay warm across evict/reload while two namespaces can
        never exchange rows (the cache key carries the namespace)."""
        index._cache = self._cache
        index._cache_ns = st.name
        st.index = index
        self._touch(st)

    def _manifest_records(self) -> dict:
        recs = {}
        for name, st in self._ns.items():
            meta = dict(st.meta)
            if st.index is not None:
                meta["n_live"] = int(st.index.n_live)
                meta["shards"] = int(st.index.n_shards)
                meta["kind"] = st.index.kind
            recs[name] = meta
        return recs

    def _save_manifest(self) -> None:
        save_manifest(self.root, self._manifest_records())

    def _checkpoint(self, st: _NsState) -> bool:
        """Persist a resident namespace iff its epoch moved since the last
        save (a clean namespace's checkpoint is already on disk — eviction
        is then free). Crash-safe via the staged-directory publish."""
        if st.index is None:
            return False
        if st.saved_epoch == st.index.epoch:
            return False
        st.index.save(self._dir(st.name))
        st.saved_epoch = st.index.epoch
        st.meta["n_live"] = int(st.index.n_live)
        return True

    # -- lifecycle -----------------------------------------------------------

    def create(self, name: str, corpus, cfg, rng=None, *, shards: int = 1,
               payload=None, max_queue: Optional[int] = None,
               **build_kw) -> Index:
        """Build + register + eagerly checkpoint a namespace. Build kwargs
        (``placement=``, ``capacity=``, …) pass through to
        ``Index.build``. ``max_queue`` bounds THIS namespace's admission
        queue on the shared plane (None = fleet/plane default)."""
        self._check_name(name)
        if name in self._ns:
            raise ValueError(f"namespace {name!r} already exists — "
                             "drop() it first")
        if self._cache is not None:
            # defensive: a crashed drop may have left stale cached rows
            self._cache.evict_namespace(name)
        index = Index.build(corpus, cfg, rng, shards=shards,
                            payload=payload, **build_kw)
        st = _NsState(name, {
            "shards": int(index.n_shards),
            "device_offset": 0,
            "max_queue": (max_queue if max_queue is not None
                          else self.config.default_max_queue),
            "n_live": int(index.n_live),
            "kind": index.kind,
        })
        self._adopt(st, index)
        self._ns[name] = st
        self._checkpoint(st)       # durable from birth: open() can see it
        self._save_manifest()
        self._maybe_evict(exclude=name)
        return index

    def get(self, name: str) -> Index:
        """The namespace's ``Index``, materializing it from its checkpoint
        if it was evicted (lazy open-on-access) and bumping LRU recency."""
        return self.resolve(name)

    def resolve(self, name: str) -> Index:
        """Router hook for ``RequestPlane``: same contract as ``get``."""
        st = self._state(name)
        if st.index is None:
            self._reload(st)
        else:
            self._touch(st)
        return st.index

    def peek(self, name: str) -> Optional[Index]:
        """The index IF resident, else None — never triggers a reload and
        never bumps recency (telemetry/tests)."""
        return self._state(name).index

    def drop(self, name: str) -> None:
        """Remove a namespace: routing entry, checkpoint directory, and its
        slice of the shared query cache (a later namespace reusing the name
        must start cold — the cache-poisoning regression in tests)."""
        st = self._state(name)
        if self.plane is not None and self.plane.namespace_load().get(name):
            raise RuntimeError(
                f"namespace {name!r} has in-flight tickets — drain before "
                "drop()")
        del self._ns[name]
        st.index = None
        if self._cache is not None:
            self._cache.evict_namespace(name)
        shutil.rmtree(self._dir(name), ignore_errors=True)
        self._save_manifest()

    # -- residency / eviction ------------------------------------------------

    @property
    def namespaces(self) -> List[str]:
        return sorted(self._ns)

    @property
    def resident(self) -> List[str]:
        return sorted(n for n, s in self._ns.items() if s.index is not None)

    @property
    def resident_count(self) -> int:
        return sum(1 for s in self._ns.values() if s.index is not None)

    @property
    def evicted_count(self) -> int:
        return len(self._ns) - self.resident_count

    @property
    def reload_count(self) -> int:
        return self._reloads

    @property
    def eviction_count(self) -> int:
        return self._evictions

    def namespace_max_queue(self, name: str) -> Optional[int]:
        """Per-namespace admission bound for the shared plane (router
        hook); None defers to the plane's own ``max_queue``."""
        st = self._ns.get(name)
        return None if st is None else st.meta.get("max_queue")

    def evict(self, name: str) -> bool:
        """Checkpoint + free one namespace. Refuses (returns False) when
        it is already cold or has in-flight tickets on the attached plane —
        eviction must be invisible to callers, so it only takes quiesced
        namespaces. The shared cache KEEPS the namespace's entries: the
        reload restores a bit-identical store, so they stay valid (drop()
        is the path that purges them)."""
        st = self._state(name)
        if st.index is None:
            return False
        if self.plane is not None and self.plane.namespace_load().get(name):
            return False
        self._checkpoint(st)
        st.index = None
        self._evictions += 1
        self._save_manifest()
        log.info("evicted namespace %r (resident=%d/%d)", name,
                 self.resident_count, self.config.max_resident)
        return True

    def _maybe_evict(self, exclude: Optional[str] = None) -> int:
        """LRU-evict until at most ``max_resident`` namespaces are
        materialized. Busy namespaces are skipped (never evicted out from
        under their tickets); ``exclude`` protects the namespace that
        triggered the scan (it is the most recently touched by
        definition)."""
        evicted = 0
        while self.resident_count > self.config.max_resident:
            cands = sorted(
                (s for s in self._ns.values()
                 if s.index is not None and s.name != exclude),
                key=lambda s: s.last_used)
            progressed = False
            for st in cands:
                if self.evict(st.name):
                    evicted += 1
                    progressed = True
                    break
            if not progressed:      # everything live is busy or excluded
                break
        return evicted

    def enforce_residency(self) -> int:
        """Re-run the LRU eviction scan and return how many namespaces it
        freed. The plane materializes a namespace at ``submit`` and the
        guard never takes one with in-flight tickets, so a burst of cold
        traffic can transiently push the resident set past ``max_resident``
        until those tickets drain — serve loops call this between steps to
        pull the set back to budget as soon as namespaces quiesce."""
        return self._maybe_evict()

    def _reload(self, st: _NsState) -> None:
        """Materialize an evicted namespace from its checkpoint (payload +
        tuned sidecar restore ride ``Index.load``), re-apply its planned
        device offset, and rejoin the residency set (possibly evicting the
        coldest other namespace to stay within ``max_resident``)."""
        index = Index.load(self._dir(st.name))
        off = int(st.meta.get("device_offset", 0))
        if off and index.sharded:
            # fresh handle — placement binds before any launch, no fence
            # repro-lint: allow[epoch-fence]
            index._store = dataclasses.replace(index._store,
                                               device_offset=off)
        self._adopt(st, index)
        st.saved_epoch = index.epoch
        self._reloads += 1
        log.info("reloaded namespace %r (n_live=%d)", st.name, index.n_live)
        self._maybe_evict(exclude=st.name)

    # -- placement -----------------------------------------------------------

    def footprints(self) -> Dict[str, tuple]:
        """namespace → (n_shards, live_rows), from the live index when
        resident, else the manifest record."""
        out = {}
        for name, st in self._ns.items():
            if st.index is not None:
                out[name] = (st.index.n_shards, int(st.index.n_live))
            else:
                out[name] = (int(st.meta.get("shards", 1)),
                             int(st.meta.get("n_live", 0)))
        return out

    def rebalance(self, n_devices: Optional[int] = None) -> Dict[str, int]:
        """Re-plan namespace placement by live-row footprint and apply it:
        resident sharded namespaces whose device window moved are swapped
        onto the new offset through the epoch fence; cold namespaces pick
        their new offset up at reload. Returns the plan. Shard-count
        changes are the caller's lever (``Fleet.reshard``) — this only
        moves windows."""
        n_devices = n_devices or jax.device_count()
        plan = plan_placement(self.footprints(), n_devices)
        for name, off in plan.items():
            st = self._ns[name]
            if st.meta.get("device_offset", 0) == off:
                continue
            st.meta["device_offset"] = off
            if st.index is not None and st.index.sharded:
                st.index._swap(dataclasses.replace(st.index.store,
                                                   device_offset=off))
        self._save_manifest()
        return plan

    def reshard(self, name: str, n_shards: int) -> np.ndarray:
        """Change one namespace's shard count (the expensive rebalance
        primitive — ``repro.api.admin.live_reshard`` under the hood)."""
        st = self._state(name)
        old_ids = self.resolve(name).reshard(n_shards)
        st.meta["shards"] = int(st.index.n_shards)
        self._save_manifest()
        return old_ids

    # -- serving / persistence ----------------------------------------------

    def serve(self, config=None, *, obs=None, default: Optional[str] = None):
        """One shared ``RequestPlane`` over every namespace (tickets carry
        ``namespace=``); also attached as the fleet's eviction guard.

        ``default=`` binds that namespace's live handle as the plane's
        default index: un-namespaced submits route to it, and the plane's
        δ-auditor (``PlaneConfig.audit_rate``) audits its traffic — other
        namespaces stay outside the auditor's contract (``note_skip``).
        The binding is by handle identity, so if the default namespace is
        ever evicted and reloaded the auditor stops sampling (gracefully —
        racing stays correct) until a new plane is built."""
        from repro.serve.plane import RequestPlane
        index = self.get(default) if default is not None else None
        return RequestPlane(index, config=config, obs=obs, router=self)

    def attach_plane(self, plane) -> None:
        """Called by ``RequestPlane(router=self)`` — wires the in-flight
        guard ``plane.namespace_load`` into eviction decisions."""
        self.plane = plane

    def flush(self) -> int:
        """Checkpoint every dirty resident namespace + the manifest
        (shutdown/suspend path). Returns namespaces written."""
        wrote = sum(1 for st in self._ns.values() if self._checkpoint(st))
        self._save_manifest()
        return wrote

    def stats(self) -> dict:
        """Fleet-level rollup (the ``health_snapshot`` fleet section)."""
        return {
            "namespaces": len(self._ns),
            "resident": self.resident_count,
            "evicted": self.evicted_count,
            "reloads": self._reloads,
            "evictions": self._evictions,
            "max_resident": self.config.max_resident,
            "cache_entries": (len(self._cache)
                              if self._cache is not None else 0),
            "ns_queue_depth": (self.plane.ns_queue_depth()
                               if self.plane is not None else {}),
        }

    def __contains__(self, name: str) -> bool:
        return name in self._ns

    def __len__(self) -> int:
        return len(self._ns)

    def __repr__(self) -> str:
        return (f"Fleet(root={self.root!r}, namespaces={len(self._ns)}, "
                f"resident={self.resident_count}/"
                f"{self.config.max_resident})")
