"""repro.index.sharded — one persistent racing index spanning the mesh
(DESIGN.md §5).

The paper's O((n+d)·log²(nd/δ)) bound is per machine; past one device the
slot axis of the PR-1 ``IndexStore`` is partitioned across a named mesh axis
("shards") and raced *shard-locally*:

  * **Addressing** (placement.py): every shard owns ``stride`` slots and
    ``global_id = shard · stride + local_slot`` — two integer ops on device.
    The stride is uniform across shards and changes only on global growth /
    compaction / re-shard events, each of which returns an old→new global-id
    map (the ``mutable.compact`` contract) for payload reindexing.
  * **Racing**: dense/rotated boxes run the PR-2 fused epoch race under
    ``shard_map`` — each shard keeps its own survivor frontier over its
    ``stride`` slots and certifies its own local top-k. The host epoch loop
    is shared: one fused launch per shard per epoch, shard-local survivor
    compaction at a common bucket width, and a **cross-shard pull-budget
    reallocator**: the per-epoch fused round count R scales with the global
    pull budget over the *total* surviving work, so when a shard certifies
    and goes idle its share of the budget shifts to the still-racing shards
    (Neufeld et al.-style bandit allocation across estimators). Sparse boxes
    run the per-round driver shard-locally in a single collective program.
  * **Merge**: θ is a per-coordinate average, so the global top-k is
    contained in the union of per-shard certified top-ks (the
    ``core/distributed.py`` argument). One ``all_gather`` of each shard's
    (values, global ids) over the shard axis + a replicated top-k reduce
    finishes the query. A shard with fewer than k live slots certifies its
    whole live set (the drivers' candidate-exhaustion ``done`` rule) and
    pads its contribution with +inf values.

Failure budget: shard-local races run at δ/S, so the per-interval budget is
δ′ = (δ/S)/(stride·MAX_PULLS) = δ/(n_total·MAX_PULLS) — exactly the
single-shard union bound; CI radii match the single-shard driver arm for
arm (the variance *pool* is shard-local, which only changes the empirical
shrinkage target).

Lifecycle: ``build_sharded_index`` (round-robin or least-loaded placement),
``sharded_insert`` (routed to the least-loaded shard, uniform capacity
growth), ``sharded_delete`` (tombstones), ``sharded_maybe_compact`` (global
threshold policy, per-shard rebuild, global-id remap), and persistence as
per-shard checkpoint directories plus a manifest — an index saved at S
shards reloads at S′ ≠ S (``load_sharded_index(shards=S')`` re-shards the
live rows and returns the global-id remap).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import BMOConfig
from repro.core import confidence as conf
from repro.core.bmo_nn import sparse_exact_theta
from repro.core.datasets import SparseDataset, next_pow2
from repro.index import placement as plc
from repro.index.batched_race import (_dense_exact_theta, _frontier_ci,
                                      draw_blocks,
                                      _fused_epoch_step, _fused_init,
                                      _sparse_index_knn, batched_race_topk)
from repro.index.builder import build_index
from repro.index.frontier import (FrontierState, bucket_width,
                                  compact_frontier, floor_width, pow2_floor)
from repro.index.mutable import _take_pad, _widen_sparse
from repro.index import mutable
from repro.index.store import IndexStore
from repro.kernels import ops as kops
from repro.obs import get_obs
from repro.utils import get_logger
from repro.utils.hostsync import host_fetch

log = get_logger("repro.index")

AXIS = "shards"
MANIFEST = "manifest.msgpack"
INF = jnp.inf


class ShardedKNNResult(NamedTuple):
    """KNNResult-compatible (duck-typed on the serving path) plus the
    per-shard counters the engine surfaces as ``knn_shard_*`` stats."""
    indices: jax.Array          # (Q, k) GLOBAL slot ids
    values: jax.Array           # (Q, k) ascending θ
    coord_ops: jax.Array        # (Q,) summed over shards
    rounds: jax.Array           # (Q,) max over shards
    n_exact: jax.Array          # (Q,) summed over shards
    shard_coord_ops: jax.Array  # (S,) total coordinate-ops per shard
    shard_rounds: jax.Array     # (S,) max rounds per shard


@dataclasses.dataclass
class ShardedIndexStore:
    """S per-shard ``IndexStore``s with uniform capacity (the stride), one
    logical index. Immutable like IndexStore — every mutation builds a new
    instance, so engine-side cache invalidation-by-identity keeps working."""
    shards: List[IndexStore]
    placement: str = "round_robin"
    device_offset: int = 0    # first visible device of this store's mesh —
                              # read replicas (repro.api.admin) place copies
                              # of the same shards on disjoint device slices

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def stride(self) -> int:
        return self.shards[0].capacity

    @property
    def capacity(self) -> int:
        return self.n_shards * self.stride

    @property
    def n_live(self) -> int:
        return sum(s.n_live for s in self.shards)

    @property
    def kind(self) -> str:
        return self.shards[0].kind

    @property
    def cfg(self) -> BMOConfig:
        return self.shards[0].cfg

    @property
    def d(self) -> int:
        return self.shards[0].d

    @property
    def block(self) -> int:
        return self.shards[0].block

    @property
    def prior_weight(self) -> float:
        return self.shards[0].prior_weight

    @property
    def prior_var(self) -> jax.Array:
        """(capacity,) per-arm priors in global-id order (shard-major)."""
        return jnp.concatenate([s.prior_var for s in self.shards])

    @property
    def live_per_shard(self) -> List[int]:
        return [s.n_live for s in self.shards]

    @property
    def mesh(self) -> Mesh:
        """1-D mesh over the first S local devices (cached per instance)."""
        if "_mesh" not in self.__dict__:
            devs = jax.devices()
            lo, hi = self.device_offset, self.device_offset + self.n_shards
            if len(devs) < hi:
                raise RuntimeError(
                    f"{self.n_shards} index shards at device offset "
                    f"{self.device_offset} need {hi} devices but only "
                    f"{len(devs)} are visible — on CPU run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{hi}")
            self._mesh = Mesh(np.asarray(devs[lo:hi]), (AXIS,))
        return self._mesh

    def device_arrays(self) -> dict:
        """Per-shard arrays assembled into mesh-sharded global arrays, each
        shard's piece on its own device (cached per instance; mutations
        build new instances so this self-invalidates). The dense corpus is
        concatenated, (S·stride, d_pad) P("shards"): every device's piece
        IS its shard's ``x``, so the corpus is never copied or gathered on
        one device. The small per-slot arrays are stacked, (S, …)."""
        if "_dev" not in self.__dict__:
            sh = NamedSharding(self.mesh, P(AXIS))
            devs = list(self.mesh.devices.flat)

            def assemble(name, stack):
                parts = [jax.device_put(getattr(s, name), dv)
                         for s, dv in zip(self.shards, devs)]
                if stack:
                    parts = [p[None] for p in parts]
                shape = ((len(parts) * parts[0].shape[0],)
                         + parts[0].shape[1:])
                return jax.make_array_from_single_device_arrays(
                    shape, sh, parts)

            names = (("indices", "values", "nnz") if self.kind == "sparse"
                     else ()) + ("alive", "prior_var")
            self._dev = {name: assemble(name, True) for name in names}
            if self.kind != "sparse":
                self._dev["x"] = assemble("x", False)
        return self._dev

    def prepare_queries(self, queries) -> jax.Array:
        return self.shards[0].prepare_queries(queries)

    def query(self, queries, rng, *, k=None, impl: str = "auto"):
        return sharded_index_knn(self, queries, rng, k=k, impl=impl)


# ---------------------------------------------------------------------------
# build / mutate
# ---------------------------------------------------------------------------


def build_sharded_index(corpus, cfg: BMOConfig, rng: jax.Array, *,
                        shards: int, placement: str = "round_robin",
                        capacity: Optional[int] = None,
                        ) -> Tuple[ShardedIndexStore, np.ndarray]:
    """Partition ``corpus`` (n, d) across ``shards`` per-shard IndexStores.
    Returns ``(store, global_ids)`` with ``global_ids[i]`` the global slot of
    corpus row i — align side payloads with it. ``capacity``: total slots
    (split evenly); default next-pow2 of the heaviest shard.

    All shards share one rotation: ``build_index`` draws the §IV-B sign
    vector from ``rng`` alone, so passing the *same* key to every shard
    build caches the same rotation everywhere (queries are rotated once).
    When the process sees at least ``shards`` devices, shard s is built on
    device s — the device the mesh places it on — so no device ever holds
    more than its own shard."""
    corpus = np.asarray(corpus)
    n = corpus.shape[0]
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    sid = plc.assign(placement, np.zeros(shards, np.int64), n)
    rows_of = [np.nonzero(sid == s)[0] for s in range(shards)]
    per_cap = (capacity // shards if capacity
               else next_pow2(max(1, max(len(r) for r in rows_of))))
    devs = jax.devices()
    stores = []
    for s, rows in enumerate(rows_of):
        with jax.default_device(devs[s] if shards <= len(devs) else None):
            stores.append(build_index(corpus[rows], cfg, rng,
                                      capacity=per_cap))
    if cfg.sparse:                     # uniform padded-CSR width across shards
        m_max = max(s.m for s in stores)
        stores = [_widen_sparse(s, m_max) for s in stores]
    gids = np.empty((n,), np.int64)
    for s, rows in enumerate(rows_of):
        gids[rows] = s * per_cap + np.arange(len(rows))
    log.info("built sharded %s index: n=%d shards=%d stride=%d (%s)",
             stores[0].kind, n, shards, per_cap, placement)
    return ShardedIndexStore(stores, placement), gids


def _grow_to(shard: IndexStore, cap: int) -> IndexStore:
    """Pad one shard to an exact capacity (uniform-stride growth)."""
    extra = cap - shard.capacity
    if extra <= 0:
        return shard
    kw = dict(alive=jnp.pad(shard.alive, (0, extra)),
              prior_var=jnp.pad(shard.prior_var, (0, extra)))
    if shard.kind == "sparse":
        kw.update(indices=jnp.pad(shard.indices, ((0, extra), (0, 0)),
                                  constant_values=shard.d),
                  values=jnp.pad(shard.values, ((0, extra), (0, 0))),
                  nnz=jnp.pad(shard.nnz, (0, extra)))
    else:
        kw.update(x=jnp.pad(shard.x, ((0, extra), (0, 0))))
    return dataclasses.replace(shard, **kw)


def _stride_remap(S: int, old_stride: int, new_stride: int) -> np.ndarray:
    """old→new global-id map for a stride change (compact contract:
    ``old_ids[new_gid]`` = previous gid, −1 where no slot existed)."""
    old_ids = np.full((S * new_stride,), -1, np.int64)
    keep = min(old_stride, new_stride)
    for s in range(S):
        old_ids[s * new_stride: s * new_stride + keep] = \
            s * old_stride + np.arange(keep)
    return old_ids


def sharded_insert(store: ShardedIndexStore, rows
                   ) -> Tuple[ShardedIndexStore, np.ndarray,
                              Optional[np.ndarray]]:
    """Insert (B, d) dense rows, each routed to the least-loaded shard.
    Returns ``(store, global_ids (B,), old_ids)`` — ``old_ids`` is None
    unless a shard's growth changed the stride (then it is the global
    old→new slot map; reindex payloads with it before using the new ids)."""
    rows = np.asarray(rows, np.float32)
    if rows.ndim == 1:
        rows = rows[None]
    bsz = rows.shape[0]
    S = store.n_shards
    old_stride = store.stride
    sid = plc.assign_least_loaded([s.n_live for s in store.shards], bsz)
    shards = list(store.shards)
    local_slots = np.empty((bsz,), np.int64)
    for s in set(sid.tolist()):
        mask = sid == s
        shards[s], slots = mutable.insert(shards[s], rows[mask])
        local_slots[mask] = slots
    new_stride = max(s.capacity for s in shards)
    if new_stride != old_stride:
        shards = [_grow_to(s, new_stride) for s in shards]
    if store.kind == "sparse":
        m_max = max(s.m for s in shards)
        shards = [_widen_sparse(s, m_max) for s in shards]
    gids = sid.astype(np.int64) * new_stride + local_slots
    old_ids = (None if new_stride == old_stride
               else _stride_remap(S, old_stride, new_stride))
    if old_ids is not None:
        log.info("sharded index stride grew %d -> %d (global-id remap)",
                 old_stride, new_stride)
    return dataclasses.replace(store, shards=shards), gids, old_ids


def sharded_delete(store: ShardedIndexStore, global_ids) -> ShardedIndexStore:
    """Tombstone global slots (O(1) per shard)."""
    gids = np.atleast_1d(np.asarray(global_ids, np.int64))
    stride = store.stride
    shards = list(store.shards)
    for s in np.unique(gids // stride):
        shards[s] = mutable.delete(shards[s], gids[gids // stride == s] % stride)
    return dataclasses.replace(store, shards=shards)


def tombstone_fraction(store: ShardedIndexStore) -> float:
    return 1.0 - store.n_live / max(store.capacity, 1)


def sharded_compact(store: ShardedIndexStore
                    ) -> Tuple[ShardedIndexStore, np.ndarray]:
    """Rebuild every shard's slot layout dropping tombstones, at a common
    (uniform-stride) capacity. Returns (store, old_ids) with the global
    old→new slot map (−1 for empty slots)."""
    S, old_stride = store.n_shards, store.stride
    live = [np.nonzero(np.asarray(s.alive))[0] for s in store.shards]
    new_stride = max(1, next_pow2(max(1, max(len(l) for l in live))))
    shards = []
    old_ids = np.full((S * new_stride,), -1, np.int64)
    for s, (shard, sl) in enumerate(zip(store.shards, live)):
        slj = jnp.asarray(sl)
        kw = dict(alive=jnp.arange(new_stride) < len(sl),
                  prior_var=_take_pad(shard.prior_var, slj, new_stride))
        if shard.kind == "sparse":
            kw.update(indices=_take_pad(shard.indices, slj, new_stride,
                                        fill=shard.d),
                      values=_take_pad(shard.values, slj, new_stride),
                      nnz=_take_pad(shard.nnz, slj, new_stride))
        else:
            kw.update(x=_take_pad(shard.x, slj, new_stride))
        shards.append(dataclasses.replace(shard, **kw))
        old_ids[s * new_stride: s * new_stride + len(sl)] = s * old_stride + sl
    log.info("compacted sharded index: stride %d -> %d (%d live)",
             old_stride, new_stride, store.n_live)
    return dataclasses.replace(store, shards=shards), old_ids


def sharded_maybe_compact(store: ShardedIndexStore, *,
                          threshold: float = 0.5
                          ) -> Tuple[ShardedIndexStore, Optional[np.ndarray]]:
    """Global auto-compaction policy (the ``mutable.maybe_compact`` contract
    lifted to the sharded store): rebuild only when the global tombstone
    fraction crosses ``threshold`` AND the uniform stride actually shrinks."""
    if (store.capacity and tombstone_fraction(store) > threshold
            and next_pow2(max(max(store.live_per_shard), 1)) < store.stride):
        return sharded_compact(store)
    return store, None


# ---------------------------------------------------------------------------
# persistence: per-shard checkpoints + manifest, re-shard on load
# ---------------------------------------------------------------------------


def save_sharded_index(store: ShardedIndexStore, path: str, *,
                       extra=None) -> None:
    """path/shard_%04d/ (checkpoint layout, one per shard) + path/manifest.

    The whole directory — every shard, the manifest, and any ``extra``
    sidecars — is staged in a tmp sibling and published with one rename
    (``checkpoint.manager.staged_dir``): a crash mid-save leaves the
    previous index intact, never a mix of old and new shards."""
    import msgpack
    from repro import checkpoint
    with checkpoint.manager.staged_dir(path) as tmp:
        for s, shard in enumerate(store.shards):
            checkpoint.manager.save(os.path.join(tmp, f"shard_{s:04d}"),
                                    shard.arrays(), meta=shard.meta())
        manifest = {
            "version": 1,
            "n_shards": store.n_shards,
            "stride": store.stride,
            "placement": store.placement,
            "kind": store.kind,
            "live_per_shard": store.live_per_shard,
            "capacities": [s.capacity for s in store.shards],
        }
        with open(os.path.join(tmp, MANIFEST), "wb") as f:
            f.write(msgpack.packb(manifest))
        if extra is not None:
            extra(tmp)


def is_sharded_index_dir(path: str) -> bool:
    return os.path.exists(os.path.join(path, MANIFEST))


def read_manifest(path: str) -> dict:
    import msgpack
    with open(os.path.join(path, MANIFEST), "rb") as f:
        return msgpack.unpackb(f.read())


def load_sharded_index(path: str, *, shards: Optional[int] = None
                       ) -> Tuple[ShardedIndexStore, Optional[np.ndarray]]:
    """Load a saved sharded index; ``shards=S'`` re-shards on the way in.
    Returns ``(store, old_ids)`` — ``old_ids`` is None when the shard count
    is unchanged, else the old→new global-id map (compact contract)."""
    from repro import checkpoint
    manifest = read_manifest(path)
    S0 = int(manifest["n_shards"])
    stores = []
    for s in range(S0):
        sdir = os.path.join(path, f"shard_{s:04d}")
        stores.append(IndexStore.from_arrays(
            checkpoint.manager.load_arrays(sdir),
            checkpoint.manager.read_meta(sdir)))
    store = ShardedIndexStore(stores, manifest.get("placement", "round_robin"))
    if shards is None or shards == S0:
        return store, None
    return reshard(store, shards)


def reshard(store: ShardedIndexStore, n_shards: int
            ) -> Tuple[ShardedIndexStore, np.ndarray]:
    """Redistribute the live rows of ``store`` over ``n_shards`` shards
    (round-robin in ascending old-global-id order — deterministic, so a
    S→S′→S round trip is the identity on row *data*). Per-slot arrays (rows,
    priors, padded-CSR triplets) ride along untouched: the rotation is NOT
    redrawn, so rotated stores stay query-compatible. Returns
    ``(store, old_ids)`` with the global old→new slot map."""
    S0, stride0 = store.n_shards, store.stride
    alive = np.concatenate([np.asarray(s.alive) for s in store.shards])
    old_gids = np.nonzero(alive)[0]               # ascending global-id order
    n = len(old_gids)
    sid = plc.assign_round_robin(n, n_shards)
    counts = np.bincount(sid, minlength=n_shards)
    new_stride = max(1, next_pow2(max(1, int(counts.max(initial=1)))))

    def stacked(name):
        return np.concatenate([np.asarray(getattr(s, name))
                               for s in store.shards])

    proto = store.shards[0]
    names = (("indices", "values", "nnz") if store.kind == "sparse"
             else ("x",)) + ("prior_var",)
    data = {name: stacked(name)[old_gids] for name in names}

    shards = []
    old_ids = np.full((n_shards * new_stride,), -1, np.int64)
    for t in range(n_shards):
        rows = np.nonzero(sid == t)[0]            # ascending
        kw = dict(alive=jnp.arange(new_stride) < len(rows))
        for name in names:
            taken = jnp.asarray(data[name][rows])
            fill = proto.d if name == "indices" else 0
            kw[name] = _take_pad(taken, jnp.arange(len(rows)), new_stride,
                                 fill=fill)
        shards.append(dataclasses.replace(proto, **kw))
        old_ids[t * new_stride: t * new_stride + len(rows)] = old_gids[rows]
    log.info("re-sharded index: %d shards (stride %d) -> %d shards "
             "(stride %d), %d live rows", S0, stride0, n_shards, new_stride, n)
    return ShardedIndexStore(shards, store.placement), old_ids


# ---------------------------------------------------------------------------
# racing: shard-local races + certified all-gather merge
# ---------------------------------------------------------------------------


def flat_axis_index(axes):
    """Flattened index across one or more mesh axes (row-major)."""
    if isinstance(axes, str):
        return jax.lax.axis_index(axes)
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return idx


def merge_local_topk(vals, gids, axes, k: int):
    """All-gather every shard's certified local top-k over ``axes`` and
    reduce to the global top-k (the global top-k ⊆ union of local top-ks;
    invalid local entries must arrive as +inf). vals/gids (Q, k) →
    replicated (Q, k) (indices, values ascending)."""
    Q = vals.shape[0]
    vals_all = jax.lax.all_gather(vals, axes, tiled=True)     # (D·Q, k)
    gids_all = jax.lax.all_gather(gids, axes, tiled=True)
    D = vals_all.shape[0] // Q
    v = vals_all.reshape(D, Q, k).transpose(1, 0, 2).reshape(Q, D * k)
    g = gids_all.reshape(D, Q, k).transpose(1, 0, 2).reshape(Q, D * k)
    neg, pos = jax.lax.top_k(-v, k)
    return jnp.take_along_axis(g, pos, axis=1), -neg


def guard_local_topk(indices, values, alive):
    """Mask junk entries of a shard-local top-k before the merge: a shard
    with fewer than k live slots fills its missing entries from its (dead,
    pre-rejected) padding — elimination never rejects a live arm while
    fewer than k live candidates exist, so deadness is exactly the junk
    test. Their values become +inf so the merge ignores them."""
    return jnp.where(alive[indices], values, INF)


# Why the merge needs EXACT values (DESIGN.md §5.3): certification is an
# *ordering* guarantee within a shard — an accepted arm's mean is only known
# to within its final CI, and sharding makes local races easier (fewer close
# competitors per shard), so they stop with looser estimates than the
# single-shard race would. Merging estimates across shards then misorders
# near-ties. Each shard therefore exact-evaluates its ≤ k certified winners
# before the gather — S·k·d coordinate reads per query batch, the same O(d)
# term the paper's bound already pays per query — and the merged top-k is
# exact whenever every shard's local top-k set is (w.h.p. 1 − δ).


def model_exact_theta(x_loc, qs, sel, metric: str,
                      model_axis: Optional[str] = None):
    """Exact θ for selected slots when the coordinates are split over
    ``model_axis`` into equal slices: each device's θ is its slice's
    distance / slice width, and their pmean is the full distance / full
    width — the scale of the pmean'd block pulls. sel (Q, B) → (Q, B)."""
    th = _dense_exact_theta(x_loc, qs, sel, metric)
    if model_axis is not None:
        th = jax.lax.pmean(th, model_axis)
    return th


def local_dense_race(x_loc, qs, alive, prior, rng, *, cfg: BMOConfig,
                     block: int, d: int, impl: str, eliminate: bool,
                     prior_weight: float, model_axis: Optional[str] = None):
    """One shard's per-round (PR-1) batched race over its local slots —
    also the body ``core.distributed`` wraps, where pulls are additionally
    stratified over a model (coordinate) axis and pmean-reduced."""
    n_loc, d_loc = x_loc.shape
    nb_loc = d_loc // block
    Q = qs.shape[0]
    P_ = cfg.pulls_per_round

    def pull(sel, key):
        if model_axis is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(model_axis))
        blk = draw_blocks(key, sel.shape + (P_,), nb_loc)
        vals = kops.block_pull_multi(x_loc, qs, sel, blk, block=block,
                                     metric=cfg.metric, impl=impl)
        if model_axis is not None:
            vals = jax.lax.pmean(vals, model_axis)
        return vals

    def exact(sel):
        return model_exact_theta(x_loc, qs, sel, cfg.metric, model_axis)

    return batched_race_topk(
        pull, exact, n=n_loc, Q=Q,
        max_pulls=float(nb_loc), pull_cost=float(block),
        exact_cost=float(d_loc) if model_axis is not None else float(d),
        cfg=cfg, rng=rng, eliminate=eliminate,
        dead=~alive, prior_var=prior, prior_weight=prior_weight)


def _shard_delta(cfg: BMOConfig, S: int) -> BMOConfig:
    """δ/S per shard-local race ⇒ δ′ = δ/(S·stride·MAX_PULLS) per interval —
    the same union bound the single-shard driver runs at n_total slots."""
    return dataclasses.replace(cfg, delta=conf.shard_delta(cfg.delta, S))


def _squeeze(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def _unsqueeze(tree):
    return jax.tree_util.tree_map(lambda a: a[None], tree)


def _finish_local(vals, gids, coord_ops, rounds, n_exact, k: int):
    """Merge + per-query/per-shard stat reduction shared by every driver."""
    merged_idx, merged_vals = merge_local_topk(vals, gids, AXIS, k)
    coord_q = jax.lax.psum(coord_ops, AXIS)
    rounds_q = jax.lax.pmax(rounds, AXIS)
    nex_q = jax.lax.psum(n_exact, AXIS)
    shard_ops = jnp.sum(coord_ops)[None]
    shard_rounds = jnp.max(rounds)[None]
    return (merged_idx, merged_vals, coord_q, rounds_q, nex_q,
            shard_ops, shard_rounds)


_OUT_SPECS = (P(), P(), P(), P(), P(), P(AXIS), P(AXIS))


@functools.lru_cache(maxsize=None)
def _rounds_dense_fn(mesh, cfg, block, d, impl, eliminate, prior_weight,
                     stride):
    def body(x, qs, alive, prior, rng):
        alive, prior = alive[0], prior[0]
        rng = jax.random.fold_in(rng, jax.lax.axis_index(AXIS))
        res = local_dense_race(x, qs, alive, prior, rng, cfg=cfg, block=block,
                               d=d, impl=impl, eliminate=eliminate,
                               prior_weight=prior_weight)
        exact_vals = _dense_exact_theta(x, qs, res.indices, cfg.metric)
        vals = guard_local_topk(res.indices, exact_vals, alive)
        gids = jax.lax.axis_index(AXIS) * stride + res.indices
        coord_ops = res.coord_ops + float(cfg.k * d)
        return _finish_local(vals, gids, coord_ops, res.rounds,
                             res.n_exact, cfg.k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(), P(AXIS), P(AXIS), P()),
        out_specs=_OUT_SPECS, check_vma=False))


@functools.lru_cache(maxsize=None)
def _rounds_sparse_fn(mesh, cfg, d, eliminate, prior_weight, stride):
    def body(idx, val, nnz, alive, prior, qi, qv, qn, rng):
        idx, val, nnz, alive, prior = (idx[0], val[0], nnz[0], alive[0],
                                       prior[0])
        rng = jax.random.fold_in(rng, jax.lax.axis_index(AXIS))
        res = _sparse_index_knn(idx, val, nnz, alive, prior, qi, qv, qn, rng,
                                cfg=cfg, d=d, eliminate=eliminate,
                                prior_weight=prior_weight)
        ds = SparseDataset(indices=idx, values=val, nnz=nnz, d=d)
        exact_vals = jax.vmap(
            lambda qi_, qv_, s: sparse_exact_theta(ds, qi_, qv_, s)
        )(qi, qv, res.indices)
        vals = guard_local_topk(res.indices, exact_vals, alive)
        gids = jax.lax.axis_index(AXIS) * stride + res.indices
        coord_ops = res.coord_ops + jnp.sum(
            nnz[res.indices].astype(jnp.float32) + qn[:, None], axis=1)
        return _finish_local(vals, gids, coord_ops, res.rounds,
                             res.n_exact, cfg.k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS),
                  P(), P(), P(), P()),
        out_specs=_OUT_SPECS, check_vma=False))


# -- epoch-fused sharded driver ---------------------------------------------

_ST_SPEC = FrontierState(*([P(AXIS)] * len(FrontierState._fields)))


@functools.lru_cache(maxsize=None)
def _fused_init_fn(mesh, cfg, block, impl, prior_weight):
    def body(x, qs, alive, prior, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(AXIS))
        st, pool = _fused_init(x, qs, alive[0], prior[0], rng, cfg=cfg,
                               block=block, impl=impl,
                               prior_weight=prior_weight)
        return _unsqueeze(st), pool[None]

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(), P(AXIS), P(AXIS), P()),
        out_specs=(_ST_SPEC, P(AXIS)), check_vma=False))


@functools.lru_cache(maxsize=None)
def _fused_step_fn(mesh, cfg, block, d, impl, eliminate, prior_weight,
                   log_term, T):
    def body(x, qs, st, pool):
        st2, n_surv, done = _fused_epoch_step(
            x, qs, _squeeze(st), pool[0], cfg=cfg, block=block, d=d,
            impl=impl, eliminate=eliminate, prior_weight=prior_weight,
            log_term=log_term, T=T)
        return _unsqueeze(st2), n_surv[None], done[None]

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(), _ST_SPEC, P(AXIS)),
        out_specs=(_ST_SPEC, P(AXIS), P(AXIS)), check_vma=False))


@functools.lru_cache(maxsize=None)
def _fused_finalize_fn(mesh, cfg, log_term, prior_weight, stride, block, d,
                       metric):
    k = cfg.k

    def body(x, qs, st, pool):
        st = _squeeze(st)
        ci = _frontier_ci(st, cfg, log_term, pool[0], prior_weight)
        # local ranking with explicit junk detection: entries picked from
        # rejected/padding (only possible when the shard has < k live slots)
        # surface as +inf values, which the merge discards
        score = jnp.where(st.accepted & st.valid, st.mean - 1e9,
                          jnp.where(st.rejected | ~st.valid, INF,
                                    st.mean - ci))
        _, pos = jax.lax.top_k(-score, k)                     # (Q, k)
        slots = jnp.take_along_axis(st.ids, pos, axis=1)
        vals = _dense_exact_theta(x, qs, slots, metric)
        ok = jnp.take_along_axis(score, pos, axis=1) < INF
        vals = jnp.where(ok, vals, INF)
        gids = jax.lax.axis_index(AXIS) * stride + slots
        coord_ops = st.coord_ops + float(k * d)
        return _finish_local(vals, gids, coord_ops, st.rounds,
                             st.n_exact, k)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(), _ST_SPEC, P(AXIS)),
        out_specs=_OUT_SPECS, check_vma=False))


@functools.partial(jax.jit, static_argnames=("W_new",))
def _compact_stacked(st: FrontierState, *, W_new: int) -> FrontierState:
    """frontier.compact_frontier vmapped over the leading shard axis of the
    (S, Q, W)-stacked per-shard state — per-shard-local gathers, no
    collectives, one shared bucket width."""
    return jax.vmap(functools.partial(compact_frontier, W_new=W_new))(st)


def _sharded_fused_race(store: ShardedIndexStore, qs, prior_st, rng, *,
                        cfg: BMOConfig, impl: str, eliminate: bool,
                        prior_weight: float):
    """The PR-2 epoch-fused race run shard-locally under shard_map, with the
    host epoch loop shared across shards (DESIGN.md §5.2). Collectives per
    query: nothing during the race (each epoch launch is shard-local), one
    all-gather of (2·k fp32+int32 per shard) at the merge."""
    S, stride, mesh = store.n_shards, store.stride, store.mesh
    dev = store.device_arrays()
    x_st, alive_st = dev["x"], dev["alive"]
    block = store.block
    Q = qs.shape[0]
    k = cfg.k
    nb = x_st.shape[1] // block
    P_ = cfg.pulls_per_round
    # δ′ at the GLOBAL slot count — identical per-arm budget to the
    # single-shard fused driver over the same corpus
    log_term = float(np.log(2.0 / conf.delta_prime(cfg.delta, S * stride, nb)))
    B0 = min(cfg.batch_arms, stride)
    R0 = max(cfg.epoch_rounds, 1)
    R_cap = max(1, -(-nb // P_))
    floor_w = floor_width(cfg, stride, B0=B0)
    max_rounds = cfg.max_rounds or int(
        2 * math.ceil(stride * nb / max(B0 * P_, 1)) + stride + 16)

    st, pool = _fused_init_fn(mesh, cfg, block, impl, prior_weight)(
        x_st, qs, alive_st, prior_st, rng)
    W0 = st.ids.shape[2]
    rounds_spent = 0
    n_surv = np.full((S, Q), stride)
    done = np.zeros((S, Q), bool)
    obs = get_obs()
    epoch_ms = obs.registry.histogram(
        "repro_race_epoch_ms", "wall time of one race epoch (ms)",
        kind="sharded_fused_blocking")
    while not done.all() and rounds_spent < max_rounds:
        active = ~done
        need = int(n_surv[active].max(initial=1))
        W_new = bucket_width(need, floor=floor_w, current=st.ids.shape[2])
        if W_new < st.ids.shape[2]:
            st = _compact_stacked(st, W_new=W_new)
        # cross-shard pull-budget reallocation: the per-epoch budget is
        # S·W0·R0 pulls; R fuses enough rounds to spend it over the TOTAL
        # surviving work, so certified (idle) shards' shares flow to the
        # still-racing ones. With S=1 this is exactly the single-shard
        # adaptive rule R = R0·max(1, W0/need) (pow2-quantized so T = R·P
        # stays on the warm specialization chain).
        total_need = sum(int(n_surv[s][active[s]].max(initial=0))
                         for s in range(S))
        R = min(R0 * pow2_floor((S * W0) // max(total_need, 1)), R_cap)
        t0 = time.perf_counter()
        with obs.tracer.annotate("race.epoch.sharded_fused_blocking"):
            with obs.tracer.span("race.launch"):
                st, n_surv_d, done_d = _fused_step_fn(
                    mesh, cfg, block, store.d, impl, eliminate,
                    prior_weight, log_term, R * P_)(x_st, qs, st, pool)
            rounds_spent += R
            with obs.tracer.span("race.sync"):
                n_surv, done = host_fetch((n_surv_d, done_d))
        # per-epoch timing under the same histogram the anytime sessions
        # feed — repro.tune races candidate configs on this series
        epoch_ms.observe((time.perf_counter() - t0) * 1e3)

    outs = _fused_finalize_fn(mesh, cfg, log_term, prior_weight, stride,
                              block, store.d, cfg.metric)(x_st, qs, st, pool)
    return ShardedKNNResult(*outs)


# ---------------------------------------------------------------------------
# front-end
# ---------------------------------------------------------------------------


def sharded_index_knn(store: ShardedIndexStore, queries, rng: jax.Array, *,
                      k=None, impl: str = "auto", eliminate: bool = True,
                      warm_start: bool = True, mode: str = "auto",
                      prior_hint=None) -> ShardedKNNResult:
    """Batched k-NN against a ShardedIndexStore: shard-local racing + the
    certified all-gather merge. Same contract as ``index_knn`` (which
    dispatches here), with GLOBAL slot ids in the result."""
    cfg = store.cfg if k is None else dataclasses.replace(store.cfg, k=k)
    n_live = store.n_live
    if cfg.k > n_live:
        raise ValueError(
            f"k={cfg.k} exceeds the index's {n_live} live slots — "
            "tombstoned slots can never be returned")
    if mode not in ("auto", "fused", "rounds"):
        raise ValueError(f"unknown mode {mode!r}")
    S, stride = store.n_shards, store.stride
    Q = (queries[0] if isinstance(queries, tuple) else
         jnp.asarray(queries)).shape[0]
    w = store.prior_weight if (warm_start or prior_hint is not None) else 0.0
    if prior_hint is not None:
        # (Q, capacity) global per-query priors → (S, Q, stride) shard-major
        prior_st = jnp.asarray(prior_hint, jnp.float32).reshape(
            Q, S, stride).transpose(1, 0, 2)
    else:
        prior_st = store.device_arrays()["prior_var"]          # (S, stride)

    if store.kind == "sparse":
        if mode == "fused":
            raise ValueError("the fused epoch driver pulls corpus blocks — "
                             "sparse boxes race on the per-round driver")
        dev = store.device_arrays()
        q_idx, q_val, q_nnz = queries
        outs = _rounds_sparse_fn(store.mesh, _shard_delta(cfg, S), store.d,
                                 eliminate, w, stride)(
            dev["indices"], dev["values"], dev["nnz"], dev["alive"], prior_st,
            jnp.asarray(q_idx), jnp.asarray(q_val), jnp.asarray(q_nnz), rng)
        return ShardedKNNResult(*outs)
    qs = store.prepare_queries(queries)
    if mode == "rounds":
        dev = store.device_arrays()
        outs = _rounds_dense_fn(store.mesh, _shard_delta(cfg, S), store.block,
                                store.d, impl, eliminate, w, stride)(
            dev["x"], qs, dev["alive"], prior_st, rng)
        return ShardedKNNResult(*outs)
    return _sharded_fused_race(store, qs, prior_st, rng, cfg=cfg, impl=impl,
                               eliminate=eliminate, prior_weight=w)
