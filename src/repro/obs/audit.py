"""repro.obs.audit — the shadow δ-auditor and failure flight recorder
(DESIGN.md §10).

The paper's whole contract is statistical: the racing index returns exact
nearest neighbors with probability ≥ 1−δ. Nothing in PRs 1–7 ever
*measures* that on served traffic — this module closes the loop:

  * ``exact_topk`` / ``exact_theta_of`` — a brute-force oracle over every
    store box (dense / rotated / sparse / sharded) built from the SAME
    exact-evaluation primitives the racing drivers use for Alg. 1 lazy
    exact evaluation, chunked so a full corpus scan stays memory-bounded.
  * ``DeltaAuditor`` — samples a configurable fraction of terminal tickets
    into a bounded per-tenant reservoir (``offer``, a cheap RNG draw plus
    array refs — nothing expensive on the serving path) and re-answers
    them exactly later (``process``/``flush``, run off the critical path:
    the plane only calls it between races or on demand). Per
    (tenant, store-epoch, tuned-vs-default) empirical error rates carry a
    Wilson/Clopper–Pearson upper confidence bound compared against the
    effective δ, exported as ``repro_audit_{sampled,mismatch}_total``
    counters and ``repro_audit_err_upper`` gauges.
  * ``FlightRecorder`` — every audit mismatch is captured as a replayable
    on-disk bundle (query arrays, QuerySpec, store epoch, tuned config,
    the ticket's trace spans, served-vs-exact ids/θ) written atomically;
    ``replay_bundle`` / ``tools/replay_audit.py`` re-run a bundle
    deterministically against a loaded index.

Mismatch definition: a served id is *correct* iff its exact θ is within a
tie tolerance of the k-th smallest exact θ (distinct slots may tie — the
1−δ contract promises *a* set of exact nearest neighbors, not a unique
one); a row fails if any served id is invalid, duplicated, or strictly
worse than the k-th exact value plus tolerance.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import json
import math
import os
import random
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.utils import get_logger

log = get_logger("repro.obs.audit")

#: flight-recorder bundle schema — bump on any layout change so
#: ``tools/replay_audit.py`` can gate.
BUNDLE_SCHEMA = 1

BUNDLE_DOC = "bundle.json"
BUNDLE_ARRAYS = "arrays.npz"

#: tie tolerance for the served-vs-exact θ comparison: θ values are f32
#: distances / d, so equal slots can differ in the last few ulps between
#: the racing driver's accumulation order and the oracle's.
DEFAULT_RTOL = 1e-4
DEFAULT_ATOL = 1e-5

_AUDIT_SKIP_REASONS = ("stale_epoch", "uncertified", "reservoir_full",
                       "namespaced", "unroutable")


# -- binomial upper confidence bounds ---------------------------------------

def _norm_ppf(p: float) -> float:
    """Inverse standard-normal CDF (Acklam's rational approximation,
    |rel err| < 1.2e-9 — no scipy in the container)."""
    if not 0.0 < p < 1.0:
        raise ValueError(f"p must be in (0, 1), got {p}")
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    plow, phigh = 0.02425, 1 - 0.02425
    if p < plow:
        q = math.sqrt(-2 * math.log(p))
        return (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4])
                * q + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q
                                + d[3]) * q + 1)
    if p > phigh:
        q = math.sqrt(-2 * math.log(1 - p))
        return -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4])
                 * q + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q
                                 + d[3]) * q + 1)
    q = p - 0.5
    r = q * q
    return (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4])
            * r + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r
                                 + b[3]) * r + b[4]) * r + 1)


def wilson_upper(failures: int, n: int, confidence: float = 0.95) -> float:
    """One-sided Wilson-score upper bound on a binomial proportion: the
    largest error rate still consistent (at ``confidence``) with seeing
    ``failures`` δ-failures in ``n`` audited rows. 1.0 when nothing has
    been audited yet — no evidence means no claim."""
    if n <= 0:
        return 1.0
    if failures < 0 or failures > n:
        raise ValueError(f"failures must be in [0, {n}], got {failures}")
    z = _norm_ppf(confidence)
    p = failures / n
    z2 = z * z
    center = p + z2 / (2 * n)
    rad = z * math.sqrt(p * (1 - p) / n + z2 / (4 * n * n))
    return min(1.0, (center + rad) / (1 + z2 / n))


def clopper_pearson_upper(failures: int, n: int,
                          confidence: float = 0.95) -> float:
    """Exact (Clopper–Pearson) one-sided upper bound, via bisection on the
    binomial CDF in log space. Slower than ``wilson_upper`` but exact —
    the estimator default stays Wilson; this is the cross-check."""
    if n <= 0:
        return 1.0
    if failures < 0 or failures > n:
        raise ValueError(f"failures must be in [0, {n}], got {failures}")
    if failures >= n:
        return 1.0
    alpha = 1.0 - confidence
    log_comb = [math.lgamma(n + 1) - math.lgamma(i + 1)
                - math.lgamma(n - i + 1) for i in range(failures + 1)]

    def cdf(p: float) -> float:
        if p <= 0.0:
            return 1.0
        if p >= 1.0:
            return 0.0
        lp, l1p = math.log(p), math.log1p(-p)
        return sum(math.exp(lc + i * lp + (n - i) * l1p)
                   for i, lc in enumerate(log_comb))

    lo, hi = failures / n, 1.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if cdf(mid) > alpha:
            lo = mid
        else:
            hi = mid
    return hi


# -- exact oracle over every store box ---------------------------------------

def _dense_theta(store, qs_dev, sel: np.ndarray) -> np.ndarray:
    """Exact θ of (Q, B) local slots against prepared queries — the same
    ``_dense_exact_theta`` the racing drivers use for lazy exact eval."""
    import jax.numpy as jnp

    from repro.index.batched_race import _dense_exact_theta
    th = _dense_exact_theta(store.x, qs_dev,
                            jnp.asarray(sel, jnp.int32),
                            store.cfg.metric)
    return np.asarray(th, np.float64)


def _sparse_ds(store):
    from repro.core.datasets import SparseDataset
    return SparseDataset(indices=store.indices, values=store.values,
                         nnz=store.nnz, d=store.d)


def _merge_topk(cand_i: np.ndarray, cand_v: np.ndarray,
                k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row top-k of a (Q, C) candidate pool (C >= k), ascending θ."""
    if cand_v.shape[1] > k:
        part = np.argpartition(cand_v, k - 1, axis=1)[:, :k]
        cand_v = np.take_along_axis(cand_v, part, axis=1)
        cand_i = np.take_along_axis(cand_i, part, axis=1)
    order = np.argsort(cand_v, axis=1, kind="stable")
    return (np.take_along_axis(cand_i, order, axis=1),
            np.take_along_axis(cand_v, order, axis=1))


def _dense_topk(store, queries, k: int) -> Tuple[np.ndarray, np.ndarray]:
    import jax.numpy as jnp
    qs_dev = jnp.asarray(store.prepare_queries(
        np.asarray(queries, np.float32)))
    Q = int(qs_dev.shape[0])
    cap = store.capacity
    alive = np.asarray(store.alive)
    kk = min(k, cap)
    d_pad = int(store.x.shape[1])
    # bound the (Q, B, d_pad) gather the exact-θ kernel materialises
    chunk = int(max(kk, min(cap, (1 << 22) // max(d_pad, 1))))
    best_i = np.full((Q, kk), -1, np.int64)
    best_v = np.full((Q, kk), np.inf, np.float64)
    for s in range(0, cap, chunk):
        slots = np.arange(s, min(s + chunk, cap))
        sel = np.broadcast_to(slots[None, :], (Q, len(slots)))
        th = _dense_theta(store, qs_dev, np.ascontiguousarray(sel))
        th = np.where(alive[slots][None, :], th, np.inf)
        best_i, best_v = _merge_topk(
            np.concatenate([best_i, sel], axis=1),
            np.concatenate([best_v, th], axis=1), kk)
    return best_i, best_v


def _sparse_theta(store, q_idx, q_val, arm_idx: np.ndarray) -> np.ndarray:
    """Exact sparse θ of (B,) slots for ONE query row (alive-agnostic)."""
    import jax.numpy as jnp

    from repro.core.bmo_nn import sparse_exact_theta
    th = sparse_exact_theta(_sparse_ds(store), jnp.asarray(q_idx),
                            jnp.asarray(q_val), jnp.asarray(arm_idx))
    return np.asarray(th, np.float64)


def _sparse_topk(store, queries, k: int) -> Tuple[np.ndarray, np.ndarray]:
    q_idx, q_val, _q_nnz = (np.asarray(a) for a in queries)
    Q = q_idx.shape[0]
    cap = store.capacity
    alive = np.asarray(store.alive)
    kk = min(k, cap)
    chunk = max(kk, min(cap, 8192))
    best_i = np.full((Q, kk), -1, np.int64)
    best_v = np.full((Q, kk), np.inf, np.float64)
    for s in range(0, cap, chunk):
        slots = np.arange(s, min(s + chunk, cap))
        th = np.stack([_sparse_theta(store, q_idx[i], q_val[i], slots)
                       for i in range(Q)])
        th = np.where(alive[slots][None, :], th, np.inf)
        sel = np.broadcast_to(slots[None, :], (Q, len(slots)))
        best_i, best_v = _merge_topk(
            np.concatenate([best_i, sel], axis=1),
            np.concatenate([best_v, th], axis=1), kk)
    return best_i, best_v


def exact_topk(store, queries, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Ground-truth top-k over any store box: (Q, k) GLOBAL slot ids
    (ascending exact θ) and the θ values. Dead slots never appear; ids are
    −1 (θ = inf) past the live count. Sharded stores merge per-shard exact
    candidates exactly like the serving merge, gid = shard·stride + local."""
    if hasattr(store, "shards"):
        stride = store.stride
        pools_i, pools_v = [], []
        for si, shard in enumerate(store.shards):
            ids, vals = exact_topk(shard, queries, k)
            gids = np.where(ids >= 0, si * stride + ids, -1)
            pools_i.append(gids)
            pools_v.append(vals)
        return _merge_topk(np.concatenate(pools_i, axis=1),
                           np.concatenate(pools_v, axis=1), k)
    if store.kind == "sparse":
        ids, vals = _sparse_topk(store, queries, k)
    else:
        ids, vals = _dense_topk(store, queries, k)
    ids = np.where(np.isfinite(vals), ids, -1)
    if ids.shape[1] < k:            # store smaller than k: pad with -1/inf
        pad = k - ids.shape[1]
        ids = np.concatenate(
            [ids, np.full((ids.shape[0], pad), -1, np.int64)], axis=1)
        vals = np.concatenate(
            [vals, np.full((vals.shape[0], pad), np.inf)], axis=1)
    return ids, vals


def exact_theta_of(store, queries, ids: np.ndarray) -> np.ndarray:
    """Exact θ of arbitrary (Q, k) GLOBAL slot ids; inf where an id is
    invalid (−1 / out of range) or tombstoned."""
    import jax.numpy as jnp
    ids = np.asarray(ids, np.int64)
    Q, k = ids.shape
    out = np.full((Q, k), np.inf)
    if hasattr(store, "shards"):
        stride = store.stride
        valid = (ids >= 0) & (ids < store.capacity)
        si_of = np.where(valid, ids // stride, -1)
        local = np.where(valid, ids % stride, 0)
        for si, shard in enumerate(store.shards):
            m = si_of == si
            if not m.any():
                continue
            th = exact_theta_of(shard, queries, np.where(m, local, 0))
            out[m] = th[m]
        return out
    alive = np.asarray(store.alive)
    valid = (ids >= 0) & (ids < store.capacity)
    valid &= alive[np.where(valid, ids, 0)]
    sel = np.where(valid, ids, 0)
    if store.kind == "sparse":
        q_idx, q_val, _ = (np.asarray(a) for a in queries)
        th = np.stack([_sparse_theta(store, q_idx[i], q_val[i], sel[i])
                       for i in range(Q)])
    else:
        qs_dev = jnp.asarray(store.prepare_queries(
            np.asarray(queries, np.float32)))
        th = _dense_theta(store, qs_dev, sel)
    out[valid] = th[valid]
    return out


@dataclasses.dataclass(frozen=True)
class AuditCheck:
    """One oracle comparison: served ids vs the exact answer."""

    row_mismatch: np.ndarray     # (Q,)   bool — row violated the contract
    bad: np.ndarray              # (Q, k) bool — per served id
    served_theta: np.ndarray     # (Q, k) exact θ of the served ids
    exact_ids: np.ndarray        # (Q, k) oracle top-k (global ids)
    exact_vals: np.ndarray       # (Q, k) oracle θ (ascending)

    @property
    def mismatches(self) -> int:
        return int(self.row_mismatch.sum())


def check_topk(store, queries, served_ids, k: int, *,
               rtol: float = DEFAULT_RTOL,
               atol: float = DEFAULT_ATOL) -> AuditCheck:
    """Audit one served batch against the exact oracle. A served id passes
    iff it is a live slot whose exact θ is ≤ the k-th exact θ + tie
    tolerance; a row additionally fails on duplicated served ids (a
    duplicate means some true neighbor is missing)."""
    served_ids = np.asarray(served_ids, np.int64)[:, :k]
    exact_ids, exact_vals = exact_topk(store, queries, k)
    kth = exact_vals[:, min(k, exact_vals.shape[1]) - 1]
    served_theta = exact_theta_of(store, queries, served_ids)
    tol = atol + rtol * np.abs(np.where(np.isfinite(kth), kth, 0.0))
    bad = served_theta > (kth + tol)[:, None]
    row_bad = bad.any(axis=1)
    for i in range(served_ids.shape[0]):
        if len(np.unique(served_ids[i])) < served_ids.shape[1]:
            row_bad[i] = True
    return AuditCheck(row_mismatch=row_bad, bad=bad,
                      served_theta=served_theta,
                      exact_ids=exact_ids, exact_vals=exact_vals)


# -- flight recorder ---------------------------------------------------------

def _spec_doc(spec) -> dict:
    """JSON-safe QuerySpec view (arrays/objects are summarised, never
    serialised — the bundle's arrays.npz carries the data that matters)."""
    return {
        "k": spec.k, "mode": spec.mode, "impl": spec.impl,
        "delta": spec.delta, "max_rounds": spec.max_rounds,
        "eliminate": spec.eliminate, "warm_start": spec.warm_start,
        "cache": spec.cache, "use_tuned": spec.use_tuned,
        "deadline": repr(spec.deadline) if spec.deadline else None,
        "budget": repr(spec.budget) if spec.budget else None,
        "prior_hint": (None if spec.prior_hint is None
                       else f"array{np.asarray(spec.prior_hint).shape}"),
    }


def ticket_events(obs, trace_id: str) -> List[dict]:
    """The ticket's trace events plus the race-session spans it joined
    (the ``plane.admit`` instant carries ``session=<sid>`` as the join
    key, DESIGN.md §8.3) — the bundle's why-did-this-certify evidence."""
    if obs is None:
        return []
    evs = obs.events.snapshot()
    mine = [e for e in evs if e.get("trace") == trace_id]
    sids = {e.get("attrs", {}).get("session") for e in mine}
    sids.discard(None)
    race = [e for e in evs if e.get("trace") in sids]
    return mine + race


class FlightRecorder:
    """Writes one replayable bundle directory per audit mismatch:
    ``bundle.json`` (metadata, spec, tuned config, mismatch rows, trace
    events) + ``arrays.npz`` (queries, served/exact ids and θ). Bundles
    are staged in a ``.tmp`` sibling and ``os.replace``d into place, so a
    reader never sees a half-written bundle (same atomic-write idiom as
    the tuned.json sidecar)."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self._seq = itertools.count()

    def record(self, *, check: AuditCheck, queries, served_ids, served_vals,
               k: int, delta: float, trace_id: str = "", tenant: str = "",
               store_epoch: int = 0, contract: str = "default",
               store_kind: str = "", metric: str = "", spec=None,
               tuned=None, obs=None) -> str:
        """Capture one mismatch. Returns the bundle directory path."""
        safe = "".join(ch if ch.isalnum() or ch in "._-" else "_"
                       for ch in (trace_id or "anon"))
        final = os.path.join(self.root,
                             f"audit-{next(self._seq):04d}-{safe}")
        while os.path.exists(final):       # seq restarts across processes
            final = os.path.join(self.root,
                                 f"audit-{next(self._seq):04d}-{safe}")
        tmp = final + f".tmp-{os.getpid()}"
        os.makedirs(tmp)
        arrays = {
            "served_ids": np.asarray(served_ids, np.int64),
            "served_vals": np.asarray(served_vals, np.float64),
            "served_theta": check.served_theta,
            "exact_ids": check.exact_ids,
            "exact_vals": check.exact_vals,
            "bad": check.bad,
        }
        if isinstance(queries, tuple):
            q_idx, q_val, q_nnz = (np.asarray(a) for a in queries)
            arrays.update(q_idx=q_idx, q_val=q_val, q_nnz=q_nnz)
        else:
            arrays["queries"] = np.asarray(queries)
        np.savez(os.path.join(tmp, BUNDLE_ARRAYS), **arrays)
        doc = {
            "schema_version": BUNDLE_SCHEMA,
            "trace_id": trace_id,
            "tenant": tenant,
            "store_epoch": int(store_epoch),
            "contract": contract,
            "k": int(k),
            "delta": float(delta),
            "store_kind": store_kind,
            "metric": metric,
            "sparse_queries": isinstance(queries, tuple),
            "mismatch_rows": np.nonzero(check.row_mismatch)[0].tolist(),
            "spec": _spec_doc(spec) if spec is not None else None,
            "tuned": (tuned.to_dict() if tuned is not None
                      and hasattr(tuned, "to_dict") else None),
            "written_at": time.time(),
            "events": ticket_events(obs, trace_id),
        }
        with open(os.path.join(tmp, BUNDLE_DOC), "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=str)
        os.replace(tmp, final)
        return final


def load_bundle(path: str) -> Tuple[dict, dict]:
    """(doc, arrays) of one flight-recorder bundle directory."""
    with open(os.path.join(path, BUNDLE_DOC)) as f:
        doc = json.load(f)
    if doc.get("schema_version") != BUNDLE_SCHEMA:
        raise ValueError(
            f"bundle schema {doc.get('schema_version')!r} != "
            f"{BUNDLE_SCHEMA} (bundle {path})")
    with np.load(os.path.join(path, BUNDLE_ARRAYS)) as z:
        arrays = {k: z[k] for k in z.files}
    return doc, arrays


def replay_bundle(index, path: str) -> dict:
    """Re-run a bundle against a loaded ``repro.api.Index``: recompute the
    exact oracle on the CURRENT store and re-check the recorded served
    ids. ``reproduced`` is True when the same rows mismatch again — on an
    index with the same content this is deterministic (the oracle has no
    randomness); on a mutated store ``epoch_match=False`` flags that the
    ground truth itself may have moved."""
    doc, arrays = load_bundle(path)
    queries = ((arrays["q_idx"], arrays["q_val"], arrays["q_nnz"])
               if doc["sparse_queries"] else arrays["queries"])
    check = check_topk(index.store, queries, arrays["served_ids"],
                       int(doc["k"]))
    now_rows = np.nonzero(check.row_mismatch)[0].tolist()
    recorded = list(doc["mismatch_rows"])
    return {
        "bundle": path,
        "schema_version": BUNDLE_SCHEMA,
        "reproduced": now_rows == recorded,
        "mismatch_rows_recorded": recorded,
        "mismatch_rows_now": now_rows,
        "exact_ids_match": bool(
            (check.exact_ids == arrays["exact_ids"]).all()),
        "store_epoch_recorded": doc["store_epoch"],
        "store_epoch_now": index.epoch,
        "epoch_match": doc["store_epoch"] == index.epoch,
        "delta": doc["delta"],
        "k": doc["k"],
        "trace_id": doc["trace_id"],
    }


# -- the shadow auditor ------------------------------------------------------

@dataclasses.dataclass
class _AuditItem:
    """One sampled terminal ticket, queued for off-path oracle work."""

    trace_id: str
    tenant: str
    store_epoch: int
    contract: str                 # "tuned" | "default"
    k: int
    delta: float
    queries: object               # (Q, d) dense or (q_idx, q_val, q_nnz)
    served_ids: np.ndarray        # (Q, k)
    served_vals: np.ndarray       # (Q, k)
    spec: object = None
    namespace: Optional[str] = None   # fleet namespace; None = default

    @property
    def rows(self) -> int:
        return int(self.served_ids.shape[0])


@dataclasses.dataclass
class _KeyState:
    """Empirical error-rate estimator for one (namespace, tenant,
    store-epoch, contract) key: audited rows, observed δ-failures, the
    tightest δ any audited query promised."""

    sampled: int = 0
    mismatches: int = 0
    delta: float = 1.0

    def err_upper(self, confidence: float) -> float:
        return wilson_upper(self.mismatches, self.sampled, confidence)


class DeltaAuditor:
    """Shadow δ-auditor over one ``repro.api.Index`` — or, given a
    ``router`` (a ``repro.fleet.Fleet``), over every namespace a fleet
    plane serves.

    ``offer`` runs ON the serving path and must stay cheap: one RNG draw,
    then array copies into a bounded per-tenant reservoir (overflow drops
    the oldest pending item, counted — backpressure by forgetting audits,
    never by stalling serving). ``process``/``flush`` run the brute-force
    oracle OFF the critical path; namespaced items resolve their backing
    index through the router at oracle time (transparent reload-on-access,
    the plane's own routing contract). Items whose store epoch fell behind
    a mutation are skipped (the ground truth they were served against no
    longer exists) and counted as ``stale_epoch``; items whose namespace
    was dropped in the meantime count as ``unroutable``."""

    def __init__(self, index=None, *, router=None, rate: float, obs=None,
                 recorder: Optional[FlightRecorder] = None, seed: int = 0,
                 reservoir: int = 256, confidence: float = 0.95,
                 rtol: float = DEFAULT_RTOL, atol: float = DEFAULT_ATOL,
                 labels: Optional[dict] = None):
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"audit rate must be in [0, 1], got {rate}")
        if reservoir < 1:
            raise ValueError(f"reservoir must be >= 1, got {reservoir}")
        if not 0.5 <= confidence < 1.0:
            raise ValueError(
                f"confidence must be in [0.5, 1), got {confidence}")
        if index is None and router is None:
            raise ValueError("DeltaAuditor needs an index, a router "
                             "(fleet), or both")
        self.index = index
        self.router = router
        self.rate = rate
        self.obs = obs
        self.recorder = recorder
        self.confidence = confidence
        self.rtol, self.atol = rtol, atol
        self._rng = random.Random(seed)
        self._labels = dict(labels or {})
        self._reservoir = reservoir
        self._pending: "collections.OrderedDict[str, collections.deque]" = \
            collections.OrderedDict()
        self._states: Dict[Tuple[str, str, int, str], _KeyState] = {}
        self.bundles: List[str] = []
        self.offered = 0              # terminal tickets seen
        self.sampled_tickets = 0      # tickets drawn into the reservoir
        self.dropped = 0              # items evicted by reservoir overflow
        self.skipped: Dict[str, int] = {r: 0 for r in _AUDIT_SKIP_REASONS}
        if obs is not None:
            reg = obs.registry
            self._c_dropped = reg.counter(
                "repro_audit_dropped_total",
                "sampled audits evicted by reservoir overflow",
                **self._labels)
            self._g_pending = reg.gauge(
                "repro_audit_pending",
                "audited rows waiting in the shadow reservoir",
                **self._labels)
            self._h_ms = reg.histogram(
                "repro_audit_ms", "oracle wall time per audited item (ms)",
                **self._labels)
        else:
            self._c_dropped = self._g_pending = self._h_ms = None

    # -- serving-path half ---------------------------------------------------

    def offer(self, *, trace_id: str, tenant: str, store_epoch: int,
              contract: str, k: int, delta: float, queries, served_ids,
              served_vals, spec=None,
              namespace: Optional[str] = None) -> bool:
        """Maybe sample one terminal ticket into the reservoir. Cheap by
        construction — a Bernoulli(rate) draw plus array copies; all
        oracle work waits for ``process``. Returns True iff sampled."""
        self.offered += 1
        if self._rng.random() >= self.rate:
            return False
        if contract not in ("tuned", "default"):
            raise ValueError(
                f"contract must be 'tuned' or 'default', got {contract!r}")
        if isinstance(queries, tuple):
            q = tuple(np.array(a) for a in queries)
        else:
            q = np.array(queries)
        item = _AuditItem(
            trace_id=trace_id, tenant=tenant, store_epoch=int(store_epoch),
            contract=contract, k=int(k), delta=float(delta), queries=q,
            served_ids=np.array(served_ids, np.int64),
            served_vals=np.array(served_vals), spec=spec,
            namespace=namespace)
        dq = self._pending.setdefault(tenant, collections.deque())
        if len(dq) >= self._reservoir:
            dq.popleft()
            self.dropped += 1
            self.skipped["reservoir_full"] += 1
            if self._c_dropped is not None:
                self._c_dropped.inc()
        dq.append(item)
        self.sampled_tickets += 1
        if self._g_pending is not None:
            self._g_pending.set(self.pending)
        return True

    def note_skip(self, reason: str) -> None:
        """Count a terminal ticket the plane chose not to audit (e.g. a
        partial deadline/budget result — only fully-certified answers
        claim the full 1-δ contract)."""
        self.skipped[reason] = self.skipped.get(reason, 0) + 1

    @property
    def pending(self) -> int:
        return sum(len(dq) for dq in self._pending.values())

    # -- off-path half -------------------------------------------------------

    def _pop_round_robin(self) -> Optional[_AuditItem]:
        for tenant in list(self._pending):
            dq = self._pending[tenant]
            if not dq:
                del self._pending[tenant]
                continue
            item = dq.popleft()
            self._pending.move_to_end(tenant)   # fairness across tenants
            if not dq:
                del self._pending[tenant]
            return item
        return None

    def _key_metrics(self, key):
        namespace, tenant, epoch, contract = key
        if self.obs is None:
            return None, None, None
        reg = self.obs.registry
        lbl = dict(self._labels, tenant=tenant, store_epoch=str(epoch),
                   contract=contract)
        if namespace:
            lbl["namespace"] = namespace
        return (reg.counter("repro_audit_sampled_total",
                            "query rows shadow-audited", **lbl),
                reg.counter("repro_audit_mismatch_total",
                            "audited rows that violated the 1-δ contract",
                            **lbl),
                reg.gauge("repro_audit_err_upper",
                          "Wilson upper confidence bound on the empirical "
                          "error rate (compare against δ)", **lbl))

    def _resolve_index(self, item: _AuditItem):
        """The backing index the item's ground truth lives in: the bound
        default for un-namespaced items, the router's (possibly lazily
        reloaded) handle for namespaced ones. None when unroutable."""
        if item.namespace is None:
            return self.index
        if self.router is None:
            return None
        try:
            return self.router.resolve(item.namespace)
        except KeyError:
            return None                     # namespace dropped since

    def _audit(self, item: _AuditItem, index) -> bool:
        """Oracle one item against its resolved index. Returns True iff a
        mismatch was found."""
        t0 = time.perf_counter()
        check = check_topk(index.store, item.queries, item.served_ids,
                           item.k, rtol=self.rtol, atol=self.atol)
        if self._h_ms is not None:
            self._h_ms.observe((time.perf_counter() - t0) * 1e3)
        key = (item.namespace or "", item.tenant, item.store_epoch,
               item.contract)
        state = self._states.setdefault(key, _KeyState())
        state.sampled += item.rows
        state.mismatches += check.mismatches
        state.delta = min(state.delta, item.delta)
        c_sampled, c_mismatch, g_upper = self._key_metrics(key)
        if c_sampled is not None:
            c_sampled.inc(item.rows)
            if check.mismatches:
                c_mismatch.inc(check.mismatches)
            g_upper.set(state.err_upper(self.confidence))
        if check.mismatches == 0:
            if self.obs is not None:
                self.obs.tracer.instant(
                    "audit.pass", trace=item.trace_id, rows=item.rows,
                    store_epoch=item.store_epoch, contract=item.contract)
            return False
        bundle = None
        if self.recorder is not None:
            bundle = self.recorder.record(
                check=check, queries=item.queries,
                served_ids=item.served_ids, served_vals=item.served_vals,
                k=item.k, delta=item.delta, trace_id=item.trace_id,
                tenant=item.tenant, store_epoch=item.store_epoch,
                contract=item.contract, store_kind=index.kind,
                metric=index.cfg.metric, spec=item.spec,
                tuned=index.tuned, obs=self.obs)
            self.bundles.append(bundle)
        log.bind(trace=item.trace_id, tenant=item.tenant).warning(
            "delta-audit MISMATCH: %d/%d rows violate the 1-delta contract "
            "(delta=%g, store_epoch=%d, contract=%s)%s",
            check.mismatches, item.rows, item.delta, item.store_epoch,
            item.contract, f" -> bundle {bundle}" if bundle else "")
        if self.obs is not None:
            self.obs.tracer.instant(
                "audit.mismatch", trace=item.trace_id,
                rows=item.rows, mismatches=check.mismatches,
                store_epoch=item.store_epoch, contract=item.contract,
                bundle=bundle or "")
        return True

    def process(self, limit: Optional[int] = None) -> int:
        """Run the oracle on up to ``limit`` pending items (None = all).
        Call this OFF the serving critical path — the plane does so only
        when no race group is active, or from an explicit flush. Returns
        the number of items processed (audited or skipped)."""
        done = 0
        while limit is None or done < limit:
            item = self._pop_round_robin()
            if item is None:
                break
            done += 1
            index = self._resolve_index(item)
            if index is None:
                self.skipped["unroutable"] += 1
                if self.obs is not None:
                    self.obs.tracer.instant(
                        "audit.skip", trace=item.trace_id,
                        reason="unroutable",
                        namespace=item.namespace or "")
                continue
            if item.store_epoch != index.epoch:
                self.skipped["stale_epoch"] += 1
                if self.obs is not None:
                    self.obs.tracer.instant(
                        "audit.skip", trace=item.trace_id,
                        reason="stale_epoch",
                        item_epoch=item.store_epoch,
                        index_epoch=index.epoch)
                continue
            self._audit(item, index)
        if self._g_pending is not None:
            self._g_pending.set(self.pending)
        return done

    def flush(self) -> int:
        """Drain the whole reservoir through the oracle."""
        return self.process(None)

    # -- reporting -----------------------------------------------------------

    @property
    def sampled_rows(self) -> int:
        return sum(s.sampled for s in self._states.values())

    @property
    def mismatch_rows(self) -> int:
        return sum(s.mismatches for s in self._states.values())

    def err_upper(self) -> float:
        """Global Wilson upper bound over every audited row."""
        return wilson_upper(self.mismatch_rows, self.sampled_rows,
                            self.confidence)

    def summary(self) -> dict:
        """JSON-safe estimator state (the health snapshot's audit section):
        per-key counts, error rates, upper bounds, and whether each key's
        bound still clears its effective δ."""
        keys = []
        for (ns, tenant, epoch, contract), st in sorted(
                self._states.items()):
            upper = st.err_upper(self.confidence)
            keys.append({
                "namespace": ns,
                "tenant": tenant,
                "store_epoch": epoch,
                "contract": contract,
                "sampled": st.sampled,
                "mismatches": st.mismatches,
                "err_rate": (st.mismatches / st.sampled
                             if st.sampled else 0.0),
                "err_upper": upper,
                "delta": st.delta,
                # the bound needs ~log(1-conf)/log(1-δ) clean rows before
                # it can dip under δ — until then "not yet violated" is
                # the honest reading, so gate on observed failures
                "violated": st.mismatches > 0 and upper > st.delta,
            })
        return {
            "rate": self.rate,
            "confidence": self.confidence,
            "method": "wilson",
            "offered": self.offered,
            "sampled_tickets": self.sampled_tickets,
            "sampled_rows": self.sampled_rows,
            "mismatch_rows": self.mismatch_rows,
            "err_upper": self.err_upper(),
            "pending": self.pending,
            "dropped": self.dropped,
            "skipped": dict(self.skipped),
            "bundles": list(self.bundles),
            "keys": keys,
        }
