"""Bring-up smoke test on a TPU: the paper's dense retrieval configuration
(``configs/bmo_nn.py`` DENSE — n=100000, d=12288, rotated ℓ2, block 128,
k=5, δ=0.01) generated from a seed, built through ``Index.build`` and
answered through a ``RequestPlane`` and a blocking ``Index.query``.

Every answer is checked twice: by the δ-auditor's oracle
(``obs.audit.check_topk``, exact θ over the store) and by an independent
float64 scan of the unrotated corpus on the host. The run fails on any
mismatch, on a shed or uncertified ticket, on a platform other than TPU,
and when the compiled race step holds no Pallas kernel (``tpu_custom_call``).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the 4-shard index, one per chip

The last line of output is one JSON object naming the device. With no TPU
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

from repro.configs.bmo_nn import DENSE  # noqa: E402  (needs src/ on the path)

N_QUERIES = 32
TICKETS = 4                 # the plane receives the queries as 4 requests
SCAN_ROWS = 8192            # corpus rows per step of the host oracle scan
RTOL, ATOL = 1e-4, 1e-5     # tie tolerance, as obs.audit.check_topk


def exact_scan(corpus: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """(Q, n) squared ℓ2 distances in the unrotated space, in float64 on the
    host — independent of the store, the rotation and the device, and far
    below any gap fp32 rounding could close."""
    q = queries.astype(np.float64)
    qq = np.einsum("ij,ij->i", q, q)
    out = np.empty((q.shape[0], corpus.shape[0]), np.float64)
    for s in range(0, corpus.shape[0], SCAN_ROWS):
        x = corpus[s:s + SCAN_ROWS].astype(np.float64)
        xx = np.einsum("ij,ij->i", x, x)
        out[:, s:s + len(x)] = qq[:, None] - 2.0 * (q @ x.T) + xx[None, :]
    return out


def oracle_mismatches(dist: np.ndarray, rows: np.ndarray, k: int) -> int:
    """Rows whose served corpus rows are not a true top-k up to ties: each
    served row's exact distance must be ≤ the k-th smallest (+ tolerance),
    with k distinct rows."""
    kth = np.partition(dist, k - 1, axis=1)[:, k - 1]
    bad = 0
    for i in range(dist.shape[0]):
        r = rows[i]
        ok = (np.all(r >= 0) and len(np.unique(r)) == k
              and np.all(dist[i, r] <= kth[i] + ATOL + RTOL * abs(kth[i])))
        bad += int(not ok)
    return bad


def kernels_in(hlo: str) -> list:
    """Named scopes (``repro.*``) of the Pallas custom calls in ``hlo``."""
    found = set()
    for line in hlo.splitlines():
        if "tpu_custom_call" in line:
            found.update(re.findall(r"repro\.[a-z_]+", line) or ["unnamed"])
    return sorted(found)


def run(workload=DENSE, *, seed: int = 0, n_queries: int = N_QUERIES,
        shards: int = 1, impl: str = "auto", log=print) -> dict:
    """Generate, build, serve and check one configuration. Returns a report
    whose ``failures`` lists every check that failed (empty = pass)."""
    import jax

    from repro.api import Index
    from repro.api.stream import R_CERTIFIED, SHED
    from repro.data.synthetic import make_knn_benchmark_data
    from repro.obs import get_obs
    from repro.obs.audit import check_topk
    from repro.obs.jaxmon import install_compile_hook
    from repro.serve.plane import RequestPlane

    install_compile_hook()
    compile_ms = get_obs().registry.histogram(
        "repro_xla_compile_ms", "XLA backend compile wall time (ms)")
    cfg, k = workload.bmo, workload.bmo.k
    rep = {"config": workload.name, "n": workload.n_points,
           "d": workload.dim, "shards": shards, "failures": []}
    fail = rep["failures"].append

    t0 = time.perf_counter()
    corpus, queries = make_knn_benchmark_data(
        "dense", workload.n_points, workload.dim, n_queries, seed=seed)
    rep["data_s"] = time.perf_counter() - t0

    c0, t0 = compile_ms.sum, time.perf_counter()
    index = Index.build(corpus, cfg, jax.random.PRNGKey(seed),
                        shards=shards)
    store = index.store
    shard_stores = store.shards if index.sharded else [store]
    jax.block_until_ready([s.x for s in shard_stores])
    rep["build_s"] = time.perf_counter() - t0
    rep["build_compile_s"] = (compile_ms.sum - c0) / 1e3
    rep["d_pad"] = int(shard_stores[0].d_pad)
    rep["capacity"] = int(index.capacity)
    rep["store_bytes"] = int(sum(s.x.nbytes for s in shard_stores))
    rep["bytes_in_use"] = [int((dv.memory_stats() or {}).get("bytes_in_use", 0))
                           for dv in jax.devices()[:shards]]
    log(f"built {rep['config']}: n={rep['n']} d={rep['d']} "
        f"d_pad={rep['d_pad']} capacity={rep['capacity']} shards={shards} "
        f"store_bytes={rep['store_bytes']} build_s={rep['build_s']:.3f} "
        f"(compile {rep['build_compile_s']:.3f})")
    if shards > 1:
        for i, b in enumerate(rep["bytes_in_use"]):
            log(f"device {i}: bytes_in_use={b} "
                f"({b / rep['store_bytes']:.3f} of the store)")
            if b > 0.5 * rep["store_bytes"]:
                fail(f"device {i} holds {b} bytes, more than half the store")

    # row id of each global slot, for the unrotated oracle
    row_of = np.full((index.capacity,), -1, np.int64)
    row_of[index.build_gids] = np.arange(workload.n_points)
    dist = exact_scan(corpus, queries)

    def check(name, ids, certified=None):
        ids = np.asarray(ids, np.int64)[:, :k]
        audit = check_topk(store, queries, ids, k)
        bad = oracle_mismatches(
            dist, np.where(ids >= 0, row_of[np.maximum(ids, 0)], -1), k)
        rep[f"{name}_answered"] = int(ids.shape[0])
        rep[f"{name}_audit_mismatches"] = audit.mismatches
        rep[f"{name}_oracle_mismatches"] = bad
        log(f"{name}: answered={ids.shape[0]} certified={certified} "
            f"audit_mismatches={audit.mismatches} oracle_mismatches={bad}")
        if audit.mismatches or bad:
            fail(f"{name}: {audit.mismatches} audit / {bad} oracle "
                 "mismatched rows")
        if certified is not None:
            rep[f"{name}_certified"] = int(certified)
            if certified != ids.shape[0]:
                fail(f"{name}: {ids.shape[0] - certified} rows uncertified")

    # 1. the request plane: submit every request, then drain
    c0, t0 = compile_ms.sum, time.perf_counter()
    plane = RequestPlane(index)
    per = -(-n_queries // TICKETS)
    tickets = [plane.submit(queries[s:s + per], impl=impl)
               for s in range(0, n_queries, per)]
    plane.drain()
    rep["plane_s"] = time.perf_counter() - t0
    rep["plane_compile_s"] = (compile_ms.sum - c0) / 1e3
    shed = [t for t in tickets if t.status == SHED]
    rep["plane_shed"] = len(shed)
    for t in shed:
        fail(f"ticket {t.id} shed: {t.reason}")
    ids = np.concatenate([np.asarray(t.result.indices) for t in tickets])
    cert = sum(int(np.sum(np.asarray(t.result.certified_count) >= k))
               for t in tickets if t.result.reason == R_CERTIFIED)
    log(f"plane: {len(tickets)} tickets, shed={len(shed)} "
        f"wall_s={rep['plane_s']:.3f} (compile {rep['plane_compile_s']:.3f})")
    check("plane", ids, cert)

    # 2. the blocking query (past the query cache the plane just filled)
    c0, t0 = compile_ms.sum, time.perf_counter()
    res = index.query(queries, jax.random.PRNGKey(seed + 1), impl=impl,
                      cache="bypass")
    rep["query_s"] = time.perf_counter() - t0
    rep["query_compile_s"] = (compile_ms.sum - c0) / 1e3
    log(f"query: wall_s={rep['query_s']:.3f} "
        f"(compile {rep['query_compile_s']:.3f})")
    check("query", res.indices)

    # 3. the race's epoch step as compiled for this device: a session
    #    opened as the plane opens them, lowered by the launch it steps
    session = index.race(queries[:per], jax.random.PRNGKey(seed + 2),
                         impl=impl)
    hlo = session.epoch_hlo()
    rep["custom_call"] = "tpu_custom_call" in hlo
    rep["kernels"] = kernels_in(hlo)
    log(f"race step: tpu_custom_call={rep['custom_call']} "
        f"kernels={rep['kernels']}")
    rep["compile_s"] = compile_ms.sum / 1e3
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: build the index as 4 shards, one per chip, "
                         "and run only that path and its oracle check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.utils.compile_cache import use_compile_cache
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r}); "
              "this check runs on the chip only", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    print(f"cache: {use_compile_cache()}")
    print(f"device_kind: {dev.device_kind} count: {len(devs)}")
    t0 = time.perf_counter()
    rep = run(DENSE, seed=args.seed, shards=args.chips)
    rep["wall_s"] = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    rep["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
    print(f"peak_bytes_in_use: {rep['peak_bytes_in_use']} "
          f"({rep['peak_bytes_in_use'] / 2**30:.3f} GiB) on device 0")
    if not rep["custom_call"]:
        rep["failures"].append("the race step holds no tpu_custom_call")
    print("report: " + json.dumps(rep, sort_keys=True))
    if rep["failures"]:
        for f in rep["failures"]:
            print(f"chip_smoke: FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
