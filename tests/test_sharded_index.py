"""Sharded index subsystem (DESIGN.md §5): placement/addressing, exact
top-k parity with the single-shard fused driver across shard counts and
boxes, mutation through global ids, and the checkpoint-manifest round trip
including save-at-S → load-at-S′ re-sharding.

Device-needing tests are in-process but skip unless the interpreter already
sees enough devices — the CI job `sharded-mesh` runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Two subprocess
tests (the test_distributed.py harness) cover the critical parity and
manifest paths on every tier-1 run regardless of the parent's device count.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.base import BMOConfig
from repro.core import oracle
from repro.data.synthetic import make_knn_benchmark_data
from repro.index import placement as plc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(prog: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(prog)],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=timeout)
    assert out.returncode == 0 and "OK" in out.stdout, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"


def _devices(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (run under XLA_FLAGS="
               f"--xla_force_host_platform_device_count={n})")


# ---------------------------------------------------------------------------
# placement + addressing (host-side, any device count)
# ---------------------------------------------------------------------------


def test_round_robin_is_balanced_and_deterministic():
    sid = plc.assign_round_robin(10, 4)
    np.testing.assert_array_equal(sid, [0, 1, 2, 3, 0, 1, 2, 3, 0, 1])
    assert plc.balance(np.bincount(sid, minlength=4)) <= 1.5


def test_least_loaded_fills_valleys_first():
    sid = plc.assign_least_loaded([5, 0, 3, 5], 8)
    # shard 1 (load 0) takes the first three items to reach 3, then 1/2
    # alternate up to 5, then everyone round-robins
    loads = np.asarray([5, 0, 3, 5]) + np.bincount(sid, minlength=4)
    assert loads.max() - loads.min() <= 1
    assert sid[0] == 1


def test_assign_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown placement"):
        plc.assign("hash", [0, 0], 4)


def test_global_addressing_round_trip():
    stride = 128
    gid = plc.global_id(3, 17, stride)
    assert (plc.shard_of(gid, stride), plc.local_of(gid, stride)) == (3, 17)


def test_build_returns_consistent_global_ids():
    corpus, _ = make_knn_benchmark_data("dense", 50, 256, 2, seed=0)
    from repro.index import build_sharded_index
    cfg = BMOConfig(k=3, delta=0.05, block=32, batch_arms=8, metric="l2")
    store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                      shards=1)
    assert store.n_shards == 1 and store.capacity == store.stride
    assert len(set(gids.tolist())) == 50
    # the addressed slot holds the row it claims to
    for i in (0, 13, 49):
        s, l = plc.shard_of(gids[i], store.stride), plc.local_of(
            gids[i], store.stride)
        row = np.asarray(store.shards[s].x)[l][:256]
        np.testing.assert_allclose(row, corpus[i], rtol=1e-6)


def test_stride_remap_contract():
    from repro.index.sharded import _stride_remap
    old_ids = _stride_remap(2, 4, 8)
    # shard 0 slots 0..3 keep ids 0..3; shard 1 slots 8..11 held 4..7
    np.testing.assert_array_equal(old_ids[:4], [0, 1, 2, 3])
    np.testing.assert_array_equal(old_ids[4:8], [-1] * 4)
    np.testing.assert_array_equal(old_ids[8:12], [4, 5, 6, 7])


def test_manifest_contents(tmp_path):
    from repro.index import build_sharded_index, save_sharded_index
    from repro.index.sharded import is_sharded_index_dir, read_manifest
    corpus, _ = make_knn_benchmark_data("dense", 40, 256, 2, seed=1)
    cfg = BMOConfig(k=2, delta=0.05, block=32, batch_arms=8, metric="l2")
    store, _ = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                   shards=1)
    path = os.path.join(tmp_path, "idx")
    save_sharded_index(store, path)
    assert is_sharded_index_dir(path)
    m = read_manifest(path)
    assert m["n_shards"] == 1 and m["stride"] == store.stride
    assert m["kind"] == "dense" and m["live_per_shard"] == [40]
    assert m["placement"] == "round_robin"


def test_single_shard_store_parity_and_k_guard():
    """S=1 runs on any machine: the sharded driver must agree with the
    single-shard fused driver and enforce the same k-vs-live guard."""
    from repro.index import (build_index, build_sharded_index, index_knn,
                             sharded_delete)
    corpus, queries = make_knn_benchmark_data("dense", 200, 512, 3, seed=5)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16, metric="l2")
    single = build_index(corpus, cfg, jax.random.PRNGKey(0))
    want = index_knn(single, queries, jax.random.PRNGKey(1), mode="fused")
    store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                      shards=1)
    got = index_knn(store, queries, jax.random.PRNGKey(1))
    row_of = np.full(store.capacity, -1)
    row_of[gids] = np.arange(len(gids))
    rows = row_of[np.asarray(got.indices)]
    assert [set(r.tolist()) for r in rows] == \
        [set(np.asarray(want.indices[i]).tolist()) for i in range(3)]
    assert got.shard_rounds.shape == (1,)

    store = sharded_delete(store, gids[: 198])
    with pytest.raises(ValueError, match="live slots"):
        index_knn(store, queries, jax.random.PRNGKey(2))


# ---------------------------------------------------------------------------
# parity across shard counts (needs devices; runs in the sharded-mesh CI job)
# ---------------------------------------------------------------------------


@_devices(8)
@pytest.mark.parametrize("shards", [2, 4, 8])
@pytest.mark.parametrize("mode", ["fused", "rounds"])
def test_sharded_parity_dense(shards, mode):
    from repro.index import build_index, build_sharded_index, index_knn
    corpus, queries = make_knn_benchmark_data("dense", 400, 1024, 6, seed=1)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16,
                    pulls_per_round=2, metric="l2")
    single = build_index(corpus, cfg, jax.random.PRNGKey(0))
    want = index_knn(single, queries, jax.random.PRNGKey(1), mode="fused")
    ex = oracle.exact_knn(corpus, queries, 3, "l2")
    store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                      shards=shards)
    res = index_knn(store, queries, jax.random.PRNGKey(1), mode=mode)
    row_of = np.full(store.capacity, -1)
    row_of[gids] = np.arange(len(gids))
    rows = [set(r.tolist()) for r in row_of[np.asarray(res.indices)]]
    assert rows == [set(np.asarray(want.indices[i]).tolist())
                    for i in range(6)]
    assert rows == [set(np.asarray(ex.indices[i]).tolist()) for i in range(6)]
    # merged values are exact θ, ascending
    vals = np.asarray(res.values)
    assert (np.diff(vals, axis=1) >= -1e-6).all()
    np.testing.assert_allclose(np.sort(vals, 1),
                               np.asarray(ex.values), rtol=1e-4, atol=1e-5)
    assert res.shard_rounds.shape == (shards,)
    assert float(np.asarray(res.coord_ops).sum()) > 0


@_devices(4)
def test_sharded_parity_rotated():
    from repro.index import build_index, build_sharded_index, index_knn
    corpus, queries = make_knn_benchmark_data("dense", 300, 512, 4, seed=2)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16, metric="l2",
                    rotate=True)
    single = build_index(corpus, cfg, jax.random.PRNGKey(0))
    want = index_knn(single, queries, jax.random.PRNGKey(1), mode="fused")
    store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                      shards=4)
    res = index_knn(store, queries, jax.random.PRNGKey(1))
    row_of = np.full(store.capacity, -1)
    row_of[gids] = np.arange(len(gids))
    rows = [set(r.tolist()) for r in row_of[np.asarray(res.indices)]]
    assert rows == [set(np.asarray(want.indices[i]).tolist())
                    for i in range(4)]


@_devices(4)
def test_sharded_parity_sparse():
    from repro.core.datasets import SparseDataset
    from repro.data.synthetic import clustered_sparse
    from repro.index import build_index, build_sharded_index, index_knn
    corpus = clustered_sparse(200, 2048, seed=4)
    ds = SparseDataset.build(corpus)
    queries = (ds.indices[:4], ds.values[:4], ds.nnz[:4])
    cfg = BMOConfig(k=3, delta=0.01, block=1, batch_arms=16,
                    pulls_per_round=8, init_pulls=16, metric="l1", sparse=True)
    single = build_index(corpus, cfg, jax.random.PRNGKey(0))
    want = index_knn(single, queries, jax.random.PRNGKey(5))
    store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                      shards=4)
    res = index_knn(store, queries, jax.random.PRNGKey(5))
    row_of = np.full(store.capacity, -1)
    row_of[gids] = np.arange(len(gids))
    rows = [set(r.tolist()) for r in row_of[np.asarray(res.indices)]]
    assert rows == [set(np.asarray(want.indices[i]).tolist())
                    for i in range(4)]


@_devices(4)
def test_sharded_mutation_insert_delete_compact():
    """Full lifecycle through global ids: delete the certified NN, insert a
    closer point (least-loaded routing), auto-compact with payload remap —
    top-k stays exact at every step."""
    from repro.index import (build_sharded_index, index_knn, sharded_delete,
                             sharded_insert, sharded_maybe_compact)
    corpus, queries = make_knn_benchmark_data("dense", 200, 512, 3, seed=11)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16, metric="l2")
    ex = oracle.exact_knn(corpus, queries, 3, "l2")
    store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                      shards=4)
    kill_rows = np.asarray(ex.indices[0])[:2]
    store = sharded_delete(store, gids[kill_rows])
    res = index_knn(store, queries, jax.random.PRNGKey(1))
    killed = set(gids[kill_rows].tolist())
    for row in np.asarray(res.indices):
        assert not (set(row.tolist()) & killed)

    store, ins, grow_ids = sharded_insert(store, queries + 1e-3)
    res = index_knn(store, queries, jax.random.PRNGKey(2))
    for i in range(len(queries)):
        assert int(np.asarray(res.indices[i])[0]) == int(ins[i])

    # least-loaded routing: the shards that lost slots get refilled first
    live = store.live_per_shard
    assert max(live) - min(live) <= 1

    # tombstone most of the corpus → auto-compaction shrinks the stride
    dead_rows = [r for r in range(40, 200)
                 if int(gids[r]) not in set(ins.tolist())]
    store = sharded_delete(store, gids[dead_rows])
    before = index_knn(store, queries, jax.random.PRNGKey(3))
    store2, old_ids = sharded_maybe_compact(store, threshold=0.5)
    assert old_ids is not None and store2.stride < store.stride
    after = index_knn(store2, queries, jax.random.PRNGKey(3))
    remapped = [set(int(old_ids[j]) for j in row)
                for row in np.asarray(after.indices)]
    assert remapped == [set(r.tolist()) for r in np.asarray(before.indices)]


@_devices(8)
@pytest.mark.parametrize("kind_cfg", [
    ("dense", dict(metric="l2", block=64)),
    ("rotated", dict(metric="l2", block=64, rotate=True)),
    ("sparse", dict(metric="l1", block=1, pulls_per_round=8, init_pulls=16,
                    sparse=True)),
])
@pytest.mark.parametrize("s_new", [2, 8])
def test_manifest_round_trip_reshard(tmp_path, kind_cfg, s_new):
    """build at S=4 → mutate → save → load at S′ ∈ {2, 8} → exact parity
    with the pre-save results through the returned global-id remap."""
    from repro.core.datasets import SparseDataset
    from repro.data.synthetic import clustered_sparse
    from repro.index import (build_sharded_index, index_knn,
                             load_sharded_index, save_sharded_index,
                             sharded_delete, sharded_insert)
    kind, kw = kind_cfg
    cfg = BMOConfig(k=3, delta=0.01, batch_arms=16, **kw)
    if kind == "sparse":
        corpus = clustered_sparse(120, 512, seed=3)
        ds = SparseDataset.build(corpus)
        queries = (ds.indices[:2], ds.values[:2], ds.nnz[:2])
    else:
        corpus, queries = make_knn_benchmark_data("dense", 120, 256, 2, seed=3)
    store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                      shards=4)
    store = sharded_delete(store, gids[[7, 19, 64]])
    if kind != "sparse":
        store, _, _ = sharded_insert(store, np.asarray(corpus[:2]) * 0.5)
    path = os.path.join(tmp_path, "idx")
    save_sharded_index(store, path)
    want = index_knn(store, queries, jax.random.PRNGKey(7))

    loaded, none_ids = load_sharded_index(path)
    assert none_ids is None and loaded.n_shards == 4
    same = index_knn(loaded, queries, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(same.indices),
                                  np.asarray(want.indices))

    res2, old_ids = load_sharded_index(path, shards=s_new)
    assert res2.n_shards == s_new and res2.n_live == store.n_live
    got = index_knn(res2, queries, jax.random.PRNGKey(7))
    remapped = [set(int(old_ids[j]) for j in row)
                for row in np.asarray(got.indices)]
    assert remapped == [set(r.tolist()) for r in np.asarray(want.indices)]
    np.testing.assert_allclose(np.sort(np.asarray(got.values), 1),
                               np.sort(np.asarray(want.values), 1),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# subprocess coverage for single-device tier-1 runs
# ---------------------------------------------------------------------------


def test_sharded_parity_subprocess():
    """Dense + rotated parity at S=2 on a forced 2-device host mesh — runs
    on every tier-1 invocation regardless of the parent's device count."""
    _run("""
        import jax, numpy as np
        from repro.configs.base import BMOConfig
        from repro.core import oracle
        from repro.data.synthetic import make_knn_benchmark_data
        from repro.index import build_sharded_index, index_knn
        corpus, queries = make_knn_benchmark_data("dense", 256, 512, 4, seed=1)
        ex = oracle.exact_knn(corpus, queries, 3, "l2")
        for kw in (dict(), dict(rotate=True)):
            cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16,
                            pulls_per_round=2, metric="l2", **kw)
            store, gids = build_sharded_index(corpus, cfg,
                                              jax.random.PRNGKey(0), shards=2)
            res = index_knn(store, queries, jax.random.PRNGKey(1))
            row_of = np.full(store.capacity, -1)
            row_of[gids] = np.arange(len(gids))
            rows = row_of[np.asarray(res.indices)]
            acc = np.mean([set(rows[i].tolist())
                           == set(np.asarray(ex.indices[i]).tolist())
                           for i in range(4)])
            assert acc == 1.0, (kw, acc)
        print("OK")
    """, devices=2)


def test_manifest_reshard_subprocess(tmp_path):
    """Save at S=2 → load at S′=4 → parity through the remap (dense)."""
    _run(f"""
        import jax, numpy as np
        from repro.configs.base import BMOConfig
        from repro.data.synthetic import make_knn_benchmark_data
        from repro.index import (build_sharded_index, index_knn,
                                 load_sharded_index, save_sharded_index,
                                 sharded_delete)
        corpus, queries = make_knn_benchmark_data("dense", 128, 256, 2, seed=3)
        cfg = BMOConfig(k=3, delta=0.01, block=32, batch_arms=16, metric="l2")
        store, gids = build_sharded_index(corpus, cfg, jax.random.PRNGKey(0),
                                          shards=2)
        store = sharded_delete(store, gids[[3, 50]])
        want = index_knn(store, queries, jax.random.PRNGKey(7))
        path = r"{str(tmp_path)}/idx"
        save_sharded_index(store, path)
        st2, old_ids = load_sharded_index(path, shards=4)
        got = index_knn(st2, queries, jax.random.PRNGKey(7))
        remapped = [set(int(old_ids[j]) for j in row)
                    for row in np.asarray(got.indices)]
        assert remapped == [set(r.tolist())
                            for r in np.asarray(want.indices)], remapped
        print("OK")
    """, devices=4)


def test_shards_live_on_their_own_devices_subprocess():
    """Each shard is built on the device its mesh places it on, and the
    mesh-sharded corpus is assembled from those very buffers — no device
    ever stacks or holds another shard's rows."""
    _run("""
        import jax, numpy as np
        from repro.api import Index
        from repro.configs.base import BMOConfig
        from repro.data.synthetic import make_knn_benchmark_data
        corpus, _ = make_knn_benchmark_data("dense", 256, 512, 1, seed=2)
        cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16,
                        metric="l2", rotate=True)
        store = Index.build(corpus, cfg, jax.random.PRNGKey(0),
                            shards=4).store
        devs = jax.devices()
        for s, shard in enumerate(store.shards):
            for name in ("x", "alive", "prior_var", "signs"):
                assert getattr(shard, name).devices() == {devs[s]}, (s, name)
        x = store.device_arrays()["x"]
        assert x.shape == (4 * store.stride, store.shards[0].d_pad)
        for piece in x.addressable_shards:
            own = store.shards[devs.index(piece.device)].x
            assert (piece.data.unsafe_buffer_pointer()
                    == own.unsafe_buffer_pointer())
        print("OK")
    """, devices=4)


def test_sharded_session_epoch_hlo_subprocess():
    """A sharded session lowers its shard_map'd epoch step through the
    launch builder ``step()`` uses, and then races on unchanged."""
    _run("""
        import jax, numpy as np
        from repro.api import Index
        from repro.configs.base import BMOConfig
        from repro.data.synthetic import make_knn_benchmark_data
        corpus, queries = make_knn_benchmark_data("dense", 256, 512, 4, seed=2)
        cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16,
                        metric="l2", rotate=True)
        index = Index.build(corpus, cfg, jax.random.PRNGKey(0), shards=2)
        lowered = index.race(queries, jax.random.PRNGKey(1), impl="ref")
        plain = index.race(queries, jax.random.PRNGKey(1), impl="ref")
        hlo = lowered.epoch_hlo()
        assert hlo.startswith("HloModule"), hlo[:80]
        assert "num_partitions=2" in hlo.splitlines()[0], hlo[:200]
        for s in (lowered, plain):
            while s.step():
                pass
        for a, b in zip(lowered.snapshot, plain.snapshot):
            np.testing.assert_array_equal(a, b)
        print("OK")
    """, devices=2)
