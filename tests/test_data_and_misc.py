"""Data-pipeline determinism, input_specs coverage, misc substrate tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_arch
from repro.data.synthetic import clustered_dense, clustered_sparse, lm_batch
from repro.models import build_model


def test_lm_batch_deterministic():
    a = lm_batch(1000, 4, 32, seed=7, step=123)
    b = lm_batch(1000, 4, 32, seed=7, step=123)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"], b["labels"])
    c = lm_batch(1000, 4, 32, seed=7, step=124)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_lm_batch_labels_are_shifted():
    a = lm_batch(1000, 2, 16, seed=1, step=0)
    # labels[t] is the next token of an underlying (seq+1) stream; check
    # alignment: tokens[1:] == labels[:-1]
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_clustered_dense_shape_and_variance():
    x = clustered_dense(100, 64, n_clusters=4, seed=0)
    assert x.shape == (100, 64) and x.dtype == np.float32
    assert np.isfinite(x).all()


def test_clustered_dense_row_chunks_match_one_draw(monkeypatch):
    """The generator draws in row chunks; the corpus is the one a single
    (n, d) draw from the same seed gives."""
    from repro.data import synthetic
    n, d, seed = 50, 33, 3
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(64, d)).astype(np.float32)
    assign = rng.integers(0, 64, n)
    scale = (1.0 + rng.exponential(1.0, size=(n, 1))).astype(np.float32)
    want = centers[assign] + 0.15 * scale * rng.normal(
        size=(n, d)).astype(np.float32)
    monkeypatch.setattr(synthetic, "_ROWS", 7)
    np.testing.assert_array_equal(clustered_dense(n, d, seed=seed), want)


def test_clustered_sparse_sparsity():
    x = clustered_sparse(200, 512, sparsity=0.07, seed=0)
    frac = (x != 0).mean()
    assert 0.02 < frac < 0.15
    assert (x >= 0).all()


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_all_cells(arch, shape_name):
    """input_specs must be well-defined for every (arch × shape) cell —
    ShapeDtypeStructs only, no allocation."""
    from repro.launch import dryrun
    specs = dryrun.input_specs(arch, shape_name)
    assert isinstance(specs, dict) and specs
    for k, v in specs.items():
        assert isinstance(v, jax.ShapeDtypeStruct), (k, type(v))
        assert all(d > 0 for d in v.shape)


def test_shape_table():
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["decode_32k"].kind == "decode"
    assert SHAPES["long_500k"].seq_len == 524288


def test_registry_covers_all_archs():
    assert len(ARCHS) == 10
    for a in ARCHS:
        e = get_arch(a)
        assert e.config.name == a
        assert e.smoke.d_model <= 128  # genuinely reduced


def test_shard_act_noop_outside_context(rng):
    from repro.sharding.context import shard_act
    x = jnp.asarray(rng.normal(size=(2, 3, 4)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(shard_act(x)), np.asarray(x))


def test_tree_utils(rng):
    from repro.utils.tree import tree_bytes, tree_count
    t = {"a": jnp.zeros((3, 4), jnp.float32), "b": jnp.zeros((5,), jnp.bfloat16)}
    assert tree_count(t) == 17
    assert tree_bytes(t) == 3 * 4 * 4 + 5 * 2


def test_roofline_table_renders(tmp_path):
    import json
    from benchmarks.roofline_table import load, markdown_table
    p = tmp_path / "r.jsonl"
    rec = {"arch": "x", "shape": "train_4k", "mesh": "single",
           "variant": "baseline", "status": "ok", "t_compute": 1.0,
           "t_memory": 2.0, "t_collective": 0.5, "bottleneck": "memory",
           "useful_flops_ratio": 0.7, "roofline_fraction": 0.35,
           "peak_memory_per_chip": 2.0 * 2**30, "fits_hbm": True}
    p.write_text(json.dumps(rec) + "\n")
    rows = load(str(p))
    md = markdown_table(rows)
    assert "memory" in md and "0.3500" in md
