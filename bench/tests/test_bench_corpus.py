"""The device generator: keyed per row, so chunks redraw one whole draw."""
import numpy as np
import pytest

from bench.corpus import (QUERIES, RACE, WARM, Generator, RowSource,
                          row_blocks)
from bench.tests.tiny import TINY


@pytest.mark.parametrize("data_seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_redrawn_chunks_equal_one_whole_draw(data_seed):
    gen = Generator(dict(TINY, data_seed=data_seed), 1)
    whole = np.asarray(gen.rows(np.arange(gen.n)))
    parts = np.concatenate([np.asarray(x) for _, x in row_blocks(gen, 300)])
    np.testing.assert_array_equal(parts, whole)
    picked = np.array([1000, 3, 517, 3])
    np.testing.assert_array_equal(np.asarray(gen.rows(picked)),
                                  whole[picked])


def test_row_source_serves_slices_as_build_reads_them():
    gen = Generator(TINY, 5)
    src = RowSource(gen)
    assert src.shape == (TINY["n"], TINY["d"])
    whole = np.asarray(gen.rows(np.arange(gen.n)))
    np.testing.assert_array_equal(np.asarray(src[100:400]), whole[100:400])
    np.testing.assert_array_equal(np.asarray(src[1000:5000]), whole[1000:])


def test_data_is_the_configurations_and_the_seed_keys_the_run():
    a, b = Generator(TINY, 9), Generator(TINY, 2**33 + 10)
    np.testing.assert_array_equal(a.queries(8), b.queries(8))
    np.testing.assert_array_equal(np.asarray(a.rows(np.arange(50))),
                                  np.asarray(b.rows(np.arange(50))))
    assert not np.array_equal(a.stream(RACE), b.stream(RACE))
    c = Generator(dict(TINY, data_seed=1), 9)
    assert not np.array_equal(a.queries(8), c.queries(8))
    # the warm-up stream is apart from the window's queries
    assert not np.array_equal(a.queries(8), a.queries(8, stream=WARM))
    np.testing.assert_array_equal(a.queries(8, stream=QUERIES)[3:],
                                  a.queries(5, first=3))


def test_queries_are_perturbed_corpus_rows():
    gen = Generator(TINY, 3)
    whole = np.asarray(gen.rows(np.arange(gen.n)))
    q = gen.queries(16)
    d2 = ((q[:, None, :] - whole[None]) ** 2).sum(-1)
    # the nearest row sits at the perturbation's own scale
    expect = TINY["generator"]["query_noise"] ** 2 * TINY["d"]
    assert np.all(d2.min(1) < 3 * expect)


def test_normalised_rows_have_unit_norm():
    gen = Generator(dict(TINY, generator=dict(TINY["generator"],
                                              normalize=True)), 1)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(gen.rows(np.arange(64))), axis=1), 1.0,
        rtol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(gen.queries(8), axis=1), 1.0,
                               rtol=1e-5)


def test_row_source_as_an_array_is_its_slices(monkeypatch):
    """``__array__`` (the sharded build's host copy) draws block by block,
    a block a device in flight, and equals the slices put together."""
    gen = Generator(TINY, 5)
    logs = []
    src = RowSource(gen, logs.append)
    monkeypatch.setattr(RowSource, "HOST_ROWS", 300)
    whole = np.asarray(src)
    assert whole.dtype == np.float32 and whole.shape == src.shape
    parts = np.concatenate([np.asarray(src[a:a + 300])
                            for a in range(0, gen.n, 300)])
    np.testing.assert_array_equal(whole, parts)
    assert len(logs) == 1 and str(whole.nbytes) in logs[0]


def test_one_chip_build_reads_slices_and_never_the_whole_corpus(
        monkeypatch):
    """``build_index`` reads ``.shape`` and row slices, so the cells on one
    chip draw no host copy of the corpus."""
    import jax

    from repro.api import Index
    from repro.configs.base import BMOConfig
    calls = []
    monkeypatch.setattr(RowSource, "__array__",
                        lambda self, *a, **kw: calls.append(1))
    gen = Generator(TINY, 5)
    index = Index.build(gen.source(), BMOConfig(**TINY["bmo"]),
                        jax.random.PRNGKey(0))
    assert calls == []
    assert index.store.x.shape[0] >= TINY["n"]
