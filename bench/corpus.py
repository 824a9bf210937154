"""Corpora and queries, drawn on the device.

The generator is the clustered image-like corpus of
``repro.data.synthetic.clustered_dense`` (cluster centres plus per-row
heavy-tailed noise), copied here and keyed per row: row ``r`` depends on
``fold_in(key, r)`` alone, so any chunk of rows drawn again on its own
equals the same rows of one whole draw. Queries are perturbed corpus rows,
as in the paper's protocol, from a key stream of their own.

A configuration's ``generator`` block sets the parameters::

    {"n_clusters": 64, "noise": 0.15, "heavy_tail": 1.0,
     "query_noise": 0.05, "normalize": false}

``normalize`` scales each row (and each query, after its perturbation) to
unit ℓ2 norm, so that ℓ2 ranks as cosine does.
"""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

#: key streams: the corpus and the queries (window and warm-up) from the
#: configuration's data key, the build and the race from the run's seed
CORPUS, QUERIES, RACE, BUILD, WARM = range(5)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole number: the low and the high
    32 bits are folded in apart, so seeds past 2**31 are fine."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                 seed >> 64):
        key = jax.random.fold_in(key, np.uint32(word & 0xFFFFFFFF))
    return key


@functools.partial(jax.jit, static_argnames=(
    "d", "n_clusters", "noise", "heavy_tail", "normalize"))
def _rows(key, ids, *, d: int, n_clusters: int, noise: float,
          heavy_tail: float, normalize: bool):
    centers = jax.random.normal(jax.random.fold_in(key, 0), (n_clusters, d))
    row_key = jax.random.fold_in(key, 1)

    def one(r):
        ka, ks, kn = jax.random.split(jax.random.fold_in(row_key, r), 3)
        c = jax.random.randint(ka, (), 0, n_clusters)
        scale = 1.0 + heavy_tail * jax.random.exponential(ks, ())
        return centers[c] + noise * scale * jax.random.normal(kn, (d,))

    x = jax.vmap(one)(ids)
    if normalize:
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return x


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "n_clusters", "noise", "heavy_tail", "query_noise",
    "normalize"))
def _queries(corpus_key, query_key, js, *, n: int, d: int, n_clusters: int,
             noise: float, heavy_tail: float, query_noise: float,
             normalize: bool):
    def src_and_noise(j):
        kj = jax.random.fold_in(query_key, j)
        ks, kn = jax.random.split(kj)
        return (jax.random.randint(ks, (), 0, n),
                query_noise * jax.random.normal(kn, (d,)))

    src, eps = jax.vmap(src_and_noise)(js)
    q = _rows(corpus_key, src, d=d, n_clusters=n_clusters, noise=noise,
              heavy_tail=heavy_tail, normalize=False) + eps
    if normalize:
        q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    return q


class Generator:
    """The corpus and queries of one configuration, and the random streams
    of one run.

    The corpus and the query streams are the configuration's data, drawn
    from its ``data_seed`` (0 when absent): a deployment serves one data
    set, and every run's seed then offers the same work. The run's seed
    keys only the build (the rotation's signs) and the race's random
    pulls."""

    def __init__(self, config: dict, seed: int):
        g = config["generator"]
        self.n, self.d = int(config["n"]), int(config["d"])
        self._kw = dict(d=self.d, n_clusters=int(g["n_clusters"]),
                        noise=float(g["noise"]),
                        heavy_tail=float(g["heavy_tail"]))
        self._query_noise = float(g["query_noise"])
        self._normalize = bool(g.get("normalize", False))
        self.key = seed_key(seed)
        self._data = seed_key(int(config.get("data_seed", 0)))
        self._corpus_key = jax.random.fold_in(self._data, CORPUS)

    def stream(self, which: int) -> jax.Array:
        """The run's key of one named stream (``RACE``, ``BUILD``)."""
        return jax.random.fold_in(self.key, which)

    def rows(self, ids) -> jax.Array:
        """Corpus rows ``ids`` (device, float32)."""
        return _rows(self._corpus_key, jnp.asarray(ids, jnp.int32),
                     normalize=self._normalize, **self._kw)

    def queries(self, count: int, *, stream: int = QUERIES,
                first: int = 0) -> np.ndarray:
        """Queries ``first .. first+count-1`` of a data stream (``QUERIES``
        or ``WARM``), on the host."""
        js = jnp.arange(first, first + count, dtype=jnp.int32)
        q = _queries(self._corpus_key, jax.random.fold_in(self._data, stream),
                     js, n=self.n, query_noise=self._query_noise,
                     normalize=self._normalize, **self._kw)
        return np.asarray(jax.device_get(q), np.float32)

    def source(self, log=lambda msg: None) -> "RowSource":
        return RowSource(self, log)


class RowSource:
    """The (n, d) corpus as ``Index.build`` reads it: ``.shape`` and row
    slices that are drawn on the device when asked for, so the unrotated
    corpus is never resident whole. A build that needs the whole corpus on
    the host (the sharded one) gets it from ``__array__``, drawn block by
    block on the device and logged with its bytes and seconds."""

    #: rows of one block that ``__array__`` draws on a device
    HOST_ROWS = 4096

    def __init__(self, gen: Generator, log=lambda msg: None):
        self._gen = gen
        self._log = log
        self.shape = (gen.n, gen.d)

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, sl):
        if not isinstance(sl, slice):
            raise TypeError("a RowSource is read by row slices")
        start, stop, step = sl.indices(self.shape[0])
        if step != 1:
            raise ValueError("a RowSource is read by contiguous row slices")
        return self._gen.rows(jnp.arange(start, stop, dtype=jnp.int32))

    def __array__(self, dtype=None, copy=None):
        """The whole corpus as one float32 host array. Blocks are drawn on
        the local devices in turn and copied back while the next ones are
        drawn, one block a device in flight."""
        n, d = self.shape
        out = np.empty((n, d), np.float32)
        devs = jax.local_devices()
        t = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.host_corpus"):
            flight = collections.deque()
            for i, start in enumerate(range(0, n, self.HOST_ROWS)):
                stop = min(n, start + self.HOST_ROWS)
                with jax.default_device(devs[i % len(devs)]):
                    x = self._gen.rows(jnp.arange(start, stop,
                                                  dtype=jnp.int32))
                x.copy_to_host_async()
                flight.append((start, stop, x))
                if len(flight) > len(devs):
                    a, b, x = flight.popleft()
                    out[a:b] = np.asarray(x)
            for a, b, x in flight:
                out[a:b] = np.asarray(x)
        self._log(f"host corpus: {out.nbytes} bytes drawn to the host in "
                  f"{time.monotonic() - t:.3f} s")
        return out if dtype is None else out.astype(dtype, copy=False)


def row_blocks(gen: Generator, rows_per_block: int):
    """(start, rows) over the whole corpus, one device block at a time."""
    for start in range(0, gen.n, rows_per_block):
        stop = min(gen.n, start + rows_per_block)
        yield start, gen.rows(jnp.arange(start, stop, dtype=jnp.int32))
