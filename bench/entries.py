"""The entry points a mix can drive, behind one small interface.

``submit(request)`` hands one request to the system, ``step()`` advances
a scheduler (plane only), ``poll(request)`` is True while the request is
in flight and fills it in once it is done, and ``warm(batches)`` races
the shapes the window will use before it opens.

* ``PlaneEntry``: ``repro.serve.plane.RequestPlane`` with the mix's
  ``PlaneConfig`` (its ``plane`` block over the defaults); one ticket per
  request. The query LRU is bypassed:
  with its 256 entries full, about half of the synthetic queries have a
  cached one within the near-repeat threshold, so a warm start would fire
  on an artefact of the generator's clusters.
* ``ControlEntry``: the bfloat16 reference scan in the program's place
  (``reference.Bf16Scan``); only the control runs use it.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench.traffic import Request


def reachable_rounds(R0: int, R_cap: int, W0: int, w: int, *, floor: int,
                     shards: int = 1) -> list:
    """Every rounds value a race session can launch at frontier width
    ``w``, ascending. A session takes min(R0 · pow2_floor(S·W0 // need),
    R_cap), where ``need`` is its most survivors of a query (S = 1) or,
    sharded, the sum over its S shards of each shard's most. Below W0
    (and at the floor) ``need`` is anything from 1 to S·w; at W0 above the
    floor the frontier stays only while some shard keeps over half its
    arms, so ``need`` is over W0/2: one chip reaches only R0 there, S
    shards R0 · 2^m for each 2^m < 2S (R0, 2·R0 and 4·R0 with four).
    Below W0 both reach the same R0 · 2^m chain, capped at R_cap
    (``tests/test_bench_sharded.py``)."""
    from repro.index.frontier import pow2_floor

    def rounds(need: int) -> int:
        return min(R0 * pow2_floor(shards * W0 // max(need, 1)), R_cap)

    top = shards * w
    needs = {1 << j for j in range(top.bit_length())} | {top}
    if w == W0 and w > floor:
        needs = {t for t in needs if t > W0 // 2} | {W0 // 2 + 1}
    return sorted({rounds(t) for t in needs})


class PlaneEntry:
    CACHE = "bypass"

    def __init__(self, index, queries: np.ndarray, key, *, k: int,
                 plane, annotate):
        from repro.serve.plane import RequestPlane
        self.index = index
        self.plane = RequestPlane(index, plane)
        self.queries, self.key, self.k = queries, key, k
        self.annotate = annotate

    def warm(self, batches) -> None:
        """A full-certification race through the plane for each warm
        batch, then every epoch step that a race of its size can reach
        (``_warm_epochs``)."""
        for i, batch in enumerate(batches):
            self.plane.submit(batch, cache=self.CACHE,
                              rng=jax.random.fold_in(self.key, 1 << 30 | i))
            self.plane.drain()
            self._warm_epochs(batch, jax.random.fold_in(self.key, 1 << 29 | i))

    def _warm_epochs(self, batch, rng) -> None:
        """Launch once every (width, rounds) pair that a plane race of this
        batch's padded size can reach, from the states it would reach them
        by. The rounds of a launch are a static argument of the epoch step,
        and a session takes them from its survivors
        (``reachable_rounds``). The width halves at most once an epoch. So
        a race whose survivors fall fast enters a width with more rounds
        than one whose survivors fall slowly, and which pairs a race meets
        depends on its queries and its seed: one warm race left others to
        compile inside the window (single queries at widths 65536, 32768
        and 16384 of the dense set, on the chip). This walks the halving
        chain once for each number of rounds, from the state after the
        first epoch.

        A sharded session (``ShardedFusedSession``) compacts every shard's
        frontier at once (``_compact_stacked``), and each of its epochs
        runs a second program after the step, the exactify and the merged
        summary (``_refresh``), whose shape the width alone sets: that one
        is launched once a width."""
        from repro.api import QuerySpec
        from repro.core.datasets import next_pow2
        from repro.index.anytime import ShardedFusedSession
        from repro.index.frontier import compact_frontier
        from repro.index.sharded import _compact_stacked

        pad = next_pow2(len(batch)) - len(batch)
        rows = np.concatenate([batch, np.repeat(batch[:1], pad, 0)])
        # the plane's own call, with the spec its submit above made
        s = self.index.race(rows, rng, spec=QuerySpec(cache=self.CACHE),
                            raced_queries=len(batch))
        sharded = isinstance(s, ShardedFusedSession)
        compact = _compact_stacked if sharded else compact_frontier
        summarised = set()

        def reach(w: int) -> list:
            return reachable_rounds(s._R0, s._R_cap, s._W0, w,
                                    floor=s._floor_w,
                                    shards=s._S if sharded else 1)

        def launch(R: int) -> None:
            fn, args, kwargs = s._epoch_launch(R)
            st, n_surv, _ = fn(*args, **kwargs)
            jax.block_until_ready(n_surv)
            w = st.ids.shape[-1]
            if sharded and w not in summarised:
                summarised.add(w)
                s._refresh(st)
            else:
                # the epoch program's state is already exactified, as a
                # serving epoch leaves it: no snapshot program to run
                s._st = st

        first_rounds, *more = reach(s._W0)
        launch(first_rounds)            # the first epoch: every arm survives
        first = s._st
        for R in more:                  # a shard keeps over half its arms
            s._st = first
            launch(R)
        widths, w = [], s._W0 // 2
        while w >= s._floor_w:
            widths.append(w)
            w //= 2
        reach_of = {w: reach(w) for w in widths}
        seen = set()
        for r in sorted({r for rs in reach_of.values() for r in rs},
                        reverse=True):
            s._st = first
            for w in widths:
                s._st = compact(s._st, W_new=w)
                R = r if r in reach_of[w] else reach_of[w][0]
                if (w, R) not in seen:
                    seen.add((w, R))
                    launch(R)

    def submit(self, r: Request) -> None:
        with self.annotate("bench.submit"):
            r.submitted = time.monotonic()
            r.handle = self.plane.submit(
                self.queries[r.qids], cache=self.CACHE,
                rng=jax.random.fold_in(self.key, r.idx))
            if r.handle.terminal:
                self._finish(r)

    @property
    def active(self) -> int:
        return self.plane.active

    def step(self) -> None:
        self.plane.step()

    def poll(self, r: Request) -> bool:
        if r.handle is not None and r.handle.terminal:
            self._finish(r)
        return r.status == "pending"

    def _finish(self, r: Request) -> None:
        from repro.api.stream import R_CERTIFIED, SHED
        t, res = r.handle, r.handle.result
        r.admitted, r.finished = t.admitted_at, t.finished_at
        r.status = "shed" if t.status == SHED else "done"
        r.slots = np.asarray(res.indices, np.int64)
        r.values = np.asarray(res.values, np.float64)
        r.certified = ((np.asarray(res.certified_count) >= self.k)
                       & (res.reason == R_CERTIFIED))
        r.coord_ops = np.asarray(res.coord_ops, np.float64)
        r.handle = None


class ControlEntry:
    """A blocking entry: ``submit`` serves the request before it returns."""

    active = 0

    def __init__(self, scan, queries: np.ndarray, *, theta_scale: float,
                 annotate):
        self.scan, self.queries = scan, queries
        self.theta_scale, self.annotate = theta_scale, annotate

    def warm(self, batches) -> None:
        for batch in batches:
            self.scan.query(batch)

    def step(self) -> None:
        pass

    def poll(self, r: Request) -> bool:
        return r.status == "pending"

    def submit(self, r: Request) -> None:
        with self.annotate("bench.query"):
            r.submitted = time.monotonic()
            dists, rows = self.scan.query(self.queries[r.qids])
            r.finished = time.monotonic()
        r.slots = rows.astype(np.int64)
        r.values = dists.astype(np.float64) / self.theta_scale
        r.certified = np.ones((r.rows,), bool)
        r.coord_ops = np.zeros((r.rows,))
        r.status = "done"
