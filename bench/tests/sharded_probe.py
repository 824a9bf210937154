"""Drive the harness over a four-shard index on four virtual CPU devices
and print what ``test_bench_sharded.py`` checks, as one JSON line.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 -m bench.tests.sharded_probe

One process runs every scenario, so the sharded programs compile once:
a sound run of the open loop, races after set-up on other queries and
seeds, the bfloat16 control, and the program broken underneath the timed
path in each way a sharded cell can be broken.
"""
from __future__ import annotations

import json
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench.corpus import RowSource
from bench.harness import run_cell, setup
from bench.tests.tiny import OPEN, TINY

SHARDS = 4
CONFIG = dict(TINY, shards=SHARDS)
# arrivals fast enough that groups of 2 rows form behind a running race
MIX = dict(OPEN, arrival=dict(OPEN["arrival"], rate_qps=30.0),
           plane={"max_group_queries": 2})
SPECS = [{"name": n, "unit": "-"} for n in (
    "latency_p50_ms", "setup_s", "shard_straggle.shard4",
    "epoch_fetches.shard4", "race_epoch_ms.shard4")]
# 128 blocks of 4 coordinates a row and tight clusters, as in
# test_bench_warm.py: races take more rounds at a width than a warm race
WARM_CONFIG = dict(CONFIG, d=512, d_pad=512, bmo=dict(TINY["bmo"], block=4),
                   generator=dict(TINY["generator"], n_clusters=32,
                                  noise=0.02))


def _run(control: bool = False) -> dict:
    logs = []
    out = run_cell("tiny4", CONFIG, MIX, SPECS, seed=2**33 + 5, seconds=1.5,
                   trace=False, t_start=time.monotonic(), control=control,
                   log=logs.append)
    window = [m for m in logs if m.startswith("window compiles")]
    out["window_compiles"] = int(window[0].split()[2]) if window else None
    out["groups"] = [m for m in logs if m.startswith("largest group")]
    return out


@jax.jit
def _same_state(x, qs, st, pool):
    n_surv = jnp.sum(st.valid & ~st.rejected & ~st.done[..., None], -1)
    return st, n_surv, st.done


def _unchanged_step():
    """Every epoch step returns the shards' state as it found it."""
    return mock.patch("repro.index.anytime._fused_step_fn",
                      lambda *args, **kwargs: _same_state)


def _half_batch():
    """Half of each group raced; the other half answered from its rows."""
    from repro.index import anytime
    real = anytime.make_session

    def make_session(store, queries, rng, **kw):
        q = np.asarray(queries)
        keep = (len(q) + 1) // 2
        return real(store, np.concatenate([q[:keep], q[:len(q) - keep]]),
                    rng, **kw)
    return mock.patch.object(anytime, "make_session", make_session)


def _merge_left_out():
    """The exchange between chips left out: the answer is shard 0's."""
    from repro.index import anytime
    real = anytime._merge_shard_partials

    def merge(p):
        return real(p._replace(**{f: getattr(p, f)[:1] for f in p._fields}))
    return mock.patch.object(anytime, "_merge_shard_partials", merge)


def _altered_answer():
    """Served ids altered where the shards' summaries reach the host."""
    from repro.index import anytime
    real = anytime._to_host

    def to_host(summ):
        p = real(summ)
        return p._replace(ids=np.where(p.ids >= 0, p.ids + 1, p.ids))
    return mock.patch.object(anytime, "_to_host", to_host)


FAULTS = {"unchanged_step": _unchanged_step, "half_batch": _half_batch,
          "merge_left_out": _merge_left_out,
          "altered_answer": _altered_answer}


def _warm_compiles() -> int:
    """Compiles by races of 1 and 2 rows on other queries and seeds, after
    set-up."""
    from repro.obs import ObsContext, install_compile_hook, set_obs
    from repro.obs.jaxmon import compiles_total
    obs = ObsContext("warm")
    set_obs(obs)
    install_compile_hook()
    s = setup(WARM_CONFIG, MIX, 2**33 + 5, seconds=2.0)
    before = compiles_total(obs)
    rng = np.random.default_rng(0)
    for i in range(24):
        q = s.pool[rng.integers(0, len(s.pool), 1 + i % 2)]
        if i % 3 == 1:      # midway between two rows: a harder query
            q = (q + s.pool[rng.integers(0, len(s.pool), len(q))]) / 2
        s.entry.plane.submit(q, cache="bypass", rng=jax.random.PRNGKey(i))
        s.entry.plane.drain()
    return int(compiles_total(obs) - before)


def main() -> int:
    if len(jax.devices()) < SHARDS:
        print(f"needs {SHARDS} devices, found {len(jax.devices())}",
              file=sys.stderr)
        return 2
    calls = []
    real_array = RowSource.__array__

    def counted(self, *args, **kwargs):
        calls.append(self.shape)
        return real_array(self, *args, **kwargs)
    with mock.patch.object(RowSource, "__array__", counted):
        sound = _run()
    result = {"sound": sound, "host_corpus_calls": len(calls),
              "warm_compiles": _warm_compiles(),
              "control": _run(control=True)}
    for name, fault in FAULTS.items():
        with fault():
            result[name] = _run()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
