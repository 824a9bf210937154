"""Set-up warms every epoch step a race of a warmed size can reach, so
that races on other queries and seeds compile nothing in the window."""
import jax
import numpy as np

from bench.harness import setup
from bench.tests.tiny import OPEN, TINY

# 128 blocks of 4 coordinates a row, as many as the dense set has, and
# tight clusters: survivors fall by more than half in some epochs, so races
# take more rounds at a width than one warm race of their size did
CONFIG = dict(TINY, d=512, d_pad=512, bmo=dict(TINY["bmo"], block=4),
              generator=dict(TINY["generator"], n_clusters=32, noise=0.02))


def test_races_after_set_up_compile_nothing():
    from repro.obs import ObsContext, install_compile_hook, set_obs
    from repro.obs.jaxmon import compiles_total
    obs = ObsContext("warm")
    set_obs(obs)
    install_compile_hook()
    s = setup(CONFIG, dict(OPEN, plane={"max_group_queries": 2}),
              2**33 + 5, seconds=2.0)
    before = compiles_total(obs)
    rng = np.random.default_rng(0)
    for i in range(24):
        q = s.pool[rng.integers(0, len(s.pool), 1 + i % 2)]
        if i % 3 == 1:      # midway between two rows: a harder query
            q = (q + s.pool[rng.integers(0, len(s.pool), len(q))]) / 2
        s.entry.plane.submit(q, cache="bypass", rng=jax.random.PRNGKey(i))
        s.entry.plane.drain()
    assert compiles_total(obs) == before
