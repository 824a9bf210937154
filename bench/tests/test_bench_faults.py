"""A whole run of the harness on the CPU at a tiny size, past the look for
a chip: a sound program comes out correct; the control, and the program
broken underneath the timed path, come out not correct. (The cells run on
one chip, so there is no exchange between chips to leave out.)"""
import itertools
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import run_cell
from bench.tests.tiny import CLOSED, OPEN, TINY

SPECS = [{"name": "qps", "unit": "queries/s"},
         {"name": "setup_s", "unit": "s"}]


def _run(mix=CLOSED, **kw):
    return run_cell("tiny", TINY, mix, SPECS, seed=2**32 + 17,
                    seconds=1.5, trace=False, t_start=time.monotonic(),
                    log=lambda msg: None, **kw)


@pytest.mark.parametrize("mix", [CLOSED, OPEN], ids=["closed", "open"])
def test_sound_program_is_correct(mix):
    out = _run(mix)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"wrong_share", "value_gap"}
    assert out["metrics"]["qps"]["value"] > 0


def test_bf16_control_is_not_correct():
    out = _run(control=True)
    assert not out["correct"], out["checks"]


def _unchanged_step(monkeypatch):
    from repro.index import anytime

    def step(x, qs, st, pool, **kw):
        n_surv = jnp.sum(st.valid & ~st.rejected & ~st.done[:, None], 1)
        return st, n_surv, st.done
    monkeypatch.setattr(anytime, "_fused_epoch_step", step)


def _half_batch(monkeypatch):
    from repro.index import anytime
    real = anytime.make_session

    def make_session(store, queries, rng, **kw):
        q = np.asarray(queries)
        keep = (len(q) + 1) // 2
        # the left-out half is answered from the rows that were raced
        return real(store, np.concatenate([q[:keep], q[:len(q) - keep]]),
                    rng, **kw)
    monkeypatch.setattr(anytime, "make_session", make_session)


def _altered_answer(monkeypatch):
    from repro.index import anytime
    real = anytime._to_host

    def to_host(summ):
        p = real(summ)
        return p._replace(ids=np.where(p.ids >= 0, p.ids + 1, p.ids))
    monkeypatch.setattr(anytime, "_to_host", to_host)


def _shed_requests(monkeypatch):
    """A plane that sheds every other ticket at admission: fewer answers,
    not faster ones."""
    from repro.serve.plane import RequestPlane
    calls = itertools.count()
    monkeypatch.setattr(RequestPlane, "_max_queue",
                        lambda self, ns: 0 if next(calls) % 2 else 64)


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch,
                                   _altered_answer, _shed_requests],
                         ids=["unchanged_step", "half_batch",
                              "altered_answer", "shed_requests"])
def test_broken_program_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _run()
    assert not out["correct"], out["checks"]
    if fault is _shed_requests:
        assert out["failed"] > 0
        assert out["checks"]["wrong_share"]["value"] > 0
