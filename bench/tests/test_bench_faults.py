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


def _run(mix=CLOSED, seconds=1.5, **kw):
    return run_cell("tiny", TINY, mix, SPECS, seed=2**32 + 17,
                    seconds=seconds, trace=False, t_start=time.monotonic(),
                    log=lambda msg: None, **kw)


@pytest.mark.parametrize("mix", [CLOSED, OPEN], ids=["closed", "open"])
def test_sound_program_is_correct(mix):
    out = _run(mix)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"wrong_share", "value_gap"}
    assert out["metrics"]["qps"]["value"] > 0


def _one_step_window(monkeypatch):
    """Plane steps of 0.05 s at least: a 0.01 s window sends one request a
    client, steps the plane once and closes, before any race is done."""
    from bench.entries import PlaneEntry
    real = PlaneEntry.step

    def step(self):
        time.sleep(0.05)
        real(self)
    monkeypatch.setattr(PlaneEntry, "step", step)


@pytest.mark.parametrize("seconds", [0.01, 1.5])
def test_closed_window_ends_at_the_close_and_the_check_reads_every_request(
        seconds, monkeypatch):
    """The metrics read the requests answered by the close; the check reads
    every request, those drained after the close too. In 0.01 s the loop
    sends one request a client and closes before any is answered."""
    from bench import harness, spec
    from bench import traffic as tr
    if seconds < 0.1:
        _one_step_window(monkeypatch)
    seen = {"rows": 0}
    real_check, real_drain, real_reader = harness.check, tr.drain, spec.reader

    def check(gen, q, rows, cert, k):
        seen["rows"] += len(q)
        return real_check(gen, q, rows, cert, k)

    def drain(entry, pending, secs):
        seen["inflight"] = list(pending)
        return real_drain(entry, pending, secs)

    def reader(path):
        def read(run):
            seen["run"] = run
            return real_reader(path)(run)
        return read
    monkeypatch.setattr(harness, "check", check)
    monkeypatch.setattr(tr, "drain", drain)
    monkeypatch.setattr(spec, "reader", reader)
    out = run_cell("tiny", TINY, CLOSED, SPECS, seed=2**32 + 17,
                   seconds=seconds, trace=False, t_start=time.monotonic(),
                   log=lambda msg: None)
    run, inflight = seen["run"], seen["inflight"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and seen["rows"] == out["attempted"]
    assert len(run.requests) + len(inflight) == out["attempted"]
    assert all(r.finished <= run.t1 for r in run.requests)
    assert all(r.finished > run.t1 for r in inflight)
    if seconds < 0.1:
        assert run.requests == [] and len(inflight) == CLOSED["clients"]
        assert out["metrics"]["qps"]["value"] == 0.0
    else:
        assert run.requests and out["metrics"]["qps"]["value"] > 0


def test_closed_trace_stops_at_the_close_before_the_drain(monkeypatch):
    """A closed loop's trace stops at the close, before the drain, and its
    roofline reads the race spans that ended by then. (The profiler is
    faked: its reduction needs a TPU's trace.)"""
    import jax

    from bench import harness, spec
    from bench import trace as bt
    from bench import traffic as tr
    calls, seen = [], {}
    real_drain, real_reader = tr.drain, spec.reader
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **kw:
                        calls.append(("start", time.monotonic())))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda:
                        calls.append(("stop", time.monotonic())))

    def drain(entry, pending, secs):
        calls.append(("drain", time.monotonic()))
        seen["inflight"] = list(pending)
        return real_drain(entry, pending, secs)

    def reader(path):
        def read(run):
            seen["run"] = run
            return real_reader(path)(run)
        return read
    monkeypatch.setattr(tr, "drain", drain)
    monkeypatch.setattr(spec, "reader", reader)
    monkeypatch.setattr(spec, "peaks", lambda kind: {
        "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(bt, "find_xplane", lambda d: __file__)
    monkeypatch.setattr(bt, "reduce_trace", lambda path: {
        "window_s": 1.0, "busy_s": 0.5,
        "op_s": {"fused_epoch_pull": 1e-3}, "device_ops": [],
        "idle_gaps": []})
    specs = [{"name": "fused_epoch_pull_roofline.closed", "unit": "%"}]
    out = run_cell("tiny", TINY, CLOSED, specs, seed=2**32 + 17,
                   seconds=1.5, trace=True, t_start=time.monotonic(),
                   log=lambda msg: None)
    run = seen["run"]
    assert out["correct"], out["checks"]
    assert [c for c, _ in calls] == ["start", "stop", "drain"]
    assert calls[0][1] < run.t0 and calls[1][1] >= run.t1
    assert any(e["name"] in ("race.init", "race.epoch") for e in run.events)
    assert out["metrics"]["fused_epoch_pull_roofline.closed"]["value"] > 0


@pytest.mark.parametrize("seconds", [0.01, 1.5])
def test_every_race_started_by_the_close_has_its_init_span_by_then(
        seconds, monkeypatch):
    """The plane starts a race inside the loop's own step and fetches its
    first snapshot before the step returns, so no ``race.init`` is open at
    the close: the spans the closed roofline sums hold the wide init of
    every group admitted in the window, those still in flight included."""
    from bench import spec
    if seconds < 0.1:
        _one_step_window(monkeypatch)
    seen, real_reader = {}, spec.reader

    def reader(path):
        def read(run):
            seen["run"] = run
            return real_reader(path)(run)
        return read
    monkeypatch.setattr(spec, "reader", reader)
    out = _run(seconds=seconds)
    events = seen["run"].events
    admitted = {e["attrs"]["session"] for e in events
                if e["name"] == "plane.admit"}
    inits = {e["trace"] for e in events if e["name"] == "race.init"}
    assert out["correct"], out["checks"]
    assert admitted and admitted <= inits
    if seconds < 0.1:           # every request was in flight at the close
        assert seen["run"].requests == []


def test_a_wrong_answer_drained_after_the_close_is_not_correct(
        monkeypatch):
    """Every request of a 0.01 s window is answered after the close; an
    altered answer among them still fails the check."""
    _one_step_window(monkeypatch)
    _altered_answer(monkeypatch)
    out = _run(seconds=0.01)
    assert out["attempted"] == CLOSED["clients"]
    assert not out["correct"], out["checks"]


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
