"""Traffic: the one generator that reads a mix's data file, and the loops
that offer it to the entry point the mix names.

A mix (``traffic/<name>.json``)::

    {"loop": "open",                  # open | closed
     "arrival": {"process": "poisson", "rate_qps": 4.0,
                 "on_s": 0, "off_s": 0},   # on/off bursts when on_s > 0
     "clients": 16,                   # closed loop: requests outstanding
     "rows_per_request": 1,
     "queries": {"kind": "pool", "size": 64},
     "entry": "plane",                # RequestPlane
     "plane": {"max_group_queries": 8},    # PlaneConfig over its defaults
     "drain_s": 60}                   # wait for answers past the close
                                      # (closed loop: from the drain's start)

Every run offers the same work at the same times. The queries are a
fixed pool drawn from the configuration's data (``corpus.Generator``) and
sent in an order fixed by the configuration's ``data_seed``; an open loop
offers a fixed count of arrivals, round(rate · seconds), whose gaps are
the exponential's quantiles in an order fixed the same way. The run's
seed keys only the build and the race. (With the order drawn from the
run's seed, the cell's median latency moved by a third between seeds,
against a few % between two runs of one seed: which request queued behind
which was the seed's doing.) Latency is charged from the intended
arrival, so a loop that falls behind shows as latency, and the generator's
own lateness (submit minus intended arrival) is reported beside it.

Closed loop: each client sends its next request when its last one has
finished, and none is sent after the window's close. The window ends at
the close: ``run_closed`` returns there with the requests still in
flight, which ``drain`` then answers for the check alone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

LOOPS = ("open", "closed")
ENTRIES = ("plane",)


@dataclasses.dataclass
class Request:
    """One request as the client saw it (``time.monotonic()`` seconds)."""

    idx: int
    qids: np.ndarray                  # query-pool rows it carries
    intended: float                   # when it was due to be sent
    submitted: float = 0.0
    admitted: Optional[float] = None  # plane admission (None elsewhere)
    finished: Optional[float] = None
    status: str = "pending"           # pending | done | shed | error
    slots: Optional[np.ndarray] = None      # (rows, k) served slot ids
    values: Optional[np.ndarray] = None     # (rows, k) served θ
    certified: Optional[np.ndarray] = None  # (rows,) bool
    coord_ops: Optional[np.ndarray] = None  # (rows,)
    handle: object = None             # the entry's ticket, while in flight

    @property
    def rows(self) -> int:
        return int(self.qids.shape[0])


def validate(mix: dict) -> None:
    if mix["loop"] not in LOOPS:
        raise ValueError(f"unknown loop {mix['loop']!r} (want {LOOPS})")
    if mix["entry"] not in ENTRIES:
        raise ValueError(f"unknown entry {mix['entry']!r} (want {ENTRIES})")
    if mix["loop"] == "open" and mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process "
                         f"{mix['arrival']['process']!r}")


def arrivals(mix: dict, seconds: float, order: int) -> np.ndarray:
    """Intended arrival offsets (s) of an open-loop mix over the window;
    ``order`` (the configuration's ``data_seed``) orders the gaps."""
    arr = mix["arrival"]
    rate = float(arr["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    t = np.cumsum(np.random.default_rng(order).permutation(gaps))
    t = t * (seconds * n / (n + 1)) / t[-1]      # the last one inside
    on, off = float(arr.get("on_s", 0)), float(arr.get("off_s", 0))
    if on > 0 and off > 0:
        # squeeze the same arrivals into the on periods: the mean rate is
        # kept, the rate while on is (on + off) / on times it
        busy = t * on / (on + off)
        t = (busy // on) * (on + off) + busy % on
    return t


def pool_rows(mix: dict, seconds: float) -> int:
    """Query rows in the mix's fixed pool: ``size`` when it names one,
    else (open loop) every row the window's arrivals carry."""
    q = mix.get("queries", {"kind": "pool"})
    if "size" in q:
        return int(q["size"])
    if mix["loop"] != "open":
        raise ValueError("a closed loop's query pool needs a size")
    rows = int(mix.get("rows_per_request", 1))
    return len(arrivals(mix, seconds, 0)) * rows


def query_ids(mix: dict, seconds: float, order: int) -> np.ndarray:
    """The pool row of each request row, in sending order: the whole pool
    in the order ``order`` (the configuration's ``data_seed``) gives it;
    a longer run cycles through it."""
    q = mix.get("queries", {"kind": "pool"})
    if q["kind"] != "pool":
        raise ValueError(f"unknown query kind {q['kind']!r}")
    return np.random.default_rng([order, 1]).permutation(
        pool_rows(mix, seconds))


def run_open(entry, mix: dict, seconds: float, order: int, qid_of: Callable,
             annotate: Callable = lambda name: contextlib.nullcontext()
             ) -> dict:
    """Offer the mix's arrivals; return the window's requests and times.
    ``annotate(name)`` names the loop's waits for the next arrival
    (``bench.wait``) in a profiler trace."""
    at = arrivals(mix, seconds, order)
    rows = int(mix.get("rows_per_request", 1))
    drain = float(mix.get("drain_s", 60))
    reqs: List[Request] = []
    pending: List[Request] = []
    t0 = time.monotonic()
    i = 0
    while True:
        now = time.monotonic()
        while i < len(at) and t0 + at[i] <= now:
            r = Request(i, qid_of(i * rows, rows), t0 + float(at[i]))
            entry.submit(r)
            reqs.append(r)
            if r.status == "pending":
                pending.append(r)
            i += 1
        if entry.active:
            entry.step()
            pending = [r for r in pending if entry.poll(r)]
        elif i < len(at):
            with annotate("bench.wait"):
                time.sleep(max(0.0, min(t0 + at[i] - time.monotonic(),
                                        0.005)))
        else:
            break
        if now > t0 + seconds + drain:
            break
    return {"t0": t0, "t1": t0 + seconds, "requests": reqs,
            "end": time.monotonic()}


def run_closed(entry, mix: dict, seconds: float, qid_of: Callable) -> dict:
    """Keep ``clients`` requests outstanding until the close, the first
    look at the clock at or past ``seconds`` after the open, and return
    there: the window's requests and times, and (``inflight``) those not
    answered by the close."""
    clients = int(mix.get("clients", 1))
    rows = int(mix.get("rows_per_request", 1))
    reqs: List[Request] = []
    inflight: dict = {}
    t0 = time.monotonic()
    while True:
        now = time.monotonic()
        if now >= t0 + seconds:
            break
        for c in range(clients):
            if c not in inflight:
                r = Request(len(reqs), qid_of(len(reqs) * rows, rows), now)
                entry.submit(r)
                reqs.append(r)
                if r.status == "pending":
                    inflight[c] = r
        if entry.active:
            entry.step()
            inflight = {c: r for c, r in inflight.items() if entry.poll(r)}
    return {"t0": t0, "t1": now, "requests": reqs,
            "inflight": list(inflight.values())}


def drain(entry, pending: List[Request], seconds: float) -> List[Request]:
    """Step ``entry`` until every request of ``pending`` is answered, or
    for ``seconds`` from the drain's start; return those still pending.
    The deadline starts here and not at the close: a traced run's
    ``stop_trace`` holds the host for a minute or more in between."""
    end = time.monotonic() + seconds
    while True:
        pending = [r for r in pending if entry.poll(r)]
        if not pending or not entry.active or time.monotonic() >= end:
            return pending
        entry.step()
