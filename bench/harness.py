"""One run of one cell: set-up, the measured window, the check against the
reference, and the metrics.

``run_cell`` takes the configuration and the mix as dicts, so the tests
can drive it on the CPU at a tiny size; ``run.py`` looks for the chip
first and finds the files by name.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import time
from typing import Callable, List, Optional

import numpy as np

from bench import spec
from bench import traffic as tr
from bench.corpus import BUILD, RACE, WARM, Generator
from bench.reference import Bf16Scan, check, value_gaps

TRACE_DIR = os.path.join(spec.ROOT, ".bench", "trace")


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    workload: str
    config: dict
    mix: dict
    seconds: float
    t0: float                 # window open (time.monotonic())
    t1: float                 # window close: open + seconds, or (closed
                              # loop) the loop's last look at the clock
    requests: List[tr.Request]    # closed loop: those answered by the close
    setup_s: float
    peak_bytes: int
    events: list              # obs trace events logged in the window
    hist: dict                # histogram name -> (sum, count) in the window
                              # (closed loop: both up to the close)
    trace: Optional[dict] = None    # trace.reduce(...) of a traced run
    peaks: Optional[dict] = None    # peaks.json entry of the device


def _histograms(obs) -> dict:
    out = {}
    for m in obs.registry.collect():
        if m.kind == "histogram":
            s, c = out.get(m.name, (0.0, 0))
            out[m.name] = (s + m.sum, c + m.count)
    return out


def warm_sizes(mix: dict, max_group: int) -> list:
    """Rows of each warm batch: one per power-of-two race size up to the
    largest group the mix can form (the plane's ``max_group_queries``, or
    the closed loop's outstanding rows where fewer), one row short of it
    from 4 up so that the plane pads the group and retires the pad as it
    does for a group of odd size. Any group a burst can form is warmed: a
    race size first met in the window compiles there, and the queue that
    builds behind the compile forms larger groups still."""
    from repro.core.datasets import next_pow2
    rows = int(mix.get("rows_per_request", 1))
    most = max_group
    if mix["loop"] == "closed":
        most = min(most, int(mix.get("clients", 1)) * rows)
    sizes, s = [], next_pow2(rows)
    while s <= next_pow2(most):
        sizes.append(s - 1 if s >= 4 and s - 1 >= rows else s)
        s *= 2
    return sizes


def _device_peak() -> int:
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks)


def _scan_ms(store_x, rows: int, k: int) -> float:
    """Median device time of an exact scan over the resident store for one
    request of ``rows`` queries: what the race has to beat. A sharded
    store's rows (``store_rows``) are scanned where they lie, each chip its
    own shard."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scan(x, q):
        d = (jnp.sum(q * q, 1)[:, None]
             - 2.0 * jnp.dot(q, x.T, precision="highest")
             + jnp.sum(x * x, 1)[None, :])
        return jax.lax.top_k(-d, k)

    q = store_x[:rows]
    jax.block_until_ready(scan(store_x, q))
    times = []
    for _ in range(10):
        t = time.perf_counter()
        jax.block_until_ready(scan(store_x, q))
        times.append(time.perf_counter() - t)
    return float(np.median(times)) * 1e3


@dataclasses.dataclass
class Setup:
    gen: Generator
    k: int
    pool: np.ndarray          # (rows, d) queries the window may send
    qid_of: Callable          # (first request row, rows) -> pool rows
    entry: object             # bench.entries.*Entry, warmed
    index: object             # repro.api.Index (None for the control)
    slot_row: np.ndarray      # corpus row of each served slot
    sizes: list               # the warmed race batch sizes


def setup(config: dict, mix: dict, seed: int, seconds: float, *,
          control: bool = False, annotate=None,
          log: Callable = lambda msg: None) -> Setup:
    """Draw the queries, build the index from the configuration's corpus
    (the rotation keyed by the seed), and warm every shape the mix will
    use."""
    import jax

    from repro.api import Index
    from repro.configs.base import BMOConfig
    from repro.serve.plane import PlaneConfig

    from bench.entries import ControlEntry, PlaneEntry

    tr.validate(mix)
    annotate = annotate or jax.profiler.TraceAnnotation
    bmo = BMOConfig(**config["bmo"])
    gen = Generator(config, seed)
    plane = PlaneConfig(**mix.get("plane", {}))
    sizes = warm_sizes(mix, plane.max_group_queries)
    pool = gen.queries(tr.pool_rows(mix, seconds))
    warm_q = gen.queries(sum(sizes), stream=WARM)
    cuts = np.cumsum([0] + sizes)
    batches = [warm_q[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    order = tr.query_ids(mix, seconds, data_seed(config))

    def qid_of(start: int, rows: int) -> np.ndarray:
        return order[(start + np.arange(rows)) % len(order)]

    t = time.monotonic()
    index = None
    if control:
        entry = ControlEntry(Bf16Scan(gen, bmo.k), pool,
                             theta_scale=float(config["d_pad"]),
                             annotate=annotate)
        slot_row = np.arange(gen.n)
    else:
        with annotate("bench.build"):
            index = Index.build(gen.source(log), bmo, gen.stream(BUILD),
                                shards=spec.shards(config))
            jax.block_until_ready(store_rows(index.store))
        slot_row = np.full((index.capacity,), -1, np.int64)
        slot_row[np.asarray(index.build_gids)] = np.arange(gen.n)
        entry = PlaneEntry(index, pool, gen.stream(RACE), k=bmo.k,
                           plane=plane, annotate=annotate)
    built = time.monotonic() - t
    with annotate("bench.warm"):
        entry.warm(batches)
    log(f"set-up: build {built:.3f} s, warm races "
        f"{time.monotonic() - t - built:.3f} s")
    return Setup(gen, bmo.k, pool, qid_of, entry, index, slot_row, sizes)


def store_rows(store):
    """The store's rows on the device: one array, or for a sharded store
    the (S·stride, d_pad) array over the mesh, each shard's rows on its own
    chip."""
    return (store.device_arrays()["x"] if hasattr(store, "shards")
            else store.x)


def data_seed(config: dict) -> int:
    """The configuration's data seed: its corpus, its queries and the
    order and times at which a mix sends them."""
    return int(config.get("data_seed", 0))


def group_rows(events: list) -> dict:
    """Query rows the plane admitted into each race group, by session,
    from its ``plane.admit`` instants."""
    rows: dict = {}
    for e in events:
        if e.get("name") == "plane.admit":
            sid = e["attrs"]["session"]
            rows[sid] = rows.get(sid, 0) + int(e["attrs"]["rows"])
    return rows


def run_cell(workload: str, config: dict, mix: dict, metric_specs: list, *,
             seed: int, seconds: float, trace: bool, t_start: float,
             control: bool = False, log: Callable = print,
             trace_dir: str = TRACE_DIR) -> dict:
    """Set up, measure, check and read the metrics of one cell. Returns
    the result object ``run.py`` prints."""
    import jax

    from repro.obs import ObsContext, install_compile_hook, set_obs

    obs = ObsContext("bench", event_capacity=1 << 20)
    set_obs(obs)
    install_compile_hook()
    compiles = obs.registry.counter("repro_xla_compiles_total",
                                    "XLA backend compiles since process start")
    compile_ms = obs.registry.histogram("repro_xla_compile_ms",
                                        "XLA backend compile wall time (ms)")
    annotate = jax.profiler.TraceAnnotation
    s = setup(config, mix, seed, seconds, control=control, annotate=annotate,
              log=log)
    gen, k, pool, entry, index = s.gen, s.k, s.pool, s.entry, s.index
    rows_per = int(mix.get("rows_per_request", 1))
    setup_compiles, setup_compile_s = compiles.value, compile_ms.sum / 1e3
    events_before = obs.events.total
    hist_before = _histograms(obs)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # no Python call tracing: its events would swamp a long window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.monotonic() - t_start
    log(f"setup_s {setup_s:.3f} (compiling {setup_compile_s:.3f} s in "
        f"{int(setup_compiles)} compiles); warm sizes {s.sizes}")

    # name on standard error whatever compiles inside the window
    jax.config.update("jax_log_compiles", True)
    with annotate("bench.window"):
        if mix["loop"] == "open":
            win = tr.run_open(entry, mix, seconds, data_seed(config),
                              s.qid_of, annotate)
        else:
            win = tr.run_closed(entry, mix, seconds, s.qid_of)
    # what the metrics read stands as it did at the close
    in_window = int(compiles.value - setup_compiles)
    window_compile_s = compile_ms.sum / 1e3 - setup_compile_s
    hist_after = _histograms(obs)
    n_new = obs.events.total - events_before
    events = obs.events.snapshot()[-n_new:] if n_new > 0 else []
    reqs = win["requests"]
    closed = mix["loop"] == "closed"
    window_reqs = ([r for r in reqs if r.status != "pending"] if closed
                   else reqs)
    if trace:
        t = time.monotonic()
        jax.profiler.stop_trace()
        log(f"stop_trace {time.monotonic() - t:.3f} s")
    if closed:
        # a closed loop's window ends at the close; the requests still in
        # flight are answered untraced, for the check alone
        t = time.monotonic()
        with annotate("bench.drain"):
            left = tr.drain(entry, win["inflight"],
                            float(mix.get("drain_s", 60)))
        log(f"drain: {len(win['inflight'])} requests in flight at the "
            f"close, {len(left)} unanswered after {time.monotonic() - t:.3f}"
            f" s; {int(compiles.value - setup_compiles) - in_window} "
            f"compiles")
    jax.config.update("jax_log_compiles", False)
    log(f"window compiles {in_window} ({window_compile_s:.3f} s)")
    if mix["loop"] == "open" and reqs:
        late = np.array([r.submitted - r.intended for r in reqs]) * 1e3
        log(f"generator lateness ms: mean {late.mean():.3f} "
            f"max {late.max():.3f} over {len(reqs)} requests")
    peak = _device_peak()
    hist = {name: (s - hist_before.get(name, (0.0, 0))[0],
                   c - hist_before.get(name, (0.0, 0))[1])
            for name, (s, c) in hist_after.items()}
    if not control:
        rows = sorted(group_rows(events).values())
        log(f"largest group {max(rows, default=0)} rows (warm batches "
            f"{s.sizes}); groups by rows "
            f"{ {r: rows.count(r) for r in sorted(set(rows))} }")
    if index is not None:
        ms = _scan_ms(store_rows(index.store), rows_per, k)
        log(f"exact scan over the resident store ({spec.shards(config)} "
            f"shards): {ms:.4f} ms per request of {rows_per} rows (median "
            f"of 10)")
    s.entry = s.index = entry = index = None
    gc.collect()

    # -- the check, once the window has closed and the program is freed ---
    # every row of a request that was shed, errored or never answered is
    # wrong: an answer dropped is no faster answer
    done = [r for r in reqs if r.status == "done"]
    missed = [r for r in reqs if r.status != "done"]
    wrong = total = sum(r.rows for r in missed)
    gaps = np.zeros((0,))
    if done:
        with annotate("bench.reference"):
            q = pool[np.concatenate([r.qids for r in done])]
            slots = np.concatenate([r.slots for r in done])
            ok = (slots >= 0) & (slots < len(s.slot_row))
            rows = np.where(ok, s.slot_row[np.where(ok, slots, 0)], -1)
            cert = np.concatenate([r.certified for r in done])
            res = check(gen, q, rows, cert, k)
        wrong += int(res["wrong"].sum())
        total += len(res["wrong"])
        right = ~res["wrong"]
        vals = np.concatenate([r.values for r in done])
        gaps = value_gaps(res["served_d"][right], vals[right],
                          float(config["d_pad"]))
    limits = config["limits"]
    checks = {
        "wrong_share": {"value": wrong / total if total else 1.0,
                        "limit": float(limits["wrong_share"])},
        "value_gap": {"value": float(gaps.max()) if gaps.size
                      else float("inf"),
                      "limit": float(limits["value_gap"])}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    run = Run(workload=workload, config=config, mix=mix, seconds=seconds,
              t0=win["t0"], t1=win["t1"], requests=window_reqs,
              setup_s=setup_s, peak_bytes=peak, events=events, hist=hist)
    device = _device_info(peak)
    if trace:
        from bench.trace import find_xplane, reduce_trace
        t = time.monotonic()
        xplane = find_xplane(trace_dir)
        run.trace = reduce_trace(xplane)
        log(f"trace: {os.path.getsize(xplane)} bytes, reduced in "
            f"{time.monotonic() - t:.3f} s")
        run.peaks = spec.peaks(device["kind"])
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    metrics = {}
    for m in metric_specs:
        v = spec.reader(spec.reader_path(m["name"]))(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(reqs),
           "failed": len(missed) + sum(
               int(not np.all(r.certified)) for r in done),
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def _device_info(peak: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}
