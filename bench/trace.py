"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

* ``window_s``: the length of the benchmark's ``bench.window`` annotation.
* ``busy_s``: the union of the device-operation intervals inside it,
  averaged over the TPU devices in the trace; ``idle = 1 - busy/window``.
* ``op_s``: summed device time per operation, by the HLO instruction's
  name without its number (``%fused_epoch_pull.5 = ...`` counts under
  ``fused_epoch_pull``). Control flow (``while``, ``conditional``,
  ``call``) is left out: the operations it runs are events of their own
  inside it, and would count twice.
* ``device_ops``: the ten operations that took most time.
* ``idle_gaps``: the idle time of the first device (``/device:TPU:0``, the
  lowest number in the trace), split by what the host was doing meanwhile:
  the innermost ``bench.*`` or ``repro.*`` host annotation open at that
  moment, or ``host.other``.

Over several chips: ``busy_s`` is the mean chip's, so ``1 - busy/window``
is the chips' mean idle share; ``op_s`` sums each operation over the
chips, so a roofline of the work's bytes at one chip's bandwidth over that
sum is the mean chip's share; ``idle_gaps`` are the first chip's alone.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "repro.")
CONTAINERS = ("while", "conditional", "call")
_NUMBER = re.compile(r"(\.(\d+|clone))+$")


def op_name(event_name: str) -> str:
    """``%fused_epoch_pull.5 = f32[...] fusion(...)`` -> ``fused_epoch_pull``."""
    return _NUMBER.sub("", event_name.split(" = ", 1)[0].lstrip("%"))
WINDOW = "bench.window"
TOP = 10


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _gaps(busy, lo, hi):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def _timeline(spans):
    """Partition time into (start, end, name) segments, each named by the
    innermost (latest-opened) host span open over it."""
    marks = sorted([(s, 1, i) for i, (_, s, _e) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _s, e) in enumerate(spans)])
    segs, open_, t = [], [], None
    for when, opening, i in marks:
        if open_ and t is not None and when > t:
            segs.append((t, when, spans[open_[-1]][0]))
        if opening:
            open_.append(i)
        elif i in open_:
            open_.remove(i)
        t = when
    return segs


def _attribute(gaps, spans):
    """Idle ns per host activity over the gaps (``host.other`` where no
    annotation is open)."""
    out, segs, j = {}, _timeline(spans), 0
    for g0, g1 in gaps:
        covered = 0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        m = j
        while m < len(segs) and segs[m][0] < g1:
            s, e, name = segs[m]
            part = min(e, g1) - max(s, g0)
            if part > 0:
                out[name] = out.get(name, 0) + part
                covered += part
            m += 1
        if g1 - g0 > covered:
            out["host.other"] = out.get("host.other", 0) + (g1 - g0 - covered)
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(path: str) -> dict:
    """The numbers of one trace (a file, or a directory holding one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices, spans, window = {}, [], None
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events)
            devices[int(dev.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(HOST_PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    lo, hi = window
    busy_ns, op_ns, first_busy = [], {}, None
    for _, ops in sorted(devices.items()):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        merged = _union((s, e) for _, s, e in inside)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for n, s, e in inside:
            if n not in CONTAINERS:
                op_ns[n] = op_ns.get(n, 0) + (e - s)
    idle = _attribute(_gaps(first_busy, lo, hi), spans)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "op_s": {k: v / 1e9 for k, v in op_ns.items()},
            "device_ops": top(op_ns),
            "idle_gaps": top(idle)}


def kernel_s(reduced: dict, kernel: str) -> float:
    """Summed device time of the operations named ``kernel``."""
    return reduced["op_s"].get(kernel, 0.0)
