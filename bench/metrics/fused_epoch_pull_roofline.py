"""fused_epoch_pull_roofline: the pull kernel's share of its roofline, %.

The least time is the corpus bytes the window's pulls need over the
device's HBM bandwidth (``peaks.json``); the kernel is bytes-bound. Each
pull needs ``block × itemsize`` bytes of one corpus row, counted from the
served results' ``coord_ops`` (wide init included): the work, not the
(8, 128) tile the kernel DMAs for it, one read per (query, arm, pull). A
kernel that lets one read serve several queries makes this count stale.
The time is the summed device time of the kernel's events in the trace."""
from bench.trace import kernel_s

KERNEL = "fused_epoch_pull"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = kernel_s(run.trace, KERNEL)
    coords = sum(float(v) for r in run.requests if r.status == "done"
                 for v in r.coord_ops)
    if t <= 0 or coords <= 0:
        return None
    need = coords * run.config["itemsize"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / t
