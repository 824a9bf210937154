"""fused_epoch_pull_roofline.closed: the pull kernel's share of its
roofline over a closed loop's traced window, which ends at the close, %.

As ``fused_epoch_pull_roofline.py``, but the bytes are counted from the
``coord_ops`` of the ``race.init`` and ``race.epoch`` spans that ended by
the close (the attributes ``init_coord_share`` reads), not from the
results of the requests answered by then: the requests still in flight
at the close have had device time in the trace too."""
from bench.trace import kernel_s

KERNEL = "fused_epoch_pull"
SPANS = ("race.init", "race.epoch")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = kernel_s(run.trace, KERNEL)
    coords = sum(float(e["attrs"]["coord_ops"]) for e in run.events
                 if e.get("name") in SPANS)
    if t <= 0 or coords <= 0:
        return None
    need = coords * run.config["itemsize"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / t
