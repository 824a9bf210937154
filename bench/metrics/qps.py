"""qps: query rows certified in the window per second of the window.

Open loop: rows certified by the close, over the window's seconds, so a
growing backlog lowers it. Closed loop: every row, over the time to the
last completion."""


def read(run):
    rows = sum(int(r.certified.sum()) for r in run.requests
               if r.status == "done" and r.finished <= run.t1)
    return rows / (run.t1 - run.t0)
