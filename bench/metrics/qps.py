"""qps: query rows certified by the window's close, over the window's
seconds. The same in both loops: an open loop's backlog lowers it, and a
closed loop's drain after the close is left out of it."""


def read(run):
    rows = sum(int(r.certified.sum()) for r in run.requests
               if r.status == "done" and r.finished <= run.t1)
    return rows / (run.t1 - run.t0)
