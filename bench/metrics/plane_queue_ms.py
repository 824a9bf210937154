"""plane_queue_ms: mean wait in the request plane's admission queue,
admission minus submission, over the window's tickets."""


def read(run):
    waits = [(r.admitted - r.submitted) * 1e3 for r in run.requests
             if r.admitted is not None]
    return sum(waits) / len(waits) if waits else None
