"""plane_self_ms: mean host time of one request-plane step outside the
race, the ``self_ms`` of the window's ``plane.step`` spans: the step's
wall time less the ``race.init`` and ``race.epoch`` spans inside it
(fence, admission, harvest, the epochs' record-keeping)."""


def read(run):
    own = [e["attrs"]["self_ms"] for e in run.events
           if e.get("name") == "plane.step" and "self_ms" in e["attrs"]]
    return sum(own) / len(own) if own else None
