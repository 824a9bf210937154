"""epoch_host_ms: mean time of one race epoch the host spent on anything
but waiting for the device (dispatch, compaction, Python), the
``host_ms`` of the window's ``race.epoch`` spans: the epoch's wall time
less its ``wait_ms``."""


def read(run):
    host = [e["attrs"]["host_ms"] for e in run.events
            if e.get("name") == "race.epoch" and "host_ms" in e["attrs"]]
    return sum(host) / len(host) if host else None
