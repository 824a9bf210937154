"""epoch_wait_ms: mean time of one race epoch spent blocked on the device,
the ``wait_ms`` of the window's ``race.epoch`` spans (every fetch of a
session goes through its ``race.sync`` span). None where the program
records no such attribute."""


def read(run):
    waits = [e["attrs"]["wait_ms"] for e in run.events
             if e.get("name") == "race.epoch" and "wait_ms" in e["attrs"]]
    return sum(waits) / len(waits) if waits else None
