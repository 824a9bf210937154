"""race_init_ms: mean wall time of a race session's start in the window,
the ``race.init`` spans: the wide init's dispatch and the first snapshot
fetch, which the plane blocks on while it launches a group."""


def read(run):
    inits = [e["dur"] * 1e3 for e in run.events
             if e.get("name") == "race.init"]
    return sum(inits) / len(inits) if inits else None
