"""epoch_fetches: mean device-to-host fetches that block one race epoch,
the ``fetches`` of the window's ``race.epoch`` spans (each fetch is a
``race.sync`` span): 1 for a one-chip epoch, whose step and summary are
one program; 2 for a sharded epoch, which fetches the survivors after the
step and then the merged summary of a second program."""


def read(run):
    n = [e["attrs"]["fetches"] for e in run.events
         if e.get("name") == "race.epoch" and "fetches" in e["attrs"]]
    return sum(n) / len(n) if n else None
