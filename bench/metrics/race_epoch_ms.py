"""race_epoch_ms: mean wall time of one race epoch in the window
(``repro_race_epoch_ms``, the plane's sessions and the blocking loop
alike); each epoch ends in a host fetch, so its device work is inside."""


def read(run):
    s, c = run.hist.get("repro_race_epoch_ms", (0.0, 0))
    return s / c if c else None
