"""group_rows: mean query rows per race group the plane launched in the
window, from its ``plane.admit`` instants (``rows`` by ``session``)."""
from bench.harness import group_rows


def read(run):
    rows = group_rows(run.events)
    return sum(rows.values()) / len(rows) if rows else None
