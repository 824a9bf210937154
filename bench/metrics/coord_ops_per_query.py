"""coord_ops_per_query: mean coordinates read per served query row,
the wide init included (the results' own ``coord_ops``)."""


def read(run):
    ops = [float(v) for r in run.requests if r.status == "done"
           for v in r.coord_ops]
    return sum(ops) / len(ops) if ops and sum(ops) > 0 else None
