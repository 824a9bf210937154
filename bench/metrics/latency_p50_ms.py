"""latency_p50_ms: median of completion minus intended arrival, over every
request of the window that was answered (shed and unanswered requests
count in ``failed``)."""
import numpy as np


def read(run):
    lat = [(r.finished - r.intended) * 1e3 for r in run.requests
           if r.status == "done"]
    return float(np.percentile(lat, 50)) if lat else None
