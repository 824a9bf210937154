"""latency_p87_ms: the 87th percentile of completion minus intended
arrival, over every request of the window that was answered. At 1.6
queries/s a 51 s window holds 82 requests, and p87 is the highest
percentile that keeps ten of them beyond it."""
import numpy as np


def read(run):
    lat = [(r.finished - r.intended) * 1e3 for r in run.requests
           if r.status == "done"]
    return float(np.percentile(lat, 87)) if lat else None
