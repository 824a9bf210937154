"""A tiny configuration and mixes for CPU tests of the harness."""

TINY = {
    "n": 1024, "d": 128, "d_pad": 128, "itemsize": 4,
    "bmo": {"k": 3, "delta": 0.01, "block": 32, "batch_arms": 8,
            "metric": "l2", "rotate": True},
    "generator": {"n_clusters": 8, "noise": 0.15, "heavy_tail": 1.0,
                  "query_noise": 0.05, "normalize": False},
    "limits": {"wrong_share": 0.01, "value_gap": 1e-05},
}

CLOSED = {"loop": "closed", "clients": 2, "rows_per_request": 1,
          "queries": {"kind": "pool", "size": 64},
          "entry": "plane", "drain_s": 20}

OPEN = {"loop": "open", "arrival": {"process": "poisson", "rate_qps": 10.0},
        "rows_per_request": 1, "entry": "plane", "drain_s": 20}
