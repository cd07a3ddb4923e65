"""95th percentile of how late the load generator submitted each request
after its due time (host clock)."""


def read(name, run):
    return run.window.get("gen_lag_p95_ms")
