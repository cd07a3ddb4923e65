"""95th percentile of due time to the engine's dispatch, over every request
(the requests' ``RequestContext.t_dispatch`` stamps)."""


def read(name, run):
    return run.window.get("queue_wait_p95_ms")
